"""Kind ``lm_train``: the production epoch driver on the window-and-full-
attention mixture-of-experts decoder (``models/afmoe.py``), whole epochs back
to back.

``ScanEpochDriver`` with the language-model step (``train/lm_step.py``: the
next-token loss, and the routers' selection biases moved after the
optimizer's step) over a resident pool of packed token sequences,
``TrainState`` (the biases in ``batch_stats``) and ``make_optimizer("adamw")``
as ``fit`` builds them for ``train.py --task lm``. The window, its clock, the
schedule's rng (``ScheduleRng``), the deferred fetch, the rate and the
evidence are kind ``train``'s (``kinds/train.py``), which this driver
extends; the rows of the first-steps comparison are ``kinds/bd_train.py``'s
(``compare``) and one more, the biases. Set-up is ordered as
``kinds/bd_train.py`` orders it, because one state is most of the chip's
memory: no two states are ever held at once, and the program's state is
freed before the reference builds its own.

**What ``--seed`` changes here, and what it does not.** The work of a step
depends on the weights: how many rows the routers send to the 8 experts held
is the weights' luck. So the window trains the CONFIGURATION's weights
(``model.weights_seed``; biases at 0), the same in every run, and ``--seed``
draws (1) the order in which an epoch visits its batches and (2) the weights
and biases (uniform in +-0.1) on which ``correct`` is decided: the three
compared steps run from a state seeded by ``--seed`` through the warmed
one-step program, before the window's state exists. Set-up: pool -> driver ->
state from ``weights_seed`` -> ``warm()`` consumes it (its epoch in pack
order is where the counters are read: the same numbers in every run) ->
state from ``--seed`` -> three compared steps -> outputs to the host, state
freed -> state from ``weights_seed`` again -> window.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import counts, counts_afmoe
from benchmark.kinds import train
from benchmark.kinds.bd_train import compare as leaf_rows, first_gradient
from benchmark.reference import afmoe_ref as ref
from benchmark.weights_afmoe import StateMaker

# name -> keywords of ``Driver.check`` (``benchmark/control.py``): the
# reference computed that way stands in the program's place and has to come
# out as not correct. ``float8``: e4m3 on every matmul operand, the precision
# below the bfloat16 this configuration states. The others are faults of
# this model that no precision explains (``reference/afmoe_ref.py``
# ``FAULTS``): window layers that see every earlier key, RoPE in the full
# layers too, the top-8 of the scores alone, softmax scores, no shared
# expert, no gate on the attention's output.
CONTROLS = {"float8": {"control_mm": ref.mm_fp8},
            **{name: {"fault": name} for name in ref.FAULTS}}
# the step's metric sums that go on as counters (train/lm_step.py), an
# epoch's totals; ``EPOCH_MEANS`` are a step's own (the mean of the steps')
EPOCH_TOTALS = ("moe_rows_here", "moe_rows_balanced", "moe_rows_capacity",
                "attn_window_tiles_live", "attn_window_tiles_grid",
                "attn_full_tiles_live", "attn_full_tiles_grid",
                "weighted_tokens")
EPOCH_MEANS = ("expert_load_max_over_mean", "expert_bias_abs_max")
# keys of the source's config.json the program's model takes as they are
PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
             "head_dim", "num_hidden_layers", "num_dense_layers",
             "layer_types", "sliding_window", "intermediate_size",
             "moe_intermediate_size", "num_experts_per_tok",
             "num_shared_experts", "score_func", "route_norm", "route_scale",
             "load_balance_coeff", "mup_enabled", "vocab_size",
             "rms_norm_eps", "rope_theta")
# the biases of the state ``correct`` is decided on: uniform in +-this
COMPARED_BIAS = 0.1


def _held(config: dict) -> tuple:
    held = tuple(int(x) for x in config["share"]["experts_held"])
    if held[1] != int(config["num_experts"]):
        raise ValueError("num_experts states the experts held here; "
                         "share.experts_held has to count as many")
    return held


def model_config(config: dict):
    """The program's ``AfmoeConfig`` for the configuration file: the
    source's keys at the top level, the share beside them."""
    from cgnn_tpu.models.afmoe import AfmoeConfig

    return AfmoeConfig(
        **{k: config[k] for k in PUBLISHED},
        n_experts=int(config["share"]["num_experts_published"]),
        experts_held=_held(config), dtype=config["precision"]["compute"])


def reference_model(config: dict) -> dict:
    """What the reference reads of the configuration (plain data)."""
    return {**{k: config[k] for k in PUBLISHED},
            "experts_held": _held(config)}


def counts_model(config: dict) -> dict:
    return {**{k: config[k] for k in PUBLISHED},
            "num_experts": int(config["num_experts"]),
            "num_experts_published": int(
                config["share"]["num_experts_published"])}


class Driver(train.Driver):

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax

        # the program first: a checkout without the model (the parent of the
        # PR that added this kind) fails here, at once
        from cgnn_tpu.data import tokens
        from cgnn_tpu.models import afmoe
        from cgnn_tpu.train import lm_step, make_optimizer
        from cgnn_tpu.train.loop import ScanEpochDriver

        ctx, cfg, tr = self.ctx, self.config, self.config["train"]
        data = cfg["data"]
        if tr["optim"].lower() != "adamw":
            raise ValueError("lm_train follows AdamW's first steps "
                             "(reference/afmoe_ref.py adamw_steps)")
        with ctx.span("init"):
            self.model_cfg = model_config(cfg)
            tx = make_optimizer(
                optim="adamw", lr=float(tr["lr"]), b1=float(tr["b1"]),
                b2=float(tr["b2"]), weight_decay=float(tr["weight_decay"]),
                lr_milestones=[])
            self.maker = StateMaker(
                self.model_cfg, cfg["init"], tx,
                functools.partial(afmoe.apply, self.model_cfg))
        with ctx.span("data"):
            docs = data["documents"]
            pool = tokens.make_pool(
                int(data["n"]), int(data["sequence_length"]),
                vocab_size=self.model_cfg.vocab_size,
                seed=int(data["pool_seed"]), doc_median=docs["median"],
                doc_sigma=docs["sigma"], doc_min=docs["min"],
                doc_max=docs["max"], kind="causal")
            batches = tokens.split_batches(pool, int(tr["batch_size"]))
        self.batches = batches
        self.steps_per_epoch = len(batches)
        self.structures_per_epoch = int(data["n"])
        s, length = batches[0].tokens.shape
        self.positions = s * length
        print(f"pool: {data['n']} sequences of {length} tokens, "
              f"{int(pool.segment_ids.max(axis=1).sum()) + int(data['n'])} "
              f"documents, {self.steps_per_epoch} steps of {s} an epoch; "
              f"model {self.model_cfg.n_params() / 1e6:.1f} M parameters")
        ctx.obs["counts"].update(
            steps_per_epoch=self.steps_per_epoch,
            structures_per_epoch=self.structures_per_epoch,
            # no padding: documents are packed to exactly the length
            real_nodes=self.steps_per_epoch * self.positions,
            node_slots=self.steps_per_epoch * self.positions)
        tiles = afmoe.attention_tiles(self.model_cfg, length)
        with ctx.span("pack_stage"):
            self.driver = ScanEpochDriver(
                lm_step.make_lm_train_step(self.model_cfg, tiles),
                lm_step.make_lm_eval_step(self.model_cfg, tiles),
                batches, [], self._schedule_rng(),
                chunk_steps=int(self.traffic["chunk_steps"]),
                telemetry=ctx.telemetry, preempt=self.clock)
        with ctx.span("compile"):
            self.driver.warm(self._window_state(), consume=True)
        self._note_counters(pool)
        self.state = None
        self._compared_steps(ctx.seed)
        with ctx.span("init"):
            self.state = self._window_state()
            jax.block_until_ready(self.state.params)

    def _window_state(self):
        """The state every run's window trains: the configuration's
        weights, biases at 0, whatever ``--seed``."""
        return self.maker.make(int(self.config["model"]["weights_seed"]))

    def reseed(self, seed: int) -> None:
        """Other compared weights through the same compiled programs (the
        limits' readings take a dozen seeds in one process)."""
        self.state = None
        self._compared_steps(seed)

    def _note_counters(self, pool) -> None:
        """What warm()'s epoch counted (pack order, the configuration's
        weights: the same in every run), as counters, and the least time a
        step and its kernels could take on this chip."""
        import jax

        ctx, mc = self.ctx, self.model_cfg
        means = self.driver.warm_metrics[0]
        steps = self.steps_per_epoch
        seen = {k: means[k] * steps for k in EPOCH_TOTALS}
        seen.update({k: means[k] for k in EPOCH_MEANS})
        # every (token, choice) pair of every expert layer: what the rungs'
        # rows are a share of
        seen["moe_rows_all"] = float(steps * mc.n_expert_layers
                                     * self.positions
                                     * mc.num_experts_per_tok)
        ctx.obs["counts"].update(seen)
        if ctx.telemetry is not None:
            for name, value in seen.items():
                ctx.telemetry.set_gauge(name, float(value))
        print("counters (warm epoch): " + ", ".join(
            f"{k} {v:.6g}" for k, v in seen.items()))
        if jax.devices()[0].platform != "tpu":
            return  # no roofline off the chip
        model = counts_model(self.config)
        pairs = {
            kind: counts_afmoe.visible_pairs(
                pool.segment_ids,
                mc.sliding_window if kind == counts_afmoe.SLIDING else 0)
            / steps for kind in set(mc.layer_types)}
        rows = seen["moe_rows_here"] / steps / mc.n_expert_layers
        peaks = counts.peaks_for(jax.devices()[0].device_kind)
        whole = counts_afmoe.step_counts(
            model, positions=self.positions,
            weighted=seen["weighted_tokens"] / steps, pairs=pairs, rows=rows)
        least, bound = counts.least_seconds(whole, peaks)
        ctx.obs["counts"]["least_s_per_traced_steps"] = least
        ctx.obs["counts"]["attn_causal_least_s_per_traced_steps"] = sum(
            counts.least_seconds(counts_afmoe.attention_counts(
                model, self.positions, pairs[kind]), peaks)[0]
            for kind in mc.layer_types)
        ctx.obs["counts"]["moe_expert_least_s_per_traced_steps"] = (
            mc.n_expert_layers * counts.least_seconds(
                counts_afmoe.expert_counts(model, rows), peaks)[0])
        print(f"roofline: least {1e3 * least:.3f} ms a step, bound by "
              f"{bound} ({whole['flops']:.4g} FLOP, {whole['bytes']:.4g} B "
              f"a step; visible pairs a layer "
              + ", ".join(f"{k} {v:.4g}" for k, v in sorted(pairs.items()))
              + f"; {rows:.4g} routed rows an expert layer)")

    def _compared_steps(self, seed: int) -> None:
        """The warmed one-step program through the first steps from a state
        seeded by ``seed``, batches 0, 1, 2 in pack order; what the
        comparison reads goes to the host, and the state is freed."""
        import jax

        tmap = jax.tree_util.tree_map
        d = self.driver
        (key, stacked), = d._train_groups.items()
        state = self.maker.make(seed, COMPARED_BIAS)
        self.params0 = tmap(np.array, state.params)
        self.bias0 = np.array(state.batch_stats["router_bias"])
        self.check_batches = list(range(train.N_CHECK_STEPS))
        fn = d._scan_fn(d._train_scans, (key, 1), d._train_body, True)
        got = {"loss": [], "bias": []}
        for s in self.check_batches:
            perm = jax.device_put(np.array([s], np.int32))
            state, sums = fn(state, stacked, perm)
            sums = tmap(float, jax.device_get(sums))
            got["loss"].append(sums["loss_sum"] / max(sums["count"], 1.0))
            got["bias"].append(np.array(state.batch_stats["router_bias"]))
            if s == 0:
                got["grad"] = first_gradient(
                    state.opt_state, float(self.config["train"]["b1"]))
                got["grad_norm"] = ref.leaf_norms(got["grad"])
        got["delta_norm"] = ref.leaf_norms(tmap(
            lambda a, b: np.array(a) - b, state.params, self.params0))
        del state
        self.got = got
        self.want = None  # of another seed's weights

    # ---- the comparison -----------------------------------------------

    def check(self, control_mm=None, fault=None) -> list:
        """The reference follows the same first steps from the same seeded
        weights and biases on the same batches. With ``control_mm`` or a
        ``fault`` (``CONTROLS``) the reference computed that way stands in
        the program's place. The program's state is freed first: the two
        never share the device."""
        self.state = None
        tr = self.config["train"]
        batches = [{"tokens": b.tokens, "segment_ids": b.segment_ids,
                    "loss_weight": b.loss_weight}
                   for b in (self.batches[i] for i in self.check_batches)]

        def follow(**kw):
            return ref.adamw_steps(
                self.params0, self.bias0, batches,
                reference_model(self.config), lr=float(tr["lr"]),
                b1=float(tr["b1"]), b2=float(tr["b2"]),
                weight_decay=float(tr["weight_decay"]), **kw)

        if self.want is None:
            self.want = follow()
        got = self.got
        if control_mm is not None:
            got = self.control = follow(mm=control_mm)
        elif fault is not None:
            got = self.control = follow(faults=(fault,))
        return compare(got, self.want, self.config["limits"]["lm_train"],
                       float(self.config["load_balance_coeff"]))

    def raw_readings(self) -> dict:
        """Per-leaf readings behind the comparison (control.py prints them
        when a statistic has to be chosen)."""
        def diffs(got):
            import jax

            return ref.leaf_norms(jax.tree_util.tree_map(
                lambda a, b: np.asarray(a) - np.asarray(b), got["grad"],
                self.want["grad"]))

        return {"ref_norm": self.want["grad_norm"],
                "program_diff": diffs(self.got),
                "control_diff": diffs(self.control),
                "program_delta": self.got["delta_norm"],
                "ref_delta": self.want["delta_norm"],
                "counts_off_mean_least": [
                    float(np.abs(c - c.mean(axis=-1, keepdims=True)).min())
                    for c in self.want["counts"]]}


def compare(got: dict, want: dict, limits: dict, coeff: float) -> list:
    """``kinds/bd_train.py``'s rows over this model's leaves, and the
    biases: the share of them, over every expert layer and each of the
    steps, that are not the reference's (one step moves a bias by ``coeff``
    up or down: a wrong count, sign or centring flips it)."""
    rows = leaf_rows(got, want, limits)
    rows.append({"name": "bias_diff_share",
                 "value": ref.bias_diff_share(got["bias"], want["bias"],
                                              coeff),
                 "limit": limits["bias_diff_share"]})
    return rows
