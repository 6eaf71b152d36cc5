"""Kind ``bd_train``: the production epoch driver on the block-diffusion
mixture-of-experts decoder (``models/sdar.py``), whole epochs back to back.

``ScanEpochDriver`` with the language-model step (``train/lm_step.py``) over
a resident pool of packed, pre-noised token sequences, ``TrainState`` and
``make_optimizer("adamw")`` as ``fit`` builds them for ``train.py --task
blockdiff``. The window, its clock, the schedule's rng (``ScheduleRng``), the
deferred fetch, the rate, the evidence and the rows of the first-steps
comparison are kind ``train``'s (``kinds/train.py``), which this driver
extends. What differs: the model, its step, its data (no graphs), its weights
(``weights_sdar.py``), its reference (``reference/sdar_ref.py``), its counts
(``counts_sdar.py``), and how set-up is ordered, because one state is most of
the chip's memory: no two states are ever held at once, and the program's
state is freed before the reference builds its own.

**What ``--seed`` changes here, and what it does not.** The work of a step
depends on the weights: how many rows the routers send to the 16 experts
held is the weights' luck (a quarter of all positions carry the one [MASK]
embedding and route alike in the first layer). So the window trains the
CONFIGURATION's weights (``model.weights_seed``), the same in every run, and
``--seed`` draws (1) the order in which an epoch visits its batches and (2)
the weights on which ``correct`` is decided: the three compared steps run
from a state seeded by ``--seed`` through the warmed one-step program, before
the window's state exists. Set-up: pool -> driver -> state from
``weights_seed`` -> ``warm()`` consumes it (its epoch in pack order is where
the counters are read: the same numbers in every run) -> state from ``--seed``
-> three compared steps -> outputs to the host, state freed -> state from
``weights_seed`` again -> window.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import counts, counts_sdar
from benchmark.kinds import train
from benchmark.reference import sdar_ref as ref
from benchmark.weights_sdar import StateMaker

# name -> keywords of ``Driver.check`` (``benchmark/control.py``): the
# reference computed that way stands in the program's place and has to come
# out as not correct. ``float8``: e4m3 on every matmul operand, the precision
# below the bfloat16 this configuration states. The other three are faults of
# this model that no precision explains. ``causal_mask``: the plain causal
# mask over the 2L positions in the block-diffusion mask's place.
# ``unweighted``: the loss without 1/t. ``dropped_rows``: each held expert
# computes no more rows than a balanced router would send it.
CONTROLS = {"float8": {"control_mm": ref.mm_fp8},
            "causal_mask": {"fault": "causal_mask"},
            "unweighted": {"fault": "unweighted"},
            "dropped_rows": {"fault": "dropped_rows"}}
# the step's metric sums that go on as counters (train/lm_step.py), an
# epoch's totals but for the ratio, which is the mean of the steps' worst
EPOCH_TOTALS = ("moe_rows_here", "moe_rows_balanced", "bd_tiles_live",
                "bd_tiles_grid", "masked_tokens")
# keys of the source's config.json the program's model takes as they are
PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
             "head_dim", "num_hidden_layers", "num_experts_per_tok",
             "moe_intermediate_size", "vocab_size", "rms_norm_eps")


def model_config(config: dict):
    """The program's ``SdarConfig`` for the configuration file: the source's
    keys at the top level, the share and what was assumed beside them."""
    from cgnn_tpu.models.sdar import SdarConfig

    share = config["share"]
    held = tuple(int(x) for x in share["experts_held"])
    if held[1] != int(config["num_experts"]):
        raise ValueError("num_experts states the experts held here; "
                         "share.experts_held has to count as many")
    return SdarConfig(
        **{k: config[k] for k in PUBLISHED},
        rope_theta=float(config["rope_theta"]),
        n_experts=int(share["num_experts_published"]), experts_held=held,
        block_length=int(config["diffusion"]["block_length"]),
        dtype=config["precision"]["compute"])


def reference_model(config: dict) -> dict:
    """What the reference reads of the configuration (plain data)."""
    share = config["share"]
    return {**{k: config[k] for k in PUBLISHED},
            "rope_theta": float(config["rope_theta"]),
            "experts_held": tuple(int(x) for x in share["experts_held"]),
            "block_length": int(config["diffusion"]["block_length"])}


def counts_model(config: dict) -> dict:
    return {**{k: config[k] for k in PUBLISHED},
            "num_experts": int(config["num_experts"]),
            "num_experts_published": int(
                config["share"]["num_experts_published"])}


def first_gradient(opt_state, b1: float):
    """The gradient AdamW was given in its first step, as host arrays: after
    one step its first moment is (1 - b1) times that gradient."""
    import jax

    mu = [t for t in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(t, "mu")][0].mu
    return jax.tree_util.tree_map(lambda m: np.array(m) / (1.0 - b1), mu)


class Driver(train.Driver):

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax

        # the program first: a checkout without the model (the parent of the
        # PR that added this kind) fails here, at once
        from cgnn_tpu.data import tokens
        from cgnn_tpu.models import sdar
        from cgnn_tpu.train import lm_step, make_optimizer
        from cgnn_tpu.train.loop import ScanEpochDriver

        ctx, cfg, tr = self.ctx, self.config, self.config["train"]
        data = cfg["data"]
        if tr["optim"].lower() != "adamw":
            raise ValueError("bd_train follows AdamW's first steps "
                             "(reference/sdar_ref.py adamw_steps)")
        with ctx.span("init"):
            self.model_cfg = model_config(cfg)
            tx = make_optimizer(
                optim="adamw", lr=float(tr["lr"]), b1=float(tr["b1"]),
                b2=float(tr["b2"]), weight_decay=float(tr["weight_decay"]),
                lr_milestones=[])
            self.maker = StateMaker(
                self.model_cfg, cfg["init"], tx,
                functools.partial(sdar.apply, self.model_cfg))
        with ctx.span("data"):
            docs = data["documents"]
            pool = tokens.make_pool(
                int(data["n"]), int(data["sequence_length"]),
                vocab_size=self.model_cfg.vocab_size,
                block=self.model_cfg.block_length,
                seed=int(data["pool_seed"]), doc_median=docs["median"],
                doc_sigma=docs["sigma"], doc_min=docs["min"],
                doc_max=docs["max"])
            batches = tokens.split_batches(pool, int(tr["batch_size"]))
        self.batches = batches
        self.steps_per_epoch = len(batches)
        self.structures_per_epoch = int(data["n"])
        s, n2 = batches[0].tokens.shape
        self.positions = s * n2
        print(f"pool: {data['n']} sequences of {n2 // 2} tokens "
              f"({n2} positions with the noised copy), "
              f"{int(pool.segment_ids.max(axis=1).sum()) + int(data['n'])} "
              f"documents, {self.steps_per_epoch} steps of {s} an epoch; "
              f"model {self.model_cfg.n_params() / 1e6:.1f} M parameters")
        ctx.obs["counts"].update(
            steps_per_epoch=self.steps_per_epoch,
            structures_per_epoch=self.structures_per_epoch,
            # no padding: documents are packed to exactly the length
            real_nodes=self.steps_per_epoch * self.positions,
            node_slots=self.steps_per_epoch * self.positions)
        tiles = sdar.attention_tiles(self.model_cfg, n2 // 2)
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
        weights, whatever ``--seed``."""
        return self.maker.make(int(self.config["model"]["weights_seed"]))

    def reseed(self, seed: int) -> None:
        """Other compared weights through the same compiled programs (the
        limits' readings take a dozen seeds in one process)."""
        self.state = None
        self._compared_steps(seed)

    def _note_counters(self, pool) -> None:
        """What warm()'s epoch counted (pack order, the configuration's
        weights: the same in every run), as counters, and the least time a
        step and its two kernels could take on this chip."""
        import jax

        ctx = self.ctx
        means = self.driver.warm_metrics[0]
        steps = self.steps_per_epoch
        seen = {k: means[k] * steps for k in EPOCH_TOTALS}
        seen["expert_load_max_over_mean"] = means["expert_load_max_over_mean"]
        ctx.obs["counts"].update(seen)
        if ctx.telemetry is not None:
            for name, value in seen.items():
                ctx.telemetry.set_gauge(name, float(value))
        print("counters (warm epoch): " + ", ".join(
            f"{k} {v:.6g}" for k, v in seen.items()))
        if jax.devices()[0].platform != "tpu":
            return  # no roofline off the chip
        model = counts_model(self.config)
        layers = model["num_hidden_layers"]
        pairs = counts_sdar.visible_pairs(
            pool.segment_ids, self.model_cfg.block_length) / steps
        rows = seen["moe_rows_here"] / steps / layers
        peaks = counts.peaks_for(jax.devices()[0].device_kind)
        whole = counts_sdar.step_counts(
            model, positions=self.positions, noised=self.positions / 2,
            pairs=pairs, rows=rows)
        least, bound = counts.least_seconds(whole, peaks)
        by_kernel = {
            "attn_bd": counts_sdar.attention_counts(
                model, self.positions, pairs),
            "moe_expert": counts_sdar.expert_counts(model, rows)}
        ctx.obs["counts"]["least_s_per_traced_steps"] = least
        for name, c in by_kernel.items():
            ctx.obs["counts"][name + "_least_s_per_traced_steps"] = (
                layers * counts.least_seconds(c, peaks)[0])
        print(f"roofline: least {1e3 * least:.3f} ms a step, bound by "
              f"{bound} ({whole['flops']:.4g} FLOP, {whole['bytes']:.4g} B "
              f"a step; {pairs:.4g} visible pairs and {rows:.4g} routed "
              f"rows a layer)")

    def _compared_steps(self, seed: int) -> None:
        """The warmed one-step program through the first steps from a state
        seeded by ``seed``, batches 0, 1, 2 in pack order; what the
        comparison reads goes to the host, and the state is freed."""
        import jax

        tmap = jax.tree_util.tree_map
        d = self.driver
        (key, stacked), = d._train_groups.items()
        state = self.maker.make(seed)
        self.params0 = tmap(np.array, state.params)
        self.check_batches = list(range(train.N_CHECK_STEPS))
        fn = d._scan_fn(d._train_scans, (key, 1), d._train_body, True)
        got = {"loss": []}
        for s in self.check_batches:
            perm = jax.device_put(np.array([s], np.int32))
            state, sums = fn(state, stacked, perm)
            sums = tmap(float, jax.device_get(sums))
            got["loss"].append(sums["loss_sum"] / max(sums["count"], 1.0))
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
        weights on the same batches. With ``control_mm`` or a ``fault``
        (``CONTROLS``) the reference computed that way stands in the
        program's place. The program's state is freed first: the two never
        share the device."""
        self.state = None
        tr = self.config["train"]
        batches = [{"tokens": b.tokens, "segment_ids": b.segment_ids,
                    "loss_weight": b.loss_weight}
                   for b in (self.batches[i] for i in self.check_batches)]
        per_sequence = self.positions // batches[0]["tokens"].shape[0]
        balanced = (per_sequence * self.model_cfg.num_experts_per_tok
                    // self.model_cfg.n_experts)

        def follow(fault=None, **kw):
            faults = {None: {}, "causal_mask": {"causal_mask": True},
                      "unweighted": {"unweighted": True},
                      "dropped_rows": {"dropped_rows": balanced}}
            if fault not in faults:
                raise ValueError(f"no fault {fault!r}")
            return ref.adamw_steps(
                self.params0, batches, reference_model(self.config),
                lr=float(tr["lr"]), b1=float(tr["b1"]), b2=float(tr["b2"]),
                weight_decay=float(tr["weight_decay"]), **faults[fault],
                **kw)

        if self.want is None:
            self.want = follow()
        got = self.got
        if control_mm is not None or fault is not None:
            kw = {} if control_mm is None else {"mm": control_mm}
            got = self.control = follow(fault, **kw)
        return compare(got, self.want, self.config["limits"]["bd_train"])

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
                "expert_rows_most": self.want["expert_rows_most"]}


def compare(got: dict, want: dict, limits: dict) -> list:
    """Kind ``train``'s rows (``kinds/train.py`` ``compare``) over this
    model's leaves, none of which is zero by construction."""
    rows = [
        {"name": f"loss_step{s + 1}_rel",
         "value": abs(g - w) / max(abs(w), 1e-30),
         "limit": limits["loss_rel"]}
        for s, (g, w) in enumerate(zip(got["loss"], want["loss"]))
    ]
    rows.append({"name": "grad_diff_median_leaf",
                 "value": ref.median_leaf_diff(got["grad"], want["grad"]),
                 "limit": limits["grad_diff_median_leaf"]})
    rows.append({"name": "grad_norm_worst_leaf",
                 "value": max(ref.leaf_gaps(got["grad_norm"],
                                            want["grad_norm"])),
                 "limit": limits["grad_norm_worst_leaf"]})
    rows.append({"name": "delta_norm_median_leaf",
                 "value": float(np.median(ref.leaf_gaps(
                     got["delta_norm"], want["delta_norm"]))),
                 "limit": limits["delta_norm_median_leaf"]})
    return rows
