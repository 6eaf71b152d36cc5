"""Kind ``nemotron_train``: the production epoch driver on the hybrid
Mamba-2 / attention mixture-of-experts decoder (``models/nemotron_h.py``),
whole epochs back to back.

``ScanEpochDriver`` with the language-model step (``train/lm_step.py``: the
next-token loss over an untied head; the routers' selection biases are fixed
and no step moves them) over a resident pool of packed token sequences,
``TrainState`` (the biases in ``batch_stats``) and ``make_optimizer("adamw")``
as ``fit`` builds them for ``train.py --task lm --lm-model
nemotron-3-nano-30b-a3b-ep16``. The window, its clock, the schedule's rng
(``ScheduleRng``), the deferred fetch, the rate and the evidence are kind
``train``'s (``kinds/train.py``); the set-up order, the three compared steps
through the warmed one-step program and ``reseed`` are kind ``lm_train``'s
(``kinds/lm_train.py``, which has why: one state is most of the chip's
memory, so no two states are ever held at once), whose driver this one
extends with its own model, weights, reference and counts, as
``kinds/lfm2_train.py`` does; the rows of the comparison are
``kinds/lfm2_train.py``'s (``compare``: the losses, the timed step's
gradient, the parameters' change, the biases bit for bit).

**What ``--seed`` changes here, and what it does not.** As in
``kinds/lm_train.py``: the window trains the CONFIGURATION's weights
(``model.weights_seed``; biases uniform in +-0.01 from the same seed), the
same in every run, and ``--seed`` draws (1) the order in which an epoch
visits its batches and (2) the weights and biases (uniform in +-0.1) on which
``correct`` is decided.
"""

from __future__ import annotations

import functools

from benchmark import counts, counts_nemotron
from benchmark.kinds import lfm2_train, lm_train
from benchmark.reference import nemotron_ref as ref
from benchmark.weights_nemotron import StateMaker

# name -> keywords of ``Driver.check`` (``benchmark/control.py``): the
# reference computed that way stands in the program's place and has to come
# out as not correct. ``float8``: e4m3 on every matmul operand, the precision
# below the bfloat16 this configuration states. The others are faults of
# this model that no precision explains (``reference/nemotron_ref.py``
# ``FAULTS``). ``UNDECIDED`` (the same form) are those that no row over the
# timed steps can tell from bfloat16 at the cell's traffic and these weights
# (the configuration's ``limits_why`` has the readings, PERF.md section 2
# the timed step's gradient leaf by leaf): ``state_bfloat16``, the scan's
# state rounded to bfloat16 after every position, the precision below the
# float32 the program carries it in (under the bfloat16 program on EVERY
# leaf, the decays' ``a_log`` and ``dt_bias`` too: no row can tell it); a
# state or a filter that crosses a document's start (52 documents in 131,072
# positions; ``a_log`` reads 2.3x the program's for the state, the attention
# layer's leaves 3-4x for the filter, on the one seed read); RoPE on the
# one attention layer (16-20x the program's on that layer's leaves, 1.3x on
# the median: one seed, so a row of their own waits for a dozen sound
# readings). ``tests/test_ssd.py`` and ``tests/test_nemotron_h.py`` hold the
# program to them at sizes where documents are short, and
# ``tests/benchmark/test_nemotron_cell.py`` shows that the timed step's
# gradient tells each of the faults there.
_FAULTS_UNDECIDED = ("state_crosses_documents", "conv_crosses_documents",
                     "attention_rotated")
CONTROLS = {"float8": {"control_mm": ref.mm_fp8},
            **{name: {"fault": name} for name in ref.FAULTS
               if name not in _FAULTS_UNDECIDED}}
UNDECIDED = {"state_bfloat16": {"state_dtype": "bfloat16"},
             **{name: {"fault": name} for name in _FAULTS_UNDECIDED}}
# the step's metric sums that go on as counters (train/lm_step.py), an
# epoch's totals; ``EPOCH_MEANS`` are a step's own (the mean of the steps')
EPOCH_TOTALS = ("moe_rows_here", "moe_rows_balanced", "moe_rows_capacity",
                "moe_calls_full_rung", "attn_full_tiles_live",
                "attn_full_tiles_grid", "weighted_tokens", "ssm_positions",
                "ssm_resets", "ssm_chunks", "ssm_chunks_cut")
EPOCH_MEANS = ("expert_load_max_over_mean",)
# keys of the source's config.json the program's model takes as they are
PUBLISHED = ("hidden_size", "num_hidden_layers", "hybrid_override_pattern",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "mamba_num_heads", "mamba_head_dim", "n_groups",
             "ssm_state_size", "conv_kernel", "chunk_size",
             "moe_intermediate_size", "moe_shared_expert_intermediate_size",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "vocab_size", "layer_norm_epsilon")
# the window's biases: uniform in +-this (the compared state's are
# ``lm_train.COMPARED_BIAS``, which its ``_compared_steps`` asks for)
WINDOW_BIAS = 0.01


def _held(config: dict) -> tuple:
    held = tuple(int(x) for x in config["share"]["experts_held"])
    if held[1] != int(config["n_routed_experts"]):
        raise ValueError("n_routed_experts states the experts held here; "
                         "share.experts_held has to count as many")
    return held


def _model(config: dict) -> dict:
    """The source's keys the model is built from, after holding the file
    to what models/nemotron_h.py is: no bias but the filter's, one shared
    expert, no group limit on the router, ``relu2`` experts."""
    is_the_model = (
        config["use_conv_bias"] and not config["use_bias"]
        and not config["mamba_proj_bias"] and not config["mlp_bias"]
        and not config["attention_bias"] and config["n_shared_experts"] == 1
        and config["n_group"] == config["topk_group"] == 1
        and config["mlp_hidden_act"] == "relu2"
        and config["mamba_hidden_act"] == "silu"
        and not config["tie_word_embeddings"])
    if not is_the_model:
        raise ValueError("models/nemotron_h.py has a bias on the filter "
                         "alone, one shared expert, no group limit, relu2 "
                         "experts and an untied head")
    return {k: config[k] for k in PUBLISHED}


def model_config(config: dict):
    """The program's ``NemotronHConfig`` for the configuration file: the
    source's keys at the top level, the share beside them."""
    from cgnn_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig(
        **_model(config),
        n_experts=int(config["share"]["num_experts_published"]),
        experts_held=_held(config), dtype=config["precision"]["compute"])


def reference_model(config: dict) -> dict:
    """What the reference reads of the configuration (plain data)."""
    return {**_model(config), "experts_held": _held(config),
            "rope_theta": float(config["rope_theta"])}


def counts_model(config: dict) -> dict:
    return {**_model(config),
            "n_routed_experts": int(config["n_routed_experts"]),
            "num_experts_published": int(
                config["share"]["num_experts_published"])}


class Driver(lm_train.Driver):

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax

        # the program first: a checkout without the model (the parent of the
        # PR that added this kind) fails here, at once
        from cgnn_tpu.data import tokens
        from cgnn_tpu.models import nemotron_h
        from cgnn_tpu.train import lm_step, make_optimizer
        from cgnn_tpu.train.loop import ScanEpochDriver

        ctx, cfg, tr = self.ctx, self.config, self.config["train"]
        data = cfg["data"]
        if tr["optim"].lower() != "adamw":
            raise ValueError("nemotron_train follows AdamW's first steps "
                             "(reference/nemotron_ref.py adamw_steps)")
        with ctx.span("init"):
            self.model_cfg = model_config(cfg)
            tx = make_optimizer(
                optim="adamw", lr=float(tr["lr"]), b1=float(tr["b1"]),
                b2=float(tr["b2"]), weight_decay=float(tr["weight_decay"]),
                lr_milestones=[])
            self.maker = StateMaker(
                self.model_cfg, cfg["init"], tx,
                functools.partial(nemotron_h.apply, self.model_cfg))
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
        tiles = nemotron_h.attention_tiles(self.model_cfg, length)
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
        """The state every run's window trains: the configuration's weights
        and biases (+-0.01), whatever ``--seed``."""
        return self.maker.make(int(self.config["model"]["weights_seed"]),
                               WINDOW_BIAS)

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
        pairs = counts_nemotron.visible_pairs(pool.segment_ids) / steps
        rows = seen["moe_rows_here"] / steps / mc.n_expert_layers
        peaks = counts.peaks_for(jax.devices()[0].device_kind)
        whole = counts_nemotron.step_counts(
            model, positions=self.positions,
            weighted=seen["weighted_tokens"] / steps, pairs=pairs, rows=rows)
        least, bound = counts.least_seconds(whole, peaks)
        by_phase = {
            "ssm_scan": (mc.n_ssm_layers, counts_nemotron.ssm_scan_counts(
                model, self.positions)),
            "attn_causal": (mc.n_attention_layers,
                            counts_nemotron.attention_counts(
                                model, self.positions, pairs)),
            "moe_expert": (mc.n_expert_layers,
                           counts_nemotron.expert_counts(model, rows))}
        ctx.obs["counts"]["least_s_per_traced_steps"] = least
        for name, (layers, c) in by_phase.items():
            ctx.obs["counts"][name + "_least_s_per_traced_steps"] = (
                layers * counts.least_seconds(c, peaks)[0])
        print(f"roofline: least {1e3 * least:.3f} ms a step, bound by "
              f"{bound} ({whole['flops']:.4g} FLOP, {whole['bytes']:.4g} B "
              f"a step; {pairs:.4g} visible pairs the attention layer, "
              f"{rows:.4g} routed rows an expert layer)")

    # ---- the comparison -----------------------------------------------

    def check(self, control_mm=None, fault=None, state_dtype=None) -> list:
        """The reference follows the same first steps from the same seeded
        weights and biases on the same batches. With ``control_mm``, a
        ``fault`` or a ``state_dtype`` (``CONTROLS``) the reference computed
        that way stands in the program's place. The program's state is
        freed first: the two never share the device."""
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
        elif state_dtype is not None:
            got = self.control = follow(state_dtype=state_dtype)
        return lfm2_train.compare(got, self.want, self.bias0,
                                  self.config["limits"]["nemotron_train"])
