"""Kind ``lfm2_train``: the production epoch driver on the hybrid
short-convolution / attention mixture-of-experts decoder
(``models/lfm2.py``), whole epochs back to back.

``ScanEpochDriver`` with the language-model step (``train/lm_step.py``: the
next-token loss over a tied head; the routers' selection biases are fixed and
no step moves them) over a resident pool of packed token sequences,
``TrainState`` (the biases in ``batch_stats``) and ``make_optimizer("adamw")``
as ``fit`` builds them for ``train.py --task lm --lm-model
lfm2-24b-a2b-ep8``. The window, its clock, the schedule's rng
(``ScheduleRng``), the deferred fetch, the rate and the evidence are kind
``train``'s (``kinds/train.py``); the set-up order, the three compared steps
through the warmed one-step program and ``reseed`` are kind ``lm_train``'s
(``kinds/lm_train.py``, which has why: one state is most of the chip's
memory, so no two states are ever held at once), whose driver this one
extends with its own model, weights, reference and counts; the rows of the
comparison are ``kinds/bd_train.py``'s (``compare``) and one more: the
biases, which have to come back bit for bit.

**What the rows cannot decide.** A convolution that read across a
document's start (``reference/lfm2_ref.py`` ``FAULTS``
``conv_crosses_documents``): at this traffic a document starts once in
4,400 positions, so the fault moves the three steps' gradients by less than
bfloat16 does and no row over the timed steps can tell. It is no control
of this kind (``CONTROLS``); ``tests/test_lfm2.py`` holds the op, at every
edge, and both mixers through the model to "nothing crosses a document".

**What ``--seed`` changes here, and what it does not.** As in
``kinds/lm_train.py``: the window trains the CONFIGURATION's weights
(``model.weights_seed``; biases uniform in +-0.01 from the same seed), the
same in every run, and ``--seed`` draws (1) the order in which an epoch
visits its batches and (2) the weights and biases (uniform in +-0.1) on which
``correct`` is decided.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import counts, counts_lfm2
from benchmark.kinds import lm_train
from benchmark.kinds.bd_train import compare as leaf_rows
from benchmark.reference import lfm2_ref as ref
from benchmark.weights_lfm2 import StateMaker

# name -> keywords of ``Driver.check`` (``benchmark/control.py``): the
# reference computed that way stands in the program's place and has to come
# out as not correct. ``float8``: e4m3 on every matmul operand, the precision
# below the bfloat16 this configuration states. The others are faults of
# this model that no precision explains (``reference/lfm2_ref.py``
# ``FAULTS``): a convolution without its two gates, one of two taps, the
# top-4 of the scores alone, softmax scores, a head of its own draw in the
# embedding's place, no norm over a head. Not among them: the reference's
# ``conv_crosses_documents``, which no row over the timed steps can tell from
# bfloat16 at this traffic (the module's docstring).
UNDECIDED = ("conv_crosses_documents",)
CONTROLS = {"float8": {"control_mm": ref.mm_fp8},
            **{name: {"fault": name} for name in ref.FAULTS
               if name not in UNDECIDED}}
# the step's metric sums that go on as counters (train/lm_step.py), an
# epoch's totals; ``EPOCH_MEANS`` are a step's own (the mean of the steps')
EPOCH_TOTALS = ("moe_rows_here", "moe_rows_balanced", "moe_rows_capacity",
                "moe_calls_full_rung", "attn_full_tiles_live",
                "attn_full_tiles_grid", "weighted_tokens", "sconv_positions",
                "sconv_taps_cut")
EPOCH_MEANS = ("expert_load_max_over_mean",)
# keys of the source's config.json the program's model takes as they are
PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
             "num_hidden_layers", "num_dense_layers", "layer_types",
             "intermediate_size", "moe_intermediate_size",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "vocab_size", "norm_eps")
# the window's biases: uniform in +-this (the compared state's are
# ``lm_train.COMPARED_BIAS``, which its ``_compared_steps`` asks for)
WINDOW_BIAS = 0.01


def _model(config: dict) -> dict:
    """The source's keys and what the file states beside them (the head's
    size is ``assumed``; RoPE's theta is inside the source's group)."""
    if config["conv_bias"] or not config["use_expert_bias"]:
        raise ValueError("models/lfm2.py has no bias on the convolution and "
                         "a selection bias on every router")
    return {**{k: config[k] for k in PUBLISHED},
            "head_dim": int(config["head_dim"]),
            "rope_theta": float(config["rope_parameters"]["rope_theta"])}


def model_config(config: dict):
    """The program's ``Lfm2Config`` for the configuration file: the source's
    keys at the top level, the share beside them. The filter's length is
    the op's constant, so the file's is held to it here, once."""
    from cgnn_tpu.models.lfm2 import Lfm2Config
    from cgnn_tpu.ops.short_conv import TAPS

    if config["conv_L_cache"] != TAPS:
        raise ValueError(f"ops/short_conv.py has {TAPS} taps, the "
                         f"configuration {config['conv_L_cache']}")
    return Lfm2Config(
        **_model(config),
        n_experts=int(config["share"]["num_experts_published"]),
        experts_held=lm_train._held(config),
        dtype=config["precision"]["compute"])


def reference_model(config: dict) -> dict:
    """What the reference reads of the configuration (plain data)."""
    return {**_model(config), "experts_held": lm_train._held(config)}


def counts_model(config: dict) -> dict:
    return {**_model(config), "conv_L_cache": int(config["conv_L_cache"]),
            "num_experts": int(config["num_experts"]),
            "num_experts_published": int(
                config["share"]["num_experts_published"])}


class Driver(lm_train.Driver):

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax

        # the program first: a checkout without the model (the parent of the
        # PR that added this kind) fails here, at once
        from cgnn_tpu.data import tokens
        from cgnn_tpu.models import lfm2
        from cgnn_tpu.train import lm_step, make_optimizer
        from cgnn_tpu.train.loop import ScanEpochDriver

        ctx, cfg, tr = self.ctx, self.config, self.config["train"]
        data = cfg["data"]
        if tr["optim"].lower() != "adamw":
            raise ValueError("lfm2_train follows AdamW's first steps "
                             "(reference/lfm2_ref.py adamw_steps)")
        with ctx.span("init"):
            self.model_cfg = model_config(cfg)
            tx = make_optimizer(
                optim="adamw", lr=float(tr["lr"]), b1=float(tr["b1"]),
                b2=float(tr["b2"]), weight_decay=float(tr["weight_decay"]),
                lr_milestones=[])
            self.maker = StateMaker(
                self.model_cfg, cfg["init"], tx,
                functools.partial(lfm2.apply, self.model_cfg))
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
        tiles = lfm2.attention_tiles(self.model_cfg, length)
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
        pairs = counts_lfm2.visible_pairs(pool.segment_ids) / steps
        rows = seen["moe_rows_here"] / steps / mc.n_expert_layers
        peaks = counts.peaks_for(jax.devices()[0].device_kind)
        whole = counts_lfm2.step_counts(
            model, positions=self.positions,
            weighted=seen["weighted_tokens"] / steps, pairs=pairs, rows=rows)
        least, bound = counts.least_seconds(whole, peaks)
        by_phase = {
            "sconv_mix": (mc.n_conv_layers, counts_lfm2.sconv_mix_counts(
                model, self.positions)),
            "attn_causal": (mc.n_attention_layers,
                            counts_lfm2.attention_counts(
                                model, self.positions, pairs)),
            "moe_expert": (mc.n_expert_layers,
                           counts_lfm2.expert_counts(model, rows))}
        ctx.obs["counts"]["least_s_per_traced_steps"] = least
        for name, (layers, c) in by_phase.items():
            ctx.obs["counts"][name + "_least_s_per_traced_steps"] = (
                layers * counts.least_seconds(c, peaks)[0])
        print(f"roofline: least {1e3 * least:.3f} ms a step, bound by "
              f"{bound} ({whole['flops']:.4g} FLOP, {whole['bytes']:.4g} B "
              f"a step; {pairs:.4g} visible pairs the attention layer, "
              f"{rows:.4g} routed rows an expert layer)")

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
        return compare(got, self.want, self.bias0,
                       self.config["limits"]["lfm2_train"])


def compare(got: dict, want: dict, bias0, limits: dict) -> list:
    """``kinds/bd_train.py``'s rows over this model's leaves, and the
    biases: the share of them, over every expert layer and each of the
    steps, that are not bit for bit what they were (no step moves them; the
    reference holds none to move, so a control reads 0 here)."""
    rows = leaf_rows(got, want, limits)
    moved = [np.asarray(b) != np.asarray(bias0) for b in got.get("bias", [])]
    rows.append({"name": "bias_moved_share",
                 "value": float(np.mean(moved)) if moved else 0.0,
                 "limit": limits["bias_moved_share"]})
    return rows
