"""A window-and-full-attention mixture-of-experts decoder (arcee-ai's
``afmoe`` block: Trinity), as one chip of an expert-parallel layer holds it.

A layer, on the residual stream ``x [S, L, H]`` (``x0 = embed[tokens] *
sqrt(H)`` under ``mup_enabled``):

    h = RMSNorm(x);  q, k, v, g = h W_q, h W_k, h W_v, h W_g    (no bias)
    q, k = RMSNorm over each head's dims
    sliding_attention: q, k = RoPE(q, k);  key j is visible to query i iff
                       doc(j) = doc(i) and i - window < j <= i
    full_attention:    no RoPE;  visible iff doc(j) = doc(i) and j <= i
    a = masked_attention(q / sqrt(d), k, v)               (grouped queries)
    x += RMSNorm((a * sigmoid(g)) W_o)
    h = RMSNorm(x)
    a leading dense layer: m = W_d(silu(h W_gate) * (h W_up))
    an expert layer:       m = shared(h) + this share of sum_k p_k e_k(h)
                           (ops/moe.py: sigmoid scores, the ``k`` largest of
                           score + bias, p the scores' own, normed and scaled)
    x += RMSNorm(m)

The stack is the leading dense layers, then the expert layers in periods of
``layer_types`` (window, window, window, full as published), scanned as
``lm_blocks.Stack`` has it (``lm_blocks.scan_stack``, which models/lfm2.py
shares: ``periods/run0`` the three window layers, ``periods/run1`` the full
one, each leaf ``[periods, layers of the run, ...]``). Within a layer the
step's sequences go one at a time, a ``jax.checkpoint`` a layer and sequence
(``lm_blocks.by_sequence``) that keeps the layer's input, its attention's
output and the routed experts' output (neither the attention's forward
kernel nor the expert layer's switch is run again when the reverse pass
rebuilds the rest). Weights are float32 and are cast to the
compute dtype inside the layer; norms, RoPE, the router, the gate's sigmoid
and the loss are float32.

The selection biases are no parameters: they ride in the state's
``batch_stats`` (``router_bias [periods, layers a period, E]``), ``apply``
returns every router's counts, and the step moves the biases
(train/lm_step.py). The vocabulary is a slice (``vocab_size`` rows are
held); the loss is next-token cross-entropy over the slice.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from cgnn_tpu.models import lm_blocks
from cgnn_tpu.models.lm_blocks import (
    by_sequence, chunked_loss_sums, prepare_heads, rms_norm,
)
from cgnn_tpu.observe import phases
from cgnn_tpu.ops import moe
from cgnn_tpu.ops.masked_attention import (
    StaticMask, live_tiles, mask_tiles, masked_attention,
)

SLIDING, FULL = "sliding_attention", "full_attention"
# the attention layers' kinds under the names their counters go by
_COUNTED = (("window", SLIDING), ("full", FULL))
# what an expert layer's checkpoint keeps beside its input and its
# attention's output (``lm_blocks.by_sequence``)
ROUTED = "moe.routed"
# leaves initialised at the output projections' scale (``init_params``)
OUTPUT_PROJECTIONS = ("wo", "w_down", "mlp_down", "shared_down")


@dataclasses.dataclass(frozen=True)
class AfmoeConfig(lm_blocks.Stack):
    # what train/lm_step.py makes of a batch (no field: the model's own)
    objective = "causal"
    # both kinds mix by attention and route after the leading dense layer
    layer_kinds = {SLIDING: (lm_blocks.ATTENTION, True),
                   FULL: (lm_blocks.ATTENTION, True)}

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_hidden_layers: int = 5
    num_dense_layers: int = 1
    layer_types: tuple = (SLIDING,) * 4 + (FULL,)
    sliding_window: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    # the router's outputs (all experts of the layer) and the experts a token
    # takes; ``experts_held`` = (first, count) is this chip's share
    n_experts: int = 128
    num_experts_per_tok: int = 8
    experts_held: tuple = (0, 8)
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 0.001
    mup_enabled: bool = True
    vocab_size: int = 25024
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    attn_impl: str = "auto"
    moe_impl: str = "auto"

    def __post_init__(self):
        self.check_stack()

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def routing(self) -> moe.Router:
        return moe.Router(score_func=self.score_func, norm=self.route_norm,
                          norm_eps=1e-20, scale=self.route_scale)

    def _attention_shapes(self) -> dict:
        h, d = self.hidden_size, self.head_dim
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        return {"attn_norm": (h,), "wq": (h, hq * d), "wk": (h, hkv * d),
                "wv": (h, hkv * d), "wg": (h, hq * d), "q_norm": (d,),
                "k_norm": (d,), "wo": (hq * d, h), "post_attn_norm": (h,),
                "mlp_norm": (h,), "post_mlp_norm": (h,)}

    def shapes(self) -> dict:
        """The parameter tree's shapes, float32 all."""
        h, i = self.hidden_size, self.moe_intermediate_size
        e = self.experts_held[1]
        shared = i * self.num_shared_experts
        expert_layer = {
            **self._attention_shapes(), "router": (h, self.n_experts),
            "w_gate_up": (e, h, 2 * i), "w_down": (e, i, h),
            "shared_gate_up": (h, 2 * shared), "shared_down": (shared, h)}
        dense_layer = {
            **self._attention_shapes(),
            "mlp_gate_up": (h, 2 * self.intermediate_size),
            "mlp_down": (self.intermediate_size, h)}
        return {
            "embed": (self.vocab_size, h),
            **lm_blocks.stack_shapes(self, lambda kind: dense_layer,
                                     lambda kind: expert_layer),
            "final_norm": (h,),
            "head": (h, self.vocab_size),
        }

    def stats_shapes(self) -> dict:
        """``batch_stats``: the selection biases, float32."""
        return {"router_bias": (self.n_periods, len(self.period),
                                self.n_experts)}

    def live_tiles(self, segment_ids) -> dict:
        """{``window`` | ``full``: (the tiles a head visits of each sequence
        ``[S]``, its documents given, layers of the kind)}
        (ops/masked_attention.py)."""
        n = segment_ids.shape[-1]
        return {name: (live_tiles(_mask(self, kind, n), segment_ids),
                       self.layer_types.count(kind))
                for name, kind in _COUNTED}


def _mask(cfg: AfmoeConfig, kind: str, n: int) -> StaticMask:
    return StaticMask("causal", n,
                      window=cfg.sliding_window if kind == SLIDING else 0)


def _attention(cfg: AfmoeConfig, kind: str, x, p, segment_ids):
    """``x += RMSNorm((attention * sigmoid(gate)) W_o)`` on ``x [S, N, H]``."""
    dt = cfg.compute_dtype
    s, n, _ = x.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    eps = cfg.rms_norm_eps
    with jax.named_scope(phases.ATTN_PROJ):
        hn = rms_norm(x, p["attn_norm"], eps).astype(dt)
        # the full layers take no positions (NoPE)
        positions = (jnp.arange(n, dtype=jnp.int32) if kind == SLIDING
                     else None)
        q = prepare_heads(hn @ p["wq"].astype(dt), p["q_norm"], positions,
                          theta=cfg.rope_theta, eps=eps,
                          scale=1.0 / math.sqrt(d))
        k = prepare_heads(hn @ p["wk"].astype(dt), p["k_norm"], positions,
                          theta=cfg.rope_theta, eps=eps)
        v = jnp.swapaxes((hn @ p["wv"].astype(dt)).reshape(s, n, hkv, d),
                         1, 2)
        gate = hn @ p["wg"].astype(dt)
    with jax.named_scope(phases.ATTN_WINDOW if kind == SLIDING
                         else phases.ATTN_FULL):
        a = masked_attention(q, k, v, segment_ids, _mask(cfg, kind, n),
                             impl=cfg.attn_impl)
    with jax.named_scope(phases.ATTN_PROJ):
        a = jnp.swapaxes(a, 1, 2).reshape(s, n, hq * d)
        a = (a.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
        out = a @ p["wo"].astype(dt)
        return x + rms_norm(out, p["post_attn_norm"], eps).astype(dt)


def _mlp(x, gate_up, down):
    return moe.swiglu(x @ gate_up) @ down


def _dense_layer(cfg: AfmoeConfig, kind: str, x, p, segment_ids):
    """A leading dense layer -> ``(x,)``."""
    dt, eps = cfg.compute_dtype, cfg.rms_norm_eps
    x = _attention(cfg, kind, x, p, segment_ids)
    with jax.named_scope(phases.MLP_DENSE):
        hn = rms_norm(x, p["mlp_norm"], eps).astype(dt)
        m = _mlp(hn, p["mlp_gate_up"].astype(dt), p["mlp_down"].astype(dt))
        return (x + rms_norm(m, p["post_mlp_norm"], eps).astype(dt),)


def _expert_layer(cfg: AfmoeConfig, kind: str, x, p, bias, segment_ids):
    """An expert layer -> (x, group_sizes, the rung that carried the held
    experts' rows: ops/moe.py)."""
    dt, eps = cfg.compute_dtype, cfg.rms_norm_eps
    s, n, h = x.shape
    x = _attention(cfg, kind, x, p, segment_ids)
    # the layer's two MLP norms and the sum are booked with the shared
    # expert, as ``mlp.dense`` holds the dense layer's: ``moe.route`` is the
    # router and the rows' way out and back alone (ops/moe.py)
    with jax.named_scope(phases.MOE_SHARED):
        hn = rms_norm(x, p["mlp_norm"], eps).astype(dt)
    with jax.named_scope(phases.MOE_EXPERT):
        w_gate_up, w_down = p["w_gate_up"].astype(dt), p["w_down"].astype(dt)
    routed, group_sizes, rung = moe.expert_share(
        hn.reshape(s * n, h), p["router"], w_gate_up, w_down,
        experts_held=cfg.experts_held, k=cfg.num_experts_per_tok,
        impl=cfg.moe_impl, routing=cfg.routing, bias=bias)
    routed = checkpoint_name(routed, ROUTED)
    with jax.named_scope(phases.MOE_SHARED):
        # every chip of the layer computes the shared expert whole
        shared = _mlp(hn, p["shared_gate_up"].astype(dt),
                      p["shared_down"].astype(dt))
        m = routed.reshape(s, n, h).astype(jnp.float32) + shared
        x = x + rms_norm(m, p["post_mlp_norm"], eps).astype(dt)
    return x, group_sizes, rung


def hidden_states(cfg: AfmoeConfig, params, router_bias, tokens,
                  segment_ids):
    """``tokens, segment_ids [S, L]`` -> (the last layer's ``x [S, L, H]``,
    ``group_sizes [expert layers, E]``, ``rungs [expert layers, S]``: the
    rung of each expert layer's and sequence's call)."""
    with jax.named_scope(phases.LM_EMBED):
        x = params["embed"][tokens]
        if cfg.mup_enabled:
            x = x * math.sqrt(cfg.hidden_size)
        x = x.astype(cfg.compute_dtype)

    def dense_layer(kind, x, p):
        return by_sequence(
            lambda x_seq, seg: _dense_layer(cfg, kind, x_seq, p, seg), x,
            segment_ids)[0]

    def expert_layer(kind, x, p, bias):
        return by_sequence(
            lambda x_seq, seg: _expert_layer(cfg, kind, x_seq, p, bias, seg),
            x, segment_ids, keep=(ROUTED,))

    return lm_blocks.scan_stack(cfg, x, params, router_bias, dense_layer,
                                expert_layer)


def apply(cfg: AfmoeConfig, variables: dict, batch, train: bool = True):
    """The ``apply_fn`` of a ``TrainState``: -> (each sequence's loss ``[S]``
    float32, ``group_sizes [expert layers, E]``, ``rungs [expert layers,
    S]``: ``hidden_states``). Position ``i`` predicts token ``i + 1`` where
    ``batch.loss_weight`` says so (data/tokens.py, ``causal``); the logits
    are an intermediate (``lm_blocks.chunked_loss_sums``)."""
    del train  # no dropout; the biases move in the step, not here
    params = variables["params"]
    x, group_sizes, rungs = hidden_states(
        cfg, params, variables["batch_stats"]["router_bias"], batch.tokens,
        batch.segment_ids)
    with jax.named_scope(phases.LM_HEAD):
        # the last position's target is no token: its weight is 0
        targets = jnp.roll(batch.tokens, -1, axis=1)
        losses = chunked_loss_sums(
            x, targets, batch.loss_weight, params["final_norm"],
            params["head"], eps=cfg.rms_norm_eps, dtype=cfg.compute_dtype)
    return losses, group_sizes, rungs


def init_params(cfg: AfmoeConfig, rng, n_layers_published: int | None = None,
                std: float = 0.02):
    """normal(``std``) weights, the output projections (``wo`` and every
    down projection) at ``std / sqrt(2 x published depth)``; norms at 1.
    float32."""
    depth = n_layers_published or cfg.num_hidden_layers
    return lm_blocks.init_params(
        cfg.shapes(), rng, std=std, out_std=std / math.sqrt(2.0 * depth),
        output_projections=OUTPUT_PROJECTIONS)


def init_stats(cfg: AfmoeConfig) -> dict:
    """The selection biases at 0."""
    return {k: jnp.zeros(s, jnp.float32)
            for k, s in cfg.stats_shapes().items()}


def attention_tiles(cfg: AfmoeConfig, seq_len: int) -> dict:
    """{``window`` | ``full``: (live tiles, grid tiles a head and a
    sequence, layers of the kind)}, documents aside
    (ops/masked_attention.py)."""
    return {name: (*mask_tiles(_mask(cfg, kind, seq_len)),
                   cfg.layer_types.count(kind))
            for name, kind in _COUNTED}
