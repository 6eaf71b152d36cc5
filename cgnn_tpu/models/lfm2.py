"""A hybrid short-convolution / attention mixture-of-experts decoder
(LiquidAI's ``lfm2_moe`` block: LFM2-24B-A2B), as one chip of an
expert-parallel layer holds it.

A layer, on the residual stream ``x [S, L, H]`` (``x0 = embed[tokens]``):

    x += mixer(RMSNorm(x));   x += ffn(RMSNorm(x))      (pre-norm, two norms)
    conv:            B, C, u = h W_in   (no bias, thirds in this order)
                     mixer = (C * conv3(B * u)) W_out
                     (ops/short_conv.py: depthwise, causal, 3 taps, a tap
                     before the sequence or in another document adds 0)
    full_attention:  q, k, v = h W_q, h W_k, h W_v;  q, k = RoPE(RMSNorm over
                     each head's dims); key j is visible to query i iff
                     doc(j) = doc(i) and j <= i
                     mixer = masked_attention(q / sqrt(d), k, v) W_o
                     (grouped queries)
    a leading dense layer: ffn = W_2(silu(h W_1) * (h W_3))
    an expert layer:       ffn = this share of sum_k p_k e_k(h)
                           (ops/moe.py: sigmoid scores, the ``k`` largest of
                           score + bias, p the scores' own over their sum)
    logits = RMSNorm(x) embed^T                        (the head is tied)

The stack is ``lm_blocks.Stack``'s (the leading dense layers, then the
expert layers in periods of ``layer_types``: full, conv, conv, conv as
published; ``lm_blocks.scan_stack``, which models/afmoe.py shares), and here
the layers of a period differ in their mixer, so a run's leaves do
(``periods/run0`` the attention layer's, ``periods/run1`` the three
convolution layers'). Within a layer the step's sequences go one at a time,
a ``jax.checkpoint`` a layer and sequence (``lm_blocks.by_sequence``) that
keeps the layer's input, an attention's output and the routed experts'
output. Weights are float32 and are cast to the compute dtype inside the
layer; norms, RoPE, the router, the convolution's arithmetic and the loss are
float32.

The selection biases are no parameters and nothing moves them (the published
module holds them as a buffer, and the config names no rate of update): they
ride in the state's ``batch_stats`` (``router_bias [periods, layers a
period, E]``) and a step returns them as they were. The vocabulary is a
slice (``vocab_size`` rows are held); the embedding is the head, so its
gradient is the sum of both uses and it is counted and decayed once.
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
from cgnn_tpu.ops.short_conv import TAPS, short_conv

CONV, FULL = lm_blocks.CONV, "full_attention"
# what an expert layer's checkpoint keeps beside its input and its
# attention's output (``lm_blocks.by_sequence``)
ROUTED = "moe.routed"
# leaves initialised at the output projections' scale (``init_params``)
OUTPUT_PROJECTIONS = ("w_out", "wo", "w_down", "mlp_down")


@dataclasses.dataclass(frozen=True)
class Lfm2Config(lm_blocks.Stack):
    # what train/lm_step.py makes of a batch (no field: the model's own)
    objective = "causal"
    # both kinds route after the leading dense layer
    layer_kinds = {CONV: (lm_blocks.CONV, True),
                   FULL: (lm_blocks.ATTENTION, True)}

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    num_hidden_layers: int = 5
    num_dense_layers: int = 1
    layer_types: tuple = (CONV, FULL, CONV, CONV, CONV)
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    # the router's outputs (all experts of the layer) and the experts a token
    # takes; ``experts_held`` = (first, count) is this chip's share
    n_experts: int = 64
    num_experts_per_tok: int = 4
    experts_held: tuple = (0, 8)
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    vocab_size: int = 8192
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
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
        return moe.Router(score_func="sigmoid", norm=self.norm_topk_prob,
                          norm_eps=1e-6, scale=self.routed_scaling_factor)

    def _mixer_shapes(self, kind: str) -> dict:
        h, d = self.hidden_size, self.head_dim
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        norms = {"op_norm": (h,), "ffn_norm": (h,)}
        if kind == CONV:
            return {**norms, "w_in": (h, 3 * h), "conv_w": (h, TAPS),
                    "w_out": (h, h)}
        return {**norms, "wq": (h, hq * d), "wk": (h, hkv * d),
                "wv": (h, hkv * d), "q_norm": (d,), "k_norm": (d,),
                "wo": (hq * d, h)}

    def shapes(self) -> dict:
        """The parameter tree's shapes, float32 all; no ``head``: the
        embedding is the head."""
        h, i = self.hidden_size, self.moe_intermediate_size
        e = self.experts_held[1]
        return {
            "embed": (self.vocab_size, h),
            **lm_blocks.stack_shapes(
                self,
                lambda kind: {
                    **self._mixer_shapes(kind),
                    "mlp_gate_up": (h, 2 * self.intermediate_size),
                    "mlp_down": (self.intermediate_size, h)},
                lambda kind: {
                    **self._mixer_shapes(kind), "router": (h, self.n_experts),
                    "w_gate_up": (e, h, 2 * i), "w_down": (e, i, h)}),
            "final_norm": (h,),
        }

    def stats_shapes(self) -> dict:
        """``batch_stats``: the selection biases, float32."""
        return {"router_bias": (self.n_periods, len(self.period),
                                self.n_experts)}

    def live_tiles(self, segment_ids) -> dict:
        """{``full``: (the tiles a head visits of each sequence ``[S]``, its
        documents given, the attention layers)} (ops/masked_attention.py)."""
        return {"full": (live_tiles(_mask(segment_ids.shape[-1]), segment_ids),
                         self.n_attention_layers)}


def _mask(n: int) -> StaticMask:
    return StaticMask("causal", n)


def _conv_mixer(cfg: Lfm2Config, x, p, segment_ids):
    """``x += (C * conv3(B * u)) W_out`` on ``x [S, N, H]``."""
    dt = cfg.compute_dtype
    with jax.named_scope(phases.SCONV_PROJ):
        hn = rms_norm(x, p["op_norm"], cfg.norm_eps).astype(dt)
        bcu = hn @ p["w_in"].astype(dt)
    with jax.named_scope(phases.SCONV_MIX):
        y = short_conv(bcu, p["conv_w"], segment_ids)
    with jax.named_scope(phases.SCONV_PROJ):
        return x + y @ p["w_out"].astype(dt)


def _attention_mixer(cfg: Lfm2Config, x, p, segment_ids):
    """``x += attention W_o`` on ``x [S, N, H]``."""
    dt = cfg.compute_dtype
    s, n, _ = x.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    eps = cfg.norm_eps
    with jax.named_scope(phases.ATTN_PROJ):
        hn = rms_norm(x, p["op_norm"], eps).astype(dt)
        positions = jnp.arange(n, dtype=jnp.int32)
        q = prepare_heads(hn @ p["wq"].astype(dt), p["q_norm"], positions,
                          theta=cfg.rope_theta, eps=eps,
                          scale=1.0 / math.sqrt(d))
        k = prepare_heads(hn @ p["wk"].astype(dt), p["k_norm"], positions,
                          theta=cfg.rope_theta, eps=eps)
        v = jnp.swapaxes((hn @ p["wv"].astype(dt)).reshape(s, n, hkv, d),
                         1, 2)
    with jax.named_scope(phases.ATTN_FULL):
        a = masked_attention(q, k, v, segment_ids, _mask(n),
                             impl=cfg.attn_impl)
    with jax.named_scope(phases.ATTN_PROJ):
        a = jnp.swapaxes(a, 1, 2).reshape(s, n, hq * d)
        return x + a @ p["wo"].astype(dt)


def _mixer(cfg: Lfm2Config, kind: str, x, p, segment_ids):
    mix = _conv_mixer if kind == CONV else _attention_mixer
    return mix(cfg, x, p, segment_ids)


def _dense_layer(cfg: Lfm2Config, kind: str, x, p, segment_ids):
    """A leading dense layer -> ``(x,)``."""
    dt = cfg.compute_dtype
    x = _mixer(cfg, kind, x, p, segment_ids)
    with jax.named_scope(phases.MLP_DENSE):
        hn = rms_norm(x, p["ffn_norm"], cfg.norm_eps).astype(dt)
        m = moe.swiglu(hn @ p["mlp_gate_up"].astype(dt)) \
            @ p["mlp_down"].astype(dt)
        return (x + m,)


def _expert_layer(cfg: Lfm2Config, kind: str, x, p, bias, segment_ids):
    """An expert layer -> (x, group_sizes, the rung that carried the held
    experts' rows: ops/moe.py)."""
    dt = cfg.compute_dtype
    s, n, h = x.shape
    x = _mixer(cfg, kind, x, p, segment_ids)
    # the layer's second norm and the residual sum are the rows' way out
    # and back: ``moe.route``
    with jax.named_scope(phases.MOE_ROUTE):
        hn = rms_norm(x, p["ffn_norm"], cfg.norm_eps).astype(dt)
    with jax.named_scope(phases.MOE_EXPERT):
        w_gate_up, w_down = p["w_gate_up"].astype(dt), p["w_down"].astype(dt)
    routed, group_sizes, rung = moe.expert_share(
        hn.reshape(s * n, h), p["router"], w_gate_up, w_down,
        experts_held=cfg.experts_held, k=cfg.num_experts_per_tok,
        impl=cfg.moe_impl, routing=cfg.routing, bias=bias)
    routed = checkpoint_name(routed, ROUTED)
    with jax.named_scope(phases.MOE_ROUTE):
        return x + routed.reshape(s, n, h), group_sizes, rung


def hidden_states(cfg: Lfm2Config, params, router_bias, tokens,
                  segment_ids):
    """``tokens, segment_ids [S, L]`` -> (the last layer's ``x [S, L, H]``,
    ``group_sizes [expert layers, E]``, ``rungs [expert layers, S]``: the
    rung of each expert layer's and sequence's call)."""
    with jax.named_scope(phases.LM_EMBED):
        x = params["embed"][tokens].astype(cfg.compute_dtype)

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


def apply(cfg: Lfm2Config, variables: dict, batch, train: bool = True):
    """The ``apply_fn`` of a ``TrainState``: -> (each sequence's loss ``[S]``
    float32, ``group_sizes [expert layers, E]``, ``rungs [expert layers,
    S]``: ``hidden_states``). Position ``i`` predicts token ``i + 1`` where
    ``batch.loss_weight`` says so (data/tokens.py, ``causal``); the head is
    the embedding's transpose, and the logits are an intermediate
    (``lm_blocks.chunked_loss_sums``)."""
    del train  # no dropout, and no state that a step moves
    params = variables["params"]
    x, group_sizes, rungs = hidden_states(
        cfg, params, variables["batch_stats"]["router_bias"], batch.tokens,
        batch.segment_ids)
    with jax.named_scope(phases.LM_HEAD):
        # the last position's target is no token: its weight is 0
        targets = jnp.roll(batch.tokens, -1, axis=1)
        losses = chunked_loss_sums(
            x, targets, batch.loss_weight, params["final_norm"],
            params["embed"].T, eps=cfg.norm_eps, dtype=cfg.compute_dtype)
    return losses, group_sizes, rungs


def init_params(cfg: Lfm2Config, rng, n_layers_published: int | None = None,
                std: float = 0.02):
    """normal(``std``) weights, the output projections (``w_out``, ``wo`` and
    every down projection) at ``std / sqrt(2 x published depth)``; norms at
    1. float32."""
    depth = n_layers_published or cfg.num_hidden_layers
    return lm_blocks.init_params(
        cfg.shapes(), rng, std=std, out_std=std / math.sqrt(2.0 * depth),
        output_projections=OUTPUT_PROJECTIONS)


def init_stats(cfg: Lfm2Config) -> dict:
    """The selection biases at 0 (a trained model brings its own; nothing
    here moves them)."""
    return {k: jnp.zeros(s, jnp.float32)
            for k, s in cfg.stats_shapes().items()}


def attention_tiles(cfg: Lfm2Config, seq_len: int) -> dict:
    """{``full``: (live tiles, grid tiles a head and a sequence, attention
    layers)}, documents aside (ops/masked_attention.py)."""
    return {"full": (*mask_tiles(_mask(seq_len)), cfg.n_attention_layers)}
