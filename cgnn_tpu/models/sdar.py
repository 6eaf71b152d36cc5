"""A block-diffusion mixture-of-experts decoder (SDAR, arXiv:2510.06303;
the block is Qwen3-MoE's), as one chip of an expert-parallel layer holds it.

A layer, on the residual stream ``x [S, 2L, H]``:

    h = RMSNorm(x);  q, k, v = h W_q, h W_k, h W_v        (no bias)
    q, k = RMSNorm over each head's dims, then RoPE at position i mod L
    x += bd_attention(q / sqrt(d), k, v) W_o               (grouped queries)
    h = RMSNorm(x);  x += this share of sum_k p_k e_k(h)   (ops/moe.py)

The layers' weights are stacked on a leading axis and the stack is scanned;
within a layer the step's sequences go one at a time, a ``jax.checkpoint`` a
layer and sequence that keeps the layer's input and its attention's output
(``by_sequence``; the norm, RoPE and the chunked head are
``models/lm_blocks.py``'s too). Weights are float32 and are cast to the
compute dtype inside the layer; norms, RoPE, the router and the loss are
float32.

The vocabulary is a slice too (``vocab_size`` rows are held): ids are drawn
from the slice and the loss is over the slice.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from cgnn_tpu.models import lm_blocks
from cgnn_tpu.models.lm_blocks import (
    by_sequence, chunked_loss_sums, prepare_heads, rms_norm,
)
from cgnn_tpu.observe import phases
from cgnn_tpu.ops import moe
from cgnn_tpu.ops.bd_attention import bd_attention, bd_live_tiles, bd_tiles


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    # what train/lm_step.py makes of a batch (no field: the model's own)
    objective = "blockdiff"

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_hidden_layers: int = 6
    # the router's outputs (all experts of the layer) and the experts a token
    # takes; ``experts_held`` = (first, count) is this chip's share
    n_experts: int = 128
    num_experts_per_tok: int = 8
    experts_held: tuple = (0, 16)
    moe_intermediate_size: int = 768
    vocab_size: int = 18992
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    dtype: str = "bfloat16"
    attn_impl: str = "auto"
    moe_impl: str = "auto"

    @property
    def mask_id(self) -> int:
        """``[MASK]``: the slice's last id."""
        return self.vocab_size - 1

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    # every layer mixes by attention and prepares q and k (what
    # train/lm_step.py counts by)
    n_conv_layers = n_ssm_layers = 0
    heads_prepared_a_layer = 2

    @property
    def n_attention_layers(self) -> int:
        return self.num_hidden_layers

    def live_tiles(self, segment_ids) -> dict:
        """{``bd``: (the tiles a head visits of each sequence ``[S]``, its
        documents given, the layers)} (ops/bd_attention.py)."""
        return {"bd": (bd_live_tiles(segment_ids, self.block_length),
                       self.num_hidden_layers)}

    def shapes(self) -> dict:
        """The parameter tree's shapes, float32 all."""
        h, d, n = self.hidden_size, self.head_dim, self.num_hidden_layers
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        e, i = self.experts_held[1], self.moe_intermediate_size
        return {
            "embed": (self.vocab_size, h),
            "layers": {
                "attn_norm": (n, h), "wq": (n, h, hq * d),
                "wk": (n, h, hkv * d), "wv": (n, h, hkv * d),
                "q_norm": (n, d), "k_norm": (n, d), "wo": (n, hq * d, h),
                "moe_norm": (n, h), "router": (n, h, self.n_experts),
                "w_gate_up": (n, e, h, 2 * i), "w_down": (n, e, i, h),
            },
            "final_norm": (h,),
            "head": (h, self.vocab_size),
        }

    def n_params(self) -> int:
        return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
            self.shapes(), is_leaf=lambda x: isinstance(x, tuple)))


def _layer(cfg: SdarConfig, x, p, segment_ids):
    """One layer on ``x [S, N, H]`` (compute dtype) -> (x, group_sizes, the
    rung that carried the held experts' rows: ops/moe.py)."""
    dt = cfg.compute_dtype
    s, n, h = x.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    with jax.named_scope(phases.ATTN_PROJ):
        hn = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps).astype(dt)
        positions = jnp.arange(n, dtype=jnp.int32) % (n // 2)
        q = prepare_heads(hn @ p["wq"].astype(dt), p["q_norm"], positions,
                          theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
                          scale=1.0 / math.sqrt(d))
        k = prepare_heads(hn @ p["wk"].astype(dt), p["k_norm"], positions,
                          theta=cfg.rope_theta, eps=cfg.rms_norm_eps)
        v = jnp.swapaxes((hn @ p["wv"].astype(dt)).reshape(s, n, hkv, d),
                         1, 2)
    with jax.named_scope(phases.ATTN_BD):
        a = bd_attention(q, k, v, segment_ids, block=cfg.block_length,
                         impl=cfg.attn_impl)
    with jax.named_scope(phases.ATTN_PROJ):
        a = jnp.swapaxes(a, 1, 2).reshape(s, n, hq * d)
        x = x + (a @ p["wo"].astype(dt))
    with jax.named_scope(phases.MOE_ROUTE):
        hn = rms_norm(x, p["moe_norm"], cfg.rms_norm_eps).astype(dt)
    out, group_sizes, rung = moe.expert_share(
        hn.reshape(s * n, h), p["router"], p["w_gate_up"].astype(dt),
        p["w_down"].astype(dt), experts_held=cfg.experts_held,
        k=cfg.num_experts_per_tok, impl=cfg.moe_impl)
    with jax.named_scope(phases.MOE_ROUTE):
        x = x + out.reshape(s, n, h)
    return x, group_sizes, rung


def hidden_states(cfg: SdarConfig, params, tokens, segment_ids):
    """``tokens [S, 2L]`` (noised, then clean), ``segment_ids [S, L]`` ->
    (the last layer's ``x [S, 2L, H]``, ``group_sizes [layers, E]``, ``rungs
    [layers, S]``: the rung of each layer's and sequence's expert call)."""
    with jax.named_scope(phases.LM_EMBED):
        x = params["embed"][tokens].astype(cfg.compute_dtype)

    def step(x, p):
        # the casts to the compute dtype stay inside the layer: hoisted out
        # of the scan they are a second copy of every layer's weights
        p = jax.lax.optimization_barrier(p)
        x, sizes, rungs = by_sequence(
            lambda x_seq, seg: _layer(cfg, x_seq, p, seg), x, segment_ids)
        return x, (sizes.sum(axis=0), rungs)

    x, (group_sizes, rungs) = jax.lax.scan(step, x, params["layers"])
    return x, group_sizes, rungs


def noised_loss_sums(cfg: SdarConfig, params, x, batch):
    """Each sequence's loss from the last layer's ``x``: the final norm, the
    head over the vocabulary slice and the loss at the noised half only (the
    clean half is context), a chunk of positions at a time
    (``lm_blocks.chunked_loss_sums``). The targets are the clean ids,
    ``loss_weight`` ``1 / t`` of its block where the token was masked."""
    length = batch.loss_weight.shape[-1]
    return chunked_loss_sums(
        x[:, :length], batch.tokens[:, length:], batch.loss_weight,
        params["final_norm"], params["head"], eps=cfg.rms_norm_eps,
        dtype=cfg.compute_dtype)


def apply(cfg: SdarConfig, variables: dict, batch, train: bool = True):
    """The ``apply_fn`` of a ``TrainState``: -> (each sequence's loss ``[S]``
    float32, ``group_sizes [layers, E]``, ``rungs [layers, S]``:
    ``hidden_states``). The logits are an intermediate
    (``noised_loss_sums``): at the vocabulary's width a step's would be the
    largest array of the program."""
    del train  # no dropout, no statistics
    x, group_sizes, rungs = hidden_states(
        cfg, variables["params"], batch.tokens, batch.segment_ids)
    with jax.named_scope(phases.LM_HEAD):
        return noised_loss_sums(cfg, variables["params"], x, batch), \
            group_sizes, rungs


def init_params(cfg: SdarConfig, rng, n_layers_published: int | None = None,
                std: float = 0.02):
    """normal(``std``) weights, the output projections (``wo``, ``w_down``)
    at ``std / sqrt(2 x published depth)``; norms at 1. float32."""
    depth = n_layers_published or cfg.num_hidden_layers
    return lm_blocks.init_params(
        cfg.shapes(), rng, std=std, out_std=std / math.sqrt(2.0 * depth),
        output_projections=("wo", "w_down"))


def attention_tiles(cfg: SdarConfig, seq_len: int) -> tuple[int, int]:
    """(live, grid) tiles a head and a sequence, documents aside
    (ops/bd_attention.py)."""
    return bd_tiles(seq_len, cfg.block_length)
