"""A block-diffusion mixture-of-experts decoder (SDAR, arXiv:2510.06303;
the block is Qwen3-MoE's), as one chip of an expert-parallel layer holds it.

A layer, on the residual stream ``x [S, 2L, H]``:

    h = RMSNorm(x);  q, k, v = h W_q, h W_k, h W_v        (no bias)
    q, k = RMSNorm over each head's dims, then RoPE at position i mod L
    x += bd_attention(q / sqrt(d), k, v) W_o               (grouped queries)
    h = RMSNorm(x);  x += this share of sum_k p_k e_k(h)   (ops/moe.py)

The layers' weights are stacked on a leading axis and the stack is scanned;
within a layer the step's sequences go one at a time, a ``jax.checkpoint`` a
layer and sequence: a layer's input is all the reverse pass keeps, and what
it rebuilds (a sequence's projections, the rows routed to the experts held)
is one sequence's at a time. Weights are float32 and are cast to the compute
dtype inside the layer; norms, RoPE, the router and the loss are float32.

The vocabulary is a slice too (``vocab_size`` rows are held): ids are drawn
from the slice and the loss is over the slice.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from cgnn_tpu.observe import phases
from cgnn_tpu.ops import moe
from cgnn_tpu.ops.bd_attention import bd_attention, bd_tiles


@dataclasses.dataclass(frozen=True)
class SdarConfig:
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


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return y * scale


def rope(x, positions, theta: float):
    """``x [S, N, heads, D]`` float32, rotate-half as Qwen's."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [N, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _layer(cfg: SdarConfig, x, p, segment_ids):
    """One layer on ``x [S, N, H]`` (compute dtype) -> (x, group_sizes, the
    rung that carried the held experts' rows: ops/moe.py)."""
    dt = cfg.compute_dtype
    s, n, h = x.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    with jax.named_scope(phases.ATTN_PROJ):
        hn = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps).astype(dt)
        q = (hn @ p["wq"].astype(dt)).reshape(s, n, hq, d)
        k = (hn @ p["wk"].astype(dt)).reshape(s, n, hkv, d)
        v = (hn @ p["wv"].astype(dt)).reshape(s, n, hkv, d)
        positions = jnp.arange(n, dtype=jnp.int32) % (n // 2)
        q = rope(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions,
                 cfg.rope_theta) * (1.0 / math.sqrt(d))
        k = rope(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions,
                 cfg.rope_theta)
        q, k, v = (jnp.swapaxes(t.astype(dt), 1, 2) for t in (q, k, v))
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

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def one(row):
            x_seq, seg = row
            out, sizes, rung = _layer(cfg, x_seq[None], p, seg[None])
            return out[0], sizes, rung

        # a scan over the sequences, and it has to stay one: under ``vmap``
        # the expert layer's ``lax.switch`` becomes a select that runs every
        # rung on every sequence
        x, sizes, rungs = jax.lax.map(one, (x, segment_ids))
        return x, (sizes.sum(axis=0), rungs)

    x, (group_sizes, rungs) = jax.lax.scan(step, x, params["layers"])
    return x, group_sizes, rungs


# positions of the noised half whose logits are held at once
HEAD_CHUNK = 1024


def sequence_loss(logits, targets, loss_weight):
    """One sequence's loss: ``logits [L, V]`` float32 at the noised half,
    ``targets [L]`` the clean ids, ``loss_weight [L]`` (``1 / t`` of its
    block where the token was masked, else 0): ``-(1 / L) sum_i w_i log
    p(x_0^i)``."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -(loss_weight * picked).sum() / targets.shape[0]


def noised_loss_sums(cfg: SdarConfig, params, x, batch):
    """Each sequence's loss from the last layer's ``x``: the final norm, the
    head over the vocabulary slice and the loss at the noised half only (the
    clean half is context), ``HEAD_CHUNK`` positions at a time and a
    ``jax.checkpoint`` each: a chunk's ``[HEAD_CHUNK, V]`` float32 logits
    are all that is ever held."""
    length = batch.loss_weight.shape[-1]
    chunk = HEAD_CHUNK if length % HEAD_CHUNK == 0 else length

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one(row):
        x_rows, targets, weight = row
        hn = rms_norm(x_rows, params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(hn.astype(cfg.compute_dtype),
                         params["head"].astype(cfg.compute_dtype),
                         preferred_element_type=jnp.float32)
        return sequence_loss(logits, targets, weight) * (chunk / length)

    def rows(a):
        return a.reshape(-1, chunk, *a.shape[2:])

    losses = jax.lax.map(one, (rows(x[:, :length]),
                               rows(batch.tokens[:, length:]),
                               rows(batch.loss_weight)))
    return losses.reshape(x.shape[0], -1).sum(axis=1)


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
    out_std = std / math.sqrt(2.0 * depth)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        cfg.shapes(), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        scale = out_std if name in ("wo", "w_down") else std
        leaves.append(scale * jax.random.normal(
            jax.random.fold_in(rng, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, leaves)


def attention_tiles(cfg: SdarConfig, seq_len: int) -> tuple[int, int]:
    """(live, grid) tiles a head and a sequence (ops/bd_attention.py)."""
    return bd_tiles(seq_len, cfg.block_length)
