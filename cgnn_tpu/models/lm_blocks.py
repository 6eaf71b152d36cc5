"""What the decoders of this repo share (models/sdar.py, models/afmoe.py):
the float32 norm and RoPE, the one op that makes a projection's output the
attention's operand, the initialiser, the plan that keeps a layer's input
and its attention's output for the reverse pass, and the head over the
vocabulary slice a chunk at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cgnn_tpu.ops import prepare_heads as fused
from cgnn_tpu.ops.masked_attention import KEPT


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return y * scale


def rope(x, positions, theta: float):
    """``x [S, N, heads, D]`` float32, rotate-half as Qwen's."""
    d = x.shape[-1]
    cos, sin = fused.rope_tables(positions, d, theta)  # [N, D/2]
    cos = jnp.concatenate([cos, cos], -1)[None, :, None]
    sin = jnp.concatenate([sin, sin], -1)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def heads_fused(n: int, head_dim: int) -> bool:
    """Whether ``prepare_heads`` takes the kernel on ``n`` positions of
    ``head_dim``: on the TPU, at the shapes the kernel takes."""
    return jax.default_backend() == "tpu" and fused.supported(n, head_dim)


def prepare_heads(x, norm_scale, positions, *, theta: float, eps: float,
                  scale: float = 1.0):
    """A projection's output -> the attention's operand: ``x [S, N, heads *
    D]`` in the compute dtype, ``norm_scale [D]``, ``positions [N]`` int32
    or None (a layer that takes no positions) -> ``rope(rms_norm(x)) scale``
    as ``[S, heads, N, D]`` in the compute dtype. float32 arithmetic; the
    array is rounded where the matmul left it and where the attention takes
    it. One pass on the chip (``ops/prepare_heads.py``) where
    ``heads_fused`` says so, the backend and the shape deciding as in
    ``masked_attention``'s ``auto``; else the composition below, which is
    the kernel's reference."""
    s, n, width = x.shape
    d = norm_scale.shape[-1]
    if heads_fused(n, d):
        return fused.prepare_heads(x, norm_scale, positions, theta, eps,
                                   scale)
    y = rms_norm(x.reshape(s, n, width // d, d), norm_scale, eps)
    if positions is not None:
        y = rope(y, positions, theta)
    return jnp.swapaxes((y * scale).astype(x.dtype), 1, 2)


def init_params(shapes: dict, rng, *, std: float, out_std: float,
                output_projections: tuple):
    """float32 leaves for a tree of shapes: normal(``std``), the leaves named
    in ``output_projections`` at ``out_std``; every ``*norm`` at 1."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        scale = out_std if name in output_projections else std
        leaves.append(scale * jax.random.normal(
            jax.random.fold_in(rng, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, leaves)


def by_sequence(layer, x, segment_ids, keep=()):
    """One layer over the step's sequences one at a time, a
    ``jax.checkpoint`` a layer and sequence: the reverse pass keeps a
    layer's input and what the attention's reverse kernels read of its
    forward one (``ops/masked_attention.py`` ``KEPT``: the output and the
    log-sum-exp, so the forward kernel runs once), and what it rebuilds (a
    sequence's projections, the rows routed to the experts held) is one
    sequence's at a time. ``layer(x [1, N, H], segment_ids [1, ..]) -> (x
    [1, N, H], *aux)``; -> ``(x [S, N, H], *aux stacked over the
    sequences)``. ``keep``: the names (``checkpoint_name``) of what else of
    its own a model has the checkpoint keep.

    A scan over the sequences, and it has to stay one: under ``vmap`` the
    expert layer's ``lax.switch`` becomes a select that runs every rung on
    every sequence."""
    policy = jax.checkpoint_policies.save_only_these_names(KEPT, *keep)

    @functools.partial(jax.checkpoint, prevent_cse=False, policy=policy)
    def one(row):
        x_seq, seg = row
        out, *aux = layer(x_seq[None], seg[None])
        return (out[0], *aux)

    return jax.lax.map(one, (x, segment_ids))


# positions whose logits are held at once
HEAD_CHUNK = 1024


def sequence_loss(logits, targets, loss_weight):
    """One sequence's loss: ``logits [L, V]`` float32, ``targets [L]`` the
    ids to be predicted, ``loss_weight [L]``: ``-(1 / L) sum_i w_i log
    p(target_i)``."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -(loss_weight * picked).sum() / targets.shape[0]


def chunked_loss_sums(x, targets, loss_weight, final_norm, head, *,
                      eps: float, dtype):
    """Each sequence's loss from the last layer's ``x [S, L, H]`` at the
    positions that predict (``targets``, ``loss_weight [S, L]``): the final
    norm, the head over the vocabulary slice and ``sequence_loss``,
    ``HEAD_CHUNK`` positions at a time and a ``jax.checkpoint`` each: a
    chunk's ``[HEAD_CHUNK, V]`` float32 logits are all that is ever held."""
    length = loss_weight.shape[-1]
    chunk = HEAD_CHUNK if length % HEAD_CHUNK == 0 else length

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one(row):
        x_rows, tgt, weight = row
        hn = rms_norm(x_rows, final_norm, eps)
        logits = jnp.dot(hn.astype(dtype), head.astype(dtype),
                         preferred_element_type=jnp.float32)
        return sequence_loss(logits, tgt, weight) * (chunk / length)

    def rows(a):
        return a.reshape(-1, chunk, *a.shape[2:])

    losses = jax.lax.map(one, (rows(x), rows(targets), rows(loss_weight)))
    return losses.reshape(x.shape[0], -1).sum(axis=1)
