"""One chip's share of an expert-parallel mixture-of-experts layer.

The layer is told which experts it holds (``first``, ``count``). It routes
every token over ALL ``n_experts`` (softmax over the router's logits, the
``k`` largest kept and renormalised to sum 1), computes the experts it holds
on the rows routed to them, and adds nothing for the absent ones: what comes
out is this chip's part of the layer's result. On one chip there is no
exchange, and nothing here stands in for one.

Dropless, with static shapes: all ``T x k`` (token, expert) rows are sorted
by expert; the grouped matmul is handed the sizes of all ``n_experts`` groups
and the offset of the first one held, and visits the rows of the held groups
only (``megablox`` on the TPU; ``lax.ragged_dot`` elsewhere). No row is
dropped whatever the load, and no count is read on the host.

Rows travel by permutation, never by scatter: the sort order ``order`` sends
a token's row out (``x[order // k]``) and its inverse brings an expert's
output back, so each gather's transpose is the other permutation's gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cgnn_tpu.observe import phases


def route(logits, k: int):
    """Router logits ``[T, E]`` float32 -> (``weights [T, k]`` renormalised
    to sum 1, ``experts [T, k]`` int32), over all ``E``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(probs, k)
    return top / top.sum(axis=-1, keepdims=True), experts.astype(jnp.int32)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` of the rows (``inverse`` its
    inverse): the transpose is ``g[inverse]``, a gather too."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _send(x, order, inverse, k: int):
    """A token's row to each of its ``k`` places in the sorted order: row
    ``r`` is token ``order[r] // k``. The transpose brings the ``k`` rows
    back by the inverse permutation and adds them."""
    return x[order // k]


def _send_fwd(x, order, inverse, k):
    return x[order // k], (inverse, x.shape[0])


def _send_bwd(k, res, g):
    inverse, t = res
    back = g[inverse].reshape(t, k, g.shape[-1])
    return back.sum(axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_send.defvjp(_send_fwd, _send_bwd)


@jax.custom_vjp
def swiglu(gate_up):
    """``silu(g) * u`` of ``[g | u]`` along the last axis, computed in
    float32 and returned in the input's dtype. The reverse pass keeps the
    input alone and works the activation out again: no float32 copy of a
    ``T x k``-row array outlives its fusion."""
    return _swiglu(gate_up)


def _swiglu(gate_up):
    inter = gate_up.shape[-1] // 2
    g = gate_up[..., :inter].astype(jnp.float32)
    u = gate_up[..., inter:].astype(jnp.float32)
    return (jax.nn.silu(g) * u).astype(gate_up.dtype)


def _swiglu_fwd(gate_up):
    return _swiglu(gate_up), gate_up


def _swiglu_bwd(gate_up, d):
    inter = gate_up.shape[-1] // 2
    g = gate_up[..., :inter].astype(jnp.float32)
    u = gate_up[..., inter:].astype(jnp.float32)
    d = d.astype(jnp.float32)
    s = jax.nn.sigmoid(g)
    dg = d * u * s * (1.0 + g * (1.0 - s))
    du = d * g * s
    return (jnp.concatenate([dg, du], axis=-1).astype(gate_up.dtype),)


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _tile_of(n: int) -> int:
    for t in (1024, 768, 512, 256, 128):
        if n % t == 0:
            return t
    return n


def grouped_matmul(lhs, rhs, group_sizes, first: int, *, impl: str):
    """``lhs [R, K]`` rows sorted by group, ``rhs [count, K, N]`` the held
    groups' matrices, ``group_sizes [E]`` of ALL groups: rows of group
    ``first + g`` times ``rhs[g]``. Rows of groups not held come back as
    whatever the kernel left there: the caller masks them."""
    if impl == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        tiling = (512, _tile_of(lhs.shape[1]), _tile_of(rhs.shape[2]))
        return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling,
                            jnp.asarray(first, jnp.int32))
    if impl == "ragged":
        # every group a matrix, the absent ones zero: the plain form
        full = jnp.zeros((group_sizes.shape[0], *rhs.shape[1:]), rhs.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(full, rhs, first, axis=0)
        return jax.lax.ragged_dot(
            lhs, full, group_sizes,
            preferred_element_type=jnp.float32).astype(lhs.dtype)
    raise ValueError(f"no grouped matmul {impl!r}")


def expert_share(x, router, w_gate_up, w_down, *, experts_held: tuple,
                 k: int, impl: str = "auto"):
    """``x [T, H]`` (normed hidden states) -> (this share's part of the
    layer's output ``[T, H]`` float32, ``group_sizes [E]`` int32: the rows
    each of ALL experts was routed).

    ``router [H, E]`` float32; ``w_gate_up [count, H, 2I]`` and ``w_down
    [count, I, H]`` in the compute dtype, the experts ``first .. first +
    count - 1``: ``e(h) = (silu(h W_g) * (h W_u)) W_d``.
    """
    first, count = experts_held
    t, h = x.shape
    n_experts = router.shape[1]
    if impl == "auto":
        impl = "megablox" if jax.default_backend() == "tpu" else "ragged"
    with jax.named_scope(phases.MOE_ROUTE):
        logits = jnp.dot(x.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        weights, experts = route(logits, k)
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        group_sizes = (flat[:, None] == jnp.arange(
            n_experts, dtype=jnp.int32)).sum(axis=0, dtype=jnp.int32)
        sorted_expert = flat[order]
        here = ((sorted_expert >= first)
                & (sorted_expert < first + count))[:, None]
        rows = jnp.where(here, _send(x, order, inverse, k), 0)
    with jax.named_scope(phases.MOE_EXPERT):
        # rows of absent experts hold whatever the kernel's buffer held
        gu = jnp.where(here, grouped_matmul(rows, w_gate_up, group_sizes,
                                            first, impl=impl), 0)
        y = grouped_matmul(swiglu(gu), w_down, group_sizes, first,
                           impl=impl)
    with jax.named_scope(phases.MOE_ROUTE):
        y = jnp.where(here, y, 0)
        back = _permute(y, inverse, order).reshape(t, k, h)
        # the weights join the rows in the compute dtype; the sum over a
        # token's k rows accumulates in float32
        out = (back * weights.astype(back.dtype)[:, :, None]).sum(
            axis=1, dtype=jnp.float32)
    return out, group_sizes
