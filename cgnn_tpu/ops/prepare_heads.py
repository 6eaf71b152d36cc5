"""A projection's output made the attention kernel's operand in one pass.

``models/lm_blocks.py`` ``prepare_heads`` is the op; this is its TPU form.
``x [S, N, heads * D]`` in the compute dtype, as the matmul wrote it, becomes
``[S, heads, N, D]`` in the compute dtype, as ``ops/masked_attention.py``
reads it: the RMS norm over a head's ``D`` lanes, rotate-half RoPE (or none),
a scale, and the heads moved to the front, with float32 arithmetic inside and
no float32 array outside. A block is ``rows`` positions of every head: read
at ``(s, i, :)``, written at ``(s, :, i)``, so the transposition is the
output's index map and a head's 128 lanes never leave their tile; the norm's
mean is a lane reduction, rotate-half a roll by ``D / 2`` lanes against a
sine whose first half carries the sign.

The reverse pass is a kernel of the same shape (``jax.custom_vjp``): it reads
the cotangent ``[S, heads, N, D]`` and ``x`` (the only residual, in the
compute dtype) and writes ``x``'s cotangent in the compute dtype and the norm
scale's as float32 partial sums a block, summed outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cgnn_tpu.observe import phases

LANES = 128
# a block's input bytes (as many again go out): large enough that a grid
# step's fixed cost is nothing beside its transfer, small enough that the
# reverse kernel's three double-buffered blocks fit the 16 MiB of fast
# memory a kernel is given unasked. Asking for more (``vmem_limit_bytes``)
# is paid by the neighbours: with 64 MiB here the compiler no longer kept
# the splash kernels' mask tables, k and v in the fast space (PERF.md
# section 6, PR 47)
_BLOCK_BYTES = 1 << 21
_MIN_ROWS, _MAX_ROWS = 16, 1024


def rope_tables(positions, d: int, theta: float):
    """``positions [N]`` -> (``cos``, ``sin``) ``[N, D / 2]`` float32, Qwen's
    rotate-half angles."""
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def supported(n: int, d: int) -> bool:
    """The shapes the kernels take: whole 128-lane tiles a head, rows in
    whole sublane tiles of either dtype."""
    return d % LANES == 0 and n % _MIN_ROWS == 0


def _block_rows(n: int, row_bytes: int) -> int:
    rows = min(_MAX_ROWS, max(_MIN_ROWS, _BLOCK_BYTES // row_bytes))
    rows = 1 << (rows.bit_length() - 1)
    while n % rows:
        rows //= 2
    return rows


def _tables(positions, d: int, theta: float):
    """(``cos``, ``sin``) ``[N, D]`` float32 for the kernels: both halves,
    the sine's first half negated, so that ``rope(y) = y cos + roll(y, D / 2)
    sin``."""
    cos, sin = rope_tables(positions, d, theta)
    return (jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1))


def _normed(x_ref, h: int, d: int, eps: float):
    """Head ``h`` of the block -> (``x r``, ``r``) float32."""
    x = x_ref[:, h * d:(h + 1) * d].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _fwd_kernel(*refs, heads: int, d: int, eps: float, scale: float,
                rotate: bool):
    if rotate:
        x_ref, w_ref, cos_ref, sin_ref, o_ref = refs
    else:
        x_ref, w_ref, o_ref = refs
    w = w_ref[...]
    for h in range(heads):
        y = _normed(x_ref, h, d, eps)[0] * w
        if rotate:
            y = y * cos_ref[...] + pltpu.roll(y, d // 2, 1) * sin_ref[...]
        o_ref[h] = (y * scale).astype(o_ref.dtype)


def _bwd_kernel(*refs, heads: int, d: int, eps: float, scale: float,
                rotate: bool):
    if rotate:
        g_ref, x_ref, w_ref, cos_ref, sin_ref, dx_ref, dw_ref = refs
    else:
        g_ref, x_ref, w_ref, dx_ref, dw_ref = refs
    w = w_ref[...]
    dw = jnp.zeros_like(w)
    for h in range(heads):
        xn, r = _normed(x_ref, h, d, eps)
        g = g_ref[h].astype(jnp.float32) * scale
        if rotate:  # the roll by D / 2 of D lanes is its own transpose
            g = g * cos_ref[...] + pltpu.roll(g * sin_ref[...], d // 2, 1)
        dw = dw + jnp.sum(g * xn, axis=0, keepdims=True)
        g = g * w
        dx = r * (g - xn * jnp.mean(g * xn, axis=-1, keepdims=True))
        dx_ref[:, h * d:(h + 1) * d] = dx.astype(dx_ref.dtype)
    dw_ref[...] = dw


def _plan(x, norm_scale, positions, theta: float):
    """What the two kernels share: their static arguments, the grid,
    ``x``'s block and the operand's, and the operands both read after
    their own (the norm scale, the tables where there are positions) with
    their blocks."""
    s, n, width = x.shape
    d = norm_scale.shape[-1]
    rows = _block_rows(n, width * x.dtype.itemsize)
    flat = pl.BlockSpec((None, rows, width), lambda b, i: (b, i, 0))
    by_head = pl.BlockSpec((None, width // d, rows, d),
                           lambda b, i: (b, 0, i, 0))
    shared = [norm_scale.astype(jnp.float32).reshape(1, d)]
    specs = [pl.BlockSpec((1, d), lambda b, i: (0, 0))]
    if positions is not None:
        shared += _tables(positions, d, theta)
        specs += [pl.BlockSpec((rows, d), lambda b, i: (i, 0))] * 2
    static = dict(heads=width // d, d=d, rotate=positions is not None)
    return static, (s, n // rows), flat, by_head, shared, specs


def _call(kernel, name: str, grid, operands, in_specs, out_shape, out_specs):
    arrays = [*operands, *jax.tree_util.tree_leaves(out_shape)]
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=16 * operands[0].size,
            transcendentals=operands[0].size // LANES,
            bytes_accessed=sum(a.size * a.dtype.itemsize for a in arrays)),
    )(*operands)


def _forward(x, norm_scale, positions, theta, eps, scale):
    with jax.named_scope(phases.ATTN_PROJ):
        static, grid, flat, by_head, shared, specs = _plan(
            x, norm_scale, positions, theta)
        s, n, _ = x.shape
        return _call(
            functools.partial(_fwd_kernel, eps=eps, scale=scale, **static),
            "prepare_heads_fwd", grid, [x, *shared], [flat, *specs],
            jax.ShapeDtypeStruct((s, static["heads"], n, static["d"]),
                                 x.dtype), by_head)


def _reverse(g, x, norm_scale, positions, theta, eps, scale):
    with jax.named_scope(phases.ATTN_PROJ):
        static, grid, flat, by_head, shared, specs = _plan(
            x, norm_scale, positions, theta)
        d = static["d"]
        dx, dw = _call(
            functools.partial(_bwd_kernel, eps=eps, scale=scale, **static),
            "prepare_heads_bwd", grid, [g, x, *shared],
            [by_head, flat, *specs],
            (jax.ShapeDtypeStruct(x.shape, x.dtype),
             jax.ShapeDtypeStruct((*grid, 1, d), jnp.float32)),
            (flat, pl.BlockSpec((None, None, 1, d),
                                lambda b, i: (b, i, 0, 0))))
        return dx, dw.sum(axis=(0, 1, 2)).astype(norm_scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def prepare_heads(x, norm_scale, positions, theta: float, eps: float,
                  scale: float):
    """``x [S, N, heads * D]``, ``norm_scale [D]``, ``positions [N]`` int32
    or None (no rotation) -> ``[S, heads, N, D]`` in ``x``'s dtype:
    ``rope(rms_norm(x) norm_scale) scale``, head-major. ``supported`` says
    which shapes."""
    return _forward(x, norm_scale, positions, theta, eps, scale)


def _vjp_fwd(x, norm_scale, positions, theta, eps, scale):
    return (_forward(x, norm_scale, positions, theta, eps, scale),
            (x, norm_scale, positions))


def _vjp_bwd(theta, eps, scale, residuals, g):
    return (*_reverse(g, *residuals, theta, eps, scale), None)


prepare_heads.defvjp(_vjp_fwd, _vjp_bwd)
