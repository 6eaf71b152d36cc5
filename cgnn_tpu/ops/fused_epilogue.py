"""Fused conv epilogue: masked-BN normalize + gate + edge-mask + sum-over-M.

PERF.md §4b scoped this as the top remaining structural lever: elementwise/
BN loop fusions are 3.12 ms of the 8.59 ms flagship step (36%), spread over
~6 passes of the [N, M, 2F] activation in forward + backward. This module
collapses the BN1-apply -> sigmoid*softplus gate -> edge-mask -> sum-over-M
chain of CGConv's dense branch (models/cgcnn.py) into a hand-scheduled
custom-VJP with a minimal-pass structure:

  forward:  stats (1 read of z)  +  apply (1 read of z, write [N, F])
  backward: reductions (1 read)  +  dz (1 read, write [N, M, 2F])

with residuals of only (mean, rstd) [2F] — the autodiff graph otherwise
saves or rematerializes the [N, M, *] intermediates (xhat, gate, msg) with
extra full passes.

Two implementations behind one flag:

- ``impl='xla'``: plain jnp with the same pass structure — measures how much
  of the win is STRUCTURE (fewer conceptual passes for XLA to fuse).
- ``impl='pallas'``: the apply/reduction/dz passes as Pallas TPU kernels
  with explicit [BN, M, 2F] VMEM blocking — measures what hand scheduling
  adds on top.

MEASURED VERDICT (round 4, real v5e, same-process interleaved rounds at
the bench workload — PERF.md §6b): BOTH impls are ~5-20% SLOWER than the
unfused chain (unfused 33.7-39.9k structs/s vs fused-xla 32.4-32.6k vs
fused-pallas 32.0-32.8k). The custom-VJP boundary forfeits XLA's
producer/consumer fusion: unfused, the normalize+gate+sum chain fuses
into the fc_full matmul epilogue and dz into the matmul backwards, so z
and dz never round-trip HBM as standalone tensors — exactly the passes
this op "saves" were not being paid. Same verdict class as the r3 gather
kernels (§3b). The module stays as a correct, tested scaffold behind
--fused-epilogue; the default path remains unfused.

Numerical contract: identical to MaskedBatchNorm(one-pass f32 stats) +
split + sigmoid*softplus + mask + sum, to f32 roundoff (tests/test_ops.py).
NOT used by the force task (its trunk is BatchNorm-free) — this custom_vjp
is first-order only, which regression/classification training is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# apply/dz kernels block the node axis at this many rows; node capacities
# are 8-aligned, not 128-aligned, so kernels row-mask the tail block.
# On the v5e (jax 0.9.0) this compiles for bf16 only: 256 f32 rows of
# [M=12, 2F=128] ask the dz kernel for 17.15 MB of the 16 MB scoped VMEM,
# and at 128 rows the bf16 kernel returns NaN (PERF.md, bring-up)
_BLOCK_N = 256


def _masked_stats(z: jax.Array, mask: jax.Array):
    """Shifted one-pass masked moments over the (N, M) axes -> f32.

    Same estimator as ops/norm.py MaskedBatchNorm's f32 path (including the
    leading-row shift that kills E[x^2]-E[x]^2 cancellation); kept in jnp —
    a single fused multiply-reduce read of z is already roofline-bound.
    """
    zf = z.astype(jnp.float32)
    shift = jax.lax.stop_gradient(zf[:1].mean(axis=(0, 1)))
    zs = zf - shift
    m = mask.astype(jnp.float32)
    n_real = m.sum()
    zm = zs * m[..., None]
    s1 = zm.sum(axis=(0, 1))
    s2 = (zm * zs).sum(axis=(0, 1))
    n = jnp.maximum(n_real, 1.0)
    mean_s = s1 / n
    var = jnp.maximum(s2 / n - mean_s * mean_s, 0.0)
    return mean_s + shift, var, n_real


def _gate(y: jax.Array, mask: jax.Array):
    f = y.shape[-1] // 2
    sg = jax.nn.sigmoid(y[..., :f])
    sp = jax.nn.softplus(y[..., f:])
    return sg * sp * mask[..., None]


# ---------------------------------------------------------------------------
# impl='xla': hand-structured passes, XLA does the in-pass fusion
# ---------------------------------------------------------------------------


def _apply_xla(z, mask, mean, rstd, scale, bias):
    zf = z.astype(jnp.float32)
    y = (zf - mean) * (rstd * scale) + bias
    return _gate(y, mask.astype(jnp.float32)).sum(axis=1)


def _bwd_xla(z, mask, mean, rstd, scale, bias, n_real, ct_agg):
    zf = z.astype(jnp.float32)
    xhat = (zf - mean) * rstd
    # single definition of the gate gradient, shared with the Pallas
    # kernels (_gate_grad) so the two impls cannot silently diverge
    g = _gate_grad(
        xhat * scale + bias, mask.astype(jnp.float32), ct_agg
    )
    d_bias = g.sum(axis=(0, 1))
    d_scale = (g * xhat).sum(axis=(0, 1))
    dxhat = g * scale
    c = jnp.maximum(n_real, 1.0)
    mean_dxhat = dxhat.sum(axis=(0, 1)) / c
    mean_dxhat_xhat = (dxhat * xhat).sum(axis=(0, 1)) / c
    mf = mask.astype(jnp.float32)[..., None]
    dz = rstd * (dxhat - mf * (mean_dxhat + xhat * mean_dxhat_xhat))
    return dz.astype(z.dtype), d_scale, d_bias


# ---------------------------------------------------------------------------
# impl='pallas': explicit VMEM blocking over the node axis
# ---------------------------------------------------------------------------


def _row_keep(i, bn, n, m):
    """[bn, m] f32: 1 where global row i*bn+r < n (tail-block masking).

    ``n`` is the STATIC node capacity (baked at trace time); out-of-range
    rows of the final grid block read padded garbage that must not reach
    the masked sums."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, m), 0) + i * bn
    return (rows < n).astype(jnp.float32)


def _gate_grad(y, mask, ct):
    """dL/dy [BN, M, 2F] from ct [BN, F] through sigmoid*softplus*mask."""
    f = y.shape[-1] // 2
    sg = jax.nn.sigmoid(y[..., :f])
    spg = jax.nn.sigmoid(y[..., f:])  # softplus' = sigmoid
    sp = jax.nn.softplus(y[..., f:])
    dmsg = ct[:, None, :] * mask[..., None]
    return jnp.concatenate(
        [dmsg * sg * (1.0 - sg) * sp, dmsg * sg * spg], axis=-1
    )


def _apply_kernel(z_ref, mask_ref, cst_ref, agg_ref, *, n):
    pid = pl.program_id(0)
    z = z_ref[...].astype(jnp.float32)  # [BN, M, 2F]
    mean, rstd, scale, bias = (cst_ref[k] for k in range(4))
    y = (z - mean) * (rstd * scale) + bias
    keep = _row_keep(pid, z.shape[0], n, z.shape[1])
    msg = _gate(y, mask_ref[...] * keep)
    agg_ref[...] = msg.sum(axis=1)


def _reduce_kernel(z_ref, mask_ref, cst_ref, ct_ref, out_ref, *, n):
    pid = pl.program_id(0)
    z = z_ref[...].astype(jnp.float32)
    mean, rstd, scale, bias = (cst_ref[k] for k in range(4))
    keep = _row_keep(pid, z.shape[0], n, z.shape[1])
    mask = mask_ref[...] * keep
    xhat = (z - mean) * rstd
    g = _gate_grad(xhat * scale + bias, mask, ct_ref[...])
    dxhat = g * scale
    part = jnp.stack([
        g.sum(axis=(0, 1)),               # d_bias
        (g * xhat).sum(axis=(0, 1)),      # d_scale
        dxhat.sum(axis=(0, 1)),           # sum dxhat
        (dxhat * xhat).sum(axis=(0, 1)),  # sum dxhat*xhat
    ])

    @pl.when(pid == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part


def _dz_kernel(z_ref, mask_ref, cst_ref, red_ref, ct_ref, dz_ref, *, n):
    pid = pl.program_id(0)
    z = z_ref[...].astype(jnp.float32)
    mean, rstd, scale, bias = (cst_ref[k] for k in range(4))
    keep = _row_keep(pid, z.shape[0], n, z.shape[1])
    mask = mask_ref[...] * keep
    xhat = (z - mean) * rstd
    g = _gate_grad(xhat * scale + bias, mask, ct_ref[...])
    dxhat = g * scale
    mean_dxhat = red_ref[2] * red_ref[4, 0]       # x 1/C, precomputed
    mean_dxhat_xhat = red_ref[3] * red_ref[4, 0]
    dz = rstd * (
        dxhat - mask[..., None] * (mean_dxhat + xhat * mean_dxhat_xhat)
    )
    dz_ref[...] = dz.astype(dz_ref.dtype)


def _pallas_apply(z, mask, mean, rstd, scale, bias):
    n, m, c2 = z.shape
    bn = min(_BLOCK_N, n)
    grid = (pl.cdiv(n, bn),)
    cst = jnp.stack([mean, rstd, scale, bias])
    return pl.pallas_call(
        functools.partial(_apply_kernel, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, m, c2), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, m), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, c2 // 2), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, c2 // 2), jnp.float32),
    )(z, mask.astype(jnp.float32), cst)


def _pallas_bwd(z, mask, mean, rstd, scale, bias, n_real, ct_agg):
    n, m, c2 = z.shape
    bn = min(_BLOCK_N, n)
    grid = (pl.cdiv(n, bn),)
    cst = jnp.stack([mean, rstd, scale, bias])
    mask_f = mask.astype(jnp.float32)
    ct = ct_agg.astype(jnp.float32)

    red = pl.pallas_call(
        functools.partial(_reduce_kernel, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, m, c2), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, m), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, c2 // 2), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((4, c2), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((4, c2), jnp.float32),
    )(z, mask_f, cst, ct)

    d_bias, d_scale = red[0], red[1]
    inv_c = (1.0 / jnp.maximum(n_real, 1.0)) * jnp.ones((1, c2), jnp.float32)
    red5 = jnp.concatenate([red, inv_c], axis=0)  # row 4 = 1/C broadcast

    dz = pl.pallas_call(
        functools.partial(_dz_kernel, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, m, c2), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, m), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, c2 // 2), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, m, c2), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, m, c2), z.dtype),
    )(z, mask_f, cst, red5, ct)
    return dz, d_scale, d_bias


# ---------------------------------------------------------------------------
# custom-VJP wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_epilogue(z, mask, scale, bias, eps: float, impl: str):
    """(agg [N, F] f32, mean [2F], var [2F], count) — training mode.

    Differentiable in (z, scale, bias); mask gets a zero cotangent; the
    stats outputs feed the (undifferentiated) running-stat EMA.
    """
    agg, mean, var, n_real, _, _ = _fwd_parts(z, mask, scale, bias, eps, impl)
    return agg, mean, var, n_real


def _fwd_parts(z, mask, scale, bias, eps, impl):
    mean, var, n_real = _masked_stats(z, mask)
    rstd = jax.lax.rsqrt(var + eps)
    if impl == "pallas":
        agg = _pallas_apply(z, mask, mean, rstd, scale, bias)
    else:
        agg = _apply_xla(z, mask, mean, rstd, scale, bias)
    return agg, mean, var, n_real, rstd, None


def _fused_fwd(z, mask, scale, bias, eps, impl):
    agg, mean, var, n_real, rstd, _ = _fwd_parts(z, mask, scale, bias, eps,
                                                 impl)
    return (agg, mean, var, n_real), (z, mask, mean, rstd, scale, bias,
                                      n_real)


def _fused_bwd(eps, impl, res, cts):
    z, mask, mean, rstd, scale, bias, n_real = res
    ct_agg = cts[0]  # stats outputs feed only the stop-gradient EMA
    if impl == "pallas":
        dz, d_scale, d_bias = _pallas_bwd(
            z, mask, mean, rstd, scale, bias, n_real, ct_agg
        )
    else:
        dz, d_scale, d_bias = _bwd_xla(
            z, mask, mean, rstd, scale, bias, n_real, ct_agg
        )
    return dz, jnp.zeros_like(mask), d_scale, d_bias


fused_epilogue.defvjp(_fused_fwd, _fused_bwd)


def fused_epilogue_eval(z, mask, scale, bias, mean, var, eps: float,
                        impl: str = "xla"):
    """Eval-mode epilogue: normalize with running stats, gate, mask, sum."""
    rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    m32 = mean.astype(jnp.float32)
    if impl == "pallas":
        return _pallas_apply(z, mask, m32, rstd, scale, bias)
    return _apply_xla(z, mask, m32, rstd, scale, bias)


class FusedBN1GateSum(nn.Module):
    """Drop-in for CGConv's BN1 -> gate -> mask -> sum chain (dense layout).

    Owns the SAME parameter/collection names as ``MaskedBatchNorm(name=
    'bn1')`` — scale/bias params, mean/var batch_stats — so checkpoints
    trained either way restore interchangeably. Output is the aggregated
    [N, F] message sum in f32 (CGConv casts as needed).
    """

    momentum: float = 0.1
    epsilon: float = 1e-5
    impl: str = "xla"  # 'xla' (structured jnp) | 'pallas'

    @nn.compact
    def __call__(self, z, mask, use_running_average: bool = False):
        features = z.shape[-1]
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros(features, jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones(features, jnp.float32)
        )
        scale = self.param("scale", nn.initializers.ones, (features,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (features,),
                          jnp.float32)
        if use_running_average:
            return fused_epilogue_eval(
                z, mask, scale, bias, ra_mean.value, ra_var.value,
                self.epsilon, self.impl,
            )
        agg, mean, var, n_real = fused_epilogue(
            z, mask, scale, bias, self.epsilon, self.impl
        )
        if not self.is_initializing():
            has_rows = n_real > 0
            unbiased = var * n_real / jnp.maximum(n_real - 1.0, 1.0)
            ra_mean.value = jnp.where(
                has_rows,
                (1.0 - self.momentum) * ra_mean.value + self.momentum * mean,
                ra_mean.value,
            )
            ra_var.value = jnp.where(
                has_rows,
                (1.0 - self.momentum) * ra_var.value
                + self.momentum * unbiased,
                ra_var.value,
            )
        return agg
