"""Whole-conv fused CGConv: gather -> fc_full -> BN -> gate -> reduce in
one Pallas pass structure (ROADMAP item 2, the §3b/§6b successor).

PERF.md's post-r3 position: the flagship step is memory-bound, and the two
narrow kernel attempts (windowed one-hot gather, fused BN epilogue) both
measured NEGATIVE because any custom op cut at a sub-conv boundary forces
``z``/``dz`` through HBM and loses to XLA's producer/consumer fusion
(§6b). The remaining structural lever is to fuse the ENTIRE dense-branch
conv so no opaque boundary is left to pay: per 128-node block, DMA the
block's neighbor window + the conv parameters to VMEM once, run the
``fc_full`` contraction on the MXU in-kernel, apply the masked-BN
normalize + sigmoid*softplus gate, and reduce over the M edge slots
in-register — writing ONLY the aggregated ``[N, F]`` message sum back to
HBM. The ``v_j`` gather result and the ``z = fc_full(...)`` activation
never exist in HBM at all, in either direction:

- forward: two input passes (a stats pass for the masked BN moments — a
  global reduction that must complete before any element normalizes —
  and an apply pass), ZERO intermediate writes. Residuals are just
  ``(mean, rstd)``; versus the unfused path's staged ``v_j`` ([E, F])
  and partially-materialized ``z`` ([E, 2F]).
- backward: rematerialized — the custom VJP re-derives gradients through
  a structured jnp twin of the forward (the §6b-measured property that
  XLA fuses ``dz`` into the matmul backwards at near-roofline makes a
  hand-blocked backward a boundary loss, not a win), so the forward
  saves no activations.

Two implementations behind one flag (the §6b methodology):

- ``impl='xla'``: the structured jnp twin as the forward too — measures
  what the minimal-pass STRUCTURE + custom-VJP rematerialization buy
  before any hand scheduling;
- ``impl='pallas'``: the blocked TPU kernels described above.

Window contract (the in-kernel gather): the packer places each graph's
nodes contiguously and every edge's neighbor lies inside its own graph,
so the neighbors of a 128-row node block live in a bounded window around
the block (ops/pallas_gather.py proved the locality). ``window=0`` uses
the whole node range (always correct, O(E*N) one-hot work — tests);
``window=W`` with ``W >= window_width(max_graph_nodes)`` (pallas_gather)
bounds the per-block DMA; callers own the bound (train.py derives it
from the dataset). An out-of-window REAL neighbor would silently gather
zeros — the wrapper therefore only accepts ``window > 0`` together with
the caller's explicit bound.

Numerical contract: identical to the dense CGConv branch in
models/cgcnn.py — ``_SplitFcFull`` + one-pass-f32 MaskedBatchNorm + gate
+ edge mask + sum — to f32 roundoff (tests/test_ops.py
TestFusedCGConv). The kernel computes matmuls with f32 accumulation and
all BN/gate math in f32 regardless of the storage dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cgnn_tpu.ops.segment import gather, gather_transpose

_TN = 128  # node rows per block AND per window tile (lane width)


def window_width(max_graph_nodes: int) -> int:
    """Static window bound for a dataset (see ops/pallas_gather.py)."""
    need = 2 * _TN + 2 * (int(max_graph_nodes) - 1)
    return max(_TN, -(-need // _TN) * _TN)


def _win_starts(n_blocks: int, n_pad: int, window: int):
    """[NB] i32 aligned window starts: block b's graphs' node span
    sits inside [ws[b], ws[b] + window) (coverage pinned by test)."""
    import numpy as np

    pad_left = max((window - 2 * _TN) // 2, 0)
    ws = np.arange(n_blocks, dtype=np.int64) * _TN - pad_left
    ws = (ws // _TN) * _TN
    ws = np.clip(ws, 0, max(n_pad - window, 0))
    return jnp.asarray(ws.astype(np.int32))


# ---------------------------------------------------------------------------
# structured jnp twin (impl='xla' forward; the rematerialized backward; and
# the numerics reference the Pallas kernels must match)
# ---------------------------------------------------------------------------


def _masked_stats(z, mask):
    """Shifted one-pass masked moments over (N, M) -> f32 (the exact
    ops/norm.py estimator, shared with ops/fused_epilogue.py)."""
    zf = z.astype(jnp.float32)
    shift = jax.lax.stop_gradient(zf[:1].mean(axis=(0, 1)))
    zs = zf - shift
    m = mask.astype(jnp.float32)
    n_real = m.sum()
    zm = zs * m[..., None]
    s1 = zm.sum(axis=(0, 1))
    s2 = (zm * zs).sum(axis=(0, 1))
    n = jnp.maximum(n_real, jnp.float32(1.0))
    mean_s = s1 / n
    var = jnp.maximum(s2 / n - mean_s * mean_s, jnp.float32(0.0))
    return mean_s + shift, var, n_real


def _gate_sum(y, mask):
    # where-select, not multiply: padding slots of the TAIL node block
    # read out-of-range garbage in the Pallas kernels (both interpret
    # and Mosaic pad with arbitrary bytes), and 0 * NaN would poison the
    # reduction that a 0-select cannot. f32 literal: a bare python
    # float under an x64 session lowers an f64 constant (GA-F64).
    f = y.shape[-1] // 2
    msg = jax.nn.sigmoid(y[..., :f]) * jax.nn.softplus(y[..., f:])
    # expand the f32 mask, THEN compare: Mosaic has no layout for a
    # reshape of an i1 vector ([TN, M] -> [TN, M, 1])
    keep = mask[..., None] > 0
    return jnp.where(keep, msg, jnp.float32(0.0)).sum(axis=1)


def _z_structured(nodes, edges, kernel, bias, neighbors, transpose_args,
                  dtype):
    """fc_full(v_i, v_j, e) without materializing the concat — the
    _SplitFcFull contraction, with the v_j gather routed through the
    scatter-free transpose mapping when the batch carries one."""
    n, m = edges.shape[0], edges.shape[1]
    f = nodes.shape[-1]
    k = kernel.astype(dtype)
    if transpose_args is not None and transpose_args[0] is not None:
        in_slots, in_mask, over_slots, over_nodes, over_mask = transpose_args
        v_j = gather_transpose(
            nodes, neighbors, in_slots, in_mask, over_slots=over_slots,
            over_nodes=over_nodes, over_mask=over_mask,
        ).reshape(n, m, f)
    else:
        v_j = gather(nodes, neighbors).reshape(n, m, f)
    z = (
        (nodes.astype(dtype) @ k[:f])[:, None, :]
        + v_j.astype(dtype) @ k[f: 2 * f]
        + edges.astype(dtype) @ k[2 * f:]
    )
    return z + bias.astype(dtype)


def _forward_structured(nodes, edges, kernel, bias, scale, bn_bias,
                        neighbors, edge_mask, transpose_args, eps, dtype):
    z = _z_structured(nodes, edges, kernel, bias, neighbors,
                      transpose_args, dtype)
    mean, var, n_real = _masked_stats(z, edge_mask)
    rstd = jax.lax.rsqrt(var + jnp.float32(eps))
    y = (z.astype(jnp.float32) - mean) * (rstd * scale) + bn_bias
    agg = _gate_sum(y, edge_mask.astype(jnp.float32))
    return agg, mean, var, n_real


def _apply_structured(nodes, edges, kernel, bias, scale, bn_bias, mean,
                      rstd, neighbors, edge_mask, transpose_args, dtype):
    z = _z_structured(nodes, edges, kernel, bias, neighbors,
                      transpose_args, dtype)
    y = (z.astype(jnp.float32) - mean) * (rstd * scale) + bn_bias
    return _gate_sum(y, edge_mask.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Pallas kernels: per 128-node block, accumulate v_j over the window tiles
# (one-hot MXU contraction), then fc_full + BN + gate + reduce in-register
# ---------------------------------------------------------------------------


def _row_keep(b, bn, n):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + b * bn
    return (rows < n).astype(jnp.float32)


def _vj_accumulate(w, ws_ref, nbr_ref, ntile_ref, vj_scratch, n):
    """vj_scratch (+)= one_hot(local) @ node_tile for window tile w.

    Exact in any dtype: each neighbor index lies in exactly one tile, so
    every other tile contributes certified zeros. Tile rows past the
    real node count are zeroed first — they are out-of-range block reads
    (garbage, possibly NaN) and 0-one-hot times NaN is NaN."""
    b = pl.program_id(0)
    base = ws_ref[b] + w * _TN
    local = nbr_ref[...] - base  # [TN, M]
    tile_rows = jax.lax.broadcasted_iota(jnp.int32, (_TN, 1), 0) + base
    tile = jnp.where(tile_rows < n, ntile_ref[...].astype(jnp.float32),
                     jnp.float32(0.0))
    oh = (
        local[:, :, None]
        == jax.lax.broadcasted_iota(
            jnp.int32, (*local.shape, _TN), 2)
    )
    part = jax.lax.dot_general(
        oh.astype(jnp.float32), tile,
        (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(w == 0)
    def _init():
        vj_scratch[...] = part

    @pl.when(w > 0)
    def _acc():
        vj_scratch[...] += part


def _z_block(b, nodes_ref, edges_ref, cst_ref, vj, n, f, g):
    """fc_full for one block, f32: [TN, M, 2F] from VMEM-resident inputs.

    ``cst_ref`` rows: kernel [(2F+G), 2F] then bias/scale/bn_bias/extra
    rows appended by the callers (see _pack_cst). Tail-block rows past
    ``n`` are zeroed at the source (out-of-range reads are garbage) —
    their z values are then finite and the edge-mask selects drop them.
    """
    keep = _row_keep(b, _TN, n)  # [TN, 1] f32 (i1 vectors do not reshape)
    k = cst_ref[: 2 * f + g, :]
    nodes_blk = jnp.where(keep > 0, nodes_ref[...].astype(jnp.float32),
                          jnp.float32(0.0))
    edges_blk = jnp.where(keep[..., None] > 0,
                          edges_ref[...].astype(jnp.float32),
                          jnp.float32(0.0))
    vi_term = jnp.dot(nodes_blk, k[:f, :],
                      preferred_element_type=jnp.float32)
    vj_term = jax.lax.dot_general(
        vj, k[f: 2 * f, :], (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    e_term = jax.lax.dot_general(
        edges_blk, k[2 * f: 2 * f + g, :],
        (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    bias = cst_ref[2 * f + g, :]
    return vi_term[:, None, :] + vj_term + e_term + bias


def _blk_mask(b, mask_ref, n):
    """[TN, M] edge mask with tail-block garbage rows zeroed (where, not
    multiply — the source values may be NaN)."""
    return jnp.where(_row_keep(b, _TN, n) > 0, mask_ref[...],
                     jnp.float32(0.0))


def _stats_kernel(ws_ref, nbr_ref, ntile_ref, nodes_ref, edges_ref,
                  mask_ref, cst_ref, out_ref, vj_scratch, *, n, f, g):
    b = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)
    _vj_accumulate(w, ws_ref, nbr_ref, ntile_ref, vj_scratch, n)

    @pl.when(w == nw - 1)
    def _finish():
        z = _z_block(b, nodes_ref, edges_ref, cst_ref, vj_scratch[...],
                     n, f, g)
        shift = cst_ref[2 * f + g + 1, :]
        mask = _blk_mask(b, mask_ref, n)
        # zm = mask * (z - shift); the second moment is zm*zm because the
        # mask is binary (mask^2 == mask) — one select covers both sums
        zm = jnp.where(mask[..., None] > 0, z - shift, jnp.float32(0.0))
        part = jnp.stack([
            zm.sum(axis=(0, 1)),
            (zm * zm).sum(axis=(0, 1)),
        ])

        @pl.when(b == 0)
        def _zero():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += part


def _apply_kernel(ws_ref, nbr_ref, ntile_ref, nodes_ref, edges_ref,
                  mask_ref, cst_ref, agg_ref, vj_scratch, *, n, f, g):
    b = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)
    _vj_accumulate(w, ws_ref, nbr_ref, ntile_ref, vj_scratch, n)

    @pl.when(w == nw - 1)
    def _finish():
        z = _z_block(b, nodes_ref, edges_ref, cst_ref, vj_scratch[...],
                     n, f, g)
        base = 2 * f + g
        mean = cst_ref[base + 1, :]
        rstd_scale = cst_ref[base + 2, :]
        bn_bias = cst_ref[base + 3, :]
        y = (z - mean) * rstd_scale + bn_bias
        agg_ref[...] = _gate_sum(y, _blk_mask(b, mask_ref, n))


def _pack_cst(kernel, bias, *rows):
    """[(2F+G) + 1 + len(rows), 2F] f32: kernel, bias, then extra rows —
    one VMEM-resident constant block per pallas_call."""
    parts = [kernel.astype(jnp.float32), bias[None].astype(jnp.float32)]
    parts += [r[None].astype(jnp.float32) for r in rows]
    return jnp.concatenate(parts, axis=0)


def _pallas_passes(nodes, edges, kernel, bias, neighbors, edge_mask,
                   window, mode_rows, kernel_fn, out_shape):
    """Shared pallas_call plumbing for the stats/apply passes."""
    n, f = nodes.shape
    m = edges.shape[1]
    g = edges.shape[2]
    nb = pl.cdiv(n, _TN)
    n_pad = nb * _TN
    win = n_pad if window <= 0 else min(window, n_pad)
    nw = win // _TN
    ws = _win_starts(nb, n_pad, win)
    cst = _pack_cst(kernel, bias, *mode_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nw),
        in_specs=[
            pl.BlockSpec((_TN, m), lambda b, w, ws: (b, 0)),  # neighbors
            pl.BlockSpec((_TN, f), lambda b, w, ws: (ws[b] // _TN + w, 0)),
            pl.BlockSpec((_TN, f), lambda b, w, ws: (b, 0)),  # nodes blk
            pl.BlockSpec((_TN, m, g), lambda b, w, ws: (b, 0, 0)),
            pl.BlockSpec((_TN, m), lambda b, w, ws: (b, 0)),  # edge mask
            pl.BlockSpec(cst.shape, lambda b, w, ws: (0, 0)),
        ],
        out_specs=out_shape[1],
        scratch_shapes=[pltpu.VMEM((_TN, m, f), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(kernel_fn, n=n, f=f, g=g),
        grid_spec=grid_spec,
        out_shape=out_shape[0],
    )(
        ws,
        neighbors.astype(jnp.int32).reshape(n, m),
        nodes,
        nodes,
        edges,
        edge_mask.astype(jnp.float32),
        cst,
    )


def _pallas_stats(nodes, edges, kernel, bias, neighbors, edge_mask, shift,
                  window):
    f = nodes.shape[-1]
    return _pallas_passes(
        nodes, edges, kernel, bias, neighbors, edge_mask, window,
        (shift,), _stats_kernel,
        (jax.ShapeDtypeStruct((2, 2 * f), jnp.float32),
         pl.BlockSpec((2, 2 * f), lambda b, w, ws: (0, 0))),
    )


def _pallas_apply(nodes, edges, kernel, bias, neighbors, edge_mask, mean,
                  rstd_scale, bn_bias, window):
    n, f = nodes.shape
    return _pallas_passes(
        nodes, edges, kernel, bias, neighbors, edge_mask, window,
        (mean, rstd_scale, bn_bias), _apply_kernel,
        (jax.ShapeDtypeStruct((n, f), jnp.float32),
         pl.BlockSpec((_TN, f), lambda b, w, ws: (b, 0))),
    )


def _shift_row0(nodes, edges, kernel, bias, neighbors, dtype):
    """The stats estimator's cancellation shift — z of node row 0,
    averaged over its M slots (ops/norm.py semantics), computed with a
    tiny jnp expression so the kernels can consume it as a constant."""
    m = edges.shape[1]
    f = nodes.shape[-1]
    k = kernel.astype(dtype)
    vj0 = jnp.take(nodes, neighbors[:m], axis=0).astype(dtype)
    z0 = (
        nodes[0].astype(dtype) @ k[:f]
        + vj0 @ k[f: 2 * f]
        + edges[0].astype(dtype) @ k[2 * f:]
        + bias.astype(dtype)
    )
    return jax.lax.stop_gradient(z0.astype(jnp.float32).mean(axis=0))


def _forward_pallas(nodes, edges, kernel, bias, scale, bn_bias, neighbors,
                    edge_mask, eps, window, dtype):
    shift = _shift_row0(nodes, edges, kernel, bias, neighbors, dtype)
    s = _pallas_stats(nodes, edges, kernel, bias, neighbors, edge_mask,
                      shift, window)
    n_real = edge_mask.astype(jnp.float32).sum()
    c = jnp.maximum(n_real, jnp.float32(1.0))
    mean_s = s[0] / c
    var = jnp.maximum(s[1] / c - mean_s * mean_s, jnp.float32(0.0))
    mean = mean_s + shift
    rstd = jax.lax.rsqrt(var + jnp.float32(eps))
    agg = _pallas_apply(
        nodes, edges, kernel, bias, neighbors, edge_mask,
        mean, rstd * scale, bn_bias, window,
    )
    return agg, mean, var, n_real


# ---------------------------------------------------------------------------
# the op: custom VJP with a rematerialized structured backward
# ---------------------------------------------------------------------------


def fused_cgconv(
    nodes: jax.Array,  # [N, F]
    edges: jax.Array,  # [N, M, G]
    kernel: jax.Array,  # [2F+G, 2F] (fc_full)
    bias: jax.Array,  # [2F]
    scale: jax.Array,  # [2F] (bn1)
    bn_bias: jax.Array,  # [2F]
    neighbors: jax.Array,  # [N*M] i32
    edge_mask: jax.Array,  # [N, M]
    transpose_args=None,  # (in_slots, in_mask, over_*) or None
    *,
    eps: float = 1e-5,
    impl: str = "pallas",
    window: int = 0,
    dtype=jnp.float32,
):
    """(agg [N, F] f32, mean [2F], var [2F], n_real) — training mode.

    Differentiable in (nodes, edges, kernel, bias, scale, bn_bias); the
    stats outputs feed the (stop-gradient) running-stat EMA. The
    backward REMATERIALIZES through the structured twin — residuals are
    the op's own inputs, nothing forward-computed is saved — and routes
    the v_j cotangent through ``gather_transpose`` when the batch
    carries a transpose mapping (the scatter-free dense backward).
    """
    if impl not in ("xla", "pallas"):
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    tr = transpose_args

    @jax.custom_vjp
    def op(nodes, edges, kernel, bias, scale, bn_bias):
        if impl == "pallas":
            return _forward_pallas(nodes, edges, kernel, bias, scale,
                                   bn_bias, neighbors, edge_mask, eps,
                                   window, dtype)
        return _forward_structured(nodes, edges, kernel, bias, scale,
                                   bn_bias, neighbors, edge_mask, tr, eps,
                                   dtype)

    def op_fwd(nodes, edges, kernel, bias, scale, bn_bias):
        out = op(nodes, edges, kernel, bias, scale, bn_bias)
        return out, (nodes, edges, kernel, bias, scale, bn_bias)

    def op_bwd(res, cts):
        # rematerialized: re-derive the structured forward's VJP from the
        # saved INPUTS (no activations were stored); the stats outputs'
        # cotangents are zero by construction (EMA is stop-gradient)
        _, vjp_fn = jax.vjp(
            lambda *a: _forward_structured(*a, neighbors, edge_mask, tr,
                                           eps, dtype),
            *res,
        )
        zeros = (jnp.zeros_like(cts[1]), jnp.zeros_like(cts[2]),
                 jnp.zeros_like(cts[3]))
        return vjp_fn((cts[0], *zeros))

    op.defvjp(op_fwd, op_bwd)
    return op(nodes, edges, kernel, bias, scale, bn_bias)


def fused_cgconv_eval(nodes, edges, kernel, bias, scale, bn_bias,
                      neighbors, edge_mask, mean, var, transpose_args=None,
                      *, eps: float = 1e-5, impl: str = "pallas",
                      window: int = 0, dtype=jnp.float32):
    """Eval/serving mode: normalize with running stats — ONE apply pass,
    the whole-conv serving fast path."""
    rstd = jax.lax.rsqrt(var.astype(jnp.float32) + jnp.float32(eps))
    m32 = mean.astype(jnp.float32)
    if impl == "pallas":
        return _pallas_apply(nodes, edges, kernel, bias, neighbors,
                             edge_mask, m32, rstd * scale, bn_bias, window)
    return _apply_structured(nodes, edges, kernel, bias, scale, bn_bias,
                             m32, rstd, neighbors, edge_mask,
                             transpose_args, dtype)


def fused_conv_hbm_bytes(n: int, m: int, g: int, f: int,
                         dtype_bytes: int = 4) -> dict:
    """The kernel's analytic HBM byte model (graftaudit roofline budget).

    Per training-mode forward: TWO passes read the block inputs (edges
    [N,M,G] dominate; nodes via bounded windows ~2x [N,F]; neighbors +
    mask), ONE [N,F] f32 write, ZERO intermediate tensors — the ~3
    round-trips the unfused path pays for v_j/z/staging collapse to one
    per edge block. The audit gates a lowered fused program's
    cost-analysis bytes against this model so a later change that
    silently rematerializes an [N,M,*] intermediate in HBM blocks CI.
    """
    edges_b = n * m * g * dtype_bytes
    nodes_b = 2 * n * f * dtype_bytes  # block rows + window tiles
    nbr_b = n * m * 4
    mask_b = n * m * 4
    params_b = (2 * f + g) * 2 * f * 4
    read_once = edges_b + nodes_b + nbr_b + mask_b + params_b
    write_b = n * f * 4
    return {
        "reads_per_pass": read_once,
        "passes": 2,
        "write_bytes": write_b,
        "model_bytes": 2 * read_once + write_b,
    }


class FcFullParams(nn.Module):
    """``_SplitFcFull``'s parameter tree (kernel/bias) without its
    compute — instantiated by CGConv with ``name='fc_full'`` so the
    fused path owns the EXACT checkpoint layout (and, with the same rng
    path, the bit-identical init) of the unfused branch."""

    features: int  # 2F

    @nn.compact
    def __call__(self, in_dim: int):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (in_dim, self.features), jnp.float32,
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,),
                          jnp.float32)
        return kernel, bias


class BN1Params(nn.Module):
    """MaskedBatchNorm's parameter/stat tree without its compute.

    Two-phase use by CGConv (``name='bn1'``): a first call declares and
    returns (scale, bias, running mean, running var); a second call with
    ``update=(mean, var, n_real)`` applies the momentum-0.1 EMA — the
    exact update MaskedBatchNorm/FusedBN1GateSum perform, including the
    all-padding-batch guard and the unbiased-variance correction.
    Compact modules may be called repeatedly; both calls declare the
    same tree, so the layout is identical either way.
    """

    momentum: float = 0.1

    @nn.compact
    def __call__(self, features: int, update=None):
        scale = self.param("scale", nn.initializers.ones, (features,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (features,),
                          jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros(features, jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones(features, jnp.float32)
        )
        if update is not None and not self.is_initializing():
            mean, var, n_real = update
            has_rows = n_real > 0
            one = jnp.float32(1.0)
            unbiased = var * n_real / jnp.maximum(n_real - one, one)
            ra_mean.value = jnp.where(
                has_rows,
                (1.0 - self.momentum) * ra_mean.value
                + self.momentum * mean,
                ra_mean.value,
            )
            ra_var.value = jnp.where(
                has_rows,
                (1.0 - self.momentum) * ra_var.value
                + self.momentum * unbiased,
                ra_var.value,
            )
        return scale, bias, ra_mean.value, ra_var.value
