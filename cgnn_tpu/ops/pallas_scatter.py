"""Pallas TPU kernel for the gather-scatter hot loop (SURVEY.md §7 phase 6).

The reference's hottest device op is the per-node reduction of edge messages
(CUDA: ATen scatter / atomicAdd). XLA lowers ``segment_sum`` to a scatter;
this kernel instead exploits the batcher's sorted-centers invariant
(data/graph.py) to turn the reduction into MXU matmuls with zero scatter:

- a device-side ``searchsorted`` over the sorted centers yields, for every
  node, its contiguous incident-edge range [start_n, end_n);
- grid over node tiles of TN=128 rows; per-node ranges arrive as an aligned
  [num_tiles, TN] block, tile-level ranges as scalar prefetch;
- each tile's edge span is streamed HBM -> VMEM in fixed TE-row chunks; a
  chunk is reduced in one shot via an interval one-hot matmul:
      oh[e, n]  = (start_n <= g_e) & (g_e < end_n),  g_e = global edge row
      acc[n, f] += oh^T @ msg_chunk                  (MXU contraction)
  Rows past the tile's span or past E fall outside every interval, so
  over-reads are self-masking. No atomics, deterministic, tolerant of
  arbitrary degree skew and empty nodes.

Backward: aggregation is linear, so d_messages = d_out[centers] — a plain
XLA gather (custom_vjp below). Exposed through
``aggregate_edge_messages(..., impl='pallas')`` (ops/segment.py).

STATUS (round 3, measured with value-fetch fencing): NOT the default,
and NOT the answer to the scatter problem. At E=567k/F=128/bf16 on a
v5e chip: XLA segment_sum 10.1 ms, this kernel 17.3 ms, cumsum+boundary-
gather 21.3 ms — all ~50x below HBM bandwidth; scatter-shaped reductions
are simply slow on this hardware. The production fix is STRUCTURAL: the
dense edge-slot layout (data/graph.py pack_graphs dense_m) removes the
segment-sum from the model entirely (aggregation becomes a dense reduce,
measured 1.1 ms at the same shape, 2x faster end-to-end train step). The
kernel stays as a correct, tested, flag-selectable backend for the flat
layout and as the scaffold for a windowed one-hot GATHER kernel (the
remaining neighbor-gather backward is now the dominant step cost).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TN = 128  # node rows per grid step (= lane width)
_TE = 512  # edge rows per streamed chunk


def _kernel(tile_starts_ref, bounds_ref, msg_ref, out_ref, acc_ref,
            msg_vmem, sem):
    i = pl.program_id(0)
    start = tile_starts_ref[i]
    end = tile_starts_ref[i + 1]

    acc_ref[:] = jnp.zeros_like(acc_ref)
    # explicit int32: under jax_enable_x64 a Python-int operand would
    # promote the index math to int64, which SMEM scalars reject
    te = jnp.int32(_TE)
    # align the stream start down to the sublane tile (8 rows — required for
    # bf16 HBM slices); rows before `start` belong to the previous tile's
    # nodes and are self-masked by the interval one-hot
    astart = (start // 8) * 8
    num_chunks = pl.cdiv(end - astart, te)
    # bounds block is (8, TN) for sublane alignment; rows 2..7 are padding
    node_start = bounds_ref[0, :]  # [TN] first edge row of each node
    node_end = bounds_ref[1, :]  # [TN] one-past-last edge row

    def chunk_body(k, _):
        off = pl.multiple_of(astart + k * te, 8)
        dma = pltpu.make_async_copy(
            msg_ref.at[pl.ds(off, _TE), :], msg_vmem, sem
        )
        dma.start()
        dma.wait()
        # interval one-hot over global edge rows; self-masks over-read rows
        g = off + jax.lax.broadcasted_iota(jnp.int32, (_TE, _TN), 0)
        oh = jnp.logical_and(
            g >= node_start[None, :], g < node_end[None, :]
        ).astype(msg_vmem.dtype)
        # f32 operands need HIGHEST or the MXU rounds them through bf16
        # passes; bf16 operands are exact already (one-hot selection) and
        # only support the native bf16 x bf16 -> f32 path
        precision = (
            jax.lax.Precision.HIGHEST
            if msg_vmem.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT
        )
        acc_ref[:] += jax.lax.dot_general(
            oh,
            msg_vmem[:],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        return 0

    jax.lax.fori_loop(0, num_chunks, chunk_body, 0)
    out_ref[:] = acc_ref[:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def segment_sum_pallas(
    messages: jax.Array, centers: jax.Array, num_nodes: int
) -> jax.Array:
    """Scatter-free segment sum over SORTED centers -> [num_nodes, F].

    Requires the pack_graphs sortedness invariant; messages for masked
    (padding) edges must already be zeroed, as in CGConv.
    """
    return _forward(messages, centers, num_nodes)


def _forward(messages, centers, num_nodes):
    e, f = messages.shape
    num_tiles = pl.cdiv(num_nodes, _TN)
    n_pad = num_tiles * _TN
    # pad edges so chunk DMAs past `end` stay in bounds, and features to the
    # 128-lane tile (Mosaic requires aligned DMA slices)
    f_pad = -f % 128
    fp = f + f_pad
    msg_p = jnp.pad(messages, ((0, _TE), (0, f_pad)))

    centers = centers.astype(jnp.int32)
    # per-node contiguous edge ranges from the global sort
    edge_bounds = jnp.searchsorted(
        centers, jnp.arange(n_pad + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    # (8, TN)-tiled bounds block per tile: row 0 = start, row 1 = end,
    # rows 2..7 sublane-alignment padding
    bounds = jnp.zeros((num_tiles, 8, _TN), jnp.int32)
    bounds = bounds.at[:, 0].set(edge_bounds[:-1].reshape(num_tiles, _TN))
    bounds = bounds.at[:, 1].set(edge_bounds[1:].reshape(num_tiles, _TN))
    bounds = bounds.reshape(num_tiles * 8, _TN)
    tile_starts = edge_bounds[:: _TN]  # [num_tiles + 1]

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[
                pl.BlockSpec(
                    (8, _TN), lambda i, ts: (i, 0), memory_space=pltpu.VMEM
                ),
                pl.BlockSpec(memory_space=pl.ANY),  # messages
            ],
            out_specs=pl.BlockSpec(
                (_TN, fp), lambda i, ts: (i, 0), memory_space=pltpu.VMEM
            ),
            scratch_shapes=[
                pltpu.VMEM((_TN, fp), jnp.float32),
                pltpu.VMEM((_TE, fp), messages.dtype),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, fp), jnp.float32),
    )(tile_starts, bounds, msg_p)
    return out[:num_nodes, :f].astype(messages.dtype)


def _fwd(messages, centers, num_nodes):
    return _forward(messages, centers, num_nodes), centers


def _bwd(num_nodes, centers, g):
    # linear op: d_messages[e] = g[centers[e]]; centers get no gradient
    return jnp.take(g, centers, axis=0).astype(g.dtype), None


segment_sum_pallas.defvjp(_fwd, _bwd)
