"""Windowed one-hot neighbor-gather kernel (SURVEY.md §7 phase 6).

The dense-layout conv's forward ``v_j = nodes[neighbors]`` is a row-granular
HBM gather: TPU has no data cache, so each node's 128-byte row is re-read
once per incident edge (~M times), and row-granular access itself tops out
~230 GB/s on v5e (measured, PERF.md). But the batcher packs each graph's
nodes contiguously and every edge's neighbor lies INSIDE its own graph, so
the gather has perfect block locality: the edges owned by a 128-slot node
block only reference a bounded node WINDOW (that block's graphs' spans,
<= 128 + 2*(max_graph_nodes-1) rows).

This kernel exploits that: per node block b, the grid's minor dimension w
walks the (few) 128-row node tiles of b's window — Pallas pipelines each
tile HBM->VMEM via a scalar-prefetch index_map (each node row read once
per block instead of M times, sequential DMA) — and the gather becomes an
MXU contraction ``one_hot(local_idx) @ node_tile`` accumulated over w.
The accumulation is EXACT in any dtype: each edge's index lies in exactly
one tile, so all other tiles contribute zeros.

STATUS (round 3, measured on the real v5e with value-fetch fencing): NOT
integrated — a tested negative result, like the interval-one-hot
segment-sum before it (ops/pallas_scatter.py). At the bench's MP shape
(N=15488, M=12, F=64, bf16), bit-exact vs ``jnp.take`` but SLOWER:
1.96 ms vs 1.31 ms (TN=128), 1.77 vs 1.47 (TN=256), 1.86 vs 1.53
(TN=512). Why: the one-hot materialization does E*W lane-compares
(~95M elements at W=512) — ~30x the E*F output volume — and that VPU
work exceeds what the M-fold redundant HBM reads cost the native
gather. The trade would flip for much larger F (one-hot cost is
F-independent) or much larger M; at this model's F=64/M=12 XLA's
row-granular gather is the right tool. Kept as a correct, tested
scaffold; the model path keeps jnp.take + the two-tier transpose
backward (ops/segment.py gather_transpose).

Correctness cases handled:
- window start clamped to [0, N-W]; clamping only extends coverage left.
- padding slots are self-loops whose nodes may fall outside a padding
  block's window: their one-hot rows are all-zero -> v_j = 0, identical
  to the plain gather of a zeroed padding node row.
- requires node_cap % 128 == 0 and edge_cap == node_cap * M (the dense
  layout); callers align capacities.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TN = 128  # node rows per tile (= lane width)


def _kernel(ws_ref, nbr_ref, ntile_ref, out_ref, *, tn, m):
    b = pl.program_id(0)
    w = pl.program_id(1)
    base = (ws_ref[b] // tn + w) * tn  # absolute first row of this tile
    local = nbr_ref[:] - base  # [tn, m]
    oh = (
        local[:, :, None]
        == jax.lax.broadcasted_iota(jnp.int32, (tn, m, tn), 2)
    )
    part = jax.lax.dot_general(
        oh.astype(ntile_ref.dtype),
        ntile_ref[:],
        (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        # HIGHEST for f32: default MXU precision rounds f32 operands to
        # bf16, which would silently break the bit-exactness claim.
        # bf16 operands are exact at default precision, and Mosaic
        # refuses a bf16 x bf16 matmul at fp32 contract precision
        precision=(jax.lax.Precision.HIGHEST
                   if ntile_ref.dtype == jnp.float32 else None),
    ).astype(out_ref.dtype)

    @pl.when(w == 0)
    def _init():
        out_ref[:] = part

    @pl.when(w > 0)
    def _acc():
        out_ref[:] += part


@functools.partial(jax.jit, static_argnames=("window",))
def windowed_gather(
    nodes: jax.Array,  # [N, F], N % 128 == 0
    neighbors: jax.Array,  # [N*M] i32 (dense slot layout)
    win_starts: jax.Array,  # [N // 128] i32 first window row per block
    window: int,  # static width, multiple of 128 (see window_width)
) -> jax.Array:
    n, f = nodes.shape
    e = neighbors.shape[0]
    m = e // n
    assert n % _TN == 0, f"node capacity {n} not {_TN}-aligned"
    assert window % _TN == 0
    nb = n // _TN
    nw = window // _TN
    win_starts = jnp.minimum(
        win_starts.astype(jnp.int32), jnp.int32(max(n - window, 0))
    )
    win_starts = (win_starts // _TN) * _TN
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nw),
        in_specs=[
            pl.BlockSpec((_TN, m), lambda b, w, ws: (b, 0)),
            pl.BlockSpec((_TN, f), lambda b, w, ws: (ws[b] // _TN + w, 0)),
        ],
        out_specs=pl.BlockSpec((_TN, m, f), lambda b, w, ws: (b, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, tn=_TN, m=m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, m, f), nodes.dtype),
    )(win_starts, neighbors.astype(jnp.int32).reshape(n, m), nodes)


def window_width(max_graph_nodes: int) -> int:
    """Static window for a dataset: a 128-slot block can straddle one
    graph cut at its start and another at its end, plus one extra tile
    for the 128-row alignment of the window start."""
    need = 2 * _TN + 2 * (int(max_graph_nodes) - 1)
    return max(_TN, -(-need // _TN) * _TN)
