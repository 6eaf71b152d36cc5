"""Edge-sharded (graph-parallel) message passing — the sequence-parallel
analog for crystal graphs (SURVEY.md §5 "long-context analog").

A crystal-graph model has no sequence axis; its scaling axis is the EDGE
list. When a batch's edge work exceeds one chip (giant OC20 cells, or a
single structure too large for HBM), shard the edge axis across a mesh
axis ``'graph'``:

- node features are replicated; each device gathers endpoints for ITS edge
  shard only (contiguous chunks of the globally center-sorted edge list, so
  the per-shard sortedness invariant holds);
- the dominant FLOPs — the per-edge ``fc_full`` dense layer — split D ways;
- per-node partial aggregates are ``psum``-ed back to full sums (one ICI
  all-reduce per conv layer, the ring-attention-style collective);
- edge-BatchNorm moments span all shards (two-psum masked moments in
  MaskedBatchNorm.axis_name).

Gradients: the step runs under ``shard_map`` with replication checking ON
(``check_vma=True``), so JAX's transpose machinery inserts the psum that
converts each shard's partial parameter cotangents into the full gradient
— no manual pmean over 'graph' (which would be wrong: node-side parameter
contributions are replicated-complete while edge-side ones are partial).
This composes with data parallelism as a 2-D mesh ``('data', 'graph')``;
grads/stats still pmean over 'data' explicitly as in plain DP.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cgnn_tpu.data.graph import (
    TRANSPOSE_FIELDS,
    GraphBatch,
    shard_transpose_slots,
)
from cgnn_tpu.train.state import TrainState
from cgnn_tpu.train.step import (
    jit_sharded_train_step,
    make_eval_step,
    make_train_step,
)

# GraphBatch leaves whose leading axis is the edge axis
EDGE_FIELDS = ("edges", "centers", "neighbors", "edge_mask", "edge_offsets")
# transpose-slot fields exist only in the dense layout, which edge sharding
# rejects; specs carry None so the pytrees match COO batches (where they
# are None)
_DENSE_ONLY_FIELDS = TRANSPOSE_FIELDS
_ALL_FIELDS = tuple(f.name for f in dataclasses.fields(GraphBatch))


def pad_edges_divisible(batch: GraphBatch, n_shards: int) -> GraphBatch:
    """Pad the edge axis so it splits evenly into ``n_shards`` (host-side).

    Padding edges follow the pack_graphs convention: masked out, pointing
    at the last node slot (preserves the sorted-centers invariant).
    """
    e = batch.edge_capacity
    pad = -e % n_shards
    if pad == 0:
        return batch
    ncap = batch.node_capacity

    def pad_field(name, x):
        if name not in EDGE_FIELDS:
            return x
        widths = [(0, pad)] + [(0, 0)] * (np.ndim(x) - 1)
        fill = ncap - 1 if name in ("centers", "neighbors") else 0
        return np.pad(np.asarray(x), widths, constant_values=fill)

    return GraphBatch(
        **{
            name: pad_field(name, getattr(batch, name))
            for name in _ALL_FIELDS
        }
    )


def batch_specs(
    graph_axis: str | None = "graph", data_axis: str | None = None
) -> GraphBatch:
    """GraphBatch of PartitionSpecs: edge leaves sharded over ``graph_axis``,
    optional leading stacked-device axis over ``data_axis``."""
    lead = (data_axis,) if data_axis else ()

    def spec(name):
        if name in _DENSE_ONLY_FIELDS:
            return None
        if name in EDGE_FIELDS and graph_axis:
            return P(*lead, graph_axis)
        return P(*lead)

    return GraphBatch(**{name: spec(name) for name in _ALL_FIELDS})


def dense_batch_specs(
    graph_axis: str = "graph",
    data_axis: str | None = None,
    with_transpose: bool = True,
) -> GraphBatch:
    """PartitionSpecs for a DENSE-layout batch under node-strip graph
    sharding (prepare_dense_sharded): ``edges`` [N, M, G] split over its
    node-owner axis, the flat per-slot leaves ([E] = [N*M]) split likewise,
    and the per-shard transpose stacks ([D, ...]) split one-mapping-per-
    shard. Node leaves stay replicated over ``graph_axis`` — the conv
    slices its own strip and psums the padded aggregate back to full.

    ``with_transpose=False`` matches eval batches, whose transpose fields
    are dropped by ``prepare_dense_sharded`` (no backward, no mapping)."""
    lead = (data_axis,) if data_axis else ()

    def spec(name):
        if name in _DENSE_ONLY_FIELDS:
            return P(*lead, graph_axis) if with_transpose else None
        if name in EDGE_FIELDS:
            return P(*lead, graph_axis)
        return P(*lead)

    return GraphBatch(**{name: spec(name) for name in _ALL_FIELDS})


def prepare_dense_sharded(
    batch: GraphBatch, n_shards: int, train: bool = True
) -> GraphBatch:
    """Host-side prep of a dense-layout batch for node-strip sharding.

    Training batches get per-shard two-tier transpose mappings
    (data/graph.py shard_transpose_slots — shard-local slot indices,
    stacked [D, ...]); eval batches drop their mapping fields entirely
    (no backward runs, and an empty [N, 0] mapping would force a distinct
    sharded pytree/spec structure for nothing).
    """
    if np.ndim(batch.edges) != 3:
        raise ValueError(
            "prepare_dense_sharded expects a dense-layout batch "
            "(edges pre-shaped [N, M, G]; pack with dense_m)"
        )
    ncap = batch.node_capacity
    if ncap % n_shards:
        raise ValueError(
            f"node capacity {ncap} not divisible by {n_shards} graph "
            f"shards; round node_cap up to a multiple of the shard count"
        )
    if not train or batch.in_slots is None:
        return dataclasses.replace(
            batch, **dict.fromkeys(TRANSPOSE_FIELDS))
    if np.ndim(batch.in_mask) == 3:
        # already per-shard (pack_graphs transpose_shards) — but ONLY for
        # the same shard count: a 4-shard mapping split over a 2-way mesh
        # would drop half the cotangents with no shape error
        if batch.in_mask.shape[0] != n_shards:
            raise ValueError(
                f"batch carries a {batch.in_mask.shape[0]}-shard transpose "
                f"mapping but {n_shards} graph shards were requested"
            )
        return batch
    if batch.over_slots is None:
        # A single-tier mapping carries no overflow capacity, and the
        # per-shard rebuild is only guaranteed overflow-safe when the cap
        # came from the batch's own two-tier mapping (per-shard overflow
        # is a subset of global overflow). A guessed cap could raise
        # TransposeOverflowError mid-training — refuse instead.
        raise ValueError(
            "graph sharding needs the two-tier transpose layout; pack "
            "with in_cap=None (the default) instead of a single-tier "
            "in_cap"
        )
    m = batch.edges.shape[1]
    mapping = shard_transpose_slots(
        np.asarray(batch.neighbors), np.asarray(batch.edge_mask) > 0,
        ncap, m, n_shards, len(batch.over_slots), len(batch.over_runs),
    )
    return dataclasses.replace(batch, **dict(zip(TRANSPOSE_FIELDS, mapping)))


def _auto_specs(
    batch: GraphBatch,
    graph_axis: str,
    data_axis: str | None,
    dense_rank: int,
) -> GraphBatch:
    """The ONE dense/COO spec dispatch: dense layouts are detected by the
    edges leaf's rank (``dense_rank`` = 3 + one per leading stack axis),
    and dense batches' transpose fields follow their presence (train
    batches carry per-shard mappings, eval batches dropped theirs)."""
    if np.ndim(batch.edges) == dense_rank:
        return dense_batch_specs(
            graph_axis=graph_axis, data_axis=data_axis,
            with_transpose=batch.in_slots is not None,
        )
    return batch_specs(graph_axis=graph_axis, data_axis=data_axis)


def _put_specs(tree, mesh: Mesh, specs, prefix: tuple = ()):
    """device_put every leaf per its spec, with ``prefix`` axes prepended
    (the scan staging's replicated step axis)."""

    def put(x, s):
        return jax.device_put(x, NamedSharding(mesh, P(*prefix, *s)))

    return jax.tree_util.tree_map(
        put, tree, specs, is_leaf=lambda x: isinstance(x, P)
    )


def shard_batch(
    batch: GraphBatch,
    mesh: Mesh,
    graph_axis: str = "graph",
    data_axis: str | None = None,
):
    """device_put a batch with edge leaves split over the graph axis (and,
    when ``data_axis`` is given, every leaf's leading stacked-device axis
    split over it). Dense-layout batches ([N, M, G] edges, optionally
    prepared by ``prepare_dense_sharded``) get the dense spec set."""
    specs = _auto_specs(batch, graph_axis, data_axis,
                        dense_rank=4 if data_axis else 3)
    return _put_specs(batch, mesh, specs)


def _specs(graph_axis, data_axis=None, dense=False, with_transpose=True):
    """Spec pytree for COO (batch_specs) or dense (dense_batch_specs)."""
    if dense:
        return dense_batch_specs(
            graph_axis=graph_axis, data_axis=data_axis,
            with_transpose=with_transpose,
        )
    return batch_specs(graph_axis=graph_axis, data_axis=data_axis)


def _harden(inner: Callable, guard: bool) -> Callable:
    """Optionally wrap an edge-sharded train body with the divergence
    guard. Safe under replication checking: the guard's keep-or-skip
    condition reads post-transpose-psum grads/params, which are already
    replicated over 'graph', so its selects and skip metrics are too."""
    if not guard:
        return inner
    from cgnn_tpu.resilience.guard import guard_step

    return guard_step(inner)


def make_edge_parallel_train_step(
    mesh: Mesh,
    classification: bool = False,
    graph_axis: str = "graph",
    dense: bool = False,
    grad_health: bool = False,
    guard: bool = False,
) -> Callable:
    """(replicated state, edge-sharded batch) -> (state, metrics).

    The model inside ``state.apply_fn`` must be built with
    ``edge_axis_name=graph_axis`` (and, for ``dense=True``, the matching
    ``dense_m``; batches via ``prepare_dense_sharded``). Replication
    checking stays ON so the parameter-gradient psum over the graph axis
    is inserted by transpose.

    ``grad_health`` adds the in-graph grad/update-norm and NaN/Inf
    metrics (observe.health) — the PR-1 known gap, closed: the values
    derive from the post-transpose-psum grads and the model's own
    psum-complete loss, both replicated over 'graph', so they pass
    replication checking without extra collectives. ``guard`` wraps the
    body with the divergence guard (see ``_harden``).
    """
    inner = _harden(
        make_train_step(classification, grad_health=grad_health), guard
    )

    smapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), _specs(graph_axis, dense=dense)),
        out_specs=(P(), P()),
    )
    return jit_sharded_train_step(smapped, mesh)


def make_edge_parallel_eval_step(
    mesh: Mesh,
    classification: bool = False,
    graph_axis: str = "graph",
    dense: bool = False,
) -> Callable:
    inner = make_eval_step(classification)
    smapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), _specs(graph_axis, dense=dense, with_transpose=False)),
        out_specs=P(),
    )
    return jax.jit(smapped)


def make_dp_edge_parallel_train_step(
    mesh: Mesh,
    classification: bool = False,
    data_axis: str = "data",
    graph_axis: str = "graph",
    dense: bool = False,
    grad_health: bool = False,
    guard: bool = False,
) -> Callable:
    """2-D mesh step: batches stacked over 'data', edges sharded over
    'graph' within each data shard. Input leaves: [D, ...] with edge leaves
    [D, E]; stats pmean over 'data', metrics psum over 'data'.

    Gradients: replication checking is ON, so the shard_map transpose
    psums parameter cotangents over BOTH mesh axes (over 'graph' that
    completes the edge-partial grads; over 'data' it sums per-shard grads).
    Scaling the loss by 1/n_data turns that data-axis sum into the DDP
    mean — an explicit pmean here would be an identity on the already
    reduced value (it arrives axis-invariant), silently leaving grads
    n_data times too large.

    ``grad_health``/``guard`` as in ``make_edge_parallel_train_step``;
    the health loss is additionally pmean-ed over 'data' by the inner
    step (any shard's NaN must be visible everywhere, not just shard 0's
    escaping value).
    """
    from cgnn_tpu.parallel.data_parallel import _squeeze0

    inner = _harden(
        make_train_step(
            classification,
            axis_name=data_axis,
            loss_scale=1.0 / mesh.shape[data_axis],
            pmean_grads=False,
            grad_health=grad_health,
        ),
        guard,
    )

    def body(state: TrainState, stacked: GraphBatch):
        return inner(state, _squeeze0(stacked))

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), _specs(graph_axis, data_axis, dense=dense)),
        out_specs=(P(), P()),
    )
    return jit_sharded_train_step(smapped, mesh)


def make_dp_edge_parallel_eval_step(
    mesh: Mesh,
    classification: bool = False,
    loss_fn: Callable | None = None,
    data_axis: str = "data",
    graph_axis: str = "graph",
    dense: bool = False,
) -> Callable:
    """2-D mesh eval step: metrics psum over 'data' (each graph shard
    computes identical metrics after the model's psum over 'graph')."""
    from cgnn_tpu.parallel.data_parallel import _squeeze0

    inner = make_eval_step(classification, axis_name=data_axis, loss_fn=loss_fn)

    def body(state: TrainState, stacked: GraphBatch):
        return inner(state, _squeeze0(stacked))

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), _specs(graph_axis, data_axis, dense=dense,
                              with_transpose=False)),
        out_specs=P(),
    )
    return jax.jit(smapped)


def shard_stacked_batch(
    stacked: GraphBatch,
    mesh: Mesh,
    data_axis: str = "data",
    graph_axis: str = "graph",
):
    """device_put a [D, ...]-stacked batch onto a 2-D mesh: leading axis over
    'data', edge leaves additionally split over 'graph' (dense-layout
    batches — edges stacked [D, N, M, G] — get the dense spec set)."""
    return shard_batch(
        stacked, mesh, graph_axis=graph_axis, data_axis=data_axis
    )


def shard_scan_stack_2d(
    tree: GraphBatch,
    mesh: Mesh,
    data_axis: str = "data",
    graph_axis: str = "graph",
):
    """device_put a STACK of device-stacked batches ([B, D, ...] leaves)
    onto a ('data','graph') mesh — the ScanEpochDriver staging for
    graph-sharded runs (the 2-D twin of data_parallel.shard_scan_stack).

    Axis 0 is the scan/step axis (replicated); axis 1 the data-device
    axis; edge leaves and per-shard transpose stacks additionally split
    over 'graph' on their own axes. The scan body's dynamic index along
    axis 0 preserves the inner shardings, so the shard_map step inside
    the scan sees exactly the per-step path's layout. COO stacks
    ([B, D, E, G] edges) and dense stacks ([B, D, N, M, G]) are
    distinguished by rank, like shard_batch."""
    specs = _auto_specs(tree, graph_axis, data_axis, dense_rank=5)
    return _put_specs(tree, mesh, specs, prefix=(None,))
