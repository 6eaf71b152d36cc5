"""Graph containers and static-shape batching for TPU.

The reference batches variable-size crystal graphs by concatenation with a
``crystal_atom_idx`` range list and a dense [N, M] neighbor layout
(SURVEY.md §2 components 5-6). TPU/XLA wants static shapes, so this module
uses the idiomatic flat-COO design instead (SURVEY.md §7 phase 2):

- ``CrystalGraph``: one featurized crystal, host-side numpy, flat edge list.
- ``GraphBatch``: many crystals packed into fixed-capacity node/edge/graph
  slots with masks — a jraph-``GraphsTuple``-like pytree (jraph is not
  installed). Padding edges point at the LAST node slot and are masked;
  padding nodes belong to graph slot 0 and are masked.

  Invariant: ``centers`` is non-decreasing — ENFORCED by ``pack_graphs``
  (edges are stable-sorted by center per graph at pack time; node offsets
  grow monotonically across graphs; padding edges target the last slot).
  The jitted aggregation can therefore pass ``indices_are_sorted=True`` to
  XLA's scatter — an unchecked promise on TPU — and skip a device sort
  (ops/segment.py).
- bucketed capacity selection (geometric growth) to bound XLA recompiles
  while keeping padding waste low (SURVEY.md §5 "long-context analog").
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Sequence

import numpy as np
from flax import struct

from cgnn_tpu.data import invariants


class TransposeOverflowError(ValueError):
    """A batch's two-tier transpose overflow exceeded ``over_cap``.

    ``over_cap`` is sized statistically (``overflow_cap``: mean + 3 sigma
    of shuffle-composition variance), so shuffled runs that repack every
    epoch can hit this on a tail batch deep into a long job.
    ``batch_iterator`` catches THIS TYPE and splits the offending batch
    (same compiled shape); direct ``pack_graphs`` callers see the raise.
    """


class TransposeRunError(ValueError):
    """A node's run in the two-tier transpose overflow list is longer than
    ``run_cap`` (``overflow_run_cap``: the data set's largest in-degree
    beyond ``dense_m``). The backward sums each node's run looking back
    ``run_cap - 1`` entries, a reach fixed when the program is compiled
    (ops/segment.py _run_totals), so a longer run would lose gradient: it
    raises at pack time.
    Splitting the batch cannot cure it (a run belongs to one graph); it
    means ``run_cap`` was sized from other graphs than are being packed.
    """


@dataclasses.dataclass
class CrystalGraph:
    """One featurized crystal (host-side, numpy)."""

    atom_fea: np.ndarray  # [N, D] float32
    edge_fea: np.ndarray  # [E, G] float32 (Gaussian-expanded distances)
    centers: np.ndarray  # [E] int32 — receiving atom i
    neighbors: np.ndarray  # [E] int32 — source atom j
    target: np.ndarray  # [T] float32
    cif_id: str = ""
    # geometry (kept for the differentiable force path — SURVEY.md §7 phase 7)
    positions: np.ndarray | None = None  # [N, 3] cartesian
    lattice: np.ndarray | None = None  # [3, 3]
    offsets: np.ndarray | None = None  # [E, 3] int32 periodic images
    distances: np.ndarray | None = None  # [E] raw distances
    target_mask: np.ndarray | None = None  # [T] 1.0 where label present
    forces: np.ndarray | None = None  # [N, 3] per-atom force labels (MD17)
    # atomic numbers (kept with geometry): the raw wire format is
    # (positions, lattice, species), so a geometry-carrying graph can be
    # converted back to wire form (data/rawbatch.raw_from_graph)
    numbers: np.ndarray | None = None  # [N] int32

    @property
    def num_nodes(self) -> int:
        return len(self.atom_fea)

    @property
    def num_edges(self) -> int:
        return len(self.centers)


class GraphBatch(struct.PyTreeNode):
    """Fixed-capacity packed batch of graphs (device-side pytree)."""

    nodes: Any  # [Ncap, D] f32
    edges: Any  # [Ecap, G] f32 (COO) / [Ncap, M, G] (dense layout)
    centers: Any  # [Ecap] i32 (receiving node slot)
    neighbors: Any  # [Ecap] i32 (source node slot)
    node_graph: Any  # [Ncap] i32 (graph slot of each node)
    node_mask: Any  # [Ncap] f32 (1 = real)
    edge_mask: Any  # [Ecap] f32
    graph_mask: Any  # [Gcap] f32
    targets: Any  # [Gcap, T] f32
    target_mask: Any  # [Gcap, T] f32 (multi-task missing labels)
    # optional geometry for the force head; zeros when unused
    positions: Any  # [Ncap, 3] f32
    lattices: Any  # [Gcap, 3, 3] f32
    edge_offsets: Any  # [Ecap, 3] f32
    node_targets: Any  # [Ncap, 3] f32 per-atom force labels; zeros when unused
    # transpose of the neighbor gather (dense layout only, else None):
    # row j lists the edge slots e with neighbors[e] == j, so the gather's
    # backward becomes gather(ct, in_slots) + masked sum — a dense reduce —
    # instead of an XLA scatter-add (ops/segment.py gather_slot_major)
    in_slots: Any = None  # [Ncap, In] i32 edge-slot indices
    in_mask: Any = None  # [Ncap, In] u8 (1 = real incoming edge)
    # two-tier transpose overflow (pack_graphs over_cap): when in_slots is
    # sized [Ncap, M] (tier 1 = first M incoming edges; mean in-degree == M
    # but max can be ~2M), the ~7% of edges beyond rank M land here as a
    # node-sorted list: each overflowing node (~40% of atoms) owns one RUN
    # of at most Kcap entries. The backward gathers the list's rows, sums
    # every run in place and gathers each node's total through over_last
    # (ops/segment.py) — so tier 1 moves no padding bytes (measured: the
    # [N, 2M] single-tier gather was the largest op of the whole step, half
    # padding) and no direction of the conv holds a scatter
    over_slots: Any = None  # [Ocap] i32 edge-slot indices (real: a prefix)
    over_nodes: Any = None  # [Ocap] i32 neighbor node (non-decreasing)
    over_last: Any = None  # [Ncap] i32 last entry of the node's run; Ocap
    #   (out of range: reads as a zero row) for a node that owns none
    over_runs: Any = None  # [Kcap] i32 runs of length k+1 in this batch;
    #   its LENGTH is the run capacity the program is compiled for

    @property
    def node_capacity(self) -> int:
        return self.nodes.shape[0]

    @property
    def edge_capacity(self) -> int:
        # dense layout stores edges pre-shaped [Ncap, M, G] (the device
        # [E, G] -> [N, M, G] reshape is a measured 0.34 ms/step relayout
        # under the epoch scan); COO keeps the flat [Ecap, G]
        if np.ndim(self.edges) == 3:
            return self.edges.shape[0] * self.edges.shape[1]
        return self.edges.shape[0]

    @property
    def graph_capacity(self) -> int:
        return self.targets.shape[0]

    def num_real_graphs(self) -> Any:
        return self.graph_mask.sum()

    @property
    def flat_edges(self) -> Any:
        """Edge features viewed [Ecap, G] regardless of storage layout —
        the ONE place that knows the dense layout's [Ncap, M, G] shape
        (host-side numpy view; on device this reshape is a relayout)."""
        e = self.edges
        return e.reshape(-1, np.shape(e)[-1]) if np.ndim(e) == 3 else e


# the transpose mapping's members, in the order transpose_slots returns them
TRANSPOSE_FIELDS = ("in_slots", "in_mask", "over_slots", "over_nodes",
                    "over_last", "over_runs")


def dense_neighbor_views(
    g: CrystalGraph, m: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Flat COO graph -> the lineage's dense per-node neighbor arrays:
    (nbr_fea [N, M, G], nbr_idx [N, M] int64, mask [N, M] f32).

    Padding slots are masked self-loops. This is the ONE definition of the
    dense-slot assignment (k-th edge of center c -> slot (c, k), edges in
    center-sorted order) shared by the torch-oracle parity harness and
    tests — pack_graphs' dense layout uses the same rule batch-wide.
    """
    n = g.num_nodes
    counts = np.bincount(g.centers, minlength=n)
    if counts.max(initial=0) > m:
        raise ValueError(f"a node has {counts.max()} edges > M={m}")
    within = np.arange(g.num_edges) - np.repeat(
        np.cumsum(counts) - counts, counts
    )
    nbr = np.zeros((n, m, g.edge_fea.shape[1]), np.float32)
    idx = np.tile(np.arange(n, dtype=np.int64)[:, None], (1, m))
    mask = np.zeros((n, m), np.float32)
    nbr[g.centers, within] = g.edge_fea
    idx[g.centers, within] = g.neighbors
    mask[g.centers, within] = 1.0
    return nbr, idx, mask


def batch_shape_key(batch) -> tuple:
    """Hashable key identifying a batch's full compiled shape — the ONE
    definition shared by every shape-grouping consumer (ScanEpochDriver,
    parallel_batches); a new shape-bearing GraphBatch field belongs here,
    not in per-caller copies."""
    if hasattr(batch, "atom_idx"):  # CompactBatch (duck-typed: no cycle)
        from cgnn_tpu.data.compact import compact_shape_key

        return compact_shape_key(batch)
    if hasattr(batch, "tokens"):  # TokenBatch (data/tokens.py)
        from cgnn_tpu.data.tokens import token_shape_key

        return token_shape_key(batch)
    return (
        np.shape(batch.nodes),
        # dtype too: f32 and bf16 edge batches with identical shapes must
        # not be np.stack-ed together (silent upcast + mixed-precision
        # mix). Read the attribute, NOT np.asarray(...): the batch may be
        # device-resident and asarray would fetch the whole tensor.
        np.shape(batch.edges),
        str(batch.edges.dtype),
        None if batch.in_slots is None else np.shape(batch.in_slots),
        None if batch.over_slots is None else np.shape(batch.over_slots),
        None if batch.over_runs is None else np.shape(batch.over_runs),
    )


def max_in_degree(graphs: Sequence[CrystalGraph]) -> int:
    """Largest per-node incoming-edge count over ``graphs`` (memoized).

    In-degree (how many other atoms list atom j among their ``max_num_nbr``
    nearest) is not bounded by ``max_num_nbr``: a central atom in an open
    cell can be "nearest" to many. The transpose-slot capacity must cover
    the observed maximum; compute it once per dataset (results are cached
    on each CrystalGraph) and round up for sublane alignment.
    """
    worst = 0
    for g in graphs:
        d = getattr(g, "_max_in_degree", None)
        if d is None:
            d = (
                int(np.bincount(g.neighbors, minlength=g.num_nodes).max())
                if g.num_edges
                else 0
            )
            g._max_in_degree = d
        worst = max(worst, d)
    return worst


def in_degree_cap(graphs: Sequence[CrystalGraph]) -> int:
    """Transpose-slot capacity for a dataset: max in-degree, 8-aligned."""
    return max(8, -(-max_in_degree(graphs) // 8) * 8)


def overflow_cap(
    graphs: Sequence[CrystalGraph], graph_cap: int, dense_m: int
) -> int:
    """Static capacity for the two-tier transpose overflow list.

    Overflow per graph = sum over nodes of max(in_degree - M, 0), cached
    per graph. A batch of up to ``graph_cap`` graphs needs about
    graph_cap * mean; 3 sigma * sqrt(graph_cap) covers shuffle composition
    variance and the per-graph max guards small batches. Exceeding this at
    pack time raises loudly (pack_graphs), never truncates.
    """
    per_graph = []
    for g in graphs:
        o = getattr(g, "_overflow_" + str(dense_m), None)
        if o is None:
            o = (
                int(
                    np.maximum(
                        np.bincount(g.neighbors, minlength=g.num_nodes)
                        - dense_m,
                        0,
                    ).sum()
                )
                if g.num_edges
                else 0
            )
            setattr(g, "_overflow_" + str(dense_m), o)
        per_graph.append(o)
    per_graph = np.asarray(per_graph, np.float64)
    need = graph_cap * per_graph.mean() + 3.0 * per_graph.std() * np.sqrt(
        graph_cap
    )
    return _align8(int(max(need, per_graph.max(), 8)))


def overflow_run_cap(graphs: Sequence[CrystalGraph], dense_m: int) -> int:
    """Static capacity for ONE node's run in the overflow list: the data
    set's (8-aligned) largest in-degree beyond tier 1's ``dense_m``. A
    property of the data set like ``overflow_cap`` (the backward's reach
    over the list is compiled from it); a longer run raises
    ``TransposeRunError`` at pack time."""
    return max(in_degree_cap(graphs) - dense_m, 1)


def overflow_rows(batch) -> int:
    """Real entries in ``batch``'s overflow list (its prefix), read off the
    run counts; over every leading stack axis (steps, chips)."""
    runs = np.asarray(batch.over_runs)
    return int((runs * np.arange(1, runs.shape[-1] + 1)).sum())


def round_to_bucket(n: int, minimum: int = 64, growth: float = 1.3) -> int:
    """Smallest capacity in the geometric bucket ladder that fits ``n``.

    Geometric buckets bound the number of distinct compiled shapes to
    O(log(max/min) / log(growth)) while wasting at most (growth-1) padding.
    """
    if n <= minimum:
        return minimum
    steps = math.ceil(math.log(n / minimum) / math.log(growth))
    return int(math.ceil(minimum * growth**steps))


def pack_graphs(
    graphs: Sequence[CrystalGraph],
    node_cap: int,
    edge_cap: int,
    graph_cap: int,
    num_targets: int | None = None,
    dense_m: int | None = None,
    in_cap: int | None = None,
    over_cap: int | None = None,
    edge_dtype=np.float32,
    run_cap: int | None = None,
) -> GraphBatch:
    """Concatenate graphs into one fixed-capacity GraphBatch (numpy).

    ``dense_m=M`` activates the DENSE SLOT layout: node slot ``n`` owns edge
    slots ``[n*M, (n+1)*M)`` (its real edges first, masked padding after),
    requiring ``edge_cap == node_cap * M``. Every flat-COO invariant still
    holds (centers non-decreasing, masks zero on padding), so all existing
    consumers work unchanged — but a model built with ``dense_m=M`` can
    reshape the edge axis to [N, M] and aggregate messages with a plain
    sum over M instead of a segment-sum: on TPU the XLA scatter behind
    segment ops runs ~50x below HBM bandwidth, while a dense reduction is
    a fused full-speed reduce, and the per-edge v_i gather becomes a
    broadcast (measured: see models/cgcnn.py).

    ``in_cap`` (dense layout only) additionally fills ``in_slots``/
    ``in_mask`` — the transpose of the neighbor gather, sized for a maximum
    per-node in-degree of ``in_cap`` (see ``in_degree_cap``) — making the
    gather's *backward* scatter-free too (ops/segment.py gather_slot_major).

    ``over_cap`` selects the TWO-TIER transpose instead (exclusive with
    ``in_cap``): tier 1 is ``in_slots`` at width ``dense_m`` (each node's
    first M incoming edges — zero padding bytes at mean in-degree M), and
    the ~7% of edges with within-neighbor rank >= M go to the node-sorted
    ``over_slots``/``over_nodes`` overflow list (capacity ``over_cap``, see
    ``overflow_cap``; overflowing it raises, never truncates). ~40% of the
    atoms of an MP-like crystal own a run of it, 1 to ~10 entries long;
    ``over_last`` points every node at its run's last entry, where the
    backward leaves the run's sum, and ``over_runs`` (``run_cap`` long,
    see ``overflow_run_cap``; ``None`` sizes it from this batch alone)
    counts the runs by length. A run longer than ``run_cap`` raises
    ``TransposeRunError``.
    """
    if not graphs:
        raise ValueError("cannot pack an empty graph list")
    if dense_m is not None and edge_cap != node_cap * dense_m:
        raise ValueError(
            f"dense layout requires edge_cap == node_cap * dense_m "
            f"({node_cap} * {dense_m} != {edge_cap})"
        )
    n_graphs = len(graphs)
    total_nodes = sum(g.num_nodes for g in graphs)
    total_edges = sum(g.num_edges for g in graphs)
    if n_graphs > graph_cap or total_nodes > node_cap or (
        dense_m is None and total_edges > edge_cap
    ):
        raise ValueError(
            f"batch ({n_graphs} graphs, {total_nodes} nodes, {total_edges} edges)"
            f" exceeds capacity ({graph_cap}, {node_cap}, {edge_cap})"
        )
    node_dim = graphs[0].atom_fea.shape[1]
    edge_dim = graphs[0].edge_fea.shape[1]
    tdim = num_targets or int(np.atleast_1d(graphs[0].target).shape[0])

    nodes = np.zeros((node_cap, node_dim), np.float32)
    # edge features are the largest staged tensor (G floats/edge); bf16
    # storage (train.py --bf16, bench) halves their HBM footprint and
    # per-step read bytes — the model casts to its compute dtype anyway
    edges = np.zeros((edge_cap, edge_dim), edge_dtype)
    if dense_m is None:
        # padding edges point at the last node slot: keeps `centers` sorted
        # (see module docstring) and their masked zero messages harmless
        centers = np.full(edge_cap, node_cap - 1, np.int32)
        neighbors = np.full(edge_cap, node_cap - 1, np.int32)
    else:
        # dense layout: slot k belongs to node k // M; padding slots are
        # masked self-loops on their owning node (sortedness preserved)
        centers = (np.arange(edge_cap, dtype=np.int32) // dense_m).astype(
            np.int32
        )
        neighbors = centers.copy()
    node_graph = np.zeros(node_cap, np.int32)
    node_mask = np.zeros(node_cap, np.float32)
    edge_mask = np.zeros(edge_cap, np.float32)
    graph_mask = np.zeros(graph_cap, np.float32)
    targets = np.zeros((graph_cap, tdim), np.float32)
    target_mask = np.zeros((graph_cap, tdim), np.float32)
    positions = np.zeros((node_cap, 3), np.float32)
    lattices = np.zeros((graph_cap, 3, 3), np.float32)
    edge_offsets = np.zeros((edge_cap, 3), np.float32)
    node_targets = np.zeros((node_cap, 3), np.float32)

    # ---- vectorized packing: one pass of concatenated arrays per field.
    # The per-graph Python loop this replaces was the last major
    # single-core host stage at MP-146k scale (84 s of a 656 s first
    # epoch: ~30 small numpy calls x 131k graphs); concatenation turns it
    # into ~15 C-level ops per batch regardless of graph count.
    nn_arr = np.fromiter((g.num_nodes for g in graphs), np.int64, n_graphs)
    ne_arr = np.fromiter((g.num_edges for g in graphs), np.int64, n_graphs)
    node_offs = np.zeros(n_graphs + 1, np.int64)
    np.cumsum(nn_arr, out=node_offs[1:])
    edge_offs = np.zeros(n_graphs + 1, np.int64)
    np.cumsum(ne_arr, out=edge_offs[1:])

    np.concatenate([g.atom_fea for g in graphs], axis=0,
                   out=nodes[:total_nodes])
    node_graph[:total_nodes] = np.repeat(
        np.arange(n_graphs, dtype=np.int32), nn_arr
    )
    node_mask[:total_nodes] = 1.0

    # global centers with node offsets applied: per-graph value ranges are
    # disjoint and increasing, so the batch vector is non-decreasing IFF
    # every graph is center-sorted, and ONE global stable argsort restores
    # per-graph center order without mixing graphs
    e_node_off = np.repeat(node_offs[:-1], ne_arr)
    gcent = np.concatenate([g.centers for g in graphs]).astype(np.int64)
    gcent += e_node_off
    gnbr = np.concatenate([g.neighbors for g in graphs]).astype(np.int64)
    gnbr += e_node_off
    if np.all(gcent[1:] >= gcent[:-1]):
        order = None  # knn_neighbor_list output is already center-sorted
    else:
        order = np.argsort(gcent, kind="stable")
        gcent, gnbr = gcent[order], gnbr[order]
    efea = np.concatenate([g.edge_fea for g in graphs], axis=0)
    if order is not None:
        efea = efea[order]

    if dense_m is None:
        slots = slice(0, total_edges)
        edges[slots] = efea
        edge_mask[slots] = 1.0
    else:
        counts = np.bincount(gcent, minlength=node_cap)
        worst = int(counts.max(initial=0))
        if worst > dense_m:
            bad = int(np.argmax(counts))
            gi = int(np.searchsorted(node_offs, bad, side="right")) - 1
            raise ValueError(
                f"graph {graphs[gi].cif_id!r} has a node with {worst} "
                f"edges > dense_m={dense_m}; featurize with "
                f"max_num_nbr <= dense_m"
            )
        # edge k's within-center rank: its position minus its center's
        # first position in the center-sorted edge ordering
        within = np.arange(total_edges) - (np.cumsum(counts) - counts)[gcent]
        slots = gcent * dense_m + within
        # fill the [node_cap * M] slot grid by GATHER, not scatter: slot
        # (n, k) takes sorted edge starts[n] + k when k < counts[n], else
        # a sentinel zero row — a row-scatter at these sizes ran ~4x
        # slower than take() and needed a separate edge_mask scatter
        starts = np.cumsum(counts) - counts
        src = starts[:, None] + np.arange(dense_m)
        grid_valid = np.arange(dense_m) < counts[:, None]
        np.copyto(src, total_edges, where=~grid_valid)
        efea_pad = np.empty((total_edges + 1, edge_dim), edge_dtype)
        efea_pad[:total_edges] = efea  # casts to edge_dtype in one pass
        efea_pad[total_edges] = 0.0  # sentinel zero row for padding slots
        np.take(efea_pad, src.ravel(), axis=0, out=edges, mode="clip")
        edge_mask[:] = grid_valid.ravel()
    if dense_m is None:
        centers[slots] = gcent.astype(np.int32)
    # (dense: real slot s has centers[s] == s // M by construction — the
    # arange//M initialization already equals the scatter)
    neighbors[slots] = gnbr.astype(np.int32)

    graph_mask[:n_graphs] = 1.0
    tgt = [np.atleast_1d(np.asarray(g.target, np.float32)) for g in graphs]
    if all(len(t) == len(tgt[0]) for t in tgt):
        tw = len(tgt[0])
        targets[:n_graphs, :tw] = np.stack(tgt)
        masks = [g.target_mask for g in graphs]
        if all(m is None for m in masks):
            target_mask[:n_graphs, :tw] = 1.0
        else:
            # broadcast_to: a narrower mask (e.g. a scalar ones(1) on a
            # width-3 target) broadcasts across the width, matching the
            # old per-graph `target_mask[gi, :tw] = mask` assignment
            target_mask[:n_graphs, :tw] = np.stack([
                np.ones(tw, np.float32) if m is None
                else np.broadcast_to(np.atleast_1d(m), (tw,))
                for m in masks
            ])
    else:  # ragged target widths (unusual): per-graph fallback
        for gi, (g, t) in enumerate(zip(graphs, tgt)):
            targets[gi, : len(t)] = t
            if g.target_mask is not None:
                target_mask[gi, : len(t)] = np.atleast_1d(g.target_mask)
            else:
                target_mask[gi, : len(t)] = 1.0

    def _per_graph_edge_slots(gi: int):
        # the global sort keeps graphs contiguous (disjoint gcent ranges),
        # so graph gi's edges occupy the same [edge_offs] range after it
        s = slice(edge_offs[gi], edge_offs[gi + 1])
        return slots[s] if dense_m is not None else s

    have_pos = [g.positions is not None for g in graphs]
    if all(have_pos):
        np.concatenate([g.positions for g in graphs], axis=0,
                       out=positions[:total_nodes])
    elif any(have_pos):
        for gi, g in enumerate(graphs):
            if g.positions is not None:
                positions[node_offs[gi] : node_offs[gi + 1]] = g.positions
    have_lat = [g.lattice is not None for g in graphs]
    if all(have_lat):
        lattices[:n_graphs] = np.stack([g.lattice for g in graphs])
    elif any(have_lat):
        for gi, g in enumerate(graphs):
            if g.lattice is not None:
                lattices[gi] = g.lattice
    have_off = [g.offsets is not None for g in graphs]
    if all(have_off) and total_edges:
        goff = np.concatenate([g.offsets for g in graphs], axis=0)
        edge_offsets[slots] = goff if order is None else goff[order]
    elif any(have_off):
        for gi, g in enumerate(graphs):
            if g.offsets is not None and g.num_edges:
                o = g.offsets
                if order is not None:
                    # recover this graph's local order from the global sort
                    lo = np.argsort(g.centers, kind="stable")
                    o = o[lo]
                edge_offsets[_per_graph_edge_slots(gi)] = o
    have_f = [g.forces is not None for g in graphs]
    if all(have_f):
        np.concatenate([g.forces for g in graphs], axis=0,
                       out=node_targets[:total_nodes])
    elif any(have_f):
        for gi, g in enumerate(graphs):
            if g.forces is not None:
                node_targets[node_offs[gi] : node_offs[gi + 1]] = g.forces

    mapping = (None,) * len(TRANSPOSE_FIELDS)
    if in_cap is not None and over_cap is not None:
        raise ValueError("in_cap (single-tier) and over_cap (two-tier) are "
                         "mutually exclusive")
    if in_cap is not None or over_cap is not None:
        if dense_m is None:
            raise ValueError("transpose slots require the dense layout "
                             "(dense_m)")
        mapping = transpose_slots(
            neighbors, edge_mask > 0, node_cap, dense_m, in_cap,
            over_cap, run_cap,
        )

    return GraphBatch(
        nodes=nodes,
        edges=(edges.reshape(node_cap, dense_m, edge_dim)
               if dense_m is not None else edges),
        centers=centers,
        neighbors=neighbors,
        node_graph=node_graph,
        node_mask=node_mask,
        edge_mask=edge_mask,
        graph_mask=graph_mask,
        targets=targets,
        target_mask=target_mask,
        positions=positions,
        lattices=lattices,
        edge_offsets=edge_offsets,
        node_targets=node_targets,
        **dict(zip(TRANSPOSE_FIELDS, mapping)),
    )


def transpose_slots(
    neighbors: np.ndarray,
    edge_real: np.ndarray,
    node_cap: int,
    dense_m: int,
    in_cap: int | None,
    over_cap: int | None,
    run_cap: int | None = None,
) -> tuple:
    """Transpose of the neighbor gather: group real edge slots by their
    neighbor node (the scatter-free-backward mapping; see pack_graphs).

    ``neighbors`` [Ecap] i32, ``edge_real`` [Ecap] bool. Returns the
    ``TRANSPOSE_FIELDS`` ``(in_slots, in_mask, over_slots, over_nodes,
    over_last, over_runs)`` — the last four ``None`` unless ``over_cap``
    selects the two-tier layout.
    Stable-sorting by neighbor + a cumcount gives each real edge its
    row-local position; padding entries stay masked at slot 0.
    Shared by ``pack_graphs`` and the compact-staging packer
    (data/compact.py), which must agree exactly.
    """
    real = np.nonzero(edge_real)[0]
    nb = neighbors[real]
    counts = np.bincount(nb, minlength=node_cap)
    order = np.argsort(nb, kind="stable")
    tier = dense_m if over_cap is not None else in_cap
    if over_cap is None and len(real) and counts.max() > tier:
        raise ValueError(
            f"a node has in-degree {counts.max()} > in_cap={in_cap}; "
            f"size in_cap with in_degree_cap(graphs)"
        )
    # fill by gather (same pattern as the dense edge grid in pack_graphs):
    # row j's k-th incoming edge is the neighbor-sorted edge at
    # starts[j] + k when k < in-degree, else the sentinel zero
    real_sorted = real[order].astype(np.int32)
    starts = np.cumsum(counts) - counts
    src = starts[:, None] + np.arange(tier)
    tier_valid = np.arange(tier) < counts[:, None]
    np.copyto(src, len(real), where=~tier_valid)
    pad = np.concatenate([real_sorted, np.zeros(1, np.int32)])
    # stored FLAT [node_cap * tier]: the backward's gather wants flat
    # indices, and flattening the 2-D array on DEVICE costs a tiled->
    # linear relayout measured at 0.75 ms/step under the epoch scan
    # (s32 [1, N, In] slice -> [N*In]); in_mask keeps the 2-D shape
    # for the masked in-degree reduction. uint8 mask: it is only ever
    # cast to the compute dtype on device, and at MP-146k scale a f32
    # mask would stage ~0.5 GB of HBM
    in_slots = np.take(pad, src.ravel(), mode="clip")
    in_mask = tier_valid.astype(np.uint8)
    over_slots = over_nodes = over_last = over_runs = None
    if over_cap is not None:
        # edges with within-neighbor rank >= tier, in sorted positions
        sel2 = np.arange(len(real)) - starts.repeat(counts) >= tier
        k = int(sel2.sum())
        if k > over_cap:
            raise TransposeOverflowError(
                f"batch has {k} transpose-overflow edges > over_cap="
                f"{over_cap}; size over_cap with overflow_cap(graphs)"
            )
        run_len = np.maximum(counts - tier, 0)  # [node_cap]; sums to k
        longest = int(run_len.max(initial=0))
        if run_cap is None:
            run_cap = max(longest, 1)
        if longest > run_cap:
            raise TransposeRunError(
                f"a node has {longest} transpose-overflow edges > run_cap="
                f"{run_cap}; size run_cap with overflow_run_cap(graphs)"
            )
        # the real entries are a prefix, node-sorted, so a node's entries
        # are one run. The padding after them names the LAST node slot
        # (over_nodes stays non-decreasing) and slot 0: its rows are
        # whatever slot 0 holds, and nothing points at them. They may
        # CONTINUE a run of the last node, which the backward allows for by
        # summing each run forwards only: the total at the run's last REAL
        # entry, where over_last points, holds real entries alone.
        over_slots = np.zeros(over_cap, np.int32)
        over_nodes = np.full(over_cap, node_cap - 1, np.int32)
        over_slots[:k] = real_sorted[sel2]
        over_nodes[:k] = nb[order][sel2]
        over_last = np.where(run_len > 0, np.cumsum(run_len) - 1,
                             over_cap).astype(np.int32)
        over_runs = np.bincount(run_len[run_len > 0] - 1,
                                minlength=run_cap).astype(np.int32)
    return in_slots, in_mask, over_slots, over_nodes, over_last, over_runs


def pad_batch(
    graphs: Sequence[CrystalGraph],
    graph_cap: int,
    bucket_min_nodes: int = 64,
    bucket_min_edges: int = 512,
    growth: float = 1.3,
) -> GraphBatch:
    """Pack with bucketed node/edge capacities chosen from the batch content."""
    node_cap = round_to_bucket(
        sum(g.num_nodes for g in graphs), bucket_min_nodes, growth
    )
    edge_cap = round_to_bucket(
        sum(g.num_edges for g in graphs), bucket_min_edges, growth
    )
    return pack_graphs(graphs, node_cap, edge_cap, graph_cap)


def capacities_for(
    graphs: Sequence[CrystalGraph],
    batch_size: int,
    headroom: float = 1.15,
    dense_m: int | None = None,
    snug: bool = False,
) -> tuple[int, int]:
    """Pick one (node_cap, edge_cap) for a dataset so every shuffled batch
    fits: batch_size * max-per-graph sizes would be safe but wasteful; use
    mean + headroom over the largest observed, bucketed. Fine ladder floors
    (16/128) keep small-graph buckets tight — a 64-node floor would cap
    padding efficiency at ~60% for 8x5-atom batches.

    ``snug=True`` returns exact 8-aligned capacities at ``batch_size *
    mean`` with NO headroom and NO ladder rounding — for the
    fill-to-capacity packing mode (``batch_iterator(snug=True)``), where
    batches close on capacity rather than on graph count, so headroom
    would only manufacture padding. The number of compiled shapes is
    unchanged (one per call / per bucket); only cross-dataset shape reuse
    is given up. Measured on the MP-like distribution this lifts padding
    efficiency from ~0.69 (1 / (1.15 headroom x ~1.3 ladder step)) to
    >=0.97.

    With ``dense_m`` the edge capacity is exactly ``node_cap * dense_m``
    (the dense slot layout, pack_graphs)."""
    nodes = np.array([g.num_nodes for g in graphs])
    if snug:
        # balance capacity to the BATCH COUNT: with B = ceil(n/batch_size)
        # batches, the best possible efficiency is total/(B*cap), so size
        # cap at total/B plus a packing margin (greedy fill wastes ~mean/2
        # per batch; mean+std covers it with room for shuffle variance)
        # instead of batch_size*mean — otherwise the last batch per epoch
        # is fractionally full and costs ~1/(2B) efficiency by itself.
        b_count = max(1, math.ceil(len(graphs) / batch_size))
        margin = nodes.mean() + nodes.std()
        node_cap = _align8(
            int(max(nodes.sum() / b_count + margin, nodes.max()))
        )
        if dense_m is not None:
            return node_cap, node_cap * dense_m
        edges = np.array([g.num_edges for g in graphs])
        margin_e = edges.mean() + edges.std()
        edge_cap = _align8(
            int(max(edges.sum() / b_count + margin_e, edges.max()))
        )
        return node_cap, edge_cap
    node_cap = round_to_bucket(
        int(max(batch_size * nodes.mean() * headroom, nodes.max())), minimum=16
    )
    if dense_m is not None:
        return node_cap, node_cap * dense_m
    edges = np.array([g.num_edges for g in graphs])
    edge_cap = round_to_bucket(
        int(max(batch_size * edges.mean() * headroom, edges.max())), minimum=128
    )
    return node_cap, edge_cap


def _align8(n: int) -> int:
    """Round up to a multiple of 8 (TPU sublane alignment)."""
    return max(8, -(-n // 8) * 8)


def graph_cap_for(batch_size: int) -> int:
    """Graph-slot capacity for fill-to-capacity packing: ``batch_size``
    plus ~12% slack (8-aligned) so node/edge capacity — not the graph
    count — is what closes a typical batch. Graph slots are cheap
    ([G, T] targets + [G, 3, 3] lattices); node/edge slots are not."""
    return batch_size + _align8(max(8, batch_size // 8))


@dataclasses.dataclass
class PaddingStats:
    """Accumulates padding efficiency over an epoch of packed batches.

    Efficiency = real slots / allocated slots; the figure the bucketing
    policy optimizes (SURVEY.md §5 long-context analog, §7 hard parts #1).
    """

    real_nodes: int = 0
    real_edges: int = 0
    slot_nodes: int = 0
    slot_edges: int = 0
    batches: int = 0
    shapes: set = dataclasses.field(default_factory=set)
    # per compiled (node_cap, edge_cap) shape: [real_nodes, real_edges,
    # slot_nodes, slot_edges, batches] — the per-bucket breakdown the
    # telemetry gauges report (observe.gauges.padding_gauges)
    per_shape: dict = dataclasses.field(default_factory=dict)

    def update(self, batch: GraphBatch) -> None:
        real_n = int(np.asarray(batch.node_mask).sum())
        real_e = int(np.asarray(batch.edge_mask).sum())
        self.real_nodes += real_n
        self.real_edges += real_e
        self.slot_nodes += batch.node_capacity
        self.slot_edges += batch.edge_capacity
        self.batches += 1
        shape = (batch.node_capacity, batch.edge_capacity)
        self.shapes.add(shape)
        acc = self.per_shape.setdefault(shape, [0, 0, 0, 0, 0])
        acc[0] += real_n
        acc[1] += real_e
        acc[2] += batch.node_capacity
        acc[3] += batch.edge_capacity
        acc[4] += 1

    @property
    def node_efficiency(self) -> float:
        return self.real_nodes / max(self.slot_nodes, 1)

    @property
    def edge_efficiency(self) -> float:
        return self.real_edges / max(self.slot_edges, 1)

    def wrap(self, iterator):
        """Pass batches through while accumulating stats."""
        for b in iterator:
            self.update(b)
            yield b

    def summary(self) -> str:
        return (
            f"padding efficiency: nodes {self.node_efficiency:.1%}, "
            f"edges {self.edge_efficiency:.1%} over {self.batches} batches, "
            f"{len(self.shapes)} compiled shape(s)"
        )


def assign_size_buckets(
    graphs: Sequence[CrystalGraph], n_buckets: int
) -> np.ndarray:
    """Bucket index per graph by node-count quantiles ([len(graphs)] int)."""
    sizes = np.array([g.num_nodes for g in graphs])
    if n_buckets <= 1:
        return np.zeros(len(graphs), np.int64)
    cuts = np.quantile(sizes, np.linspace(0, 1, n_buckets + 1)[1:-1])
    return np.searchsorted(cuts, sizes, side="left")


def bucketed_batch_iterator(
    graphs: Sequence[CrystalGraph],
    batch_size: int,
    n_buckets: int,
    shuffle: bool = False,
    rng: np.random.Generator | None = None,
    stats: PaddingStats | None = None,
    headroom: float = 1.15,
    dense_m: int | None = None,
    in_cap: int | None = None,
    snug: bool = False,
    per_bucket_in_cap: bool = False,
    edge_dtype=np.float32,
    pack_fn=None,
):
    """Yield batches using per-size-class static capacities.

    Graphs are partitioned into ``n_buckets`` size classes (node-count
    quantiles); each class batches with its own (node_cap, edge_cap), so the
    jitted step compiles at most ``n_buckets`` distinct shapes while padding
    tracks each class's actual size distribution — the multi-bucket
    "long-context" policy for mixed MP+OC20 datasets (SURVEY.md §5).
    Batches from different classes interleave (weighted random under
    ``shuffle``) to avoid size-ordered epochs.

    ``snug`` selects fill-to-capacity packing per bucket (see
    ``batch_iterator``). ``per_bucket_in_cap`` sizes the transpose-slot
    capacity from each bucket's own worst in-degree instead of the
    dataset-wide maximum — one skewed graph (an adsorbate nearest to dozens
    of slab atoms, the OC20 geometry) then inflates only its own bucket's
    ``in_slots`` bytes, at the cost of no extra compiles (bucket shapes
    already differ).
    """
    rng = rng or np.random.default_rng()
    bucket_of = assign_size_buckets(graphs, n_buckets)
    # transpose slots default to the two-tier layout with ONE dataset-wide
    # overflow capacity: per-bucket over_caps would split otherwise-equal
    # bucket shapes into distinct compiled shapes (and strand DP device
    # groups — two buckets of small graphs often share (node_cap, edge_cap)
    # after alignment). per_bucket_in_cap forces legacy single-tier slots
    # sized by each bucket's own worst in-degree.
    over_cap = run_cap = None
    if dense_m is not None and in_cap is None and not per_bucket_in_cap:
        # one uniform capacity sized by the WORST bucket: a large-graph
        # bucket's batches carry far more overflow than the dataset mean
        # (bimodal mixes), and per-bucket caps would split otherwise-equal
        # bucket shapes; the waste is a few KB of i32 per batch
        run_cap = overflow_run_cap(graphs, dense_m)
        gcap = graph_cap_for(batch_size) if snug else batch_size
        over_cap = max(
            overflow_cap(
                [graphs[int(i)] for i in np.nonzero(bucket_of == b)[0]],
                gcap, dense_m,
            )
            for b in range(int(bucket_of.max()) + 1)
            if np.any(bucket_of == b)
        )
    iters, weights = [], []
    for b in range(int(bucket_of.max()) + 1):
        idxs = np.nonzero(bucket_of == b)[0]
        if len(idxs) == 0:
            continue
        sub = [graphs[int(i)] for i in idxs]
        nc, ec = capacities_for(sub, batch_size, headroom, dense_m=dense_m,
                                snug=snug)
        b_in_cap = in_cap
        if dense_m is not None and b_in_cap is None and per_bucket_in_cap:
            b_in_cap = in_degree_cap(sub)
        it = batch_iterator(sub, batch_size, nc, ec, shuffle=shuffle, rng=rng,
                            dense_m=dense_m, in_cap=b_in_cap, snug=snug,
                            over_cap=over_cap, run_cap=run_cap,
                            edge_dtype=edge_dtype, pack_fn=pack_fn)
        iters.append(stats.wrap(it) if stats is not None else it)
        weights.append(float(len(idxs)))
    active = list(range(len(iters)))
    w = np.array(weights)
    while active:
        if shuffle and len(active) > 1:
            p = w[active] / w[active].sum()
            pick = int(rng.choice(active, p=p))
        else:
            pick = active[0]
        try:
            yield next(iters[pick])
        except StopIteration:
            active.remove(pick)


def plan_batches(
    graphs: Sequence[CrystalGraph],
    batch_size: int,
    node_cap: int,
    edge_cap: int,
    snug: bool = False,
):
    """Yield ``(start, end)`` index spans over ``graphs`` matching
    ``batch_iterator``'s greedy close condition EXACTLY (no shuffle),
    without packing anything.

    This is the planning half of the parallel ingest pipeline
    (data/pipeline.py): the plan is computed once on the consumer,
    cheap and deterministic, and the spans are handed to a pool of
    packer workers — input order is preserved by construction, so the
    reassembled batches map back to the input the same way the serial
    ``batch_iterator`` loop's would. Oversize graphs raise the same
    error ``batch_iterator`` raises (a plan that silently diverged from
    the packer would break span bookkeeping downstream).
    """
    graph_cap = graph_cap_for(batch_size) if snug else batch_size
    start, nn, ne = 0, 0, 0
    for i, g in enumerate(graphs):
        if g.num_nodes > node_cap or g.num_edges > edge_cap:
            raise ValueError(
                f"graph {g.cif_id!r} ({g.num_nodes} nodes, {g.num_edges} "
                f"edges) exceeds batch capacity ({node_cap}, {edge_cap}); "
                f"increase caps or filter the dataset"
            )
        if i > start and (
            i - start == graph_cap
            or nn + g.num_nodes > node_cap
            or ne + g.num_edges > edge_cap
        ):
            yield start, i
            start, nn, ne = i, 0, 0
        nn += g.num_nodes
        ne += g.num_edges
    if start < len(graphs):
        yield start, len(graphs)


def count_batches(
    graphs: Sequence[CrystalGraph],
    batch_size: int,
    node_cap: int,
    edge_cap: int,
    snug: bool = False,
) -> int:
    """Exact number of batches ``batch_iterator`` yields, without packing.

    ``len(graphs) // batch_size`` undercounts because capacity-filled
    batches split early; LR-milestone step conversion needs the real count.
    Must mirror ``batch_iterator``'s close condition exactly (incl. the
    ``snug`` graph-cap slack).
    """
    graph_cap = graph_cap_for(batch_size) if snug else batch_size
    count, in_bucket, nn, ne = 0, 0, 0, 0
    for g in graphs:
        if in_bucket and (
            in_bucket == graph_cap
            or nn + g.num_nodes > node_cap
            or ne + g.num_edges > edge_cap
        ):
            count += 1
            in_bucket, nn, ne = 0, 0, 0
        in_bucket += 1
        nn += g.num_nodes
        ne += g.num_edges
    return count + (1 if in_bucket else 0)


def _pack_overflow_safe(
    bucket: list,
    node_cap: int,
    edge_cap: int,
    graph_cap: int,
    dense_m,
    in_cap,
    over_cap,
    edge_dtype,
    pack_fn=None,
    run_cap=None,
):
    """pack_graphs, splitting the batch on a two-tier over_cap overrun.

    ``over_cap`` covers mean + 3 sigma of shuffle-composition variance
    (``overflow_cap``), so a tail composition can exceed it after many
    successful epochs. Splitting the offending batch in half re-packs each
    half to the SAME compiled shape (capacities unchanged) — one extra
    partially-filled batch instead of a dead run. A single graph that
    exceeds ``over_cap`` on its own cannot be split and re-raises (it
    indicates over_cap was sized from different graphs than are being
    packed).
    """
    pack = pack_fn or pack_graphs
    kw = {} if over_cap is None else {"run_cap": run_cap}
    try:
        yield pack(bucket, node_cap, edge_cap, graph_cap,
                   dense_m=dense_m, in_cap=in_cap, over_cap=over_cap,
                   edge_dtype=edge_dtype, **kw)
    except TransposeOverflowError:
        if len(bucket) < 2:
            raise
        warnings.warn(
            f"batch of {len(bucket)} graphs exceeded over_cap={over_cap} "
            f"(a 3-sigma shuffle tail); splitting it in half instead of "
            f"aborting the run",
            stacklevel=2,
        )
        mid = len(bucket) // 2
        for half in (bucket[:mid], bucket[mid:]):
            yield from _pack_overflow_safe(
                half, node_cap, edge_cap, graph_cap, dense_m, in_cap,
                over_cap, edge_dtype, pack_fn=pack_fn, run_cap=run_cap)


def batch_iterator(
    graphs: Sequence[CrystalGraph],
    batch_size: int,
    node_cap: int,
    edge_cap: int,
    shuffle: bool = False,
    rng: np.random.Generator | None = None,
    drop_last: bool = False,
    dense_m: int | None = None,
    in_cap: int | None = None,
    snug: bool = False,
    over_cap: int | None = None,
    edge_dtype=np.float32,
    pack_fn=None,
    run_cap: int | None = None,
):
    """Yield fixed-shape GraphBatches of ``batch_size`` graphs each.

    All batches share one (node_cap, edge_cap, graph_cap) shape so the jitted
    train step compiles exactly once. Oversize batches (rare tail events) are
    split greedily rather than dropped. ``dense_m`` selects the dense slot
    layout (see pack_graphs); transpose slots are sized automatically
    (``in_degree_cap``) unless ``in_cap`` is given.

    ``snug=True`` switches to FILL-TO-CAPACITY packing: a batch closes when
    the next graph would overflow node/edge capacity (use with the snug
    capacities from ``capacities_for(snug=True)``), not when it holds
    ``batch_size`` graphs; graph slots get ~12% slack (``graph_cap_for``)
    so capacity is the binding constraint. Padding efficiency becomes
    1 - O(mean_graph / 2 / cap) per batch instead of 1/(headroom x ladder
    step) — measured 0.69 -> >=0.97 on the MP-like distribution.

    Transpose slots (dense layout): ``in_cap=None`` (default) packs the
    TWO-TIER transpose — tier-1 width ``dense_m`` + the overflow list
    sized by ``overflow_cap`` and ``overflow_run_cap`` — for the
    scatter-free backward with no in-degree padding bytes; ``in_cap>0``
    forces the legacy single-tier layout;
    ``in_cap=0`` disables transpose packing (eval-only batches).
    """
    graph_cap = graph_cap_for(batch_size) if snug else batch_size
    if dense_m is not None and in_cap is None and over_cap is None:
        over_cap = overflow_cap(graphs, graph_cap, dense_m)
    if in_cap is not None:
        over_cap = None  # explicit single-tier (or in_cap=0: disabled)
    if over_cap is not None and run_cap is None:
        run_cap = overflow_run_cap(graphs, dense_m)
    in_cap = in_cap or None  # 0 disables (eval-only batches: no backward)
    order = np.arange(len(graphs))
    if shuffle:
        (rng or np.random.default_rng()).shuffle(order)
    bucket: list[CrystalGraph] = []
    nn = ne = 0
    for idx in order:
        g = graphs[int(idx)]
        if g.num_nodes > node_cap or g.num_edges > edge_cap:
            raise ValueError(
                f"graph {g.cif_id!r} ({g.num_nodes} nodes, {g.num_edges} edges) "
                f"exceeds batch capacity ({node_cap}, {edge_cap}); "
                f"increase caps or filter the dataset"
            )
        if bucket and (
            len(bucket) == graph_cap
            or nn + g.num_nodes > node_cap
            or ne + g.num_edges > edge_cap
        ):
            for packed in _pack_overflow_safe(
                    bucket, node_cap, edge_cap, graph_cap, dense_m, in_cap,
                    over_cap, edge_dtype, pack_fn=pack_fn, run_cap=run_cap):
                yield invariants.maybe_check(packed, dense_m)
            bucket, nn, ne = [], 0, 0
        bucket.append(g)
        nn += g.num_nodes
        ne += g.num_edges
    # drop_last drops only an *incomplete* tail (standard loader
    # semantics): fewer than batch_size graphs. Compared against
    # batch_size, NOT graph_cap — under snug packing batches close on
    # capacity and essentially never reach graph_cap's slack, so a
    # graph_cap comparison would silently drop full tails.
    if bucket and (not drop_last or len(bucket) >= batch_size):
        for packed in _pack_overflow_safe(
                bucket, node_cap, edge_cap, graph_cap, dense_m, in_cap,
                over_cap, edge_dtype, pack_fn=pack_fn, run_cap=run_cap):
            yield invariants.maybe_check(packed, dense_m)
