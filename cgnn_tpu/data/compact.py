"""Compact device staging: stage raw atoms + distances, featurize on device.

A packed ``GraphBatch`` stages ~2.2 KB/node: one-hot-style atom rows
([N, 92] f32) and Gaussian-expanded edge features ([N, M, G] f32) dominate.
Both are pure functions of tiny raw data — atom rows are rows of a small
per-dataset vocabulary table, and edge features are a fixed radial basis of
the scalar distance (SURVEY.md §2 components 3-4). ``CompactBatch`` stages
the raw form instead (~180 B/node, ~12x less) and ``make_expander`` rebuilds
the exact ``GraphBatch`` INSIDE the jitted step, where the table gather and
``exp()`` fuse into the surrounding program at negligible cost next to the
conv matmuls.

Why this is the TPU-first shape of the problem:
- the MP-146k device-resident epoch stages ~8.9 GB host->device in its
  first epoch; compact staging cuts that ~12x.
- HBM holds the compact form (~0.7 GB for MP-146k vs ~8.9 GB), so
  device-resident training scales to ~10x larger datasets per chip.
- host packing writes ~12x fewer bytes (the full-fidelity pack is
  page-fault-bound, not compute-bound).

Supported: the dense slot layout (``dense_m``) for energy / band-gap /
multi-task / classification models. The force task recomputes geometry
in-model from positions and does not read staged edge features at all
(models/forcefield.py); it keeps its own staging path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import numpy as np
from flax import struct

from cgnn_tpu.data.featurize import gaussian_expand
from cgnn_tpu.data.graph import TRANSPOSE_FIELDS, GraphBatch, transpose_slots


class CompactUnsupported(ValueError):
    """The dataset cannot be staged compactly (caller should fall back to
    full-fidelity packing — this is a capability probe, not a failure)."""


class AtomVocab:
    """Per-dataset vocabulary of distinct atom-feature rows.

    The reference lineage draws atom features from a fixed per-element
    table (``atom_init.json``; data/elements.py here), so a dataset has at
    most ~100 distinct rows. The vocabulary is recovered from the data
    (hash rows, dedupe) rather than assumed, so any upstream featurizer
    works; datasets with effectively-continuous atom features overflow
    ``max_size`` and raise ``CompactUnsupported``.
    """

    def __init__(self, table: np.ndarray, hash_vec: np.ndarray,
                 hash_order: np.ndarray):
        self.table = table  # [V, D] f32
        self._hash_vec = hash_vec
        self._sorted_hashes = hash_order  # sorted row hashes, index-aligned
        self._sorted_to_idx: np.ndarray | None = None

    @classmethod
    def build(cls, graphs: Sequence, max_size: int = 4096) -> "AtomVocab":
        rng = np.random.default_rng(0x5EED)
        dim = graphs[0].atom_fea.shape[1]
        hv = rng.standard_normal(dim)
        seen: dict[float, np.ndarray] = {}
        for g in graphs:
            h = np.asarray(g.atom_fea, np.float64) @ hv
            # cache per graph: index lookup reuses these (pack time)
            g._vocab_hashes = h
            for hh in np.unique(h):
                if hh not in seen:
                    row = np.asarray(
                        g.atom_fea[np.argmax(h == hh)], np.float32
                    )
                    seen[float(hh)] = row
                    if len(seen) > max_size:
                        raise CompactUnsupported(
                            f"more than {max_size} distinct atom-feature "
                            f"rows; atom features look continuous — use "
                            f"full-fidelity staging"
                        )
        hashes = np.array(sorted(seen))
        table = np.stack([seen[float(h)] for h in hashes])
        return cls(table, hv, hashes)

    @property
    def size(self) -> int:
        return len(self.table)

    def indices(self, g) -> np.ndarray:
        """[N] i32 vocabulary index per atom (cached on the graph);
        verifies exact reconstruction (hash collisions raise loudly)."""
        idx = getattr(g, "_vocab_idx", None)
        if idx is None:
            h = getattr(g, "_vocab_hashes", None)
            if h is None:
                h = np.asarray(g.atom_fea, np.float64) @ self._hash_vec
            idx = np.searchsorted(self._sorted_hashes, h).astype(np.int32)
            if (
                idx.max(initial=0) >= self.size
                or not np.array_equal(
                    self.table[idx], np.asarray(g.atom_fea, np.float32)
                )
            ):
                raise CompactUnsupported(
                    f"graph {g.cif_id!r} has atom rows outside the "
                    f"vocabulary (hash collision or mixed featurizers)"
                )
            g._vocab_idx = idx
            if hasattr(g, "_vocab_hashes"):
                del g._vocab_hashes
        return idx


@dataclasses.dataclass(frozen=True)
class CompactSpec:
    """Everything the expander needs to rebuild GraphBatches on device."""

    vocab: AtomVocab
    gauss_filter: np.ndarray  # [G] f32 mu grid
    gauss_var: float
    dense_m: int
    edge_dtype: Any = np.float32

    def __post_init__(self):
        # identity token for per-graph probe verdicts: a verdict cached
        # under spec A must never be read by spec B (different
        # checkpoint/vocabulary in the same process). The token object
        # is retained by every cache entry that references it, so its
        # identity can never be recycled into a false match.
        object.__setattr__(self, "_probe_token", object())

    def graph_compactable(self, g, atol: float = 1e-5,
                          sample_edges: int = 32) -> bool:
        """Can THIS graph be staged compactly under this spec?

        The dataset-level ``build`` probe validates a sample; serving
        admits arbitrary per-request graphs, so each one is checked
        individually (and the verdict cached on the graph, keyed to this
        spec): raw distances present and consistent, atom rows inside
        the vocabulary, and the stored edge features equal to the
        Gaussian expansion of the distances — so a client-supplied graph
        whose ``edge_fea`` disagrees with its ``distances`` is staged
        full-fidelity instead of silently answered from different edges.
        The feature check verifies an evenly spaced sample of
        ``sample_edges`` edges: featurization mismatches (wrong
        radius/step, different featurizer) are global and any sample
        catches them, while a full O(E x G) expansion per request would
        tax the submit path with a meaningful fraction of the very cost
        compact staging removes. Never raises.
        """
        cached = getattr(g, "_compact_ok", None)
        if cached is not None and cached[0] is self._probe_token:
            return cached[1]
        ok = False
        try:
            if (
                g.distances is not None
                and len(g.distances) == g.num_edges
                and np.ndim(g.edge_fea) == 2
                and g.edge_fea.shape[1] == len(self.gauss_filter)
            ):
                self.vocab.indices(g)  # raises CompactUnsupported if not
                d = np.asarray(g.distances, np.float32)
                step = max(1, len(d) // sample_edges)
                idx = np.arange(0, len(d), step)[:sample_edges]
                want = gaussian_expand(d[idx], self.gauss_filter,
                                       self.gauss_var)
                ok = np.allclose(
                    np.asarray(g.edge_fea, np.float32)[idx], want,
                    atol=atol,
                )
        except (CompactUnsupported, ValueError, TypeError):
            ok = False
        try:
            g._compact_ok = (self._probe_token, ok)
        except AttributeError:  # frozen/slotted graph: just skip the cache
            pass
        return ok

    @classmethod
    def build(cls, graphs: Sequence, gdf, dense_m: int,
              edge_dtype=np.float32, validate_k: int = 8) -> "CompactSpec":
        """Probe a dataset for compact stageability.

        ``gdf`` is the GaussianDistance the caller believes featurized the
        dataset; a sample of graphs is re-expanded and compared against the
        stored edge features, so a stale cache featurized with different
        parameters raises instead of training on silently different edges.
        """
        if not graphs:
            raise CompactUnsupported("empty graph list")
        if any(g.distances is None for g in graphs):
            raise CompactUnsupported(
                "graphs carry no raw distances (old cache format?)"
            )
        step = max(1, len(graphs) // validate_k)
        for g in graphs[:: step][:validate_k]:
            want = np.asarray(g.edge_fea, np.float32)
            got = gdf.expand(g.distances)
            if want.shape != got.shape or not np.allclose(
                want, got, atol=1e-5
            ):
                raise CompactUnsupported(
                    f"graph {g.cif_id!r}: edge features do not match the "
                    f"Gaussian expansion of stored distances (dataset "
                    f"featurized with different radius/step?)"
                )
        vocab = AtomVocab.build(graphs)
        return cls(vocab, np.asarray(gdf.filter, np.float32),
                   float(gdf.var), int(dense_m), edge_dtype)


class CompactBatch(struct.PyTreeNode):
    """Raw-form packed batch (dense slot layout; device-side pytree).

    Same slot geometry and invariants as the GraphBatch that
    ``make_expander`` rebuilds from it: node slot ``n`` owns edge slots
    ``[n*M, (n+1)*M)``, masks zero on padding, ``in_slots``/``over_*``
    identical to ``pack_graphs`` (shared ``transpose_slots``).
    """

    atom_idx: Any  # [Ncap] i32 vocabulary row per node
    distances: Any  # [Ncap, M] f32 (0 on padding slots)
    neighbors: Any  # [Ncap*M] i32 (padding: own node)
    edge_mask: Any  # [Ncap, M] u8
    node_graph: Any  # [Ncap] i32
    node_mask: Any  # [Ncap] u8
    graph_mask: Any  # [Gcap] f32
    targets: Any  # [Gcap, T] f32
    target_mask: Any  # [Gcap, T] f32
    in_slots: Any = None  # [Ncap*M] i32 (two-tier tier 1)
    in_mask: Any = None  # [Ncap, M] u8
    over_slots: Any = None  # [O] i32
    over_nodes: Any = None  # [O] i32
    over_last: Any = None  # [Ncap] i32
    over_runs: Any = None  # [K] i32

    # PaddingStats/driver interface parity with GraphBatch
    @property
    def node_capacity(self) -> int:
        return self.atom_idx.shape[0]

    @property
    def edge_capacity(self) -> int:
        return self.distances.shape[0] * self.distances.shape[1]

    @property
    def graph_capacity(self) -> int:
        return self.targets.shape[0]


def compact_shape_key(batch: CompactBatch) -> tuple:
    """Hashable full-shape key (the batch_shape_key analog)."""
    return (
        "compact",
        np.shape(batch.distances),
        np.shape(batch.targets),
        None if batch.in_slots is None else np.shape(batch.in_slots),
        None if batch.over_slots is None else np.shape(batch.over_slots),
        None if batch.over_runs is None else np.shape(batch.over_runs),
    )


def flat_rows(batch: CompactBatch) -> CompactBatch:
    """``batch`` (host arrays, any leading stack axes) with its per-slot
    members — ``distances``, ``edge_mask``, ``in_mask``, ``[..., N, M]`` —
    merged to ``[..., N*M]``, the shape ``neighbors`` and ``in_slots``
    already have. The same bytes; ``make_expander`` takes either shape.

    For staging under a mesh (``parallel.data_parallel.shard_scan_stack``):
    a device's share of a resident stack is ``[B, 1, N, M]``, and with M =
    12 the minor axis the chip re-laid out the WHOLE stack of distances and
    of the edge mask once a launch, a quarter of the step (PERF.md section
    6, PR 32); a flat row is dense as it lies, and only the step's own
    slice is ever reshaped."""
    def flat(x):
        return None if x is None else np.reshape(x, np.shape(x)[:-2] + (-1,))

    return batch.replace(distances=flat(batch.distances),
                         edge_mask=flat(batch.edge_mask),
                         in_mask=flat(batch.in_mask))


def compact_buffer_key(node_cap: int, dense_m: int, graph_cap: int,
                       tdim: int) -> tuple:
    """Pool key for reusable compact staging buffers (data/pipeline.py
    ``BufferPool``): one free-list per distinct buffer geometry."""
    return ("compact", node_cap, dense_m, graph_cap, tdim)


def alloc_compact_buffers(node_cap: int, dense_m: int, graph_cap: int,
                          tdim: int) -> CompactBatch:
    """Freshly allocate one forward-only (no transpose slots) compact
    staging buffer set — the ``BufferPool`` factory for
    ``pack_compact(out=...)``."""
    return CompactBatch(
        atom_idx=np.zeros(node_cap, np.int32),
        distances=np.zeros((node_cap, dense_m), np.float32),
        neighbors=np.zeros(node_cap * dense_m, np.int32),
        edge_mask=np.zeros((node_cap, dense_m), np.uint8),
        node_graph=np.zeros(node_cap, np.int32),
        node_mask=np.zeros(node_cap, np.uint8),
        graph_mask=np.zeros(graph_cap, np.float32),
        targets=np.zeros((graph_cap, tdim), np.float32),
        target_mask=np.zeros((graph_cap, tdim), np.float32),
    )


# base dense neighbor pattern (slot k -> its owning node k // M), cached
# per shape: recomputing it per batch is an avoidable fresh allocation on
# the packer's critical path
_BASE_NEIGHBORS: dict[tuple[int, int], np.ndarray] = {}


def _base_neighbors(node_cap: int, dense_m: int) -> np.ndarray:
    base = _BASE_NEIGHBORS.get((node_cap, dense_m))
    if base is None:
        base = (np.arange(node_cap * dense_m, dtype=np.int32)
                // dense_m).astype(np.int32)
        base.setflags(write=False)
        _BASE_NEIGHBORS[(node_cap, dense_m)] = base
    return base


def pack_compact(
    graphs: Sequence,
    node_cap: int,
    edge_cap: int,
    graph_cap: int,
    spec: CompactSpec,
    num_targets: int | None = None,
    dense_m: int | None = None,
    in_cap: int | None = None,
    over_cap: int | None = None,
    edge_dtype=None,  # accepted for pack_fn signature parity; spec wins
    out: CompactBatch | None = None,
    run_cap: int | None = None,
) -> CompactBatch:
    """pack_graphs' compact twin: same slot geometry, raw-form payload.

    Raises the same ``TransposeOverflowError`` on two-tier overflow so
    ``_pack_overflow_safe``'s split-don't-abort recovery applies unchanged
    (and the same ``TransposeRunError`` on a run longer than ``run_cap``).

    ``out`` (forward-only batches) recycles a previously allocated buffer
    set (``alloc_compact_buffers``) instead of allocating fresh arrays:
    PERF.md §7 measured fresh zeros page-faulting at ~0.2 GB/s effective,
    so reuse turns the pack's output writes into stores to already-mapped
    pages. The returned batch ALIASES ``out``'s arrays — hand the buffer
    back to its pool only after the device has consumed the dispatch that
    read it. Bit-identical to a fresh pack (pinned by test).
    """
    dense_m = dense_m if dense_m is not None else spec.dense_m
    if dense_m is None:
        raise ValueError("compact staging requires the dense layout")
    if edge_cap != node_cap * dense_m:
        raise ValueError(
            f"dense layout requires edge_cap == node_cap * dense_m "
            f"({node_cap} * {dense_m} != {edge_cap})"
        )
    if not graphs:
        raise ValueError("cannot pack an empty graph list")
    if out is not None and (in_cap or over_cap is not None):
        raise ValueError("buffer reuse (out=) is forward-only: transpose "
                         "slots are not pooled")
    n_graphs = len(graphs)
    if n_graphs > graph_cap:
        raise ValueError(f"{n_graphs} graphs exceed graph_cap={graph_cap}")
    nn_arr = np.fromiter((g.num_nodes for g in graphs), np.int64, n_graphs)
    ne_arr = np.fromiter((g.num_edges for g in graphs), np.int64, n_graphs)
    node_offs = np.zeros(n_graphs + 1, np.int64)
    np.cumsum(nn_arr, out=node_offs[1:])
    total_nodes = int(node_offs[-1])
    total_edges = int(ne_arr.sum())
    if total_nodes > node_cap:
        raise ValueError(
            f"batch ({total_nodes} nodes) exceeds node_cap={node_cap}"
        )
    tdim = num_targets or int(np.atleast_1d(graphs[0].target).shape[0])

    if out is not None:
        want = (node_cap, dense_m, graph_cap, tdim)
        got = (out.atom_idx.shape[0], out.distances.shape[1],
               out.targets.shape[0], out.targets.shape[1])
        if want != got:
            raise ValueError(
                f"out buffer geometry {got} does not match the requested "
                f"pack {want} (pool keyed by compact_buffer_key?)"
            )
        atom_idx, node_graph, node_mask = (
            out.atom_idx, out.node_graph, out.node_mask
        )
        # only the padding tail needs zeroing: [:total_nodes] is fully
        # overwritten below (bit-parity with the fresh-zeros path)
        atom_idx[total_nodes:] = 0
        node_graph[total_nodes:] = 0
        node_mask[total_nodes:] = 0
    else:
        atom_idx = np.zeros(node_cap, np.int32)
        node_graph = np.zeros(node_cap, np.int32)
        node_mask = np.zeros(node_cap, np.uint8)
    np.concatenate([spec.vocab.indices(g) for g in graphs],
                   out=atom_idx[:total_nodes])
    node_graph[:total_nodes] = np.repeat(
        np.arange(n_graphs, dtype=np.int32), nn_arr
    )
    node_mask[:total_nodes] = 1

    e_node_off = np.repeat(node_offs[:-1], ne_arr)
    gcent = np.concatenate([g.centers for g in graphs]).astype(np.int64)
    gcent += e_node_off
    gnbr = np.concatenate([g.neighbors for g in graphs]).astype(np.int64)
    gnbr += e_node_off
    dist = np.concatenate([g.distances for g in graphs]).astype(np.float32)
    if not np.all(gcent[1:] >= gcent[:-1]):
        order = np.argsort(gcent, kind="stable")
        gcent, gnbr, dist = gcent[order], gnbr[order], dist[order]

    counts = np.bincount(gcent, minlength=node_cap)
    worst = int(counts.max(initial=0))
    if worst > dense_m:
        bad = int(np.argmax(counts))
        gi = int(np.searchsorted(node_offs, bad, side="right")) - 1
        raise ValueError(
            f"graph {graphs[gi].cif_id!r} has a node with {worst} edges "
            f"> dense_m={dense_m}; featurize with max_num_nbr <= dense_m"
        )
    within = np.arange(total_edges) - (np.cumsum(counts) - counts)[gcent]
    slots = gcent * dense_m + within
    starts = np.cumsum(counts) - counts
    src = starts[:, None] + np.arange(dense_m)
    grid_valid = np.arange(dense_m) < counts[:, None]
    np.copyto(src, total_edges, where=~grid_valid)
    dist_pad = np.concatenate([dist, np.zeros(1, np.float32)])
    if out is not None:
        distances, edge_mask, neighbors = (
            out.distances, out.edge_mask, out.neighbors
        )
        # every slot of all three is overwritten: take covers the full
        # [node_cap, M] grid (padding slots read the appended 0), the
        # mask copies the full grid, neighbors resets to the base
        # pattern before the real-edge scatter
        np.take(dist_pad, src, mode="clip", out=distances)
        np.copyto(edge_mask, grid_valid, casting="unsafe")
        np.copyto(neighbors, _base_neighbors(node_cap, dense_m))
    else:
        distances = np.take(dist_pad, src, mode="clip")  # [node_cap, M]
        edge_mask = grid_valid.astype(np.uint8)
        neighbors = _base_neighbors(node_cap, dense_m).copy()
    neighbors[slots] = gnbr.astype(np.int32)

    if out is not None:
        graph_mask, targets, target_mask = (
            out.graph_mask, out.targets, out.target_mask
        )
        graph_mask[n_graphs:] = 0.0
        targets.fill(0.0)  # ragged target widths: no full overwrite below
        target_mask.fill(0.0)
    else:
        graph_mask = np.zeros(graph_cap, np.float32)
        targets = np.zeros((graph_cap, tdim), np.float32)
        target_mask = np.zeros((graph_cap, tdim), np.float32)
    graph_mask[:n_graphs] = 1.0
    tgt = [np.atleast_1d(np.asarray(g.target, np.float32)) for g in graphs]
    if all(len(t) == len(tgt[0]) for t in tgt):
        tw = len(tgt[0])
        targets[:n_graphs, :tw] = np.stack(tgt)
        masks = [g.target_mask for g in graphs]
        if all(m is None for m in masks):
            target_mask[:n_graphs, :tw] = 1.0
        else:
            target_mask[:n_graphs, :tw] = np.stack([
                np.ones(tw, np.float32) if m is None
                else np.broadcast_to(np.atleast_1d(m), (tw,))
                for m in masks
            ])
    else:
        for gi, (g, t) in enumerate(zip(graphs, tgt)):
            targets[gi, : len(t)] = t
            if g.target_mask is not None:
                target_mask[gi, : len(t)] = np.atleast_1d(g.target_mask)
            else:
                target_mask[gi, : len(t)] = 1.0

    mapping = (None,) * len(TRANSPOSE_FIELDS)
    if in_cap is not None and over_cap is not None:
        raise ValueError("in_cap and over_cap are mutually exclusive")
    if in_cap == 0:  # explicit disable (eval-only batches: no backward)
        in_cap = None
    if in_cap is not None or over_cap is not None:
        mapping = transpose_slots(
            neighbors, edge_mask.reshape(-1) > 0, node_cap, dense_m,
            in_cap, over_cap, run_cap,
        )

    return CompactBatch(
        atom_idx=atom_idx,
        distances=distances,
        neighbors=neighbors,
        edge_mask=edge_mask,
        node_graph=node_graph,
        node_mask=node_mask,
        graph_mask=graph_mask,
        targets=targets,
        target_mask=target_mask,
        **dict(zip(TRANSPOSE_FIELDS, mapping)),
    )


def make_expander(spec: CompactSpec) -> Callable[[CompactBatch], GraphBatch]:
    """Jit-composable CompactBatch -> GraphBatch reconstruction.

    Numerics: identical to pack_graphs except edge features go through
    ``jnp.exp`` instead of ``np.exp`` (<= 1 ulp f32 difference, washed out
    by the bf16 compute cast). Geometry fields come back ``None`` — the
    energy-family models never read them (models/cgcnn.py), and staging
    zeros for them would defeat the point.
    """
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.observe import phases

    table = np.asarray(spec.vocab.table, np.float32)
    mu = np.asarray(spec.gauss_filter, np.float32)
    inv_var2 = np.float32(1.0 / spec.gauss_var**2)
    edge_dtype = spec.edge_dtype

    def expand(cb: CompactBatch) -> GraphBatch:
        with jax.named_scope(phases.EXPAND):
            # [N, M], or [N*M] as flat_rows stages it (no-ops on the former)
            m = spec.dense_m
            distances = cb.distances.reshape(-1, m)
            in_mask = (None if cb.in_mask is None
                       else cb.in_mask.reshape(-1, m))
            n = distances.shape[0]
            node_mask = cb.node_mask.astype(jnp.float32)
            nodes = jnp.asarray(table)[cb.atom_idx] * node_mask[:, None]
            emask = cb.edge_mask.reshape(-1, m).astype(jnp.float32)
            d = distances[..., None]
            efea = jnp.exp(-((d - jnp.asarray(mu)) ** 2) * inv_var2)
            efea = (efea * emask[..., None]).astype(edge_dtype)
            centers = jnp.arange(n * m, dtype=jnp.int32) // m
            return GraphBatch(
                nodes=nodes,
                edges=efea,
                centers=centers,
                neighbors=cb.neighbors,
                node_graph=cb.node_graph,
                node_mask=node_mask,
                edge_mask=emask.reshape(-1),
                graph_mask=cb.graph_mask,
                targets=cb.targets,
                target_mask=cb.target_mask,
                positions=None,
                lattices=None,
                edge_offsets=None,
                node_targets=None,
                in_slots=cb.in_slots,
                in_mask=in_mask,
                over_slots=cb.over_slots,
                over_nodes=cb.over_nodes,
                over_last=cb.over_last,
                over_runs=cb.over_runs,
            )

    return expand


def compact_pack_fn(spec: CompactSpec) -> Callable:
    """Adapter matching the ``pack_fn`` signature batch_iterator threads to
    ``_pack_overflow_safe`` (pack_graphs-compatible keyword set)."""

    def pack(graphs, node_cap, edge_cap, graph_cap, **kw):
        return pack_compact(graphs, node_cap, edge_cap, graph_cap, spec,
                            **kw)

    return pack
