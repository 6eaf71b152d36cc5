"""Warm shape set: the fixed ladder of precompiled batch shapes.

The whole CGCNN-on-XLA lineage rests on one packing insight: dispatch is
cheap exactly when every batch reuses an already-compiled fixed shape
(data/graph.py). Offline that is easy — ``capacities_for`` derives snug
capacities per dataset. Online it is the hard part: traffic arrives one
structure at a time, batch composition varies second to second, and a
recompile (seconds, through a high-latency link) inside a request's
latency budget is an SLO kill. So the serving path inverts the offline
derivation: a SMALL FIXED LADDER of (graph_cap, node_cap, edge_cap)
rungs is quantized ONCE from a calibration sample, every rung is
compiled at startup (through the persistent XLA compile cache, so a
restart warms from disk), and the micro-batcher only ever packs into
rungs from this set — zero recompiles after warmup, by construction.

The same ``ShapeSet`` serves offline: ``train.infer.run_fast_inference``
accepts one in place of its per-bucket capacity derivation, so predict
jobs reuse the serving shapes (and the serving compile cache) instead of
compiling fresh per-dataset programs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from cgnn_tpu.data.graph import (
    CrystalGraph,
    GraphBatch,
    capacities_for,
    graph_cap_for,
    pack_graphs,
)


def _align8(n: int) -> int:
    return max(8, -(-int(n) // 8) * 8)


@dataclasses.dataclass(frozen=True, order=True)
class BatchShape:
    """One compiled batch shape (capacities, not contents)."""

    graph_cap: int
    node_cap: int
    edge_cap: int

    def fits(self, n_graphs: int, n_nodes: int, n_edges: int) -> bool:
        return (
            n_graphs <= self.graph_cap
            and n_nodes <= self.node_cap
            and n_edges <= self.edge_cap
        )

    def to_meta(self) -> dict:
        return dataclasses.asdict(self)


class ShapeSet:
    """An ascending ladder of :class:`BatchShape` rungs plus the packing
    parameters (dense layout, edge dtype, target width) every rung shares.

    ``shape_for`` picks the SMALLEST rung that fits a request set — a
    half-empty flush then pays a small program's latency, not the full
    batch shape's. ``admits`` is the oversize gate: a single structure
    that does not fit the largest rung can never be served and is
    rejected at admission, with the observed sizes in the error.
    """

    def __init__(
        self,
        shapes: Sequence[BatchShape],
        *,
        dense_m: int | None = None,
        edge_dtype=np.float32,
        num_targets: int = 1,
        compact=None,
        raw=None,
    ):
        if not shapes:
            raise ValueError("a ShapeSet needs at least one shape")
        self.shapes = tuple(sorted(set(shapes)))
        self.dense_m = dense_m
        self.edge_dtype = edge_dtype
        self.num_targets = num_targets
        # CompactSpec | None: with a spec, pack() stages the raw compact
        # form (data/compact.py — ~12x fewer host bytes written and H2D
        # bytes moved) and the predict step must carry the matching
        # expander (train.step.make_predict_step(expander=...)) so the
        # exact GraphBatch is rebuilt INSIDE the compiled program
        self.compact = compact
        if compact is not None and dense_m is None:
            raise ValueError("compact staging requires the dense layout "
                             "(dense_m)")
        # RawSpec | None (ISSUE 11): with one, the set ALSO compiles a
        # raw-wire program per rung — wire-form (positions, lattice,
        # species) structures stage as RawBatch and the in-program
        # neighbor search builds the graph (ops/neighbor_search.py).
        # The spec's snode_cap/image caps are shared by every rung (the
        # admitted-fits-every-rung floor rule); rung r's raw program
        # holds graph_cap_r structure slots.
        self.raw = raw
        if raw is not None:
            if dense_m is None:
                raise ValueError("raw wire requires the dense layout "
                                 "(dense_m)")
            if raw.dense_m != dense_m:
                raise ValueError(
                    f"raw spec max_num_nbr {raw.dense_m} != layout "
                    f"dense_m {dense_m} (the in-program truncation must "
                    f"match the model's slot layout)"
                )
        for s in self.shapes:
            if dense_m is not None and s.edge_cap != s.node_cap * dense_m:
                raise ValueError(
                    f"dense layout requires edge_cap == node_cap * dense_m "
                    f"for every rung; {s} violates it (dense_m={dense_m})"
                )

    def __len__(self) -> int:
        return len(self.shapes)

    def __iter__(self):
        return iter(self.shapes)

    @property
    def largest(self) -> BatchShape:
        return self.shapes[-1]

    def expander(self):
        """Jit-composable CompactBatch -> GraphBatch reconstruction for
        this set's spec (None without compact staging) — hand it to
        ``train.step.make_predict_step(expander=...)``."""
        if self.compact is None:
            return None
        from cgnn_tpu.data.compact import make_expander

        return make_expander(self.compact)

    def compactable(self, graph: CrystalGraph) -> bool:
        """Can this graph stage compactly under the set's spec? (Always
        False without one; never raises — the serving admission probe.)"""
        return (self.compact is not None
                and self.compact.graph_compactable(graph))

    def raw_expander(self):
        """Jit-composable RawBatch -> (GraphBatch, overflow, n_edges)
        for this set's raw spec (None without one) — hand it to
        ``train.step.make_predict_step(raw_expander=...)``."""
        if self.raw is None:
            return None
        from cgnn_tpu.ops.neighbor_search import make_raw_expander

        return make_raw_expander(self.raw, edge_dtype=self.edge_dtype)

    def admits_raw(self, rs) -> bool:
        """Host pre-check: can this wire-form structure be staged raw
        (atom count + periodic image caps, f64)? Always False without a
        raw spec; never raises — the serving admission probe. A False
        here routes the request to the host-featurized fallback, not to
        a rejection."""
        return self.raw is not None and self.raw.admits(rs)

    def pack_raw(self, items: Sequence, shape: BatchShape | None = None):
        """Stage wire-form structures into one rung's RawBatch (default:
        the smallest rung whose graph slots fit them)."""
        if self.raw is None:
            raise ValueError("this shape set carries no raw spec")
        from cgnn_tpu.data.rawbatch import pack_raw

        if shape is None:
            for s in self.shapes:
                if len(items) <= s.graph_cap:
                    shape = s
                    break
            if shape is None:
                raise ValueError(
                    f"{len(items)} structures fit no rung's graph slots"
                )
        return pack_raw(list(items), shape.graph_cap, self.raw,
                        num_targets=self.num_targets)

    def graph_counts(self, graph: CrystalGraph) -> tuple[int, int]:
        """(nodes, edge slots) one graph consumes under this set's layout.

        Dense layout consumes ``nodes * dense_m`` edge slots regardless of
        the true edge count (slot ownership is structural)."""
        if self.dense_m is not None:
            return graph.num_nodes, graph.num_nodes * self.dense_m
        return graph.num_nodes, graph.num_edges

    def admits(self, graph: CrystalGraph) -> bool:
        n, e = self.graph_counts(graph)
        return self.largest.fits(1, n, e)

    def oversize_detail(self, graph: CrystalGraph) -> str:
        n, e = self.graph_counts(graph)
        big = self.largest
        return (
            f"structure has {n} nodes / {e} edge slots; the largest "
            f"compiled shape holds {big.node_cap} nodes / {big.edge_cap} "
            f"edge slots"
        )

    def shape_for(self, n_graphs: int, n_nodes: int,
                  n_edges: int) -> BatchShape | None:
        """Smallest rung fitting the given totals (None = nothing fits)."""
        for s in self.shapes:
            if s.fits(n_graphs, n_nodes, n_edges):
                return s
        return None

    def _resolve(self, graphs: Sequence[CrystalGraph],
                 shape: BatchShape | None) -> BatchShape:
        if shape is not None:
            return shape
        n = sum(g.num_nodes for g in graphs)
        e = sum(self.graph_counts(g)[1] for g in graphs)
        shape = self.shape_for(len(graphs), n, e)
        if shape is None:
            raise ValueError(
                f"{len(graphs)} graphs ({n} nodes) fit no shape in "
                f"{self.shapes}"
            )
        return shape

    def pack(self, graphs: Sequence[CrystalGraph],
             shape: BatchShape | None = None, out=None):
        """Pack ``graphs`` into ``shape`` (default: smallest fitting rung).

        With a compact spec this stages the raw ``CompactBatch`` form
        (``out`` recycles a pooled staging buffer); without one, the
        full-fidelity ``GraphBatch``."""
        shape = self._resolve(graphs, shape)
        if self.compact is not None:
            from cgnn_tpu.data.compact import pack_compact

            return pack_compact(
                list(graphs),
                shape.node_cap,
                shape.edge_cap,
                shape.graph_cap,
                self.compact,
                num_targets=self.num_targets,
                dense_m=self.dense_m,
                out=out,
            )
        return self.pack_full(graphs, shape)

    def pack_full(self, graphs: Sequence[CrystalGraph],
                  shape: BatchShape | None = None) -> GraphBatch:
        """Full-fidelity pack regardless of the compact spec — the
        serving fallback for requests that cannot stage compactly (no
        raw distances / atom rows outside the vocabulary)."""
        shape = self._resolve(graphs, shape)
        return pack_graphs(
            list(graphs),
            shape.node_cap,
            shape.edge_cap,
            shape.graph_cap,
            num_targets=self.num_targets,
            dense_m=self.dense_m,
            # in_cap/over_cap omitted: forward-only batches carry no
            # transpose slots (the backward-pass-only layout)
            edge_dtype=self.edge_dtype,
        )

    def abstract_batches(self, template: CrystalGraph) -> dict:
        """{(rung index, staging form): abstract batch pytree} for every
        program this set compiles — the graftaudit lowering surface.

        Packs one copy of ``template`` per rung (exactly the batches
        ``serve.server.warm()`` dispatches) and maps every leaf to a
        ``jax.ShapeDtypeStruct``, so ``jax.jit(...).lower(state_aval,
        batch_aval)`` sees the same traced programs serving warms —
        without touching a device. Forms: ``"compact"`` and ``"full"``
        for a compact set (warm() compiles both per rung), ``"full"``
        only otherwise."""
        import jax

        def aval(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        out = {}
        for i, shape in enumerate(self.shapes):
            forms = {}
            if self.compact is not None:
                forms["compact"] = self.pack([template], shape=shape)
            forms["full"] = self.pack_full([template], shape=shape)
            if self.raw is not None:
                forms["raw"] = self.pack_raw([self.raw.template()],
                                             shape=shape)
            for form, batch in forms.items():
                out[(i, form)] = jax.tree_util.tree_map(aval, batch)
        return out

    def buffer_key(self, shape: BatchShape) -> tuple:
        """Staging-buffer pool key for one rung (compact sets only)."""
        if self.compact is None:
            raise ValueError("buffer pooling applies to compact staging")
        from cgnn_tpu.data.compact import compact_buffer_key

        return compact_buffer_key(shape.node_cap, self.dense_m,
                                  shape.graph_cap, self.num_targets)

    def buffer_factory(self, shape: BatchShape):
        """() -> fresh staging buffers for one rung (BufferPool factory)."""
        if self.compact is None:
            raise ValueError("buffer pooling applies to compact staging")
        from cgnn_tpu.data.compact import alloc_compact_buffers

        return lambda: alloc_compact_buffers(
            shape.node_cap, self.dense_m, shape.graph_cap, self.num_targets
        )

    def to_meta(self) -> dict:
        return {
            "shapes": [s.to_meta() for s in self.shapes],
            "dense_m": self.dense_m,
            "edge_dtype": np.dtype(self.edge_dtype).name
            if self.edge_dtype is not np.float32 else "float32",
            "num_targets": self.num_targets,
            "compact": self.compact is not None,
            "raw": None if self.raw is None else self.raw.to_meta(),
        }


def plan_shape_set(
    calibration: Sequence[CrystalGraph],
    batch_size: int,
    *,
    rungs: int = 3,
    dense_m: int | None = None,
    edge_dtype=np.float32,
    num_targets: int | None = None,
    compact=None,
    raw=None,
) -> ShapeSet:
    """Quantize a serving ladder from a calibration sample.

    The top rung is the offline-proven snug full-batch shape
    (``capacities_for(snug=True)`` at ``batch_size`` with
    ``graph_cap_for`` slack); each lower rung halves the graph budget and
    scales node/edge capacity proportionally (8-aligned), floored so that
    ANY admitted structure fits EVERY rung — a deadline flush holding one
    lone large structure must still have a rung to land in. ``rungs``
    bounds the compile count: warmup compiles exactly ``len(set)``
    programs, and nothing after warmup ever compiles.
    """
    if not len(calibration):
        raise ValueError("shape planning needs a calibration sample")
    if rungs < 1:
        raise ValueError(f"rungs must be >= 1, got {rungs}")
    node_cap, edge_cap = capacities_for(
        calibration, batch_size, dense_m=dense_m, snug=True
    )
    # any admitted graph must fit the smallest rung (see docstring)
    max_nodes = max(g.num_nodes for g in calibration)
    max_edges = max(g.num_edges for g in calibration)
    if num_targets is None:
        num_targets = int(np.atleast_1d(calibration[0].target).shape[0])
    shapes = []
    for r in range(rungs):
        scale = 2**r
        b = max(1, math.ceil(batch_size / scale))
        nc = _align8(max(math.ceil(node_cap / scale), max_nodes))
        if dense_m is not None:
            ec = nc * dense_m
        else:
            ec = _align8(max(math.ceil(edge_cap / scale), max_edges))
        shapes.append(BatchShape(graph_cap_for(b), nc, ec))
    return ShapeSet(
        shapes,
        dense_m=dense_m,
        edge_dtype=edge_dtype,
        num_targets=num_targets,
        compact=compact,
        raw=raw,
    )
