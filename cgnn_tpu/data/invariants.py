"""GraphBatch invariant checks (SURVEY.md §5 race-detection/sanitizers).

The jitted step trusts several non-local data-plane invariants that are
established at pack time and never re-checked (``indices_are_sorted`` is an
UNCHECKED promise to XLA's TPU scatter; ``gather_slot_major``'s declared
transpose is only correct when the transpose mapping is complete). A
corrupted batch — a bug in a new iterator, a bad cache file — would train
silently wrong. This module is the loud path: ``--check-invariants``
(train.py) enables validation of every packed batch at iterator exit;
``check_batch`` can also be called directly (tests, debugging).

Checks are host-side (numpy + chex static assertions) so they add zero
device work; cost is one pass over each batch's index arrays.
"""

from __future__ import annotations

import chex
import numpy as np

_ENABLED = False


def enable(on: bool = True) -> None:
    """Globally enable per-batch validation (the --check-invariants flag)."""
    global _ENABLED
    _ENABLED = on


def enabled() -> bool:
    return _ENABLED


class BatchInvariantError(AssertionError):
    pass


def _fail(msg: str):
    raise BatchInvariantError(msg)


def check_batch(batch, dense_m: int | None = None):
    """Validate one host-side GraphBatch; raises BatchInvariantError.

    Invariants (data/graph.py module docstring + pack_graphs):
    - shape/dtype consistency across leaves (chex);
    - masks are exactly {0, 1};
    - ``centers`` is non-decreasing (the sorted-scatter promise) and every
      real edge's endpoints are real, in-range node slots;
    - padding edges carry zero mask AND zero features;
    - ``node_graph`` is non-decreasing with real nodes pointing at real
      graph slots;
    - dense layout: slot ownership centers[k] == k // M (``dense_m`` is
      inferred from pre-shaped [N, M, G] edges when not given);
    - transpose slots: ``in_slots``/``in_mask`` list every real edge slot
      exactly once under its neighbor node — the completeness property
      gather_slot_major's scatter-free backward silently relies on — with
      each row's real entries first (ops/segment.gather_slot_major masks
      tier 1 by rank < in-degree), and the overflow list's run structure:
      real entries a node-sorted prefix, ``over_last`` the end of each
      owner's run and ``over_cap`` for every other node, no run longer
      than ``over_runs`` (which counts them by length) allows.
    """
    if dense_m is None and np.ndim(batch.edges) == 3:
        dense_m = int(np.shape(batch.edges)[1])
    nodes = np.asarray(batch.nodes)
    edges = np.asarray(batch.flat_edges)
    centers = np.asarray(batch.centers)
    neighbors = np.asarray(batch.neighbors)
    node_graph = np.asarray(batch.node_graph)
    node_mask = np.asarray(batch.node_mask)
    edge_mask = np.asarray(batch.edge_mask)
    graph_mask = np.asarray(batch.graph_mask)

    ncap, ecap = nodes.shape[0], edges.shape[0]
    chex.assert_shape(centers, (ecap,))
    chex.assert_shape(neighbors, (ecap,))
    chex.assert_shape(edge_mask, (ecap,))
    chex.assert_shape(node_graph, (ncap,))
    chex.assert_shape(node_mask, (ncap,))
    chex.assert_type([centers, neighbors, node_graph], np.integer)

    for name, m in (("node_mask", node_mask), ("edge_mask", edge_mask),
                    ("graph_mask", graph_mask)):
        if not np.isin(m, (0.0, 1.0)).all():
            _fail(f"{name} contains values outside {{0, 1}}")

    if np.any(np.diff(centers) < 0):
        _fail("centers is not non-decreasing (sorted-scatter promise broken)")
    if centers.min(initial=0) < 0 or centers.max(initial=0) >= ncap:
        _fail("centers out of node-slot range")
    if neighbors.min(initial=0) < 0 or neighbors.max(initial=0) >= ncap:
        _fail("neighbors out of node-slot range")

    real_e = edge_mask > 0
    if real_e.any():
        if not node_mask[centers[real_e]].all():
            _fail("a real edge's center is a padding node")
        if not node_mask[neighbors[real_e]].all():
            _fail("a real edge's neighbor is a padding node")
    if np.any(np.abs(edges[~real_e]) > 0):
        _fail("padding edge slots carry nonzero features")

    real_n = node_mask > 0
    if not np.all(np.diff(node_mask) <= 0):
        _fail("real nodes are not a contiguous prefix of the node slots")
    if np.any(np.diff(node_graph[real_n]) < 0):
        _fail("node_graph is not non-decreasing over real nodes")
    if np.any(node_graph[~real_n] != 0):
        _fail("padding nodes must belong to graph slot 0")
    if real_n.any() and not graph_mask[node_graph[real_n]].all():
        _fail("a real node belongs to a padding graph slot")

    if dense_m is not None:
        owner = np.arange(ecap) // dense_m
        if not np.array_equal(centers, owner.astype(centers.dtype)):
            _fail(f"dense slot ownership broken: centers != slot//{dense_m}")

    if batch.in_slots is not None:
        _check_transpose_mapping(batch, neighbors, real_e, ncap)
    return batch


def _check_transpose_mapping(batch, neighbors, real_e, ncap):
    """The transposable gather's completeness property (flat ``neighbors``
    [E] and ``real_e`` [E] bool; ops/segment.py gather_slot_major) — shared
    by GraphBatch and CompactBatch."""
    in_slots = np.asarray(batch.in_slots)
    in_mask = np.asarray(batch.in_mask)
    n_slots = len(real_e)
    if in_mask.shape[0] != ncap:
        _fail("in_slots/in_mask row count != node capacity")
    if np.any(np.diff(in_mask.astype(np.int8), axis=1) > 0):
        _fail("in_mask rows are not in-degree prefixes (the "
              "slot-major transpose masks by rank < in-degree)")
    lst = in_slots.reshape(in_mask.shape)[in_mask > 0]
    if lst.size and (lst.min() < 0 or lst.max() >= n_slots):
        _fail(f"transpose mapping lists a slot outside its "
              f"range [0, {n_slots})")
    parts = [lst]
    rows = [np.repeat(np.arange(ncap), (in_mask > 0).sum(axis=1))]
    if batch.over_slots is not None:
        osl, ond, last, runs = (
            np.asarray(batch.over_slots), np.asarray(batch.over_nodes),
            np.asarray(batch.over_last), np.asarray(batch.over_runs))
        chex.assert_shape(ond, osl.shape)
        chex.assert_shape(last, (ncap,))
        if np.any(np.diff(ond) < 0):
            _fail("over_nodes is not non-decreasing (a node's "
                  "overflow entries must be one run)")
        # the run structure the backward's run sum and pointer gather
        # rely on: the real entries are a prefix of k, node j's run is
        # the runs[j] entries ending at last[j] and names j throughout,
        # a node without a run points out of range (reads a zero row),
        # and no run is longer than the capacity over_runs is sized to
        owner = last < len(osl)
        ends = last[owner]
        if np.any(np.diff(ends) <= 0) or (ends.size and ends[0] < 0):
            _fail("over_last is not increasing over the nodes "
                  "that own a run")
        if np.any(last[~owner] != len(osl)):
            _fail("a node without overflow must point at "
                  "over_cap (the out-of-range zero row)")
        run_len = np.diff(ends, prepend=-1)
        k = int(ends[-1]) + 1 if ends.size else 0
        if not np.array_equal(ond[:k],
                              np.repeat(np.nonzero(owner)[0], run_len)):
            _fail("over_last does not end each node's run of "
                  "over_nodes")
        if run_len.size and run_len.max() > len(runs):
            _fail(f"a run of {run_len.max()} overflow entries "
                  f"exceeds the run capacity {len(runs)}")
        if not np.array_equal(
                runs, np.bincount(run_len - 1, minlength=len(runs))):
            _fail("over_runs does not count the runs by length")
        if k and (osl[:k].min() < 0 or osl[:k].max() >= n_slots):
            _fail("overflow lists a slot outside its range")
        parts.append(osl[:k])
        rows.append(ond[:k])
    listed = np.concatenate(parts)
    rows = np.concatenate(rows)
    if listed.size != int(real_e.sum()):
        _fail(
            f"transpose mapping lists {listed.size} edges but the batch "
            f"has {int(real_e.sum())} real edges (gather_slot_major "
            f"backward would drop/duplicate gradient)"
        )
    if listed.size:
        if np.unique(listed).size != listed.size:
            _fail("transpose mapping lists an edge slot twice")
        if not real_e[listed].all():
            _fail("transpose mapping lists a padding edge slot")
        if not np.array_equal(
            np.sort(listed), np.sort(np.nonzero(real_e)[0])
        ):
            _fail("transpose mapping misses a real edge slot")
        if not np.array_equal(neighbors[listed], rows):
            _fail("a transpose row lists an edge of a different neighbor")


def check_compact_batch(batch, dense_m: int | None = None):
    """Validate a CompactBatch (data/compact.py) — the raw-form analog of
    ``check_batch``. The expensive expanded-form checks (feature zeros on
    padding) become mask/range checks on the raw payload; the transpose-
    mapping completeness check is shared verbatim."""
    atom_idx = np.asarray(batch.atom_idx)
    distances = np.asarray(batch.distances)
    neighbors = np.asarray(batch.neighbors)
    node_graph = np.asarray(batch.node_graph)
    node_mask = np.asarray(batch.node_mask)
    edge_mask = np.asarray(batch.edge_mask)
    graph_mask = np.asarray(batch.graph_mask)
    ncap, m = distances.shape
    if dense_m is not None and dense_m != m:
        _fail(f"compact batch packed with M={m} but dense_m={dense_m} "
              f"expected")
    chex.assert_shape(atom_idx, (ncap,))
    chex.assert_shape(neighbors, (ncap * m,))
    chex.assert_shape(edge_mask, (ncap, m))
    chex.assert_shape(node_mask, (ncap,))
    for name, msk in (("node_mask", node_mask), ("edge_mask", edge_mask),
                      ("graph_mask", graph_mask)):
        if not np.isin(msk, (0, 1)).all():
            _fail(f"{name} contains values outside {{0, 1}}")
    if atom_idx.min(initial=0) < 0:
        _fail("negative atom vocabulary index")
    if neighbors.min(initial=0) < 0 or neighbors.max(initial=0) >= ncap:
        _fail("neighbors out of node-slot range")
    real_e = edge_mask > 0
    if not node_mask[neighbors.reshape(ncap, m)[real_e]].all():
        _fail("a real edge's neighbor is a padding node")
    if np.any(real_e & ~(node_mask > 0)[:, None]):
        _fail("a padding node owns a real edge slot")
    if np.any(distances[~real_e] != 0):
        _fail("padding edge slots carry nonzero distances")
    if not np.isfinite(distances).all():
        _fail("non-finite distances")
    real_n = node_mask > 0
    if not np.all(np.diff(node_mask.astype(np.int8)) <= 0):
        _fail("real nodes are not a contiguous prefix of the node slots")
    if np.any(np.diff(node_graph[real_n]) < 0):
        _fail("node_graph is not non-decreasing over real nodes")
    if real_n.any() and not graph_mask[node_graph[real_n]].all():
        _fail("a real node belongs to a padding graph slot")
    if batch.in_slots is not None:
        _check_transpose_mapping(batch, neighbors, real_e.reshape(-1), ncap)
    return batch


def maybe_check(batch, dense_m: int | None = None):
    """check_batch when globally enabled, else pass-through."""
    if _ENABLED:
        if hasattr(batch, "atom_idx"):
            check_compact_batch(batch, dense_m)
        else:
            check_batch(batch, dense_m)
    return batch


def check_stacked_batch(stacked, dense_m: int | None = None,
                        train: bool = False):
    """Validate a device-stacked batch ([D, ...] leaves) row by row.

    ``train=True`` additionally requires every device row to carry at
    least one real graph: ``empty_batch_like`` rows are an EVAL-ONLY
    padding device (psum-neutral metrics) — in a training step their
    zero gradients would silently dilute the pmean and their degenerate
    statistics would reach the BatchNorm EMA (the docstring contract
    this check enforces; see parallel/data_parallel.py).
    """
    import jax

    n_dev = int(np.shape(stacked.node_mask)[0])
    checker = (check_compact_batch if hasattr(stacked, "atom_idx")
               else check_batch)
    for d in range(n_dev):
        row = jax.tree_util.tree_map(lambda x, _d=d: x[_d], stacked)
        checker(row, dense_m)
        if train and float(np.asarray(row.graph_mask).sum()) == 0:
            _fail(
                f"device row {d} of a TRAINING batch has zero real graphs "
                f"(empty_batch_like is eval-only padding; training on it "
                f"dilutes pmean gradients)"
            )
    return stacked


def check_token_batch(batch):
    """Validate one host-side ``TokenBatch`` (data/tokens.py): ids
    non-negative, documents as non-decreasing runs from 0, weights finite
    and non-negative; of a block-diffusion batch (the noised copy and the
    clean one side by side) a weight in the loss wherever the noised token
    differs from the clean one; of a causal batch none where the next token
    is another document's or there is none."""
    tokens = np.asarray(batch.tokens)
    seg = np.asarray(batch.segment_ids)
    w = np.asarray(batch.loss_weight)
    length = seg.shape[-1]
    if tokens.ndim != 2 or tokens.shape[1] not in (length, 2 * length) \
            or seg.shape != w.shape or seg.shape[0] != tokens.shape[0]:
        _fail(f"token batch shapes disagree: tokens {tokens.shape}, "
              f"segment_ids {seg.shape}, loss_weight {w.shape}")
    chex.assert_type([tokens, seg], np.integer)
    if tokens.min() < 0:
        _fail("negative token id")
    if (np.diff(seg, axis=1) < 0).any() or (seg[:, 0] != 0).any():
        _fail("segment_ids are not non-decreasing runs from 0")
    if not np.isfinite(w).all() or (w < 0).any():
        _fail("loss weights must be finite and non-negative")
    if tokens.shape[1] == length:  # causal
        if (w[:, -1] != 0).any() or (
                (np.diff(seg, axis=1) != 0) & (w[:, :-1] != 0)).any():
            _fail("a weight in the loss where the next token is another "
                  "document's or there is none")
        return batch
    changed = tokens[:, :length] != tokens[:, length:]
    if (changed & (w == 0)).any():
        _fail("a noised token without a weight in the loss")
    return batch


def check_any(batch, dense_m: int | None = None, train: bool = False):
    """Dispatch on the batch's kind and stacking: a token batch; else 1-D
    node_mask -> single batch, 2-D -> stacked.

    Single training batches cannot be empty by construction
    (batch_iterator never yields an empty pack), so ``train`` only adds
    the non-empty-row requirement for stacked batches.
    """
    if hasattr(batch, "tokens"):
        return check_token_batch(batch)
    if np.ndim(batch.node_mask) == 1:
        if hasattr(batch, "atom_idx"):
            return check_compact_batch(batch, dense_m)
        return check_batch(batch, dense_m)
    return check_stacked_batch(batch, dense_m, train=train)


def maybe_check_any(batch, dense_m: int | None = None, train: bool = False):
    if _ENABLED:
        check_any(batch, dense_m, train=train)
    return batch


def spot_check_graphs(graphs, k: int = 16):
    """Sample-validate CrystalGraphs (cache reload path: a bad/truncated
    cache file would otherwise surface as silent training corruption).

    Checks an evenly spaced sample of ``k`` graphs: index ranges, sorted
    centers (the pack-time no-op-sort assumption), finite features and
    labels, and per-array row-count consistency.
    """
    if not graphs:
        _fail("empty graph list")
    idx = np.unique(np.linspace(0, len(graphs) - 1, num=min(k, len(graphs)),
                                dtype=np.int64))
    for i in idx:
        g = graphs[int(i)]
        n, e = g.num_nodes, g.num_edges
        if len(g.edge_fea) != e or len(g.neighbors) != e:
            _fail(f"graph {g.cif_id!r}: edge array row counts disagree")
        if e:
            c, nb = np.asarray(g.centers), np.asarray(g.neighbors)
            if c.min() < 0 or c.max() >= n or nb.min() < 0 or nb.max() >= n:
                _fail(f"graph {g.cif_id!r}: edge endpoints out of range")
        if not np.isfinite(np.asarray(g.atom_fea)).all():
            _fail(f"graph {g.cif_id!r}: non-finite atom features")
        if not np.isfinite(np.asarray(g.edge_fea)).all():
            _fail(f"graph {g.cif_id!r}: non-finite edge features")
        if not np.isfinite(np.asarray(g.target, np.float64)).all():
            _fail(f"graph {g.cif_id!r}: non-finite target")
    return graphs


def maybe_spot_check_graphs(graphs, k: int = 16):
    if _ENABLED:
        spot_check_graphs(graphs, k)
    return graphs
