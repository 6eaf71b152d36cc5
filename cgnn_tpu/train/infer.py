"""Pipelined forward-only inference — predict.py's fast path.

The round-2-era predict loop fetched every batch synchronously; on a
high-latency link every fetch is a full round trip, so inference ran at
r2-era rates while training had moved on (VERDICT r4 weak #5). This path
applies the training loop's lessons to the forward pass:

- snug fill-to-capacity packing + size-class buckets (same policies as
  train.py; >=0.97 padding efficiency at MP scale);
- dispatch pipelining with a windowed value-fetch fence (bounds in-flight
  staged batches without a per-batch round trip);
- ONE stacked device_get per compiled shape instead of one transfer per
  batch (a device-side jnp.stack then a single link transfer).

ISSUE 3 made the compiled shapes injectable: pass ``shape_set`` (a
``serve.shapes.ShapeSet`` — the serving ladder) and batches pack into
those FIXED precompiled rungs instead of deriving fresh per-bucket
capacities — an offline predict job then reuses the online service's
shapes (and, through the persistent XLA cache, its compiled programs),
and the total compile count is pinned at ``len(shape_set)`` regardless
of dataset. ``predict_step`` is likewise injectable, so serve and
predict can share one jitted callable and its jit cache.

ISSUE 4 closed the remaining host gap (round 5, PERF.md: device 112,305
structs/s vs 1,461 end-to-end — 98.7% of a cold predict run was host
packing on the critical path) three ways, all in this function:

- **compact staging** (``compact=`` / a compact shape set): batches
  stage the raw ``CompactBatch`` form (~12x fewer host bytes written
  and H2D bytes moved) and the exact GraphBatch is rebuilt inside the
  jitted ``predict_step`` via ``make_expander`` — the train path's §7
  win, applied to the forward path, same parity pins;
- **parallel packing** (``pack_workers=``): a bounded pool of packer
  threads (data/pipeline.py) with order-restoring reassembly feeds the
  dispatch window, so the device never waits on a single packer;
- **buffer pooling**: compact packers write into reusable preallocated
  per-shape buffers instead of allocating per batch (the §7 page-fault
  bound); a buffer is recycled only after the window fence proves the
  dispatch that read it completed (FIFO per-device execution order).
"""

from __future__ import annotations

import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cgnn_tpu.data import invariants
from cgnn_tpu.data.graph import (
    assign_size_buckets,
    capacities_for,
    graph_cap_for,
    pack_graphs,
    plan_batches,
)
from cgnn_tpu.data.pipeline import BufferPool, parallel_pack
from cgnn_tpu.train.step import make_predict_step

# in-flight dispatch window before a bounding value fetch (same role as
# train.loop._WINDOW; one fence per window, NOT per batch)
_WINDOW = 16


def _shape_set_plan(graphs: Sequence, shape_set):
    """Yield (index span, graph sublist, shape): greedy fill to the
    LARGEST rung in input order; the ragged tail takes the smallest rung
    that fits it. Input order is preserved by construction, so spans are
    contiguous."""
    big = shape_set.largest
    start = 0
    cur: list = []
    n = e = 0
    for i, g in enumerate(graphs):
        if not shape_set.admits(g):
            raise ValueError(
                f"graph {getattr(g, 'cif_id', i)!r} exceeds the shape set: "
                f"{shape_set.oversize_detail(g)}"
            )
        gn, ge = shape_set.graph_counts(g)
        if cur and not big.fits(len(cur) + 1, n + gn, e + ge):
            yield np.arange(start, i), cur, big
            start, cur, n, e = i, [], 0, 0
        cur.append(g)
        n += gn
        e += ge
    if cur:
        yield (np.arange(start, len(graphs)), cur,
               shape_set.shape_for(len(cur), n, e))


def run_raw_inference(
    state,
    items: Sequence,
    shape_set,
    *,
    predict_step=None,
    devices: Sequence | None = None,
    engine: str = "auto",
    raw_fallback=None,
) -> tuple[np.ndarray, float]:
    """Predict over wire-form ``RawStructure`` items through the
    in-program neighbor search (ISSUE 11) -> ([n, T] predictions in
    input order, end-to-end structures/sec).

    ``shape_set`` must carry a raw spec; every item must pass
    ``shape_set.admits_raw`` (callers route the rest through the
    featurized path — predict.py does). Packing is near-zero host work
    (slot copies), so there is no pack pipeline here; batches fill the
    largest rung's graph slots greedily and the tail takes the smallest
    fitting rung. In-program cap-overflow flags (a lattice needing more
    images than the rung provides — possible only within the f32/f64
    eps band once ``admits_raw`` passed) are re-served through
    ``raw_fallback`` (RawStructure -> CrystalGraph) when given, else
    raised — NEVER silently answered from a truncated graph.

    ``devices``/``engine`` mirror ``run_fast_inference``: 'mesh' stacks
    batches N-at-a-time under one sharded dispatch; 'threads'
    round-robins per-device replicas; both bit-exact vs single-device.
    """
    from cgnn_tpu.data.rawbatch import RawStructure

    if shape_set is None or shape_set.raw is None:
        raise ValueError("run_raw_inference needs a shape set with a "
                         "raw spec (plan_shape_set(raw=...))")
    if not len(items):
        raise ValueError("no structures to predict")
    for it in items:
        if not isinstance(it, RawStructure):
            raise ValueError("run_raw_inference takes RawStructure items")
        if not shape_set.admits_raw(it):
            raise ValueError(
                f"structure {it.cif_id!r} exceeds the raw rung caps: "
                f"{shape_set.raw.oversize_detail(it)} — route it "
                f"through the featurized path"
            )
    if predict_step is None:
        predict_body = make_predict_step(
            shape_set.expander(), shape_set.raw_expander())
        predict_step = jax.jit(predict_body)
    else:
        predict_body = predict_step
    n = len(items)
    t0 = time.perf_counter()

    big = shape_set.largest

    def plan():
        start = 0
        while start < n:
            end = min(start + big.graph_cap, n)
            count = end - start
            shape = next(s for s in shape_set.shapes
                         if s.graph_cap >= count)
            yield np.arange(start, end), items[start:end], shape
            start = end

    use_mesh = (devices is not None and len(devices) > 1
                and engine in ("auto", "mesh"))
    if use_mesh:
        from cgnn_tpu.parallel.executor import MeshExecutor

        executor = MeshExecutor(devices)
        mesh_predict = executor.shard_predict(predict_body)
        placed = executor.place_params(state)
        states, n_dev = (state,), 1
    elif devices is not None and len(devices) > 1:
        from cgnn_tpu.serve.devices import replicate_state

        states = replicate_state(state, devices)
        n_dev = len(states)
    else:
        states, n_dev = (state,), 1

    preds: np.ndarray | None = None
    overflow_at: list = []  # (global index, item) pairs to re-serve
    outs: list = []  # (spans, shape, out tuple) per dispatch
    recent: list[list] = [[] for _ in range(max(n_dev, 1))]
    di_seq = [0]

    if use_mesh:
        group: list = []
        group_shape = [None]

        def _flush_group():
            if not group:
                return
            batches = [b for _, b in group]
            while len(batches) < len(executor):
                batches.append(batches[-1])
            staged = executor.stage(executor.stack(batches))
            out = mesh_predict(placed, staged)
            outs.append(([s for s, _ in group], group_shape[0], out))
            recent[0].append(out)
            if len(recent[0]) == _WINDOW:
                # fence on the OLDEST in-window result (the _WINDOW
                # discipline): the newer dispatches stay in flight
                float(recent[0][0][0][0, 0, 0])
                del recent[0][:]
            del group[:]

        for span, sub, shape in plan():
            if group_shape[0] is not None and (
                shape != group_shape[0] or len(group) == len(executor)
            ):
                _flush_group()
            group_shape[0] = shape
            group.append((span, shape_set.pack_raw(sub, shape=shape)))
            if len(group) == len(executor):
                _flush_group()
        _flush_group()
        for spans, _shape, out in outs:
            fetched = jax.tree_util.tree_map(
                lambda x: np.array(jax.device_get(x)), out)
            p, ovf = fetched[0], fetched[1]
            if preds is None:
                preds = np.zeros((n, p.shape[-1]), np.float32)
            for i, span in enumerate(spans):
                preds[span] = p[i][: len(span)]
                for k in np.nonzero(ovf[i][: len(span)])[0]:
                    overflow_at.append(int(span[k]))
    else:
        for span, sub, shape in plan():
            batch = shape_set.pack_raw(sub, shape=shape)
            di = di_seq[0] % n_dev
            di_seq[0] += 1
            out = predict_step(states[di], batch)
            outs.append(([span], shape, out))
            recent[di].append(out)
            if len(recent[di]) == _WINDOW:
                # value-fetch fence on the oldest in-window result
                # (train.loop._WINDOW discipline, tuple-aware)
                float(recent[di][0][0][0, 0])
                del recent[di][:]
        for spans, _shape, out in outs:
            p = np.array(jax.device_get(out[0]))
            ovf = np.array(jax.device_get(out[1]))
            span = spans[0]
            if preds is None:
                preds = np.zeros((n, p.shape[-1]), np.float32)
            preds[span] = p[: len(span)]
            for k in np.nonzero(ovf[: len(span)])[0]:
                overflow_at.append(int(span[k]))

    if overflow_at:
        # the in-program flag fired (INVARIANTS.md: never serve a
        # truncated graph): re-serve those rows host-featurized
        if raw_fallback is None:
            bad = [items[i].cif_id or str(i) for i in overflow_at]
            raise RuntimeError(
                f"in-program cap-overflow flag on {bad}; pass "
                f"raw_fallback= to re-serve them host-featurized"
            )
        fgraphs = [raw_fallback(items[i]) for i in overflow_at]
        fpreds, _ = run_fast_inference(
            state, fgraphs, max(1, len(fgraphs)), shape_set=shape_set,
            predict_step=predict_step,
        )
        for row, i in enumerate(overflow_at):
            preds[i] = fpreds[row]
    return preds, n / (time.perf_counter() - t0)


def run_fast_inference(
    state,
    graphs: Sequence,
    batch_size: int,
    *,
    buckets: int = 1,
    dense_m: int | None = None,
    snug: bool = True,
    edge_dtype=np.float32,
    predict_step=None,
    shape_set=None,
    compact=None,
    pack_workers: int = 0,
    devices: Sequence | None = None,
    engine: str = "auto",
    telemetry=None,
) -> tuple[np.ndarray, float]:
    """Predict over ``graphs`` -> ([n, T] predictions in input order,
    end-to-end structures/sec including host packing).

    Without ``shape_set``: buckets are processed one at a time with their
    own snug capacities; within a bucket the original graph order is
    preserved, so the output rows map back to the input by construction.

    With ``shape_set``: batches pack into the fixed rungs (module
    docstring); ``buckets``/``dense_m``/``snug``/``edge_dtype`` are
    ignored — the set carries the layout (including its compact spec).

    ``compact`` (a ``data.compact.CompactSpec``) stages the raw compact
    form; a compact ``shape_set`` implies it. The default
    ``predict_step`` then carries the matching expander — an INJECTED
    step must accept ``CompactBatch`` (``make_predict_step(expander)``).

    ``pack_workers > 0`` packs batches on that many pipeline threads
    (data/pipeline.py) overlapping the dispatch loop; ``0`` packs
    serially on the calling thread (identical outputs, pinned by test).

    ``devices`` (ISSUE 5; e.g. ``serve.devices.resolve_devices('auto')``)
    distributes the dispatch over that many devices; ``None`` keeps the
    single-device loop. ``engine`` picks HOW (ISSUE 10):

    - ``'mesh'`` (the ``'auto'`` default with > 1 device): consecutive
      same-shape batches stack N-at-a-time on a device axis and ONE
      sharded jitted dispatch (Mesh + NamedSharding,
      parallel/executor.py) runs all N — the program count stays at one
      per compiled shape (never programs x N executables), and the
      windowed value-fetch fence bounds in-flight stacks exactly like
      the single-device loop;
    - ``'threads'`` keeps the ISSUE-5 replica path: batch k runs on
      device k % N against that device's committed replica, per-device
      in-flight windows, ONE stacked fetch per (shape, device).

    Both are BIT-identical to the single-device path over identical
    batches (same packing plan, same per-shard program — pinned by
    tests/test_executor.py and test_infer.py).
    """
    if not len(graphs):
        raise ValueError("no graphs to predict")
    if shape_set is not None and shape_set.compact is not None:
        if compact is not None and compact is not shape_set.compact:
            raise ValueError("shape_set already carries a compact spec")
        compact = shape_set.compact
    if engine not in ("auto", "mesh", "threads"):
        raise ValueError(
            f"engine must be 'auto', 'mesh', or 'threads', got {engine!r}"
        )
    predict_body = None
    if predict_step is None:
        expander = None
        if compact is not None:
            from cgnn_tpu.data.compact import make_expander

            expander = make_expander(compact)
        predict_body = make_predict_step(expander)
        predict_step = jax.jit(predict_body)
    n = len(graphs)
    preds: np.ndarray | None = None
    t0 = time.perf_counter()

    # the execution layer over the device set (ISSUE 10): 'mesh' = one
    # sharded dispatch covers N devices (the default); 'threads' = the
    # ISSUE-5 per-device replica round-robin, kept for the A/B
    use_mesh = (devices is not None and len(devices) > 1
                and engine in ("auto", "mesh"))
    executor = mesh_predict = placed_state = None
    if use_mesh:
        from cgnn_tpu.parallel.executor import MeshExecutor

        executor = MeshExecutor(devices)
        # wrap the raw body when we built it; an injected (jitted)
        # predict_step traces through inside the sharded program
        mesh_predict = executor.shard_predict(predict_body or predict_step)
        placed_state = executor.place_params(state)
        states = (state,)
        n_dev = 1  # the per-batch round-robin below is bypassed
    # device replicas (threads engine): batch k dispatches against
    # states[k % n_dev] — the replica is committed to its device, the
    # staged batch is uncommitted host memory, so computation follows
    # the params to the right chip (serve/devices.py)
    elif devices is not None and len(devices):
        from cgnn_tpu.serve.devices import replicate_state

        states = replicate_state(state, devices)
        n_dev = len(states)
    else:
        states = (state,)
        n_dev = 1
    dispatched = [0]

    # ((shape key, device) -> [(span, out)]) so the single stacked fetch
    # groups by compiled shape AND by the device holding the outputs;
    # spans restore input order on the host afterwards
    outs_by_shape: dict = {}
    recent: list[list] = [[] for _ in range(n_dev)]
    # compact staging buffers in per-device dispatch order; an entry is
    # released to the pool once ITS device's window fence proves its
    # dispatch completed (execution is FIFO per device, not across them).
    # The mesh engine packs fresh arrays instead: the group stack copies
    # every staged byte immediately, so a recycle fence buys nothing
    pool = BufferPool() if compact is not None and not use_mesh else None
    pending: list[list] = [[] for _ in range(n_dev)]

    def _release_fenced(di):
        # the fence blocked on the FIRST dispatch of device di's closing
        # window: everything dispatched before it on THAT device
        # completed (FIFO per device), so all but the window's remaining
        # _WINDOW - 1 dispatches are safe
        safe = len(pending[di]) - (_WINDOW - 1)
        if safe > 0:
            for item in pending[di][:safe]:
                if item is not None:
                    pool.release(*item)
            del pending[di][:safe]

    def _dispatch(span, batch, key, buf=None):
        di = dispatched[0] % n_dev  # round-robin across the device set
        dispatched[0] += 1
        out = predict_step(states[di], batch)
        outs_by_shape.setdefault((key, di), []).append((span, out))
        recent[di].append(out)
        if pool is not None:
            pending[di].append(buf)
        if len(recent[di]) == _WINDOW:
            # true fence (a value fetch: a data dependency on any
            # runtime) on the OLDEST in-window result: proves everything
            # dispatched before it ON THIS DEVICE finished — bounding
            # staged-batch HBM per chip — while the newer _WINDOW-1
            # dispatches stay in flight
            float(recent[di][0][0, 0])
            del recent[di][:]
            if pool is not None:
                _release_fenced(di)

    if shape_set is not None:
        def pack_job(job):
            span, sub, shape = job
            buf = None
            if pool is not None:
                key = shape_set.buffer_key(shape)
                buf = (key, pool.acquire(key, shape_set.buffer_factory(shape)))
            batch = shape_set.pack(sub, shape=shape,
                                   out=None if buf is None else buf[1])
            return span, invariants.maybe_check(batch, shape_set.dense_m), \
                shape, buf

        jobs = _shape_set_plan(graphs, shape_set)
    else:
        bucket_of = assign_size_buckets(graphs, buckets)
        graph_cap = graph_cap_for(batch_size) if snug else batch_size
        tdim = int(np.atleast_1d(graphs[0].target).shape[0])

        def bucket_jobs():
            for b in range(int(bucket_of.max()) + 1):
                idxs = np.nonzero(bucket_of == b)[0]
                if len(idxs) == 0:
                    continue
                sub = [graphs[int(i)] for i in idxs]
                nc, ec = capacities_for(sub, batch_size, dense_m=dense_m,
                                        snug=snug)
                for s, e in plan_batches(sub, batch_size, nc, ec, snug=snug):
                    yield idxs[s:e], sub[s:e], (b, nc, ec), nc, ec

        def pack_job(job):
            span, sub, key, nc, ec = job
            buf = None
            if compact is not None:
                from cgnn_tpu.data.compact import (
                    alloc_compact_buffers,
                    compact_buffer_key,
                    pack_compact,
                )

                if pool is not None:
                    bkey = compact_buffer_key(nc, dense_m, graph_cap, tdim)
                    buf = (bkey, pool.acquire(
                        bkey,
                        lambda: alloc_compact_buffers(nc, dense_m,
                                                      graph_cap, tdim),
                    ))
                batch = pack_compact(sub, nc, ec, graph_cap, compact,
                                     num_targets=tdim, dense_m=dense_m,
                                     out=None if buf is None else buf[1])
            else:
                batch = pack_graphs(sub, nc, ec, graph_cap, dense_m=dense_m,
                                    edge_dtype=edge_dtype)
            return span, invariants.maybe_check(batch, dense_m), key, buf

        jobs = bucket_jobs()

    if pack_workers > 0:
        packed = parallel_pack(jobs, pack_job, workers=pack_workers,
                               telemetry=telemetry)
    else:
        packed = map(pack_job, jobs)

    if use_mesh:
        # mesh engine: consecutive same-shape batches stack N-at-a-time
        # on the device axis; ONE sharded dispatch runs all N. A group
        # shorter than the mesh (the shape-boundary or dataset tail)
        # pads by repeating its last batch — padded rows are never read.
        group: list = []  # [(span, batch)]
        group_key = [None]
        recent_m: list = []

        def _flush_group():
            if not group:
                return
            batches = [b for _, b in group]
            while len(batches) < len(executor):
                batches.append(batches[-1])
            staged = executor.stage(executor.stack(batches))
            out = mesh_predict(placed_state, staged)
            outs_by_shape.setdefault(group_key[0], []).append(
                ([s for s, _ in group], out))
            recent_m.append(out)
            if len(recent_m) == _WINDOW:
                # the same in-flight bound as the single-device loop,
                # per sharded dispatch: a true value fetch on the
                # oldest in-window result (FIFO dispatch stream)
                float(recent_m[0][0, 0, 0])
                del recent_m[:]
            del group[:]

        for span, batch, key, _buf in packed:
            if group_key[0] is not None and (
                key != group_key[0] or len(group) == len(executor)
            ):
                _flush_group()
            group_key[0] = key
            group.append((span, batch))
            if len(group) == len(executor):
                _flush_group()
        _flush_group()

        for entries in outs_by_shape.values():
            # one stacked fetch per compiled shape: [D, N, G, T]
            fetched = np.array(  # true copy, not an alias (GC-ALIAS)
                jax.device_get(jnp.stack([o for _, o in entries]))
            )
            if preds is None:
                preds = np.zeros((n, fetched.shape[-1]), np.float32)
            for (spans, _), o in zip(entries, fetched):
                for i, span in enumerate(spans):
                    preds[span] = o[i][: len(span)]
        return preds, n / (time.perf_counter() - t0)

    for span, batch, key, buf in packed:
        _dispatch(span, batch, key, buf)

    for group in outs_by_shape.values():
        stacked = np.array(  # true copy, not an aliasing view (GC-ALIAS)
            jax.device_get(jnp.stack([out for _, out in group]))
        )
        if preds is None:
            preds = np.zeros((n, stacked.shape[-1]), np.float32)
        for (span, _), o in zip(group, stacked):
            preds[span] = o[: len(span)]
    return preds, n / (time.perf_counter() - t0)
