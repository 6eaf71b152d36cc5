"""Data-parallel training over a mesh (SURVEY.md §2 parallelism inventory).

The reference wraps its model in DDP: per-rank processes, NCCL allreduce on
gradient buckets overlapped with backward (SURVEY.md §3.5). The TPU-native
equivalent is one SPMD program: per-device packed GraphBatches are stacked
on a leading device axis, sharded over ``Mesh(('data',))``, and the step
body (cgnn_tpu.train.step with ``axis_name='data'``) runs under shard_map —
``pmean`` on grads/BatchNorm stats becomes an ICI allreduce placed by XLA
wherever it overlaps best. Batch semantics match DDP: identical params on
every device, global batch = sum of per-device batches, metric sums are
exact psum totals.
"""

from __future__ import annotations

import time
from typing import Callable, Iterable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cgnn_tpu.data.graph import (
    CrystalGraph,
    GraphBatch,
    PaddingStats,
    batch_iterator,
    batch_shape_key,
    bucketed_batch_iterator,
)
from cgnn_tpu.resilience import faultinject
from cgnn_tpu.train.state import TrainState
from cgnn_tpu.train.step import (
    jit_sharded_train_step,
    make_eval_step,
    make_train_step,
)


def stack_batches(batches: Sequence[GraphBatch]) -> GraphBatch:
    """Stack D same-shape batches on a new leading device axis."""
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)


def empty_batch_like(batch: GraphBatch) -> GraphBatch:
    """All-padding batch with the same capacities (masks are zero).

    Used to pad the last eval step up to a full device group; contributes
    exactly zero to psum-ed metric sums. Never use for training steps —
    running-stat updates would average in its degenerate statistics.
    Under --check-invariants this is ENFORCED: parallel_batches checks
    train-time device groups and make_parallel_train_step rejects
    host-side stacked batches with an all-padding row.
    """
    ncap = batch.node_capacity
    if hasattr(batch, "atom_idx"):  # CompactBatch: the same contract
        from cgnn_tpu.data.compact import _base_neighbors

        empty = jax.tree_util.tree_map(np.zeros_like, batch)
        return empty.replace(
            # padding slots point at their own node, as pack_compact's do
            neighbors=_base_neighbors(
                ncap, batch.distances.shape[-1]).copy(),
            **_empty_overflow(batch),
        )
    # dense layout: centers/neighbors are STRUCTURAL (slot k belongs to
    # node k//M; padding = masked self-loops), so the empty batch keeps the
    # ownership pattern; flat COO padding points at the last node slot
    dense = np.ndim(batch.edges) == 3
    empty_centers = (np.array(batch.centers) if dense
                     else np.full_like(batch.centers, ncap - 1))
    return GraphBatch(
        nodes=np.zeros_like(batch.nodes),
        edges=np.zeros_like(batch.edges),
        centers=empty_centers,
        neighbors=(empty_centers.copy() if dense
                   else np.full_like(batch.neighbors, ncap - 1)),
        node_graph=np.zeros_like(batch.node_graph),
        node_mask=np.zeros_like(batch.node_mask),
        edge_mask=np.zeros_like(batch.edge_mask),
        graph_mask=np.zeros_like(batch.graph_mask),
        targets=np.zeros_like(batch.targets),
        target_mask=np.zeros_like(batch.target_mask),
        positions=np.zeros_like(batch.positions),
        lattices=np.zeros_like(batch.lattices),
        edge_offsets=np.zeros_like(batch.edge_offsets),
        node_targets=np.zeros_like(batch.node_targets),
        in_slots=None if batch.in_slots is None else np.zeros_like(batch.in_slots),
        in_mask=None if batch.in_mask is None else np.zeros_like(batch.in_mask),
        over_slots=(None if batch.over_slots is None
                    else np.zeros_like(batch.over_slots)),
        over_runs=(None if batch.over_runs is None
                   else np.zeros_like(batch.over_runs)),
        **_empty_overflow(batch),
    )


def _empty_overflow(batch) -> dict:
    """The overflow list of an all-padding batch, as ``transpose_slots``
    packs one with no real entry: every entry names the last node slot (so
    ``over_nodes`` stays non-decreasing) and no node owns a run (every
    pointer out of range, where the backward reads a zero row)."""
    if batch.over_nodes is None:
        return {}
    return {
        "over_nodes": np.full_like(batch.over_nodes,
                                   batch.node_capacity - 1),
        "over_last": np.full_like(batch.over_last,
                                  np.shape(batch.over_slots)[-1]),
    }


def parallel_batches(
    graphs: Sequence[CrystalGraph],
    n_devices: int,
    batch_size: int,
    node_cap: int,
    edge_cap: int,
    shuffle: bool = False,
    rng: np.random.Generator | None = None,
    pad_incomplete: bool = False,
    dense_m: int | None = None,
    in_cap: int | None = None,
    buckets: int = 1,
    snug: bool = False,
    stats: PaddingStats | None = None,
    edge_dtype=np.float32,
    pack_fn: Callable | None = None,
    telemetry=None,
) -> Iterable[GraphBatch]:
    """Yield device-stacked batches: leaves have leading axis [D, ...].

    ``batch_size`` is per device (global batch = D * batch_size). Training
    drops an incomplete trailing device group (DDP drop_last semantics);
    eval pads it with empty batches so every structure is scored.

    ``buckets > 1`` sources per-size-class batches (bucketed_batch_iterator;
    ``node_cap``/``edge_cap`` are then ignored — each bucket computes its
    own) and groups same-shape batches into device groups, so every device
    in a group runs the same compiled shape. At most ``n_devices - 1``
    batches per shape are dropped per training epoch (the per-shape
    drop_last tail).

    ``pack_fn`` is handed to the batch iterators as the one-chip path hands
    it (``data.compact.compact_pack_fn``: compact staging); a compact batch
    stacks on the device axis like any other pytree.

    ``telemetry`` counts the training tail that ``drop_last`` discards
    (``dp_dropped_batches``: per-device batches, once a pass over the data).
    """
    if buckets > 1:
        source = bucketed_batch_iterator(
            graphs, batch_size, buckets, shuffle=shuffle, rng=rng,
            dense_m=dense_m, in_cap=in_cap, snug=snug, stats=stats,
            edge_dtype=edge_dtype, pack_fn=pack_fn,
        )
    else:
        source = batch_iterator(
            graphs, batch_size, node_cap, edge_cap, shuffle=shuffle, rng=rng,
            dense_m=dense_m, in_cap=in_cap, snug=snug,
            edge_dtype=edge_dtype, pack_fn=pack_fn,
        )
        if stats is not None:
            source = stats.wrap(source)
    from cgnn_tpu.data import invariants

    pending: dict[tuple, list[GraphBatch]] = {}
    for b in source:
        key = batch_shape_key(b)
        q = pending.setdefault(key, [])
        q.append(b)
        if len(q) == n_devices:
            # train-time device groups (pad_incomplete=False) must have no
            # empty rows — the empty_batch_like eval-only contract
            yield invariants.maybe_check_any(
                stack_batches(q), dense_m, train=not pad_incomplete
            )
            pending[key] = []
    if pad_incomplete:
        for q in pending.values():
            if q:
                q += [empty_batch_like(q[0])] * (n_devices - len(q))
                yield invariants.maybe_check_any(stack_batches(q), dense_m)
    elif telemetry is not None:
        telemetry.counter_add("dp_dropped_batches",
                              sum(len(q) for q in pending.values()))


def is_multiprocess_mesh(mesh: Mesh) -> bool:
    """True when ``mesh`` spans devices of more than one jax process —
    the multi-host DP case, where host-local staging must go through
    the global-array layer (parallel/dist.py) because ``device_put``
    can only address local devices."""
    from cgnn_tpu.parallel import dist

    if not dist.active():
        return False
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def shard_leading_axis(tree, mesh: Mesh):
    """Stage a stacked batch: leading axis split over every mesh axis.

    Single-process: a plain sharded ``device_put``. Multi-process
    (``jax.distributed``): ``tree`` is this HOST'S local ``[n_local,
    ...]`` stack and the global batch is the process-order concatenation
    of every host's stack (dist.shard_global) — the loader-side per-host
    slicing of multi-host DP."""
    axes = _replica_axes(mesh)
    if is_multiprocess_mesh(mesh):
        from cgnn_tpu.parallel import dist

        return dist.shard_global(tree, mesh, P(axes))

    def put(x):
        return jax.device_put(
            x, NamedSharding(mesh, P(axes, *([None] * (np.ndim(x) - 1)))))
    return jax.tree_util.tree_map(put, tree)


def shard_scan_stack(tree, mesh: Mesh):
    """device_put a STACK of device-stacked batches ([B, D, ...] leaves):
    axis 0 is the scan/step axis (replicated), axis 1 the device axis
    (split over the mesh's axes) — the staging for ScanEpochDriver
    under data parallelism. A compact stack goes over with flat rows
    (``data.compact.flat_rows``: the same fields and bytes, laid out so
    that the chip never re-lays out the resident stack)."""
    axes = _replica_axes(mesh)
    if hasattr(tree, "atom_idx"):  # CompactBatch (duck-typed, as elsewhere)
        from cgnn_tpu.data.compact import flat_rows

        tree = flat_rows(tree)

    def put(x):
        return jax.device_put(
            x,
            NamedSharding(mesh, P(None, axes, *([None] * (np.ndim(x) - 2)))),
        )
    return jax.tree_util.tree_map(put, tree)


def _squeeze0(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _per_shard(inner: Callable, expand: Callable | None) -> Callable:
    """The body one device runs under shard_map: its row of the stacked
    batch, expanded from the compact form when that is what was staged."""

    def body(state: TrainState, stacked):
        batch = _squeeze0(stacked)
        return inner(state, batch if expand is None else expand(batch))

    return body


def allreduce_nbytes(state: TrainState) -> int:
    """Bytes one replica hands to a training step's all-reduce: the
    gradient (one entry a parameter) and the running statistics."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (state.params, state.batch_stats)))


def count_deployment(telemetry, state: TrainState, n_replicas: int,
                     batch_size: int) -> None:
    """The data-parallel deployment as counters of the run (once a fit)."""
    telemetry.counter_add("dp_replicas", n_replicas)
    telemetry.counter_add("dp_global_batch", n_replicas * batch_size)
    telemetry.counter_add("allreduce_bytes_per_step",
                          allreduce_nbytes(state))


def _replica_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh's axes, every one of which carries data replicas. A
    multi-host ('dcn', 'data') mesh reduces over both axes — XLA routes each
    partial reduction over the matching fabric. A mesh comes from outside:
    one with any other axis is refused, not folded into the replica count."""
    for a in mesh.axis_names:
        if a not in ("dcn", "data"):
            raise ValueError(
                f"mesh axis {a!r} is not a data-parallel axis: the step "
                f"replicates over 'data' (and 'dcn' above it) and shards "
                f"nothing else (mesh axes {mesh.axis_names})")
    return tuple(mesh.axis_names)


def make_parallel_train_step(
    mesh: Mesh,
    classification: bool = False,
    loss_fn: Callable | None = None,
    inner_step: Callable | None = None,
    grad_health: bool = False,
    guard: bool = False,
    expand: Callable | None = None,
) -> Callable:
    """shard_map-wrapped train step: (replicated state, [D,...] batch).

    The batch's leading device axis is split over every mesh axis, so a
    1-D ('data',) mesh and a hierarchical ('dcn', 'data') multi-host mesh
    run the same step body.

    ``inner_step`` overrides the default step body entirely (it must already
    be built with ``axis_name='data'`` — e.g. the force-task step; only
    supported on 1-D data meshes). ``grad_health`` adds the in-graph
    grad/update-norm and NaN/Inf metrics to the default body
    (train.step.make_train_step); extra outputs only. ``guard`` wraps the
    body with the in-graph divergence guard (resilience.guard): the
    post-pmean params it checks are replicated, so every device takes the
    same keep-or-skip branch.

    ``expand`` (``data.compact.make_expander``: compact staging) rebuilds
    the full batch from its raw form INSIDE the per-shard body, after the
    device row is taken: every chip expands its own row from its own copy
    of the element table, so no table row and no batch crosses the mesh.
    """
    axes = _replica_axes(mesh)
    if inner_step is not None and axes != ("data",):
        raise NotImplementedError(
            f"custom step bodies assume axis_name='data'; mesh has {axes}"
        )
    inner = inner_step or make_train_step(
        classification, axis_name=axes, loss_fn=loss_fn,
        grad_health=grad_health,
    )
    if guard:
        from cgnn_tpu.resilience.guard import guard_step

        inner = guard_step(inner)
    body = _per_shard(inner, expand)

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(axes)),
        out_specs=(P(), P()),
        check_vma=False,  # grads/stats are pmean-ed -> replicated outputs
    )
    jitted = jit_sharded_train_step(smapped, mesh)

    def guarded(state: TrainState, stacked: GraphBatch):
        # --check-invariants last line of defense for direct callers that
        # bypass the (already-checked) iterators: a host-side batch with an
        # all-padding device row must not reach a TRAINING step (the
        # empty_batch_like eval-only contract). Device-resident/traced
        # batches skip this (their construction paths were checked).
        from cgnn_tpu.data import invariants

        if invariants.enabled() and isinstance(stacked.graph_mask, np.ndarray):
            gm = stacked.graph_mask
            if (gm.reshape(gm.shape[0], -1).sum(axis=1) == 0).any():
                raise invariants.BatchInvariantError(
                    "training step received a stacked batch with an "
                    "all-padding device row (empty_batch_like is eval-only)"
                )
        return jitted(state, stacked)

    # the underlying jit, exposed for .lower() callers (the graftaudit
    # donation/roofline checks lower the REAL DP program, not a rebuild)
    guarded.jitted = jitted
    return guarded


def make_parallel_eval_step(
    mesh: Mesh,
    classification: bool = False,
    loss_fn: Callable | None = None,
    inner_step: Callable | None = None,
    expand: Callable | None = None,
) -> Callable:
    axes = _replica_axes(mesh)
    if inner_step is not None and axes != ("data",):
        raise NotImplementedError(
            f"custom step bodies assume axis_name='data'; mesh has {axes}"
        )
    inner = inner_step or make_eval_step(
        classification, axis_name=axes, loss_fn=loss_fn
    )
    smapped = jax.shard_map(
        _per_shard(inner, expand), mesh=mesh, in_specs=(P(), P(axes)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(smapped)


def replicate_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place every state leaf replicated across the mesh (the
    global-array path when the mesh spans processes — every host must
    hold the identical state, which resume/restore guarantees)."""
    if is_multiprocess_mesh(mesh):
        from cgnn_tpu.parallel import dist

        return dist.replicate_global(state, mesh)
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), state
    )


def fit_data_parallel(
    state: TrainState,
    train_graphs: Sequence[CrystalGraph],
    val_graphs: Sequence[CrystalGraph],
    *,
    epochs: int,
    batch_size: int,
    node_cap: int,
    edge_cap: int,
    classification: bool = False,
    seed: int = 0,
    print_freq: int = 10,
    on_epoch_end: Callable | None = None,
    log_fn: Callable = print,
    start_epoch: int = 0,
    mesh: Mesh | None = None,
    train_step_fn: Callable | None = None,
    eval_step_fn: Callable | None = None,
    best_metric: str | None = None,
    on_epoch_metrics: Callable | None = None,
    pack_once: bool = False,
    device_resident: bool = False,
    dense_m: int | None = None,
    buckets: int = 1,
    snug: bool = False,
    scan_epochs: bool = False,
    profile_steps: int = 0,
    profile_dir: str = "",
    edge_dtype=np.float32,
    compact=None,
    chunk_steps: int | None = None,
    telemetry=None,
    guard: bool = False,
    monitor=None,
    preempt=None,
) -> tuple[TrainState, dict]:
    """DP twin of train.loop.fit; ``batch_size`` is per device.

    Feature parity with the single-device loop (VERDICT r2 #3): ``buckets``
    batches per size class and groups same-shape batches per device group;
    ``scan_epochs`` folds each epoch into one lax.scan dispatch per shape
    (ScanEpochDriver over mesh-sharded stacks); ``profile_steps`` traces
    post-compile steps of the first epoch. None of these are silently
    dropped anymore — unsupported combinations raise.

    ``train_step_fn``/``eval_step_fn`` override the step bodies (they must
    be built with ``axis_name='data'``); ``best_metric`` overrides the
    model-selection key.

    ``pack_once`` / ``device_resident`` mirror train.loop.fit: pack (and,
    for device_resident, mesh-shard into HBM) the stacked batches once,
    reshuffling stacked-batch order across epochs.

    What is staged under a mesh is what is staged on one chip. With
    ``compact`` (a ``data.compact.CompactSpec``; requires ``scan_epochs``
    and the dense layout, as in train.loop.fit) every device row is a
    ``CompactBatch`` — vocabulary indices and scalar distances, ~12x fewer
    bytes — split over the replica axes like any other leaf, and each
    device rebuilds its own row inside the per-shard step body
    (``make_parallel_train_step(expand=...)``). Without it the rows are
    full ``GraphBatch``es.

    ``telemetry`` mirrors train.loop.fit: spans, padding/HBM gauges, and
    — with ``scan_epochs`` at step level — the in-scan per-step stream
    (the driver taps the post-shard_map metrics, one callback per step).
    The DP PER-STEP loop does not stream (its metrics live inside the
    shard_map body); epoch aggregates and gauges still flow.

    ``guard``/``monitor``/``preempt`` mirror train.loop.fit (the
    resilience layer; see that docstring). The guard wraps the step
    INSIDE shard_map — its keep-or-skip condition reads replicated
    post-pmean values, so every device selects the same branch. A
    monitor rollback re-replicates the restored state over the mesh
    automatically.
    """
    from cgnn_tpu.observe import Telemetry
    from cgnn_tpu.parallel.mesh import make_mesh

    telemetry = telemetry or Telemetry.disabled()
    mesh = mesh or make_mesh()
    _replica_axes(mesh)  # refuses a mesh with an axis that is no replica axis
    n_dev = int(mesh.devices.size)
    if dense_m is not None:
        edge_cap = node_cap * dense_m
    pack_fn = expand = None
    if compact is not None:
        if not scan_epochs or dense_m is None:
            raise ValueError("compact staging requires scan_epochs and the "
                             "dense layout (dense_m), as in train.loop.fit")
        from cgnn_tpu.data.compact import compact_pack_fn, make_expander

        pack_fn, expand = compact_pack_fn(compact), make_expander(compact)
    multiproc = is_multiprocess_mesh(mesh)
    if multiproc:
        if scan_epochs or device_resident or pack_once:
            raise NotImplementedError(
                "multi-host DP runs the per-step loop (scan/"
                "device-resident staging is host-local); drop "
                "--scan-epochs/--device-resident/--pack-once"
            )
        # each host packs device groups for its LOCAL share of the
        # mesh; the global batch is the process-order concatenation
        # (shard_leading_axis stages it as one global array). The
        # CALLER host-shards the graphs (dist.host_shard) so hosts
        # pack disjoint data.
        n_dev_global = n_dev
        n_dev = max(1, n_dev // jax.process_count())
    train_step = make_parallel_train_step(
        mesh, classification, inner_step=train_step_fn,
        grad_health=telemetry.step_level, guard=guard, expand=expand,
    )
    eval_step = make_parallel_eval_step(
        mesh, classification, inner_step=eval_step_fn, expand=expand,
    )
    shard_put = lambda b: shard_leading_axis(b, mesh)  # noqa: E731
    state = replicate_state(state, mesh)
    count_deployment(telemetry, state,
                     n_dev_global if multiproc else n_dev, batch_size)
    best = -np.inf if classification else np.inf
    history = []
    rng = np.random.default_rng(seed)
    from cgnn_tpu.data.loader import prefetch_to_device
    from cgnn_tpu.train.loop import (
        PackOncePlan,
        ScanEpochDriver,
        profile_wrap,
        resilience_epoch_end,
        run_epoch,
        save_preempted_mid_epoch,
    )

    device_resident = device_resident or scan_epochs
    pack_once = pack_once or device_resident
    pad_stats = PaddingStats()

    def make_train_it():
        # env-gated deterministic fault injection (NaN batches, loader
        # exceptions); unwrapped when no plan is active. Wrapped AROUND
        # parallel_batches, so a poisoned batch is a full stacked device
        # group — every shard sees the fault, like a real bad record
        return faultinject.poison_batches(parallel_batches(
            train_graphs, n_dev, batch_size, node_cap, edge_cap,
            shuffle=True, rng=rng, dense_m=dense_m, buckets=buckets,
            snug=snug, stats=pad_stats, edge_dtype=edge_dtype,
            pack_fn=pack_fn, telemetry=telemetry,
        ))

    def make_val_it():
        return parallel_batches(
            val_graphs, n_dev, batch_size, node_cap, edge_cap,
            pad_incomplete=True, dense_m=dense_m, in_cap=0, buckets=buckets,
            snug=snug, edge_dtype=edge_dtype, pack_fn=pack_fn,
        )

    if multiproc:
        from cgnn_tpu.parallel import dist

        _base_train_it, _base_val_it = make_train_it, make_val_it

        def _equalized(base):
            # every host must run the SAME number of collective steps:
            # a host whose shard packed one more device group than its
            # peers would enter an allreduce nobody else joins (hang,
            # not error) — truncate every epoch to the shortest host.
            # COST, stated honestly: the count requires packing the
            # epoch, so the stacked batches materialize in host RAM up
            # front and the prefetch pack/compute overlap is lost for
            # multi-host runs (a second packing pass can't replace it:
            # the shuffled pack is rng-drawn, so two passes disagree on
            # the count itself). Fine at readiness scale; a streaming
            # upgrade needs a deterministic batch-count plan.
            batches = list(base())
            return iter(batches[: dist.min_over_hosts(len(batches))])

        def make_train_it():
            return _equalized(_base_train_it)

        def make_val_it():
            return _equalized(_base_val_it)

    driver: ScanEpochDriver | None = None
    packed_lists: tuple | None = None
    if scan_epochs:
        if profile_steps:
            log_fn(
                "scan_epochs: --profile is unavailable inside the "
                "whole-epoch scan (epoch-level metrics only)"
            )
        from cgnn_tpu.train.loop import (
            check_device_resident_fit,
            staged_nbytes,
        )

        with telemetry.span("pack"):
            train_list = list(make_train_it())
            val_list = list(make_val_it())
        staged_bytes = staged_nbytes(train_list + val_list)
        fits = check_device_resident_fit(staged_bytes, n_devices=n_dev,
                                         log_fn=log_fn)
        if fits:
            with telemetry.span("stage_scan_stacks"):
                driver = ScanEpochDriver(
                    train_step, eval_step, train_list, val_list, rng,
                    stage=lambda t: shard_scan_stack(t, mesh),
                    chunk_steps=chunk_steps, telemetry=telemetry,
                    preempt=preempt,
                )
            telemetry.sample_hbm("post_staging")
        else:
            # loud fallback (see check_device_resident_fit): host-side
            # pack-once, mesh-sharded restaging per epoch
            scan_epochs = False
            device_resident = False
            packed_lists = (train_list, val_list)
    plan = (
        PackOncePlan(
            (lambda: packed_lists[0]) if packed_lists is not None
            else make_train_it,
            (lambda: packed_lists[1]) if packed_lists is not None
            else make_val_it,
            rng,
            device_resident=device_resident, stage=shard_put,
        )
        if pack_once and driver is None
        else None
    )

    telemetry.observe_padding(pad_stats)
    if telemetry.step_level and driver is None:
        # the PR-1 known gap, closed (ISSUE 3): the DP per-step loop now
        # streams step records like the scan path. The tap cannot live
        # INSIDE the shard_map body (per-shard callbacks would emit one
        # partial record per device), but by the time metrics exit the
        # shard_map they are replicated psum totals — so wrap the whole
        # sharded step in an outer jit that stages ONE async callback per
        # step carrying the global sums. The scan driver is excluded on
        # purpose: it stages its own in-scan tap (wrapping here too would
        # double-record every step).
        train_step = jit_sharded_train_step(
            telemetry.wrap_train_body(train_step), mesh)
        eval_step = jax.jit(telemetry.wrap_eval_body(eval_step))
    if monitor is not None and monitor.post_restore is None:
        # a rollback restores onto the default device; re-place it
        # replicated over the mesh before the next sharded step
        monitor.post_restore = lambda s: replicate_state(s, mesh)
    preempted = False
    for epoch in range(start_epoch, epochs):
        t0 = time.perf_counter()
        if driver is not None:
            with telemetry.span("epoch", epoch=epoch, driver="scan"):
                state, train_m, val_m = driver.run_epoch_pair(
                    state, first=epoch == start_epoch
                )
            if driver.aborted:
                save_preempted_mid_epoch(state, epoch, on_epoch_end, log_fn)
                preempted = True
                break
            if epoch == start_epoch:
                log_fn(pad_stats.summary())
        else:
            if plan is not None:
                epoch_train, epoch_val = plan.epoch_iterators()
                if device_resident:
                    train_it, val_it = epoch_train, epoch_val
                else:
                    train_it = prefetch_to_device(
                        epoch_train, device_put=shard_put,
                        telemetry=telemetry)
                    val_it = prefetch_to_device(
                        epoch_val, device_put=shard_put, telemetry=telemetry)
            else:
                train_it = prefetch_to_device(
                    make_train_it(), device_put=shard_put, telemetry=telemetry
                )
                val_it = prefetch_to_device(
                    make_val_it(), device_put=shard_put, telemetry=telemetry)
            if epoch == start_epoch and profile_steps:
                train_it = profile_wrap(
                    train_it, profile_steps, profile_dir, log_fn
                )
            with telemetry.span("epoch", epoch=epoch, driver="per_step"):
                state, train_m = run_epoch(
                    train_step, state, train_it, train=True,
                    print_freq=print_freq, epoch=epoch, log_fn=log_fn,
                    telemetry=telemetry,
                )
            if epoch == start_epoch:
                log_fn(pad_stats.summary())
        if train_m["steps"] == 0:
            # drop_last semantics silently discard every incomplete device
            # group; a too-small dataset would otherwise "train" on nothing
            raise ValueError(
                f"no full device group: {len(train_graphs)} training graphs "
                f"cannot fill {n_dev} devices x batch_size {batch_size}; "
                f"reduce --batch-size or the device count"
            )
        train_count = max(train_m.get("count", 1.0), 1.0)
        train_loss = train_m.get("loss", np.nan)

        if driver is None:
            _, val_m = run_epoch(
                eval_step, state, val_it, train=False, epoch=epoch,
                log_fn=log_fn, telemetry=telemetry,
            )
        best_key = best_metric or ("correct" if classification else "mae")
        metric = val_m.get(best_key, np.nan)
        is_best = metric > best if classification else metric < best
        if driver is not None and driver.eval_truncated:
            # preemption cut eval short: the metric covers a fraction of
            # the validation set — never let it repoint 'best'
            is_best = False
        if is_best:
            best = metric
        history.append({"epoch": epoch, "train_loss": train_loss, "val": val_m})
        tag = (f"dp x{n_dev_global} over {jax.process_count()} hosts"
               if multiproc else f"dp x{n_dev}")
        log_fn(
            f"Epoch {epoch} [{tag}]: train loss {train_loss:.4f}"
            f"  val {best_key} {metric:.4f}"
            f"{' *' if is_best else ''}  ({time.perf_counter() - t0:.1f}s)"
        )
        if on_epoch_metrics is not None:
            on_epoch_metrics(
                epoch, {"loss": train_loss, "count": train_count}, val_m
            )
        state, _, preempted = resilience_epoch_end(
            state, epoch, train_m, val_m, is_best, monitor=monitor,
            on_epoch_end=on_epoch_end, preempt=preempt, log_fn=log_fn,
        )
        if preempted:
            break
    out = {"best": best, "history": history}
    if preempted:
        out["preempted"] = True
    return state, out
