"""Epoch loops (SURVEY.md §2 component 1: ``train()``/``validate()``).

Host-side orchestration only — all math lives in the jitted step. The loop
overlaps host batch packing with device execution naturally: dispatching a
jitted step is async, so packing batch k+1 proceeds while the device runs
batch k. Timing meters separate data time from step time, like the
reference's console output.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Callable, Iterable, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from cgnn_tpu.data.graph import (
    CrystalGraph,
    GraphBatch,
    PaddingStats,
    batch_iterator,
    batch_shape_key,
    bucketed_batch_iterator,
    capacities_for,  # re-exported; moved to data/graph.py
    overflow_rows,
    round_to_bucket,
)
import jax.numpy as jnp

from cgnn_tpu.train.metrics import (
    AverageMeter,
    accumulate_on_device,
    fetch_device_sums,
    means_from_sums,
)

# In-flight dispatch window (backpressure depth) for the epoch drivers here
# and in parallel.data_parallel. The fence cadence bounds live staged
# batches at 2*_WINDOW (not _WINDOW+1): that is intentional — one fence per
# _WINDOW steps instead of per step halves link round trips — but it doubles
# peak HBM held by staged batches, so memory-tight large-capacity configs
# can shrink it via the environment (CGNN_TPU_WINDOW=2 bounds staging at 4
# batches at the cost of more frequent fences).
try:
    _WINDOW = int(os.environ.get("CGNN_TPU_WINDOW", "8"))
except ValueError:
    import warnings

    warnings.warn("CGNN_TPU_WINDOW must be a positive integer; using 8")
    _WINDOW = 8
if _WINDOW < 1:
    import warnings

    warnings.warn("CGNN_TPU_WINDOW must be >= 1; clamping to 1")
    _WINDOW = 1
from cgnn_tpu.observe import Telemetry, phases
from cgnn_tpu.resilience import faultinject
from cgnn_tpu.train.state import TrainState
from cgnn_tpu.train.step import (
    TRAIN_STEP_DONATE,
    jit_train_step,
    make_eval_step,
    make_train_step,
)

# fraction of free HBM the staged dataset may claim — the rest is params,
# opt state, activations, XLA workspace, and the scan driver's staged perms
_STAGE_FRACTION = 0.8


def staged_nbytes(batches) -> int:
    """Total bytes the batch pytrees would occupy staged on device — the
    ONE definition both fit() and fit_data_parallel feed the capacity
    precheck (what counts toward the budget must not diverge)."""
    return sum(
        x.nbytes for b in batches for x in jax.tree_util.tree_leaves(b)
    )


def device_hbm_budget(device=None) -> int | None:
    """Usable staging budget in bytes for ``device``, from what its
    ``memory_stats()`` reports free. None on the CPU backend only: its
    "device memory" is the host memory the packed batches already
    occupy, so there is nothing further to fit."""
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return None
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{device} reports no memory_stats(); cannot size "
            f"device-resident staging on an accelerator of unknown capacity"
        )
    free = int(stats["bytes_limit"]) - int(stats["bytes_in_use"])
    return int(free * _STAGE_FRACTION)


def check_device_resident_fit(staged_bytes: int, n_devices: int = 1,
                              log_fn: Callable = print) -> bool:
    """True when ``staged_bytes`` fits the device-resident budget.

    False (with a LOUD explanation of the fallback and the knobs that
    shrink staging) means the caller should keep batches host-side and
    restage per epoch (``pack_once`` semantics) instead of dying in an
    opaque XLA OOM mid-staging. The CPU backend has no separate device
    memory and always fits (``device_hbm_budget``).
    """
    budget = device_hbm_budget()
    if budget is None:
        return True
    per_device = staged_bytes / max(n_devices, 1)
    if per_device <= budget:
        return True
    log_fn(
        f"device-resident staging needs {per_device / 1e9:.1f} GB/device "
        f"but only ~{budget / 1e9:.1f} GB of HBM is budgeted for data "
        f"({_STAGE_FRACTION:.0%} of what "
        f"{jax.devices()[0].device_kind} reports free): "
        f"FALLING BACK to host-side pack-once staging (per-step H2D each "
        f"epoch). To stage on-device: --compact-staging (~12x smaller), "
        f"more data-parallel devices, or a smaller dataset/batch capacity."
    )
    return False


def save_preempted_mid_epoch(state, epoch: int, on_epoch_end,
                             log_fn: Callable) -> None:
    """Chunk-boundary preemption: the epoch is partial, so checkpoint
    the CURRENT weights under the last COMPLETED epoch — resume then
    redoes this epoch instead of skipping its unseen tail. Shared by
    ``fit`` and ``parallel.fit_data_parallel`` (the recovery protocol
    must not diverge between the single-host and DP loops)."""
    log_fn(
        f"preemption: epoch {epoch} stopped at a chunk boundary; saving "
        f"resumable checkpoint (epoch {epoch - 1})"
    )
    if on_epoch_end is not None:
        on_epoch_end(state, epoch - 1, {}, False)


def resilience_epoch_end(state, epoch: int, train_m: dict, val_m: dict,
                         is_best: bool, *, monitor, on_epoch_end, preempt,
                         log_fn: Callable):
    """The epoch-boundary resilience protocol shared by ``fit`` and
    ``parallel.fit_data_parallel``: divergence check BEFORE the save (a
    diverged epoch's state must not overwrite the last good checkpoint),
    the save itself, injected-SIGTERM delivery, and the preemption poll.
    -> (state, rolled_back, preempted)."""
    rolled_back = False
    if monitor is not None:
        state, rolled_back = monitor.observe(state, epoch, train_m)
    if on_epoch_end is not None and not rolled_back:
        on_epoch_end(state, epoch, val_m, is_best)
    faultinject.maybe_sigterm(epoch)
    preempted = preempt is not None and preempt.requested
    if preempted:
        if rolled_back:
            # the diverged epoch was NOT saved (by design) — don't tell
            # the operator a boundary checkpoint exists for it
            log_fn(
                f"preemption: stopping after epoch {epoch} — the epoch "
                f"diverged and was not saved; resume restarts from the "
                f"last good checkpoint"
            )
        else:
            log_fn(
                f"preemption: stopping after epoch {epoch} (checkpoint "
                f"saved at the epoch boundary)"
            )
    return state, rolled_back, preempted


def run_epoch(
    step_fn: Callable,
    state: TrainState,
    batches: Iterable[GraphBatch],
    train: bool,
    print_freq: int = 0,
    epoch: int = 0,
    log_fn: Callable = print,
    telemetry: Telemetry | None = None,
) -> tuple[TrainState, dict]:
    """Drive one epoch; returns (state, aggregated metric means).

    Metric sums accumulate ON DEVICE (a dispatched add per step) and are
    fetched once at epoch end — a per-step ``device_get`` would insert a
    host<->device round trip into every step, which dominates epoch time
    whenever the host-device round trip is not negligible and throttles
    dispatch pipelining everywhere else. A sliding window of
    in-flight step results provides backpressure (bounds how many staged
    batches can hold live HBM buffers ahead of execution): once
    ``2 * _WINDOW`` results are in flight, ONE scalar from ``_WINDOW``
    dispatches ago is VALUE-FETCHED — a true data dependency, correct on
    any runtime — proving every earlier step finished, so at most
    ``2 * _WINDOW`` batches hold live buffers. One fence per ``_WINDOW``
    steps, NOT per step: each fetch costs a full host-device round trip
    and stalls the dispatch pipeline behind it. ``batch_time``
    reports the wall-clock mean per step over each sync window (dispatch
    is async, so a per-dispatch stopwatch would read zero); ``data_time``
    is host wait per batch as before.
    """
    from collections import deque

    meters = {
        "batch_time": AverageMeter(),
        "data_time": AverageMeter(),
    }
    dev_sums: dict | None = None
    inflight: deque = deque()
    window_t0 = time.perf_counter()
    window_steps = 0
    end = time.perf_counter()
    it = -1

    def _sync_window(now):
        nonlocal window_t0, window_steps
        if window_steps:
            meters["batch_time"].update(
                (now - window_t0) / window_steps, n=window_steps
            )
        window_t0, window_steps = now, 0

    for it, batch in enumerate(batches):
        meters["data_time"].update(time.perf_counter() - end)
        if train:
            state, metrics = step_fn(state, batch)
        else:
            metrics = step_fn(state, batch)
        dev_sums = accumulate_on_device(dev_sums, metrics)
        inflight.append(next(iter(metrics.values())))
        if len(inflight) >= 2 * _WINDOW:
            # ONE fence per _WINDOW steps, not per step: each value fetch
            # is a full host-device round trip. Fetching the
            # _WINDOW-th-oldest handle proves every step before it
            # finished, so at most 2*_WINDOW batches hold live HBM
            # buffers ahead of execution.
            for _ in range(_WINDOW - 1):
                inflight.popleft()
            jax.device_get(inflight.popleft())  # true fence, see docstring
        window_steps += 1
        end = time.perf_counter()
        if print_freq and it % print_freq == 0:
            sums = fetch_device_sums(dev_sums)
            _sync_window(time.perf_counter())
            count = max(sums.get("count", 1.0), 1.0)
            parts = [
                f"{'Epoch' if train else 'Val'}: [{epoch}][{it}]",
                f"Time/step {meters['batch_time'].val:.3f} ({meters['batch_time'].avg:.3f})",
                f"Data {meters['data_time'].val:.3f} ({meters['data_time'].avg:.3f})",
                f"Loss {sums.get('loss_sum', 0.0) / count:.4f}",
            ]
            if "mae_sum" in sums:
                parts.append(f"MAE {sums['mae_sum'] / count:.4f}")
            if "force_mae_sum" in sums:
                fcount = max(sums.get("force_mae_count", 1.0), 1.0)
                parts.append(f"F-MAE {sums['force_mae_sum'] / fcount:.4f}")
            if "correct_sum" in sums:
                parts.append(f"Acc {sums['correct_sum'] / count:.4f}")
            log_fn("  ".join(parts))
    sums = fetch_device_sums(dev_sums)
    _sync_window(time.perf_counter())
    if telemetry is not None:
        # dispatch-share counter (flushed in the run summary)
        telemetry.counter_add("per_step_steps", it + 1)
    return state, means_from_sums(sums, it + 1)


def profile_wrap(iterator, profile_steps: int, profile_dir: str,
                 log_fn: Callable = print):
    """Trace steps [1, 1+profile_steps) of ``iterator`` (step 0 is the
    compile step; tracing it would swamp the timeline). Shared by the
    single-device and data-parallel epoch loops."""
    if not profile_steps:
        yield from iterator
        return
    tracing = False
    try:
        for i, b in enumerate(iterator):
            if i == 1:
                jax.profiler.start_trace(profile_dir or "profile")
                tracing = True
            yield b
            if tracing and i >= profile_steps:
                jax.profiler.stop_trace()
                tracing = False
                log_fn(f"profiler trace written to {profile_dir}")
    finally:
        if tracing:
            jax.profiler.stop_trace()


class PackOncePlan:
    """pack_once / device_resident epoch staging, shared by ``fit`` and
    ``parallel.fit_data_parallel``: pack every batch on the first epoch,
    reshuffle BATCH order (not graph membership) on later epochs, and —
    with ``device_resident`` — stage each batch's buffers on device once
    so later epochs incur zero host->device traffic."""

    def __init__(
        self,
        make_train_batches: Callable,
        make_val_batches: Callable,
        rng: np.random.Generator,
        device_resident: bool = False,
        stage: Callable | None = None,
    ):
        self._make_train = make_train_batches
        self._make_val = make_val_batches
        self._rng = rng
        self._device_resident = device_resident
        self._stage = stage if stage is not None else jax.device_put
        self._train: list | None = None
        self._val: list | None = None

    def epoch_iterators(self) -> tuple[Iterable, Iterable]:
        if self._train is None:
            self._train = list(self._make_train())
            self._val = list(self._make_val())
            if self._device_resident:
                self._train = [self._stage(b) for b in self._train]
                self._val = [self._stage(b) for b in self._val]
            # keep packing order: the first epoch is then bit-identical to
            # the per-epoch-packing path with the same seed
            order = np.arange(len(self._train))
        else:
            order = self._rng.permutation(len(self._train))
        return (self._train[i] for i in order), iter(self._val)


class PendingPairMetrics:
    """A deferred epoch-pair sums fetch running on a background thread
    (ISSUE 5 satellite: the fetch measured 224.9 ms of a 256 ms
    bench-scale epoch on the retired runtime, ``git show
    a13ea23:SCAN_COST.json`` — almost all of it the fetch WAITING for the
    epoch's in-flight compute, during which the host sat idle instead of
    dispatching the next epoch).

    ``result()`` joins the thread and returns ``(train_means,
    val_means)`` — the exact values the synchronous path computes, from
    the exact same ``fetch_device_sums`` call (bit-identical, pinned by
    test); an exception from the fetch re-raises at the join."""

    def __init__(self, fn: Callable):
        self._fn = fn
        self._out = None
        self._err: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="cgnn-pair-fetch"
        )
        self._thread.start()

    def _run(self) -> None:
        try:
            self._out = self._fn()
        except BaseException as e:  # noqa: BLE001 — re-raised at result()
            self._err = e

    def done(self) -> bool:
        return not self._thread.is_alive()

    def result(self):
        self._thread.join()
        if self._err is not None:
            raise self._err
        return self._out


def program_name(key, train: bool) -> str:
    """The name of one scan program, as the profiler's ``XLA Modules``
    line shows it (``jit_scan_train_n23944_l2``): train or eval, the
    bucket's node capacity (of a token batch, its positions), the chunk
    length. ``key`` is ``(shape key, length)`` with the shape key of
    ``batch_shape_key``."""
    shape_key, length = key
    if shape_key[0] == "tokens":  # tokens [.., S, 2L]
        rows = int(np.prod(shape_key[1][-2:]))
    else:
        # nodes [.., N, F] of a GraphBatch, distances [.., N, M] of a
        # compact one
        shape = shape_key[1] if shape_key[0] == "compact" else shape_key[0]
        rows = int(shape[-2])
    return f"scan_{'train' if train else 'eval'}_n{rows}_l{int(length)}"


def staged_edge_fea_nbytes(batches) -> int:
    """Of ``staged_nbytes``, the Gaussian-expanded edge features (the
    ``edges`` field; none under compact staging, which stages distances).
    The force model recomputes them from positions and never reads the
    staged ones (models/forcefield.py), so under full staging this is the
    share of its resident set that is dead."""
    return sum(int(b.edges.nbytes) for b in batches
               if getattr(b, "edges", None) is not None)


def transpose_overflow_stats(batches) -> dict:
    """How far the staged batches engage the overflow tier of the gather's
    transpose (ops/segment.py): ``transpose_overflow_rows`` real entries
    (rows of a conv's ``dz`` that reach their node through the run sum and
    the pointer gather; ~7% of the real edges) in ``transpose_overflow_cap``
    entries of capacity, and ``transpose_overflow_max_run``, the run
    capacity the programs are compiled for. Summed over every batch (and
    chip, and shard); zeros for batches that carry no mapping."""
    over = [b for b in batches if getattr(b, "over_runs", None) is not None]
    return {
        "transpose_overflow_rows": sum(overflow_rows(b) for b in over),
        "transpose_overflow_cap": sum(int(np.size(b.over_slots))
                                      for b in over),
        "transpose_overflow_max_run": max(
            (int(np.shape(b.over_runs)[-1]) for b in over), default=0),
    }


def conv_shape_gauges(params, dense_m: int | None) -> dict:
    """The three sizes that price a conv on the chip and that no other
    counter says: ``conv_row_lanes`` (2F, the width of the row every gather
    of the conv moves; 128 lanes are one tile), ``edge_gaussians`` (the
    contraction of the one per-edge matmul) and ``dense_m`` (slots a node;
    0 = COO). Read off ``fc_full``'s kernel, [2F + G, 2F] in every model of
    this repo; levels, so gauges (``fit`` and the benchmark's kinds set
    them beside ``staged_bytes``)."""
    kernel = params.get("conv_0", {}).get("fc_full", {}).get("kernel")
    if kernel is None:
        return {}
    rows, lanes = (int(d) for d in np.shape(kernel))
    return {"conv_row_lanes": lanes, "edge_gaussians": rows - lanes,
            "dense_m": int(dense_m or 0)}


def _staging_args(batches: list) -> dict:
    """Args of the ``scan.stage`` span. ``bytes`` is what the host hands
    over: under a mesh the batches carry the device axis, so it is the
    GLOBAL total over all chips (a chip holds its share of axis 1)."""
    return {"groups": len({batch_shape_key(b) for b in batches}),
            "batches": len(batches), "bytes": int(staged_nbytes(batches)),
            "edge_fea_bytes": staged_edge_fea_nbytes(batches),
            **transpose_overflow_stats(batches)}


@contextlib.contextmanager
def _compile_events():
    """What XLA did while open: ``{"compiled": a program was compiled,
    "cache_read": one came from the persistent cache}``; both false when
    jit already held every program."""
    seen = {"compiled": False, "cache_read": False}

    def on(name: str, _secs: float, **_kw) -> None:
        if "cache_retrieval" in name:
            seen["cache_read"] = True
        elif "backend_compile" in name:
            seen["compiled"] = True

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
        # backend_compile wraps compile-or-read; a read says so itself
        seen["compiled"] &= not seen["cache_read"]


class ScanEpochDriver:
    """Whole-epoch dispatch for device-resident datasets: one ``lax.scan``
    per bucket shape per epoch instead of one dispatch per step.

    Where per-dispatch latency is not negligible against the step time,
    the per-step Python dispatch dominates the epoch once batches are
    HBM-resident; folding the steps into a scan reduces an
    epoch to (number of bucket shapes) dispatches + fetches. Batch order
    shuffles via the scanned index array (a device-side dynamic index into
    the stacked batch arrays), grouped by shape — cross-bucket interleaving
    is traded away for the dispatch amortization.
    """

    def __init__(self, train_body: Callable, eval_body: Callable,
                 train_batches: list, val_batches: list,
                 rng: np.random.Generator, stage: Callable | None = None,
                 expand: Callable | None = None,
                 chunk_steps: int | None = None,
                 telemetry: Telemetry | None = None,
                 preempt=None):
        """``stage`` places each stacked group on device (default
        ``jax.device_put``); data-parallel callers pass a mesh-sharding
        stage so the per-step device axis (axis 1 of the stack) lands
        split over the mesh.

        ``expand`` (compact staging, data/compact.py) maps each scanned
        batch to the full GraphBatch INSIDE the jitted scan body — the
        stacked groups then hold the ~12x smaller raw form in HBM and the
        table-gather + Gaussian expansion fuse into each step. One-chip
        callers only: under a mesh the scanned batch still carries the
        device axis, and the expander belongs inside the per-shard body
        (``parallel.make_parallel_train_step(expand=...)``), which stages
        the same compact form.

        ``telemetry`` at step level stages the in-scan metric tap
        (observe.stream) into every scan body: per-step scalars ring out
        to the host via an async callback with no fetch on the dispatch
        path and no effect on the donated-carry trajectory. Below step
        level NOTHING is staged — the scanned HLO is identical to a
        telemetry-free build.

        ``preempt`` (a ``resilience.PreemptionHandler``) is polled at
        every CHUNK boundary while driving an epoch: a whole-epoch scan
        can outlast a preemption grace window, so the driver stops
        dispatching further chunks as soon as a checkpoint is requested
        and sets ``self.aborted`` for the caller to save-and-exit."""
        from cgnn_tpu.data import invariants

        if expand is not None:
            tb, eb = train_body, eval_body
            train_body = lambda s, b: tb(s, expand(b))  # noqa: E731
            eval_body = lambda s, b: eb(s, expand(b))  # noqa: E731
        if chunk_steps is not None:
            if chunk_steps < 1:
                raise ValueError(f"chunk_steps must be >= 1, got {chunk_steps}")
            self.chunk_steps = int(chunk_steps)

        # the scan trusts these stacks for a whole training run; validate
        # every input batch (incl. DP-stacked rows) before staging them
        for b in train_batches:
            invariants.maybe_check_any(b, train=True)
        for b in val_batches:
            invariants.maybe_check_any(b)
        self._rng = rng
        self._telemetry = telemetry
        self._preempt = preempt
        # True when the LAST driven epoch stopped early at a chunk
        # boundary on a preemption request (reset per public drive call)
        self.aborted = False
        # True when the last run_epoch_pair's EVAL phase was cut short
        # by preemption (its val means cover only the chunks that ran)
        self.eval_truncated = False
        # the tap is staged into scan bodies ONLY at step-level telemetry
        self._tap = (
            telemetry.tap_metrics
            if telemetry is not None and telemetry.stream is not None
            else None
        )
        self._stage = stage if stage is not None else jax.device_put
        with self._span("scan.stage") as args:
            self._train_groups = self._stack_groups(train_batches)
            self._val_groups = self._stack_groups(val_batches)
            if args is not None:
                args.update(_staging_args(
                    [*train_batches, *val_batches]))
                telemetry.counter_add("staged_bytes", args["bytes"])
                telemetry.counter_add("staged_edge_fea_bytes",
                                      args["edge_fea_bytes"])
                for name in ("transpose_overflow_rows",
                             "transpose_overflow_cap"):
                    telemetry.counter_add(name, args[name])
                # a level, not a sum: a second driver must not double it
                telemetry.set_gauge("transpose_overflow_max_run",
                                    args["transpose_overflow_max_run"])
        self._train_body, self._eval_body = train_body, eval_body
        # the programs an epoch runs, by (shape key, length): _window_fn
        self._train_scans: dict = {}
        self._eval_scans: dict = {}
        # one-epoch-ahead staged schedules, keyed (id(groups), train,
        # first) — see _sched/_drive
        self._sched_cache: dict = {}
        # _drive calls outside warm-up so far: the ``epoch`` that the
        # spans of one driven epoch share
        self._epochs_driven = 0
        # [train means, val means] of warm()'s epoch
        self.warm_metrics: list | None = None

    def _span(self, name: str):
        """A set-up span of the program's tracer (and, through it, of the
        profiler's clock), yielding its args dict; with telemetry off a
        context that yields None."""
        tel = self._telemetry
        return tel.span(name) if tel is not None else contextlib.nullcontext()

    def _run_span(self, name: str, **args):
        """A span of run work, once an epoch or rarer (a chunk's spans are
        opened in ``run_queues``, which builds nothing when they are off),
        yielding its args dict, or None where none is opened. Unlike
        ``_span`` it is muted while warming, as ``scan.chunk`` is: a
        warm-up epoch is not run work."""
        tel = self._telemetry
        if tel is None or tel.spans is None or tel.warming:
            return contextlib.nullcontext()
        return tel.spans.span(name, **args)

    def _stack_groups(self, batches: list) -> dict:
        """Group same-shape batches, stack on a leading axis, stage to HBM.

        Keys on the full (nodes, edges, in_slots) shapes — not the
        capacity scalars — so already-device-stacked DP batches (leading
        device axis) group correctly too."""
        groups: dict = {}
        for b in batches:
            groups.setdefault(batch_shape_key(b), []).append(b)
        return {
            k: self._stage(
                jax.tree_util.tree_map(lambda *xs: np.stack(xs), *bs)
            )
            for k, bs in groups.items()
        }

    # mean steps folded into one dispatch. Small, deliberately: r4
    # measured that dispatch COUNT is essentially free (48 two-step scans
    # run at the rate of 3 thirty-two-step scans — only SYNC points cost,
    # PERF.md 6c), while chunk GRANULARITY is what multi-bucket
    # convergence pays for — at MP-146k, chunk 8's same-shape runs cost
    # ~35% val MAE vs the per-step interleave (0.0599 vs 0.0447, same
    # seed/budget), and chunk 2 recovers it fully (0.0424 at 3.0 s vs
    # 2.7 s epochs; PERF.md 6e). Actual lengths are drawn from
    # {1, 2, 4} and groups picked weighted-randomly (see _drive) so the
    # step sequence tracks the per-step loop's weighted interleave.
    chunk_steps = 2

    def _window_fn(self, cache: dict, key, body: Callable, train: bool):
        """The program an epoch runs for ``key = (shape key, length)``:
        ``fn(state, stacked, perm_all, cursor) -> (state, sums, cursor +
        length)``, one ``lax.scan`` over ``length`` batches of ``stacked``
        with the step metrics summed. ``perm_all`` is the group's whole
        epoch of batch indices in the order its chunks are dispatched
        (``_sched`` staged it in one transfer) and ``cursor`` a device
        scalar that every chunk of the group hands to the next, so a chunk
        finds its rows on the device and a dispatch moves nothing from the
        host."""
        if key not in cache:
            length = int(key[1])

            def scan_fn(state, stacked, perm_all, cursor):
                def step(carry, i):
                    with jax.named_scope(phases.SCAN):
                        batch = jax.tree_util.tree_map(
                            lambda x: x[i], stacked)
                    if train:
                        carry, metrics = body(carry, batch)
                        if self._tap is not None:
                            # per-step scalars ring out to the host from
                            # INSIDE the scan (async callback; no fetch,
                            # no change to the donated carry)
                            self._tap(metrics, "train", step=carry.step)
                    else:
                        metrics = body(carry, batch)
                        if self._tap is not None:
                            self._tap(metrics, "eval")
                    return carry, metrics

                with jax.named_scope(phases.SCAN):
                    perm = jax.lax.dynamic_slice_in_dim(
                        perm_all, cursor, length)
                state2, ms = jax.lax.scan(step, state, perm)
                with jax.named_scope(phases.SCAN):
                    return state2, jax.tree_util.tree_map(
                        lambda m: m.sum(0), ms
                    ), cursor + length

            # the name the profiler's module line and the compile log show
            scan_fn.__name__ = program_name(key, train)
            cache[key] = jax.jit(
                scan_fn,
                donate_argnums=TRAIN_STEP_DONATE if train else (),
            )
        return cache[key]

    def _scan_fn(self, cache: dict, key, body: Callable, train: bool):
        """``fn(state, stacked, perm) -> (state, sums)`` for a caller that
        brings a chunk's perm of its own (the benchmark's compared steps):
        ``_window_fn``'s program, the one an epoch runs and ``warm()``
        compiled, over that perm staged at the front of an otherwise zero
        ``perm_all`` with the cursor at zero. No second program."""
        fn = self._window_fn(cache, key, body, train)

        def scan_fn(state, stacked, perm):
            n = int(jax.tree_util.tree_leaves(stacked)[0].shape[0])
            perm_all = np.zeros(n, np.int32)
            perm_all[: int(key[1])] = np.asarray(perm)
            state, sums, _ = fn(state, stacked,
                                *self._put_perms(perm_all, stacked))
            return state, sums

        return scan_fn

    # per-group steps reserved for the end of each training epoch and run
    # ONE step at a time, round-robin across groups: BatchNorm's running
    # stats are an EMA with momentum 0.1, so the last ~16 steps carry most
    # of their weight — ending on a single-shape 16-step chunk would skew
    # eval statistics toward one size class (observed: val MAE 2x worse at
    # MP-146k scale until the tail was mixed). Capped at n//4 per group
    # (r4 scan-cost record): a FIXED 8-per-group tail turned small epochs
    # into mostly single-step dispatching — at the 18-batch bench scale it
    # was the whole 31.5k-vs-50k gap — while a proportional tail keeps the
    # last few steps shape-mixed at every scale
    mixed_tail = 8

    def _tail_for(self, n: int) -> int:
        return min(self.mixed_tail, max(1, n // 4))

    def _build_sched(self, groups, train, first):
        """The host's draw of one epoch: ``(queues, tails, steps,
        pick_order)``, the entries of ``queues`` and ``tails`` ``(key,
        stacked, chunks)`` with every chunk a ``numpy`` view of its
        group's permutation, an int32 array that owns its data (so
        ``chunk.base`` is that permutation whole: what ``_sched`` stages).
        Nothing reaches the device here.
        """
        c = self.chunk_steps
        queues = []
        tails = []
        steps = 0
        pick_order: list[int] = []
        multi = train and len(groups) > 1
        for key, stacked in groups.items():
            n = int(jax.tree_util.tree_leaves(stacked)[0].shape[0])
            tail = self._tail_for(n) if multi else 0
            # int32 explicitly: np.arange is int64 and would trace
            # distinct (or x64-invalid) scans
            perm = np.array(
                np.arange(n) if (first or not train)
                else self._rng.permutation(n), dtype=np.int32)
            head, foot = perm[: n - tail], perm[n - tail :]
            if multi:
                # randomized chunk lengths from {c/2, c, 2c} (mean ~c;
                # only 3 distinct compile keys per group): varied lengths
                # + weighted-random group picks below make the step
                # sequence statistically match the per-step weighted
                # interleave instead of the r2 deterministic round-robin
                chunks, i = [], 0
                sizes = [max(1, c // 2), c, 2 * c]
                while i < len(head):
                    rem = len(head) - i
                    # only draw sizes that fit: the final remainder is
                    # then < c/2, so distinct compile keys stay bounded
                    # at {1..c/2-1} + the 3 sizes per group, stable
                    # across epochs (an arbitrary-length remainder would
                    # accumulate up to 2c scan compiles)
                    avail = [s for s in sizes if s <= rem]
                    ln = int(self._rng.choice(avail)) if avail else rem
                    chunks.append(head[i : i + ln])
                    i += ln
            else:
                chunks = [head[i : i + c] for i in range(0, len(head), c)]
            if chunks:
                queues.append((key, stacked, chunks))
            if len(foot):
                tails.append((key, stacked, [foot[i : i + 1]
                                             for i in range(len(foot))]))
            steps += n
        # weighted group-pick sequence, PRECOMPUTED here so that no
        # np.array + rng.choice(p=...) runs a chunk on the DISPATCH path
        # in run_queues: it is part of the schedule build, which _drive
        # prebuilds one epoch AHEAD. Same sampler, same weights
        # (remaining steps per group), same rng stream shape — the
        # step-sequence distribution is unchanged, and the
        # sync-vs-async-fetch bit-identity pin still holds because both
        # paths build schedules in the same order.
        if multi and not first:
            rem = [[len(ch) for ch in entry[2]] for entry in queues]
            alive = list(range(len(queues)))
            while alive:
                if len(alive) > 1:
                    w = np.array([float(sum(rem[i])) for i in alive])
                    gi = alive[int(self._rng.choice(len(alive),
                                                    p=w / w.sum()))]
                else:
                    gi = alive[0]
                pick_order.append(gi)
                rem[gi].pop(0)
                if not rem[gi]:
                    alive.remove(gi)
        return queues, tails, steps, pick_order

    @staticmethod
    def _put_perms(perm_all, stacked):
        """One group's schedule on the device, committed whole to every
        device that holds a part of ``stacked`` (under a mesh: replicated
        over it, so that no dispatch has to place it): ``(perm_all, a zero
        cursor)``, both int32, two transfers. ``warm()`` and the epochs
        stage through here alike: jit keys its programs on where their
        arguments live and on which of them are committed."""
        where = jax.tree_util.tree_leaves(stacked)[0].sharding
        if isinstance(where, NamedSharding):
            where = NamedSharding(where.mesh, PartitionSpec())
        return jax.device_put(
            (np.asarray(perm_all, np.int32), np.zeros((), np.int32)), where)

    def _sched(self, groups, train, first, prebuilt: bool):
        """One epoch's schedule as run work: ``_build_sched``'s draw, then
        the staging of its perms, ``(sched, staged)`` with ``staged[key]``
        the group's ``(perm_all, zero cursor)`` on the device. A group's
        perms go over as ONE array, in the order the epoch consumes them
        (the chunks of its queue front to back, then its tail singles:
        together the group's permutation), whatever the number of chunks:
        a buffer of a few bytes costs the host 0.16-0.19 ms wherever it is
        made, and an epoch has thousands of chunks (PERF.md §6, PR 40).
        Called one epoch AHEAD of use (see _drive); where the device
        outruns the host (the four-chip mesh) the build still holds the
        dispatch thread for its whole length with the device's queue
        empty. Spans ``epoch.sched`` (``prebuilt``: built ahead of the
        epoch that uses it, or on the miss at its head) and, inside it,
        ``epoch.sched.put`` say for how long; the counters say what was
        staged (``sched_perms_staged``: the chunk perms the schedule
        covers; ``sched_transfers``: the host-to-device transfers made
        for them)."""
        with self._run_span("epoch.sched", train=train,
                            prebuilt=prebuilt) as args:
            sched = self._build_sched(groups, train, first)
            queues, tails, steps, _ = sched
            chunks = sum(len(entry[2]) for entry in queues)
            perms = chunks + sum(len(entry[2]) for entry in tails)
            # a group's head chunks and foot singles are views of one
            # array, in its order
            perm_of = {key: group_chunks[0].base
                       for key, _, group_chunks in queues + tails}
            transfers = 2 * len(perm_of)
            with self._run_span("epoch.sched.put", perms=perms,
                                bytes=4 * steps, transfers=transfers):
                staged = {key: self._put_perms(perm_all, groups[key])
                          for key, perm_all in perm_of.items()}
            if args is not None:
                args.update(chunks=chunks, perms=perms)
        if self._telemetry is not None:
            self._telemetry.counter_add("sched_builds", 1)
            self._telemetry.counter_add("sched_perms_staged", perms)
            self._telemetry.counter_add("sched_transfers", transfers)
        return sched, staged

    def warm(self, state: TrainState, consume: bool = False) -> TrainState:
        """Compile every (shape, chunk-length) scan program the driver can
        draw, so no first-compile (seconds through a high-latency link)
        lands inside a caller's timed region.

        ``consume=True`` is for a state too large to hold twice (the
        block-diffusion decoder's is most of a chip's memory): the programs
        run on ``state`` itself, which is donated and gone, and the caller
        builds its state anew afterwards. Returns None then.

        Runs the REAL train bodies (compilation requires execution here),
        but against a disposable on-device copy of ``state``, so the
        ~1+ epochs of optimizer updates on skewed arange%n-repeated batches
        never touch the caller's state: the returned state is the input,
        untrained, with every program the driver can draw sitting in the
        jit cache (keyed on shapes/dtypes, which the copy shares).

        Deterministic by enumeration: chunk lengths come from the bounded
        set {1 .. c/2, c, 2c} (sizes + remainders + tail singles), so each
        is executed once directly — sampling warmup epochs until the
        program set stabilizes can miss a rare length for many epochs when
        ``chunk_steps`` is small.
        """
        # Real buffers, not aliases: the train bodies donate their state
        # argument, so passing the caller's arrays would invalidate them.
        # Copy-THEN-place: jnp.array(x) alone makes the copy but relies on
        # it implicitly keeping x's layout, and jax.device_put(x,
        # x.sharding) alone ALIASES the buffer (measured: same
        # unsafe_buffer_pointer, donation kills the original) — the
        # device_put onto the source sharding makes the replicated/sharded
        # layout explicit on a buffer that is already a fresh copy.
        scratch = state if consume else jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.array(x), x.sharding)
            if isinstance(x, jax.Array) else x,
            state,
        )
        c = self.chunk_steps
        lengths = sorted(set(range(1, max(2, c // 2 + 1))) | {c, 2 * c})
        # warmup dispatches run the REAL compiled programs — mute the
        # step stream so compile-time executions don't pollute the
        # per-step record stream
        tel = self._telemetry
        warm_ctx = (
            tel.warmup() if tel is not None else contextlib.nullcontext()
        )
        spans = tel.spans if tel is not None else None
        with warm_ctx:
            for key, stacked in self._train_groups.items():
                n = int(jax.tree_util.tree_leaves(stacked)[0].shape[0])
                # staged as an epoch's schedule is: jit keys its programs
                # on where their arguments live
                perm_all, cursor = self._put_perms(np.arange(n), stacked)
                for ln in lengths:
                    if ln > n:
                        continue
                    fn = self._window_fn(
                        self._train_scans, (key, ln), self._train_body,
                        True)
                    if spans is None:
                        scratch, _, _ = fn(scratch, stacked, perm_all, cursor)
                        continue
                    name = program_name((key, ln), True)
                    with spans.span("warm.program", module=name,
                                    length=ln) as args:
                        with _compile_events() as seen:
                            scratch, _, _ = fn(scratch, stacked, perm_all,
                                               cursor)
                        args.update(seen)
                    self._emit_program(spans, fn, name, key, ln,
                                       (scratch, stacked, perm_all, cursor))
            # eval programs + the pair plumbing compile on a normal epoch
            # its means are kept: an epoch in pack order over the caller's
            # state as the programs above left it, the same whatever rng
            # the driver was given
            with self._span("warm.epoch"):
                _, train_m, val_m = self.run_epoch_pair(scratch, first=True)
                self.warm_metrics = [train_m, val_m]
            if spans is not None and self._eval_scans:
                # as the warm epoch staged them, once for every epoch
                _, staged = self._sched_cache[
                    (id(self._val_groups), False, True)]
                for (key, ln), fn in self._eval_scans.items():
                    self._emit_program(
                        spans, fn, program_name((key, ln), False), key, ln,
                        (scratch, self._val_groups[key], *staged[key]))
        return None if consume else state

    @staticmethod
    def _emit_program(spans, fn, name: str, key, length: int,
                      example: tuple) -> None:
        """One ``scan.program`` instant: the program's module name and the
        phase of each of its instructions (observe/phases.py), for whoever
        reads a device trace of it. The device events of this runtime carry
        the instruction and no ``op_name``, so the table is read off the
        compiled module's text; jit holds the executable by now, so nothing
        compiles here, and the time it takes is a span of its own."""
        with spans.span("warm.phase_map", module=name):
            text = fn.lower(*example).compile().as_text()
            table = phases.phase_table(text)
        spans.instant("scan.program", module="jit_" + name, key=repr(key),
                      length=int(length), table=table)

    def _drive(self, state: TrainState, groups, scans, body, train, first,
               prebuild: bool = True):
        """Dispatch one epoch; returns (state, device_sums, steps) WITHOUT
        fetching — callers combine/fetch sums (run_epoch_pair: one link
        sync for train+eval; train_epoch/eval_epoch: per-phase fetch).
        ``prebuild=False`` defers the next-epoch schedule prebuild to the
        caller (run_epoch_pair's async-fetch mode overlaps it with the
        background sums fetch instead).

        With telemetry on, the whole call is one ``scan.epoch`` span whose
        ``epoch`` every span of its chunks carries too (warm-up epochs
        are not run work: no span, and no count)."""
        with self._run_span("scan.epoch", epoch=self._epochs_driven,
                            train=train) as ids:
            if ids is not None:
                self._epochs_driven += 1
            return self._dispatch_epoch(state, groups, scans, body, train,
                                        first, prebuild, ids)

    def _dispatch_epoch(self, state: TrainState, groups, scans, body, train,
                        first, prebuild: bool, ids: dict | None):
        """``_drive``'s body. ``ids`` is the args dict of the epoch's
        ``scan.epoch`` span (its ``epoch`` is handed on to the chunks'
        spans, and the epoch's chunks and steps are left in it), or None
        where no span is to be opened."""
        sched_key = (id(groups), train, first)
        if train:
            sched = self._sched_cache.pop(sched_key, None)
            if sched is None:
                sched = self._sched(groups, train, first, prebuilt=False)
        else:
            # the eval schedule is deterministic (first=True, arange
            # perms): build once, reuse every epoch — re-staging identical
            # perms each epoch was pure waste
            sched = self._sched_cache.get(sched_key)
            if sched is None:
                sched = self._sched(groups, train, first, prebuilt=False)
                self._sched_cache[sched_key] = sched
        (queues, tails, _planned_steps, pick_order), staged = sched
        # each group's place in its perm_all: a device scalar that a chunk
        # takes and hands on (the staged zero is never donated, so the
        # cached eval schedule starts from it every epoch)
        cursors = {key: zero for key, (_, zero) in staged.items()}
        # run_queues consumes the chunk lists: work on shallow DEQUE
        # copies (O(1) popleft — pop(0) shifted the whole list per
        # chunk) so the cached eval schedule survives reuse
        queues = [(k, st, collections.deque(ch)) for k, st, ch in queues]
        tails = [(k, st, collections.deque(ch)) for k, st, ch in tails]
        multi = train and len(groups) > 1
        # chunk dispatch is the host-side hot loop, and where the device
        # outruns the host (the four-chip mesh) it sets the pace (PERF.md
        # §5, ``mp.train-dp4``): the weighted group picks were PREDRAWN
        # into pick_order by _build_sched (one epoch ahead), so per chunk
        # this loop does the preemption poll, a deque pop, a dict lookup
        # and TWO dispatches over all devices: the scan program and one
        # device-side accumulate of its metric sums (one fused async add),
        # which are fetched ONCE an epoch, packed into a single array
        # (metrics.fetch_device_sums). Spans ``scan.chunk`` and
        # ``scan.accumulate`` time the two; what is left of the
        # ``scan.epoch`` span is the loop's own.
        dev_sums: dict | None = None
        executed = 0
        chunks_run = 0
        spans = None if ids is None else self._telemetry.spans
        epoch = None if ids is None else ids["epoch"]

        def run_queues(qs, weighted):
            nonlocal state, dev_sums, executed, chunks_run
            rr = 0
            picks = iter(pick_order)
            by_index = list(qs)  # pick_order indexes the BUILD order
            while qs:
                if self._preempt is not None and self._preempt.requested:
                    # chunk-boundary preemption: stop dispatching; the
                    # caller saves the (mid-epoch) state and exits with
                    # the resumable code. Metric denominators use the
                    # executed step count, not the planned one.
                    self.aborted = True
                    return
                if weighted and pick_order:
                    entry = by_index[next(picks)]
                else:
                    # round-robin across groups (never drain one bucket
                    # before starting the next: BN's momentum-0.1 EMA and
                    # the optimizer must not see a size-sorted epoch)
                    entry = qs[rr % len(qs)]
                    rr += 1
                key, stacked, chunks = entry
                # the host's copy of the chunk's perm says how long it is;
                # the program reads the rows themselves off the device
                length = len(chunks.popleft())
                # compile key includes the chunk length (bounded per
                # group: <= 2c distinct lengths, one remainder, length 1)
                fn = self._window_fn(scans, (key, length), body, train)
                perm_all = staged[key][0]
                if spans is None:
                    state, chunk_sums, cursors[key] = fn(
                        state, stacked, perm_all, cursors[key])
                    dev_sums = accumulate_on_device(dev_sums, chunk_sums)
                else:
                    # the host's side of the chunk's two dispatches, in
                    # trace.json and (as cgnn:scan.chunk, with the same
                    # ids) on the profiler's clock beside the device's
                    # launch of the same program; the program's name is
                    # the jitted function's own (_window_fn set it)
                    with spans.span("scan.chunk", steps=length, train=train,
                                    epoch=epoch, chunk=chunks_run,
                                    program=fn.__name__):
                        state, chunk_sums, cursors[key] = fn(
                            state, stacked, perm_all, cursors[key])
                    with spans.span("scan.accumulate", epoch=epoch,
                                    chunk=chunks_run):
                        dev_sums = accumulate_on_device(dev_sums,
                                                        chunk_sums)
                chunks_run += 1
                executed += length
                if not chunks:
                    qs.remove(entry)

        run_queues(queues, weighted=multi and not first)
        run_queues(tails, weighted=False)  # mixed single-step tail
        # prebuild + stage the NEXT train epoch's schedule while this
        # epoch's dispatches are still executing: its H2D transfers ride
        # along the in-flight work instead of stalling the next epoch's
        # first scan. (If the run ends here the prebuild is unused — a few
        # rng draws consumed in the same order a further epoch would have.)
        if train and not self.aborted and prebuild:
            self._sched_cache[(id(groups), True, False)] = \
                self._sched(groups, True, False, prebuilt=True)
        if self._telemetry is not None:
            self._telemetry.counter_add("scan_steps", executed)
            self._telemetry.counter_add("scan_chunks", chunks_run)
        if ids is not None:
            ids.update(chunks=chunks_run, steps=executed)
        return state, dev_sums, executed

    def train_epoch(self, state: TrainState, first: bool):
        self.aborted = False
        state, dev_sums, steps = self._drive(
            state, self._train_groups, self._train_scans,
            self._train_body, train=True, first=first,
        )
        return state, means_from_sums(fetch_device_sums(dev_sums), steps)

    def eval_epoch(self, state: TrainState):
        self.aborted = False
        _, dev_sums, steps = self._drive(
            state, self._val_groups, self._eval_scans,
            self._eval_body, train=False, first=True,
        )
        return means_from_sums(fetch_device_sums(dev_sums), steps)

    def run_epoch_pair(self, state: TrainState, first: bool,
                       async_fetch: bool = False):
        """Train epoch + eval epoch with ONE link sync for both.

        Each fetch on a high-latency link stalls the device for a full
        round trip (the trace's only remaining gap); eval's dispatches
        depend on the post-train state only THROUGH THE DEVICE, so they
        can be enqueued before the train sums are ever fetched —
        halving the per-epoch sync count. -> (state, train_means,
        val_means).

        ``async_fetch=True`` (ISSUE 5 satellite) returns ``(state,
        PendingPairMetrics)`` instead: the sums fetch — almost all of it
        waiting for the epoch's in-flight compute — runs on a
        background thread while the caller keeps dispatching (the next
        epoch's first scans in ``fit``), and the next-epoch schedule
        prebuild moves AFTER the fetch thread starts so it overlaps the
        wait too. The rng draw ORDER is unchanged (train draws, then
        prebuild draws; eval consumes none in between), so schedules,
        trajectories, and the fetched metrics are bit-identical to the
        synchronous path — pinned by test.
        """
        self.aborted = False
        self.eval_truncated = False
        pair_epoch = self._epochs_driven  # the train epoch's id
        state, tr_sums, tr_steps = self._drive(
            state, self._train_groups, self._train_scans,
            self._train_body, train=True, first=first,
            prebuild=not async_fetch,
        )
        train_aborted = self.aborted
        ev_sums, ev_steps = None, 0
        if self._val_groups and not train_aborted:
            # a preempted train epoch skips eval outright: the grace
            # window is for the checkpoint, not for scoring a half epoch
            _, ev_sums, ev_steps = self._drive(
                state, self._val_groups, self._eval_scans,
                self._eval_body, train=False, first=True,
            )
            # a preemption that lands during EVAL must not mark the
            # (fully completed) train epoch aborted — the caller would
            # checkpoint it under epoch-1 and retrain the whole epoch on
            # resume. The epoch completes; eval_truncated tells the
            # caller its val means cover only the eval chunks that ran
            # (so a lucky partial score must not repoint 'best'), and
            # the epoch-boundary preempt check exits after the save.
            self.eval_truncated = self.aborted
            self.aborted = train_aborted
        combined = {f"t:{k}": v for k, v in (tr_sums or {}).items()}
        combined |= {f"e:{k}": v for k, v in (ev_sums or {}).items()}

        # made here, entered on whichever thread fetches
        fetch_span = self._run_span("epoch.fetch", epoch=pair_epoch)

        def fetch_pair():
            with fetch_span:
                fetched = fetch_device_sums(combined or None)
            tr = {k[2:]: v for k, v in fetched.items()
                  if k.startswith("t:")}
            ev = {k[2:]: v for k, v in fetched.items()
                  if k.startswith("e:")}
            return (means_from_sums(tr, tr_steps),
                    means_from_sums(ev, ev_steps))

        if not async_fetch:
            train_m, val_m = fetch_pair()
            return state, train_m, val_m
        with self._run_span("epoch.fetch_start", epoch=pair_epoch):
            pending = PendingPairMetrics(fetch_pair)
        # the deferred prebuild (see _drive): schedule + stage the next
        # train epoch while the fetch thread blocks on this epoch's
        # in-flight compute. Same rng draws, same order as the sync path.
        if not train_aborted:
            self._sched_cache[(id(self._train_groups), True, False)] = \
                self._sched(self._train_groups, True, False, prebuilt=True)
        return state, pending


def fit(
    state: TrainState,
    train_graphs: Sequence[CrystalGraph],
    val_graphs: Sequence[CrystalGraph],
    *,
    epochs: int,
    batch_size: int,
    node_cap: int | None = None,
    edge_cap: int | None = None,
    classification: bool = False,
    seed: int = 0,
    print_freq: int = 10,
    on_epoch_end: Callable | None = None,
    log_fn: Callable = print,
    start_epoch: int = 0,
    train_step_fn: Callable | None = None,
    eval_step_fn: Callable | None = None,
    best_metric: str | None = None,
    buckets: int = 1,
    on_epoch_metrics: Callable | None = None,
    profile_steps: int = 0,
    profile_dir: str = "",
    pack_once: bool = False,
    device_resident: bool = False,
    dense_m: int | None = None,
    scan_epochs: bool = False,
    snug: bool = False,
    edge_dtype=np.float32,
    compact=None,
    chunk_steps: int | None = None,
    telemetry: Telemetry | None = None,
    guard: bool = False,
    monitor=None,
    preempt=None,
    packed: tuple[list, list] | None = None,
) -> tuple[TrainState, dict]:
    """Reference ``main()`` loop: train/validate per epoch, track best.

    ``packed`` (requires ``scan_epochs``) hands over ``(train batches, val
    batches)`` that a task packed itself: nothing is packed here,
    ``train_graphs`` and ``val_graphs`` are not read, and everything after
    the pack (staging, the scan driver, the epochs) is the path of every
    task. The block-diffusion task's token batches come this way
    (data/tokens.py): they are no graphs.

    ``train_step_fn``/``eval_step_fn`` override the default task steps (the
    force task passes its composite-loss steps); ``best_metric`` overrides
    the model-selection metric key (lower-is-better unless classification).
    ``buckets > 1`` batches with per-size-class capacities (at most
    ``buckets`` compiled step shapes) instead of one global capacity.
    ``on_epoch_metrics(epoch, train_m, val_m)`` fires after each epoch (the
    machine-readable metrics hook); ``profile_steps > 0`` wraps that many
    post-compile steps of the first epoch in ``jax.profiler.trace`` writing
    to ``profile_dir``.

    ``pack_once`` packs the training batches on the first epoch and reuses
    them, shuffling BATCH order (not graph membership) across epochs — for
    large cached datasets where per-epoch host packing would starve the
    device (the reference reshuffles graphs per epoch; batch-level
    shuffling is the standard streaming-dataset trade and costs a little
    within-batch randomness for host throughput). Batches stay host-side;
    the prefetcher re-stages them to HBM each epoch.

    ``device_resident`` (implies pack_once) additionally stages every packed
    batch into HBM once and reuses the device buffers across epochs — zero
    per-epoch host->device traffic. For datasets whose packed batches fit
    in HBM alongside the model (MP-146k at batch 512 is ~10 GB); the fix
    for epochs bound by host->device transfer.

    ``compact`` (a ``data.compact.CompactSpec``; requires ``scan_epochs``
    and ``dense_m``) stages batches in raw form — atom vocabulary indices
    + scalar distances, ~12x fewer bytes — and rebuilds the GraphBatch
    inside the jitted scan body (data/compact.py). Cuts device-resident
    H2D staging and HBM footprint ~12x; measured neutral on steady-state
    step time (the expansion fuses into the step).

    ``telemetry`` (an ``observe.Telemetry``) wires spans around the
    staging/epoch phases, padding + dispatch gauges, and — at step level
    — the in-scan per-step metric stream plus in-graph grad-health
    metrics. None (or level 'off') changes nothing: no wrapper is applied
    to any step body and no callback is staged into any compiled program.

    ``guard`` wraps the train body with the in-graph divergence guard
    (``resilience.guard.guard_step``): non-finite updates are skipped on
    device; the select is an identity when nothing fires. ``monitor`` (a
    ``resilience.DivergenceMonitor``) is consulted once per epoch and may
    roll the state back to the last good checkpoint with an LR cut.
    ``preempt`` (a ``resilience.PreemptionHandler``) is polled at epoch
    boundaries (chunk boundaries inside the epoch scan): when a signal
    arrived, the loop saves a resumable checkpoint via ``on_epoch_end``,
    stops, and marks the result ``{"preempted": True}``.

    ``scan_epochs`` (implies device_resident) folds the epoch into one
    ``lax.scan`` dispatch per bucket shape (ScanEpochDriver).
    Single-bucket runs are trajectory-identical to the per-step loop;
    multi-bucket runs use randomized chunk scheduling (r3) and converge
    identically to the per-step loop (scripts/scan_convergence.py:
    val-MAE plateau 0.158-0.159 for both drivers, epoch-by-epoch, vs
    0.024 per-step seed noise) — train.py makes scan the default
    whenever --device-resident is set.
    """
    device_resident = device_resident or scan_epochs
    pack_once = pack_once or device_resident
    if compact is not None and not scan_epochs:
        raise ValueError("compact staging requires scan_epochs (the "
                         "expander runs inside the scan body)")
    if compact is not None and dense_m is None:
        raise ValueError("compact staging requires the dense layout "
                         "(dense_m)")
    if packed is not None and not scan_epochs:
        raise ValueError("packed batches are driven by the scan driver "
                         "(scan_epochs)")
    if packed is None and (node_cap is None or edge_cap is None):
        nc, ec = capacities_for(train_graphs, batch_size, dense_m=dense_m,
                                snug=snug)
        node_cap, edge_cap = node_cap or nc, edge_cap or ec
    if dense_m is not None:
        edge_cap = node_cap * dense_m
    pack_fn = None
    if compact is not None:
        from cgnn_tpu.data.compact import compact_pack_fn

        pack_fn = compact_pack_fn(compact)
    from cgnn_tpu.data.loader import prefetch_to_device

    def train_batches(rng):
        if buckets > 1:
            it = bucketed_batch_iterator(
                train_graphs, batch_size, buckets, shuffle=True, rng=rng,
                stats=pad_stats, dense_m=dense_m, snug=snug,
                edge_dtype=edge_dtype, pack_fn=pack_fn,
            )
        else:
            it = pad_stats.wrap(
                batch_iterator(
                    train_graphs, batch_size, node_cap, edge_cap,
                    shuffle=True, rng=rng, dense_m=dense_m, snug=snug,
                    edge_dtype=edge_dtype, pack_fn=pack_fn,
                )
            )
        # env-gated deterministic fault injection (NaN batches, loader
        # exceptions); returns `it` unwrapped when no plan is active
        return faultinject.poison_batches(it)

    def val_batches():
        # in_cap=0: eval has no backward, so skip transpose-slot packing
        if buckets > 1:
            return bucketed_batch_iterator(
                val_graphs, batch_size, buckets, dense_m=dense_m, in_cap=0,
                snug=snug, edge_dtype=edge_dtype, pack_fn=pack_fn,
            )
        return batch_iterator(
            val_graphs, batch_size, node_cap, edge_cap, dense_m=dense_m,
            in_cap=0, snug=snug, edge_dtype=edge_dtype, pack_fn=pack_fn,
        )

    telemetry = telemetry or Telemetry.disabled()
    for name, level in conv_shape_gauges(state.params, dense_m).items():
        telemetry.set_gauge(name, level)
    # raw step BODIES (shared by the per-step jits below and the scan
    # driver, which stages its own in-scan tap); default steps compute
    # grad health in-graph at step-level telemetry — extra metric outputs
    # only, so the trajectory is unchanged
    base_train = train_step_fn or make_train_step(
        classification, grad_health=telemetry.step_level
    )
    if guard:
        # in-graph divergence guard INSIDE the jit/scan bodies (and
        # inside the telemetry tap below, so the stream sees skip flags)
        from cgnn_tpu.resilience.guard import guard_step

        base_train = guard_step(base_train)
    base_eval = eval_step_fn or make_eval_step(classification)
    train_step = jit_train_step(telemetry.wrap_train_body(base_train))
    eval_step = jax.jit(telemetry.wrap_eval_body(base_eval))
    best_key = best_metric or ("correct" if classification else "mae")
    best = -np.inf if classification else np.inf
    history = []
    rng = np.random.default_rng(seed)
    pad_stats = PaddingStats()

    def _with_profile(iterator, epoch):
        return profile_wrap(
            iterator,
            profile_steps if epoch == start_epoch else 0,
            profile_dir, log_fn,
        )

    driver: ScanEpochDriver | None = None
    if scan_epochs and (profile_steps or print_freq):
        log_fn(
            "scan_epochs: --profile and per-step prints are unavailable "
            "inside the whole-epoch scan (epoch-level metrics only)"
        )
    staging: dict = {}
    packed_lists: tuple | None = None
    if scan_epochs:
        # fold each epoch into one lax.scan dispatch per bucket shape over
        # the HBM-resident stacked batches (amortizes per-step dispatch
        # latency; see ScanEpochDriver and the fit docstring caveat)
        expand = None
        if compact is not None:
            from cgnn_tpu.data.compact import make_expander

            expand = make_expander(compact)
        t_pack = time.perf_counter()
        with telemetry.span("pack"):
            if packed is not None:
                train_list, val_list = (list(x) for x in packed)
            else:
                train_list = list(train_batches(rng))
                val_list = list(val_batches())
        staging["pack_s"] = round(time.perf_counter() - t_pack, 2)
        staged_bytes = staged_nbytes(train_list + val_list)
        staging["staged_mb"] = round(staged_bytes / 1e6, 1)
        staging["compact"] = compact is not None
        if check_device_resident_fit(staged_bytes, log_fn=log_fn):
            t_stage = time.perf_counter()
            with telemetry.span("stage_scan_stacks",
                                staged_mb=staging["staged_mb"]):
                driver = ScanEpochDriver(
                    base_train,
                    base_eval,
                    train_list,
                    val_list,
                    rng,
                    expand=expand,
                    chunk_steps=chunk_steps,
                    telemetry=telemetry,
                    preempt=preempt,
                )
            staging["stack_stage_dispatch_s"] = round(
                time.perf_counter() - t_stage, 2
            )
            telemetry.sample_hbm("post_staging")
        else:
            # LOUD fallback (check_device_resident_fit already logged the
            # numbers): keep the packed batches host-side and restage per
            # epoch instead of dying in an opaque XLA OOM mid-staging
            staging["fallback"] = "host_pack_once"
            scan_epochs = False
            device_resident = False
            packed_lists = (train_list, val_list)
            if expand is not None:
                # the per-step loop sees CompactBatches: expansion moves
                # into the jitted step bodies
                train_step = jit_train_step(
                    telemetry.wrap_train_body(
                        lambda s, b: base_train(s, expand(b))
                    )
                )
                eval_step = jax.jit(
                    telemetry.wrap_eval_body(
                        lambda s, b: base_eval(s, expand(b))
                    )
                )
    plan = (
        PackOncePlan(
            (lambda: packed_lists[0]) if packed_lists is not None
            else (lambda: train_batches(rng)),
            (lambda: packed_lists[1]) if packed_lists is not None
            else val_batches,
            rng,
            device_resident=device_resident,
        )
        if pack_once and driver is None
        else None
    )
    telemetry.observe_padding(pad_stats)
    preempted = False

    def finish_epoch(epoch, train_m, val_m, eval_truncated, t0):
        """Epoch bookkeeping that needs the fetched metrics (best
        tracking, history, logging, the metrics hook) — shared by the
        synchronous path and the deferred async-fetch path, which runs
        it one epoch late, after the NEXT epoch's dispatches are already
        in flight. Returns is_best."""
        nonlocal best
        if epoch == start_epoch:
            log_fn(pad_stats.summary())
        metric = val_m.get(best_key, np.nan)
        is_best = metric > best if classification else metric < best
        if eval_truncated:
            # preemption cut eval short: the metric covers a fraction of
            # the validation set — never let it repoint 'best'
            is_best = False
        if is_best:
            best = metric
        history.append({"epoch": epoch, "train": train_m, "val": val_m})
        epoch_s = time.perf_counter() - t0
        log_fn(
            f"Epoch {epoch}: train loss {train_m.get('loss', np.nan):.4f}"
            f"  val {best_key} {metric:.4f}{' *' if is_best else ''}"
            f"  ({epoch_s:.1f}s)"
        )
        # live-progress gauges + windowed epoch-time series: a mid-run
        # registry scrape (train.py --live-metrics / metrics_live.jsonl)
        # sees where the run is and how fast it is moving, instead of
        # waiting for the exit-time run_summary (host-side bookkeeping
        # only — the trajectory is untouched)
        telemetry.set_gauge("train_epoch", float(epoch))
        telemetry.set_gauge("train_loss_last",
                            float(train_m.get("loss", np.nan)))
        telemetry.set_gauge(f"val_{best_key}_last", float(metric))
        telemetry.set_gauge(f"val_{best_key}_best", float(best))
        telemetry.observe_value("epoch_time_s", epoch_s)
        if on_epoch_metrics is not None:
            on_epoch_metrics(epoch, train_m, val_m)
        return is_best

    # ISSUE 5 satellite: the epoch-pair sums fetch moves to a
    # background thread whenever the divergence monitor doesn't need the
    # sums before proceeding (--guard rollback). Full one-epoch-deep
    # overlap — epoch N's fetch runs while epoch N+1's scans dispatch —
    # additionally requires no epoch-end checkpoint consumer: the save
    # needs (state, metrics) together at the boundary, and the state is
    # donated into the next epoch's first scan the moment it dispatches.
    # With a consumer, the fetch thread still overlaps the next-epoch
    # schedule prebuild and is joined in-iteration (metrics bit-identical
    # either way, pinned by test).
    async_pair = driver is not None and monitor is None
    defer_pair = async_pair and on_epoch_end is None and preempt is None
    pending_prev: tuple | None = None  # (epoch, pending, eval_trunc, t0)

    for epoch in range(start_epoch, epochs):
        t0 = time.perf_counter()
        if driver is not None:
            with telemetry.span("epoch", epoch=epoch, driver="scan"):
                if async_pair:
                    state, pending = driver.run_epoch_pair(
                        state, first=epoch == start_epoch, async_fetch=True
                    )
                else:
                    state, train_m, val_m = driver.run_epoch_pair(
                        state, first=epoch == start_epoch
                    )
            aborted, eval_trunc = driver.aborted, driver.eval_truncated
            if defer_pair:
                if pending_prev is not None:
                    # epoch N-1's fetch ran while epoch N's dispatches
                    # were enqueued; resolve + bookkeep it now, with the
                    # device already streaming into epoch N
                    p_epoch, p_pending, p_trunc, p_t0 = pending_prev
                    tm, vm = p_pending.result()
                    finish_epoch(p_epoch, tm, vm, p_trunc, p_t0)
                    pending_prev = None
                if aborted:
                    # defensive only — defer_pair requires preempt=None,
                    # and the driver sets aborted solely from a preempt
                    # poll. Mirror the sync path: the partial epoch's
                    # metrics are DROPPED (never queued for bookkeeping)
                    save_preempted_mid_epoch(state, epoch, on_epoch_end,
                                             log_fn)
                    preempted = True
                    break
                pending_prev = (epoch, pending, eval_trunc, t0)
                faultinject.maybe_sigterm(epoch)
                continue
            if async_pair:
                train_m, val_m = pending.result()
            if aborted:
                save_preempted_mid_epoch(state, epoch, on_epoch_end, log_fn)
                preempted = True
                break
        else:
            if plan is not None:
                epoch_train, epoch_val = plan.epoch_iterators()
            else:
                epoch_train = train_batches(rng)
                epoch_val = val_batches()
            # device-resident batches need no staging; re-putting them
            # through the prefetch thread would only add overhead
            stage = (
                (lambda it: it) if device_resident
                else (lambda it: prefetch_to_device(it, telemetry=telemetry))
            )
            with telemetry.span("epoch", epoch=epoch, driver="per_step"):
                state, train_m = run_epoch(
                    train_step,
                    state,
                    _with_profile(stage(epoch_train), epoch),
                    train=True,
                    print_freq=print_freq,
                    epoch=epoch,
                    log_fn=log_fn,
                    telemetry=telemetry,
                )
            _, val_m = run_epoch(
                eval_step,
                state,
                stage(epoch_val),
                train=False,
                epoch=epoch,
                log_fn=log_fn,
                telemetry=telemetry,
            )
        is_best = finish_epoch(
            epoch, train_m, val_m,
            driver is not None and driver.eval_truncated, t0,
        )
        state, _, preempted = resilience_epoch_end(
            state, epoch, train_m, val_m, is_best, monitor=monitor,
            on_epoch_end=on_epoch_end, preempt=preempt, log_fn=log_fn,
        )
        if preempted:
            break
    if pending_prev is not None:
        # the deferred path's final epoch: nothing overlaps its fetch —
        # resolve and bookkeep it before reporting the run
        p_epoch, p_pending, p_trunc, p_t0 = pending_prev
        tm, vm = p_pending.result()
        finish_epoch(p_epoch, tm, vm, p_trunc, p_t0)
    out = {"best": best, "history": history}
    if preempted:
        out["preempted"] = True
    if staging:
        out["staging"] = staging
    return state, out


def evaluate(
    state: TrainState,
    graphs: Sequence[CrystalGraph],
    batch_size: int,
    node_cap: int,
    edge_cap: int,
    classification: bool = False,
    eval_step_fn: Callable | None = None,
    dense_m: int | None = None,
    snug: bool = False,
    edge_dtype=np.float32,
) -> dict:
    if dense_m is not None:
        edge_cap = node_cap * dense_m
    eval_step = jax.jit(eval_step_fn or make_eval_step(classification))
    _, metrics = run_epoch(
        eval_step,
        state,
        batch_iterator(graphs, batch_size, node_cap, edge_cap,
                       dense_m=dense_m, in_cap=0, snug=snug,
                       edge_dtype=edge_dtype),
        train=False,
    )
    return metrics
