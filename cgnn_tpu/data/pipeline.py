"""Parallel host-ingest pipeline: a pool of packers feeding one consumer.

``prefetch_to_device`` (data/loader.py) hides ONE producer behind the
device; that is enough for training, where a multi-ms fused train step
amortizes a single packer. The forward path has no such luck: a predict
step is sub-ms, so at inference the device drains batches faster than
one thread can pack them and the chip sits idle on the host's critical
path (round 5, PERF.md: 112,305 structs/s device rate vs 1,461 end-to-end —
98.7% host). ``parallel_pack`` generalizes the producer pattern to a
POOL of packer threads with order-restoring reassembly:

    jobs ──feeder──> in-queue ──N workers (pack_fn)──> reassembly
                                                          │ (in order)
                                                       consumer

- **Bounded**: at most ``depth`` jobs are in flight (queued + packing +
  reassembled-but-unconsumed), so host memory for staged batches stays
  flat no matter how far the packers outrun the consumer.
- **Order-restoring**: results are yielded in job order regardless of
  which worker finishes first — the caller's span bookkeeping (output
  row -> input graph) survives parallelism untouched.
- **Deterministic shutdown**: every blocking queue operation is bounded
  by a stop event the consumer generator's ``finally`` sets, exactly
  like the loader's ``bounded_put`` — a consumer that abandons the
  iterator mid-stream (exception, early return) releases feeder and
  workers within one timeout tick; nothing ever blocks forever holding
  packed batches alive.
- **Per-job errors**: a ``pack_fn`` exception is delivered IN ORDER as a
  :class:`PackError` result (``raise_on_error=True`` re-raises it at the
  consumer) so one poisoned batch fails its own slot, not the stream —
  the serving path resolves just that flush's futures with the error.

Packing is numpy (the big copies release the GIL), so threads scale
until memory bandwidth, not the interpreter, is the wall — the same
reasoning as the loader, multiplied.

Telemetry (mirrors ``loader_wait_s``/``loader_put_s``):

- ``pipeline_wait_s``   — consumer blocked waiting for the next in-order
  result (packers failing to keep ahead; the starvation signal);
- ``pipeline_pack_s``   — cumulative worker seconds spent in ``pack_fn``;
- ``pipeline_jobs``     — jobs completed;
- ``pipeline_workers``  / ``pipeline_occupancy`` gauges — pool size and
  pack-busy share of the pool's wall-clock capacity.

``BufferPool`` is the allocation half of the fix: PERF.md §7 measured
the full-fidelity pack PAGE-FAULT bound (fresh zeros at ~0.2 GB/s
effective), so packers that re-use preallocated per-shape buffers
(``pack_compact(out=...)``) write into already-mapped pages instead of
faulting fresh ones in per batch. Release discipline is the caller's:
a buffer goes back to the pool only once the device has consumed the
dispatch that read it (see train/infer.py's window-fence release and
serve/server.py's post-fetch release).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Hashable, Iterable, Iterator

from cgnn_tpu.analysis import racecheck

_STOP = object()
_TICK = 0.05  # seconds; the shutdown-latency bound for every blocking op


@dataclasses.dataclass
class PackError:
    """An in-order stand-in for a job whose ``pack_fn`` raised."""

    error: BaseException


class BufferPool:
    """Reusable host staging buffers, keyed by (hashable) shape.

    ``acquire`` pops a free buffer for ``key`` or builds one via
    ``factory``; ``release`` returns it. The pool never blocks and never
    shrinks below what the pipeline's bounded depth can have in flight;
    ``limit_per_key`` only caps pathological release floods (extras are
    dropped to the GC). Thread-safe: packers acquire from worker
    threads, the consumer releases after the device consumed the batch.
    """

    def __init__(self, limit_per_key: int = 16):
        self._free: dict[Hashable, list] = {}
        self._lock = threading.Lock()
        self.limit_per_key = limit_per_key
        self.allocated = 0  # fresh factory builds (the page-fault count)
        self.reused = 0

    def acquire(self, key: Hashable, factory: Callable[[], Any]):
        with self._lock:
            free = self._free.get(key)
            if free:
                self.reused += 1
                return free.pop()
            self.allocated += 1
        return factory()

    def release(self, key: Hashable, buf: Any) -> None:
        with self._lock:
            free = self._free.setdefault(key, [])
            if len(free) < self.limit_per_key:
                free.append(buf)


def parallel_pack(
    jobs: Iterable,
    pack_fn: Callable[[Any], Any],
    *,
    workers: int = 2,
    depth: int | None = None,
    telemetry=None,
    raise_on_error: bool = True,
    name: str = "cgnn-pack",
    join_timeout: float = 5.0,
) -> Iterator[Any]:
    """Yield ``pack_fn(job)`` for each job, in job order, packed by a
    pool of ``workers`` threads (module docstring has the contract).

    ``jobs`` is consumed by a dedicated feeder thread, so a blocking
    jobs generator (e.g. a batcher's ``next_flush`` stream) overlaps
    with packing too. ``depth`` bounds in-flight jobs (default
    ``2 * workers``). An exception raised by the JOBS iterable itself is
    re-raised at the consumer after in-flight results drain (the
    loader's producer-error contract).
    """
    workers = max(1, int(workers))
    depth = depth or 2 * workers
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    in_q: queue.Queue = queue.Queue()
    stop = threading.Event()
    slots = threading.BoundedSemaphore(depth)
    cond = threading.Condition()
    results: dict[int, Any] = {}
    feed_err: list[BaseException] = []
    n_jobs = [-1]  # total job count, known once the feeder exhausts jobs
    pack_busy = [0.0]

    def feeder() -> None:
        seq = 0
        try:
            for payload in jobs:
                while not stop.is_set():
                    if slots.acquire(timeout=_TICK):
                        break
                else:
                    return  # consumer gone; drop the stream
                if stop.is_set():
                    slots.release()
                    return
                in_q.put((seq, payload))
                seq += 1
        except BaseException as e:  # noqa: BLE001 — re-raised consumer-side
            feed_err.append(e)
        finally:
            with cond:
                n_jobs[0] = seq
                cond.notify_all()
            in_q.put(_STOP)

    # the pack-pool hop in the Chrome-trace stream: one span per job on
    # its worker's track, keyed by sequence number so a request trace
    # (serve.pack carries the same wall window) lines up with the pool
    spans = getattr(telemetry, "spans", None)

    def worker() -> None:
        while not stop.is_set():
            racecheck.heartbeat()  # ticks every _TICK even when starved
            try:
                item = in_q.get(timeout=_TICK)
            except queue.Empty:
                continue
            if item is _STOP:
                in_q.put(_STOP)  # wake the sibling workers too
                return
            seq, payload = item
            t0 = time.perf_counter()
            try:
                res = pack_fn(payload)
            except BaseException as e:  # noqa: BLE001 — delivered in-order
                res = PackError(e)
            t1 = time.perf_counter()
            dt = t1 - t0
            with cond:
                pack_busy[0] += dt
                results[seq] = res
                cond.notify_all()
            if spans is not None:
                spans.complete(f"{name}.job", t0, t1, seq=seq,
                               error=isinstance(res, PackError))
            if telemetry is not None:
                telemetry.counter_add("pipeline_pack_s", dt)
                telemetry.counter_add("pipeline_jobs", 1)

    # stable names (graftcheck GC-THREADNAME): racecheck heartbeats and
    # faulthandler deadlock dumps key on them. The pool prefix stays in
    # the worker name — the beats registry is keyed BY name, so two
    # pools in one process (serve's 'cgnn-serve-pack' + an inference
    # 'cgnn-pack') must not share a key, or one pool's fresh beat masks
    # the other pool's wedged worker
    feed_t = threading.Thread(target=feeder, daemon=True,
                              name=f"{name}-feeder")
    work_ts = [
        threading.Thread(target=worker, daemon=True,
                         name=f"{name}-worker-{i}")
        for i in range(workers)
    ]
    t_start = time.perf_counter()
    feed_t.start()
    for t in work_ts:
        t.start()
    if telemetry is not None:
        telemetry.set_gauge("pipeline_workers", float(workers))
    try:
        seq = 0
        while True:
            t0 = time.perf_counter()
            with cond:
                while seq not in results:
                    if n_jobs[0] >= 0 and seq >= n_jobs[0]:
                        break
                    cond.wait(timeout=_TICK)
                if n_jobs[0] >= 0 and seq >= n_jobs[0]:
                    break
                res = results.pop(seq)
            if telemetry is not None:
                telemetry.counter_add(
                    "pipeline_wait_s", time.perf_counter() - t0
                )
            seq += 1
            slots.release()
            if isinstance(res, PackError) and raise_on_error:
                raise res.error
            yield res
    finally:
        # reached on normal exhaustion AND on generator close (consumer
        # abandonment): release feeder + workers, then join — every
        # blocking op above is bounded by _TICK, so they exit promptly
        stop.set()
        feed_t.join(join_timeout)
        for t in work_ts:
            t.join(join_timeout)
        if telemetry is not None:
            wall = max(time.perf_counter() - t_start, 1e-9)
            telemetry.set_gauge(
                "pipeline_occupancy",
                min(1.0, pack_busy[0] / (workers * wall)),
            )
    if feed_err:
        raise feed_err[0]
