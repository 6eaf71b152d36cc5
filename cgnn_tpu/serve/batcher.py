"""Priority-class continuous micro-batcher: coalesce single-structure
requests into fixed-shape batches under per-class latency deadlines,
backfilling padding slack with lower-class work.

The queueing policy in one paragraph: requests carry a priority CLASS
(``interactive`` / ``batch`` / ``scavenger``; CLASSES) and accumulate in
one bounded queue. A flush is cut for the HEAD class — the
highest-priority class present, unless a lower class has aged past its
own per-class wait budget (starvation freedom: a scavenger request
cannot sit forever behind a saturated interactive stream). Within the
head class, requests are ordered by weighted fair queuing across
tenants (per-tenant virtual finish times, so one heavy tenant cannot
starve the rest), and the flush fires when the head batch would
overflow the LARGEST precompiled shape ("shape_full"), the head class's
oldest request has waited its class budget ("deadline"), or the head
prefix hits a (class, tier, form) cut boundary ("tier_boundary" — one
program per flush). After the rung is chosen for the head prefix,
BACKFILL (ISSUE 19) fills the rung's remaining graph/node/edge slack
with lower-class requests sharing the head's (tier, form): padded slots
become goodput without delaying the head flush (the rung is already
chosen and fires NOW) and without ever leaving the warm shape set — so
in no case does packing wait on a recompile.

Admission control happens at ``offer``:

- bounded queue (``max_queue``): a full queue REJECTS instead of
  buffering unboundedly — the client sees backpressure (HTTP 429) while
  the server keeps serving its current load at its current latency;
- oversize structures (don't fit the largest shape even alone) are
  rejected with the observed sizes — queueing one would wedge the head
  of the FIFO forever;
- an unknown priority class is MALFORMED — silently mapping it to a
  default would quietly change the request's scheduling contract;
- a closed (draining) batcher rejects new work but keeps flushing what
  it already accepted — the SIGTERM drain path.

Per-request deadlines are enforced at flush time: a request whose
deadline passed while queued is returned in ``Flush.expired`` (never
packed) so the caller can fail it promptly — serving a reply the client
already gave up on wastes a batch slot.

Everything here is pure host-side data-structure logic with an
injectable clock: the decision core (``poll``) is synchronously testable
with a fake clock; ``next_flush`` adds the blocking condition-variable
loop the server's worker thread runs.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable

from cgnn_tpu.analysis import racecheck
from cgnn_tpu.data.graph import CrystalGraph
from cgnn_tpu.serve.shapes import BatchShape, ShapeSet

# rejection reasons (stable strings: telemetry counter suffixes and HTTP
# error payloads key on them)
QUEUE_FULL = "queue_full"
OVERSIZE = "oversize"
TIMEOUT = "timeout"
SHUTDOWN = "shutdown"
MALFORMED = "malformed"

# priority classes (ISSUE 19), rank order = scheduling order (index 0
# preempts index 1, ...). Stable strings: they ride HTTP payloads,
# metric label values, and counter suffixes, so renaming one is a wire
# protocol change.
CLASSES = ("interactive", "batch", "scavenger")
DEFAULT_CLASS = CLASSES[0]
_CLASS_RANK = {c: i for i, c in enumerate(CLASSES)}

# per-class wait budget as a multiple of max_wait when no explicit
# class_max_wait_ms map is given: interactive keeps the legacy flush
# deadline; batch and scavenger trade latency for riding backfill slack
_DEFAULT_WAIT_MULT = {"interactive": 1.0, "batch": 4.0, "scavenger": 16.0}


def parse_kv_spec(spec: str) -> dict[str, float]:
    """Parse a ``"key=float,key=float"`` spec string (class waits, class
    SLOs, tenant weights — the shared flag grammar of serve.py /
    fleet.py / the loadgen). Empty -> {}."""
    out: dict[str, float] = {}
    for part in str(spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"malformed spec entry {part!r} (want key=value)")
        k, v = part.split("=", 1)
        out[k.strip()] = float(v)
    return out


class ServeRejection(RuntimeError):
    """A request the server declines to process; ``reason`` is one of the
    module-level rejection constants."""

    def __init__(self, reason: str, detail: str = ""):
        self.reason = reason
        super().__init__(detail or reason)


class RequestFuture:
    """One request's pending result (threading.Event + slot).

    ``add_done_callback`` exists for single-flight miss coalescing
    (server.py): follower requests for an in-flight fingerprint attach
    to the leader's future instead of entering the batcher, and are
    resolved on whichever thread completes the leader — success, error,
    or expiry all fire the callbacks exactly once."""

    def __init__(self):
        self._done = threading.Event()
        self._result = None
        self._error: BaseException | None = None
        self._cb_lock = threading.Lock()
        self._callbacks: list = []

    def set_result(self, result) -> None:
        self._result = result
        self._done.set()
        self._fire_callbacks()

    def set_error(self, error: BaseException) -> None:
        self._error = error
        self._done.set()
        self._fire_callbacks()

    def add_done_callback(self, fn) -> None:
        """``fn(self)`` once this future resolves (immediately if it
        already has); callbacks run on the resolving thread."""
        with self._cb_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _fire_callbacks(self) -> None:
        with self._cb_lock:
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            fn(self)

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float | None = None):
        if not self._done.wait(timeout):
            raise TimeoutError("request still pending")
        if self._error is not None:
            raise self._error
        return self._result


@dataclasses.dataclass
class Request:
    """A queued single-structure prediction request."""

    graph: CrystalGraph
    enqueued: float  # monotonic seconds
    deadline: float | None  # absolute monotonic; None = no deadline
    future: RequestFuture = dataclasses.field(default_factory=RequestFuture)
    fingerprint: str | None = None
    # slot budget under the shape set's layout, computed once at admission
    nodes: int = 0
    edges: int = 0
    # can this graph stage compactly (raw distances present + consistent,
    # atom rows in the vocabulary)? Decided ONCE at admission — a flush
    # whose requests are all compactable packs the raw CompactBatch form;
    # any non-compactable member demotes its flush to full-fidelity
    # packing (both programs are warmed, so neither path ever recompiles)
    compactable: bool = False
    # per-request trace identity (minted at admission; an inbound
    # X-Request-Id is honored) + the monotonic per-stage stamps
    # (SpanTracer.now_s clock): queued / packed / dispatched / fetched /
    # replied — the live-observability request journey
    trace_id: str = ""
    stamps: dict = dataclasses.field(default_factory=dict)
    # inbound cross-process span parent (observe/tracectx.py): the
    # upstream attempt span this request's serve.request span nests
    # under in a joined fleet trace; "" when the request arrived with
    # no X-Trace-Parent (this process roots its own tree)
    trace_parent: str = ""
    # precision tier (serve/quantize.py TIERS), validated at admission
    # against the server's warmed set: a flush runs ONE program, so
    # co-batched requests must share a tier — the batcher cuts a flush
    # at every tier boundary in the head prefix (see _take_locked)
    precision: str = "f32"
    # staging form (ISSUE 11): 'feat' = a featurized CrystalGraph (or a
    # wire-form structure the pack stage will featurize on the pool —
    # graph then holds the RawStructure until pack time), 'raw' = staged
    # as a RawBatch for the in-program neighbor search. Like precision,
    # a flush runs ONE program, so the head prefix cuts at form
    # boundaries — with the class, the full cut key is the
    # (class, tier, form) triple (ISSUE 19).
    form: str = "feat"
    # priority class (ISSUE 19, CLASSES): which per-class wait budget
    # and scheduling rank this request rides. The default keeps
    # single-class callers on the legacy FIFO behavior exactly.
    klass: str = DEFAULT_CLASS
    # fair-queuing tenant ("" = the shared anonymous tenant): WFQ
    # ordering within a class is by per-tenant virtual finish time
    tenant: str = ""
    # set by the batcher when this request rode a higher-class flush's
    # padding slack instead of waiting for its own class's cut — it is
    # still answered exactly once under its own trace id, never
    # downgraded (INVARIANTS.md)
    backfilled: bool = False
    # WFQ virtual finish time, stamped at offer() under the queue lock
    vft: float = 0.0


@dataclasses.dataclass
class Flush:
    """One batcher decision: requests to pack (into ``shape``) plus any
    requests whose deadline expired while queued."""

    requests: list
    shape: BatchShape | None
    expired: list
    reason: str = ""  # 'shape_full' | 'tier_boundary' | 'deadline' | 'drain' | ''
    # batch identity: co-batched requests carry DISTINCT trace ids but
    # share this flush id — the join key between a request's trace and
    # the flush-level pack/dispatch/fetch spans
    flush_id: str = ""
    # per-flush stage stamps (packed/dispatched/fetched), merged into
    # every member request's journey at reply time
    stamps: dict = dataclasses.field(default_factory=dict)
    # the tier every member shares (dispatch picks this tier's program
    # + param variant; serve/quantize.py)
    precision: str = "f32"
    # the staging form every member shares ('feat' | 'raw'; ISSUE 11)
    form: str = "feat"
    # the priority class this flush was CUT FOR (ISSUE 19): backfilled
    # lower-class members ride along without changing it — the flush's
    # timing contract belongs to the head class
    klass: str = DEFAULT_CLASS
    # backfill accounting: members that rode padding slack, and the
    # graph-slot slack the chosen rung had before backfill ran (the
    # serve_padding_fill_share numerator/denominator)
    n_backfilled: int = 0
    slack_slots: int = 0

    def __bool__(self) -> bool:
        return bool(self.requests or self.expired)

    def trace_ids(self) -> list:
        return [r.trace_id for r in self.requests]


class MicroBatcher:
    """Bounded priority queue + the flush policy described in the module
    docstring."""

    def __init__(
        self,
        shape_set: ShapeSet,
        *,
        max_queue: int = 256,
        max_wait_ms: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
        queue_wait_hist=None,
        class_max_wait_ms: dict | None = None,
        backfill: bool = True,
        wfq_weights: dict | None = None,
    ):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.shape_set = shape_set
        self.max_queue = max_queue
        self.max_wait = max_wait_ms / 1000.0
        self._clock = clock
        # mergeable queue-wait histogram (observe/hist.py, ISSUE 16):
        # each fired request's enqueue->flush wait lands here at the
        # flush decision, the queueing truth independent of pack/dispatch
        # time downstream. None keeps the hot path untouched.
        self.queue_wait_hist = queue_wait_hist
        # per-class wait budget (seconds): explicit ms overrides, else
        # the default multiples of max_wait. An unknown class in the
        # override is a config error, not a silent default.
        self.class_wait = {
            c: self.max_wait * _DEFAULT_WAIT_MULT[c] for c in CLASSES
        }
        for c, ms in (class_max_wait_ms or {}).items():
            if c not in _CLASS_RANK:
                raise ValueError(
                    f"unknown priority class {c!r} in class_max_wait_ms "
                    f"(have: {list(CLASSES)})")
            self.class_wait[c] = float(ms) / 1000.0
        # padding-slack backfill switch
        self.backfill = bool(backfill)
        # WFQ tenant weights (share of service per unit weight); tenants
        # absent from the map get weight 1.0
        self.wfq_weights: dict[str, float] = {}
        for t, w in (wfq_weights or {}).items():
            if float(w) <= 0:
                raise ValueError(
                    f"wfq weight for tenant {t!r} must be > 0, got {w}")
            self.wfq_weights[str(t)] = float(w)
        self._queue: list[Request] = []
        # a plain Condition normally; instrumented (lock-order + held-by
        # tracking) under CGNN_TPU_RACECHECK=1 — racecheck.make_condition
        # returns threading.Condition() when the gate is off
        self._cond = racecheck.make_condition("serve.batcher")
        self._closed = False
        self._flush_seq = 0
        # WFQ virtual time: advances to the largest served finish time;
        # a newly-arriving tenant starts HERE, so idling never banks
        # credit. All mutated under self._cond (GC-LOCKSHARE).
        self._vtime = 0.0
        self._tenant_vft: dict[str, float] = {}
        # lifetime backfill accounting (the serve_padding_fill_share
        # feed): requests that rode slack / graph-slot slack offered
        self._backfilled_total = 0
        self._slack_total = 0

    # ---- admission ----

    def offer(self, request: Request) -> None:
        """Admit or reject (raises ServeRejection; never blocks)."""
        if request.klass not in _CLASS_RANK:
            raise ServeRejection(
                MALFORMED,
                f"unknown priority class {request.klass!r} "
                f"(have: {list(CLASSES)})",
            )
        n, e = self.shape_set.graph_counts(request.graph)
        request.nodes, request.edges = n, e
        if not self.shape_set.largest.fits(1, n, e):
            raise ServeRejection(
                OVERSIZE, self.shape_set.oversize_detail(request.graph)
            )
        with self._cond:
            if self._closed:
                raise ServeRejection(SHUTDOWN, "server is draining")
            if len(self._queue) >= self.max_queue:
                raise ServeRejection(
                    QUEUE_FULL,
                    f"request queue at capacity ({self.max_queue})",
                )
            # WFQ stamp: finish time = max(global vtime, the tenant's
            # last finish) + cost/weight (cost 1 per request — service
            # share is in requests). Same-tenant arrivals chain, so a
            # single tenant degenerates to strict FIFO.
            w = self.wfq_weights.get(request.tenant, 1.0)
            base = max(self._vtime,
                       self._tenant_vft.get(request.tenant, 0.0))
            request.vft = base + 1.0 / w
            self._tenant_vft[request.tenant] = request.vft
            self._queue.append(request)
            self._cond.notify_all()

    @property
    def depth(self) -> int:
        with self._cond:
            return len(self._queue)

    @property
    def backfilled_total(self) -> int:
        """Requests that rode a higher-class flush's padding slack."""
        with self._cond:
            return self._backfilled_total

    @property
    def slack_total(self) -> int:
        """Graph-slot slack offered to backfill across all flushes."""
        with self._cond:
            return self._slack_total

    # ---- flush policy ----

    def _head_class_locked(self, live: list, now: float) -> str:
        """The class the next flush is cut for: the highest-priority
        class present — unless some class has AGED past its own wait
        budget, in which case the most-overdue class wins (starvation
        freedom: sustained interactive load cannot pin a scavenger
        request forever; once overdue it gets its own flush)."""
        oldest: dict[str, float] = {}
        for r in live:
            if r.klass not in oldest or r.enqueued < oldest[r.klass]:
                oldest[r.klass] = r.enqueued

        def urgency(c: str) -> float:
            return (now - oldest[c]) / max(self.class_wait[c], 1e-9)

        overdue = [c for c in oldest if urgency(c) >= 1.0]
        if overdue:
            # most overdue first; ties break toward the higher class
            return max(overdue,
                       key=lambda c: (urgency(c), -_CLASS_RANK[c]))
        return min(oldest, key=lambda c: _CLASS_RANK[c])

    def _take_locked(self, now: float) -> tuple[list, list, bool, bool]:
        """(head-class batch prefix, expired, shape-full, hit-boundary).
        The _locked suffix is the graftcheck GC-LOCKSHARE contract:
        callers hold self._cond.

        The cut key is the (class, tier, form) TRIPLE (ISSUE 19): the
        head class is chosen first (_head_class_locked), then within it
        requests are walked in WFQ order and a precision-tier or
        staging-form change is a batch boundary exactly like shape-full
        — the head (tier, form) prefix fires NOW (one program per
        flush), the rest starts the next batch. A mixed queue degrades
        to smaller flushes, never to head-of-line blocking; single-class
        single-tenant traffic walks in strict FIFO order, preserving the
        legacy behavior exactly."""
        big = self.shape_set.largest
        expired = [r for r in self._queue
                   if r.deadline is not None and now >= r.deadline]
        dead = set(map(id, expired))
        live = [r for r in self._queue if id(r) not in dead]
        if not live:
            return [], expired, False, False
        head = self._head_class_locked(live, now)
        # WFQ order within the head class (stable sort: equal finish
        # times keep arrival order)
        cand = sorted((r for r in live if r.klass == head),
                      key=lambda r: r.vft)
        take: list[Request] = []
        n_nodes = n_edges = 0
        full = False
        boundary = False
        key: tuple | None = None
        for req in cand:
            if key is None:
                key = (req.precision, req.form)
            elif (req.precision, req.form) != key:
                boundary = True  # tier/form cut: fire the head prefix now
                break
            if not big.fits(len(take) + 1, n_nodes + req.nodes,
                            n_edges + req.edges):
                full = True
                break
            take.append(req)
            n_nodes += req.nodes
            n_edges += req.edges
        # graph slots saturated = full even with nothing else queued (a
        # later arrival could never join this batch anyway)
        return (take, expired, full or len(take) >= big.graph_cap,
                boundary)

    def _backfill_locked(self, fired: list, shape: BatchShape,
                         now: float) -> tuple[int, int]:
        """Fill the chosen rung's remaining graph/node/edge slack with
        LOWER-class queued requests sharing the head's (tier, form)
        (ISSUE 19). The rung was already chosen for the head prefix and
        the flush fires NOW either way, so backfill can only convert
        padding into goodput — never delay the head class, never change
        the shape, never leave the warm set. A candidate that does not
        fit the remaining slack stays queued (a later, smaller one may
        still fit). -> (backfilled count, graph-slot slack offered)."""
        head = fired[0]
        head_rank = _CLASS_RANK[head.klass]
        key = (head.precision, head.form)
        n = len(fired)
        slack = shape.graph_cap - n
        if slack <= 0:
            return 0, 0
        n_nodes = sum(r.nodes for r in fired)
        n_edges = sum(r.edges for r in fired)
        taken = set(map(id, fired))
        cand = [r for r in self._queue
                if id(r) not in taken
                and _CLASS_RANK[r.klass] > head_rank
                and (r.precision, r.form) == key
                and not (r.deadline is not None and now >= r.deadline)]
        # highest class first among the lower ones, WFQ order within
        cand.sort(key=lambda r: (_CLASS_RANK[r.klass], r.vft))
        backfilled = 0
        for r in cand:
            if not shape.fits(n + 1, n_nodes + r.nodes,
                              n_edges + r.edges):
                continue
            r.backfilled = True
            fired.append(r)
            n += 1
            n_nodes += r.nodes
            n_edges += r.edges
            backfilled += 1
            if n >= shape.graph_cap:
                break
        return backfilled, slack

    def poll(self, now: float | None = None) -> Flush | None:
        """Non-blocking flush decision at time ``now``.

        Returns a Flush when the policy says fire (shape-full, head
        class's oldest waited past its class budget, tier/form boundary,
        draining, or deadline expiries need delivering), else None. Pure
        given the clock — the unit-testable core of the batcher."""
        now = self._clock() if now is None else now
        with self._cond:
            take, expired, full, boundary = self._take_locked(now)
            head_wait = (self.class_wait[take[0].klass] if take
                         else self.max_wait)
            waited = (
                take and now - min(r.enqueued for r in take) >= head_wait
            )
            if full or boundary or waited or (self._closed and take):
                # tier_boundary gets its own reason: conflating it with
                # shape_full would inflate the ladder-tuning signal with
                # tier-fragmentation flushes (they can be nearly empty)
                reason = ("shape_full" if full
                          else "tier_boundary" if boundary
                          else "deadline" if waited else "drain")
                fired = take
            elif expired:
                # nothing to pack yet, but expiries must not sit until
                # the next natural flush — deliver them now
                reason, fired = "", []
            else:
                return None
            shape = None
            n_back = slack = 0
            if fired:
                # the rung is chosen for the HEAD prefix; backfill then
                # packs lower-class work into its remaining slack
                # without ever upgrading the rung
                shape = self.shape_set.shape_for(
                    len(fired),
                    sum(r.nodes for r in fired),
                    sum(r.edges for r in fired),
                )
                if self.backfill and shape is not None:
                    n_back, slack = self._backfill_locked(
                        fired, shape, now)
                    self._backfilled_total += n_back
                    self._slack_total += slack
            drop = set(map(id, fired)) | set(map(id, expired))
            self._queue = [r for r in self._queue if id(r) not in drop]
            if self.queue_wait_hist is not None:
                for r in fired:
                    self.queue_wait_hist.observe((now - r.enqueued) * 1e3)
            if fired:
                # advance WFQ virtual time to the largest served finish
                # tag — late-arriving tenants start from here
                self._vtime = max(self._vtime,
                                  max(r.vft for r in fired))
            self._flush_seq += 1
            return Flush(fired, shape, expired, reason,
                         flush_id=f"flush-{self._flush_seq:06d}",
                         precision=(fired[0].precision if fired
                                    else "f32"),
                         form=(fired[0].form if fired else "feat"),
                         klass=(fired[0].klass if fired
                                else DEFAULT_CLASS),
                         n_backfilled=n_back, slack_slots=slack)

    def next_flush(self) -> Flush | None:
        """Block until the policy fires (worker-thread API).

        Returns None exactly once the batcher is closed AND empty — the
        worker's signal to exit after the drain is complete."""
        while True:
            # ticks every <= max_wait even when idle, so the racecheck
            # deadlock watchdog can tell 'no traffic' from 'wedged'
            racecheck.heartbeat()
            with self._cond:
                if self._closed and not self._queue:
                    return None
                if not self._queue:
                    self._cond.wait(timeout=self.max_wait)
                    continue
                # sleep until the soonest event that can fire a flush:
                # a class wait budget elapsing OR a per-request deadline
                # expiring (a lower-class-only queue may legitimately
                # sleep past max_wait; a new arrival that makes the
                # batch shape-full wakes us early via notify)
                next_at = min(
                    r.enqueued + self.class_wait[r.klass]
                    for r in self._queue
                )
                dl = min((r.deadline for r in self._queue
                          if r.deadline is not None), default=None)
                if dl is not None:
                    next_at = min(next_at, dl)
                remaining = next_at - self._clock()
                closed = self._closed  # read under the lock (GC-LOCKSHARE)
            if remaining > 0 and not closed:
                with self._cond:
                    self._cond.wait(timeout=remaining)
            flush = self.poll()
            if flush is not None:
                return flush

    # ---- drain ----

    def close(self) -> None:
        """Stop admitting; queued work still flushes (graceful drain)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    @property
    def closed(self) -> bool:
        with self._cond:
            return self._closed
