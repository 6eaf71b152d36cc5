"""The in-process online inference server (the serve/ core).

``InferenceServer`` is deliberately socket-free: submit() -> future ->
result, driven by one worker thread — the whole request path (admission,
micro-batching, packing, dispatch, hot reload, caching, draining) is
exercisable from a unit test or an in-process load generator with no
ports involved. The stdlib HTTP front-end (serve/http.py) is a thin
translation layer on top.

Request lifecycle::

    submit(graph)
      -> cache hit?  resolve immediately (no queue)
      -> batcher.offer (admission: oversize / queue-full / draining;
         compact-stageability decided here, per request)
    worker (pack_workers > 0 — the default on accelerators):
      feeder: batcher.next_flush() -> expired fail with TIMEOUT
        -> packer pool (data/pipeline.py): pack into the flush's
           precompiled shape — compact raw form when every member can,
           warmed full-fidelity otherwise — into pooled buffers
      dispatch: for each packed flush, in order:
        -> (state, version) = param_store.get(device)  # hot-swap boundary
        -> predict_step(state, batch) -> device_get
        -> resolve each future with (row, version, latency, device_id)
      (so the batcher coalesces flush N+2 while N+1 packs and N runs;
       pack_workers=0 runs the same stages in-line on one thread)
    with devices > 1 the ENGINE decides how the set is driven
      (ISSUE 10): the default 'mesh' engine splits each flush
      round-robin across a Mesh + NamedSharding layout and ONE sharded
      jitted dispatch covers every device (no router, no per-device
      threads; parallel/executor.py); 'threads' keeps the ISSUE-5
      DeviceSet layer — a router assigns each packed flush to the
      least-loaded device and one dispatch thread PER device runs it
      against that device's param replica

Hot reload safety rides on the ``param_store.get()`` placement: the pair
is read once per batch, so a watcher swap lands cleanly between batches
and in-flight work finishes on the params it started with. Every
response carries ``param_version`` so clients (and the loadgen's
hot-swap assertion) can see exactly which weights answered.

``warm()`` compiles every shape in the set before the server accepts
traffic — with the persistent XLA compile cache configured, a restart
replays compilations from disk. After warmup the compile count is
PINNED: the batcher only emits shapes from the warm set, so
``predict_step`` never traces again (asserted by tests via the jit
cache-miss counter, and re-checked per flush when telemetry is on).

Live observability plane (ISSUE 6), all host-side — predictions are
bit-identical with it on or off and nothing new is staged into jitted
code:

- every request gets a trace id at admission (inbound ``X-Request-Id``
  honored) and monotonic stage stamps (queued/packed/dispatched/
  fetched/replied) that ride the ``ServeResult`` and, when telemetry is
  on, land as ``serve.request``/``serve.pack``/``serve.dispatch`` spans
  in the Chrome-trace stream, joined by the flush id co-batched
  requests share;
- ``self.registry`` (observe/export.py) is the scrape point behind
  ``GET /metrics`` and ``stats()["rolling"]``: request counters,
  per-device in-flight depth, and 60 s rolling-window latency/occupancy
  quantiles, live at any moment of the run;
- ``enable_profiling(dir)`` arms the on-demand bounded ``jax.profiler``
  capture behind ``POST /profile`` and SIGUSR2 (one at a time;
  concurrent requests are rejected).
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Sequence

import numpy as np

from cgnn_tpu.analysis import racecheck
from cgnn_tpu.data.graph import CrystalGraph
from cgnn_tpu.data.rawbatch import RawStructure, raw_fingerprint
from cgnn_tpu.resilience import faultinject
from cgnn_tpu.serve.batcher import (
    CLASSES,
    DEFAULT_CLASS,
    MALFORMED,
    OVERSIZE,
    TIMEOUT,
    Flush,
    MicroBatcher,
    Request,
    RequestFuture,
    ServeRejection,
)
from cgnn_tpu.serve.cache import ResultCache, structure_fingerprint
from cgnn_tpu.serve.devices import DeviceSet, resolve_devices
from cgnn_tpu.serve.reload import CheckpointWatcher, ParamStore
from cgnn_tpu.serve.shapes import ShapeSet, plan_shape_set


@dataclasses.dataclass
class ServeResult:
    """One answered request."""

    prediction: np.ndarray  # [T] denormalized
    param_version: str
    latency_ms: float
    cached: bool = False
    # precision tier that computed it (serve/quantize.py; 'f32' =
    # checkpoint-native program)
    precision: str = "f32"
    batch_occupancy: float = 0.0  # real graphs / graph slots of its batch
    # which device of the set answered (ISSUE 5); -1 for cache hits — no
    # device computed them, and attributing them to device 0 would skew
    # client-side per-device accounting on a multi-device server
    device_id: int = 0
    # the request's journey (live observability plane): its trace id
    # (minted at admission or inherited from X-Request-Id), the flush it
    # was co-batched into, and the monotonic per-stage stamps
    # (queued/packed/dispatched/fetched/replied; SpanTracer.now_s
    # seconds — cache hits carry only queued/replied)
    trace_id: str = ""
    flush_id: str = ""
    stamps: dict = dataclasses.field(default_factory=dict)
    # which wire form computed it (ISSUE 11): 'raw' = the in-program
    # neighbor search built the graph from (positions, lattice,
    # species); 'featurized' = a host-built graph (client-featurized
    # arrays, the deferred pack-pool featurize, or the cap-overflow
    # fallback)
    wire: str = "featurized"
    # priority class served under (ISSUE 19; batcher.CLASSES) and
    # whether this request rode a higher-class flush's padding slack —
    # a backfilled reply is a normal reply (same program, same rung,
    # own trace id), the flag is accounting, never a quality downgrade
    klass: str = DEFAULT_CLASS
    backfilled: bool = False
    # single-flight miss coalescing (ISSUE 20): this answer was copied
    # from an identical-fingerprint request already in flight instead of
    # entering the batcher — same row the leader computed, own trace id
    coalesced: bool = False


class InferenceServer:
    """Micro-batching online inference over a warm shape set.

    ``state`` is a restored-for-inference TrainState; ``shape_set`` the
    precompiled ladder (shapes.plan_shape_set). ``predict_step`` defaults
    to ``jax.jit(make_predict_step())`` — inject a pre-jitted one to share
    its compile cache with an offline predict path. ``devices`` (a list
    of jax devices, or None for the backend-aware auto resolution) sets
    the dispatch fan-out: params replicate per device, flushes route
    least-loaded, every response records its ``device_id``.
    """

    def __init__(
        self,
        state,
        shape_set: ShapeSet,
        *,
        predict_step: Callable | None = None,
        version: str = "init",
        telemetry=None,
        max_queue: int = 256,
        max_wait_ms: float = 5.0,
        class_max_wait_ms: dict | None = None,
        backfill: bool = True,
        wfq_weights: dict | None = None,
        default_timeout_ms: float | None = 1000.0,
        cache_size: int = 1024,
        single_flight: bool = True,
        pack_workers: int = 1,
        devices=None,
        engine: str = "auto",
        precisions: Sequence[str] = ("f32",),
        model=None,
        featurizer: Callable | None = None,
        raw_precheck: bool = True,
        trace_ring: int = 65536,
        slo_layer: bool = True,
        slo_objectives=None,
        slo_rules=None,
        tsdb_interval_s: float = 2.0,
        clock: Callable[[], float] = time.monotonic,
        log_fn: Callable = print,
    ):
        import jax

        from cgnn_tpu.observe import Telemetry
        from cgnn_tpu.train.step import make_predict_step

        self.shape_set = shape_set
        # the device inventory + per-device accounting (serve/devices.py);
        # None = the backend-aware 'auto' resolution (all accelerator
        # devices; single device on CPU backends). How the devices are
        # DRIVEN is the engine's choice below.
        self.device_set = DeviceSet(devices)
        # execution engine over the device set (ISSUE 10):
        # - 'mesh' (the default with > 1 device): ONE Mesh+NamedSharding
        #   jitted program per (rung, form, tier) whose single dispatch
        #   covers every device — flushes split batch-axis across the
        #   mesh, params live as one replicated tree, no router and no
        #   per-device dispatch threads (parallel/executor.py);
        # - 'threads' (the ISSUE-5 layer, kept for the A/B): per-device
        #   param replicas, least-loaded router, one dispatch thread per
        #   device, programs x N executables.
        # With one device both engines degenerate to the single-device
        # dispatch loop; 'auto' resolves to 'mesh' on a real multi-device
        # set and leaves single-device servers on the classic path.
        if engine not in ("auto", "mesh", "threads"):
            raise ValueError(
                f"engine must be 'auto', 'mesh', or 'threads', "
                f"got {engine!r}"
            )
        if engine == "auto":
            engine = "mesh" if len(self.device_set) > 1 else "threads"
        self.mesh_exec = None
        if engine == "mesh" and len(self.device_set) > 1:
            from cgnn_tpu.parallel.executor import MeshExecutor

            self.mesh_exec = MeshExecutor(self.device_set.devices)
        # report what actually RUNS, not what was requested: a forced
        # 'mesh' on a 1-device set takes the single-device loop, and
        # stats claiming otherwise would let a dryrun assert an engine
        # that never dispatched
        if len(self.device_set) == 1:
            engine = "single"
        elif self.mesh_exec is None:
            engine = "threads"
        self.engine = engine
        # precision tiers (serve/quantize.py): the warmed set a request
        # picks from. 'f32' (the native program) is always present —
        # it is the default tier and the parity baseline. Tier states
        # are derived ONCE here (stable apply_fn identities) and
        # re-derived through the same specs on every hot swap.
        tiers = tuple(dict.fromkeys(("f32", *precisions)))
        tier_specs = None
        if tiers != ("f32",):
            from cgnn_tpu.serve.quantize import build_tier_specs

            if model is None:
                raise ValueError(
                    "precision tiers beyond 'f32' need the model module "
                    "(InferenceServer(model=...)) to derive bf16/int8 "
                    "programs"
                )
            tier_specs = build_tier_specs(model, tiers)
        self.precisions = tiers
        if self.mesh_exec is not None:
            # mesh engine: the store holds ONE mesh-replicated tree per
            # tier (get(0, tier)); a hot swap publishes one sharded
            # param tree under one version — no replica tuples
            self.param_store = ParamStore(
                state, version, tier_specs=tier_specs,
                placer=self.mesh_exec.place_params,
            )
        else:
            self.param_store = ParamStore(state, version,
                                          devices=self.device_set.devices,
                                          tier_specs=tier_specs)
        # wire-form structure handling (ISSUE 11): ``featurizer``
        # (RawStructure -> CrystalGraph, see ``structure_featurizer``)
        # powers the deferred pack-pool featurize and the cap-overflow
        # fallback; ``raw_precheck=False`` skips the host image-cap
        # pre-check at admission so tests/smoke can exercise the
        # IN-PROGRAM overflow flag end to end (production keeps it on —
        # the flag is the safety net, not the primary gate)
        self.featurizer = featurizer
        self._raw_precheck = bool(raw_precheck)
        # a compact shape set rebuilds GraphBatches INSIDE the compiled
        # program (expander); a raw shape set ADDITIONALLY carries the
        # in-program neighbor-search program (raw_expander); the same
        # jitted callable still accepts full-fidelity batches — the
        # fallback for non-compactable/non-raw requests (every form is
        # warmed, so none ever recompiles)
        predict_body = make_predict_step(shape_set.expander(),
                                         shape_set.raw_expander())
        self.predict_step = predict_step or jax.jit(predict_body)
        # the mesh engine's one-dispatch-covers-all-devices program
        # (parallel/executor.py): per (rung, form, tier) there is ONE
        # cache entry and ONE multi-device executable. An injected
        # predict_step is wrapped so the body stays shared.
        self.mesh_predict = None
        if self.mesh_exec is not None:
            self.mesh_predict = self.mesh_exec.shard_predict(
                predict_step or predict_body
            )
        # pack pipeline threads between the batcher and the dispatch
        # loop (data/pipeline.py): packing comes off the flush/dispatch
        # thread so the batcher coalesces the NEXT flush while the
        # current one packs and runs; 0 restores the in-line pack
        self._pack_workers = max(0, int(pack_workers))
        self.telemetry = telemetry or Telemetry.disabled()
        # ---- metrics-truth layer (ISSUE 16) ----
        # mergeable log-bucket histograms beside the rolling quantiles:
        # per-process quantiles are local color — they CANNOT be merged
        # across replicas — while integer bucket counts add associatively
        # and commutatively, so the `_hist` families are what the
        # router's /metrics/fleet pools into one fleet-wide truth. The
        # SLO burn-rate engine and the embedded time-series ring ride
        # the same switch (`slo_layer`). Pure host-side bookkeeping:
        # served numbers are bit-exact either way and nothing is staged
        # into jitted code.
        from cgnn_tpu.observe.hist import (
            LATENCY_MS_BOUNDS,
            OCCUPANCY_BOUNDS,
            QUEUE_WAIT_MS_BOUNDS,
            Histogram,
        )
        from cgnn_tpu.observe.slo import SLOEngine, SLOObjective
        from cgnn_tpu.observe.tsdb import TimeSeriesStore, TsdbCollector

        self.hists: dict[str, Histogram] = {}
        self.slo = None
        self.tsdb = None
        self._tsdb_collector = None
        if slo_layer:
            self.hists = {
                "serve_latency_ms_hist": Histogram(LATENCY_MS_BOUNDS),
                "serve_queue_wait_ms_hist": Histogram(QUEUE_WAIT_MS_BOUNDS),
                "serve_flush_occupancy_hist": Histogram(OCCUPANCY_BOUNDS),
            }
            objectives = (tuple(slo_objectives) if slo_objectives else (
                SLOObjective("availability", target=0.999, window_s=300.0),
                SLOObjective("latency", target=0.95,
                             latency_threshold_ms=1000.0, window_s=300.0),
            ))
            # clock matches the server's (injectable for tests); the
            # fire hook reads self.flightrec at fire time, so attaching
            # a recorder later still routes alerts into bundles
            self.slo = SLOEngine(
                objectives, rules=slo_rules, clock=clock,
                on_fire=self._on_slo_fire, on_resolve=self._on_slo_resolve,
            )
            self.tsdb = TimeSeriesStore()
        # priority-class continuous batching (ISSUE 19): per-class wait
        # budgets, padding-slack backfill, and WFQ tenant weights all
        # live in the batcher — the server's share is the per-class
        # metric families below and the slack accounting in dispatch
        self.batcher = MicroBatcher(
            shape_set, max_queue=max_queue, max_wait_ms=max_wait_ms,
            clock=clock,
            queue_wait_hist=self.hists.get("serve_queue_wait_ms_hist"),
            class_max_wait_ms=class_max_wait_ms, backfill=backfill,
            wfq_weights=wfq_weights,
        )
        # per-class latency histograms (labeled members of one family,
        # serve_class_latency_ms_hist{class="..."}) — created lazily
        # like the per-version family so single-class traffic pays one
        # dict miss, not three idle histograms
        self._class_hists: dict[str, object] = {}
        # backfill accounting (the serve_padding_fill_share feed):
        # graph-slot slack offered to backfill vs slots actually filled,
        # accumulated per flush under self._lock
        self._backfill_filled = 0
        self._backfill_slack = 0
        self.default_timeout = (
            None if default_timeout_ms is None else default_timeout_ms / 1000.0
        )
        self.cache = ResultCache(cache_size) if cache_size else None
        # single-flight miss coalescing (ISSUE 20): per-fingerprint
        # waiter table. The FIRST miss for a key enters the batcher as
        # the leader; concurrent identical-fingerprint misses attach as
        # followers and are resolved from the leader's future (success,
        # error, or expiry — the wait is bounded by the leader's own
        # deadline plus the follower's client timeout), so a trending-
        # structure stampede costs one forward pass, not a batch of
        # duplicates. Off (`single_flight=False`) is the A/B baseline:
        # duplicates then enter the batcher and are COUNTED
        # (cache_dup_misses) instead of coalesced.
        self._single_flight = bool(single_flight)
        self._sf_lock = racecheck.make_lock("serve.singleflight")
        self._inflight: dict[str, dict] = {}
        # per-(tier, form, outcome) cache-lookup histograms: labeled
        # members of one family (serve_cache_lookup_ms_hist{...}),
        # created lazily like the per-class family — bucket COUNTS give
        # fleet-mergeable per-(tier, form) hit ratios, values the probe
        # (hash + LRU) cost
        self._cache_hists: dict[tuple, object] = {}
        self._clock = clock
        self._log = log_fn
        self._worker: threading.Thread | None = None
        self._watcher: CheckpointWatcher | None = None
        self._draining = False
        # plain Lock normally; instrumented under CGNN_TPU_RACECHECK=1
        # (lock-order recording + held-by-current for watch_fields)
        self._lock = racecheck.make_lock("serve.server")
        # serving counters (mirrored into telemetry; kept locally so
        # stats() works with telemetry off)
        self.counts: dict[str, int] = {
            "requests": 0, "responses": 0, "cache_hits": 0,
            "cache_coalesced": 0, "cache_dup_misses": 0,
            "cache_fills": 0, "cache_fill_stale": 0,
            "reject_queue_full": 0, "reject_oversize": 0,
            "reject_timeout": 0, "reject_shutdown": 0,
            "reject_malformed": 0, "batches": 0,
        }
        self._latencies: list[float] = []  # recent, bounded (stats())
        self._occupancies: list[float] = []
        # per-rung edge-slot occupancy, last value per rung index (the
        # cap-calibration signal; exported via /metrics and stats())
        self._rung_edge_occ: dict[int, float] = {}
        self.warmed = False
        self._compiles_after_warm = 0
        # expected per-structure feature layout, learned from the warm
        # template: the admission gate that keeps a malformed request
        # from poisoning a whole co-batched flush (pack would raise) or
        # forcing a fresh trace (a recompile after warmup)
        self._feature_dims: tuple[int, int] | None = None
        # ---- live observability plane ----
        # trace ids are ALWAYS minted (cheap: prefix + counter); span
        # emission additionally needs telemetry.spans (plane on) OR the
        # always-on serving span ring below
        self._trace_prefix = os.urandom(3).hex()
        self._trace_seq = itertools.count(1)
        # the cross-process trace ring (ISSUE 15): a bounded SpanTracer
        # that serving spans land in REGARDLESS of telemetry level, so
        # `GET /trace` and the flight recorder can join this process
        # into a fleet trace mid-incident. Host-side ring appends only
        # (predictions bit-exact either way); 0 disables (the A/B
        # baseline, PERF.md §18)
        from cgnn_tpu.observe.spans import SpanTracer

        self.tracer = (SpanTracer(
            process_name=f"serve-{os.getpid()}",
            max_events=int(trace_ring)) if trace_ring else None)
        self._spans_on = (self.telemetry.spans is not None
                          or self.tracer is not None)
        # incident flight recorder (observe/flightrec.py), attached by
        # the entrypoint — None keeps every hook below a no-op
        self.flightrec = None
        # label journal (continual/journal.py, ISSUE 18), attached by
        # the entrypoint — None keeps the serving path journal-free
        self.journal = None
        # per-version latency histograms (ISSUE 18): bounded map of the
        # most recent param versions, rendered as
        # serve_version_latency_ms_hist{param_version="..."} so
        # /metrics/fleet can merge shadow-vs-live latency per version.
        # Rides the slo_layer switch like the other histogram families.
        self._version_hists: "OrderedDict[str, object]" = OrderedDict()
        self._version_hists_cap = 8
        from cgnn_tpu.observe.export import MetricsRegistry, RollingSeries

        # rolling (time-windowed) twins of the run-lifetime SLO series:
        # these answer "what is the p99 NOW", independent of telemetry
        # level, and feed /stats["rolling"] + the /metrics scrape
        self.rolling_window_s = 60.0
        self._lat_rolling = RollingSeries(window_s=self.rolling_window_s)
        self._occ_rolling = RollingSeries(window_s=self.rolling_window_s)
        self.registry = MetricsRegistry(window_s=self.rolling_window_s)
        self.registry.attach_telemetry(self.telemetry)
        self.registry.add_provider("serve", self._registry_snapshot)
        if self.tsdb is not None:
            # one heartbeat for the whole quantitative plane: registry
            # snapshots -> tsdb rings, and the SLO state machines advance
            # on the same tick (alerts resolve even with zero traffic)
            self._tsdb_collector = TsdbCollector(
                self.registry, self.tsdb, interval_s=tsdb_interval_s,
            )
            self._tsdb_collector.add_on_tick(self._slo_tick)
        # on-demand device profiling (observe/profile.py); wired by
        # enable_profiling — None until an output dir is chosen
        self.profiler = None
        # racecheck shared-field tripwire (no-op when the gate is off):
        # every field mutated under self._lock is registered, so a
        # future stats path touching one without the lock is a recorded
        # violation at runtime, not a 3am scrape mystery (the PR-6 bug)
        racecheck.watch_fields(self, self._lock, (
            "counts", "_latencies", "_occupancies", "_draining",
            "_compiles_after_warm", "_rung_edge_occ",
            "_backfill_filled", "_backfill_slack",
        ))
        racecheck.watch_fields(self, self._sf_lock, ("_inflight",))

    # ---- warmup ----

    def warm(self, template: CrystalGraph) -> int:
        """Compile every shape in the set ON EVERY DEVICE; returns the
        program count (traced forms, independent of the device count).

        ``template`` is any admissible structure (it provides feature
        dimensionality); each rung is packed with one copy and executed
        once per device. A compact set warms BOTH staging forms per rung
        — the compact fast path and the full-fidelity fallback a flush
        holding a non-compactable request takes — and every PRECISION
        TIER warms per (rung, form): the post-warmup compile count is
        pinned no matter how traffic mixes, which tier a request picks,
        OR which device a flush lands on: ``len(shape_set) * forms *
        len(precisions)`` traced programs, each built into one
        executable per device here and NEVER again (devices.py module
        docstring). Dispatches run under ``telemetry.warmup()`` so
        compile executions never pollute serving counters."""
        import jax

        self._feature_dims = (template.atom_fea.shape[1],
                              template.edge_fea.shape[1])
        raw_tpl = (self.shape_set.raw.template()
                   if self.shape_set.raw is not None else None)
        n0 = self._jit_cache_size()
        programs = 0
        with self.telemetry.warmup():
            for shape in self.shape_set:
                # pack once per form on the host; each device's replica
                # pulls the same staged batch through its own executable
                forms = [self.shape_set.pack([template], shape=shape)]
                if self.shape_set.compact is not None:
                    forms.append(
                        self.shape_set.pack_full([template], shape=shape))
                if raw_tpl is not None:
                    # the raw-wire program (ISSUE 11): in-program
                    # neighbor search + featurize, one per rung
                    forms.append(
                        self.shape_set.pack_raw([raw_tpl], shape=shape))
                if self.mesh_exec is not None:
                    # mesh engine: the warmed program IS the stacked
                    # sharded one — one dispatch covers every device, so
                    # the compile count is programs, never programs x N
                    n = len(self.mesh_exec)
                    staged_forms = [
                        self.mesh_exec.stage(self.mesh_exec.stack([b] * n))
                        for b in forms
                    ]
                    for tier in self.precisions:
                        state, _ = self.param_store.get(0, tier)
                        for staged in staged_forms:
                            jax.block_until_ready(
                                self.mesh_predict(state, staged))
                        programs += len(staged_forms)
                    continue
                for tier in self.precisions:
                    for i in range(len(self.device_set)):
                        state, _ = self.param_store.get(i, tier)
                        for b in forms:
                            # block_until_ready over the output pytree:
                            # the raw program returns a (preds,
                            # overflow, n_edges) tuple
                            jax.block_until_ready(
                                self.predict_step(state, b))
                    programs += len(forms)
        self.warmed = True
        compiled = (self._jit_cache_size() or 0) - (n0 or 0)
        self._log(
            f"serve: warmed {len(self.shape_set)} shapes / {programs} "
            f"programs on {len(self.device_set)} device(s) "
            f"[{self.engine} engine] / "
            f"{len(self.precisions)} precision tier(s) "
            f"({compiled} fresh compiles"
            f"{', compact-staged' if self.shape_set.compact else ''}"
            f"{', raw-wire' if self.shape_set.raw is not None else ''})"
        )
        return compiled

    def _jit_cache_size(self) -> int | None:
        """The jit cache-miss counter (None when the fn isn't a jax.jit).

        Under the mesh engine the dispatched program is
        ``mesh_predict`` — its cache is the one whose growth after
        warmup would be a recompile."""
        fn = self.mesh_predict if self.mesh_exec is not None \
            else self.predict_step
        try:
            return int(fn._cache_size())
        except AttributeError:
            return None

    # ---- live observability plane ----

    def _mint_trace(self, requested: str | None = None) -> str:
        """A request's trace id: the (sanitized) inbound X-Request-Id
        when the client sent one, a fresh ``req-<prefix>-<seq>`` here
        otherwise. Always minted — the id is how an operator joins an
        HTTP response to its span chain and flush."""
        if requested:
            rid = "".join(c if c.isprintable() and c not in '\\"'
                          else "_" for c in str(requested).strip())
            if rid:
                return rid[:128]
        return f"req-{self._trace_prefix}-{next(self._trace_seq):06x}"

    @staticmethod
    def _stamp() -> float:
        """The per-stage stamp clock (SpanTracer.now_s: perf_counter
        seconds) — deliberately NOT the injectable request clock, so
        stamps line up with the Chrome-trace span timeline even under a
        fake test clock."""
        return time.perf_counter()

    def _span(self, name: str, start_s: float, end_s: float,
              **args) -> None:
        """Emit one retro-stamped hop span to every live sink: the
        telemetry tracer (trace.json at close) and/or the always-on
        serving ring (`GET /trace` + flight-recorder bundles)."""
        spans = self.telemetry.spans
        if spans is not None:
            spans.complete(name, start_s, end_s, **args)
        if self.tracer is not None:
            self.tracer.complete(name, start_s, end_s, **args)

    def _note_request(self, **record) -> None:
        """Feed the flight recorder's recent-request ring (no-op until
        one is attached; one lock + deque append when it is)."""
        fr = self.flightrec
        if fr is not None:
            fr.note_request(record)

    def note_http_status(self, status: int) -> None:
        """HTTP front-end hook: response statuses feed the recorder's
        5xx burst trigger."""
        fr = self.flightrec
        if fr is not None:
            fr.note_status(int(status))

    def attach_flight_recorder(self, recorder) -> None:
        """Wire an observe.flightrec.FlightRecorder into the serving
        path: every finished request lands in its ring, HTTP statuses
        feed its burst trigger (serve/http.py calls note_http_status)."""
        self.flightrec = recorder

    # ---- metrics-truth feeds (ISSUE 16) ----

    def _observe_served(self, latency_ms: float,
                        version: str | None = None,
                        klass: str | None = None) -> None:
        """One answered request into the mergeable latency histogram +
        the SLO good/bad ledger. Cache hits count: a client got an
        answer either way, and the fleet-merged histogram must describe
        the same population clients measure. ``version`` additionally
        lands the sample in that param version's labeled family (ISSUE
        18) so per-version latency survives the fleet merge; ``klass``
        lands it in the priority class's labeled family (ISSUE 19) and
        routes it to class-scoped SLO objectives."""
        h = self.hists.get("serve_latency_ms_hist")
        if h is not None:
            h.observe(latency_ms)
            if version is not None:
                with self._lock:
                    vh = self._version_hists.get(version)
                    if vh is None:
                        from cgnn_tpu.observe.hist import (
                            LATENCY_MS_BOUNDS,
                            Histogram,
                        )

                        vh = self._version_hists[version] = Histogram(
                            LATENCY_MS_BOUNDS)
                        while len(self._version_hists) > \
                                self._version_hists_cap:
                            self._version_hists.popitem(last=False)
                vh.observe(latency_ms)
            if klass is not None:
                ch = self._class_hists.get(klass)
                if ch is None:
                    from cgnn_tpu.observe.hist import (
                        LATENCY_MS_BOUNDS,
                        Histogram,
                    )

                    with self._lock:
                        ch = self._class_hists.setdefault(
                            klass, Histogram(LATENCY_MS_BOUNDS))
                ch.observe(latency_ms)
        if self.slo is not None:
            self.slo.record(True, latency_ms, klass=klass)

    def _observe_cache_lookup(self, tier: str, form: str, outcome: str,
                              lookup_ms: float) -> None:
        """One cache probe into its (tier, form, outcome)-labeled
        histogram (ISSUE 20). The bucket COUNTS are the point: they
        merge across replicas like any histogram family, so
        /metrics/fleet derives fleet-wide per-(tier, form) hit ratios
        from hit-count / (hit-count + miss-count); the observed values
        are the probe (hash + LRU) cost in ms."""
        if not self.hists:
            return
        key = (str(tier), str(form), str(outcome))
        h = self._cache_hists.get(key)
        if h is None:
            from cgnn_tpu.observe.hist import LATENCY_MS_BOUNDS, Histogram

            with self._lock:
                h = self._cache_hists.setdefault(
                    key, Histogram(LATENCY_MS_BOUNDS))
        h.observe(lookup_ms)

    def _singleflight_done(self, fp: str, fut) -> None:
        """Leader completion: drain the waiter-table entry for ``fp``
        and answer every coalesced follower from the leader's outcome
        (runs on whichever thread resolved the leader's future)."""
        with self._sf_lock:
            entry = self._inflight.pop(fp, None)
        if not entry:
            return
        followers = entry["followers"]
        if not followers:
            return
        try:
            res = fut.result(0)
            err = None
        except BaseException as e:  # noqa: BLE001 — relayed verbatim
            res, err = None, e
        for w in followers:
            self._resolve_coalesced(w, res, err)

    def _resolve_coalesced(self, w: dict, res, err) -> None:
        """Answer one coalesced follower: the leader's row under the
        follower's own trace id / latency / class accounting (a
        coalesced reply is a served response — it must feed the same
        latency distributions clients measure)."""
        fut = w["future"]
        if err is not None:
            self._count("cache_coalesced_errors")
            fut.set_error(err)
            return
        replied = self._stamp()
        latency_ms = (self._clock() - w["t0"]) * 1e3
        fut.set_result(ServeResult(
            prediction=res.prediction, param_version=res.param_version,
            latency_ms=latency_ms, cached=res.cached,
            device_id=res.device_id, trace_id=w["trace_id"],
            precision=w["tier"],
            stamps={"queued": w["queued"], "replied": replied},
            wire=res.wire, klass=w["klass"], coalesced=True,
        ))
        self._record_latency(latency_ms)
        self._lat_rolling.add(latency_ms)
        self._observe_served(latency_ms, version=res.param_version,
                             klass=w["klass"])
        self._count("responses")
        self._count(f"responses_class_{w['klass']}")
        self.telemetry.observe_value("serve_latency_ms", latency_ms)
        if self._spans_on:
            args = {"trace_id": w["trace_id"], "coalesced": True}
            if w["trace_parent"]:
                args["parent"] = w["trace_parent"]
            self._span("serve.request", w["queued"], replied, **args)
        self._note_request(
            trace_id=w["trace_id"], status="ok", cached=bool(res.cached),
            param_version=res.param_version, precision=w["tier"],
            wire=res.wire, latency_ms=latency_ms)
        self._journal_served(
            graph=w["graph"], fingerprint=w["fingerprint"],
            trace_id=w["trace_id"], prediction=res.prediction,
            version=res.param_version, wire=res.wire)

    def cache_fill(self, fingerprint: str, prediction, param_version: str,
                   precision: str | None = None,
                   wire: str = "featurized") -> bool:
        """Peer-fill receiver (ISSUE 20): the fleet router replays a row
        a NON-owner replica just computed into this (owner) replica's
        cache, so the next hot-key request hits here. Purely an
        optimization — the row is version-checked against the LIVE
        param version at fill time AND revalidated at hit time
        (serve/cache.py), so a stale fill can never be served. The
        fingerprint arrives in edge form ('raw:'-prefixed or bare) and
        is qualified here with the same fs:/tier rules as submit().
        Returns True when the row was cached."""
        if self.cache is None or not fingerprint:
            return False
        fp = str(fingerprint)
        if fp.startswith("raw:") and wire != "raw":
            fp = "fs:" + fp[len("raw:"):]
        tier = precision or "f32"
        if tier != "f32":
            fp = f"{tier}:{fp}"
        version = str(param_version)
        if version != self.param_store.version:
            self._count("cache_fill_stale")
            return False
        row = np.asarray(prediction, np.float32)
        self.cache.put(fp, (row, version))
        self._count("cache_fills")
        return True

    def attach_journal(self, journal) -> None:
        """Wire a continual/journal.LabelJournal into the answer path:
        every served response appends a replayable record the late
        ``POST /label`` joins ground truth onto (ISSUE 18)."""
        self.journal = journal

    def _journal_served(self, *, graph, fingerprint, trace_id, prediction,
                        version, wire) -> None:
        """One answered request into the label journal (no-op until one
        is attached). The payload is the request re-encoded in its wire
        form, so the continual trainer replays EXACTLY what was served
        through the same graph_from_json path the HTTP handler uses."""
        j = self.journal
        if j is None:
            return
        try:
            pred = float(np.asarray(prediction).reshape(-1)[0])
        except (TypeError, ValueError):
            pred = None
        payload = None
        if wire == "featurized" and isinstance(graph, CrystalGraph):
            payload = {"graph": {
                "atom_fea": np.asarray(graph.atom_fea).tolist(),
                "edge_fea": np.asarray(graph.edge_fea).tolist(),
                "centers": np.asarray(graph.centers).tolist(),
                "neighbors": np.asarray(graph.neighbors).tolist(),
                "id": graph.cif_id,
            }}
        elif isinstance(graph, RawStructure):
            payload = {"structure": {
                "frac_coords": np.asarray(graph.frac_coords).tolist(),
                "lattice": np.asarray(graph.lattice).tolist(),
                "numbers": np.asarray(graph.numbers).tolist(),
                "id": graph.cif_id,
            }}
        j.note_served(trace_id=trace_id, payload=payload, prediction=pred,
                      param_version=version, fingerprint=fingerprint,
                      ts=time.time())

    def _record_slo_bad(self, klass: str | None = None) -> None:
        """One failed request (dispatch failure / deadline expiry) into
        the error-budget ledger. Admission rejections (queue-full,
        oversize, malformed) are NOT budget burn — they are the server
        protecting itself or the client's fault (the 429/400 class)."""
        if self.slo is not None:
            self.slo.record(False, 0.0, klass=klass)

    def _slo_tick(self) -> None:
        """Collector heartbeat: advance the alert state machines so
        pending->firing (for_s held) and firing->resolved happen on the
        clock, not only when traffic arrives."""
        if self.slo is not None:
            self.slo.evaluate()

    def _on_slo_fire(self, tr: dict) -> None:
        """Burn-rate alert FIRING -> incident capture: the reason names
        the objective (``slo_burn_<objective>``) so the flight-recorder
        bundle manifest identifies the alert — the fleet_smoke pin."""
        self._log(
            f"serve: SLO ALERT firing: objective={tr['objective']} "
            f"rule={tr['rule']} burn_fast={tr['burn_fast']:.2f} "
            f"burn_slow={tr['burn_slow']:.2f} (factor {tr['factor']:g})"
        )
        fr = self.flightrec
        if fr is not None:
            fr.trigger(
                f"slo_burn_{tr['objective']}",
                detail=(f"rule={tr['rule']} "
                        f"burn_fast={tr['burn_fast']:.3f} "
                        f"burn_slow={tr['burn_slow']:.3f} "
                        f"factor={tr['factor']:g}"),
            )

    def _on_slo_resolve(self, tr: dict) -> None:
        self._log(
            f"serve: SLO alert resolved: objective={tr['objective']} "
            f"rule={tr['rule']}"
        )

    def trace_window(self, since_s: float | None = None) -> dict | None:
        """The `GET /trace` body: this process's span ring as a
        joinable window (observe/trace_join.py), or None when neither
        the serving ring nor telemetry spans exist."""
        tracer = self.tracer or self.telemetry.spans
        if tracer is None:
            return None
        w = tracer.window(since_s=since_s)
        w["role"] = "replica"
        return w

    def enable_profiling(self, out_dir: str, *,
                         default_duration_s: float = 1.0,
                         max_duration_s: float = 10.0):
        """Wire on-demand device profiling (POST /profile + SIGUSR2)
        into ``out_dir``; returns the ProfileCapture (gated: concurrent
        captures are rejected, never stacked)."""
        from cgnn_tpu.observe.profile import ProfileCapture

        self.profiler = ProfileCapture(
            out_dir, spans=self.telemetry.spans,
            default_duration_s=default_duration_s,
            max_duration_s=max_duration_s, log_fn=self._log,
        )
        return self.profiler

    def _registry_snapshot(self) -> dict:
        """The serve provider for ``self.registry``: request counters,
        live queue/in-flight gauges, and the rolling-window SLO series —
        all readable with telemetry OFF (the registry's telemetry source
        contributes the rest when the plane is on). The pipeline_* and
        device* names are emitted from here too so every scrape carries
        the three metric families CI checks, whatever the config."""
        with self._lock:
            # copy under the lock: _count() inserts NEW keys concurrently
            # and a mid-iteration resize would raise, costing the scrape
            # the whole serve provider; _draining/_compiles_after_warm
            # are mutated under this lock too (graftcheck GC-LOCKSHARE)
            counts = dict(self.counts)
            draining = self._draining
            compiles_after_warm = self._compiles_after_warm
            rung_occ = dict(self._rung_edge_occ)
            backfill_filled = self._backfill_filled
            backfill_slack = self._backfill_slack
        counters = {f"serve_{k}": float(v) for k, v in counts.items()}
        tcounters = self.telemetry.counters()
        for name in ("pipeline_jobs", "pipeline_pack_s", "pipeline_wait_s"):
            counters[name] = float(tcounters.get(name, 0.0))
        # the ISSUE-11 overflow counter under its own (unprefixed) name:
        # /metrics renders it as ingest_cap_overflow_total, the name the
        # loadgen's zero-overflow assertion scrapes
        counters["ingest_cap_overflow"] = float(
            counts.get("ingest_cap_overflow", 0))
        gauges = {
            "serve_queue_depth": float(self.batcher.depth),
            "serve_draining": float(draining),
            "serve_warmed": float(self.warmed),
            "serve_recompiles_after_warm": float(compiles_after_warm),
            "serve_rolling_window_s": self.rolling_window_s,
            "pipeline_pack_workers": float(self._pack_workers),
            "device_count": float(len(self.device_set)),
            "serve_engine_mesh": float(self.mesh_exec is not None),
            "ingest_raw_wire": float(self.shape_set.raw is not None),
        }
        for rung, occ in sorted(rung_occ.items()):
            gauges[f"ingest_rung{rung}_edge_occupancy"] = float(occ)
        # padding-slack backfill (ISSUE 19): what share of the graph
        # slots higher-class flushes would have PADDED was instead
        # filled with lower-class goodput. 0 with backfill off or under
        # pure single-class load — the bench A/B's headline gauge.
        gauges["serve_backfill_enabled"] = float(self.batcher.backfill)
        gauges["serve_padding_fill_share"] = (
            backfill_filled / backfill_slack if backfill_slack else 0.0)
        counters["serve_backfill_filled_slots"] = float(backfill_filled)
        counters["serve_backfill_slack_slots"] = float(backfill_slack)
        # result-cache truth (ISSUE 20): ONE consistent snapshot under
        # the cache's own lock — scraping the bare hits/misses
        # attributes could pair a pre-increment hits with a
        # post-increment misses (a hit ratio that never existed)
        if self.cache is not None:
            hits, misses, size, capacity = self.cache.snapshot()
            counters["serve_cache_lookup_hits"] = float(hits)
            counters["serve_cache_lookup_misses"] = float(misses)
            gauges["serve_cache_size"] = float(size)
            gauges["serve_cache_capacity"] = float(capacity)
        gauges["serve_single_flight"] = float(self._single_flight)
        from cgnn_tpu.observe.gauges import cache_gauges

        gauges.update(cache_gauges(counters, gauges))
        # the cross-process observability layer's own health (ISSUE 15)
        gauges["observe_trace_ring"] = float(self.tracer is not None)
        if self.tracer is not None:
            gauges["observe_trace_dropped"] = float(self.tracer.dropped)
        fr = self.flightrec
        if fr is not None:
            frs = fr.stats()
            gauges["flightrec_bundles"] = float(frs["bundles"])
            gauges["flightrec_suppressed"] = float(frs["suppressed"])
        for i, depth in enumerate(self.device_set.inflight_depths()):
            gauges[f"device{i}_inflight"] = float(depth)
        if self.profiler is not None:
            gauges["profile_captures"] = float(self.profiler.captures)
            gauges["profile_busy"] = float(self.profiler.busy)
        series = {}
        for name, roll in (("serve_latency_ms", self._lat_rolling),
                           ("serve_batch_occupancy", self._occ_rolling)):
            q = roll.quantiles()
            if q:
                series[name] = q
        out = {"counters": counters, "gauges": gauges, "series": series}
        # the metrics-truth layer (ISSUE 16): mergeable histogram
        # snapshots under distinct `_hist` names — the summary families
        # above keep their names (one TYPE per family), the histogram
        # families are what /metrics/fleet pools across replicas
        if self.hists:
            out["histograms"] = {
                name: h.snapshot() for name, h in self.hists.items()
            }
            with self._lock:
                vhists = list(self._version_hists.items())
            if vhists:
                # per-param-version latency (ISSUE 18): labeled members
                # of one family, keyed name{param_version="..."} — the
                # canary gate's scrapeable shadow-vs-live comparison
                from cgnn_tpu.observe.hist import format_labels

                for ver, vh in vhists:
                    key = ("serve_version_latency_ms_hist"
                           + format_labels({"param_version": str(ver)}))
                    out["histograms"][key] = vh.snapshot()
            with self._lock:
                chists = list(self._class_hists.items())
            if chists:
                # per-priority-class latency (ISSUE 19): labeled members
                # of one family, keyed name{class="..."} — what lets the
                # autoscaler and fleet SLO views see classes instead of
                # one aggregate, and they merge across replicas like any
                # histogram family
                from cgnn_tpu.observe.hist import format_labels

                for kl, chh in sorted(chists):
                    key = ("serve_class_latency_ms_hist"
                           + format_labels({"class": str(kl)}))
                    out["histograms"][key] = chh.snapshot()
            with self._lock:
                cache_hists = list(self._cache_hists.items())
            if cache_hists:
                # per-(tier, form) cache hit ratio (ISSUE 20): labeled
                # members of one family keyed
                # name{tier=...,form=...,outcome=...} — the bucket
                # counts merge across replicas, so /metrics/fleet can
                # state the FLEET-wide hit ratio per tier and wire form
                from cgnn_tpu.observe.hist import format_labels

                for (tier, frm, outcome), hh in sorted(cache_hists):
                    key = ("serve_cache_lookup_ms_hist" + format_labels(
                        {"tier": tier, "form": frm, "outcome": outcome}))
                    out["histograms"][key] = hh.snapshot()
        if self.slo is not None:
            gauges.update(self.slo.gauges())
        if self.tsdb is not None:
            ts = self.tsdb.stats()
            gauges["tsdb_series"] = float(ts["series"])
            gauges["tsdb_points"] = float(ts["points"])
            gauges["tsdb_dropped_series"] = float(ts["dropped_series"])
        return out

    # ---- lifecycle ----

    def start(self) -> "InferenceServer":
        # the deadlock watchdog (racecheck-gated): any heartbeating
        # serve/pack/watcher thread silent past the bound triggers a
        # named faulthandler dump of every stack
        racecheck.start_watchdog(bound_s=30.0, log_fn=self._log)
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._serve_loop, daemon=True, name="cgnn-serve"
            )
            self._worker.start()
        if self._watcher is not None:
            self._watcher.start()
        if self._tsdb_collector is not None:
            self._tsdb_collector.start()
        return self

    def attach_watcher(self, manager, poll_interval_s: float = 2.0,
                       log_fn: Callable | None = None) -> CheckpointWatcher:
        """Wire hot checkpoint reload (reload.py) to ``manager``'s dir.

        The cache clears on every swap — cached rows are only valid for
        the version that computed them."""
        template, _ = self.param_store.get()
        self._watcher = CheckpointWatcher(
            manager, self.param_store, template,
            poll_interval_s=poll_interval_s, telemetry=self.telemetry,
            on_swap=lambda _v: self.cache.clear() if self.cache else None,
            log_fn=log_fn or self._log,
        )
        if self._worker is not None and self._worker.is_alive():
            self._watcher.start()
        return self._watcher

    @property
    def watcher(self) -> CheckpointWatcher | None:
        """The attached reload watcher (None before attach_watcher) —
        the POST /reload-control pin/gate endpoint drives it."""
        return self._watcher

    def install_signal_handlers(self):
        """SIGTERM/SIGINT -> graceful drain (resilience.preempt plumbing).

        Returns the PreemptionHandler; the caller's main thread decides
        what to do after the drain (serve.py shuts the HTTP listener and
        exits 0)."""
        from cgnn_tpu.resilience.preempt import PreemptionHandler

        handler = PreemptionHandler(
            log_fn=self._log,
            action="draining the serving queue (in-flight requests will "
                   "be answered; new ones rejected 503)",
        )
        handler.add_callback(self.begin_drain)
        return handler.install()

    def begin_drain(self) -> None:
        """Stop admitting; already-queued requests still get answers.
        Quick and thread-safe (called from signal handlers)."""
        with self._lock:
            if self._draining:
                return
            self._draining = True
        self.batcher.close()
        self._log("serve: draining (no new requests; flushing queue)")

    def drain(self, timeout_s: float = 30.0) -> bool:
        """begin_drain + wait for the worker to finish the queue.
        True when the drain completed within the timeout."""
        self.begin_drain()
        if self._watcher is not None:
            self._watcher.stop()
        if self._tsdb_collector is not None:
            self._tsdb_collector.stop()
        if self._worker is not None:
            self._worker.join(timeout=timeout_s)
            done = not self._worker.is_alive()
        else:
            # never started: flush synchronously so accepted work still
            # gets answers
            self._serve_loop()
            done = True
        if self.profiler is not None:
            # exiting while jax.profiler holds an active trace segfaults
            # in the backend; a drain waits out an in-flight capture
            # (bounded: captures are capped at max_duration_s)
            self.profiler.wait_idle()
        self.telemetry.set_gauge("serve_drained_clean", float(done))
        # per-device occupancy/dispatch gauges -> run_summary (the
        # observe.gauges.device_gauges rollup reads these names)
        self.device_set.flush_gauges(self.telemetry)
        return done

    # ---- request path ----

    def _check_wellformed(self, graph: CrystalGraph) -> None:
        """Admission-time structural validation: a malformed graph must
        fail ALONE (400) — packed, it would either blow up pack_graphs
        (failing every innocent co-batched request) or, flushed alone,
        trace a fresh program shape (a recompile after warmup)."""
        problems = []
        if self._feature_dims is not None:
            nd, ed = self._feature_dims
            if np.ndim(graph.atom_fea) != 2 or graph.atom_fea.shape[1] != nd:
                problems.append(
                    f"atom_fea must be [N, {nd}], got "
                    f"{np.shape(graph.atom_fea)}"
                )
            if np.ndim(graph.edge_fea) != 2 or graph.edge_fea.shape[1] != ed:
                problems.append(
                    f"edge_fea must be [E, {ed}], got "
                    f"{np.shape(graph.edge_fea)}"
                )
        n, e = graph.num_nodes, graph.num_edges
        if n < 1:
            problems.append("structure has no atoms")
        if len(graph.edge_fea) != e:
            problems.append(
                f"{e} edges but {len(graph.edge_fea)} edge feature rows"
            )
        for name in ("centers", "neighbors"):
            idx = np.asarray(getattr(graph, name))
            if len(idx) and (idx.min() < 0 or idx.max() >= n):
                problems.append(
                    f"{name} indices outside [0, {n}) "
                    f"(min {idx.min()}, max {idx.max()})"
                )
        if problems:
            raise ServeRejection(MALFORMED, "; ".join(problems))

    def _check_wellformed_raw(self, rs: RawStructure) -> None:
        """Admission-time validation of a wire-form structure: shape,
        species range, finite geometry, invertible lattice — everything
        the in-program search (or the fallback featurizer) would choke
        on must fail ALONE at the door (400)."""
        from cgnn_tpu.data.elements import MAX_Z

        problems = []
        if rs.num_nodes < 1:
            problems.append("structure has no atoms")
        z = rs.numbers
        if len(z) and (z.min() < 1 or z.max() > MAX_Z):
            problems.append(
                f"species outside the element table [1, {MAX_Z}] "
                f"(min {z.min()}, max {z.max()})"
            )
        if not (np.isfinite(rs.frac_coords).all()
                and np.isfinite(rs.lattice).all()):
            problems.append("non-finite coordinates or lattice")
        elif abs(float(np.linalg.det(rs.lattice))) < 1e-6:
            problems.append("degenerate lattice (volume ~ 0)")
        if problems:
            raise ServeRejection(MALFORMED, "; ".join(problems))

    def _admit_form(self, rs: RawStructure) -> str:
        """'raw' when the wire-form structure fits the raw rung caps
        (host f64 pre-check — or just the structural atom-slot cap with
        ``raw_precheck=False``, leaving the image decision to the
        in-program flag), else 'feat' (deferred pack-pool featurize)."""
        spec = self.shape_set.raw
        if spec is not None:
            if self._raw_precheck:
                if spec.admits(rs):
                    return "raw"
            elif 1 <= rs.num_nodes <= spec.snode_cap:
                return "raw"
        if self.featurizer is None:
            raise ServeRejection(
                MALFORMED,
                "wire-form structure cannot be served: "
                + (self.shape_set.raw.oversize_detail(rs)
                   if self.shape_set.raw is not None
                   else "raw wire is not enabled")
                + " and no fallback featurizer is configured",
            )
        return "feat"

    def submit(self, graph,
               timeout_ms: float | None = None,
               trace_id: str | None = None,
               precision: str | None = None,
               trace_parent: str | None = None,
               klass: str | None = None,
               tenant: str | None = None,
               fingerprint: str | None = None) -> RequestFuture:
        """Admit one structure; returns its future (raises ServeRejection
        on malformed / queue-full / oversize / draining). ``graph`` is a
        featurized ``CrystalGraph`` OR a wire-form ``RawStructure``
        (ISSUE 11): wire-form structures that fit the raw rung caps are
        staged raw (the in-program neighbor search builds the graph);
        the rest are featurized ON THE PACK POOL at pack time — never
        on this thread, so one large structure cannot head-of-line-block
        admission. ``trace_id`` carries an inbound X-Request-Id; absent,
        one is minted here — admission is where a request's journey
        starts. ``trace_parent`` carries an inbound X-Trace-Parent span
        id (observe/tracectx.py): the upstream attempt span this
        request's serve.request span nests under in a joined fleet
        trace. ``precision`` picks the serving tier (None = 'f32'); a
        tier the server did not warm is rejected AT ADMISSION —
        flushing it would trace a fresh program (a recompile after
        warmup). ``klass`` picks the priority class (ISSUE 19;
        batcher.CLASSES, default 'interactive') and ``tenant`` the WFQ
        fair-queuing tenant — an unknown class is MALFORMED at
        admission, because silently defaulting it would change the
        request's scheduling contract. ``fingerprint`` carries an
        inbound edge-computed content hash (X-Fingerprint, ISSUE 20):
        the fleet router hashes the wire arrays ONCE per request, this
        replica only qualifies the key (fs:/tier prefixes) instead of
        re-hashing — a hint whose shape mismatches the admitted form is
        ignored and the key re-derived locally."""
        now = self._clock()
        queued = self._stamp()
        tid = self._mint_trace(trace_id)
        tier = precision or "f32"
        kl = klass or DEFAULT_CLASS
        is_raw_wire = isinstance(graph, RawStructure)
        form = "feat"
        self._count("requests")
        try:
            if tier not in self.precisions:
                raise ServeRejection(
                    MALFORMED,
                    f"precision {tier!r} not in this server's warmed "
                    f"tiers {list(self.precisions)}",
                )
            if kl not in CLASSES:
                raise ServeRejection(
                    MALFORMED,
                    f"unknown priority class {kl!r} "
                    f"(have: {list(CLASSES)})",
                )
            if is_raw_wire:
                self._check_wellformed_raw(graph)
                form = self._admit_form(graph)
                if form == "feat" and self.shape_set.dense_m is None:
                    # COO layout: a flush's edge budget needs the TRUE
                    # edge count, which only featurization knows — the
                    # legacy inline path (the dense layout, where slot
                    # ownership is structural, defers to the pack pool)
                    try:
                        graph = self.featurizer(graph)
                    except Exception as e:  # noqa: BLE001 — reject alone
                        raise ServeRejection(
                            MALFORMED,
                            f"structure featurization failed: {e}",
                        ) from None
                    is_raw_wire = False
                    self._check_wellformed(graph)
            else:
                self._check_wellformed(graph)
        except ServeRejection as e:
            self._count(f"reject_{e.reason}")
            raise
        lookup_t0 = self._clock()
        if self.cache is None:
            fp = None
        else:
            fp = None
            if fingerprint:
                # edge-computed hash (ISSUE 20): trusted only when its
                # shape matches the admitted form — raw-wire requests
                # carry a 'raw:'-prefixed hash, featurized ones a bare
                # hex digest. A mismatched hint (e.g. a raw hash after
                # the COO inline featurize above) falls back to local
                # hashing rather than alias the two keyspaces.
                cand = str(fingerprint)
                if is_raw_wire and cand.startswith("raw:"):
                    fp = cand
                elif not is_raw_wire and ":" not in cand:
                    fp = cand
            if fp is None:
                if is_raw_wire:
                    # content hash of the wire encoding (see below for
                    # the form qualification)
                    fp = raw_fingerprint(graph)
                else:
                    fp = structure_fingerprint(graph)
            if is_raw_wire and form != "raw":
                # form-qualified so a row computed by the raw program
                # ('raw:...') never answers a host-featurized request
                # ('fs:...') — the two programs agree only to f32
                # roundoff, and a cached row is (params, structure,
                # PROGRAM)-determined (serve/cache.py)
                fp = "fs:" + fp[len("raw:"):]
        if fp is not None and tier != "f32":
            # cached rows are (params, structure, TIER)-determined:
            # tier-qualify the key so an f32 answer can never serve an
            # int8 request (or vice versa). f32 keeps the bare legacy key.
            fp = f"{tier}:{fp}"
        if fp is not None:
            hit = self.cache.get(fp)
            lookup_ms = (self._clock() - lookup_t0) * 1e3
            if hit is not None:
                row, version = hit
                # entries are version-tagged and only served while their
                # version is still live: the swap's cache.clear() is bulk
                # eviction, but a batch IN FLIGHT across the swap writes
                # its old-version rows AFTER the clear — this check is
                # what actually guarantees no stale science is served
                if version == self.param_store.version:
                    self._count("cache_hits")
                    self._observe_cache_lookup(tier, form, "hit",
                                               lookup_ms)
                    fut = RequestFuture()
                    replied = self._stamp()
                    latency_ms = (self._clock() - now) * 1e3
                    fut.set_result(ServeResult(
                        prediction=row, param_version=version,
                        latency_ms=latency_ms, cached=True,
                        device_id=-1, trace_id=tid, precision=tier,
                        stamps={"queued": queued, "replied": replied},
                        wire="raw" if form == "raw" else "featurized",
                        klass=kl,
                    ))
                    # cache hits ARE served responses: they must feed the
                    # same latency distributions clients measure, or the
                    # scraped rolling p99 and a loadgen's own p99 describe
                    # different populations under a warm cache
                    self._record_latency(latency_ms)
                    self._lat_rolling.add(latency_ms)
                    self._observe_served(latency_ms, version=version,
                                         klass=kl)
                    self._count(f"responses_class_{kl}")
                    self.telemetry.observe_value("serve_latency_ms",
                                                 latency_ms)
                    if self._spans_on:
                        args = {"trace_id": tid, "cached": True}
                        if trace_parent:
                            args["parent"] = trace_parent
                        self._span("serve.request", queued, replied,
                                   **args)
                    self._note_request(
                        trace_id=tid, status="ok", cached=True,
                        param_version=version, precision=tier,
                        wire="raw" if form == "raw" else "featurized",
                        latency_ms=latency_ms)
                    self._journal_served(
                        graph=graph, fingerprint=fp, trace_id=tid,
                        prediction=row, version=version,
                        wire="raw" if form == "raw" else "featurized")
                    return fut
            # a stale-version hit is a miss for accounting: the row
            # cannot be served, a forward pass (or a coalesce onto one)
            # is what answers the request
            self._observe_cache_lookup(tier, form, "miss", lookup_ms)
        timeout = (timeout_ms / 1000.0 if timeout_ms is not None
                   else self.default_timeout)
        req = Request(
            graph=graph,
            enqueued=now,
            deadline=None if timeout is None else now + timeout,
            fingerprint=fp,
            # decided once here: a flush packs compact only when EVERY
            # member can (batcher.Request docstring). Deferred-featurize
            # structures resolve their probe at pack time, on the pool.
            compactable=(False if is_raw_wire
                         else self.shape_set.compactable(graph)),
            trace_id=tid,
            stamps={"queued": queued},
            precision=tier,
            form=form,
            trace_parent=str(trace_parent or ""),
            klass=kl,
            tenant=str(tenant or ""),
        )
        # single-flight miss coalescing (ISSUE 20): one leader per
        # in-flight fingerprint; concurrent identical misses attach to
        # its future instead of entering the batcher. With coalescing
        # OFF duplicates proceed (the A/B baseline) but are counted —
        # cache_dup_misses is the figure the bench hard-asserts to 0
        # when coalescing is on.
        follower = None
        dup_in_flight = False
        if fp is not None:
            with self._sf_lock:
                entry = self._inflight.get(fp)
                if entry is None:
                    self._inflight[fp] = {"req": req, "followers": []}
                elif self._single_flight:
                    follower = {
                        "future": RequestFuture(), "trace_id": tid,
                        "queued": queued, "t0": now, "klass": kl,
                        "tier": tier, "form": form,
                        "trace_parent": str(trace_parent or ""),
                        "graph": graph, "fingerprint": fp,
                    }
                    entry["followers"].append(follower)
                else:
                    dup_in_flight = True
            if follower is not None:
                self._count("cache_coalesced")
                return follower["future"]
            if dup_in_flight:
                self._count("cache_dup_misses")
            else:
                # the leader's completion — success, error, or expiry,
                # from whichever thread resolves it — drains the waiter
                # table entry and answers every follower
                req.future.add_done_callback(
                    lambda f, _fp=fp: self._singleflight_done(_fp, f))
        try:
            self.batcher.offer(req)
        except ServeRejection as e:
            if fp is not None and not dup_in_flight:
                # the leader never entered the batcher: drop the table
                # entry and relay the rejection to any follower that
                # attached in the window (they would otherwise wait on
                # a future nothing will ever resolve)
                with self._sf_lock:
                    cur = self._inflight.get(fp)
                    waiters = ()
                    if cur is not None and cur.get("req") is req:
                        waiters = self._inflight.pop(fp)["followers"]
                for w in waiters:
                    w["future"].set_error(e)
            self._count(f"reject_{e.reason}")
            raise
        return req.future

    def predict(self, graph: CrystalGraph,
                timeout_ms: float | None = None,
                trace_id: str | None = None,
                precision: str | None = None,
                trace_parent: str | None = None,
                klass: str | None = None,
                tenant: str | None = None,
                fingerprint: str | None = None) -> ServeResult:
        """Blocking convenience: submit + wait."""
        fut = self.submit(graph, timeout_ms=timeout_ms, trace_id=trace_id,
                          precision=precision, trace_parent=trace_parent,
                          klass=klass, tenant=tenant,
                          fingerprint=fingerprint)
        # wait slightly past the serving deadline: expiry is delivered by
        # the worker, not by this caller racing it
        timeout = (timeout_ms / 1000.0 if timeout_ms is not None
                   else self.default_timeout)
        return fut.result(None if timeout is None else timeout + 30.0)

    # ---- the worker ----

    def _serve_loop(self) -> None:
        if self.mesh_exec is not None:
            return self._serve_loop_mesh()
        if len(self.device_set) > 1:
            return self._serve_loop_multidev()
        if self._pack_workers > 0:
            return self._serve_loop_pipelined()
        while True:
            racecheck.heartbeat()
            flush = self.batcher.next_flush()
            if flush is None:
                return
            self._process(flush)

    def _flushes(self):
        """The live flush stream: expiries are delivered HERE, before
        the pack stage, so a timed-out client hears promptly instead of
        queueing behind the pipeline's in-flight flushes."""
        while True:
            flush = self.batcher.next_flush()
            if flush is None:
                return
            self._fail_expired(flush)
            if flush.requests:
                yield flush

    def _make_pack_one(self, pool):
        def pack_one(flush: Flush):
            t0 = time.perf_counter()
            try:
                batch, buf = self._pack_flush(flush, pool)
                err = None
            except Exception as e:  # noqa: BLE001 — fail the flush, not the stream
                batch = buf = None
                err = e
            t1 = time.perf_counter()
            # the 'packed' hop: stamped on the flush (shared by its
            # co-batched members) and emitted as a span keyed by
            # flush_id + the member trace ids
            flush.stamps["packed"] = t1
            if self._spans_on:  # skip arg-building when off
                self._span("serve.pack", t0, t1, flush_id=flush.flush_id,
                           n=len(flush.requests),
                           trace_ids=flush.trace_ids(),
                           error=repr(err) if err is not None else "")
            self.telemetry.observe_value("serve_pack_s", t1 - t0)
            return flush, batch, buf, err

        return pack_one

    def _packed_stream(self, pool):
        """(flush, batch, buf, err) stream: through the parallel pack
        pipeline when ``pack_workers > 0``, in-line otherwise."""
        from cgnn_tpu.data.pipeline import parallel_pack

        pack_one = self._make_pack_one(pool)
        if self._pack_workers > 0:
            return iter(parallel_pack(
                self._flushes(), pack_one, workers=self._pack_workers,
                telemetry=self.telemetry, raise_on_error=False,
                name="cgnn-serve-pack",
            ))
        return map(pack_one, self._flushes())

    def _serve_loop_pipelined(self) -> None:
        """The single-device pack-overlapped worker: batcher -> packer
        pool -> dispatch.

        ``parallel_pack`` (data/pipeline.py) runs the flush stream
        through ``_pack_workers`` packer threads with order-restoring
        reassembly, so while THIS thread dispatches flush N and blocks
        on its fetch, flush N+1 is already packing and the batcher is
        coalescing N+2 — packing leaves the dispatch critical path.
        Order preservation keeps response FIFO fairness. Pack errors are
        delivered per flush (the poisoned flush fails alone; admission
        validation makes them unlikely). Pooled staging buffers recycle
        after the flush's blocking fetch — the device is done with them.
        """
        from cgnn_tpu.data.pipeline import BufferPool

        pool = BufferPool()
        stream = self._packed_stream(pool)
        while True:
            racecheck.heartbeat()
            t0 = time.perf_counter()
            try:
                item = next(stream)
            except StopIteration:
                return
            except Exception as e:  # noqa: BLE001 — flush-stream error: keep serving
                self._log(f"serve: pack pipeline error: {e!r}")
                continue
            # dispatch-side stall waiting on the packers (the ingest
            # starvation signal; run_summary p50/p95/p99 via series)
            self.telemetry.observe_value("pipeline_wait_s",
                                         time.perf_counter() - t0)
            self._run_flush(*item, pool=pool)

    def _serve_loop_multidev(self) -> None:
        """The device-parallel worker: batcher -> packer pool -> router
        -> one dispatch thread PER device (ISSUE 5).

        The router assigns each packed flush to the least-loaded device
        (DeviceSet.pick: fewest in-flight, round-robin tie-break) and
        hands it to that device's dispatch thread over a bounded queue —
        the per-device in-flight window. Each device thread reads its
        (params, version) replica pair once per flush, dispatches, and
        BLOCKS on the fetch before touching the next flush, so per
        device execution is FIFO and a pooled staging buffer is released
        only after the fetch proves its dispatch completed — the ISSUE-4
        BufferPool contract, per device. Responses stay FIFO per device;
        cross-device completion order is whatever the hardware does (the
        price of using more than one chip).
        """
        import queue as queue_mod

        from cgnn_tpu.data.pipeline import BufferPool

        pool = BufferPool()
        n = len(self.device_set)
        qs = [queue_mod.Queue(maxsize=self.device_set.window)
              for _ in range(n)]

        def device_worker(i: int) -> None:
            while True:
                racecheck.heartbeat()
                try:
                    # bounded get: the idle tick is what lets the
                    # racecheck watchdog tell 'no traffic routed here'
                    # from 'wedged mid-dispatch'
                    item = qs[i].get(timeout=1.0)
                except queue_mod.Empty:
                    continue
                if item is None:
                    return
                self._run_flush(*item, pool=pool, device=i, routed=True)

        workers = [
            threading.Thread(target=device_worker, args=(i,), daemon=True,
                             name=f"serve-dispatch-{i}")
            for i in range(n)
        ]
        for t in workers:
            t.start()
        stream = self._packed_stream(pool)
        try:
            while True:
                racecheck.heartbeat()
                t0 = time.perf_counter()
                try:
                    item = next(stream)
                except StopIteration:
                    return
                except Exception as e:  # noqa: BLE001 — keep serving
                    self._log(f"serve: pack pipeline error: {e!r}")
                    continue
                self.telemetry.observe_value("pipeline_wait_s",
                                             time.perf_counter() - t0)
                i = self.device_set.pick()
                # in-flight accounting BEFORE the put so pick() sees the
                # routed-but-unstarted load of every device
                self.device_set.note_enqueue(i)
                qs[i].put(item)
        finally:
            for q in qs:
                q.put(None)
            for t in workers:
                t.join()

    def _serve_loop_mesh(self) -> None:
        """The mesh-engine worker (ISSUE 10): batcher -> packer pool ->
        ONE sharded dispatch per flush.

        Each packed flush is already split round-robin across the mesh
        (``_pack_flush``): per-shard sub-batches of one common rung,
        stacked on the device axis. The single dispatch thread stages
        the stack batch-axis-sharded (each device receives exactly its
        slice) and runs ONE jitted call that covers every device — the
        least-loaded router, the per-device queues, and the N dispatch
        threads of the threads engine do not exist here. FIFO response
        order is global (one dispatch stream), and the hot-swap boundary
        is unchanged: one (params, version) read per flush, now of the
        single sharded tree.
        """
        stream = self._packed_stream(None)  # mesh packs fresh stacks;
        #                                     the pooled-buffer recycle
        #                                     contract belongs to the
        #                                     per-device engines
        while True:
            racecheck.heartbeat()
            t0 = time.perf_counter()
            try:
                item = next(stream)
            except StopIteration:
                return
            except Exception as e:  # noqa: BLE001 — keep serving
                self._log(f"serve: pack pipeline error: {e!r}")
                continue
            self.telemetry.observe_value("pipeline_wait_s",
                                         time.perf_counter() - t0)
            self._run_flush_mesh(*item)

    def _run_flush_mesh(self, flush: Flush, packed, buf, err) -> None:
        """Mesh twin of ``_run_flush``: one dispatch serves every shard,
        so accounting touches every shard the split populated, and a
        failed flush still fails alone."""
        counts = packed[1] if packed is not None else []
        shards = [i for i, c in enumerate(counts) if c > 0]
        for i in shards:
            self.device_set.note_enqueue(i)
        t0 = time.perf_counter()
        ok = False
        try:
            if err is not None:
                raise err
            self._dispatch_flush_mesh(flush, packed)
            ok = True
        except Exception as e:  # noqa: BLE001 — fail the flush, not the server
            self._log(f"serve: batch failed (mesh): {e!r}")
            for r in flush.requests:
                if not r.future.done():
                    r.future.set_error(e)
                    self._record_slo_bad(klass=r.klass)
                    self._note_request(
                        trace_id=r.trace_id, status="dispatch_failed",
                        error=repr(e), precision=r.precision,
                        flush_id=flush.flush_id)
        finally:
            busy = time.perf_counter() - t0
            # the shards ran CONCURRENTLY under one dispatch: each
            # participating shard was busy for the flush wall, which
            # keeps per-device occupancy comparable with the threads
            # engine's per-flush accounting
            for i in shards:
                self.device_set.note_complete(i, busy, ok=ok)

    def _dispatch_flush_mesh(self, flush: Flush, packed) -> None:
        import jax

        # same chaos point as the single-device path (ISSUE 14)
        faultinject.dispatch_point()
        stacked, counts, sub_shape = packed
        n = len(self.mesh_exec)
        reqs = flush.requests
        tier = flush.precision
        # the hot-swap boundary: ONE (sharded params, version) pair read
        # per flush — a reload landing after this line affects the NEXT
        # flush; this one keeps its dispatch-time tree alive by reference
        state, version = self.param_store.get(0, tier)
        pre = self._jit_cache_size()
        dispatched = self._stamp()
        flush.stamps["dispatched"] = dispatched
        staged = self.mesh_exec.stage(stacked)
        # tree_map(np.array, ...): a true host copy of every gathered
        # result leaf — the raw program returns a (preds, overflow,
        # n_edges) tuple (device_get ALIASES device buffers on CPU —
        # GC-ALIAS)
        res = jax.tree_util.tree_map(
            np.array, jax.device_get(self.mesh_predict(state, staged)))
        overflow = raw_edges = None
        if flush.form == "raw":
            out, overflow, raw_edges = res
        else:
            out = res
        fetched = self._stamp()
        flush.stamps["fetched"] = fetched
        post = self._jit_cache_size()
        if self.warmed and pre is not None and post is not None and post > pre:
            with self._lock:
                self._compiles_after_warm += post - pre
            self.telemetry.counter_add("serve_recompiles_after_warm",
                                       post - pre)
            self._log(
                f"serve: UNEXPECTED recompile after warmup "
                f"(mesh shape {sub_shape}); latency SLO was broken "
                f"this batch"
            )
        if self._spans_on:  # skip arg-building when off
            self._span("serve.dispatch", dispatched, fetched,
                       flush_id=flush.flush_id, engine="mesh", shards=n,
                       shape=str(sub_shape), trace_ids=flush.trace_ids())
        now = self._clock()
        # real graphs over the slots the mesh dispatch actually ran
        occupancy = len(reqs) / (n * sub_shape.graph_cap)
        for i, c in enumerate(counts):
            if c > 0:
                self._count(f"batches_device{i}")
        # same accounting as the threads engine, over the n shards the
        # dispatch spanned (raw_edges comes back [n_shards, G'])
        self._note_edge_occupancy(flush, raw_edges, shape=sub_shape,
                                  n_shards=n)
        wire = "raw" if flush.form == "raw" else "featurized"
        for j, r in enumerate(reqs):
            # request j sat at (shard j % N, row j // N): the
            # round-robin split coordinate (executor.split_round_robin)
            shard, row = j % n, j // n
            if overflow is not None and overflow[shard, row]:
                self._fallback_overflow(r)
                continue
            prediction = out[shard, row].copy()
            latency_ms = (now - r.enqueued) * 1e3
            if self.cache is not None and r.fingerprint is not None:
                self.cache.put(r.fingerprint, (prediction, version))
            replied = self._stamp()
            stamps = {**r.stamps, **flush.stamps, "replied": replied}
            r.future.set_result(ServeResult(
                prediction=prediction, param_version=version,
                latency_ms=latency_ms, batch_occupancy=occupancy,
                device_id=shard, trace_id=r.trace_id, precision=tier,
                flush_id=flush.flush_id, stamps=stamps, wire=wire,
                klass=r.klass, backfilled=r.backfilled,
            ))
            if self._spans_on:  # skip arg-building when off
                args = {"trace_id": r.trace_id,
                        "flush_id": flush.flush_id, "device": shard,
                        "queue_ms": round(
                            (stamps["packed"] - stamps["queued"]) * 1e3,
                            3),
                        "dispatch_ms": round((fetched - dispatched) * 1e3,
                                             3)}
                if r.trace_parent:
                    args["parent"] = r.trace_parent
                self._span("serve.request", stamps["queued"], replied,
                           **args)
            self._note_request(
                trace_id=r.trace_id, status="ok", param_version=version,
                precision=tier, wire=wire, flush_id=flush.flush_id,
                device=shard, latency_ms=latency_ms, stamps=stamps)
            self._journal_served(
                graph=r.graph, fingerprint=r.fingerprint,
                trace_id=r.trace_id, prediction=prediction,
                version=version, wire=wire)
            self._record_latency(latency_ms)
            self._lat_rolling.add(latency_ms)
            self._observe_served(latency_ms, version=version,
                                 klass=r.klass)
            self.telemetry.observe_value("serve_latency_ms", latency_ms)
            self._count("responses")
            self._count(f"responses_class_{r.klass}")
            if r.backfilled:
                self._count("responses_backfilled")
            if wire == "raw":
                self._count("responses_raw")
            if tier != "f32":
                self._count(f"responses_{tier}")
        self._count("batches")
        self._note_flush_backfill(flush)
        with self._lock:
            self._occupancies.append(occupancy)
            del self._occupancies[:-4096]
        self._occ_rolling.add(occupancy)
        oh = self.hists.get("serve_flush_occupancy_hist")
        if oh is not None:
            oh.observe(occupancy)
        self.telemetry.observe_value("serve_batch_occupancy", occupancy)
        self.telemetry.set_gauge("serve_queue_depth", self.batcher.depth)

    def _fail_expired(self, flush: Flush) -> None:
        for r in flush.expired:
            self._record_slo_bad(klass=r.klass)
            self._count("reject_timeout")
            self._note_request(trace_id=r.trace_id, status="timeout",
                              precision=r.precision)
            r.future.set_error(ServeRejection(
                TIMEOUT,
                f"deadline exceeded after "
                f"{(self._clock() - r.enqueued) * 1e3:.1f} ms in queue",
            ))

    def _featurize_pending(self, flush: Flush) -> None:
        """Resolve deferred wire-form structures in a featurized flush:
        featurize HERE — this runs on the pack pool (or the worker's
        pack stage), never on the admission thread, so one large
        structure cannot head-of-line-block admission (the ISSUE-11
        bugfix). A structure the featurizer rejects fails ALONE (its
        future gets the error; co-batched members keep flying)."""
        keep = []
        for r in flush.requests:
            if not isinstance(r.graph, RawStructure):
                keep.append(r)
                continue
            try:
                if self.featurizer is None:
                    raise ValueError("no fallback featurizer configured")
                g = self.featurizer(r.graph)
                self._check_wellformed(g)
            except Exception as e:  # noqa: BLE001 — fail THIS request only
                self._count("reject_malformed")
                r.future.set_error(ServeRejection(
                    MALFORMED, f"structure featurization failed: {e}"))
                continue
            r.graph = g
            r.compactable = self.shape_set.compactable(g)
            keep.append(r)
        flush.requests = keep

    def _pack_flush(self, flush: Flush, pool=None):
        """-> (batch, pool buffer or None). Raw-wire flushes stage the
        RawBatch form (near-zero host work — the in-program search
        builds the graph); featurized flushes first resolve any
        deferred wire-form structures (``_featurize_pending``), then
        compact staging when the shape set carries a spec AND every
        request in the flush is compactable, full-fidelity otherwise.

        Under the mesh engine the packed form is the SPLIT one: the
        flush's graphs round-robined across the mesh, each shard packed
        into one common rung, stacked on the device axis —
        ``(stacked, per-shard real counts, rung)``."""
        if flush.form != "raw":
            self._featurize_pending(flush)
            if not flush.requests:
                raise ValueError("every request in the flush failed "
                                 "featurization")
        graphs = [r.graph for r in flush.requests]
        if flush.form == "raw":
            self._count("pack_raw")
            if self.mesh_exec is not None:
                groups, sub_shape, counts = self.mesh_exec.plan_flush(
                    graphs, self.shape_set)
                stacked = self.mesh_exec.stack(
                    [self.shape_set.pack_raw(g, shape=sub_shape)
                     for g in groups])
                return (stacked, counts, sub_shape), None
            return self.shape_set.pack_raw(graphs, shape=flush.shape), None
        if self.mesh_exec is not None:
            groups, sub_shape, counts = self.mesh_exec.plan_flush(
                graphs, self.shape_set)
            compact = (self.shape_set.compact is not None
                       and all(r.compactable for r in flush.requests))
            pack = (self.shape_set.pack if compact
                    else self.shape_set.pack_full)
            stacked = self.mesh_exec.stack(
                [pack(g, shape=sub_shape) for g in groups])
            if self.shape_set.compact is not None:
                self._count("pack_compact" if compact else "pack_full")
            return (stacked, counts, sub_shape), None
        if self.shape_set.compact is not None:
            if all(r.compactable for r in flush.requests):
                buf = None
                if pool is not None:
                    key = self.shape_set.buffer_key(flush.shape)
                    buf = (key, pool.acquire(
                        key, self.shape_set.buffer_factory(flush.shape)))
                batch = self.shape_set.pack(
                    graphs, shape=flush.shape,
                    out=None if buf is None else buf[1],
                )
                self._count("pack_compact")
                return batch, buf
            self._count("pack_full")
            return self.shape_set.pack_full(graphs, shape=flush.shape), None
        return self.shape_set.pack(graphs, shape=flush.shape), None

    def _process(self, flush: Flush) -> None:
        """The in-line (pack_workers=0) flush path: expire, pack,
        dispatch — all on the calling thread (same stamp/span/telemetry
        discipline as the pipelined pack stage)."""
        self._fail_expired(flush)
        if not flush.requests:
            return
        self._run_flush(*self._make_pack_one(None)(flush), pool=None)

    def _run_flush(self, flush: Flush, batch, buf, err, *, pool,
                   device: int = 0, routed: bool = False) -> None:
        """Dispatch one packed flush on ``device`` with the shared
        error/accounting/buffer-release discipline: a failed flush fails
        alone (its futures get the error, the server keeps serving), the
        device's in-flight count and busy time are maintained exactly
        once per flush, and a pooled staging buffer is released only
        AFTER the blocking fetch inside ``_dispatch_flush`` proved the
        device consumed it. ``routed`` marks flushes whose enqueue was
        already counted by the multidev router."""
        if not routed:
            self.device_set.note_enqueue(device)
        t0 = time.perf_counter()
        ok = False
        try:
            if err is not None:
                raise err
            self._dispatch_flush(flush, batch, device=device)
            ok = True
        except Exception as e:  # noqa: BLE001 — fail the flush, not the server
            self._log(f"serve: batch failed (device {device}): {e!r}")
            for r in flush.requests:
                if not r.future.done():
                    r.future.set_error(e)
                    self._record_slo_bad(klass=r.klass)
                    self._note_request(
                        trace_id=r.trace_id, status="dispatch_failed",
                        error=repr(e), precision=r.precision,
                        flush_id=flush.flush_id, device=device)
        finally:
            self.device_set.note_complete(device,
                                          time.perf_counter() - t0, ok=ok)
            if buf is not None and pool is not None:
                pool.release(*buf)

    def _dispatch_flush(self, flush: Flush, batch, device: int = 0) -> None:
        import jax

        # serve-side chaos point (resilience/faultinject.py, ISSUE 14):
        # deterministic dispatch exception / wedge / slowdown — a no-op
        # without a CGNN_TPU_FAULTS plan
        faultinject.dispatch_point()
        reqs = flush.requests
        # the hot-swap boundary: one consistent (params, version) REPLICA
        # pair per batch, read from the dispatch device's slot FOR THE
        # FLUSH'S PRECISION TIER — a reload landing after this line
        # affects the NEXT batch; this one keeps its dispatch-time
        # replica alive by reference and finishes on it
        tier = flush.precision
        state, version = self.param_store.get(device, tier)
        pre = self._jit_cache_size()
        dispatched = self._stamp()
        flush.stamps["dispatched"] = dispatched
        # tree_map(np.array, ...), not asarray: a true host copy of
        # every output leaf (device_get ALIASES device buffers on CPU —
        # graftcheck GC-ALIAS) so response rows never share memory with
        # a buffer the pool is about to recycle
        res = jax.tree_util.tree_map(
            np.array, jax.device_get(self.predict_step(state, batch)))
        overflow = raw_edges = None
        if flush.form == "raw":
            # the raw program's output contract (train/step.py): a
            # (predictions, cap_overflow, n_edges) tuple
            out, overflow, raw_edges = res
        else:
            out = res
        fetched = self._stamp()
        flush.stamps["fetched"] = fetched
        post = self._jit_cache_size()
        if self.warmed and pre is not None and post is not None and post > pre:
            # a recompile after warmup is a policy bug (the batcher left
            # the warm shape set) — LOUD, and counted for the loadgen.
            # Under the lock: one dispatch thread PER device writes this
            # (a bare += loses updates across threads; GC-LOCKSHARE)
            with self._lock:
                self._compiles_after_warm += post - pre
            self.telemetry.counter_add("serve_recompiles_after_warm",
                                       post - pre)
            self._log(
                f"serve: UNEXPECTED recompile after warmup "
                f"(shape {flush.shape}); latency SLO was broken this batch"
            )
        # the dispatch->fetch hop (device compute + transfer), one span
        # per flush with the co-batched trace ids as the join keys
        if self._spans_on:  # skip arg-building when off
            self._span("serve.dispatch", dispatched, fetched,
                       flush_id=flush.flush_id, device=device,
                       shape=str(flush.shape), trace_ids=flush.trace_ids())
        now = self._clock()
        occupancy = len(reqs) / flush.shape.graph_cap
        self._count(f"batches_device{device}")
        self._note_edge_occupancy(flush, raw_edges)
        wire = "raw" if flush.form == "raw" else "featurized"
        for i, r in enumerate(reqs):
            if overflow is not None and overflow[i]:
                # the in-program cap-overflow flag (INVARIANTS.md): this
                # structure's lattice needs more periodic images than
                # the rung provides — its row was computed from a
                # TRUNCATED graph and must never be served. Route it to
                # the host-featurized fallback form instead.
                self._fallback_overflow(r)
                continue
            row = out[i].copy()
            latency_ms = (now - r.enqueued) * 1e3
            if self.cache is not None and r.fingerprint is not None:
                self.cache.put(r.fingerprint, (row, version))
            replied = self._stamp()
            stamps = {**r.stamps, **flush.stamps, "replied": replied}
            r.future.set_result(ServeResult(
                prediction=row, param_version=version,
                latency_ms=latency_ms, batch_occupancy=occupancy,
                device_id=device, trace_id=r.trace_id, precision=tier,
                flush_id=flush.flush_id, stamps=stamps, wire=wire,
                klass=r.klass, backfilled=r.backfilled,
            ))
            # the whole journey, one span per request: admission ->
            # reply, args carrying the flush join key and stage stamps
            # (plus the upstream attempt span when one propagated in —
            # the cross-process nesting key)
            if self._spans_on:  # skip arg-building when off
                args = {"trace_id": r.trace_id,
                        "flush_id": flush.flush_id, "device": device,
                        "queue_ms": round(
                            (stamps["packed"] - stamps["queued"]) * 1e3,
                            3),
                        "dispatch_ms": round((fetched - dispatched) * 1e3,
                                             3)}
                if r.trace_parent:
                    args["parent"] = r.trace_parent
                self._span("serve.request", stamps["queued"], replied,
                           **args)
            self._note_request(
                trace_id=r.trace_id, status="ok", param_version=version,
                precision=tier, wire=wire, flush_id=flush.flush_id,
                device=device, latency_ms=latency_ms, stamps=stamps)
            self._journal_served(
                graph=r.graph, fingerprint=r.fingerprint,
                trace_id=r.trace_id, prediction=row,
                version=version, wire=wire)
            self._record_latency(latency_ms)
            self._lat_rolling.add(latency_ms)
            self._observe_served(latency_ms, version=version,
                                 klass=r.klass)
            # per REQUEST, not per batch: the run-summary quantiles must
            # describe the same distribution stats() does (PERF.md §10)
            self.telemetry.observe_value("serve_latency_ms", latency_ms)
            self._count("responses")
            self._count(f"responses_class_{r.klass}")
            if r.backfilled:
                self._count("responses_backfilled")
            if wire == "raw":
                self._count("responses_raw")
            if tier != "f32":
                self._count(f"responses_{tier}")
        self._count("batches")
        self._note_flush_backfill(flush)
        with self._lock:
            self._occupancies.append(occupancy)
            del self._occupancies[:-4096]
        self._occ_rolling.add(occupancy)
        oh = self.hists.get("serve_flush_occupancy_hist")
        if oh is not None:
            oh.observe(occupancy)
        self.telemetry.observe_value("serve_batch_occupancy", occupancy)
        self.telemetry.set_gauge("serve_queue_depth", self.batcher.depth)

    # ---- raw-wire overflow + occupancy bookkeeping (ISSUE 11) ----

    def _fallback_overflow(self, r) -> None:
        """Route one overflow-flagged raw request to the featurized
        fallback: re-offer it as a deferred-featurize request sharing
        the SAME future/trace/deadline (the pack pool featurizes it, a
        featurized flush answers it). Runs on the dispatch thread —
        cheap (no featurization here), and the counter is the telemetry
        the loadgen/smoke pin."""
        self._count("ingest_cap_overflow")
        self.telemetry.counter_add("ingest_cap_overflow", 1)
        if self.featurizer is None:
            r.future.set_error(ServeRejection(
                OVERSIZE,
                self.shape_set.raw.oversize_detail(r.graph)
                + " (in-program cap-overflow flag; no fallback "
                  "featurizer configured)",
            ))
            return
        fallback = Request(
            graph=r.graph, enqueued=r.enqueued, deadline=r.deadline,
            future=r.future, fingerprint=None, compactable=False,
            trace_id=r.trace_id, stamps=r.stamps, precision=r.precision,
            form="feat", trace_parent=r.trace_parent,
            # the re-offer keeps the request's scheduling contract: same
            # class and tenant, never a silent downgrade (INVARIANTS.md)
            klass=r.klass, tenant=r.tenant,
        )
        try:
            self.batcher.offer(fallback)
        except ServeRejection as e:
            self._count(f"reject_{e.reason}")
            r.future.set_error(e)

    def _note_edge_occupancy(self, flush: Flush, raw_edges,
                             shape=None, n_shards: int = 1) -> None:
        """Per-rung edge-slot occupancy — the cap-calibration signal
        (observe/gauges.py ``ingest_gauges``; /metrics). For raw
        flushes the TRUE edge count comes back from the program
        (``n_edges``); featurized flushes count host-known edges. The
        mesh engine passes its common rung + shard count (the dispatch
        spanned ``n_shards`` copies of the rung's slots) — ONE
        accounting shared by both engines, so the formula cannot
        drift between them."""
        shape = shape or flush.shape
        try:
            rung = self.shape_set.shapes.index(shape)
        except ValueError:
            return
        if flush.form == "raw":
            if raw_edges is None:
                return
            spec = self.shape_set.raw
            slots = (n_shards * shape.graph_cap * spec.snode_cap
                     * spec.dense_m)
            occ = float(np.asarray(raw_edges).sum()) / max(slots, 1)
        else:
            occ = sum(r.graph.num_edges for r in flush.requests) \
                / max(n_shards * shape.edge_cap, 1)
        with self._lock:
            self._rung_edge_occ[rung] = occ
        self.telemetry.set_gauge(f"ingest_rung{rung}_edge_occupancy", occ)

    def _note_flush_backfill(self, flush: Flush) -> None:
        """Per-flush backfill accounting (ISSUE 19): how many graph
        slots the chosen rung had to spare after the head-class prefix,
        and how many of them lower-class requests actually filled — the
        serve_padding_fill_share numerator/denominator. Only flushes
        that OFFERED slack count, so the gauge reads "of the padding
        backfill could have converted, how much did it"."""
        if not flush.slack_slots:
            return
        with self._lock:
            self._backfill_filled += flush.n_backfilled
            self._backfill_slack += flush.slack_slots

    # ---- bookkeeping ----

    def _count(self, key: str) -> None:
        with self._lock:
            self.counts[key] = self.counts.get(key, 0) + 1
        self.telemetry.counter_add(f"serve_{key}", 1)

    def _record_latency(self, latency_ms: float) -> None:
        with self._lock:
            self._latencies.append(latency_ms)
            del self._latencies[:-8192]

    def latency_quantiles(self) -> dict:
        """{p50, p95, p99, mean, count} over recent responses."""
        with self._lock:
            vals = list(self._latencies)
        if not vals:
            return {}
        arr = np.asarray(vals)
        p50, p95, p99 = np.percentile(arr, [50, 95, 99])
        return {"p50": float(p50), "p95": float(p95), "p99": float(p99),
                "mean": float(arr.mean()), "count": len(vals)}

    def rolling_quantiles(self) -> dict:
        """Live rolling-window latency quantiles (the /metrics view)."""
        return self._lat_rolling.quantiles()

    def stats(self) -> dict:
        with self._lock:
            counts = dict(self.counts)
            occ = list(self._occupancies)
            draining = self._draining
            compiles_after_warm = self._compiles_after_warm
            rung_occ = dict(self._rung_edge_occ)
            backfill_filled = self._backfill_filled
            backfill_slack = self._backfill_slack
        out = {
            "counts": counts,
            "queue_depth": self.batcher.depth,
            "param_version": self.param_store.version,
            # which execution layer drives the devices (ISSUE 10):
            # 'mesh' = one sharded dispatch covers the set,
            # 'threads' = per-device dispatch threads (the A/B engine)
            "engine": self.engine,
            "devices": self.device_set.stats(),
            "draining": draining,
            "latency_ms": self.latency_quantiles(),
            # the live plane (ISSUE 6): rolling-window quantiles — what
            # the last `rolling_window_s` seconds looked like, not the
            # whole run — plus each device's in-flight depth right now
            "rolling": {
                "window_s": self.rolling_window_s,
                "latency_ms": self._lat_rolling.quantiles(),
                "batch_occupancy": self._occ_rolling.quantiles(),
                "device_inflight": self.device_set.inflight_depths(),
            },
            "batch_occupancy_mean": float(np.mean(occ)) if occ else 0.0,
            "shapes": [s.to_meta() for s in self.shape_set],
            "precisions": list(self.precisions),
            # priority serving (ISSUE 19): the per-class answer counts
            # and the padding->goodput conversion the bench A/B pins
            "priority": {
                "backfill": self.batcher.backfill,
                "class_wait_ms": {
                    c: round(w * 1e3, 3)
                    for c, w in self.batcher.class_wait.items()
                },
                "responses_by_class": {
                    c: counts.get(f"responses_class_{c}", 0)
                    for c in CLASSES
                },
                "backfilled_responses": counts.get(
                    "responses_backfilled", 0),
                "padding_fill_share": (
                    backfill_filled / backfill_slack
                    if backfill_slack else 0.0),
                "slack_slots": backfill_slack,
            },
            "recompiles_after_warm": compiles_after_warm,
            "ingest": {
                "compact": self.shape_set.compact is not None,
                "raw": self.shape_set.raw is not None,
                "cap_overflows": counts.get("ingest_cap_overflow", 0),
                "rung_edge_occupancy": {
                    str(k): v for k, v in sorted(rung_occ.items())
                },
                "pack_workers": self._pack_workers,
                "pack_s": self.telemetry.series_quantiles("serve_pack_s"),
                "pipeline_wait_s": self.telemetry.series_quantiles(
                    "pipeline_wait_s"),
            },
        }
        if self.cache is not None:
            cstats = self.cache.stats()
            with self._sf_lock:
                inflight_keys = len(self._inflight)
            cstats.update({
                "single_flight": self._single_flight,
                "inflight_keys": inflight_keys,
                "coalesced": counts.get("cache_coalesced", 0),
                "dup_misses": counts.get("cache_dup_misses", 0),
                "fills": counts.get("cache_fills", 0),
                "fill_stale": counts.get("cache_fill_stale", 0),
            })
            out["cache"] = cstats
        if self._watcher is not None:
            out["reload"] = {"swaps": self._watcher.swaps,
                             "skips": self._watcher.skips,
                             **self._watcher.control()}
        if self.journal is not None:
            out["journal"] = self.journal.stats()
        # the metrics-truth layer (ISSUE 16): error-budget accounting +
        # alert states, and the embedded time-series store's own health
        if self.slo is not None:
            out["slo"] = self.slo.state()
        if self.tsdb is not None:
            out["tsdb"] = self.tsdb.stats()
        return out


def structure_featurizer(data_cfg) -> Callable:
    """RawStructure -> CrystalGraph via the checkpoint's featurization
    config (the deferred pack-pool featurize + cap-overflow fallback;
    http.py's JSON featurizer delegates here so online requests are
    featurized exactly like the training data was)."""
    from cgnn_tpu.data.dataset import featurize_structure
    from cgnn_tpu.data.structure import Structure

    cfg = data_cfg.featurize_config()
    gdf = cfg.gdf()

    def featurize(rs: RawStructure) -> CrystalGraph:
        s = Structure(rs.lattice, rs.frac_coords, rs.numbers)
        target = (rs.target if rs.target is not None
                  else np.zeros(1, np.float32))
        return featurize_structure(s, target, cfg, rs.cif_id, gdf,
                                   target_mask=rs.target_mask)

    return featurize


def plan_from_state(meta: dict) -> dict:
    """Model/packing knobs serve needs from a checkpoint's meta dict."""
    from cgnn_tpu.config import DataConfig, ModelConfig

    model_cfg = ModelConfig.from_meta(meta.get("model", {}))
    data_cfg = DataConfig.from_meta(meta.get("data", {}))
    return {"model_cfg": model_cfg, "data_cfg": data_cfg,
            "task": meta.get("task", "regression")}


def load_server(
    ckpt_dir: str,
    *,
    batch_size: int = 64,
    rungs: int = 3,
    calibration: Sequence[CrystalGraph] | None = None,
    calibration_n: int = 256,
    tag: str = "latest",
    telemetry=None,
    max_queue: int = 256,
    max_wait_ms: float = 5.0,
    class_max_wait_ms: dict | None = None,
    backfill: bool = True,
    wfq_weights: dict | None = None,
    default_timeout_ms: float | None = 1000.0,
    cache_size: int = 1024,
    compact: str = "auto",
    wire: str = "auto",
    raw_precheck: bool = True,
    pack_workers: int | None = None,
    devices: str | int = "auto",
    engine: str = "auto",
    precision: str = "f32",
    trace_ring: int = 65536,
    slo_layer: bool = True,
    slo_objectives=None,
    slo_rules=None,
    watch: bool = True,
    warm: bool = True,
    poll_interval_s: float = 2.0,
    profile_dir: str = "",
    log_fn: Callable = print,
):
    """Boot an InferenceServer from a training checkpoint directory.

    Shared by serve.py (HTTP) and scripts/serve_loadgen.py (in-process):
    restores the verified checkpoint, rebuilds the model, plans the shape
    ladder from ``calibration`` (default: synthetic structures drawn with
    the checkpoint's own featurization config), warms every shape, and —
    with ``watch`` — attaches the hot-reload watcher to ``ckpt_dir``.

    ``compact='auto'`` (default) serves compact-staged when the backend
    is an ACCELERATOR and the calibration sample probes stageable
    (data/compact.py); on a CPU backend the device IS the host, so
    shrinking H2D bytes buys nothing while the on-device re-expansion
    costs real compute (the CPU loadgen reads compact serving as
    throughput-neutral with a worse p99; what it does on the chip is
    ROADMAP A1's measurement). ``'on'`` forces it (the A/B leg),
    ``'off'`` forces full-fidelity packing.

    ``pack_workers`` sizes the pack pipeline between the batcher and
    the dispatch loop (0 = pack in-line on the worker thread); default
    ``None`` follows the same device rule — 1 on accelerators (pack
    overlaps remote dispatch), 0 on CPU (an overlap thread only steals
    cores from the compute it would overlap with).

    ``precision`` names the tiers to WARM, comma-separated (e.g.
    ``'f32,bf16,int8'`` — serve/quantize.py); requests then pick a tier
    per call (default f32). Every warmed tier multiplies the warmup
    compile count and never compiles after.

    ``devices`` (ISSUE 5) selects the dispatch set: ``'auto'`` = every
    local device on accelerator backends, one device on CPU (host
    "devices" share the same cores — serve/devices.py); an int forces
    that many anywhere, which is how the 8-host-device dryrun proves
    distribution in-container.

    ``engine`` (ISSUE 10) selects HOW a multi-device set is driven:
    ``'mesh'`` (the ``'auto'`` default whenever more than one device is
    resolved) batch-shards every flush across a ``Mesh`` +
    ``NamedSharding`` layout and runs ONE jitted dispatch covering all
    devices — compile count = programs, one sharded param tree per
    tier, no router threads (parallel/executor.py); ``'threads'`` keeps
    the ISSUE-5 thread-per-device DeviceSet layer (per-device replicas,
    least-loaded routing, programs x N executables) for the A/B.
    Either engine serves bit-exact predictions; hot reload swaps
    atomically under one version in both.

    -> (server, dict of the bits callers reuse: manager, meta, configs,
    template graph, the calibration sample).
    """
    import jax

    from cgnn_tpu.config import build_model
    from cgnn_tpu.data.dataset import load_synthetic
    from cgnn_tpu.train import (
        CheckpointManager,
        Normalizer,
        create_train_state,
        make_optimizer,
    )

    mgr = CheckpointManager(ckpt_dir, log_fn=log_fn)
    if not mgr.exists(tag):
        raise FileNotFoundError(f"no {tag!r} checkpoint under {ckpt_dir}")
    meta = mgr.read_meta(tag)
    cfg = plan_from_state(meta)
    if cfg["task"] == "force":
        raise NotImplementedError(
            "online serving covers property prediction; the force task's "
            "per-atom output extraction is offline-only (predict.py)"
        )
    model_cfg, data_cfg = cfg["model_cfg"], cfg["data_cfg"]
    model = build_model(model_cfg, data_cfg, cfg["task"], log_fn=log_fn)
    if calibration is None:
        # keep_geometry: raw-wire spec planning (below) calibrates its
        # periodic image caps from the calibration LATTICES; the graphs'
        # packed shapes are unchanged (pack_graphs always allocates the
        # geometry fields)
        calibration = load_synthetic(
            calibration_n, data_cfg.featurize_config(), seed=0,
            keep_geometry=True,
        )
    dense_m = model_cfg.dense_m or None
    edge_dtype = (jax.numpy.bfloat16 if model_cfg.dtype == "bfloat16"
                  else np.float32)
    on_accelerator = jax.default_backend() != "cpu"
    device_list = resolve_devices(devices)
    if pack_workers is None:
        # accelerators overlap packing with remote dispatch; on CPU an
        # overlap thread steals the cores it would overlap with — but a
        # FORCED multi-device set (the dryrun case) gets one packer so
        # the router + per-device dispatch threads are actually fed
        pack_workers = 1 if on_accelerator or len(device_list) > 1 else 0
    want_compact = (compact == "on"
                    or (compact == "auto" and on_accelerator))
    compact_spec = None
    if want_compact and dense_m is not None:
        from cgnn_tpu.data.compact import CompactSpec, CompactUnsupported

        try:
            compact_spec = CompactSpec.build(
                list(calibration), data_cfg.featurize_config().gdf(),
                dense_m=dense_m, edge_dtype=edge_dtype,
            )
        except CompactUnsupported as e:
            log_fn(f"serve: compact staging unavailable ({e}); "
                   f"full-fidelity packing")
    # raw wire (ISSUE 11): like compact, 'auto' engages on accelerator
    # backends only — on CPU the host IS the device, so moving the
    # neighbor search "on device" just moves it between host cores while
    # paying padded per-structure slots; 'raw' forces (the CI smoke and
    # A/B legs), 'featurized' disables
    if wire not in ("auto", "raw", "featurized"):
        raise ValueError(
            f"wire must be 'auto', 'raw', or 'featurized', got {wire!r}"
        )
    want_raw = wire == "raw" or (wire == "auto" and on_accelerator)
    raw_spec = None
    if want_raw and dense_m is not None:
        from cgnn_tpu.data.rawbatch import RawUnsupported, plan_raw_spec

        fcfg = data_cfg.featurize_config()
        try:
            raw_spec = plan_raw_spec(
                list(calibration), fcfg.gdf(), fcfg.radius, dense_m,
            )
        except RawUnsupported as e:
            log_fn(f"serve: raw wire unavailable ({e}); "
                   f"featurized wire only")
    elif want_raw:
        log_fn("serve: raw wire requires the dense layout; "
               "featurized wire only")
    shape_set = plan_shape_set(
        calibration, batch_size, rungs=rungs, dense_m=dense_m,
        edge_dtype=edge_dtype, num_targets=model_cfg.num_targets,
        compact=compact_spec, raw=raw_spec,
    )
    template = calibration[0]
    # model init reads the expanded form regardless of staging mode
    example = shape_set.pack_full([template])
    state = create_train_state(
        model, example, make_optimizer(),
        Normalizer.identity(model_cfg.num_targets), rng=jax.random.key(0),
    )
    state = mgr.restore_for_inference(state, tag)
    # label with what the verifying chain ACTUALLY loaded — it can fall
    # back past a corrupt newest save, and a wrong label here would both
    # mis-tag every response and pin the watcher (newest == "current")
    version = mgr.last_restored or tag
    precisions = tuple(
        t.strip() for t in str(precision).split(",") if t.strip()
    ) or ("f32",)
    server = InferenceServer(
        state, shape_set, version=version, telemetry=telemetry,
        max_queue=max_queue, max_wait_ms=max_wait_ms,
        class_max_wait_ms=class_max_wait_ms, backfill=backfill,
        wfq_weights=wfq_weights,
        default_timeout_ms=default_timeout_ms, cache_size=cache_size,
        pack_workers=pack_workers, devices=device_list, engine=engine,
        precisions=precisions, model=model,
        featurizer=structure_featurizer(data_cfg),
        raw_precheck=raw_precheck, trace_ring=trace_ring,
        slo_layer=slo_layer, slo_objectives=slo_objectives,
        slo_rules=slo_rules, log_fn=log_fn,
    )
    # ``warm=False`` (ISSUE 14): the caller compiles later — serve.py
    # binds its HTTP listener FIRST so /healthz can report ready=False
    # for the whole warmup window instead of connection-refused (a
    # router cannot tell refused-because-warming from dead)
    if warm:
        server.warm(template)
    if profile_dir:
        server.enable_profiling(profile_dir)
    if watch:
        server.attach_watcher(mgr, poll_interval_s=poll_interval_s,
                              log_fn=log_fn)
    return server, {
        "manager": mgr, "meta": meta, "model_cfg": model_cfg,
        "data_cfg": data_cfg, "template": template,
        "calibration": calibration,
    }
