#!/usr/bin/env python
"""Online inference server entrypoint (cgnn_tpu.serve; ISSUE 3).

Loads a train.py checkpoint, plans + warms the fixed serving shape set,
starts the hot-reload watcher on the checkpoint directory, and serves
HTTP until SIGTERM/SIGINT — which triggers a graceful drain (queued
requests answered, new ones rejected 503) and exit 0.

Usage:
    python serve.py CKPT_DIR [--port 8437] [--batch-size 64] ...
"""

from __future__ import annotations

import argparse
import os
import sys
import threading

from cgnn_tpu.runtime import COMPILE_CACHE_HELP
from cgnn_tpu.runtime import start as start_runtime


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("ckpt_dir", help="checkpoint directory written by train.py")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8437)
    p.add_argument("--device", choices=["auto", "cpu", "tpu"], default="auto")
    p.add_argument("-b", "--batch-size", type=int, default=64,
                   help="graph budget of the largest serving shape")
    p.add_argument("--rungs", type=int, default=3,
                   help="shape-ladder depth (compile count at warmup)")
    p.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="micro-batch flush deadline")
    p.add_argument("--class-wait-ms", default="",
                   help="per-priority-class flush budgets, e.g. "
                        "'batch=20,scavenger=80' (ms; unlisted classes "
                        "keep the defaults: interactive=1x, batch=4x, "
                        "scavenger=16x --max-wait-ms)")
    p.add_argument("--no-backfill", action="store_true",
                   help="disable padding-slack backfill (lower-class "
                        "requests riding a higher-class flush's spare "
                        "graph/node/edge slots; the A/B baseline)")
    p.add_argument("--wfq-weights", default="",
                   help="weighted-fair-queuing tenant weights, e.g. "
                        "'acme=4,guest=1' (unlisted tenants weigh 1)")
    p.add_argument("--class-slo-ms", default="",
                   help="per-class p95 latency SLO objectives, e.g. "
                        "'interactive=250,batch=2000' — adds a "
                        "class-scoped latency objective per entry")
    p.add_argument("--max-queue", type=int, default=256,
                   help="admission bound (backpressure: reject above this)")
    p.add_argument("--timeout-ms", type=float, default=1000.0,
                   help="default per-request deadline (0 disables)")
    p.add_argument("--cache-size", type=int, default=1024,
                   help="LRU result cache entries (0 disables)")
    p.add_argument("--compact", choices=["auto", "on", "off"],
                   default="auto",
                   help="compact-staged serving (data/compact.py): auto "
                        "engages on accelerator backends, on/off force")
    p.add_argument("--wire", choices=["auto", "raw", "featurized"],
                   default="auto",
                   help="raw-wire serving (ISSUE 11): 'raw' admits "
                        "(positions, lattice, species) structure "
                        "payloads straight into a warmed in-program "
                        "neighbor-search + featurize program (~100x "
                        "fewer request bytes, near-zero host work; "
                        "structures outside the raw rung caps fall "
                        "back to pack-pool featurization); 'auto' "
                        "engages on accelerator backends")
    p.add_argument("--pack-workers", type=int, default=None,
                   help="pack pipeline threads between batcher and "
                        "dispatch (0 = in-line; default follows the "
                        "backend like --compact auto)")
    p.add_argument("--precision", default="f32", metavar="TIERS",
                   help="comma-separated precision tiers to warm "
                        "(f32,bf16,int8 — serve/quantize.py); requests "
                        "pick a tier per call via the 'precision' field "
                        "(default f32). Every tier is compiled at warmup "
                        "for every rung — zero recompiles after")
    p.add_argument("--devices", default="auto", metavar="{auto,N}",
                   help="device-parallel dispatch set (serve/devices.py): "
                        "'auto' = all local devices on accelerator "
                        "backends, one on CPU; an integer forces that "
                        "many anywhere (the 8-host-device dryrun)")
    p.add_argument("--engine", choices=["auto", "mesh", "threads"],
                   default="auto",
                   help="multi-device execution layer (ISSUE 10): 'mesh' "
                        "(the auto default with >1 device) batch-shards "
                        "each flush over a Mesh+NamedSharding layout and "
                        "ONE jitted dispatch covers all devices — compile "
                        "count = programs, one sharded param tree; "
                        "'threads' keeps the per-device dispatch-thread "
                        "DeviceSet layer (the A/B baseline)")
    p.add_argument("--poll-interval", type=float, default=2.0,
                   help="hot-reload checkpoint poll seconds (0 disables)")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="bound on the SIGTERM graceful drain: past it "
                        "the process force-exits non-zero with the "
                        "unanswered count logged (a wedged flush must "
                        "not hold shutdown forever)")
    p.add_argument("--drain-linger", type=float, default=0.0,
                   help="after a clean drain, keep answering /healthz "
                        "(draining=true) for this many seconds before "
                        "exiting — set it >= the fleet health-probe "
                        "interval so the router OBSERVES the draining "
                        "state and classifies the exit as a scale "
                        "event, not an incident (ISSUE 17)")
    p.add_argument("--calibrate", type=int, default=256,
                   help="synthetic calibration structures for shape planning")
    p.add_argument("--calibration-cache", type=str, default="",
                   help="featurized graph cache to calibrate shapes from "
                        "(real traffic distribution beats synthetic)")
    p.add_argument("--telemetry-dir", type=str, default="",
                   help="write serving metrics.jsonl here ('' disables)")
    p.add_argument("--live-metrics", type=float, default=0.0, metavar="SECS",
                   help="append a registry snapshot (counters/gauges/"
                        "rolling quantiles) to metrics_live.jsonl every "
                        "SECS seconds (0 disables); the same live view "
                        "GET /metrics serves in Prometheus format")
    p.add_argument("--profile-dir", type=str, default="auto",
                   help="where POST /profile and SIGUSR2 write bounded "
                        "on-demand jax.profiler captures ('auto' = the "
                        "telemetry dir when set, else CKPT_DIR/profiles; "
                        "'' disables)")
    p.add_argument("--compile-cache", type=str, default=None,
                   metavar="DIR", help=COMPILE_CACHE_HELP)
    p.add_argument("--trace-ring", type=int, default=65536, metavar="N",
                   help="bounded always-on serving span ring behind "
                        "GET /trace — the fleet trace-join surface "
                        "(ISSUE 15); 0 disables (the PERF.md §18 A/B "
                        "baseline)")
    p.add_argument("--flightrec-dir", type=str, default="auto",
                   help="incident flight-recorder bundles land here "
                        "('auto' = the telemetry dir when set, else "
                        "CKPT_DIR/flightrec; '' disables). Triggers: "
                        "5xx burst, drain force-exit, racecheck "
                        "watchdog")
    p.add_argument("--log-json", action="store_true",
                   help="structured JSON log lines (role + pid + "
                        "current trace id per line) instead of plain "
                        "prints — bundle logs then grep by trace id")
    # ---- SLO engine + metrics truth (ISSUE 16) ----
    p.add_argument("--no-slo", action="store_true",
                   help="disable the SLO engine, the mergeable "
                        "histogram families, and the embedded "
                        "time-series store (the A/B baseline)")
    p.add_argument("--slo-target", type=float, default=0.999,
                   help="availability objective (fraction of requests "
                        "that must be answered)")
    p.add_argument("--slo-latency-ms", type=float, default=1000.0,
                   help="latency objective threshold: 95%% of answers "
                        "must land under this")
    p.add_argument("--slo-window", type=float, default=300.0,
                   help="error-budget accounting window (seconds)")
    p.add_argument("--slo-fast-s", type=float, default=None,
                   help="burn-rate rule override: fast window seconds "
                        "(set BOTH --slo-fast-s and --slo-slow-s; "
                        "default: the standard pairs scaled to "
                        "--slo-window)")
    p.add_argument("--slo-slow-s", type=float, default=None,
                   help="burn-rate rule override: slow window seconds")
    p.add_argument("--slo-factor", type=float, default=6.0,
                   help="burn-rate rule override: burn factor")
    p.add_argument("--slo-for-s", type=float, default=0.0,
                   help="burn-rate rule override: hold time before "
                        "pending becomes firing")
    p.add_argument("--journal", type=str, default="",
                   help="label journal JSONL path (ISSUE 18): every "
                        "served response is journaled and POST /label "
                        "joins late ground truth by trace id or "
                        "fingerprint, exactly once")
    p.add_argument("--reload-gated", action="store_true",
                   help="hold the reload watcher's auto-swap at the "
                        "boot version (continual/canary plane): newer "
                        "checkpoints are CANDIDATES until a POST "
                        "/reload-control raises the gate")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    refusal = start_runtime(args.device, args.compile_cache)
    if refusal:
        print(refusal, file=sys.stderr)
        return 2

    from cgnn_tpu.observe import Telemetry, json_log_fn
    from cgnn_tpu.serve.http import make_http_server
    from cgnn_tpu.serve.batcher import parse_kv_spec
    from cgnn_tpu.serve.server import load_server

    # one logging sink for everything this process prints: JSON lines
    # (role/pid/trace id) under --log-json, plain print otherwise
    log = json_log_fn("replica") if args.log_json else print

    telemetry = (
        Telemetry(level="epoch", log_dir=args.telemetry_dir)
        if args.telemetry_dir else Telemetry.disabled()
    )
    calibration = None
    if args.calibration_cache:
        from cgnn_tpu.data.cache import load_graph_cache

        calibration = load_graph_cache(args.calibration_cache)
    profile_dir = args.profile_dir
    if profile_dir == "auto":
        profile_dir = args.telemetry_dir or os.path.join(
            args.ckpt_dir, "profiles")
    # SLO engine (ISSUE 16): objectives from the flags; rules default to
    # the standard pairs scaled to the window unless both --slo-fast-s
    # and --slo-slow-s override (second-scale windows for smoke tests)
    slo_objectives = slo_rules = None
    if not args.no_slo:
        from cgnn_tpu.observe.slo import BurnRateRule, SLOObjective

        slo_objectives = (
            SLOObjective("availability", target=args.slo_target,
                         window_s=args.slo_window),
            SLOObjective("latency", target=0.95,
                         latency_threshold_ms=args.slo_latency_ms,
                         window_s=args.slo_window),
        )
        if args.class_slo_ms:
            # class-scoped objectives (ISSUE 19): only events of the
            # matching priority class feed these windows, so a slow
            # scavenger backlog cannot burn the interactive budget
            slo_objectives += tuple(
                SLOObjective(f"latency_{kl}", target=0.95,
                             latency_threshold_ms=ms,
                             window_s=args.slo_window, klass=kl)
                for kl, ms in parse_kv_spec(args.class_slo_ms).items()
            )
        if args.slo_fast_s is not None and args.slo_slow_s is not None:
            slo_rules = (BurnRateRule(
                fast_s=args.slo_fast_s, slow_s=args.slo_slow_s,
                factor=args.slo_factor, for_s=args.slo_for_s),)
    try:
        server, parts = load_server(
            args.ckpt_dir,
            batch_size=args.batch_size,
            rungs=args.rungs,
            calibration=calibration,
            calibration_n=args.calibrate,
            telemetry=telemetry,
            max_queue=args.max_queue,
            max_wait_ms=args.max_wait_ms,
            class_max_wait_ms=(parse_kv_spec(args.class_wait_ms)
                               if args.class_wait_ms else None),
            backfill=not args.no_backfill,
            wfq_weights=(parse_kv_spec(args.wfq_weights)
                         if args.wfq_weights else None),
            default_timeout_ms=args.timeout_ms or None,
            cache_size=args.cache_size,
            compact=args.compact,
            wire=args.wire,
            pack_workers=args.pack_workers,
            devices=args.devices,
            engine=args.engine,
            precision=args.precision,
            watch=args.poll_interval > 0,
            # warm AFTER the listener binds (below): /healthz answers
            # ready=False during compilation instead of refusing
            # connections, so a fleet router can tell warming from dead
            warm=False,
            poll_interval_s=args.poll_interval or 2.0,
            profile_dir=profile_dir,
            trace_ring=args.trace_ring,
            slo_layer=not args.no_slo,
            slo_objectives=slo_objectives,
            slo_rules=slo_rules,
            log_fn=log,
        )
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 2

    # incident flight recorder (ISSUE 15; observe/flightrec.py): the
    # always-cheap per-request ring + metrics/trace bundle dumps on
    # trigger — 5xx bursts (fed by the HTTP layer), the bounded-drain
    # force exit below, and the racecheck watchdog when that gate is on
    recorder = None
    flightrec_dir = args.flightrec_dir
    if flightrec_dir == "auto":
        flightrec_dir = args.telemetry_dir or os.path.join(
            args.ckpt_dir, "flightrec")
    if flightrec_dir:
        from cgnn_tpu.observe import FlightRecorder

        recorder = FlightRecorder(
            flightrec_dir, role="replica",
            name=f"replica:{args.port}",
            registry=server.registry, tracer=server.tracer,
            manifest={
                "ckpt_dir": args.ckpt_dir,
                "param_version": server.param_store.version,
                "port": args.port,
                "engine": server.engine,
                "precisions": list(server.precisions),
            },
            log_fn=log,
        )
        server.attach_flight_recorder(recorder)

    # continual-learning plane (ISSUE 18): the label journal joins
    # late ground truth onto served responses; --reload-gated turns
    # newer checkpoints into held CANDIDATES until the canary
    # controller's promotion broadcast raises the gate
    journal = None
    if args.journal:
        from cgnn_tpu.continual import LabelJournal

        journal = LabelJournal(args.journal)
        server.attach_journal(journal)
    if args.reload_gated and server.watcher is not None:
        server.watcher.set_gate(server.param_store.version)
        log(f"reload gate held at boot version "
            f"{server.param_store.version} (POST /reload-control to "
            "promote)")

    # the live plane's two push/pull surfaces beyond HTTP: SIGUSR2 ->
    # bounded on-demand device profile; --live-metrics -> periodic
    # registry snapshots for fleets scraped by file instead of port
    if server.profiler is not None:
        from cgnn_tpu.observe import install_sigusr2

        install_sigusr2(server.profiler, log_fn=log)
    live_writer = None
    if args.live_metrics > 0:
        from cgnn_tpu.observe import LiveMetricsWriter

        live_writer = LiveMetricsWriter(
            server.registry,
            os.path.join(args.telemetry_dir or args.ckpt_dir,
                         "metrics_live.jsonl"),
            interval_s=args.live_metrics,
        ).start()

    # no handler-side featurizer: wire-form structures admit directly
    # and the SERVER featurizes on the pack pool when needed (ISSUE 11)
    httpd = make_http_server(server, host=args.host, port=args.port)

    # SIGTERM/SIGINT -> drain the batcher, stop the listener, exit
    # (resilience.preempt signal plumbing; second signal kills)
    stop = threading.Event()
    handler = server.install_signal_handlers()
    handler.add_callback(stop.set)

    # bind + listen BEFORE warm (ISSUE 14 readiness): /healthz reports
    # ready=False (503) while the shape set compiles, flipping to 200
    # the moment warm() finishes — the router's admission signal
    listener = threading.Thread(target=httpd.serve_forever, daemon=True,
                                name="http-listener")
    listener.start()
    log(f"listening on http://{args.host}:{args.port} "
        f"(warming {len(server.shape_set)} shapes; "
        f"/healthz reports ready=false until done)")
    # fleet boot fault point (ISSUE 17): the listener is bound, warm()
    # has not run — where boot_crash dies and wedge_warm hangs
    from cgnn_tpu.resilience import faultinject

    faultinject.boot_point()
    server.warm(parts["template"])
    server.start()
    if recorder is not None:
        from cgnn_tpu.analysis import racecheck

        if racecheck.enabled():
            # a deadlock-watchdog dump is exactly the incident the
            # recorder exists for: re-arm the singleton's log hook so
            # the stall report also dumps a bundle (server.start()
            # armed it with the plain server log a moment ago)
            def _watchdog_log(msg):
                log(msg)
                recorder.trigger("watchdog", str(msg))

            racecheck.start_watchdog(bound_s=30.0, log_fn=_watchdog_log)

    shapes = ", ".join(
        f"({s.graph_cap}g/{s.node_cap}n/{s.edge_cap}e)"
        for s in server.shape_set
    )
    log(f"serving on http://{args.host}:{args.port} "
        f"(params {server.param_store.version}; shapes {shapes}; "
        f"{len(server.device_set)} device(s), {server.engine} engine; "
        f"wire: "
        f"{'raw+featurized' if server.shape_set.raw is not None else 'featurized'}; "
        f"live plane: GET /metrics"
        + (", GET /trace" if server.tracer is not None else "")
        + (f", flightrec -> {flightrec_dir}" if recorder else "")
        + (f", POST /profile -> {profile_dir}" if profile_dir else "")
        + ")")
    try:
        while not stop.wait(0.5):
            pass
    except KeyboardInterrupt:
        server.begin_drain()
    # drain with the LISTENER STILL UP (ISSUE 17): /healthz keeps
    # answering draining=true (new /predict requests get the typed 503
    # rejection), so the fleet health poller can observe the planned
    # exit and classify it a scale event instead of an incident. The
    # listener closes only after the drain (+ optional linger) ends.
    clean = server.drain(timeout_s=args.drain_timeout)
    if clean and args.drain_linger > 0:
        import time as _time

        _time.sleep(args.drain_linger)
    httpd.shutdown()
    httpd.server_close()
    handler.uninstall()
    if live_writer is not None:
        live_writer.stop()
    if journal is not None:
        journal.close()
    stats = server.stats()
    lat = stats["latency_ms"]
    if lat:
        log(f"drained: {stats['counts']['responses']} responses, "
            f"p50 {lat['p50']:.1f} ms / p99 {lat['p99']:.1f} ms")
    telemetry.close()
    if not clean:
        # the bounded-drain satellite (ISSUE 14): a wedged flush must
        # not hold shutdown forever. Log the unanswered count, then
        # FORCE-exit — a daemon worker blocked in a wedged dispatch can
        # pin interpreter teardown, and the supervisor (or the chaos
        # harness) needs this process GONE with a non-zero code.
        c = stats["counts"]
        rejected = sum(v for k, v in c.items() if k.startswith("reject_"))
        unanswered = (c.get("requests", 0) - c.get("responses", 0)
                      - c.get("cache_hits", 0) - rejected)
        if recorder is not None:
            # the flight-recorder trigger for exactly this incident:
            # dump the ring + metrics + trace BEFORE the hard exit.
            # wait=True: os._exit would otherwise race the dump thread
            # and truncate the bundle. force=True: the wedge that
            # caused this drain typically ALSO fired a 5xx/timeout
            # burst moments earlier, and the final bundle must not be
            # rate-limited away by its own symptom.
            recorder.trigger(
                "drain_force_exit",
                f"{max(unanswered, 0)} unanswered after "
                f"{args.drain_timeout:.0f} s drain",
                wait=True, force=True)
        print(f"drain timed out after {args.drain_timeout:.0f} s: "
              f"{max(unanswered, 0)} accepted request(s) unanswered, "
              f"{stats['queue_depth']} still queued; force-exiting 3",
              file=sys.stderr)
        sys.stderr.flush()
        sys.stdout.flush()
        os._exit(3)
    if recorder is not None:
        recorder.wait_idle(timeout_s=10.0)
    if faultinject.exit75_requested():
        # the injected preemption drained cleanly: report it with the
        # PR-2 resumable code, the signature the fleet router records
        # as a scale event rather than an incident
        from cgnn_tpu.resilience import RESUMABLE_EXIT_CODE

        return RESUMABLE_EXIT_CODE
    return 0


if __name__ == "__main__":
    sys.exit(main())
