#!/usr/bin/env python
"""Fleet serving entrypoint (cgnn_tpu.fleet; ISSUE 14).

Boots N independent serve.py replica processes against one checkpoint
directory, fronts them with a health-routed resilient router (bounded
retries + backoff, deadline-aware hedging, per-replica circuit
breakers, 503 + Retry-After load shedding), and serves the same
``POST /predict`` wire protocol a single replica does — plus
``GET /healthz`` (fleet readiness), ``GET /stats``, ``GET /metrics``
(router counters + per-replica gauges/series), ``GET /metrics/fleet``
(replica histogram families scraped and MERGED into one fleet-wide
exposition — ISSUE 16), and ``GET /timeseries`` (the router's embedded
multi-resolution history).

The replicas share the checkpoint directory, so a rolling promotion is
just the trainer committing a new save: every replica's own hot-reload
watcher picks it up within its poll interval, swapping atomically
mid-load — old and new ``param_version`` serve fleet-wide with zero
drops, exactly like the single-process invariant, now N-wide.

SIGTERM/SIGINT drains: the router sheds new work, replicas get SIGTERM
(their own graceful drain answers queued requests), exit 0.

Usage:
    python fleet.py CKPT_DIR --replicas 3 [--port 8440] ...
"""

from __future__ import annotations

import argparse
import os
import sys
import threading


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("ckpt_dir", help="checkpoint directory written by train.py")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8440,
                   help="router listen port")
    p.add_argument("--replicas", type=int, default=3,
                   help="serve.py replica processes to boot")
    p.add_argument("--replica-base-port", type=int, default=8441,
                   help="replicas bind base..base+N-1")
    p.add_argument("--log-dir", default="",
                   help="per-replica log files ('' = discard)")
    p.add_argument("--retries", type=int, default=3,
                   help="max extra attempts per request (attempt budget "
                        "= retries + 1, shared with the hedge)")
    p.add_argument("--backoff-ms", type=float, default=25.0,
                   help="initial retry backoff (exponential, jittered)")
    p.add_argument("--hedge-ms", type=float, default=None,
                   help="hedge a request to a second replica after this "
                        "long in flight (default: auto, 2x the "
                        "replica's rolling p99; 0 disables)")
    p.add_argument("--breaker-k", type=int, default=3,
                   help="consecutive failures that eject a replica")
    p.add_argument("--breaker-cooldown", type=float, default=2.0,
                   help="seconds ejected before the half-open probe")
    p.add_argument("--health-interval", type=float, default=1.0,
                   help="seconds between /healthz + /metrics probe rounds")
    p.add_argument("--timeout-ms", type=float, default=30000.0,
                   help="default per-request fleet deadline")
    p.add_argument("--no-feasibility", action="store_true",
                   help="disable deadline-feasibility admission (the "
                        "scraped-p99/queue-depth gate that sheds "
                        "requests whose deadline cannot be met with "
                        "429/504 + Retry-After before any attempt "
                        "crosses a process boundary)")
    p.add_argument("--feasibility-margin", type=float, default=1.0,
                   help="scale the feasibility estimate: shed only when "
                        "predicted completion exceeds deadline x margin "
                        "(>1 = more headroom before shedding)")
    p.add_argument("--drain-timeout", type=float, default=60.0,
                   help="bound on the SIGTERM graceful drain of the "
                        "replica fleet; past it, replicas are killed "
                        "and the router exits non-zero")
    p.add_argument("--serve-arg", action="append", default=[],
                   metavar="ARG", help="extra argument passed through to "
                                       "every serve.py replica "
                                       "(repeatable)")
    # ---- self-driving fleet (ISSUE 17) ----
    p.add_argument("--autoscale", action="store_true",
                   help="close the control loop: grow/shrink the "
                        "routed replica set against the scraped signal "
                        "plane (queue depth, p99 vs SLO, burn rates, "
                        "shed) with hysteresis + cooldowns; drained "
                        "exits are scale events, never incidents")
    p.add_argument("--min-replicas", type=int, default=1,
                   help="autoscaler lower bound on the routed set")
    p.add_argument("--max-replicas", type=int, default=8,
                   help="autoscaler upper bound on the routed set")
    p.add_argument("--warm-pool", type=int, default=1,
                   help="spare replicas kept booted + warm()-compiled "
                        "but unrouted, so scale-up is a routing-table "
                        "add instead of a multi-second warmup")
    p.add_argument("--remediate", action="store_true",
                   help="auto-remediation (needs --flightrec-dir): "
                        "subscribe to flight-recorder triggers and "
                        "replace-and-drain wedged replicas, every "
                        "action journaled to remediation.jsonl naming "
                        "its evidence bundle")
    p.add_argument("--trace-ring", type=int, default=65536, metavar="N",
                   help="router span ring behind GET /trace (+ the "
                        "on-demand fleet join GET /trace/joined); "
                        "0 disables")
    p.add_argument("--trace-out", default="", metavar="PATH",
                   help="write ONE joined fleet trace (router + every "
                        "reachable replica's /trace window) here at "
                        "drain — open it in Perfetto")
    p.add_argument("--flightrec-dir", default="", metavar="DIR",
                   help="incident flight-recorder bundles (joined "
                        "trace + per-process request rings + metrics) "
                        "land here; triggers: replica breaker trip, "
                        "5xx burst ('' disables)")
    p.add_argument("--log-json", action="store_true",
                   help="structured JSON log lines (role + pid + "
                        "current trace id); also passed to every "
                        "replica")
    # ---- fleet SLO engine (ISSUE 16) ----
    p.add_argument("--no-slo", action="store_true",
                   help="disable the fleet SLO engine, the mergeable "
                        "histogram families, and the embedded "
                        "time-series store (the A/B baseline)")
    p.add_argument("--slo-target", type=float, default=0.999,
                   help="fleet availability objective (fraction of "
                        "attempts that must succeed)")
    p.add_argument("--slo-latency-ms", type=float, default=2000.0,
                   help="latency objective threshold: 95%% of answered "
                        "attempts must land under this")
    p.add_argument("--slo-window", type=float, default=300.0,
                   help="error-budget accounting window (seconds)")
    p.add_argument("--slo-fast-s", type=float, default=None,
                   help="burn-rate rule override: fast window seconds "
                        "(default: the two standard pairs scaled to "
                        "--slo-window; set BOTH --slo-fast-s and "
                        "--slo-slow-s to override)")
    p.add_argument("--slo-slow-s", type=float, default=None,
                   help="burn-rate rule override: slow window seconds")
    p.add_argument("--slo-factor", type=float, default=6.0,
                   help="burn-rate rule override: burn factor both "
                        "windows must exceed")
    p.add_argument("--slo-for-s", type=float, default=0.0,
                   help="burn-rate rule override: hold time before "
                        "pending becomes firing")
    # ---- closed-loop continual learning (ISSUE 18) ----
    p.add_argument("--journal", default="", metavar="PATH",
                   help="label journal JSONL: every answered /predict "
                        "is journaled and POST /label joins late "
                        "ground truth by trace id, exactly once — the "
                        "continual trainer's replay feed ('' disables)")
    p.add_argument("--canary", action="store_true",
                   help="canary-gate trainer commits (needs --journal): "
                        "replicas boot reload-GATED at their boot "
                        "version, each new committed candidate is "
                        "pinned to one canary replica, shadow-evaluated "
                        "on mirrored labeled traffic, and only a "
                        "passing candidate promotes fleet-wide "
                        "(rolling, zero downtime); failures roll back "
                        "with a flight-recorder bundle naming the "
                        "version")
    p.add_argument("--canary-mirror", type=float, default=1.0,
                   help="fraction of labeled live traffic mirrored to "
                        "the canary (0, 1]")
    p.add_argument("--canary-min-samples", type=int, default=50,
                   help="labeled shadow mirrors required for a verdict")
    p.add_argument("--canary-max-mae-ratio", type=float, default=1.05,
                   help="promote when shadow/live MAE ratio <= this")
    p.add_argument("--canary-rollback-mae-ratio", type=float,
                   default=1.25,
                   help="roll back when the MAE ratio >= this")
    p.add_argument("--canary-p99-ms", type=float, default=2000.0,
                   help="shadow p99 budget; above it the candidate "
                        "rolls back on latency")
    p.add_argument("--canary-window", type=float, default=300.0,
                   help="max seconds a candidate may stay undecided "
                        "before it rolls back (window_expired)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from cgnn_tpu.fleet.http import make_fleet_http_server
    from cgnn_tpu.fleet.replica import ReplicaState
    from cgnn_tpu.fleet.router import FleetRouter
    from cgnn_tpu.fleet.spawn import require_chips, spawn_fleet
    from cgnn_tpu.observe import json_log_fn
    from cgnn_tpu.resilience.preempt import PreemptionHandler

    log = json_log_fn("router") if args.log_json else print

    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    log(f"fleet: booting {args.replicas} replicas on ports "
        f"{args.replica_base_port}.."
        f"{args.replica_base_port + args.replicas - 1} "
        f"(ckpt {args.ckpt_dir})")
    serve_args = list(args.serve_arg)
    if args.log_json:
        serve_args.append("--log-json")
    if args.canary and not args.journal:
        print("fleet: --canary needs --journal (the gate evaluates "
              "labeled live traffic)", file=sys.stderr)
        return 2
    if args.canary:
        # every replica (boot fleet, autoscaled adds, warm spares)
        # holds its reload gate at its boot version: trainer commits
        # are CANDIDATES until the canary controller promotes them
        serve_args.append("--reload-gated")
    try:
        if args.autoscale:
            # the ceiling the autoscaler may grow to, spares included
            require_chips(args.max_replicas + args.warm_pool)
        procs = spawn_fleet(
            args.ckpt_dir, args.replicas,
            base_port=args.replica_base_port, host=args.host,
            log_dir=args.log_dir or None, serve_args=serve_args,
        )
    except (RuntimeError, FileNotFoundError) as e:
        print(str(e), file=sys.stderr)
        return 2

    replicas = [
        ReplicaState(p.rid, p.base_url, breaker_k=args.breaker_k,
                     breaker_cooldown_s=args.breaker_cooldown)
        for p in procs
    ]
    # fleet SLO engine (ISSUE 16): objectives from the flags; burn-rate
    # rules default to the standard pairs scaled to the window, with a
    # single-rule override for second-scale windows (the smoke legs)
    slo_objectives = slo_rules = None
    if not args.no_slo:
        from cgnn_tpu.observe.slo import BurnRateRule, SLOObjective

        slo_objectives = (
            SLOObjective("fleet_availability", target=args.slo_target,
                         window_s=args.slo_window),
            SLOObjective("fleet_latency", target=0.95,
                         latency_threshold_ms=args.slo_latency_ms,
                         window_s=args.slo_window),
        )
        if args.slo_fast_s is not None and args.slo_slow_s is not None:
            slo_rules = (BurnRateRule(
                fast_s=args.slo_fast_s, slow_s=args.slo_slow_s,
                factor=args.slo_factor, for_s=args.slo_for_s),)
    router = FleetRouter(
        replicas,
        max_attempts=args.retries + 1,
        backoff_ms=args.backoff_ms,
        hedge_ms=args.hedge_ms,
        default_timeout_ms=args.timeout_ms,
        feasibility=not args.no_feasibility,
        feasibility_margin=args.feasibility_margin,
        health_interval_s=args.health_interval,
        trace_ring=args.trace_ring,
        slo_layer=not args.no_slo,
        slo_objectives=slo_objectives,
        slo_rules=slo_rules,
        log_fn=log,
    ).start()

    if args.flightrec_dir:
        from cgnn_tpu.observe import FlightRecorder

        router.attach_flight_recorder(FlightRecorder(
            args.flightrec_dir, role="router",
            name=f"router:{args.port}",
            registry=router.registry, tracer=router.tracer,
            peers=router.replica_trace_urls(),
            manifest={"ckpt_dir": args.ckpt_dir,
                      "replicas": args.replicas},
            log_fn=log,
        ))

    # ---- the self-driving layers (ISSUE 17) ----
    autoscaler = None
    if args.autoscale or args.remediate:
        from cgnn_tpu.fleet.autoscale import AutoscalePolicy, Autoscaler
        from cgnn_tpu.fleet.spawn import ReplicaProcess

        def _proc_factory(rid: int) -> ReplicaProcess:
            log_path = (os.path.join(args.log_dir, f"replica-{rid}.log")
                        if args.log_dir else None)
            return ReplicaProcess(
                rid, args.ckpt_dir, args.replica_base_port + rid,
                host=args.host, log_path=log_path,
                serve_args=serve_args)

        def _state_factory(rid: int, base_url: str) -> ReplicaState:
            return ReplicaState(
                rid, base_url, breaker_k=args.breaker_k,
                breaker_cooldown_s=args.breaker_cooldown)

        autoscaler = Autoscaler(
            router,
            AutoscalePolicy(min_replicas=args.min_replicas,
                            max_replicas=args.max_replicas,
                            warm_target=args.warm_pool if args.autoscale
                            else 0),
            _proc_factory, _state_factory,
            # seed ownership with the boot fleet so scale-down can
            # drain and reap the initial replicas too
            procs={p.rid: p for p in procs}, next_rid=args.replicas,
            poll_interval_s=max(args.health_interval, 0.25),
            drain_timeout_s=args.drain_timeout, log_fn=log,
        )
        router.autoscaler = autoscaler
        if args.autoscale:
            # without --autoscale the instance is just the process
            # machinery the remediator replaces through (no loop)
            autoscaler.start()

    remediator = None
    if args.remediate:
        if router.flightrec is None:
            print("fleet: --remediate needs --flightrec-dir (the "
                  "remediator consumes flight-recorder triggers)",
                  file=sys.stderr)
            return 2
        from cgnn_tpu.fleet.remediate import Remediator

        remediator = Remediator(
            router, autoscaler,
            out_dir=args.flightrec_dir,
            drain_timeout_s=args.drain_timeout, log_fn=log,
        ).attach(router.flightrec)
        router.remediator = remediator

    # ---- closed-loop continual learning (ISSUE 18) ----
    journal = None
    canary_ctl = None
    if args.journal:
        from cgnn_tpu.continual import LabelJournal

        journal = LabelJournal(args.journal)
        router.attach_journal(journal)
        log(f"fleet: label journal -> {args.journal} (POST /label "
            "joins ground truth)")
    if args.canary:
        from cgnn_tpu.continual import (
            CanaryController,
            CanaryGate,
            GateConfig,
        )
        from cgnn_tpu.train import CheckpointManager

        canary_mgr = CheckpointManager(args.ckpt_dir)
        canary_ctl = CanaryController(
            gate=CanaryGate(GateConfig(
                min_samples=args.canary_min_samples,
                min_baseline=args.canary_min_samples,
                max_mae_ratio=args.canary_max_mae_ratio,
                rollback_mae_ratio=args.canary_rollback_mae_ratio,
                p99_budget_ms=args.canary_p99_ms,
                max_window_s=args.canary_window,
            )),
            journal=journal, fleet=router,
            newest_fn=canary_mgr.newest_committed,
            mirror_fraction=args.canary_mirror,
            flightrec=router.flightrec, log_fn=log,
        )
        router.attach_canary(canary_ctl)
        canary_ctl.start()
        log("fleet: canary gate armed (replicas reload-gated; trainer "
            "commits shadow-evaluate before fleet-wide promotion)")

    httpd = make_fleet_http_server(router, host=args.host, port=args.port)
    stop = threading.Event()
    handler = PreemptionHandler(
        log_fn=log,
        action="draining the fleet (router sheds new work; replicas "
               "drain their queues)",
    )
    handler.add_callback(stop.set)
    handler.install()

    listener = threading.Thread(target=httpd.serve_forever, daemon=True,
                                name="fleet-http")
    listener.start()
    log(f"fleet: routing on http://{args.host}:{args.port} over "
        f"{len(replicas)} replicas "
        f"({router.ready_count()} ready; live plane: GET /metrics"
        + (", GET /trace/joined" if router.tracer is not None else "")
        + ")")
    try:
        while not stop.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    httpd.shutdown()
    httpd.server_close()
    if canary_ctl is not None:
        canary_ctl.stop()
        canary_mgr.close()
    router.stop()
    if journal is not None:
        journal.close()
    if args.trace_out and router.tracer is not None:
        # one joined Perfetto file for the whole run: the router's ring
        # plus every still-reachable replica's /trace window (pulled
        # BEFORE the replicas drain away)
        from cgnn_tpu.observe import trace_join

        windows, errors = trace_join.collect_windows(
            router.replica_trace_urls())
        doc = trace_join.write_joined(
            args.trace_out, [router.trace_window(), *windows])
        log(f"fleet: joined trace -> {args.trace_out} "
            f"({1 + len(windows)} process(es), "
            f"{len(doc['traces'])} trace(s)"
            + (f"; unreachable: {sorted(errors)}" if errors else "")
            + ")")
    if remediator is not None:
        remediator.stop()
    if autoscaler is not None:
        # drains EVERYTHING the autoscaler owns: the boot fleet it was
        # seeded with, scaled-up replicas, and warm-pool spares
        codes = list(autoscaler.shutdown(
            drain_timeout_s=args.drain_timeout).values())
    else:
        codes = [p.terminate(timeout_s=args.drain_timeout) for p in procs]
    handler.uninstall()
    if router.flightrec is not None:
        router.flightrec.wait_idle(timeout_s=15.0)
    stats = router.stats()["counts"]
    log(f"fleet: drained — {stats['fleet_answered']} answered, "
        f"{stats['fleet_retries']} retries, {stats['fleet_hedges']} "
        f"hedges, {stats['fleet_shed']} shed; "
        f"{stats['fleet_scale_events']} scale events, "
        f"{stats['fleet_incidents']} incidents; replica exits {codes}")
    # the PR-2 resumable code 75 is a PREEMPTION, not a failure: a
    # drained exit-75 replica left cleanly (the scale-event contract)
    bad = [c for c in codes if c not in (0, 75)]
    if bad:
        print(f"fleet: replica drain failures: {codes}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
