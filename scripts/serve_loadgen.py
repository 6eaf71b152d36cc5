#!/usr/bin/env python
"""Concurrent open-loop load generator for the serving subsystem.

Drives the IN-PROCESS ``InferenceServer`` (no sockets — the pure core,
so CI and laptops measure batching/reload behavior, not TCP noise), a
running HTTP server (``--http URL``), or a whole REPLICA FLEET
(``--fleet N``, ISSUE 14: N real serve.py processes behind the
in-process FleetRouter, with kill -9/restart/rolling-promotion chaos
legs and the zero-lost-accepted + exactly-one-answer invariants
hard-asserted), and writes an SLO report JSON:
latency p50/p95/p99, throughput, batch occupancy, reject counts, param
versions observed, and the invariant checks the ISSUE pins:

- ZERO dropped responses: every submitted request resolves (result or
  an explicit rejection — never a hung future);
- ZERO recompiles after warmup (the jit cache-miss counter is read
  before and after the run);
- a mid-run checkpoint hot-swap (``--hot-swap``) completes with both
  param versions observed in responses and zero drops — in-flight
  requests finish on the old params.

Exit code is non-zero when any pinned invariant fails, so CI can run
this directly (tier1.yml serve-smoke).

Typical use::

    python scripts/serve_loadgen.py --make-ckpt /tmp/serve-ckpt
    python scripts/serve_loadgen.py /tmp/serve-ckpt --clients 64 \
        --duration 10 --hot-swap --report slo.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("ckpt_dir", nargs="?", default=None,
                   help="checkpoint directory (see --make-ckpt)")
    p.add_argument("--make-ckpt", metavar="DIR", default="",
                   help="create a tiny synthetic checkpoint at DIR and exit")
    p.add_argument("--http", default="",
                   help="fire at a running HTTP server instead of in-process")
    # ---- fleet chaos mode (ISSUE 14) ----
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="spawn N serve.py replica processes + the "
                        "in-process FleetRouter and drive open-loop "
                        "load THROUGH the router (cgnn_tpu/fleet/); "
                        "hard-asserts zero lost accepted requests and "
                        "exactly one answer per request — the chaos "
                        "legs below kill/restart live replicas "
                        "underneath the load")
    p.add_argument("--fleet-base-port", type=int, default=18460)
    p.add_argument("--fleet-log-dir", default="",
                   help="per-replica log files (default: next to "
                        "--report)")
    p.add_argument("--kill-at", type=float, default=0.0, metavar="FRAC",
                   help="kill -9 the victim replica at FRAC of the "
                        "load duration (0 disables) — in-flight "
                        "requests must be retried onto survivors, "
                        "zero lost")
    p.add_argument("--restart-at", type=float, default=0.0,
                   metavar="FRAC",
                   help="restart the killed replica at FRAC of the "
                        "duration; the router must probe it back in "
                        "and it must answer again (asserted)")
    p.add_argument("--kill-replica", type=int, default=1,
                   help="victim replica index for --kill-at")
    # ---- one fleet cache (ISSUE 20) ----
    p.add_argument("--zipf", type=float, default=0.0, metavar="S",
                   help="Zipf exponent for the request keyset (0 = "
                        "uniform): body i drawn with p ~ 1/(i+1)^S, so "
                        "body 0 is the hottest key — the distribution "
                        "the partitioned fleet cache is built for")
    p.add_argument("--kill-owner", action="store_true",
                   help="pick the --kill-at victim dynamically: the "
                        "cache-ring OWNER of the hottest key "
                        "(overrides --kill-replica; the ring is "
                        "deterministic, so the choice is reproducible)")
    p.add_argument("--expect-cachepart", action="store_true",
                   help="hard-assert the one-fleet-cache invariants: "
                        "owner-affinity routing engaged, zero "
                        "duplicate in-flight misses fleet-wide, "
                        "deterministic re-ownership around the kill, "
                        "and post-restart hit-ratio recovery")
    p.add_argument("--promote-at", type=float, default=0.0,
                   metavar="FRAC",
                   help="commit a NEW checkpoint version at FRAC of "
                        "the duration: every replica's own watcher "
                        "rolls it in mid-load — both versions must "
                        "answer and the fleet must converge "
                        "version-consistent with zero drops (asserted)")
    p.add_argument("--replica-faults", default="", metavar="SPEC",
                   help="CGNN_TPU_FAULTS plan injected into ONE "
                        "replica (--faulty-replica), e.g. "
                        "'slow_dispatch=150' for the hedging leg or "
                        "'dispatch_exc=5' for the 500-retry leg")
    p.add_argument("--faulty-replica", type=int, default=2)
    # ---- closed-loop continual learning (ISSUE 18) ----
    p.add_argument("--label-feedback", type=float, default=0.0,
                   metavar="P",
                   help="fleet mode (ISSUE 18): POST late ground-truth "
                        "labels for this fraction of answered requests "
                        "through the router's /label wire surface "
                        "(--label-delay-ms behind each answer). The "
                        "exactly-once join ledger is hard-asserted: "
                        "every label joins its served record, "
                        "deliberate re-POSTs answer 'already', nothing "
                        "goes unmatched")
    p.add_argument("--label-delay-ms", type=float, default=250.0,
                   help="how far behind each answer its label arrives")
    p.add_argument("--continual", action="store_true",
                   help="fleet mode (ISSUE 18): close the loop — a "
                        "continual.py trainer subprocess tails the "
                        "durable label journal and commits candidate "
                        "checkpoints (round 2 deliberately corrupted "
                        "by a label_noise fault); the canary "
                        "controller pins one replica per candidate, "
                        "shadow-evaluates it on mirrored labeled "
                        "traffic, promotes the good candidate "
                        "fleet-wide through the gated reload watchers "
                        "and rolls the bad one back with a "
                        "flight-recorder bundle naming it. Implies "
                        "--label-feedback 1.0 unless set; all of it "
                        "hard-asserted")
    # ---- the self-driving fleet (ISSUE 17) ----
    p.add_argument("--ramp", default="", metavar="LOW:PEAK",
                   help="fleet mode (ISSUE 17): open-loop fleet-total "
                        "request rate in rps — holds LOW, climbs to "
                        "PEAK by mid-duration, then drops to a calm "
                        "tail. With --autoscale the self-driving "
                        "invariants are hard-asserted: the fleet grew "
                        "BEFORE any request was shed on the way up and "
                        "shrank with zero lost accepted on the way down")
    p.add_argument("--autoscale", action="store_true",
                   help="fleet mode (ISSUE 17): run the SLO-signal-"
                        "driven autoscaler over the replica set "
                        "(hysteresis decision core, prewarmed spare "
                        "pool, drain-then-reap scale-down); drained "
                        "exits must be recorded as scale events, never "
                        "incidents (asserted)")
    p.add_argument("--min-replicas", type=int, default=1,
                   help="autoscaler lower bound")
    p.add_argument("--max-replicas", type=int, default=4,
                   help="autoscaler upper bound")
    p.add_argument("--warm-pool", type=int, default=1,
                   help="pre-compiled unrouted spares kept warm "
                        "(prewarmed before load, so a scale-up is a "
                        "routing-table add, not a cold boot)")
    p.add_argument("--remediate", action="store_true",
                   help="fleet mode (ISSUE 17): attach the flight-"
                        "recorder-driven remediator; a wedged replica "
                        "(--replica-faults wedge_flush=N — health "
                        "plane answers, dispatch plane trips its "
                        "breaker) must be replaced-and-drained with "
                        "zero lost accepted, and every action's "
                        "remediation.jsonl entry must name the "
                        "evidence bundle that justified it (asserted; "
                        "needs --trace-ring > 0)")
    p.add_argument("--retries", type=int, default=3,
                   help="fleet router max extra attempts per request")
    p.add_argument("--hedge-ms", type=float, default=None,
                   help="fleet hedge point in ms (default auto: 2x "
                        "replica rolling p99; 0 disables)")
    p.add_argument("--breaker-k", type=int, default=3)
    p.add_argument("--breaker-cooldown", type=float, default=2.0)
    p.add_argument("--expect-hedges", action="store_true",
                   help="fail unless the router actually hedged (the "
                        "slow-replica leg)")
    p.add_argument("--expect-retries", action="store_true",
                   help="fail unless the router actually retried (the "
                        "kill / dispatch-exception legs)")
    p.add_argument("--expect-trace-join", action="store_true",
                   help="fleet mode (ISSUE 15): hard-assert the "
                        "cross-process observability layer — the "
                        "joined fleet trace must contain >= 1 "
                        "retried/hedged request with spans from >= 2 "
                        "processes, AND a flight-recorder bundle must "
                        "exist whose own joined trace shows the same "
                        "(the kill/hedge chaos legs set this)")
    p.add_argument("--trace-ring", type=int, default=65536, metavar="N",
                   help="span-ring size for the server/router under "
                        "test (0 disables the cross-process trace "
                        "layer — the PERF.md §18 A/B baseline)")
    p.add_argument("--slo-report", action="store_true",
                   help="fleet mode (ISSUE 16): run the metrics-truth "
                        "leg — second-scale burn-rate rules on the "
                        "router's SLO engine; an injected 5xx burst "
                        "(--replica-faults 'dispatch_exc=START:COUNT', "
                        "pair with a high --breaker-k so the burst is "
                        "not breaker-quenched) must walk the alert "
                        "inactive -> pending -> firing -> resolved AND "
                        "dump a flight-recorder bundle whose manifest "
                        "names the alert; plus the fleet histogram "
                        "truth check: the router's /metrics/fleet "
                        "merge must be bit-identical to merging every "
                        "replica's own scrape, cover every answered "
                        "request, and agree with the client-measured "
                        "latency distribution (all hard-asserted)")
    p.add_argument("--clients", type=int, default=64)
    p.add_argument("--duration", type=float, default=10.0,
                   help="seconds of open-loop load")
    p.add_argument("--rate", type=float, default=0.0,
                   help="per-client requests/sec (0 = closed-loop as fast "
                        "as responses return)")
    # ---- mixed-priority open-loop load (ISSUE 19) ----
    p.add_argument("--priority-mix", default="", metavar="SPEC",
                   help="per-class open-loop arrival rates as "
                        "'interactive=40,scavenger=60' (total "
                        "requests/s across the client pool; classes "
                        "absent from the spec send nothing). Each "
                        "request draws its class rate-weighted; the "
                        "report breaks latency down per class")
    p.add_argument("--class-slo-ms", default="", metavar="SPEC",
                   help="HARD per-class p99 SLOs as 'interactive=250': "
                        "the run fails (exit != 0) when a class's "
                        "measured p99 exceeds its bound (needs "
                        "--priority-mix)")
    p.add_argument("--class-timeout-ms", default="", metavar="SPEC",
                   help="per-class request deadlines (classes absent "
                        "fall back to --timeout-ms)")
    p.add_argument("--class-wait-ms", default="", metavar="SPEC",
                   help="per-class batcher wait budgets, passed to the "
                        "in-proc server / every fleet replica")
    p.add_argument("--tenants", default="", metavar="SPEC",
                   help="WFQ tenants as 'name=weight,...': each request "
                        "carries a uniformly-drawn tenant; the weights "
                        "ride to the in-proc server / fleet replicas")
    p.add_argument("--no-backfill", action="store_true",
                   help="disable padding-slack backfill on the in-proc "
                        "server / fleet replicas (the A/B baseline)")
    p.add_argument("--expect-backfill", action="store_true",
                   help="fail unless lower-class responses actually "
                        "rode a higher-class flush's padding slack")
    p.add_argument("--structures", type=int, default=512,
                   help="distinct synthetic structures to draw requests from")
    p.add_argument("--timeout-ms", type=float, default=30000.0,
                   help="per-request deadline handed to the server")
    p.add_argument("--hot-swap", action="store_true",
                   help="commit a new checkpoint at half-duration and "
                        "assert a zero-drop version transition")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--rungs", type=int, default=3)
    p.add_argument("--compact", choices=["auto", "on", "off"],
                   default="auto",
                   help="compact-staged serving (ISSUE 4): auto = "
                        "accelerator backends only; on/off force the "
                        "A/B legs")
    p.add_argument("--wire", choices=["featurized", "raw", "mixed"],
                   default="featurized",
                   help="request wire format (ISSUE 11): 'raw' submits "
                        "wire-form (positions, lattice, species) "
                        "structures — the server's in-program neighbor "
                        "search builds the graph; 'mixed' draws "
                        "raw/featurized 50:50 per request (exercises "
                        "the batcher's form-boundary cut). Both force "
                        "raw-wire serving on (CPU CI never picks it "
                        "under 'auto'). The report breaks responses "
                        "down per wire and HARD-ASSERTS zero "
                        "in-program cap overflows on the calibrated "
                        "ladder (unless --raw-overflow-probe)")
    p.add_argument("--raw-overflow-probe", action="store_true",
                   help="disable the host image-cap pre-check and "
                        "submit one tiny-cell structure that the "
                        "IN-PROGRAM overflow flag must catch and route "
                        "to the featurized fallback (asserted); "
                        "in-proc raw/mixed modes only")
    p.add_argument("--pack-workers", type=int, default=None,
                   help="server pack pipeline threads (0 = in-line pack, "
                        "the pre-ISSUE-4 worker; default follows the "
                        "backend like --compact auto)")
    p.add_argument("--devices", default="auto", metavar="{auto,N}",
                   help="device-parallel dispatch set (ISSUE 5): 'auto' "
                        "= all local devices on accelerators, one on "
                        "CPU; an integer forces that many anywhere. "
                        "With a forced N > 1 the loadgen HARD-ASSERTS "
                        "that every device answered responses")
    p.add_argument("--engine", choices=["auto", "mesh", "threads"],
                   default="auto",
                   help="multi-device execution layer (ISSUE 10): 'mesh' "
                        "(the auto default with >1 device) = one "
                        "batch-sharded jitted dispatch covers all "
                        "devices, device_id = the shard that computed "
                        "the row; 'threads' = the ISSUE-5 per-device "
                        "dispatch threads. The per-device "
                        "answered/version hard asserts apply to BOTH — "
                        "under mesh they read the shard-level stats")
    p.add_argument("--precision", default="f32", metavar="TIERS",
                   help="comma-separated precision tiers (f32,bf16,int8): "
                        "the server warms ALL of them, each request "
                        "draws one uniformly — mixed-tier traffic "
                        "exercises the batcher's tier-boundary cut; the "
                        "report breaks responses down per tier")
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--max-queue", type=int, default=4096)
    p.add_argument("--report", default="slo_report.json")
    p.add_argument("--seed", type=int, default=0)
    # ---- live observability plane (ISSUE 6) ----
    p.add_argument("--telemetry", choices=["off", "epoch"], default="off",
                   help="serving telemetry level: 'epoch' turns the live "
                        "plane fully on (span tracing + metrics.jsonl + "
                        "trace.json under --telemetry-dir) — the A/B leg "
                        "for the tracing-overhead measurement (PERF §13)")
    p.add_argument("--telemetry-dir", default="",
                   help="artifact dir for --telemetry epoch (default: a "
                        "temp dir next to the report)")
    p.add_argument("--no-scrape", action="store_true",
                   help="skip the mid-load /metrics scrape + the "
                        "scraped-vs-measured p99 agreement assertion")
    p.add_argument("--scrape-tolerance", type=float, default=0.5,
                   help="relative p99 disagreement tolerated between the "
                        "mid-load scrape and the loadgen's own "
                        "measurement (plus a 15 ms absolute floor)")
    p.add_argument("--profile-mid", action="store_true",
                   help="fire one bounded on-demand profile capture "
                        "mid-load (POST /profile on --http, the gated "
                        "ProfileCapture in-process) and assert it wrote "
                        "a non-empty artifact")
    return p


def make_synth_ckpt(ckpt_dir: str, seed: int = 0) -> None:
    """Commit a tiny trained-for-zero-epochs checkpoint (the serving
    fixture: real model config + normalizer + versioned-save protocol)."""
    import jax
    import numpy as np

    from cgnn_tpu.config import DataConfig, ModelConfig, build_model
    from cgnn_tpu.data.dataset import load_synthetic
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.train import (
        CheckpointManager,
        Normalizer,
        create_train_state,
        make_optimizer,
    )

    model_cfg = ModelConfig(atom_fea_len=16, n_conv=2, h_fea_len=32,
                            dense_m=12)
    data_cfg = DataConfig(radius=6.0, max_num_nbr=12)
    graphs = load_synthetic(64, data_cfg.featurize_config(), seed=seed)
    nc, ec = capacities_for(graphs, 16, dense_m=12, snug=True)
    example = next(batch_iterator(graphs, 16, nc, ec, dense_m=12, in_cap=0,
                                  snug=True))
    model = build_model(model_cfg, data_cfg)
    state = create_train_state(
        model, example, make_optimizer(),
        Normalizer.fit(np.stack([g.target for g in graphs])),
        rng=jax.random.key(seed),
    )
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(state, {"model": model_cfg.to_meta(), "data": data_cfg.to_meta(),
                     "task": "regression", "epoch": 0})
    mgr.close()
    print(f"committed synthetic checkpoint under {ckpt_dir} "
          f"({mgr.newest_committed()})")


def _perturbed_save(manager, template_state) -> None:
    """Commit a new version with visibly different params (the hot-swap
    fixture: predictions must change across the swap)."""
    import jax
    import numpy as np

    def nudge(x):
        a = np.asarray(x)
        if np.issubdtype(a.dtype, np.floating):
            return (a * 1.05 + 0.01).astype(a.dtype)
        return a

    new_state = template_state.replace(
        params=jax.tree_util.tree_map(nudge, template_state.params)
    )
    manager.save(new_state, dict(manager.read_meta("latest"), epoch=-1))
    manager.wait()


class _ClientStats:
    def __init__(self):
        self.lock = threading.Lock()
        self.latencies: list[float] = []
        self.versions: dict[str, int] = {}
        self.occupancies: list[float] = []
        self.submitted = 0
        self.answered = 0
        self.cached = 0
        self.rejected: dict[str, int] = {}
        self.dropped = 0
        self.errors: list[str] = []
        self.device_responses: dict[int, int] = {}
        # precision tier -> responses (the quantized-serving A/B record)
        self.precision_responses: dict[str, int] = {}
        # device_id -> param versions it answered with (the per-device
        # hot-swap consistency record)
        self.device_versions: dict[int, set] = {}
        # per-request tracing (ISSUE 6): every response must carry a
        # trace id, and co-batched requests must carry DISTINCT ids —
        # global uniqueness across the run covers both
        self.trace_ids: set = set()
        self.missing_trace = 0
        self.flush_ids: set = set()
        # wire form -> responses ('raw' | 'featurized'; ISSUE 11)
        self.wire_responses: dict[str, int] = {}
        # priority-class serving (ISSUE 19): per-class latencies (the
        # per-class p99 SLO asserts read these), per-class and
        # per-tenant answer counts, and answers that rode another
        # class's padding slack
        self.class_latencies: dict[str, list] = {}
        self.class_responses: dict[str, int] = {}
        self.tenant_responses: dict[str, int] = {}
        self.backfilled = 0


def _priority_plan(args) -> dict | None:
    """The mixed-priority load plan from the flag specs (ISSUE 19):
    rate-weighted class draw, per-class deadlines, tenant pool. None
    when --priority-mix is off."""
    from cgnn_tpu.serve.batcher import CLASSES, parse_kv_spec

    if not args.priority_mix:
        return None
    rates = parse_kv_spec(args.priority_mix)
    unknown = sorted(c for c in rates if c not in CLASSES)
    if unknown:
        raise SystemExit(
            f"--priority-mix: unknown classes {unknown} "
            f"(have: {list(CLASSES)})")
    rates = {c: float(r) for c, r in rates.items() if r > 0}
    if not rates:
        raise SystemExit("--priority-mix: no class with a rate > 0")
    total = sum(rates.values())
    classes = sorted(rates, key=lambda c: -rates[c])
    return {
        "rates": rates,
        "total": total,
        "classes": classes,
        "probs": [rates[c] / total for c in classes],
        "timeouts": parse_kv_spec(args.class_timeout_ms),
        "tenants": sorted(parse_kv_spec(args.tenants))
        if args.tenants else [],
    }


def _draw_priority(plan: dict, rng) -> tuple[str, str | None]:
    """One request's (class, tenant) draw: class rate-weighted,
    tenant uniform over the pool (None without --tenants)."""
    kl = plan["classes"][int(rng.choice(len(plan["classes"]),
                                        p=plan["probs"]))]
    tn = (plan["tenants"][int(rng.integers(len(plan["tenants"])))]
          if plan["tenants"] else None)
    return kl, tn


def _note_priority_answer(stats: _ClientStats, klass: str,
                          tenant: str | None, latency_ms: float,
                          backfilled: bool) -> None:
    """Record one answered request's class accounting. Caller holds
    ``stats.lock``."""
    stats.class_responses[klass] = (
        stats.class_responses.get(klass, 0) + 1)
    stats.class_latencies.setdefault(klass, []).append(
        float(latency_ms))
    if tenant:
        stats.tenant_responses[tenant] = (
            stats.tenant_responses.get(tenant, 0) + 1)
    if backfilled:
        stats.backfilled += 1


def _priority_report(stats: _ClientStats, plan: dict) -> dict:
    import numpy as np

    with stats.lock:
        by_cls = {c: list(v) for c, v in stats.class_latencies.items()}
        out = {
            "mix_rps": plan["rates"],
            "responses_by_class": dict(sorted(
                stats.class_responses.items())),
            "responses_by_tenant": dict(sorted(
                stats.tenant_responses.items())),
            "backfilled_responses": stats.backfilled,
        }
    out["latency_ms_by_class"] = {
        c: {
            "p50": float(np.percentile(np.asarray(lat), 50)),
            "p99": float(np.percentile(np.asarray(lat), 99)),
            "count": len(lat),
        }
        for c, lat in sorted(by_cls.items()) if lat
    }
    return out


def _measured_p99(stats: _ClientStats) -> float:
    import numpy as np

    with stats.lock:
        lat = list(stats.latencies)
    return float(np.percentile(np.asarray(lat), 99)) if lat else 0.0


def _scrape_check(text: str, scraped_p99: float,
                  measured_p99: float, tolerance: float) -> dict:
    """Validate one /metrics scrape: the exposition format must parse,
    the three metric families must be present, and the scraped rolling
    p99 must agree with the loadgen's own measurement within tolerance
    (relative, with a 15 ms absolute floor — the two windows and the
    two measurement points differ, so exact equality is not the bar)."""
    from cgnn_tpu.observe.export import parse_prometheus_text

    out = {"scraped_p99_ms": scraped_p99, "measured_p99_ms": measured_p99}
    try:
        fams = parse_prometheus_text(text)
        out["families"] = len(fams)
        out["parse_ok"] = True
    except ValueError as e:
        out["parse_ok"] = False
        out["parse_error"] = str(e)
        return out
    missing = [p for p in ("cgnn_serve_", "cgnn_device", "cgnn_pipeline_")
               if not any(f.startswith(p) for f in fams)]
    out["missing_families"] = missing
    tol = max(15.0, tolerance * max(scraped_p99, measured_p99))
    out["tolerance_ms"] = round(tol, 2)
    out["agree"] = abs(scraped_p99 - measured_p99) <= tol
    return out


def _fleet_hist_check(router, procs, stats) -> dict:
    """The metrics-truth pin (ISSUE 16), run AFTER the load quiesces so
    the replica histograms are static: scrape every replica's /metrics
    directly over real HTTP, merge the mergeable ``*_hist`` families
    locally, and compare against the router's own ``/metrics/fleet``
    scrape-and-merge — bucket counts AND sums must be bit-identical
    (integer counts add associatively; the exposition round-trips
    floats via repr). Then the merged latency histogram is checked
    against the clients' OWN measurements: its total count must cover
    every answered request (hedge stragglers and retried serves may add
    more, never fewer) and its median must agree with the measured p50
    within bucket resolution (x10^(1/6) ~ 1.47) plus a router/HTTP
    overhead margin."""
    import urllib.request

    import numpy as np

    from cgnn_tpu.observe.export import parse_prometheus_text
    from cgnn_tpu.observe.hist import (
        merge_snapshot_maps,
        quantile_from_snapshot,
    )

    out: dict = {"replicas_scraped": 0}
    fam_maps: dict[str, list] = {}
    for p in procs:
        try:
            with urllib.request.urlopen(p.base_url + "/metrics",
                                        timeout=10.0) as resp:
                text = resp.read().decode()
            fams = parse_prometheus_text(text)
        except Exception as e:  # noqa: BLE001 — reported as a failure
            out.setdefault("scrape_errors", []).append(repr(e))
            continue
        out["replicas_scraped"] += 1
        for name, fam in fams.items():
            if "histogram" in fam:
                fam_maps.setdefault(name, []).append(fam["histogram"])
    pooled = {name: merge_snapshot_maps(maps)
              for name, maps in fam_maps.items()}

    mismatches = []
    try:
        fleet_fams = parse_prometheus_text(router.fleet_metrics_text())
    except ValueError as e:
        fleet_fams = {}
        mismatches.append(f"/metrics/fleet did not parse: {e}")
    for name, merged in pooled.items():
        fhist = fleet_fams.get(name, {}).get("histogram")
        if fhist is None:
            mismatches.append(f"{name}: missing from /metrics/fleet")
            continue
        for key, snap in merged.items():
            fsnap = fhist.get(key)
            if fsnap is None:
                mismatches.append(f"{name}{{{key}}}: label set missing "
                                  f"from the fleet merge")
            elif (fsnap["counts"] != snap["counts"]
                  or fsnap["count"] != snap["count"]
                  or fsnap["sum"] != snap["sum"]):
                mismatches.append(
                    f"{name}{{{key}}}: fleet merge != pooled replica "
                    f"scrapes (count {fsnap['count']} vs "
                    f"{snap['count']}, sum {fsnap['sum']} vs "
                    f"{snap['sum']})")
    out["hist_families"] = sorted(pooled)
    out["merge_mismatches"] = mismatches
    out["merge_bitexact"] = not mismatches and bool(pooled)

    # the distribution truth is checked against the ROUTER's own fleet
    # latency histogram: it observes the same per-request total_ms the
    # clients record, so the count must match EXACTLY and the median
    # must agree within bucket resolution. The replica-side serve
    # histogram measures a different quantity (serve-core latency —
    # sub-ms on a cache hit) so it only gets a coverage bound.
    with stats.lock:
        lats = list(stats.latencies)
        answered = stats.answered
    fleet_lat = None
    try:
        router_fams = parse_prometheus_text(
            router.registry.prometheus_text())
        fleet_lat = router_fams.get(
            "cgnn_fleet_latency_ms_hist", {}).get("histogram", {}).get("")
    except ValueError as e:
        out["router_scrape_error"] = str(e)
    serve_snap = pooled.get("cgnn_serve_latency_ms_hist", {}).get("")
    if fleet_lat is not None and lats:
        hist_p50 = quantile_from_snapshot(fleet_lat, 0.5)
        measured_p50 = float(np.percentile(np.asarray(lats), 50))
        # one log-spaced bucket of slack (x10^(1/6) ~ 1.47, padded to
        # 1.6) plus a small absolute floor for sub-ms medians
        lo = hist_p50 / 1.6 - 5.0
        hi = hist_p50 * 1.6 + 5.0
        out["latency_truth"] = {
            "hist_count": fleet_lat["count"],
            "answered": answered,
            "count_exact": fleet_lat["count"] == answered,
            "hist_p50_ms": round(hist_p50, 3),
            "measured_p50_ms": round(measured_p50, 3),
            "p50_agree": lo <= measured_p50 <= hi,
            "replica_hist_count": (serve_snap or {}).get("count"),
            "count_covers_answered": (
                serve_snap is not None
                and serve_snap["count"] >= answered),
        }
    else:
        out["latency_truth"] = {
            "error": "no cgnn_fleet_latency_ms_hist on the router",
            "count_exact": False,
            "count_covers_answered": False,
            "p50_agree": False,
        }
    return out


def _slo_bundle_manifests(flightrec_dir: str) -> list:
    """Flight-recorder bundles whose MANIFEST names an SLO alert as the
    trigger reason — the ISSUE-16 page-as-evidence-bundle contract."""
    found = []
    try:
        names = sorted(os.listdir(flightrec_dir))
    except OSError:
        return found
    for d in names:
        mpath = os.path.join(flightrec_dir, d, "manifest.json")
        try:
            with open(mpath) as f:
                m = json.load(f)
        except (OSError, ValueError):
            continue
        if str(m.get("reason", "")).startswith("slo_burn_"):
            found.append({"bundle": d, "reason": m["reason"],
                          "detail": m.get("detail", "")})
    return found


def _run_inproc(args) -> dict:
    import tempfile

    import numpy as np

    from cgnn_tpu.observe import Telemetry
    from cgnn_tpu.serve.batcher import ServeRejection, parse_kv_spec
    from cgnn_tpu.serve.server import load_server

    if args.telemetry != "off":
        tdir = args.telemetry_dir or tempfile.mkdtemp(prefix="loadgen-obs-")
        telemetry = Telemetry(args.telemetry, tdir)
    else:
        telemetry = Telemetry.disabled()
    want_raw = args.wire in ("raw", "mixed")
    server, parts = load_server(
        args.ckpt_dir,
        batch_size=args.batch_size,
        rungs=args.rungs,
        telemetry=telemetry,
        max_queue=args.max_queue,
        max_wait_ms=args.max_wait_ms,
        compact=args.compact,
        # raw/mixed legs FORCE raw-wire serving (CPU CI would never
        # pick it under 'auto' — the host IS the device there)
        wire="raw" if want_raw else "auto",
        raw_precheck=not args.raw_overflow_probe,
        pack_workers=args.pack_workers,
        devices=args.devices,
        engine=args.engine,
        precision=args.precision,
        default_timeout_ms=args.timeout_ms,
        cache_size=0,  # the loadgen reuses structures; caching would
                       # let most requests skip the batcher under test
        watch=args.hot_swap,
        poll_interval_s=0.2,
        trace_ring=args.trace_ring,
        # priority-class serving knobs (ISSUE 19)
        class_max_wait_ms=(parse_kv_spec(args.class_wait_ms)
                           if args.class_wait_ms else None),
        backfill=not args.no_backfill,
        wfq_weights=(parse_kv_spec(args.tenants)
                     if args.tenants else None),
    )
    if args.profile_mid:
        server.enable_profiling(tempfile.mkdtemp(prefix="loadgen-prof-"))
    server.start()
    compiles_at_warm = server._jit_cache_size()

    from cgnn_tpu.data.dataset import load_synthetic
    from cgnn_tpu.data.rawbatch import raw_from_graph

    pool = load_synthetic(args.structures, parts["data_cfg"].
                          featurize_config(), seed=args.seed + 1,
                          keep_geometry=want_raw)
    pool = [g for g in pool if server.shape_set.admits(g)]
    raw_pool = []
    if want_raw:
        raw_pool = [r for r in (raw_from_graph(g) for g in pool)
                    if r is not None]

    stats = _ClientStats()
    stop = threading.Event()
    plan = _priority_plan(args)

    def client(ci: int):
        rng = np.random.default_rng(args.seed + ci)
        interval = 1.0 / args.rate if args.rate > 0 else 0.0
        if plan is not None:
            # open-loop mixed-priority load: the POOL sends plan.total
            # rps, so each client paces at clients/total
            interval = args.clients / plan["total"]
        tiers = [t.strip() for t in args.precision.split(",") if t.strip()]
        raw_share = {"featurized": 0.0, "mixed": 0.5, "raw": 1.0}[args.wire]
        while not stop.is_set():
            if raw_pool and rng.random() < raw_share:
                g = raw_pool[int(rng.integers(len(raw_pool)))]
            else:
                g = pool[int(rng.integers(len(pool)))]
            # uniform random tier per request: with more than one tier
            # this exercises the batcher's tier-boundary flush cut under
            # real concurrency (a random draw can starve a tier on very
            # short runs — the smoke leg's duration covers it)
            tier = tiers[int(rng.integers(len(tiers)))] if tiers else None
            kl = tn = None
            timeout_ms = args.timeout_ms
            if plan is not None:
                kl, tn = _draw_priority(plan, rng)
                timeout_ms = plan["timeouts"].get(kl, args.timeout_ms)
            t0 = time.monotonic()
            try:
                with stats.lock:
                    stats.submitted += 1
                fut = server.submit(g, timeout_ms=timeout_ms,
                                    precision=tier, klass=kl, tenant=tn)
                res = fut.result(timeout=timeout_ms / 1000.0 + 60.0)
            except ServeRejection as e:
                with stats.lock:
                    stats.rejected[e.reason] = (
                        stats.rejected.get(e.reason, 0) + 1
                    )
                continue
            except TimeoutError:
                with stats.lock:
                    stats.dropped += 1  # a hung future IS a drop
                continue
            except Exception as e:  # noqa: BLE001 — report, don't die
                with stats.lock:
                    stats.errors.append(repr(e))
                continue
            with stats.lock:
                stats.answered += 1
                stats.latencies.append(res.latency_ms)
                stats.versions[res.param_version] = (
                    stats.versions.get(res.param_version, 0) + 1
                )
                tier_got = getattr(res, "precision", "f32")
                stats.precision_responses[tier_got] = (
                    stats.precision_responses.get(tier_got, 0) + 1
                )
                di = getattr(res, "device_id", 0)
                stats.device_responses[di] = (
                    stats.device_responses.get(di, 0) + 1
                )
                stats.device_versions.setdefault(di, set()).add(
                    res.param_version
                )
                tid = getattr(res, "trace_id", "")
                if tid:
                    stats.trace_ids.add(tid)
                else:
                    stats.missing_trace += 1
                fid = getattr(res, "flush_id", "")
                if fid:
                    stats.flush_ids.add(fid)
                w = getattr(res, "wire", "featurized")
                stats.wire_responses[w] = stats.wire_responses.get(w, 0) + 1
                if plan is not None:
                    _note_priority_answer(
                        stats, getattr(res, "klass", "interactive"), tn,
                        res.latency_ms,
                        getattr(res, "backfilled", False))
                if res.cached:
                    stats.cached += 1
                else:
                    stats.occupancies.append(res.batch_occupancy)
            if interval:
                stop.wait(max(0.0, interval - (time.monotonic() - t0)))

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"loadgen-client-{i}")
               for i in range(args.clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()

    # mid-load plane checks, each on its own timer thread so the load
    # keeps running underneath — that is the whole point of a LIVE plane
    scrape_result: dict = {}
    profile_result: dict = {}

    def mid_scrape():
        time.sleep(args.duration * 0.6)
        text = server.registry.prometheus_text()
        rolling = server.rolling_quantiles()
        scrape_result.update(
            at_s=round(time.monotonic() - t_start, 2),
            text_bytes=len(text),
            rolling=rolling,
            text=text,
            # the loadgen's own p99 over everything answered SO FAR —
            # the same window the 60 s rolling scrape covers; comparing
            # against the end-of-run p99 would mix in traffic the
            # scrape could not have seen yet
            measured_now_p99=_measured_p99(stats),
        )

    def mid_profile():
        time.sleep(args.duration * 0.4)
        from cgnn_tpu.observe import ProfileBusy

        try:
            profile_result.update(server.profiler.capture(0.5), ok=True)
        except ProfileBusy as e:
            profile_result.update(ok=False, error=str(e))
        except Exception as e:  # noqa: BLE001 — reported as a failure
            profile_result.update(ok=False, error=repr(e))

    checkers = []
    if not args.no_scrape:
        checkers.append(threading.Thread(target=mid_scrape, daemon=True,
                                         name="loadgen-scrape"))
    if args.profile_mid:
        checkers.append(threading.Thread(target=mid_profile, daemon=True,
                                         name="loadgen-profile"))
    for t in checkers:
        t.start()

    # trace-id probe: a request submitted with an explicit id must echo
    # it back on its result (the X-Request-Id contract, in-process form)
    probe_trace = None
    if pool:
        try:
            probe = server.submit(pool[0], timeout_ms=args.timeout_ms,
                                  trace_id="loadgen-probe-1")
            probe_trace = probe.result(
                timeout=args.timeout_ms / 1000.0 + 60.0).trace_id
        except Exception as e:  # noqa: BLE001 — reported as a failure
            probe_trace = f"ERROR: {e!r}"

    # raw-wire probes (ISSUE 11), fired alongside the load:
    # - parity: ONE structure submitted both raw and featurized must
    #   agree to f32 roundoff (the two wire forms run different warmed
    #   programs — the in-program search vs the host featurizer);
    # - overflow (with --raw-overflow-probe): a tiny cell needing more
    #   periodic images than the calibrated caps, admitted past the
    #   disabled pre-check — the IN-PROGRAM flag must catch it and the
    #   featurized fallback answer it (wire='featurized', counter > 0).
    raw_probe: dict = {}
    if want_raw and raw_pool:
        try:
            pg, pr = next(
                (g, r) for g, r in ((g, raw_from_graph(g)) for g in pool)
                if r is not None and server.shape_set.admits_raw(r)
            )
            r_raw = server.submit(pr, timeout_ms=args.timeout_ms)
            r_feat = server.submit(pg, timeout_ms=args.timeout_ms)
            a = r_raw.result(args.timeout_ms / 1000.0 + 60.0)
            b = r_feat.result(args.timeout_ms / 1000.0 + 60.0)
            diff = float(np.abs(a.prediction - b.prediction).max())
            raw_probe["parity"] = {
                "wire_a": a.wire, "wire_b": b.wire,
                "max_abs_diff": diff,
                "ok": a.wire == "raw" and diff < 1e-3,
            }
        except Exception as e:  # noqa: BLE001 — reported as a failure
            raw_probe["parity"] = {"ok": False, "error": repr(e)}
    if args.raw_overflow_probe and want_raw:
        from cgnn_tpu.data.rawbatch import RawStructure

        tiny = RawStructure(
            np.array([[0.2, 0.2, 0.2], [0.7, 0.6, 0.5]]),
            np.eye(3) * 1.8, np.array([6, 8], np.int32),
            cif_id="overflow-probe",
        )
        try:
            res = server.predict(tiny, timeout_ms=args.timeout_ms)
            raw_probe["overflow"] = {
                "wire": res.wire,
                "ok": res.wire == "featurized",
            }
        except Exception as e:  # noqa: BLE001 — reported as a failure
            raw_probe["overflow"] = {"ok": False, "error": repr(e)}

    swapped_to = None
    if args.hot_swap:
        time.sleep(args.duration / 2)
        state, _ = server.param_store.get()
        _perturbed_save(parts["manager"], state)
        # the watcher polls at 0.2 s; give it a moment inside the window
        deadline = time.monotonic() + max(5.0, args.duration / 4)
        while time.monotonic() < deadline:
            if server._watcher is not None and server._watcher.swaps:
                swapped_to = server.param_store.version
                break
            time.sleep(0.05)

    while time.monotonic() - t_start < args.duration:
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=args.timeout_ms / 1000.0 + 90.0)
    for t in checkers:
        t.join(timeout=30.0)
    wall = time.monotonic() - t_start
    server.drain(timeout_s=60.0)
    compiles_at_end = server._jit_cache_size()
    if telemetry.enabled:
        telemetry.close()  # exports trace.json with the request spans

    lat = np.asarray(stats.latencies) if stats.latencies else np.zeros(1)
    report = {
        "mode": "inproc",
        "clients": args.clients,
        "duration_s": round(wall, 2),
        "submitted": stats.submitted,
        "answered": stats.answered,
        "rejected": stats.rejected,
        "dropped": stats.dropped,
        "client_errors": stats.errors[:10],
        "throughput_rps": round(stats.answered / wall, 1),
        "latency_ms": {
            "p50": float(np.percentile(lat, 50)),
            "p95": float(np.percentile(lat, 95)),
            "p99": float(np.percentile(lat, 99)),
            "mean": float(lat.mean()),
        },
        "batch_occupancy_mean": (
            float(np.mean(stats.occupancies)) if stats.occupancies else 0.0
        ),
        "param_versions": stats.versions,
        "precision": {
            "requested": args.precision,
            "responses_by_tier": dict(sorted(
                stats.precision_responses.items())),
        },
        "wire": {
            "requested": args.wire,
            "responses_by_wire": dict(sorted(
                stats.wire_responses.items())),
            "raw_pool": len(raw_pool),
            "probes": raw_probe,
        },
        "devices": {
            "requested": str(args.devices),
            "engine": server.engine,
            "count": len(server.device_set),
            "responses_by_device": {
                str(k): v
                for k, v in sorted(stats.device_responses.items())
            },
            "versions_by_device": {
                str(k): sorted(v)
                for k, v in sorted(stats.device_versions.items())
            },
        },
        "hot_swap": {
            "requested": bool(args.hot_swap),
            "swapped_to": swapped_to,
            "watcher_swaps": (server._watcher.swaps
                              if server._watcher else 0),
            "watcher_skips": (server._watcher.skips
                              if server._watcher else 0),
        },
        "compiles": {
            "at_warm": compiles_at_warm,
            "at_end": compiles_at_end,
            "after_warm": (compiles_at_end or 0) - (compiles_at_warm or 0),
        },
        "tracing": {
            "unique_trace_ids": len(stats.trace_ids),
            "missing_trace_ids": stats.missing_trace,
            "flushes_observed": len(stats.flush_ids),
            "probe_trace_id": probe_trace,
            "telemetry": args.telemetry,
            "trace_json": (os.path.join(telemetry.log_dir, "trace.json")
                           if telemetry.enabled else None),
        },
        "server_stats": server.stats(),
    }
    if plan is not None:
        report["priority"] = {
            **_priority_report(stats, plan),
            # the server's own backfill accounting (numerator over the
            # slack the higher-class flushes offered)
            "padding_fill_share": report["server_stats"]["priority"][
                "padding_fill_share"],
            "backfill_enabled": report["server_stats"]["priority"][
                "backfill"],
        }
    if scrape_result:
        report["metrics_scrape"] = {
            "at_s": scrape_result["at_s"],
            "text_bytes": scrape_result["text_bytes"],
            "final_measured_p99_ms": _measured_p99(stats),
            **_scrape_check(
                scrape_result["text"],
                scrape_result.get("rolling", {}).get("p99", 0.0),
                scrape_result.get("measured_now_p99", 0.0),
                args.scrape_tolerance,
            ),
        }
    if profile_result:
        report["profile"] = profile_result
    return report


def _commit_new_version(ckpt_dir: str, seed: int) -> str:
    """Commit a fresh param version into the fleet's shared checkpoint
    directory (the rolling-promotion fixture): same configs as the
    resident checkpoint, different init — predictions visibly change,
    every replica's watcher rolls it in. Returns the new save name."""
    import jax
    import numpy as np

    from cgnn_tpu.config import DataConfig, ModelConfig, build_model
    from cgnn_tpu.data.dataset import load_synthetic
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.train import (
        CheckpointManager,
        Normalizer,
        create_train_state,
        make_optimizer,
    )

    mgr = CheckpointManager(ckpt_dir)
    meta = mgr.read_meta("latest")
    model_cfg = ModelConfig.from_meta(meta["model"])
    data_cfg = DataConfig.from_meta(meta["data"])
    graphs = load_synthetic(64, data_cfg.featurize_config(), seed=seed)
    nc, ec = capacities_for(graphs, 16, dense_m=model_cfg.dense_m,
                            snug=True)
    example = next(batch_iterator(graphs, 16, nc, ec,
                                  dense_m=model_cfg.dense_m, in_cap=0,
                                  snug=True))
    model = build_model(model_cfg, data_cfg, meta.get("task", "regression"))
    state = create_train_state(
        model, example, make_optimizer(),
        Normalizer.fit(np.stack([g.target for g in graphs])),
        rng=jax.random.key(seed),
    )
    mgr.save(state, dict(meta, epoch=int(meta.get("epoch", 0)) + 1))
    mgr.wait()
    name = mgr.newest_committed()
    mgr.close()
    return name


def _run_fleet(args) -> dict:
    """The fleet chaos harness (ISSUE 14): N real serve.py replica
    processes behind the in-process FleetRouter, open-loop load driven
    THROUGH the router while the chaos legs kill -9 / restart replicas
    and roll a checkpoint promotion underneath it.

    The invariants hard-asserted here (main() exits non-zero):

    - ZERO lost accepted requests: every dispatch resolves to exactly
      one typed outcome — an answer or an explicit rejection — even
      while a replica dies mid-request (retried onto survivors);
    - EXACTLY ONE answer per request: distinct trace ids == answered
      and the router's duplicate-answer counter stays 0, under retries
      AND hedges (the idempotency key is the trace id every attempt
      shares);
    - a killed replica is probed back in after restart and answers
      again; a rolling promotion serves BOTH versions mid-roll and
      converges version-consistent fleet-wide."""
    import numpy as np

    from cgnn_tpu.config import DataConfig
    from cgnn_tpu.fleet.replica import ReplicaState
    from cgnn_tpu.fleet.router import FleetRouter
    from cgnn_tpu.fleet.spawn import ReplicaProcess
    from cgnn_tpu.train import CheckpointManager

    n = args.fleet
    log_dir = args.fleet_log_dir or (
        os.path.join(os.path.dirname(os.path.abspath(args.report)) or ".",
                     "fleet-logs"))
    os.makedirs(log_dir, exist_ok=True)
    serve_args = [
        "--calibrate", "64",
        "--batch-size", str(args.batch_size),
        "--rungs", str(args.rungs),
        "--max-queue", str(args.max_queue),
        "--max-wait-ms", str(args.max_wait_ms),
        "--poll-interval", "0.5",
        "--drain-timeout", "30",
    ]
    # priority-class serving knobs (ISSUE 19) ride to every replica
    if args.class_wait_ms:
        serve_args += ["--class-wait-ms", args.class_wait_ms]
    if args.no_backfill:
        serve_args += ["--no-backfill"]
    if args.tenants:
        serve_args += ["--wfq-weights", args.tenants]
    if args.autoscale or args.remediate:
        # drain with the listener up, then linger past a health-probe
        # round (0.5 s here) so the router OBSERVES the draining flag
        # before the process exits — what classifies the disappearance
        # as a scale event instead of an incident
        serve_args += ["--drain-linger", "1.5"]
    if args.continual:
        # candidates must NOT auto-roll into replicas: every watcher
        # holds at its boot version until the canary gate's promotion
        # broadcast raises the reload gate (serve/reload.py)
        serve_args += ["--reload-gated"]
    procs = []
    for i in range(n):
        env = dict(os.environ)
        if args.replica_faults and i == args.faulty_replica % n:
            env["CGNN_TPU_FAULTS"] = args.replica_faults
        procs.append(ReplicaProcess(
            i, args.ckpt_dir, args.fleet_base_port + i,
            log_path=os.path.join(log_dir, f"replica-{i}.log"),
            serve_args=serve_args, env=env,
        ).start())
    not_ready = [p.rid for p in procs if not p.wait_ready(300.0)]
    if not_ready:
        for p in procs:
            p.terminate(timeout_s=5.0)
        raise RuntimeError(f"replicas {not_ready} never became ready "
                           f"(logs under {log_dir})")

    replicas = [ReplicaState(p.rid, p.base_url,
                             breaker_k=args.breaker_k,
                             breaker_cooldown_s=args.breaker_cooldown)
                for p in procs]
    slo_kw: dict = {}
    if args.slo_report:
        # second-scale burn-rate rules (ISSUE 16) so the injected 5xx
        # burst walks the full inactive -> pending -> firing ->
        # resolved arc inside one smoke run: fire when BOTH the 2 s and
        # 8 s windows burn >2x the 99.9% budget for 0.5 s; resolve
        # within ~8 s of the burst ending (the router's tsdb heartbeat
        # keeps evaluating with zero traffic)
        from cgnn_tpu.observe.slo import BurnRateRule, SLOObjective

        slo_kw = {
            "slo_objectives": (SLOObjective(
                "fleet_availability", target=0.999, window_s=60.0),),
            "slo_rules": (BurnRateRule(fast_s=2.0, slow_s=8.0,
                                       factor=2.0, for_s=0.5),),
        }
    router = FleetRouter(
        replicas,
        max_attempts=args.retries + 1,
        hedge_ms=args.hedge_ms,
        default_timeout_ms=args.timeout_ms,
        health_interval_s=0.5,
        trace_ring=args.trace_ring,
        **slo_kw,
    ).start()

    # the incident flight recorder under test (ISSUE 15): breaker trips
    # (the kill -9 leg ejects the victim) and 5xx bursts dump a bundle
    # holding the JOINED fleet trace + every process's request ring —
    # asserted below when --expect-trace-join
    from cgnn_tpu.observe import FlightRecorder

    flightrec_dir = os.path.join(
        os.path.dirname(os.path.abspath(args.report)) or ".",
        "flightrec")
    recorder = None
    if args.trace_ring:
        recorder = FlightRecorder(
            flightrec_dir, role="router", name="loadgen-router",
            registry=router.registry, tracer=router.tracer,
            peers=router.replica_trace_urls(),
            manifest={"ckpt_dir": args.ckpt_dir, "replicas": n},
            log_fn=print,
            # short quiet window: the chaos legs WANT each distinct
            # trigger captured — a kill's breaker_trip must not
            # rate-limit away the replica_unreachable bundle one probe
            # round (0.5 s) later, which is the one whose joined trace
            # provably holds the completed retries
            min_interval_s=0.25,
        )
        router.attach_flight_recorder(recorder)

    # ---- the label journal + /label wire surface (ISSUE 18) ----
    journal = None
    journal_path = ""
    label_httpd = None
    label_url = ""
    label_feedback = args.label_feedback
    if args.continual and label_feedback <= 0.0:
        label_feedback = 1.0  # the loop trains on labels; feed them all
    if label_feedback > 0.0:
        from cgnn_tpu.continual import LabelJournal
        from cgnn_tpu.fleet.http import make_fleet_http_server

        journal_path = os.path.join(
            os.path.dirname(os.path.abspath(args.report)) or ".",
            "labels.jsonl")
        for stale in (journal_path, journal_path + ".1"):
            if os.path.exists(stale):
                os.remove(stale)
        # durable only when a trainer tails it cross-process
        journal = LabelJournal(journal_path if args.continual else None,
                               capacity=65536)
        router.attach_journal(journal)
        # labels arrive over the SAME wire surface operators use:
        # POST /label against the router's HTTP front-end
        label_port = args.fleet_base_port + 99
        label_httpd = make_fleet_http_server(router, port=label_port)
        threading.Thread(target=label_httpd.serve_forever, daemon=True,
                         name="loadgen-fleet-http").start()
        label_url = f"http://127.0.0.1:{label_port}/label"

    # ---- the self-driving layer (ISSUE 17) ----
    autoscaler = None
    remediator = None
    asc_t0_mono = 0.0
    if args.autoscale or args.remediate:
        from cgnn_tpu.fleet.autoscale import AutoscalePolicy, Autoscaler
        from cgnn_tpu.fleet.remediate import (
            RemediationPolicy,
            Remediator,
        )

        def _proc_factory(rid: int):
            return ReplicaProcess(
                rid, args.ckpt_dir, args.fleet_base_port + rid,
                log_path=os.path.join(log_dir, f"replica-{rid}.log"),
                serve_args=serve_args)

        def _state_factory(rid: int, base_url: str):
            return ReplicaState(rid, base_url,
                                breaker_k=args.breaker_k,
                                breaker_cooldown_s=args.breaker_cooldown)

        # smoke-scale policy: second-scale cooldowns/sustain so the
        # whole grow-then-shrink arc fits inside one short leg
        asc_policy = AutoscalePolicy(
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas,
            up_queue_per_replica=2.0,
            down_queue_per_replica=0.4,
            cooldown_up_s=2.0, cooldown_down_s=4.0, down_sustain_s=3.0,
            warm_target=args.warm_pool)
        asc_t0_mono = time.monotonic()
        autoscaler = Autoscaler(
            router, asc_policy, _proc_factory, _state_factory,
            procs={p.rid: p for p in procs}, next_rid=n,
            poll_interval_s=0.5, drain_timeout_s=30.0)
        router.autoscaler = autoscaler
        if args.warm_pool > 0:
            warmed = autoscaler.prewarm()
            print(f"loadgen: prewarmed {warmed} spare replica(s) "
                  f"(pool {autoscaler.stats()['warm_pool']})")
        if args.autoscale:
            autoscaler.start()
        if args.remediate:
            if recorder is None:
                raise RuntimeError("--remediate needs the flight "
                                   "recorder (--trace-ring > 0)")
            remediator = Remediator(
                router, autoscaler,
                RemediationPolicy(min_interval_s=2.0),
                out_dir=os.path.dirname(os.path.abspath(args.report))
                or ".",
                # a wedged victim cannot drain; kill9 past this bound
                drain_timeout_s=8.0,
            ).attach(recorder)
            router.remediator = remediator

    from cgnn_tpu.data.dataset import load_synthetic

    meta = CheckpointManager(args.ckpt_dir).read_meta("latest")
    data_cfg = DataConfig.from_meta(meta["data"])
    pool = load_synthetic(min(args.structures, 64),
                          data_cfg.featurize_config(), seed=args.seed + 1)
    bodies = [{"graph": {
        "atom_fea": g.atom_fea.tolist(),
        "edge_fea": g.edge_fea.tolist(),
        "centers": g.centers.tolist(),
        "neighbors": g.neighbors.tolist(),
        "id": g.cif_id,
    }} for g in pool]
    # ground truth per body, for the late-label feed: the synthetic
    # pool's real targets, so the continual trainer fine-tunes on a
    # signal that actually exists
    truths = [float(np.asarray(g.target).reshape(-1)[0]) for g in pool]

    # Zipf keyset (ISSUE 20): body 0 is the hottest key. Precomputed
    # once; every client thread draws from the same distribution.
    zipf_p = None
    if args.zipf > 0:
        zipf_p = np.array([1.0 / (i + 1) ** args.zipf
                           for i in range(len(bodies))])
        zipf_p /= zipf_p.sum()

    stats = _ClientStats()
    stop = threading.Event()
    # per-replica answered counts + resilience meta, as the CLIENTS saw
    # them (the router's own stats ride the report separately)
    fleet_counts = {"attempts_hist": {}, "hedged_answers": 0,
                    "retried_answers": 0}
    # (due_time, trace_id, truth) entries awaiting their POST /label
    from collections import deque

    label_lock = threading.Lock()
    label_q: deque = deque()
    label_log: dict = {"sent": 0, "joined": 0, "already": 0,
                       "unmatched": 0, "double_posts": 0,
                       "resend_not_already": 0, "post_errors": []}

    # open-loop rate ramp (ISSUE 17): fleet-total rps as a function of
    # elapsed fraction — hold LOW, climb to PEAK by mid-duration, hold,
    # then drop to a calm tail (the autoscaler's scale-down window)
    ramp = None
    if args.ramp:
        _lo, _peak = (float(x) for x in args.ramp.split(":", 1))
        ramp = (_lo, _peak)

    def _ramp_rate(frac: float) -> float:
        lo, peak = ramp
        if frac < 0.1:
            return lo
        if frac < 0.45:
            return lo + (peak - lo) * (frac - 0.1) / 0.35
        if frac < 0.6:
            return peak
        return max(lo * 0.5, 0.5)

    plan = _priority_plan(args)

    def client(ci: int):
        import numpy as _np

        rng = _np.random.default_rng(args.seed + ci)
        while not stop.is_set():
            t_pace = None
            if ramp is not None:
                frac = (time.monotonic() - t_start) / max(args.duration,
                                                          1e-9)
                rate = _ramp_rate(min(frac, 1.0))
                t_pace = time.monotonic() + args.clients / max(rate, 0.1)
            elif plan is not None:
                # open-loop mixed-priority load at plan.total rps
                t_pace = (time.monotonic()
                          + args.clients / max(plan["total"], 0.1))
            if zipf_p is not None:
                bi = int(rng.choice(len(bodies), p=zipf_p))
            else:
                bi = int(rng.integers(len(bodies)))
            body = bodies[bi]
            kl = tn = None
            timeout_ms = args.timeout_ms
            if plan is not None:
                kl, tn = _draw_priority(plan, rng)
                timeout_ms = plan["timeouts"].get(kl, args.timeout_ms)
                body = dict(body, **{"class": kl})
                if tn:
                    body["tenant"] = tn
            with stats.lock:
                stats.submitted += 1
            try:
                status, payload, meta_d = router.dispatch(
                    dict(body), timeout_ms=timeout_ms)
            except Exception as e:  # noqa: BLE001 — report, don't die
                with stats.lock:
                    stats.errors.append(repr(e))
                if t_pace is not None:
                    stop.wait(max(0.0, t_pace - time.monotonic()))
                continue
            if t_pace is not None:
                stop.wait(max(0.0, t_pace - time.monotonic()))
            with stats.lock:
                if status == 200:
                    stats.answered += 1
                    stats.latencies.append(float(meta_d["latency_ms"]))
                    v = payload.get("param_version", "?")
                    stats.versions[v] = stats.versions.get(v, 0) + 1
                    rid = meta_d["replica"]
                    stats.device_responses[rid] = (
                        stats.device_responses.get(rid, 0) + 1)
                    stats.device_versions.setdefault(rid, set()).add(v)
                    tid = meta_d["trace_id"]
                    if tid:
                        stats.trace_ids.add(tid)
                    else:
                        stats.missing_trace += 1
                    a = meta_d["attempts"]
                    fleet_counts["attempts_hist"][a] = (
                        fleet_counts["attempts_hist"].get(a, 0) + 1)
                    if meta_d["hedges"]:
                        fleet_counts["hedged_answers"] += 1
                    if meta_d["retries"]:
                        fleet_counts["retried_answers"] += 1
                    if plan is not None:
                        _note_priority_answer(
                            stats,
                            str(payload.get("class") or kl
                                or "interactive"),
                            tn, float(meta_d["latency_ms"]),
                            bool(payload.get("backfilled")))
                else:
                    reason = (payload or {}).get("reason", str(status))
                    stats.rejected[reason] = (
                        stats.rejected.get(reason, 0) + 1)
            if (journal is not None and status == 200
                    and rng.random() < label_feedback):
                # ground truth "arrives" label_delay_ms later — the
                # labeler thread POSTs it to /label then
                with label_lock:
                    label_q.append((
                        time.monotonic() + args.label_delay_ms / 1e3,
                        meta_d["trace_id"], truths[bi]))

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"loadgen-fleet-client-{i}")
               for i in range(args.clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()

    # ---- the late-label feed (ISSUE 18) ----
    labeler_threads: list = []
    if journal is not None:
        import urllib.request
        from urllib.error import HTTPError, URLError

        def _post_label(tid: str, y: float) -> str:
            data = json.dumps({"trace_id": tid, "label": y},
                              allow_nan=False).encode()
            req = urllib.request.Request(
                label_url, data=data, method="POST",
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=10.0) as resp:
                    return json.loads(resp.read()).get("status", "?")
            except HTTPError as e:
                # 404 still carries {"status": "unmatched"}
                try:
                    return json.loads(e.read()).get("status", "?")
                except ValueError:
                    return f"http_{e.code}"

        # a POOL of labelers: each POST costs a fresh TCP connection
        # (~ms), so a single thread falls minutes behind a busy fleet
        # and labels would join long after their version's canary
        # window — staling the gate's live baseline
        def labeler():
            while True:
                entry = None
                with label_lock:
                    # once the run is stopping, flush without the delay
                    # so the exactly-once ledger closes complete
                    if label_q and (label_q[0][0] <= time.monotonic()
                                    or stop.is_set()):
                        entry = label_q.popleft()
                    drained = not label_q
                if entry is None:
                    if stop.is_set() and drained:
                        return
                    time.sleep(0.005)
                    continue
                _due, tid, y = entry
                try:
                    status = _post_label(tid, y)
                except (URLError, OSError) as e:
                    with label_lock:
                        label_log["post_errors"].append(repr(e))
                    continue
                with label_lock:
                    label_log["sent"] += 1
                    label_log[status] = label_log.get(status, 0) + 1
                    resend = label_log["sent"] % 7 == 0
                    if resend:
                        label_log["double_posts"] += 1
                if not resend:
                    continue
                # deliberately retransmit this label: exactly-once
                # means the journal answers 'already' and the stored
                # value stays untouched
                try:
                    again = _post_label(tid, y)
                except (URLError, OSError) as e:
                    with label_lock:
                        label_log["post_errors"].append(repr(e))
                    continue
                if again != "already":
                    with label_lock:
                        label_log["resend_not_already"] += 1

        labeler_threads = [
            threading.Thread(target=labeler, daemon=True,
                             name=f"loadgen-fleet-labeler-{i}")
            for i in range(6)]
        for t in labeler_threads:
            t.start()

    # ---- the closed loop (ISSUE 18): trainer + canary gate ----
    continual_done = threading.Event()
    continual_log: dict = {}
    canary_ctl = None
    canary_mgr = None
    cont_proc = None
    cont_log_path = ""
    if args.continual:
        from cgnn_tpu.continual import (
            CanaryController,
            CanaryGate,
            GateConfig,
        )

        canary_mgr = CheckpointManager(args.ckpt_dir)
        base_version = canary_mgr.newest_committed()
        # smoke-scale gate: loose MAE ratios (tiny fine-tunes on the
        # synthetic pool are noisy, while the injected round-2 label
        # corruption blows far past 4x) and short windows so both
        # verdicts land inside one leg
        canary_ctl = CanaryController(
            gate=CanaryGate(GateConfig(
                min_samples=20, min_baseline=20,
                max_mae_ratio=2.0, rollback_mae_ratio=4.0,
                p99_budget_ms=float(args.timeout_ms),
                min_window_s=1.0, max_window_s=120.0)),
            journal=journal, fleet=router,
            newest_fn=canary_mgr.newest_committed,
            flightrec=recorder,
            tick_interval_s=0.25,
            shadow_timeout_s=args.timeout_ms / 1e3,
            log_fn=print,
        )
        router.attach_canary(canary_ctl)
        canary_ctl.start()
        cont_log_path = os.path.join(log_dir, "continual.log")
        cont_env = dict(os.environ)
        # round 2 trains on deliberately corrupted labels: the
        # regressing candidate the canary gate MUST refuse
        cont_env["CGNN_TPU_FAULTS"] = "label_noise=2:10.0"
        with open(cont_log_path, "w") as cont_log_fh:
            cont_proc = subprocess.Popen(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.dirname(
                     os.path.abspath(__file__))), "continual.py"),
                 args.ckpt_dir, "--journal", journal_path,
                 "--min-new-labels", "48",
                 # round 2 must wait for candidate 1's verdict: the
                 # controller evaluates ONE candidate at a time and
                 # only ever picks the newest commit
                 "--min-interval", "45",
                 "--epochs-per-round", "2",
                 "--batch-size", "16",
                 "--max-rounds", "2",
                 "--poll-interval", "0.5",
                 "--device", "cpu",
                 "--seed", str(args.seed)],
                stdout=cont_log_fh, stderr=subprocess.STDOUT,
                env=cont_env)

        def continual_watch():
            commits: list = []
            deadline = time.monotonic() + 600.0
            try:
                while time.monotonic() < deadline:
                    newest = canary_mgr.newest_committed()
                    if (newest and newest != base_version
                            and newest not in commits):
                        commits.append(newest)
                        continual_log.setdefault(
                            "commit_times_s", []).append(
                            round(time.monotonic() - t_start, 2))
                    ev = canary_ctl.stats()["events"]
                    promoted = [e for e in ev
                                if e["kind"] == "promoted"]
                    rolled = [e for e in ev
                              if e["kind"] == "rolled_back"]
                    returned = [e for e in ev
                                if e["kind"] == "canary_returned"]
                    if promoted and "promoted" not in continual_log:
                        continual_log["promoted"] = (
                            promoted[0]["version"])
                        continual_log["promoted_at_s"] = round(
                            time.monotonic() - t_start, 2)
                    if rolled and returned and len(commits) >= 2:
                        continual_log["rolled_back"] = (
                            rolled[0]["version"])
                        continual_log["rollback_reason"] = (
                            rolled[0].get("reason", ""))
                        break
                    time.sleep(0.5)
                # promotion must CONVERGE: every routed replica's
                # gated watcher rolls onto the promoted version
                if "promoted" in continual_log:
                    pv = continual_log["promoted"]
                    conv_deadline = time.monotonic() + 90.0
                    consistent = False
                    while time.monotonic() < conv_deadline:
                        if set(router.versions().values()) == {pv}:
                            consistent = True
                            break
                        time.sleep(0.25)
                    continual_log["promotion_consistent"] = consistent
            finally:
                continual_log["commits"] = commits
                continual_done.set()

        threading.Thread(target=continual_watch, daemon=True,
                         name="loadgen-continual-watch").start()
    else:
        continual_done.set()

    # ---- the chaos timeline, alongside the load ----
    chaos_done = threading.Event()
    promote_done = threading.Event()
    chaos_log: dict = {}
    victim = args.kill_replica % n

    def _fleet_cache_counts() -> dict:
        # sums the replicas' OWN /stats cache counters over HTTP — they
        # are separate processes, so the router's view is not enough; a
        # kill9'd replica is simply skipped
        from cgnn_tpu.fleet.replica import http_get_json
        tot = {"requests": 0, "cache_hits": 0, "cache_coalesced": 0,
               "cache_dup_misses": 0, "cache_fills": 0}
        for p in procs:
            try:
                _, s = http_get_json(p.base_url + "/stats",
                                     timeout_s=5.0)
            except Exception:  # noqa: BLE001 — dead replica mid-chaos
                continue
            c = s.get("counts", {})
            for k in tot:
                tot[k] += int(c.get(k, 0))
        return tot

    # owner-kill leg (ISSUE 20): the victim is the ring owner of the
    # hottest key — computed BEFORE the load starts, since the ring is
    # deterministic. rid == proc index for the initial fleet.
    cachepart_log: dict = {}
    hot_key = None
    if args.kill_owner and router.cache_ring is not None:
        from cgnn_tpu.fleet.router import edge_fingerprint

        hot_key = edge_fingerprint(bodies[0])
        owner0 = router.cache_ring.owner(hot_key)
        if owner0 is not None:
            victim = int(owner0) % n
        cachepart_log["hot_fingerprint"] = hot_key
        cachepart_log["owner_before"] = owner0

    def chaos():
        try:
            if args.kill_at > 0:
                stop.wait(args.duration * args.kill_at)
                procs[victim].kill9()
                chaos_log["killed_at_s"] = round(
                    time.monotonic() - t_start, 2)
                if hot_key is not None:
                    # the prober needs a round to see the corpse; then
                    # the health-aware walk must re-own the victim's
                    # arcs to a deterministic ring successor
                    deadline_o = time.monotonic() + 15.0
                    during = None
                    while time.monotonic() < deadline_o:
                        alive = {r.rid for r in router.replicas
                                 if r.pickable()}
                        during = router.cache_ring.owner(hot_key,
                                                         alive=alive)
                        if during is not None and during != victim:
                            break
                        time.sleep(0.25)
                    cachepart_log["owner_during_kill"] = during
            if args.restart_at > 0:
                stop.wait(max(0.0, args.duration * args.restart_at
                              - (time.monotonic() - t_start)))
                procs[victim].restart()
                ready = procs[victim].wait_ready(240.0)
                chaos_log["restarted_at_s"] = round(
                    time.monotonic() - t_start, 2)
                chaos_log["restart_ready"] = ready
                # snapshot the victim's answered count the moment it is
                # back: "serves again" = the count GROWS past this
                chaos_log["victim_answered_at_restart"] = (
                    replicas[victim].counts["answered"])
                if hot_key is not None and ready:
                    # re-ownership must REVERT once the victim probes
                    # healthy again (remove + add restores the mapping
                    # bit-exactly — pinned by tests/test_cache_ring.py)
                    deadline_o = time.monotonic() + 30.0
                    after_o = None
                    while time.monotonic() < deadline_o:
                        alive = {r.rid for r in router.replicas
                                 if r.pickable()}
                        after_o = router.cache_ring.owner(hot_key,
                                                          alive=alive)
                        if after_o == cachepart_log.get("owner_before"):
                            break
                        time.sleep(0.25)
                    cachepart_log["owner_after_restart"] = after_o
                    # recovery is judged on the POST-restart window
                    # alone: snapshot fleet cache counters now, diff at
                    # the end
                    cachepart_log["counters_at_restart"] = (
                        _fleet_cache_counts())
        finally:
            chaos_done.set()

    def promote():
        try:
            if args.promote_at > 0:
                stop.wait(args.duration * args.promote_at)
                new_version = _commit_new_version(args.ckpt_dir,
                                                  seed=args.seed + 777)
                chaos_log["promoted_to"] = new_version
                chaos_log["promoted_at_s"] = round(
                    time.monotonic() - t_start, 2)
                # rolling promotion: every replica's own watcher polls
                # the shared dir — wait (bounded) until the router's
                # health view reports the new version fleet-wide
                deadline = time.monotonic() + 60.0
                consistent = False
                while time.monotonic() < deadline:
                    vs = set(router.versions().values())
                    if vs == {new_version}:
                        consistent = True
                        break
                    time.sleep(0.25)
                chaos_log["promotion_consistent"] = consistent
                chaos_log["final_versions"] = {
                    str(k): v for k, v in router.versions().items()}
        except Exception as e:  # noqa: BLE001 — reported as a failure
            chaos_log["promotion_error"] = repr(e)
        finally:
            promote_done.set()

    side = [threading.Thread(target=chaos, daemon=True,
                             name="loadgen-fleet-chaos"),
            threading.Thread(target=promote, daemon=True,
                             name="loadgen-fleet-promote")]
    for t in side:
        t.start()

    # ---- the scale-event timeline (ISSUE 17) ----
    # samples the router's own counters so the grew-BEFORE-shed assert
    # compares times from one clock, not inferred ordering
    scale_watch: dict = {}
    if autoscaler is not None:

        def scale_watcher():
            while not stop.is_set():
                if ("first_shed_at_s" not in scale_watch
                        and router.count("fleet_shed") > 0):
                    scale_watch["first_shed_at_s"] = round(
                        time.monotonic() - t_start, 2)
                if ("first_scale_event_at_s" not in scale_watch
                        and router.count("fleet_scale_events") > 0):
                    scale_watch["first_scale_event_at_s"] = round(
                        time.monotonic() - t_start, 2)
                stop.wait(0.1)

        threading.Thread(target=scale_watcher, daemon=True,
                         name="loadgen-fleet-scalewatch").start()

    # ---- the SLO alert watcher (ISSUE 16, --slo-report) ----
    slo_thread = None
    slo_timeline: dict = {}
    if args.slo_report and router.slo is not None:

        def slo_watch():
            # record the alert state machine live: the first firing and
            # the resolution that must follow once the burst's bad
            # events age out of the slow window
            deadline = time.monotonic() + args.duration + 75.0
            while time.monotonic() < deadline:
                firing = router.slo.firing()
                now_s = round(time.monotonic() - t_start, 2)
                if firing and "fired_at_s" not in slo_timeline:
                    slo_timeline["fired_at_s"] = now_s
                    slo_timeline["fired"] = [
                        {"objective": f["objective"], "rule": f["rule"],
                         "fire_count": f["fire_count"]}
                        for f in firing]
                if not firing and "fired_at_s" in slo_timeline:
                    slo_timeline["resolved_at_s"] = now_s
                    return
                time.sleep(0.2)

        slo_thread = threading.Thread(target=slo_watch, daemon=True,
                                      name="loadgen-slo-watch")
        slo_thread.start()

    # the X-Request-Id / idempotency-key contract through the router:
    # an explicit trace id must ride every attempt and echo back
    probe_trace = None
    try:
        _s, _p, probe_meta = router.dispatch(
            dict(bodies[0]), timeout_ms=args.timeout_ms,
            trace_id="loadgen-probe-1")
        probe_trace = probe_meta["trace_id"] if _s == 200 else (
            f"ERROR: status {_s}")
        if _s == 200:
            with stats.lock:
                stats.submitted += 1
                stats.answered += 1
                stats.trace_ids.add(probe_trace)
    except Exception as e:  # noqa: BLE001 — reported as a failure
        probe_trace = f"ERROR: {e!r}"

    # mid-load scrape of the ROUTER's /metrics plane (fleet counters +
    # replica-labeled gauge families + latency summaries)
    scrape: dict = {}

    def mid_scrape():
        stop.wait(args.duration * 0.6)
        from cgnn_tpu.observe.export import parse_prometheus_text

        text = router.registry.prometheus_text()
        scrape["text_bytes"] = len(text)
        try:
            fams = parse_prometheus_text(text)
            scrape["parse_ok"] = True
            scrape["missing_families"] = [
                p for p in ("cgnn_fleet_", "cgnn_replica_")
                if not any(f.startswith(p) for f in fams)
            ]
        except ValueError as e:
            scrape["parse_ok"] = False
            scrape["parse_error"] = str(e)

    scraper = threading.Thread(target=mid_scrape, daemon=True,
                               name="loadgen-fleet-scrape")
    if not args.no_scrape:
        scraper.start()

    # run until the duration elapsed AND the chaos legs finished (a
    # restart's boot may outlast a short duration — the victim must
    # still get post-restart traffic before the clients stop). The
    # continual loop also holds the load open: the canary needs live
    # labeled traffic flowing while candidates evaluate
    while True:
        elapsed = time.monotonic() - t_start
        if (elapsed >= args.duration and chaos_done.is_set()
                and promote_done.is_set()
                and continual_done.is_set()):
            break
        time.sleep(0.1)
    if chaos_log.get("restart_ready"):
        time.sleep(3.0)  # post-restart grace: let the probed-in victim
        #                  actually answer some of the closing traffic
    stop.set()
    for t in threads:
        t.join(timeout=args.timeout_ms / 1000.0 + 60.0)
    for t in side:
        t.join(timeout=120.0)
    for t in labeler_threads:
        # drains the queued labels (the pop bypasses the delay once
        # stop is set) so the exactly-once ledger closes complete
        t.join(timeout=60.0)
    if scraper.is_alive():
        scraper.join(timeout=30.0)
    wall = time.monotonic() - t_start
    # quiesce the self-driving layer BEFORE the router stops: the
    # remediator must not act on teardown noise, and autoscaler.stop()
    # joins any scale-down drain still in flight
    if remediator is not None:
        remediator.stop()
    if autoscaler is not None:
        autoscaler.stop()
    if canary_ctl is not None:
        canary_ctl.stop()
    if cont_proc is not None:
        if cont_proc.poll() is None:
            cont_proc.terminate()
        try:
            cont_proc.wait(timeout=120.0)
        except subprocess.TimeoutExpired:
            cont_proc.kill()
            cont_proc.wait(timeout=30.0)
        continual_log["trainer_exit"] = cont_proc.returncode
    slo_report: dict = {}
    if slo_thread is not None:
        # the resolve leg may land AFTER the load ends (the router's
        # tsdb heartbeat keeps evaluating with zero traffic), so wait
        # for the watcher BEFORE stopping the router; the quiesced
        # histogram truth check also needs the replicas still serving
        # their /metrics plane
        slo_thread.join(timeout=90.0)
        slo_report["alert"] = dict(slo_timeline)
        slo_report["engine"] = router.slo.state()
        slo_report.update(_fleet_hist_check(router, procs, stats))
        if recorder is not None:
            recorder.wait_idle(timeout_s=60.0)
            slo_report["flightrec"] = recorder.stats()
            slo_report["slo_bundles"] = _slo_bundle_manifests(
                flightrec_dir)
    if label_httpd is not None:
        label_httpd.shutdown()
        label_httpd.server_close()
    router.stop()
    router_stats = router.stats()
    if chaos_log.get("restart_ready"):
        chaos_log["victim_answered_at_end"] = (
            replicas[victim].counts["answered"])
    if args.expect_cachepart or args.kill_owner:
        # final replica-side cache counters (replicas still serving):
        # the dup-miss==0 and recovery assertions read these
        cachepart_log["counters_at_end"] = _fleet_cache_counts()
        chaos_log["cachepart"] = cachepart_log

    # ---- the cross-process trace join (ISSUE 15), BEFORE the
    # replicas drain away: router ring + every reachable replica's
    # /trace window -> one Perfetto file + the machine-checkable index
    observe_report: dict = {}
    if args.trace_ring:
        from cgnn_tpu.observe import trace_join

        windows, collect_errors = trace_join.collect_windows(
            router.replica_trace_urls())
        joined_path = os.path.splitext(os.path.abspath(args.report))[0] \
            + "_trace.json"
        doc = trace_join.write_joined(
            joined_path, [router.trace_window(), *windows])
        cross = trace_join.cross_process_traces(doc)
        observe_report = {
            "trace_joined": joined_path,
            "windows": 1 + len(windows),
            "collect_errors": collect_errors,
            "incomplete_processes": doc["incomplete_processes"],
            "traces_indexed": len(doc["traces"]),
            "cross_process_requests": len(cross),
        }
        if recorder is not None:
            recorder.wait_idle(timeout_s=60.0)
            frs = recorder.stats()
            observe_report["flightrec"] = frs
            if frs["last_bundle"]:
                # scan EVERY bundle's joined trace, not just the last:
                # the kill-instant breaker_trip bundle can legitimately
                # predate the first completed retry (its join then holds
                # no cross-process request yet); the ~0.5 s-later
                # replica_unreachable bundle is the deterministic one
                bundle_cross_max = 0
                try:
                    bundle_dirs = sorted(
                        os.path.join(flightrec_dir, d)
                        for d in os.listdir(flightrec_dir)
                        if d.startswith("bundle-"))
                except OSError:
                    bundle_dirs = [frs["last_bundle"]]
                for bdir in bundle_dirs:
                    try:
                        with open(os.path.join(bdir, "trace.json")) as f:
                            bundle_cross_max = max(
                                bundle_cross_max,
                                len(trace_join.cross_process_traces(
                                    json.load(f))))
                    except (OSError, ValueError) as e:
                        observe_report["bundle_trace_error"] = repr(e)
                observe_report["bundle_files"] = sorted(
                    os.listdir(frs["last_bundle"]))
                observe_report["bundle_cross_process_requests"] = (
                    bundle_cross_max)
    exit_codes = [p.terminate(timeout_s=60.0) for p in procs]
    # replicas the autoscaler booted (routed replacements + warm pool
    # spares) drain separately — 75 is the preemption-clean exit
    autoscaled_exits: dict = {}
    if autoscaler is not None:
        for rid in autoscaler.stats()["owned"]:
            if rid >= n:
                pr = autoscaler.proc_for(rid)
                if pr is not None:
                    autoscaled_exits[str(rid)] = pr.terminate(
                        timeout_s=60.0)

    lat = np.asarray(stats.latencies) if stats.latencies else np.zeros(1)
    with stats.lock:
        rejected_total = sum(stats.rejected.values())
    lost = (stats.submitted - stats.answered - rejected_total
            - len(stats.errors))
    report = {
        "mode": "fleet",
        "clients": args.clients,
        "replicas": n,
        "duration_s": round(wall, 2),
        "submitted": stats.submitted,
        "answered": stats.answered,
        "rejected": stats.rejected,
        "dropped": max(lost, 0),
        "client_errors": stats.errors[:10],
        "throughput_rps": round(stats.answered / wall, 1),
        "latency_ms": {
            "p50": float(np.percentile(lat, 50)),
            "p95": float(np.percentile(lat, 95)),
            "p99": float(np.percentile(lat, 99)),
            "mean": float(lat.mean()),
        },
        "param_versions": stats.versions,
        "devices": {
            "requested": str(n),
            "engine": "fleet",
            "count": n,
            "responses_by_device": {
                str(k): v
                for k, v in sorted(stats.device_responses.items())
            },
            "versions_by_device": {
                str(k): sorted(v)
                for k, v in sorted(stats.device_versions.items())
            },
        },
        "tracing": {
            "unique_trace_ids": len(stats.trace_ids),
            "missing_trace_ids": stats.missing_trace,
            "flushes_observed": 0,
            "probe_trace_id": probe_trace,
        },
        "fleet": {
            "chaos": chaos_log,
            "victim": victim,
            "replica_faults": args.replica_faults,
            "faulty_replica": (args.faulty_replica % n
                               if args.replica_faults else None),
            "attempts_hist": dict(sorted(
                fleet_counts["attempts_hist"].items())),
            "hedged_answers": fleet_counts["hedged_answers"],
            "retried_answers": fleet_counts["retried_answers"],
            "replica_exit_codes": exit_codes,
            "router": router_stats,
            "observe": observe_report,
        },
    }
    if plan is not None:
        report["priority"] = _priority_report(stats, plan)
    if scrape:
        report["fleet"]["metrics_scrape"] = scrape
    if slo_report:
        report["fleet"]["slo"] = slo_report
    if autoscaler is not None:
        a_stats = autoscaler.stats()
        # events carry t_s relative to the autoscaler's own birth;
        # t0_offset_s maps them onto the load timeline (t_start = 0)
        a_stats["t0_offset_s"] = round(asc_t0_mono - t_start, 3)
        a_stats.update(scale_watch)
        a_stats["exit_codes"] = autoscaled_exits
        report["fleet"]["autoscale"] = a_stats
    if remediator is not None:
        rem_stats = remediator.stats()
        rem_stats["journal"] = os.path.join(
            os.path.dirname(os.path.abspath(args.report)) or ".",
            "remediation.jsonl")
        report["fleet"]["remediation"] = rem_stats
    if journal is not None:
        labels_report = {k: v for k, v in label_log.items()
                         if k != "post_errors"}
        labels_report["post_errors"] = label_log["post_errors"][:10]
        labels_report["journal"] = journal.stats()
        labels_report["journal_path"] = (journal_path
                                         if args.continual else "")
        report["fleet"]["labels"] = labels_report
        journal.close()
    if args.continual:
        if recorder is not None:
            recorder.wait_idle(timeout_s=60.0)
        rb = continual_log.get("rolled_back", "")
        bundles = []
        if rb:
            import glob

            bundles = sorted(glob.glob(os.path.join(
                flightrec_dir, f"bundle-*canary_rollback_{rb}")))
        continual_log["rollback_bundle"] = bundles[-1] if bundles else ""
        cstats = canary_ctl.stats()
        report["fleet"]["continual"] = {
            **continual_log,
            "events": cstats["events"],
            "rejected": cstats["rejected"],
            "shadow_sent": cstats["shadow_sent"],
            "shadow_errors": cstats["shadow_errors"],
            "trainer_log": cont_log_path,
        }
        canary_mgr.close()
    return report


def _run_http(args) -> dict:
    """Minimal HTTP leg (urllib threads): smoke the wire path."""
    import urllib.request

    import numpy as np

    from cgnn_tpu.config import DataConfig
    from cgnn_tpu.data.dataset import load_synthetic
    from cgnn_tpu.data.rawbatch import raw_from_graph

    want_raw = args.wire in ("raw", "mixed")
    pool = load_synthetic(
        min(args.structures, 64),
        DataConfig(radius=6.0, max_num_nbr=12).featurize_config(),
        seed=args.seed + 1,
        keep_geometry=want_raw,
    )
    # wire-form request bodies (ISSUE 11): the ~100x smaller encoding a
    # raw-wire client ships — positions/lattice/species only
    raw_bodies = []
    if want_raw:
        for g in pool:
            r = raw_from_graph(g)
            if r is not None:
                raw_bodies.append({
                    "frac_coords": r.frac_coords.tolist(),
                    "lattice": r.lattice.tolist(),
                    "numbers": r.numbers.tolist(),
                    "id": r.cif_id,
                })
    stats = _ClientStats()
    stop = threading.Event()

    base = args.http.rstrip("/")

    def client(ci: int):
        rng = np.random.default_rng(args.seed + ci)
        raw_share = {"featurized": 0.0, "mixed": 0.5, "raw": 1.0}[args.wire]
        while not stop.is_set():
            # allow_nan=False, not jsonfinite(): features are finite by
            # construction, and the recursive rebuild in N client hot
            # loops would skew the rps/p99 this tool exists to measure
            if raw_bodies and rng.random() < raw_share:
                payload_body = {"structure": raw_bodies[
                    int(rng.integers(len(raw_bodies)))]}
            else:
                g = pool[int(rng.integers(len(pool)))]
                payload_body = {"graph": {
                    "atom_fea": g.atom_fea.tolist(),
                    "edge_fea": g.edge_fea.tolist(),
                    "centers": g.centers.tolist(),
                    "neighbors": g.neighbors.tolist(),
                    "id": g.cif_id,
                }}
            body = json.dumps({**payload_body,
                               "timeout_ms": args.timeout_ms},
                              allow_nan=False).encode()
            req = urllib.request.Request(
                base + "/predict", data=body,
                headers={"Content-Type": "application/json"},
            )
            with stats.lock:
                stats.submitted += 1
            try:
                with urllib.request.urlopen(
                    req, timeout=args.timeout_ms / 1000.0 + 30.0
                ) as resp:
                    payload = json.loads(resp.read())
            except Exception as e:  # noqa: BLE001 — count and move on
                with stats.lock:
                    reason = getattr(e, "code", "transport")
                    stats.rejected[str(reason)] = (
                        stats.rejected.get(str(reason), 0) + 1
                    )
                continue
            with stats.lock:
                stats.answered += 1
                stats.latencies.append(float(payload["latency_ms"]))
                v = payload["param_version"]
                stats.versions[v] = stats.versions.get(v, 0) + 1
                tid = payload.get("trace_id", "")
                if tid:
                    stats.trace_ids.add(tid)
                else:
                    stats.missing_trace += 1
                fid = payload.get("flush_id", "")
                if fid:
                    stats.flush_ids.add(fid)
                w = payload.get("wire", "featurized")
                stats.wire_responses[w] = stats.wire_responses.get(w, 0) + 1

    # mid-load wire-path plane checks (GET /metrics, POST /profile) —
    # fired against the LIVE server while the clients keep hammering it
    scrape_result: dict = {}
    profile_result: dict = {}

    def mid_scrape():
        time.sleep(args.duration * 0.6)
        try:
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=30.0) as resp:
                text = resp.read().decode()
            scrape_result.update(text=text, text_bytes=len(text),
                                 at_s=round(time.monotonic() - t_start, 2),
                                 measured_now_p99=_measured_p99(stats))
        except Exception as e:  # noqa: BLE001 — reported as a failure
            scrape_result.update(error=repr(e))

    def mid_profile():
        time.sleep(args.duration * 0.4)
        req = urllib.request.Request(
            base + "/profile",
            data=json.dumps({"duration_ms": 500}, allow_nan=False).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60.0) as resp:
                profile_result.update(json.loads(resp.read()))
        except Exception as e:  # noqa: BLE001 — reported as a failure
            profile_result.update(ok=False, error=repr(e))

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"loadgen-http-client-{i}")
               for i in range(args.clients)]
    checkers = []
    if not args.no_scrape:
        checkers.append(threading.Thread(target=mid_scrape, daemon=True,
                                         name="loadgen-scrape"))
    if args.profile_mid:
        checkers.append(threading.Thread(target=mid_profile, daemon=True,
                                         name="loadgen-profile"))
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in checkers:
        t.start()

    # the X-Request-Id contract, over the wire: a probe's inbound header
    # must come back as its trace id (response body AND echo header).
    # Bounded retries on TRANSPORT errors only: under a CPU-bound burst
    # a connection can be refused/reset before the listener accepts it —
    # that is load-shedding noise, not the header-echo contract this
    # probe pins (HTTP rejections still fail it immediately).
    probe_trace = None
    g = pool[0]
    req = urllib.request.Request(
        base + "/predict",
        data=json.dumps({"graph": {
            "atom_fea": g.atom_fea.tolist(),
            "edge_fea": g.edge_fea.tolist(),
            "centers": g.centers.tolist(),
            "neighbors": g.neighbors.tolist(),
        }, "timeout_ms": args.timeout_ms}, allow_nan=False).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "loadgen-probe-1"},
    )
    for attempt in range(4):
        try:
            with urllib.request.urlopen(
                req, timeout=args.timeout_ms / 1000.0 + 30.0
            ) as resp:
                payload = json.loads(resp.read())
                header_echo = resp.headers.get("X-Request-Id")
            probe_trace = payload.get("trace_id")
            if header_echo != probe_trace:
                probe_trace = (f"ERROR: body {probe_trace!r} != header "
                               f"{header_echo!r}")
            break
        except (ConnectionError, OSError) as e:
            probe_trace = f"ERROR: {e!r}"
            time.sleep(1.0 + attempt)
        except Exception as e:  # noqa: BLE001 — reported as a failure
            probe_trace = f"ERROR: {e!r}"
            break

    time.sleep(max(0.0, args.duration - (time.monotonic() - t_start)))
    stop.set()
    for t in threads:
        t.join(timeout=60.0)
    for t in checkers:
        t.join(timeout=60.0)
    wall = time.monotonic() - t_start
    lat = np.asarray(stats.latencies) if stats.latencies else np.zeros(1)
    report = {
        "mode": "http",
        "clients": args.clients,
        "duration_s": round(wall, 2),
        "submitted": stats.submitted,
        "answered": stats.answered,
        "rejected": stats.rejected,
        "dropped": 0,
        "throughput_rps": round(stats.answered / wall, 1),
        "latency_ms": {
            "p50": float(np.percentile(lat, 50)),
            "p99": float(np.percentile(lat, 99)),
        },
        "param_versions": stats.versions,
        "wire": {
            "requested": args.wire,
            "responses_by_wire": dict(sorted(
                stats.wire_responses.items())),
            "raw_pool": len(raw_bodies),
            "probes": {},
        },
        "tracing": {
            "unique_trace_ids": len(stats.trace_ids),
            "missing_trace_ids": stats.missing_trace,
            "flushes_observed": len(stats.flush_ids),
            "probe_trace_id": probe_trace,
        },
    }
    if scrape_result:
        scraped_p99 = 0.0
        if "text" in scrape_result:
            from cgnn_tpu.observe.export import parse_prometheus_text

            try:
                fams = parse_prometheus_text(scrape_result["text"])
                for name, value in fams.get(
                        "cgnn_serve_latency_ms", {}).get("samples", []):
                    if 'quantile="0.99"' in name:
                        scraped_p99 = value
            except ValueError:
                pass
            report["metrics_scrape"] = {
                "at_s": scrape_result["at_s"],
                "text_bytes": scrape_result["text_bytes"],
                "final_measured_p99_ms": _measured_p99(stats),
                **_scrape_check(scrape_result["text"], scraped_p99,
                                scrape_result.get("measured_now_p99", 0.0),
                                args.scrape_tolerance),
            }
        else:
            report["metrics_scrape"] = {"parse_ok": False,
                                        **scrape_result}
    if profile_result:
        report["profile"] = profile_result
    return report


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.make_ckpt:
        make_synth_ckpt(args.make_ckpt, seed=args.seed)
        return 0
    if not args.http and not args.ckpt_dir:
        print("CKPT_DIR (or --http URL / --make-ckpt DIR) required",
              file=sys.stderr)
        return 2
    if (args.continual or args.label_feedback > 0) and not args.fleet:
        print("--continual / --label-feedback need --fleet N",
              file=sys.stderr)
        return 2
    if args.continual and not args.trace_ring:
        print("--continual needs the flight recorder (--trace-ring > 0)",
              file=sys.stderr)
        return 2
    if args.priority_mix and args.http:
        print("--priority-mix drives the in-proc or --fleet modes "
              "(the bare --http leg has no class accounting)",
              file=sys.stderr)
        return 2

    if args.fleet:
        report = _run_fleet(args)
    elif args.http:
        report = _run_http(args)
    else:
        report = _run_inproc(args)

    failures = []
    if report.get("dropped"):
        failures.append(f"{report['dropped']} dropped responses (must be 0)")
    if report.get("client_errors"):
        failures.append(f"client errors: {report['client_errors']}")
    if report.get("compiles", {}).get("after_warm"):
        failures.append(
            f"{report['compiles']['after_warm']} recompiles after warmup "
            f"(must be 0)"
        )
    tracing = report.get("tracing", {})
    if tracing:
        if tracing["missing_trace_ids"]:
            failures.append(
                f"{tracing['missing_trace_ids']} responses carried no "
                f"trace id (every response must)"
            )
        if (report["answered"]
                and tracing["unique_trace_ids"] != report["answered"]):
            failures.append(
                f"trace ids not unique: {tracing['unique_trace_ids']} "
                f"distinct over {report['answered']} answered (co-batched "
                f"requests must carry DISTINCT ids)"
            )
        if tracing["probe_trace_id"] != "loadgen-probe-1":
            failures.append(
                f"X-Request-Id probe not honored: sent 'loadgen-probe-1', "
                f"got {tracing['probe_trace_id']!r}"
            )
    scrape = report.get("metrics_scrape")
    if scrape is not None:
        if not scrape.get("parse_ok"):
            failures.append(
                f"/metrics scrape did not parse as Prometheus exposition "
                f"format: {scrape.get('parse_error', scrape.get('error'))}"
            )
        elif scrape.get("missing_families"):
            failures.append(
                f"/metrics missing required metric families: "
                f"{scrape['missing_families']}"
            )
        elif report["answered"] >= 100 and not scrape.get("agree"):
            failures.append(
                f"scraped rolling p99 {scrape['scraped_p99_ms']:.1f} ms "
                f"disagrees with measured p99 "
                f"{scrape['measured_p99_ms']:.1f} ms beyond tolerance "
                f"{scrape['tolerance_ms']} ms"
            )
    if args.profile_mid:
        prof = report.get("profile", {})
        if not prof.get("ok", prof.get("bytes", 0) > 0):
            failures.append(f"mid-load profile capture failed: {prof}")
        elif not prof.get("bytes"):
            failures.append(
                f"mid-load profile capture wrote an EMPTY artifact: {prof}"
            )
    wire = report.get("wire", {})
    if args.wire in ("raw", "mixed"):
        by_wire = wire.get("responses_by_wire", {})
        if not by_wire.get("raw"):
            failures.append(
                f"raw wire requested but no raw-wire responses: {by_wire}"
            )
        if args.wire == "mixed" and not by_wire.get("featurized"):
            failures.append(
                f"mixed wire load produced no featurized responses "
                f"(form-boundary cut unexercised): {by_wire}"
            )
    if not args.http and args.wire in ("raw", "mixed"):
        probes = wire.get("probes", {})
        if "parity" in probes and not probes["parity"].get("ok"):
            failures.append(f"raw-vs-featurized parity probe failed: "
                            f"{probes['parity']}")
        if args.raw_overflow_probe:
            if not probes.get("overflow", {}).get("ok"):
                failures.append(f"in-program overflow probe failed: "
                                f"{probes.get('overflow')}")
            ovf = (report.get("server_stats", {}).get("ingest", {})
                   .get("cap_overflows", 0))
            if not ovf:
                failures.append(
                    "overflow probe ran but ingest_cap_overflow_total "
                    "never incremented"
                )
        else:
            # the satellite invariant: on a CALIBRATED ladder with the
            # host pre-check on, the in-program flag must never fire
            ovf = (report.get("server_stats", {}).get("ingest", {})
                   .get("cap_overflows", 0))
            if ovf:
                failures.append(
                    f"{ovf} in-program cap overflows on the calibrated "
                    f"ladder (pre-check on: must be 0)"
                )
    if args.hot_swap and not args.http:
        versions = report["param_versions"]
        if report["hot_swap"]["watcher_swaps"] < 1:
            failures.append("hot swap never happened")
        elif len([v for v, c in versions.items() if c > 0]) < 2:
            failures.append(
                f"expected responses from both param versions, saw "
                f"{versions}"
            )
    if not args.http and args.devices != "auto" and int(args.devices) > 1:
        # forced multi-device dryrun (ISSUE 5): distribution is a HARD
        # invariant — a device that answered nothing under sustained
        # load means the router (or the replica set) is broken
        dev = report["devices"]
        want = int(args.devices)
        if dev["count"] != want:
            failures.append(
                f"requested {want} devices, server resolved {dev['count']}"
            )
        silent = [i for i in range(want)
                  if not dev["responses_by_device"].get(str(i))]
        if silent:
            failures.append(
                f"devices {silent} answered no responses under load "
                f"(distribution broken: {dev['responses_by_device']})"
            )
    if args.fleet:
        # ---- the fleet chaos invariants (ISSUE 14), all HARD ----
        fl = report["fleet"]
        rc = fl["router"]["counts"]
        chaos = fl["chaos"]
        hard_rejects = dict(report["rejected"])
        if args.priority_mix:
            # deadline-feasibility sheds (ISSUE 19) are load shedding,
            # not loss (INVARIANTS.md): under a mixed-priority leg the
            # router MAY 429/504 an infeasible request before it
            # crosses a process boundary — the exactly-once ledger
            # still closes (shed requests are typed rejections)
            for reason in ("infeasible_queue", "infeasible_deadline"):
                hard_rejects.pop(reason, None)
        if hard_rejects:
            failures.append(
                f"fleet rejected requests (with {args.fleet} replicas "
                f"and retries these legs must answer everything): "
                f"{hard_rejects}"
            )
        if rc.get("fleet_exhausted"):
            failures.append(
                f"{rc['fleet_exhausted']} requests exhausted every "
                f"attempt (accepted-then-lost; must be 0)"
            )
        if rc.get("fleet_deadline_exceeded"):
            failures.append(
                f"{rc['fleet_deadline_exceeded']} requests blew the "
                f"fleet deadline (must be 0 at smoke load)"
            )
        if rc.get("fleet_duplicate_answers"):
            failures.append(
                f"{rc['fleet_duplicate_answers']} duplicate answers — "
                f"the exactly-once invariant is broken"
            )
        silent = [i for i in range(args.fleet)
                  if not report["devices"]["responses_by_device"]
                  .get(str(i))]
        if silent:
            failures.append(
                f"replicas {silent} answered nothing under load: "
                f"{report['devices']['responses_by_device']}"
            )
        if args.kill_at > 0:
            if "killed_at_s" not in chaos:
                failures.append("kill leg requested but never fired")
            elif not rc.get("fleet_transport_errors"):
                failures.append(
                    "kill -9 fired but the router saw no transport "
                    "errors — the chaos leg did not actually bite"
                )
        if args.restart_at > 0:
            if not chaos.get("restart_ready"):
                failures.append(
                    f"restarted replica {fl['victim']} never became "
                    f"ready again: {chaos}"
                )
            else:
                before = chaos.get("victim_answered_at_restart", 0)
                after = chaos.get("victim_answered_at_end", 0)
                if after <= before:
                    failures.append(
                        f"restarted replica {fl['victim']} was never "
                        f"probed back into rotation (answered {before} "
                        f"-> {after})"
                    )
                br = (fl["router"]["replicas"]
                      .get(str(fl["victim"]), {})
                      .get("breaker", {}))
                if br.get("state") != "closed":
                    failures.append(
                        f"victim breaker not re-closed after restart: "
                        f"{br}"
                    )
        if args.promote_at > 0:
            if "promotion_error" in chaos:
                failures.append(
                    f"promotion leg failed: {chaos['promotion_error']}")
            else:
                if not chaos.get("promotion_consistent"):
                    failures.append(
                        f"fleet never converged on the promoted "
                        f"version: {chaos.get('final_versions')}"
                    )
                if len([v for v, c in report["param_versions"].items()
                        if c > 0]) < 2:
                    failures.append(
                        f"rolling promotion should have answered from "
                        f"BOTH versions mid-roll, saw "
                        f"{report['param_versions']}"
                    )
        if args.expect_retries and not rc.get("fleet_retries"):
            failures.append(
                "expected router retries (--expect-retries) but none "
                "happened"
            )
        if args.expect_hedges and not rc.get("fleet_hedges"):
            failures.append(
                "expected hedged requests (--expect-hedges) but none "
                "fired"
            )
        if args.expect_cachepart:
            # ---- the one-fleet-cache invariants (ISSUE 20) ----
            if not rc.get("fleet_fingerprinted"):
                failures.append(
                    "cachepart leg: the router fingerprinted no "
                    "request — edge hashing never engaged")
            if not rc.get("fleet_owner_routed"):
                failures.append(
                    "cachepart leg: owner-affinity never routed a "
                    "request to its ring owner")
            cp = chaos.get("cachepart", {})
            if args.kill_owner:
                ob = cp.get("owner_before")
                od = cp.get("owner_during_kill")
                oa = cp.get("owner_after_restart")
                if ob is None:
                    failures.append(
                        f"cachepart leg: no ring owner recorded for "
                        f"the hot key: {cp}")
                elif args.kill_at > 0 and (od is None or od == ob):
                    failures.append(
                        f"cachepart leg: the killed owner's arcs never "
                        f"re-owned to a survivor (owner {ob} -> {od})")
                if args.restart_at > 0 and oa != ob:
                    failures.append(
                        f"cachepart leg: re-ownership did not revert "
                        f"after the restart (owner {ob} -> {oa}; the "
                        f"ring must restore the original mapping)")
            end = cp.get("counters_at_end", {})
            if end.get("cache_dup_misses"):
                failures.append(
                    f"cachepart leg: {end['cache_dup_misses']} "
                    f"duplicate in-flight misses fleet-wide — "
                    f"single-flight must hold this at exactly 0")
            base = cp.get("counters_at_restart") or {}
            d_req = (end.get("requests", 0) - base.get("requests", 0))
            d_hit = (end.get("cache_hits", 0)
                     + end.get("cache_coalesced", 0)
                     - base.get("cache_hits", 0)
                     - base.get("cache_coalesced", 0))
            ratio = d_hit / d_req if d_req > 0 else 0.0
            if d_req <= 0:
                failures.append(
                    "cachepart leg: no post-restart traffic reached "
                    "the replicas — hit-ratio recovery unmeasurable")
            elif ratio < 0.5:
                failures.append(
                    f"cachepart leg: fleet hit ratio did not recover "
                    f"after the restart ({ratio:.2%} effective over "
                    f"{d_req} requests; want >= 50% on the Zipf "
                    f"keyset)")
        if args.label_feedback > 0 or args.continual:
            # ---- the exactly-once label-join ledger (ISSUE 18) ----
            lb = fl.get("labels", {})
            js = lb.get("journal", {})
            if lb.get("post_errors"):
                failures.append(
                    f"label POSTs errored: {lb['post_errors']}")
            if not lb.get("sent"):
                failures.append(
                    "label feedback requested but no label was ever "
                    "POSTed")
            if lb.get("joined") != lb.get("sent"):
                failures.append(
                    f"label joins incomplete: {lb.get('joined')} "
                    f"joined of {lb.get('sent')} sent (every first "
                    f"POST must land exactly once)")
            if lb.get("unmatched"):
                failures.append(
                    f"{lb['unmatched']} labels joined NOTHING (every "
                    f"label targets a journaled answer)")
            if lb.get("resend_not_already"):
                failures.append(
                    f"{lb['resend_not_already']} deliberate label "
                    f"re-POSTs did NOT answer 'already' — the "
                    f"exactly-once join is broken")
            if js.get("duplicate_joins") != lb.get("double_posts"):
                failures.append(
                    f"journal duplicate_joins "
                    f"{js.get('duplicate_joins')} != deliberate "
                    f"re-POSTs {lb.get('double_posts')} (a duplicate "
                    f"apply slipped through, or one was double-counted)"
                )
            if js.get("served") != report["answered"]:
                failures.append(
                    f"journal holds {js.get('served')} served records "
                    f"for {report['answered']} answered requests "
                    f"(exactly one record per answer — hedged and "
                    f"retried attempts share the trace id)")
        if args.continual:
            # ---- the closed continual loop (ISSUE 18), all HARD ----
            cont = fl.get("continual", {})
            commits = cont.get("commits", [])
            if len(commits) < 2:
                failures.append(
                    f"continual trainer committed {len(commits)} "
                    f"candidate(s); the leg needs its clean round AND "
                    f"its corrupted one (trainer log: "
                    f"{cont.get('trainer_log')})")
            if not cont.get("promoted"):
                failures.append(
                    "no candidate was ever promoted fleet-wide")
            else:
                if commits and cont["promoted"] != commits[0]:
                    failures.append(
                        f"promoted {cont['promoted']} but the first "
                        f"(clean) candidate was {commits[0]}")
                if not cont.get("promotion_consistent"):
                    failures.append(
                        f"fleet never converged on the promoted "
                        f"candidate {cont['promoted']}")
                if not report["param_versions"].get(cont["promoted"]):
                    failures.append(
                        f"promoted candidate {cont['promoted']} never "
                        f"answered live traffic: "
                        f"{report['param_versions']}")
            if not cont.get("rolled_back"):
                failures.append(
                    "the corrupted candidate was never rolled back")
            else:
                if (len(commits) >= 2
                        and cont["rolled_back"] != commits[1]):
                    failures.append(
                        f"rolled back {cont['rolled_back']} but the "
                        f"corrupted candidate was {commits[1]}")
                if not cont.get("rollback_bundle"):
                    failures.append(
                        f"rollback of {cont['rolled_back']} dumped no "
                        f"flight-recorder bundle naming it")
            if cont.get("trainer_exit") not in (0, 75):
                failures.append(
                    f"continual trainer exited "
                    f"{cont.get('trainer_exit')} (log: "
                    f"{cont.get('trainer_log')})")
        # exits 0 (drained) and 75 (resumable preemption, PR 2) are
        # both clean; a remediated victim was force-reaped on purpose
        remediated = {a.get("replica") for a in
                      fl.get("remediation", {}).get("actions", [])}
        codes = fl["replica_exit_codes"]
        bad_exits = [
            (i, c) for i, c in enumerate(codes)
            if c not in (0, 75) and i not in remediated
            and not (i == fl["victim"] and args.kill_at > 0
                     and args.restart_at == 0)
        ]
        if bad_exits:
            failures.append(
                f"replica drain exits non-zero: {bad_exits} "
                f"(graceful SIGTERM drain must exit 0 or 75)"
            )
        for rid_s, c in (fl.get("autoscale", {}).get("exit_codes")
                         or {}).items():
            if c not in (0, 75) and int(rid_s) not in remediated:
                failures.append(
                    f"autoscaled replica {rid_s} drain exit {c} "
                    f"(must be 0 or 75)")
        if args.autoscale:
            # ---- the self-driving scaling invariants (ISSUE 17) ----
            auto = fl.get("autoscale", {})
            ac = auto.get("counts", {})
            if not ac.get("scale_ups"):
                failures.append(
                    "autoscale leg: the fleet never grew under the ramp")
            if args.ramp and not ac.get("scale_downs"):
                failures.append(
                    "autoscale leg: the fleet never shrank after the "
                    "ramp-down")
            if rc.get("fleet_shed"):
                # shedding is legitimate ONLY after growth was attempted:
                # the first scale-up must predate the first shed
                ups = [e for e in auto.get("events", [])
                       if e["action"] == "scale_up"]
                first_up = (ups[0]["t_s"] + auto.get("t0_offset_s", 0.0)
                            if ups else None)
                first_shed = auto.get("first_shed_at_s")
                if first_up is None or (first_shed is not None
                                        and first_up >= first_shed):
                    failures.append(
                        f"autoscaler shed before growing: first shed at "
                        f"{first_shed} s, first scale-up at {first_up} s "
                        f"({rc['fleet_shed']} shed)")
            if not rc.get("fleet_scale_events"):
                failures.append(
                    "no fleet scale events recorded (every drained "
                    "exit must be classified a scale event)")
            if rc.get("fleet_incidents") and not args.remediate:
                failures.append(
                    f"{rc['fleet_incidents']} fleet incident(s) during "
                    f"a pure scaling leg (planned drains must never "
                    f"count as incidents)")
        if args.remediate:
            # ---- the auto-remediation invariants (ISSUE 17) ----
            rem = fl.get("remediation", {})
            acts = rem.get("actions", [])
            if not acts:
                failures.append(
                    f"remediation leg: no action executed (policy: "
                    f"{rem.get('policy')})")
            else:
                a0 = acts[0]
                if not a0.get("bundle"):
                    failures.append(
                        "remediation action names no evidence bundle")
                repl = a0.get("replacement")
                if repl is None:
                    failures.append(
                        "remediation replace step failed (no "
                        "replacement replica booted)")
                elif not report["devices"]["responses_by_device"].get(
                        str(repl)):
                    failures.append(
                        f"replacement replica {repl} answered nothing "
                        f"after the swap: "
                        f"{report['devices']['responses_by_device']}")
                if str(a0.get("replica")) in fl["router"]["replicas"]:
                    failures.append(
                        f"remediated replica {a0.get('replica')} is "
                        f"still routed")
                jp = rem.get("journal", "")
                try:
                    with open(jp) as f:
                        entries = [json.loads(x) for x in f]
                except (OSError, ValueError):
                    entries = []
                if not entries:
                    failures.append(
                        f"remediation journal missing or empty: {jp!r}")
                elif not all(e.get("bundle") for e in entries):
                    failures.append(
                        "remediation journal entry missing its bundle "
                        "reference (every action must name its "
                        "evidence)")
            if not rc.get("fleet_incidents"):
                failures.append(
                    "wedge leg recorded no fleet incident (the "
                    "remediation removal must count as one)")
        scrape_fl = fl.get("metrics_scrape")
        if scrape_fl is not None:
            if not scrape_fl.get("parse_ok"):
                failures.append(
                    f"router /metrics did not parse: {scrape_fl}")
            elif scrape_fl.get("missing_families"):
                failures.append(
                    f"router /metrics missing families: "
                    f"{scrape_fl['missing_families']}"
                )
        if args.expect_trace_join:
            # ---- the ISSUE-15 cross-process observability asserts ----
            obs = fl.get("observe", {})
            if not obs:
                failures.append(
                    "trace join expected but the trace layer was off "
                    "(--trace-ring 0?)"
                )
            else:
                if obs.get("windows", 0) < 2:
                    failures.append(
                        f"joined trace covers {obs.get('windows')} "
                        f"process window(s); need the router plus at "
                        f"least one replica"
                    )
                if not obs.get("cross_process_requests"):
                    failures.append(
                        "joined fleet trace holds NO retried/hedged "
                        "request with spans from >= 2 processes (the "
                        "cross-process join is broken)"
                    )
                frs = obs.get("flightrec", {})
                if not frs.get("bundles"):
                    failures.append(
                        f"chaos leg produced no flight-recorder bundle "
                        f"(triggers seen: {frs.get('triggers')})"
                    )
                elif "trace.json" not in obs.get("bundle_files", []):
                    failures.append(
                        f"flight-recorder bundle is missing its joined "
                        f"trace: {obs.get('bundle_files')}"
                    )
                elif not obs.get("bundle_cross_process_requests"):
                    failures.append(
                        "flight-recorder bundle's joined trace holds "
                        "no retried/hedged request spanning >= 2 "
                        "processes"
                    )
                elif "requests.jsonl" not in obs.get("bundle_files", []):
                    failures.append(
                        f"flight-recorder bundle is missing the "
                        f"recent-request ring: {obs.get('bundle_files')}"
                    )
        if args.slo_report:
            # ---- the ISSUE-16 metrics-truth asserts, all HARD ----
            slo = fl.get("slo", {})
            if not slo:
                failures.append(
                    "--slo-report set but the SLO layer never ran "
                    "(router built without it?)"
                )
            else:
                if not slo.get("merge_bitexact"):
                    failures.append(
                        f"fleet-merged histograms are not bit-identical "
                        f"to pooling every replica's own scrape: "
                        f"{slo.get('merge_mismatches')}"
                    )
                lt = slo.get("latency_truth", {})
                if not lt.get("count_exact"):
                    failures.append(
                        f"router fleet latency histogram count != "
                        f"answered requests: {lt}"
                    )
                if not lt.get("count_covers_answered"):
                    failures.append(
                        f"merged replica latency histogram does not "
                        f"cover every answered request: {lt}"
                    )
                if not lt.get("p50_agree"):
                    failures.append(
                        f"merged-histogram median disagrees with the "
                        f"client-measured p50 beyond bucket resolution "
                        f"+ overhead margin: {lt}"
                    )
                alert = slo.get("alert", {})
                if "fired_at_s" not in alert:
                    failures.append(
                        "burn-rate alert never fired under the "
                        "injected 5xx burst"
                    )
                elif "resolved_at_s" not in alert:
                    failures.append(
                        f"burn-rate alert fired at "
                        f"{alert['fired_at_s']} s but never resolved"
                    )
                if "flightrec" in slo:
                    trig = slo["flightrec"].get("triggers", {})
                    if not any(k.startswith("slo_burn_") for k in trig):
                        failures.append(
                            f"firing SLO alert never triggered a "
                            f"flight-recorder dump (triggers: {trig})"
                        )
                    elif not slo.get("slo_bundles"):
                        failures.append(
                            "no flight-recorder bundle manifest names "
                            "an slo_burn_* trigger reason"
                        )
    if args.priority_mix:
        # ---- the mixed-priority invariants (ISSUE 19), all HARD ----
        from cgnn_tpu.serve.batcher import parse_kv_spec

        pr = report.get("priority", {})
        by_cls = pr.get("latency_ms_by_class", {})
        plan = _priority_plan(args)
        for c in sorted(plan["rates"]):
            if not pr.get("responses_by_class", {}).get(c):
                failures.append(
                    f"priority class {c!r} sent load but answered "
                    f"nothing: {pr.get('responses_by_class')}")
        for c, bound in sorted(parse_kv_spec(args.class_slo_ms).items()):
            got = by_cls.get(c, {}).get("p99")
            if got is None:
                failures.append(
                    f"--class-slo-ms names {c!r} but no latency was "
                    f"measured for it")
            elif got > bound:
                failures.append(
                    f"class {c!r} p99 {got:.1f} ms exceeds its "
                    f"{bound:.0f} ms SLO "
                    f"(over {by_cls[c]['count']} answers)")
        if args.expect_backfill:
            if not pr.get("backfilled_responses"):
                failures.append(
                    "--expect-backfill: no response ever rode a "
                    "higher-class flush's padding slack")
            if (not args.fleet
                    and not pr.get("padding_fill_share", 0.0) > 0.0):
                failures.append(
                    f"--expect-backfill: serve_padding_fill_share is "
                    f"{pr.get('padding_fill_share')} (must be > 0)")
    # racecheck leg (CGNN_TPU_RACECHECK=1): the runtime lock-discipline
    # report rides the SLO report and fails the run like any other
    # invariant — zero lock-order inversions, zero unguarded shared-field
    # touches, zero deadlock-watchdog dumps under the full client load.
    # In-proc ONLY: in --http mode the server runs in another process and
    # this process's racecheck state is empty — reporting that as "clean"
    # would be a vacuous verdict about a server never instrumented here.
    from cgnn_tpu.analysis import racecheck

    if args.http and racecheck.enabled():
        print("racecheck: gate is on but --http drives a remote process; "
              "no verdict (run the in-proc mode to instrument the server)")
    if racecheck.enabled() and not args.http:
        rc = racecheck.report()
        report["racecheck"] = rc
        if rc["inversions"]:
            failures.append(
                f"{len(rc['inversions'])} lock-order inversion(s): "
                f"{rc['inversions'][:3]}"
            )
        if rc["violations"]:
            failures.append(
                f"{len(rc['violations'])} unguarded shared-field "
                f"access(es): {rc['violations'][:3]}"
            )
        if rc["deadlock_dumps"]:
            failures.append(
                f"deadlock watchdog fired {rc['deadlock_dumps']} time(s) "
                f"(stalled: {rc['stalled_threads']})"
            )
        print(
            f"racecheck: {len(rc['inversions'])} inversions, "
            f"{len(rc['violations'])} violations, "
            f"{rc['deadlock_dumps']} watchdog dumps across "
            f"{len(rc['heartbeats_seen'])} heartbeating thread(s)"
        )
    report["failures"] = failures
    with open(args.report, "w") as f:
        json.dump(jsonfinite(report), f, indent=1)
    lat = report["latency_ms"]
    dev = report.get("devices", {})
    print(
        f"[{report['mode']}] {report['answered']}/{report['submitted']} "
        f"answered @ {report['throughput_rps']} rps | p50 "
        f"{lat['p50']:.1f} ms p99 {lat['p99']:.1f} ms | occupancy "
        f"{report.get('batch_occupancy_mean', 0):.2f} | versions "
        f"{report['param_versions']} | devices "
        f"{dev.get('responses_by_device', {})} | report -> {args.report}"
    )
    if failures:
        print("SLO INVARIANT FAILURES: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
