#!/usr/bin/env python
"""Performance model of the flagship train step (VERDICT r2 item #1).

Answers, with measurements on the chip:
1. How much of the per-step wall time is dispatch overhead vs
   device execution?  (per-step dispatch loop vs whole-`lax.scan` dispatch
   of the SAME steps — identical math, one host round trip.)
2. Where does device time go?  (jax.profiler trace of the scanned steps,
   parsed into a top-op table.)
3. Where does the step sit on the v5e roofline?  (analytic bytes-moved and
   matmul FLOPs vs ~819 GB/s HBM and 197 bf16 TFLOP/s.)

Writes PERF_DATA.json with everything; PERF.md (committed) interprets it.

Usage: python scripts/profile_step.py [--trace-dir /tmp/cgnn_trace]
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402


def build_workload(dense_m=12):
    """The flagship workload (as cell mp.train): MP-like distribution,
    dense layout, snug packing, bf16 edge storage."""
    import jax
    import numpy as np

    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
    from cgnn_tpu.data.graph import PaddingStats, bucketed_batch_iterator

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    graphs = load_synthetic_mp(8192, cfg, seed=0)
    stats = PaddingStats()
    batches = list(
        bucketed_batch_iterator(
            graphs, 512, 3, stats=stats,
            rng=np.random.default_rng(0), dense_m=dense_m, snug=True,
            edge_dtype=jax.numpy.bfloat16,
        )
    )
    return graphs, batches, stats


def build_state(batches, dense_m=12):
    import jax
    import numpy as np

    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.step import make_train_step

    model = CrystalGraphConvNet(
        atom_fea_len=64, n_conv=3, h_fea_len=128,
        dtype=jax.numpy.bfloat16, dense_m=dense_m,
    )
    tx = make_optimizer(optim="sgd", lr=0.01, lr_milestones=[10_000])
    targets = np.concatenate(
        [np.asarray(b.targets)[np.asarray(b.graph_mask) > 0] for b in batches]
    )
    normalizer = Normalizer.fit(targets)
    state = create_train_state(model, batches[0], tx, normalizer)
    return state, jax.jit(make_train_step(), donate_argnums=0)


def measure_dispatch_loop(state, step, device_batches, real_per_batch, n=60):
    """Per-step dispatch: host dispatches every step."""
    import jax  # noqa: F401

    structures = 0.0
    t0 = time.perf_counter()
    metrics = None
    for i in range(n):
        k = i % len(device_batches)
        state, metrics = step(state, device_batches[k])
        structures += real_per_batch[k]
    float(metrics["loss_sum"])  # value-fetch fence
    dt = time.perf_counter() - t0
    return state, structures / dt, dt / n


def measure_scan_dispatch(state, raw_step, device_batches, real_per_batch,
                          steps_per_scan=32, n_scans=3):
    """Whole-chunk dispatch: `steps_per_scan` steps per host round trip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cgnn_tpu.data.graph import batch_shape_key

    # group identically-shaped batches and stack on a leading axis
    groups, reals = {}, {}
    for b, r in zip(device_batches, real_per_batch):
        key = batch_shape_key(b)
        groups.setdefault(key, []).append(b)
        reals.setdefault(key, []).append(r)
    stacked = {
        k: jax.device_put(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *bs))
        for k, bs in groups.items()
    }

    def scan_fn(state, st, perm):
        def body(carry, i):
            batch = jax.tree_util.tree_map(lambda x: x[i], st)
            carry, metrics = raw_step(carry, batch)
            return carry, metrics["loss_sum"]

        state2, losses = jax.lax.scan(body, state, perm)
        return state2, losses.sum()

    scan_jit = jax.jit(scan_fn, donate_argnums=(0,))

    # warmup-compile each group's scan
    perms = {}
    for k, st in stacked.items():
        n_b = len(groups[k])
        idx = np.arange(steps_per_scan) % n_b
        perms[k] = jnp.asarray(idx)
        state, s = scan_jit(state, st, perms[k])
    float(s)

    per_scan_structs = {
        k: float(np.sum([reals[k][i % len(reals[k])]
                         for i in range(steps_per_scan)]))
        for k in stacked
    }
    t0 = time.perf_counter()
    total_structs = 0.0
    for _ in range(n_scans):
        for k, st in stacked.items():
            state, s = scan_jit(state, st, perms[k])
            total_structs += per_scan_structs[k]
    float(s)
    dt = time.perf_counter() - t0
    n_steps = n_scans * len(stacked) * steps_per_scan
    return state, scan_jit, stacked, perms, total_structs / dt, dt / n_steps


def trace_and_parse(scan_jit, state, stacked, perms, trace_dir):
    """Trace one scanned chunk per shape; aggregate device op time."""
    import jax

    jax.profiler.start_trace(trace_dir)
    for k, st in stacked.items():
        state, s = scan_jit(state, st, perms[k])
    float(s)
    jax.profiler.stop_trace()

    events = []
    for path in glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    ):
        with gzip.open(path, "rt") as f:
            trace = json.load(f)
        events.extend(trace.get("traceEvents", []))
    # device lanes: pid metadata names like "/device:TPU:0 ..." or "TPU"-ish
    pid_names = {
        e["pid"]: e["args"].get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and "args" in e
    }
    device_pids = {
        p for p, n in pid_names.items()
        if "TPU" in n or "tpu" in n or "device" in n.lower()
    }
    op_time: dict[str, float] = {}
    total = 0.0
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in device_pids:
            name = e.get("name", "?")
            dur = float(e.get("dur", 0.0))  # microseconds
            op_time[name] = op_time.get(name, 0.0) + dur
            total += dur
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:25]
    return {
        "pid_names": {str(k): v for k, v in pid_names.items()},
        "device_total_us": total,
        "top_ops_us": top,
    }


def analytic_roofline(batches, f=64, h=128, n_conv=3, n_h=1):
    """Bytes moved + matmul FLOPs per average step (bf16 compute).

    Bytes: every major [E|N, *] tensor read/written once per use in
    fwd+bwd (lower bound — XLA fusion means some never hit HBM; padding
    slots DO move, so use slot counts, not real counts).
    """
    import numpy as np

    n_slots = float(np.mean([b.node_capacity for b in batches]))
    e_slots = float(np.mean([b.edge_capacity for b in batches]))
    n_real = float(np.mean([np.asarray(b.node_mask).sum() for b in batches]))
    e_real = float(np.mean([np.asarray(b.edge_mask).sum() for b in batches]))
    g = float(np.mean([np.asarray(b.graph_mask).sum() for b in batches]))
    in_cap = float(np.mean(
        [b.in_mask.shape[1] for b in batches if b.in_mask is not None]
    )) if batches[0].in_slots is not None else 0.0
    # [-1]: dense batches store edges [N, M, G]; [E, G] for COO
    gauss = batches[0].edges.shape[-1]
    bf2 = 2.0  # bf16 bytes

    # Forward per conv layer, slot counts (padding moves too):
    #  read nodes[N,F] (gather, twice: v_i bcast + v_j), write z[E,2F+G] ->
    #  matmul -> z2[E,2F] (rw), BN (rw), msg[E,2F->F], agg[N,F], out[N,F]
    per_conv_fwd = (
        2 * n_slots * f * bf2          # node reads (v_i, v_j sources)
        + e_slots * (2 * f + gauss) * bf2   # z write (concat)
        + e_slots * (2 * f + gauss) * bf2   # z read by matmul
        + 2 * e_slots * 2 * f * bf2    # z2 write + read (BN+gate)
        + e_slots * f * bf2            # msg write
        + 2 * n_slots * f * bf2        # agg + out
    )
    # Backward roughly doubles the edge-side traffic and adds the
    # transpose-gather reduce: ct[E,F] read + in_slots[N,In] idx (4B) +
    # contrib reduce [N,In,F]
    per_conv_bwd = per_conv_fwd + n_slots * in_cap * (f * bf2 + 4)
    embed = 2 * n_slots * (92 + f) * bf2
    head = 2 * g * (f + h) * bf2 * 2
    bytes_step = embed + n_conv * (per_conv_fwd + per_conv_bwd) + head

    flops = 3.0 * (
        2.0 * n_real * 92 * f
        + n_conv * 2.0 * e_real * (2 * f + gauss) * (2 * f)
        + 2.0 * g * f * h
        + (n_h - 1) * 2.0 * g * h * h
        + 2.0 * g * h
    )
    # padded-slot matmul FLOPs actually executed (MXU does padding too)
    flops_slots = 3.0 * (
        2.0 * n_slots * 92 * f
        + n_conv * 2.0 * e_slots * (2 * f + gauss) * (2 * f)
        + 2.0 * g * f * h
        + 2.0 * g * h
    )
    return {
        "avg_node_slots": n_slots, "avg_edge_slots": e_slots,
        "avg_real_nodes": n_real, "avg_real_edges": e_real,
        "avg_real_graphs": g, "in_cap": in_cap,
        "bytes_per_step_est": bytes_step,
        "useful_matmul_flops_per_step": flops,
        "executed_matmul_flops_per_step": flops_slots,
        "hbm_peak_gbps": 819.0,
        "bf16_peak_tflops": 197.0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", default="/tmp/cgnn_trace")
    ap.add_argument("--steps-per-scan", type=int, default=32)
    ap.add_argument("--out", default="PERF_DATA.json")
    args = ap.parse_args()

    import jax
    import numpy as np

    from cgnn_tpu.train.step import make_train_step

    print(f"devices: {jax.devices()}", file=sys.stderr)
    graphs, batches, stats = build_workload()
    print(f"built {len(batches)} batches, {stats.summary()}", file=sys.stderr)
    state, step = build_state(batches)
    device_batches = [jax.device_put(b) for b in batches]
    real_per_batch = [float(np.asarray(b.graph_mask).sum()) for b in batches]

    # compile every shape once (per-step path)
    seen = set()
    metrics = None
    for b in device_batches:
        from cgnn_tpu.data.graph import batch_shape_key

        key = batch_shape_key(b)
        if key not in seen:
            seen.add(key)
            state, metrics = step(state, b)
    float(metrics["loss_sum"])
    print("per-step path compiled", file=sys.stderr)

    state, rate_loop, per_step_loop = measure_dispatch_loop(
        state, step, device_batches, real_per_batch
    )
    print(f"dispatch-loop: {rate_loop:,.0f} structs/s "
          f"({per_step_loop*1e3:.2f} ms/step)", file=sys.stderr)

    raw_step = make_train_step()
    state, scan_jit, stacked, perms, rate_scan, per_step_scan = (
        measure_scan_dispatch(
            state, raw_step, device_batches, real_per_batch,
            steps_per_scan=args.steps_per_scan,
        )
    )
    print(f"scan-dispatch: {rate_scan:,.0f} structs/s "
          f"({per_step_scan*1e3:.2f} ms/step)", file=sys.stderr)

    trace = trace_and_parse(scan_jit, state, stacked, perms, args.trace_dir)
    print(f"trace: device total {trace['device_total_us']/1e3:.1f} ms",
          file=sys.stderr)

    roof = analytic_roofline(batches)
    avg_structs = float(np.mean(real_per_batch))
    dev_step_s = per_step_scan  # scan mode ~= device-bound step time
    result = {
        "workload": "MP-like lognormal, batch 512, 3 buckets, dense_m=12",
        "dispatch_loop": {
            "structs_per_sec": rate_loop, "ms_per_step": per_step_loop * 1e3,
        },
        "scan_dispatch": {
            "structs_per_sec": rate_scan, "ms_per_step": per_step_scan * 1e3,
            "steps_per_scan": args.steps_per_scan,
        },
        "dispatch_overhead_ms_per_step": (per_step_loop - per_step_scan) * 1e3,
        "roofline": {
            **roof,
            "achieved_gbps_scan": roof["bytes_per_step_est"] / dev_step_s / 1e9,
            "achieved_useful_tflops_scan":
                roof["useful_matmul_flops_per_step"] / dev_step_s / 1e12,
            "achieved_executed_tflops_scan":
                roof["executed_matmul_flops_per_step"] / dev_step_s / 1e12,
            "mfu_scan": roof["useful_matmul_flops_per_step"] / dev_step_s
                        / (roof["bf16_peak_tflops"] * 1e12),
            "bandwidth_bound_step_ms":
                roof["bytes_per_step_est"] / (819e9) * 1e3,
            "compute_bound_step_ms":
                roof["executed_matmul_flops_per_step"] / (197e12) * 1e3,
        },
        "avg_structs_per_batch": avg_structs,
        "trace": trace,
    }
    with open(args.out, "w") as fo:
        json.dump(jsonfinite(result), fo, indent=1)
    print(json.dumps(jsonfinite({k: v for k, v in result.items()
                              if k != "trace"}), indent=1))


if __name__ == "__main__":
    main()
