#!/usr/bin/env python
"""Measure --telemetry step overhead on the scan dispatch path.

The ISSUE-1 acceptance criterion: with step-level telemetry on the bench
PRIMARY workload, per-step records stream from inside the scan AND the
measured step-time overhead stays < 5%. This harness builds the PRIMARY
MP-like workload (the cell mp.train's distribution), drives ScanEpochDriver epochs
with telemetry off vs step INTERLEAVED in one process (paired runs:
PERF.md "End-to-end metrics"), and prints
one JSON line:

    {"off_s": [...], "step_s": [...], "overhead": <median ratio - 1>,
     "step_records": N, "parity": true}

Run on the real chip for the acceptance number; on CPU it still verifies
streaming + parity and gives an upper-bound overhead reading.

Usage: python scripts/telemetry_overhead.py [--graphs 512] [--epochs 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402


def build(args, telemetry):
    import numpy as np

    import jax
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.loop import ScanEpochDriver
    from cgnn_tpu.train.step import make_eval_step, make_train_step

    graphs = load_synthetic_mp(
        args.graphs, FeaturizeConfig(radius=8.0, max_num_nbr=12), seed=0
    )
    dense_m = 12 if args.layout == "dense" else None
    node_cap, edge_cap = capacities_for(graphs, args.batch_size,
                                        dense_m=dense_m, snug=True)
    batches = list(batch_iterator(graphs, args.batch_size, node_cap,
                                  edge_cap, dense_m=dense_m, snug=True))
    model = CrystalGraphConvNet(atom_fea_len=64, n_conv=3, h_fea_len=128,
                                dense_m=dense_m)
    tx = make_optimizer(optim="sgd", lr=0.01, lr_milestones=[10**9])
    state = create_train_state(
        model, batches[0], tx,
        Normalizer.fit(np.stack([g.target for g in graphs])),
        rng=jax.random.key(0),
    )
    drv = ScanEpochDriver(
        make_train_step(grad_health=telemetry.step_level),
        make_eval_step(),
        batches, batches[:1], np.random.default_rng(0),
        telemetry=telemetry,
    )
    return state, drv


def drive(args, telemetry):
    state, drv = build(args, telemetry)
    state = drv.warm(state)
    times = []
    final = None
    for e in range(args.epochs):
        t0 = time.perf_counter()
        state, tm, _ = drv.run_epoch_pair(state, first=e == 0)
        times.append(round(time.perf_counter() - t0, 4))
        final = tm
    import jax
    import numpy as np

    params = jax.tree_util.tree_map(np.asarray, state.params)
    return times, final, params


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--graphs", type=int, default=512)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--layout", choices=["dense", "coo"],
                   default=os.environ.get("CGNN_BENCH_LAYOUT", "dense"))
    p.add_argument("--out", type=str, default="")
    args = p.parse_args()

    from cgnn_tpu.observe import Telemetry

    import numpy as np

    off_s, step_s = [], []
    step_records = 0
    params_off = params_step = None
    log_dir = tempfile.mkdtemp(prefix="telem_overhead_")
    # interleave off/step rounds (PERF.md §8: in-process interleaved
    # comparisons only; order rotated per round)
    for r in range(args.rounds):
        order = ["off", "step"] if r % 2 == 0 else ["step", "off"]
        for mode in order:
            telemetry = (
                Telemetry.disabled() if mode == "off"
                else Telemetry("step", os.path.join(log_dir, f"r{r}"))
            )
            times, _, params = drive(args, telemetry)
            if mode == "off":
                off_s.append(sum(times))
                params_off = params
            else:
                step_s.append(sum(times))
                params_step = params
                if telemetry.stream is not None:
                    import jax

                    jax.effects_barrier()
                    step_records = max(
                        step_records,
                        len(telemetry.stream.records("train")),
                    )
                telemetry.close()

    import jax

    parity = all(
        np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(params_off),
            jax.tree_util.tree_leaves(params_step),
        )
    )
    overhead = float(np.median(step_s) / np.median(off_s) - 1.0)
    out = {
        "off_s": off_s,
        "step_s": step_s,
        "overhead": round(overhead, 4),
        "step_records": step_records,
        "parity": parity,
        "device": str(jax.devices()[0].device_kind
                      or jax.devices()[0].platform),
        "layout": args.layout,
        "epochs_per_round": args.epochs,
    }
    line = json.dumps(jsonfinite(out))
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if parity else 1


if __name__ == "__main__":
    sys.exit(main())
