#!/usr/bin/env python
"""Controlled accuracy A/B: reconcile the flagship-scale val-MAE record.

VERDICT r3 weak #1: the MP-146k scale proof recorded val MAE 0.043 in round
2 but 0.05988 with the round-3 stack, and nothing on the record attributes
the delta. This script isolates the r2->r3 stack changes one at a time on a
deterministic subset of the same cached MP-like dataset, same seed, same
epoch budget, ALL CONFIGS IN ONE PROCESS (the repo's paired-run practice —
session drift cannot skew a same-process comparison, and MAE does not
depend on it anyway):

  r4         dense two-tier + snug + scan + bf16 + one-pass BN (current)
  perstep    r4 with the per-step device-resident loop (no scan)
  ladder     r4 with ladder packing (r2's batch-size-closed batches)
  twopass    r4 with two-pass centered BN statistics (r2 estimator)
  f32        r4 with float32 model compute
  r2stack    COO + ladder + per-step + two-pass BN + bf16 (the r2 recipe)
  r4-s1/-s2  r4 at seeds 1, 2 (seed-noise band, split resampled too)

Each record carries steps/epoch (packing policies change the optimizer step
count at fixed epochs — the leading undertraining suspect) and the full
per-epoch val-MAE curve. Writes MAE_AB.json.

Usage: python scripts/mae_ab.py [--n 40960] [--epochs 6]
       [--cache /tmp/mp146k_cache.npz]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402


def run_config(
    name: str,
    graphs,
    *,
    epochs: int,
    batch_size: int,
    buckets: int,
    seed: int,
    dense: bool,
    snug: bool,
    scan: bool,
    two_pass: bool,
    dtype_name: str,
    max_num_nbr: int,
) -> dict:
    import jax
    import numpy as np

    from cgnn_tpu.data.dataset import train_val_test_split
    from cgnn_tpu.data.graph import bucketed_batch_iterator, pack_graphs
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.ops.norm import force_two_pass_stats
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.loop import capacities_for, fit

    t0 = time.perf_counter()
    train_g, val_g, _ = train_val_test_split(graphs, 0.9, 0.05, seed=seed)
    layout_m = max_num_nbr if dense else None
    dtype = jax.numpy.bfloat16 if dtype_name == "bf16" else jax.numpy.float32
    model = CrystalGraphConvNet(atom_fea_len=64, n_conv=3, h_fea_len=128,
                                dtype=dtype, dense_m=layout_m)
    tx = make_optimizer(optim="adam", lr=0.01, lr_milestones=[10**9])
    normalizer = Normalizer.fit(np.stack([g.target for g in train_g]))
    node_cap, edge_cap = capacities_for(train_g, batch_size,
                                        dense_m=layout_m, snug=snug)
    example = pack_graphs(
        sorted(train_g[: batch_size // 2], key=lambda g: g.num_nodes),
        node_cap, edge_cap, batch_size, dense_m=layout_m,
    )
    state = create_train_state(model, example, tx, normalizer,
                               rng=jax.random.key(seed))

    # the step count this packing policy yields (undertraining suspect):
    # materialize one epoch's iterator exactly as fit() will
    steps = sum(1 for _ in bucketed_batch_iterator(
        train_g, batch_size, buckets,
        shuffle=True, rng=np.random.default_rng(seed),
        dense_m=layout_m, snug=snug,
    ))

    curve: list[float] = []
    train_curve: list[float] = []

    def on_epoch_metrics(_e, train_m, val_m):
        curve.append(round(float(val_m.get("mae", np.nan)), 5))
        train_curve.append(round(float(train_m.get("mae", np.nan)), 5))

    force_two_pass_stats(two_pass)
    try:
        state, result = fit(
            state, train_g, val_g, epochs=epochs, batch_size=batch_size,
            node_cap=node_cap, edge_cap=edge_cap, buckets=buckets,
            seed=seed, print_freq=0, snug=snug, dense_m=layout_m,
            scan_epochs=scan, device_resident=True,
            on_epoch_metrics=on_epoch_metrics,
            log_fn=lambda m: print(f"  [{name}] {m}", file=sys.stderr),
        )
    finally:
        force_two_pass_stats(False)
    rec = {
        "name": name,
        "seed": seed,
        "dense": dense,
        "snug": snug,
        "scan": scan,
        "two_pass_bn": two_pass,
        "dtype": dtype_name,
        "steps_per_epoch": steps,
        "val_mae_per_epoch": curve,
        "train_mae_per_epoch": train_curve,
        "best_val_mae": round(float(result["best"]), 5),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    print(json.dumps(jsonfinite(rec)), file=sys.stderr)
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--n", type=int, default=40_960)
    p.add_argument("--epochs", type=int, default=6)
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--buckets", type=int, default=3)
    p.add_argument("--cache", type=str, default="/tmp/mp146k_cache.npz")
    p.add_argument("--out", type=str, default="MAE_AB.json")
    p.add_argument("--configs", type=str, default="",
                   help="comma-separated subset of config names to run")
    args = p.parse_args(argv)

    from cgnn_tpu.data.cache import load_graph_cache
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    if os.path.exists(args.cache):
        t0 = time.perf_counter()
        graphs = load_graph_cache(args.cache)[: args.n]
        print(f"loaded {len(graphs)} graphs from cache "
              f"({time.perf_counter() - t0:.0f}s)", file=sys.stderr)
    else:
        print(f"cache {args.cache} missing; featurizing {args.n} "
              f"(slow, one-time)", file=sys.stderr)
        graphs = load_synthetic_mp(args.n, cfg, seed=0)

    base = dict(
        epochs=args.epochs, batch_size=args.batch_size, buckets=args.buckets,
        seed=0, dense=True, snug=True, scan=True, two_pass=False,
        dtype_name="bf16", max_num_nbr=cfg.max_num_nbr,
    )
    matrix = [
        ("r4", {}),
        ("perstep", {"scan": False}),
        ("ladder", {"snug": False}),
        ("twopass", {"two_pass": True}),
        ("f32", {"dtype_name": "f32"}),
        ("r2stack", {"dense": False, "snug": False, "scan": False,
                     "two_pass": True}),
        ("r4-s1", {"seed": 1}),
        ("r4-s2", {"seed": 2}),
    ]
    only = {s.strip() for s in args.configs.split(",") if s.strip()}
    records = []
    for name, overrides in matrix:
        if only and name not in only:
            continue
        print(f"=== {name} ===", file=sys.stderr)
        records.append(run_config(name, graphs, **(base | overrides)))

    out = {
        "metric": "mae_ab",
        "n_structures": len(graphs),
        "epochs": args.epochs,
        "records": records,
    }
    with open(args.out, "w") as f:
        json.dump(jsonfinite(out), f, indent=2)
    print(json.dumps(jsonfinite(
        {r["name"]: r["best_val_mae"] for r in records})))
    return 0


if __name__ == "__main__":
    sys.exit(main())
