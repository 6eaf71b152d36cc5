#!/usr/bin/env python
"""Interleaved same-process A/B of train-step variants (VERDICT r4 weak #1).

Cross-process throughput comparisons mislead where session conditions
drift (the r4 fused-kernel cross-process reading was 17% off its
interleaved truth). This harness times every variant in ONE process with
interleaved rounds on the bench PRIMARY workload, so each round's
conditions hit all variants equally.

Variants:
- linear_call  — the round-4+ gather_transpose mechanism (current default)
- custom_vjp   — the round-3 mechanism (same transpose math; the main
                 hot-path code delta between bench rounds 3 and 4)
- compact      — the round-5 compact-staging step (expansion fused in-step)

Writes BENCH_AB.json and prints it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--n", type=int, default=8192)
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--buckets", type=int, default=3)
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--out", type=str, default="BENCH_AB.json")
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from cgnn_tpu.data.compact import CompactSpec, compact_pack_fn, make_expander
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
    from cgnn_tpu.data.graph import bucketed_batch_iterator
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.ops import segment
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.step import make_train_step

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    graphs = load_synthetic_mp(args.n, cfg, seed=0)
    edge_dtype = jax.numpy.bfloat16

    def make_batches(pack_fn=None):
        return list(
            bucketed_batch_iterator(
                graphs, args.batch_size, args.buckets,
                rng=np.random.default_rng(0), dense_m=12, snug=True,
                edge_dtype=edge_dtype, pack_fn=pack_fn,
            )
        )

    full_batches = make_batches()
    spec = CompactSpec.build(graphs, cfg.gdf(), dense_m=12,
                             edge_dtype=edge_dtype)
    compact_batches = make_batches(compact_pack_fn(spec))
    expander = make_expander(spec)
    structs = [float(np.asarray(b.graph_mask).sum()) for b in full_batches]

    model = CrystalGraphConvNet(atom_fea_len=64, n_conv=3, h_fea_len=128,
                                dtype=jax.numpy.bfloat16, dense_m=12)
    tx = make_optimizer(optim="sgd", lr=0.01, lr_milestones=[10**9])
    normalizer = Normalizer.fit(
        np.stack([np.array(g.target) for g in graphs])
    )

    base_step = make_train_step()
    variants = {}
    # batches are inputs, never donated — the two full-layout variants
    # share one device copy (halves batch HBM); compact has its own
    dev_full = [jax.device_put(b) for b in full_batches]
    dev_compact = [jax.device_put(b) for b in compact_batches]
    for name in ("linear_call", "custom_vjp", "compact"):
        dev = dev_compact if name == "compact" else dev_full
        # each variant gets ITS OWN state AND normalizer arrays: donated
        # steps delete state buffers, and jax caches np->device transfers
        # by array id — sharing one Normalizer's numpy arrays across
        # variants means the first variant's donation deletes the cached
        # buffer under the others ("Array has been deleted"; this exact
        # trap broke the r4 A/B harness)
        state = create_train_state(
            model, full_batches[0], tx,
            jax.tree_util.tree_map(np.copy, normalizer),
            rng=jax.random.key(0),
        )
        if name == "compact":
            step_body = lambda s, b: base_step(s, expander(b))  # noqa: E731
        else:
            step_body = base_step
        variants[name] = {
            "dev": dev,
            "state": state,
            "step": jax.jit(step_body, donate_argnums=0),
        }

    # warmup/compile every variant (trace-time transpose impl switch)
    for name, v in variants.items():
        segment.set_transpose_impl(
            "custom_vjp" if name == "custom_vjp" else "linear_call"
        )
        seen = set()
        metrics = None
        for b in v["dev"]:
            k = (b.node_capacity, b.edge_capacity)
            if k not in seen:
                seen.add(k)
                v["state"], metrics = v["step"](v["state"], b)
        v["state"], metrics = v["step"](v["state"], v["dev"][0])
        float(metrics["loss_sum"])
    segment.set_transpose_impl("linear_call")

    # one UNRECORDED burn-in round first (despite per-shape warmup, the
    # first timed executions of a program mix in one-time runtime costs —
    # round 0 was the sole outlier in early runs), then the recorded
    # rounds ROTATE the variant order so monotonic drift within a
    # round biases each variant equally instead of always the same one
    names = list(variants)
    rounds: list[dict] = []
    for r in range(-1, args.rounds):
        order = names[r % len(names):] + names[: r % len(names)]
        for name in order:
            v = variants[name]
            t0 = time.perf_counter()
            done = 0.0
            metrics = None
            for i in range(args.steps):
                k = i % len(v["dev"])
                v["state"], metrics = v["step"](v["state"], v["dev"][k])
                done += structs[k]
            float(metrics["loss_sum"])  # value-fetch fence
            dt = time.perf_counter() - t0
            if r >= 0:  # round -1 is the discarded burn-in
                rounds.append({"round": r, "variant": name,
                               "dt_s": round(dt, 4),
                               "structs_per_sec": round(done / dt, 1)})

    def rates(name):
        return [e["structs_per_sec"] for e in rounds if e["variant"] == name]

    med = {n: float(np.median(rates(n))) for n in variants}
    spread = {n: [min(rates(n)), max(rates(n))] for n in variants}
    out = {
        "metric": "bench_ab_interleaved",
        "workload": f"MP-like n={args.n} batch={args.batch_size} "
                    f"buckets={args.buckets} dense two-tier bf16",
        "rounds": rounds,
        "median_structs_per_sec": med,
        "round_spread": spread,
        "linear_call_vs_custom_vjp": round(
            med["linear_call"] / med["custom_vjp"], 4
        ),
        "compact_vs_full": round(med["compact"] / med["linear_call"], 4),
        "device": str(jax.devices()[0].device_kind),
        "fencing": "value-fetch per round",
    }
    with open(args.out, "w") as f:
        json.dump(jsonfinite(out), f, indent=1)
    print(json.dumps(jsonfinite(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
