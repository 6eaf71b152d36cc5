#!/usr/bin/env python
"""Sweep: Pallas interval-one-hot kernel vs XLA sorted-scatter segment sum.

Default mode measures per-op forward / forward+backward times over
realistic (N, E, F, dtype, skew) shapes for each _TE chunk size.
CAVEAT: per-op timings bottom out at the per-dispatch floor regardless
of shape, so the op-level table is noise for small ops. Use
``--full-step``, which times the real jitted train step on the bench
workloads — that mode produced the retirement data recorded in
ops/pallas_scatter.py: XLA wins MP b512 by ~3% and OC20 b128 by ~13%.

Run on the real chip: python scripts/sweep_pallas.py [--full-step]
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import cgnn_tpu.ops.pallas_scatter as ps
from cgnn_tpu.ops.segment import aggregate_edge_messages


def make_case(n_nodes, deg_mean, f, dtype, skew, seed=0):
    """Sorted-centers COO case. skew='uniform'|'power' (degree distribution)."""
    rng = np.random.default_rng(seed)
    if skew == "uniform":
        deg = np.full(n_nodes, deg_mean, np.int64)
    else:  # power-law-ish: most nodes small, few huge
        deg = rng.pareto(1.5, n_nodes) + 1
        deg = np.minimum(deg / deg.mean() * deg_mean, deg_mean * 40).astype(np.int64)
    centers = np.repeat(np.arange(n_nodes, dtype=np.int32), deg)
    e = len(centers)
    msg = rng.standard_normal((e, f)).astype(np.float32)
    return (
        jnp.asarray(msg, dtype=dtype),
        jnp.asarray(centers),
        int(n_nodes),
        e,
    )


def time_fn(fn, *args, iters=30):
    out = fn(*args)
    jax.block_until_ready(out)
    best = np.inf
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e6  # us


def full_step_comparison():
    """Reliable mode: whole jitted train step per aggregation backend."""
    from bench import _bench_workload
    from cgnn_tpu.data.dataset import (
        FeaturizeConfig,
        load_synthetic_mp,
        load_synthetic_oc20,
    )
    from cgnn_tpu.ops.segment import set_default_aggregation_impl

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    workloads = [
        ("mp_b512", load_synthetic_mp(2048, cfg, seed=0), 512, 3, 24),
        ("oc20_b128", load_synthetic_oc20(384, cfg, seed=0), 128, 2, 16),
    ]
    for name, graphs, bs, buckets, n_timed in workloads:
        for impl in ("xla", "pallas"):
            set_default_aggregation_impl(impl)
            jax.clear_caches()
            r = _bench_workload(graphs, bs, buckets=buckets, n_timed=n_timed)
            print(
                f"{name:10s} {impl:7s} {r['structs_per_sec']:>11.0f} structs/s"
                f"  mfu {r['mfu']:.3f}"
            )
    set_default_aggregation_impl("xla")


def main():
    import sys

    if "--full-step" in sys.argv:
        full_step_comparison()
        return
    cases = [
        # label, nodes, mean degree, F
        ("mp_b512", 15872, 12, 64),
        ("oc20_b128", 17920, 12, 64),
        ("tiny_b512", 3584, 11, 64),
        ("wideF", 15872, 12, 128),
        ("hugeF", 15872, 12, 256),
    ]
    print(f"device: {jax.devices()[0].device_kind}")
    header = (
        f"{'case':10s} {'dtype':8s} {'skew':8s} {'E':>8s} "
        f"{'xla_fwd':>9s} {'pal_fwd':>9s} {'xla_fb':>9s} {'pal_fb':>9s}  best"
    )
    for te in (256, 512, 1024):
        ps._TE = te
        jax.clear_caches()
        print(f"\n=== _TE={te} ===\n{header}")
        for label, n, deg, f in cases:
            for dtype in (jnp.float32, jnp.bfloat16):
                for skew in ("uniform", "power"):
                    msg, centers, nn, e = make_case(n, deg, f, dtype, skew)

                    def fwd(impl):
                        return jax.jit(
                            lambda m, c: aggregate_edge_messages(m, c, nn, impl=impl)
                        )

                    def fwdbwd(impl):
                        def loss(m, c):
                            return jnp.sum(
                                aggregate_edge_messages(m, c, nn, impl=impl) ** 2
                            )
                        return jax.jit(jax.grad(loss, argnums=0))

                    tx = time_fn(fwd("xla"), msg, centers)
                    tp = time_fn(fwd("pallas"), msg, centers)
                    txb = time_fn(fwdbwd("xla"), msg, centers)
                    tpb = time_fn(fwdbwd("pallas"), msg, centers)
                    best = "pallas" if tpb < txb else "xla"
                    print(
                        f"{label:10s} {np.dtype(dtype).name:8s} {skew:8s} {e:8d} "
                        f"{tx:9.1f} {tp:9.1f} {txb:9.1f} {tpb:9.1f}  {best}"
                    )


if __name__ == "__main__":
    main()
