#!/usr/bin/env python
"""Host-ingest micro-benchmark: pack + end-to-end inference rates.

The ISSUE-4 regression guard: round 5 (PERF.md) showed the forward path 98.7%
host-bound (device 112,305 structs/s, end-to-end 1,461), and the fix —
compact staging + parallel packers + pooled buffers — lives entirely in
host code that CPU CI exercises faithfully. This script measures the
ingest path at a configurable scale and prints ONE JSON line::

    {"pack_structs_per_sec": ..., "e2e_structs_per_sec": ...,
     "bytes_staged": ..., ...extras}

- ``pack_structs_per_sec`` — the pipelined pack rate alone (graphs
  through plan -> parallel_pack -> packed batches, no device);
- ``e2e_structs_per_sec`` — ``run_fast_inference`` end to end (pack +
  dispatch + stacked fetch) with a tiny model, post-compile;
- ``bytes_staged`` — host bytes of the packed batches crossing the link
  (the compact-vs-full ~12x is visible here);
- ``serial_*`` twins measured on the pre-ISSUE-4 path (serial workers,
  full-fidelity staging) so a regression in EITHER the new machinery or
  the baseline is visible per-PR, like serve-smoke.

CI runs it at smoke scale (tier1.yml "ingest-bench" step); locally, push
``--n`` up to see the at-scale separation::

    JAX_PLATFORMS=cpu python scripts/ingest_bench.py --n 2048 --workers 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--n", type=int, default=512,
                   help="synthetic MP-like structures to ingest")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--workers", type=int, default=2,
                   help="pack pipeline threads")
    p.add_argument("--rungs", type=int, default=2)
    p.add_argument("--dense-m", type=int, default=12)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--repeats", type=int, default=3,
                   help="timed rounds per metric (best is reported)")
    p.add_argument("--devices", default="auto",
                   help="device-parallel e2e leg (ISSUE 5): 'auto' or a "
                        "forced count (the CI 8-host-device dryrun)")
    p.add_argument("--wire", choices=["both", "raw", "featurized"],
                   default="both",
                   help="ISSUE-11 raw-wire leg: measure bytes-on-wire "
                        "and host-ms/request for raw (positions/"
                        "lattice/species + in-program neighbor search) "
                        "vs compact vs full staging, parity-asserted; "
                        "'featurized' skips it (the pre-ISSUE-11 "
                        "output)")
    return p


def _tree_bytes(batch) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(batch))


def _pack_all(graphs, shape_set, workers):
    """Pack the whole dataset through the pipeline; -> (seconds, bytes)."""
    from cgnn_tpu.data.pipeline import BufferPool, parallel_pack
    from cgnn_tpu.train.infer import _shape_set_plan

    pool = BufferPool() if shape_set.compact is not None else None

    def pack_job(job):
        _, sub, shape = job
        buf = None
        if pool is not None:
            key = shape_set.buffer_key(shape)
            buf = (key, pool.acquire(key, shape_set.buffer_factory(shape)))
        batch = shape_set.pack(sub, shape=shape,
                               out=None if buf is None else buf[1])
        # byte count returned, summed on the single consumer thread — a
        # shared accumulator here would race across pack workers
        return buf, _tree_bytes(batch)

    total_bytes = 0
    t0 = time.perf_counter()
    if workers > 0:
        results = parallel_pack(_shape_set_plan(graphs, shape_set),
                                pack_job, workers=workers)
    else:
        results = map(pack_job, _shape_set_plan(graphs, shape_set))
    for buf, nbytes in results:
        total_bytes += nbytes
        if buf is not None:
            pool.release(*buf)
    return time.perf_counter() - t0, total_bytes


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax
    import numpy as np

    from cgnn_tpu.data.compact import CompactSpec
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.serve.shapes import plan_shape_set
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.infer import run_fast_inference
    from cgnn_tpu.train.step import make_predict_step

    m = args.dense_m
    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=m)
    graphs = load_synthetic_mp(args.n, cfg, seed=args.seed,
                               keep_geometry=args.wire != "featurized")
    spec = CompactSpec.build(graphs, cfg.gdf(), dense_m=m)
    ladder = plan_shape_set(graphs, args.batch_size, rungs=args.rungs,
                            dense_m=m, compact=spec)
    ladder_full = plan_shape_set(graphs, args.batch_size, rungs=args.rungs,
                                 dense_m=m)

    # tiny model: the metric is ingest, not FLOPs
    model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=32,
                                dense_m=m)
    nc, ec = capacities_for(graphs, args.batch_size, dense_m=m, snug=True)
    example = next(batch_iterator(graphs, args.batch_size, nc, ec,
                                  dense_m=m, in_cap=0, snug=True))
    state = create_train_state(
        model, example, make_optimizer(),
        Normalizer.fit(np.stack([g.target for g in graphs])),
        rng=jax.random.key(args.seed),
    )

    from cgnn_tpu.data.compact import make_expander

    pstep = jax.jit(make_predict_step(make_expander(spec)))

    # pack-only rates (no device in the loop)
    pack_s, bytes_staged = min(
        (_pack_all(graphs, ladder, args.workers) for _ in
         range(args.repeats)), key=lambda r: r[0],
    )
    serial_pack_s, serial_bytes = min(
        (_pack_all(graphs, ladder_full, 0) for _ in range(args.repeats)),
        key=lambda r: r[0],
    )

    # end-to-end rates, post-compile
    kw = dict(shape_set=ladder, predict_step=pstep,
              pack_workers=args.workers)
    preds, _ = run_fast_inference(state, graphs, args.batch_size, **kw)
    e2e = max(run_fast_inference(state, graphs, args.batch_size, **kw)[1]
              for _ in range(args.repeats))
    skw = dict(shape_set=ladder_full, predict_step=pstep, pack_workers=0)
    serial_preds, _ = run_fast_inference(state, graphs, args.batch_size,
                                         **skw)
    serial_e2e = max(
        run_fast_inference(state, graphs, args.batch_size, **skw)[1]
        for _ in range(args.repeats)
    )
    # the two staging modes must agree (compact expansion <= 1 ulp f32 on
    # edge features); a mismatch is a correctness bug, not a perf number
    np.testing.assert_allclose(preds, serial_preds, rtol=1e-4, atol=1e-4)

    # dispatch-side guard (ISSUE 5): the device-parallel e2e leg — same
    # ladder/step, round-robined over the device set. Bit-exact vs the
    # single-device run over identical batches, or the guard fails.
    from cgnn_tpu.serve.devices import resolve_devices

    devices = resolve_devices(args.devices)
    mkw = dict(kw, devices=devices)
    mdev_preds, _ = run_fast_inference(state, graphs, args.batch_size,
                                       **mkw)
    mdev_e2e = max(
        run_fast_inference(state, graphs, args.batch_size, **mkw)[1]
        for _ in range(args.repeats)
    )
    np.testing.assert_array_equal(preds, mdev_preds)

    out = {
        "pack_structs_per_sec": round(args.n / pack_s, 1),
        "e2e_structs_per_sec": round(e2e, 1),
        "e2e_multidev_structs_per_sec": round(mdev_e2e, 1),
        "inference_devices": len(devices),
        "bytes_staged": int(bytes_staged),
        "serial_pack_structs_per_sec": round(args.n / serial_pack_s, 1),
        "serial_e2e_structs_per_sec": round(serial_e2e, 1),
        "serial_bytes_staged": int(serial_bytes),
        "staged_bytes_ratio": round(serial_bytes / max(bytes_staged, 1), 2),
        "n": args.n,
        "workers": args.workers,
        "compact": True,
    }

    if args.wire != "featurized":
        # ---- ISSUE-11 raw-wire leg: bytes-on-wire + host-ms/request
        # for raw vs compact vs full, parity-asserted ----
        from cgnn_tpu.data.rawbatch import plan_raw_spec, raw_from_graph
        from cgnn_tpu.serve.shapes import plan_shape_set as _plan
        from cgnn_tpu.train.infer import run_raw_inference
        from cgnn_tpu.train.step import make_predict_step as _mps

        raw_spec = plan_raw_spec(graphs, cfg.gdf(), cfg.radius, m)
        raw_ladder = _plan(graphs, args.batch_size, rungs=args.rungs,
                           dense_m=m, compact=spec, raw=raw_spec)
        all_raws = [raw_from_graph(g) for g in graphs]
        # coverage-quantile caps (plan_raw_spec): the tail beyond them
        # rides the featurized path by design — report the admit share
        admit = [i for i, r in enumerate(all_raws)
                 if r is not None and raw_ladder.admits_raw(r)]
        assert len(admit) >= 0.8 * args.n, (
            f"only {len(admit)}/{args.n} of the calibration set fits "
            f"its own calibrated caps {raw_spec.to_meta()}"
        )
        raws = [all_raws[i] for i in admit]
        n_raw = len(raws)
        # bytes ON THE WIRE per request: the f32 raw encoding vs the
        # featurized arrays a legacy client ships (the acceptance
        # criterion is the ratio, >= 20x)
        wire_raw = sum(r.wire_nbytes for r in raws)
        wire_feat = sum(
            g.atom_fea.nbytes + g.edge_fea.nbytes + g.centers.nbytes
            + g.neighbors.nbytes for g in (graphs[i] for i in admit)
        )
        # host work per request: pack time only — the raw pack is slot
        # copies, the search itself runs in-program
        def _time_pack(fn):
            best = float("inf")
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        def _pack_raw_all():
            big = raw_ladder.largest
            for s0 in range(0, n_raw, big.graph_cap):
                raw_ladder.pack_raw(raws[s0:s0 + big.graph_cap],
                                    shape=big)

        raw_pack_s = _time_pack(_pack_raw_all)
        rstep = jax.jit(_mps(raw_ladder.expander(),
                             raw_ladder.raw_expander()))
        raw_preds, _ = run_raw_inference(state, raws, raw_ladder,
                                         predict_step=rstep)
        raw_e2e = max(
            run_raw_inference(state, raws, raw_ladder,
                              predict_step=rstep)[1]
            for _ in range(args.repeats)
        )
        # parity: the in-program graph construction must agree with the
        # host featurizer's predictions (f32-roundoff tolerance — the
        # search runs in f32 where the host ran f64; tests pin the
        # bit-exact structural contract)
        feat_preds, _ = run_fast_inference(
            state, [graphs[i] for i in admit], args.batch_size,
            shape_set=raw_ladder, predict_step=rstep, pack_workers=0,
        )
        np.testing.assert_allclose(raw_preds, feat_preds, rtol=1e-3,
                                   atol=1e-3)
        out.update({
            "raw_e2e_structs_per_sec": round(raw_e2e, 1),
            "raw_pack_structs_per_sec": round(n_raw / raw_pack_s, 1),
            "raw_admit_share": round(len(admit) / args.n, 3),
            "wire_bytes_raw": int(wire_raw),
            "wire_bytes_featurized": int(wire_feat),
            "wire_bytes_ratio": round(wire_feat / max(wire_raw, 1), 1),
            "host_ms_per_request_raw": round(raw_pack_s / n_raw * 1e3,
                                             4),
            "host_ms_per_request_compact": round(pack_s / args.n * 1e3,
                                                 4),
            "host_ms_per_request_full": round(
                serial_pack_s / args.n * 1e3, 4),
        })

    print(json.dumps(jsonfinite(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
