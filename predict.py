#!/usr/bin/env python
"""Reference-compatible inference entrypoint (SURVEY.md §2 component 2, §3.2).

Loads a checkpoint saved by train.py (model hyperparams + featurization
config + Normalizer state ride inside it, like the reference's checkpoint
``args``), runs the forward pass over a directory of CIFs, denormalizes,
and writes ``test_results.csv`` rows of ``id, target, prediction``.

Usage:
    python predict.py CKPT_DIR DATA_DIR [--device=...] [--out csv]
"""

from __future__ import annotations

import argparse
import csv
import os
import sys

from cgnn_tpu.runtime import COMPILE_CACHE_HELP
from cgnn_tpu.runtime import start as start_runtime


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("ckpt_dir", help="checkpoint directory written by train.py")
    p.add_argument("root_dir", nargs="?", default=None,
                   help="dataset dir: {id}.cif + id_prop.csv (optional "
                        "with --cache / --synthetic)")
    p.add_argument("--device", choices=["auto", "cpu", "tpu"], default="auto")
    p.add_argument("--best", action="store_true",
                   help="load the best checkpoint instead of the latest")
    p.add_argument("-b", "--batch-size", type=int, default=256)
    p.add_argument("--out", default="test_results.csv")
    p.add_argument("--synthetic", type=int, default=0,
                   help="predict on N synthetic structures (smoke runs)")
    p.add_argument("--cache", type=str, default="",
                   help="featurized graph cache (data/cache.py) to predict "
                        "from instead of parsing CIFs")
    p.add_argument("--packing", choices=["snug", "ladder"], default="snug",
                   help="snug = fill-to-capacity batches (train.py's "
                        "default; >=0.97 padding efficiency)")
    p.add_argument("--buckets", type=int, default=0,
                   help="legacy per-size-class capacity derivation (use 3 "
                        "for MP-scale mixed sizes); default packs into the "
                        "serving shape ladder instead (--rungs)")
    p.add_argument("--rungs", type=int, default=2,
                   help="serving shape-ladder depth (serve.shapes): the "
                        "compile count is pinned at this many programs, "
                        "shared with an online server via the persistent "
                        "compile cache")
    p.add_argument("--pack-workers", type=int, default=None,
                   help="host pack pipeline threads (data/pipeline.py) "
                        "overlapping packing with device dispatch; 0 packs "
                        "serially on the main thread (default: 4 on an "
                        "accelerator backend, 0 on CPU — overlap threads "
                        "only steal cores from a CPU 'device')")
    p.add_argument("--wire", choices=["auto", "raw", "featurized"],
                   default="auto",
                   help="wire format of the ladder path (ISSUE 11): "
                        "'raw' stages (positions, lattice, species) and "
                        "the compiled program runs the periodic neighbor "
                        "search + featurization itself (~100x fewer "
                        "staged bytes, near-zero host work; structures "
                        "outside the raw rung caps ride the featurized "
                        "path); 'auto' engages on accelerator backends "
                        "— on CPU the host IS the device, so moving the "
                        "search 'on device' buys nothing")
    p.add_argument("--compact", choices=["auto", "on", "off"],
                   default="auto",
                   help="stage raw CompactBatch forms (~12x fewer host and "
                        "H2D bytes; data/compact.py) and expand on device; "
                        "'auto' engages on accelerator backends when the "
                        "dataset probes stageable, falling back to "
                        "full-fidelity staging otherwise")
    p.add_argument("--devices", default="auto", metavar="{auto,N}",
                   help="device-parallel dispatch (serve/devices.py): "
                        "distribute over this many local devices. 'auto' "
                        "= all devices on accelerator backends, one on "
                        "CPU (host 'devices' share the same cores); an "
                        "integer forces")
    p.add_argument("--engine", choices=["auto", "mesh", "threads"],
                   default="auto",
                   help="multi-device execution layer (ISSUE 10): 'mesh' "
                        "(the auto default with >1 device) stacks batches "
                        "N-at-a-time and ONE sharded jitted dispatch "
                        "covers all devices; 'threads' keeps the ISSUE-5 "
                        "per-device replica round-robin (the A/B leg)")
    p.add_argument("--compile-cache", type=str, default=None,
                   metavar="DIR", help=COMPILE_CACHE_HELP)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    refusal = start_runtime(args.device, args.compile_cache)
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    from cgnn_tpu.train import CheckpointManager

    mgr = CheckpointManager(args.ckpt_dir)
    try:
        # single exit path: every return below (incl. early argument/data
        # errors) flows through the finally, so the manager's finalizer
        # thread and orbax handles are always closed
        return _run(args, mgr)
    finally:
        mgr.close()


def _probe_compact(args, graphs, data_cfg, layout_m, edge_dtype):
    """CompactSpec for this dataset, or None (full-fidelity staging):
    --compact off, a CPU backend under 'auto' (the device IS the host —
    nothing to save, re-expansion to pay), COO layout, or a dataset the
    probe rejects (continuous atom features / stale cache) all fall back
    loudly-but-gracefully."""
    import sys

    import jax

    if args.compact == "off" or layout_m is None:
        return None
    if args.compact == "auto" and jax.default_backend() == "cpu":
        return None
    from cgnn_tpu.data.compact import CompactSpec, CompactUnsupported

    try:
        return CompactSpec.build(
            graphs, data_cfg.featurize_config().gdf(), dense_m=layout_m,
            edge_dtype=edge_dtype,
        )
    except CompactUnsupported as e:
        print(f"compact staging unavailable ({e}); using full-fidelity "
              f"packing", file=sys.stderr)
        return None


def _run(args, mgr) -> int:
    import jax
    import numpy as np

    from cgnn_tpu.config import DataConfig, ModelConfig, build_model
    from cgnn_tpu.data.dataset import (
        load_cif_directory,
        load_synthetic,
        load_trajectory,
    )
    from cgnn_tpu.data.graph import batch_iterator
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.infer import run_fast_inference
    from cgnn_tpu.train.loop import capacities_for

    from cgnn_tpu.serve.devices import resolve_devices

    if args.pack_workers is None:
        args.pack_workers = 4 if jax.default_backend() != "cpu" else 0
    devices = resolve_devices(args.devices)
    # the engine that RUNS (train/infer.py's rule), for the printed line
    engine_ran = ("single" if len(devices) == 1
                  else "threads" if args.engine == "threads" else "mesh")
    tag = "best" if args.best else "latest"
    if not mgr.exists(tag):
        print(f"no '{tag}' checkpoint under {args.ckpt_dir}", file=sys.stderr)
        return 2

    meta = mgr.read_meta(tag)
    model_cfg = ModelConfig.from_meta(meta["model"])
    data_cfg = DataConfig.from_meta(meta["data"])
    task = meta.get("task", "regression")
    force_task = task == "force"
    model = build_model(model_cfg, data_cfg, task, log_fn=print)

    if args.cache and not os.path.exists(args.cache):
        print(f"--cache {args.cache} does not exist", file=sys.stderr)
        return 2
    # raw wire wants geometry kept at featurize time (the graphs convert
    # back to wire form via raw_from_graph); CPU 'auto' stays featurized
    # — the host IS the device (the compact/pack-workers rule)
    want_raw = (args.wire == "raw"
                or (args.wire == "auto" and jax.default_backend() != "cpu"))
    if args.cache:
        from cgnn_tpu.data.cache import load_graph_cache

        graphs = load_graph_cache(args.cache)
        print(f"loaded {len(graphs)} graphs from {args.cache}")
    elif args.synthetic:
        if force_task:
            graphs = load_trajectory(args.synthetic, data_cfg.featurize_config())
        else:
            graphs = load_synthetic(args.synthetic,
                                    data_cfg.featurize_config(),
                                    keep_geometry=want_raw)
    else:
        if not args.root_dir:
            print("DATA_DIR, --cache, or --synthetic is required",
                  file=sys.stderr)
            return 2
        from cgnn_tpu.data.trajectory import is_trajectory_path

        if force_task and is_trajectory_path(args.root_dir):
            from cgnn_tpu.data.trajectory import load_trajectory_root

            graphs = [
                g
                for grp in load_trajectory_root(
                    args.root_dir, data_cfg.featurize_config())
                for g in grp
            ]
        else:
            graphs = load_cif_directory(
                args.root_dir, data_cfg.featurize_config(),
                keep_geometry=force_task or want_raw,
            )
    # pack the way the model expects (dense slot layout rides in the
    # checkpoint meta; see data/graph.py pack_graphs)
    layout_m = model_cfg.dense_m or None
    snug = args.packing == "snug"
    edge_dtype = (jax.numpy.bfloat16 if model_cfg.dtype == "bfloat16"
                  else np.float32)
    node_cap, edge_cap = capacities_for(graphs, args.batch_size,
                                        dense_m=layout_m, snug=snug)

    # take the example from the iterator (respects capacities; a direct
    # pack_graphs of an oversize head batch would fail)
    example = next(batch_iterator(graphs, args.batch_size, node_cap, edge_cap,
                                  dense_m=layout_m, in_cap=0, snug=snug,
                                  edge_dtype=edge_dtype))
    state = create_train_state(
        model, example, make_optimizer(),
        Normalizer.identity(model_cfg.num_targets), rng=jax.random.key(0),
    )
    state = mgr.restore_for_inference(state, tag)

    rows = []
    force_ids: list[str] = []
    force_arrays: list[np.ndarray] = []
    if force_task:
        from cgnn_tpu.train.force_step import make_force_predict_step

        predict_step = jax.jit(make_force_predict_step())
        idx = 0
        # per-atom force extraction needs host-side node bookkeeping per
        # batch; force datasets are small, so this path keeps the simple
        # fetch-per-batch loop
        for batch in batch_iterator(graphs, args.batch_size, node_cap,
                                    edge_cap, dense_m=layout_m, in_cap=0,
                                    snug=snug, edge_dtype=edge_dtype):
            out = jax.tree_util.tree_map(  # true copies (GC-ALIAS)
                np.array, jax.device_get(predict_step(state, batch)))
            energies, forces = (np.asarray(out[0]), np.asarray(out[1]))
            node_graph = np.asarray(batch.node_graph)
            node_mask = np.asarray(batch.node_mask) > 0
            n_real = int(np.asarray(batch.graph_mask).sum())
            for k in range(n_real):
                g = graphs[idx]
                rows.append(
                    [g.cif_id]
                    + [f"{t:.6f}" for t in np.atleast_1d(g.target)]
                    + [f"{energies[k]:.6f}"]
                )
                force_ids.append(g.cif_id)
                force_arrays.append(forces[(node_graph == k) & node_mask])
                idx += 1
    elif args.buckets >= 1:
        # legacy path (any EXPLICIT --buckets, including 1): per-size-
        # class snug capacities derived from THIS dataset (fresh compiles
        # per run); the unset default (0) takes the shape ladder below
        preds, rate = run_fast_inference(
            state, graphs, args.batch_size, buckets=args.buckets,
            dense_m=layout_m, snug=snug, edge_dtype=edge_dtype,
            compact=_probe_compact(args, graphs, data_cfg, layout_m,
                                   edge_dtype),
            pack_workers=args.pack_workers, devices=devices,
            engine=args.engine,
        )
        print(f"inference throughput: {rate:.0f} structures/sec "
              f"(dispatch-pipelined, single fetch per bucket, "
              f"{len(devices)} device(s), {engine_ran} engine)")
    else:
        # default: pack into the serving shape ladder (serve.shapes) —
        # compile count pinned at --rungs, and shared with an online
        # server through the persistent XLA compile cache. Compact-staged
        # by default: batches cross the link in raw form (~12x smaller)
        # and the ladder's packers run on --pack-workers threads.
        from cgnn_tpu.serve.shapes import plan_shape_set

        # raw wire (ISSUE 11): structures stage as (positions, lattice,
        # species) and the compiled program builds the graph; anything
        # outside the raw rung caps rides the featurized ladder, rows
        # merged back in input order
        raws, raw_spec, why_not = [], None, ""
        if want_raw and layout_m is None:
            why_not = "raw wire requires the dense layout"
        elif want_raw:
            from cgnn_tpu.data.rawbatch import (
                RawUnsupported,
                plan_raw_spec,
                raw_from_graph,
            )

            raws = [raw_from_graph(g) for g in graphs]
            fcfg = data_cfg.featurize_config()
            if all(r is None for r in raws):
                why_not = ("no structure carries geometry and species — "
                           "featurize with keep_geometry (a graph cache "
                           "built without it cannot ride the raw wire)")
            else:
                try:
                    raw_spec = plan_raw_spec(graphs, fcfg.gdf(),
                                             fcfg.radius, layout_m)
                except RawUnsupported as e:
                    why_not = str(e)
        shape_set = plan_shape_set(
            graphs, args.batch_size, rungs=args.rungs, dense_m=layout_m,
            edge_dtype=edge_dtype, num_targets=model_cfg.num_targets,
            compact=_probe_compact(args, graphs, data_cfg, layout_m,
                                   edge_dtype),
            raw=raw_spec,
        )
        raw_idx = [i for i, r in enumerate(raws)
                   if r is not None and shape_set.admits_raw(r)]
        if want_raw and not raw_idx:
            why_not = why_not or "no structure fits the raw rung caps"
            if args.wire == "raw":
                print(f"--wire raw: 0/{len(graphs)} structures can ride "
                      f"the raw wire ({why_not})", file=sys.stderr)
                return 2
            print(f"raw wire unavailable ({why_not}); featurized wire",
                  file=sys.stderr)
        admitted = set(raw_idx)
        feat_idx = [i for i in range(len(graphs)) if i not in admitted]
        preds = np.zeros((len(graphs), model_cfg.num_targets), np.float32)
        if raw_idx:
            from cgnn_tpu.train.infer import run_raw_inference

            by_id = {id(raws[i]): graphs[i] for i in raw_idx}
            preds[raw_idx], rate = run_raw_inference(
                state, [raws[i] for i in raw_idx], shape_set,
                devices=devices, engine=args.engine,
                raw_fallback=lambda rs: by_id[id(rs)],
            )
            print(f"inference throughput: {rate:.0f} structures/sec "
                  f"(raw wire, in-program neighbor search, "
                  f"{len(raw_idx)}/{len(graphs)} structures raw-staged, "
                  f"{len(shape_set)}-rung ladder, {len(devices)} "
                  f"device(s), {engine_ran} engine)")
        if feat_idx:
            preds[feat_idx], rate = run_fast_inference(
                state, [graphs[i] for i in feat_idx], args.batch_size,
                shape_set=shape_set, pack_workers=args.pack_workers,
                devices=devices, engine=args.engine,
            )
            print(f"inference throughput: {rate:.0f} structures/sec "
                  f"(featurized wire, {len(feat_idx)}/{len(graphs)} "
                  f"structures, dispatch-pipelined, {len(shape_set)}-rung "
                  f"shape ladder, "
                  f"{'compact' if shape_set.compact else 'full'}-staged, "
                  f"{args.pack_workers} pack workers, "
                  f"{len(devices)} device(s), {engine_ran} engine)")
    if not force_task:
        for g, p in zip(graphs, preds):
            rows.append(
                [g.cif_id]
                + [f"{t:.6f}" for t in np.atleast_1d(g.target)]
                + [f"{v:.6f}" for v in p]
            )
    with open(args.out, "w", newline="") as f:
        csv.writer(f).writerows(rows)
    print(f"wrote {len(rows)} predictions to {args.out}")
    if force_task:
        np.savez(
            args.out + ".forces.npz",
            ids=np.array(force_ids),
            **{f"forces_{i}": f for i, f in enumerate(force_arrays)},
        )
        print(f"wrote per-atom forces to {args.out}.forces.npz")
    return 0


if __name__ == "__main__":
    sys.exit(main())
