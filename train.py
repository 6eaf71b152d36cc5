#!/usr/bin/env python
"""Reference-compatible training entrypoint with ``--device={cpu,tpu}``.

Flag surface mirrors the reference lineage's ``main.py``/``train.py``
(SURVEY.md §2 component 1, §5 config system): same names where known
(``--task``, ``--n-conv``, ``--atom-fea-len``, ``--max-num-nbr``,
``--radius``, ``--resume``, ``--lr-milestones`` in epochs, ...), plus the
TPU-native additions: ``--device``, ``--data-parallel``, ``--bf16``,
and ``--synthetic N`` (offline stand-in for MP/OC20 downloads,
SURVEY.md §7 phase 0).

Usage:
    python train.py DATA_DIR [flags]         # {id}.cif + id_prop.csv layout
    python train.py --synthetic 1000 [flags] # packaged synthetic dataset
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from cgnn_tpu.runtime import COMPILE_CACHE_HELP
from cgnn_tpu.runtime import start as start_runtime


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("root_dir", nargs="?", default=None,
                   help="dataset dir: {id}.cif files + id_prop.csv")
    p.add_argument("--synthetic", type=int, default=0, metavar="N",
                   help="train on N synthetic crystals instead of root_dir")
    p.add_argument("--synthetic-oc20", type=int, default=0, metavar="N",
                   help="train on N synthetic OC20-like catalyst slabs "
                        "(50-200+ atom graphs; BASELINE config #4)")
    p.add_argument("--task",
                   choices=["regression", "classification", "force",
                            "blockdiff", "lm"],
                   default="regression",
                   help="'force' trains the differentiable force field on "
                        "energy+force labels (BASELINE config #5); "
                        "'blockdiff' the block-diffusion mixture-of-experts "
                        "decoder on packed token sequences, 'lm' a "
                        "mixture-of-experts decoder on next-token "
                        "prediction, window-and-full-attention, hybrid "
                        "short-convolution or hybrid Mamba-2 by --lm-model (all "
                        "cgnn_tpu/train/blockdiff.py)")
    p.add_argument("--lm-model", default="tiny",
                   help="the task's preset, which names its model too "
                        "(--task blockdiff: tiny | sdar-ep8; --task lm: "
                        "tiny | trinity-mini-ep16 | lfm2-tiny | "
                        "lfm2-24b-a2b-ep8 | nemotron-tiny | "
                        "nemotron-3-nano-30b-a3b-ep16) or a JSON file of the "
                        "fields of "
                        "the task's config dataclass "
                        "(models.sdar.SdarConfig; models.afmoe.AfmoeConfig)")
    p.add_argument("--lm-seq-len", type=int, default=64,
                   help="--task blockdiff | lm: tokens a packed sequence "
                        "(--synthetic N sequences, -b of them a step)")
    p.add_argument("--device", choices=["auto", "cpu", "tpu"], default="auto",
                   help="accelerator (reference flag; 'auto' uses what jax finds)")
    p.add_argument("--epochs", type=int, default=30)
    p.add_argument("--start-epoch", type=int, default=0)
    p.add_argument("-b", "--batch-size", type=int, default=256)
    p.add_argument("--lr", "--learning-rate", type=float, default=0.01, dest="lr")
    p.add_argument("--lr-milestones", type=int, nargs="*", default=[100],
                   help="epochs at which lr decays by 10x (torch MultiStepLR)")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--optim", choices=["SGD", "Adam", "AdamW"], default="SGD")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--resume", type=str, default="",
                   help="checkpoint dir to resume from, or 'auto': resume "
                        "from --ckpt-dir when a valid checkpoint exists, "
                        "start fresh otherwise (the requeue-after-"
                        "preemption mode; see README Fault tolerance)")
    p.add_argument("--train-ratio", type=float, default=0.8)
    p.add_argument("--val-ratio", type=float, default=0.1)
    # model hyperparams (reference names)
    p.add_argument("--atom-fea-len", type=int, default=64)
    p.add_argument("--h-fea-len", type=int, default=128)
    p.add_argument("--n-conv", type=int, default=3)
    p.add_argument("--n-h", type=int, default=1)
    p.add_argument("--node-norm", choices=["batch", "layer"],
                   default="batch",
                   help="normalisation after each conv's neighbour sum: "
                        "BatchNorm (the lineage's bn2) or LayerNorm over a "
                        "node's features (the Open Catalyst CGCNN)")
    p.add_argument("--no-pool-softplus", action="store_true",
                   help="no softplus on the pooled vector before conv_to_fc "
                        "(the Open Catalyst CGCNN has none)")
    p.add_argument("--loss", choices=["mse", "l1"], default="mse",
                   help="regression loss on the standardised targets")
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--num-classes", type=int, default=2)
    p.add_argument("--multi-task-head", action="store_true",
                   help="per-task MLP heads over the shared trunk for "
                        "multi-column targets (BASELINE config #3)")
    # featurization (reference names)
    p.add_argument("--max-num-nbr", type=int, default=12)
    p.add_argument("--radius", type=float, default=8.0)
    p.add_argument("--dmin", type=float, default=0.0)
    p.add_argument("--step", type=float, default=0.2)
    p.add_argument("--gauss-var", type=float, default=None, metavar="W",
                   help="the Gaussians' width in exp(-(d - mu)^2 / W^2) "
                        "(default: --step)")
    # input pipeline
    p.add_argument("--cache", type=str, default="",
                   help="graph cache (.npz): loaded if present, else written "
                        "after featurization (see cgnn_tpu.data.preprocess)")
    p.add_argument("-j", "--workers", type=int, default=0,
                   help="featurization worker processes (0 = all cores)")
    # runtime
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ckpt-dir", type=str, default="checkpoints")
    # fault tolerance (cgnn_tpu.resilience; README "Fault tolerance")
    p.add_argument("--keep-ckpts", type=int, default=3, metavar="K",
                   help="checkpoint retention: newest K versioned saves "
                        "plus the best-pointer target (0 keeps all)")
    p.add_argument("--guard", choices=["off", "skip", "rollback"],
                   default="skip",
                   help="divergence guard. 'skip' (default): non-finite "
                        "updates are skipped ON DEVICE (jnp.where select; "
                        "an identity when nothing fires). "
                        "'rollback' additionally restores the last good "
                        "checkpoint with an LR cut when >= "
                        "--guard-max-skips steps of one epoch were "
                        "skipped. 'off' disables both")
    p.add_argument("--guard-max-skips", type=int, default=3, metavar="K",
                   help="skipped steps per epoch that count as divergence "
                        "(--guard rollback)")
    p.add_argument("--guard-lr-cut", type=float, default=0.5,
                   help="LR multiplier applied per rollback")
    p.add_argument("--guard-max-rollbacks", type=int, default=3,
                   help="rollback budget before the run fails for real")
    p.add_argument("--no-preempt-handler", action="store_true",
                   help="do not trap SIGTERM/SIGINT for graceful "
                        "checkpoint-and-resume (exit code 75)")
    # observability (SURVEY.md §5; cgnn_tpu.observe)
    p.add_argument("--telemetry", choices=["off", "epoch", "step"],
                   default="epoch",
                   help="telemetry level (cgnn_tpu.observe). 'epoch' "
                        "(default, zero per-step overhead): epoch records "
                        "in metrics.jsonl + host span trace (trace.json, "
                        "open in Perfetto) + run manifest (manifest.json) "
                        "+ padding/HBM/dispatch gauges. 'step' adds "
                        "per-step loss/grad-norm/NaN streaming from "
                        "INSIDE the epoch scan (async host callback; scan "
                        "trajectory unchanged) and in-graph grad-health "
                        "metrics. 'off' writes nothing")
    p.add_argument("--log-dir", type=str, default="",
                   help="metrics dir (metrics.jsonl + TensorBoard when "
                        "available); default: <ckpt-dir>/logs")
    p.add_argument("--live-metrics", type=float, default=0.0, metavar="SECS",
                   help="append a live registry snapshot (counters, "
                        "gauges, rolling-window quantiles) to "
                        "metrics_live.jsonl in the log dir every SECS "
                        "seconds, so a multi-hour run is scrapeable "
                        "MID-FLIGHT instead of only at exit (0 disables; "
                        "needs --telemetry != off). SIGUSR2 additionally "
                        "captures a bounded on-demand jax.profiler trace "
                        "into the log dir at any time")
    p.add_argument("--profile", type=int, default=0, metavar="N",
                   help="trace N post-compile steps of the first epoch with "
                        "jax.profiler (xprof/perfetto trace in the log dir)")
    p.add_argument("--debug-nans", action="store_true",
                   help="fail fast with a traceback at the first NaN")
    p.add_argument("--check-invariants", action="store_true",
                   help="validate every packed batch's GraphBatch "
                        "invariants (sorted centers, mask/slot consistency, "
                        "dense ownership, transpose completeness) host-side "
                        "before it reaches the step; ~free vs device time, "
                        "on by default in the test suite")
    p.add_argument("--node-cap", type=int, default=0, help="0 = auto")
    p.add_argument("--edge-cap", type=int, default=0, help="0 = auto")
    p.add_argument("--buckets", type=int, default=1,
                   help="size-class buckets for batching (>1 compiles one "
                        "step per bucket; better padding on mixed-size data)")
    p.add_argument("--packing", choices=["snug", "ladder"], default="snug",
                   help="'snug': fill-to-capacity packing with exact "
                        "batch-count-balanced capacities (~0.99 padding "
                        "efficiency); 'ladder': close batches at "
                        "--batch-size graphs with geometric-ladder "
                        "capacities (round-2 behavior)")
    p.add_argument("--pack-once", action="store_true",
                   help="pack training batches once and shuffle batch order "
                        "across epochs (large cached datasets: per-epoch "
                        "host packing would starve the device)")
    p.add_argument("--device-resident", action="store_true",
                   help="stage packed batches into HBM once and reuse the "
                        "device buffers every epoch (implies --pack-once; "
                        "dataset batches must fit in HBM)")
    p.add_argument("--scan-epochs", action="store_true",
                   help="fold each epoch into one lax.scan dispatch per "
                        "bucket shape (implies --device-resident; maximal "
                        "throughput on high-latency links). DEFAULT when "
                        "--device-resident is set: randomized chunk "
                        "scheduling (r3) brought multi-bucket convergence "
                        "within seed noise of the per-step loop "
                        "(scripts/scan_convergence.py)")
    p.add_argument("--no-scan-epochs", action="store_true",
                   help="keep the per-step loop under --device-resident")
    p.add_argument("--chunk-steps", type=int, default=2, metavar="C",
                   help="scan-driver mean chunk granularity (steps folded "
                        "per dispatch; lengths drawn from {C/2, C, 2C}). "
                        "Small on purpose: coarse chunks create long "
                        "same-shape runs that cost multi-bucket val "
                        "accuracy (~35%% MAE at MP-146k with C=8 vs C=2, "
                        "PERF.md 6e); dispatch count itself is ~free")
    # force task (BASELINE config #5)
    p.add_argument("--energy-weight", type=float, default=1.0,
                   help="w_e in L = w_e*MSE(E) + w_f*MSE(F)")
    p.add_argument("--force-weight", type=float, default=10.0,
                   help="w_f in L = w_e*MSE(E) + w_f*MSE(F)")
    p.add_argument("--md-atoms", type=int, default=8,
                   help="atoms per frame for --synthetic MD trajectories")
    p.add_argument("--md-jitter", type=float, default=0.08,
                   help="per-frame Cartesian jitter (Å) for synthetic MD")
    # TPU-native additions
    p.add_argument("--data-parallel", action="store_true",
                   help="shard batches over all visible devices (DP over "
                        "ICI); --batch-size is then per device, and the "
                        "staged form, scan driver, guard and --chunk-steps "
                        "are the one-chip path's")
    p.add_argument("--bf16", action="store_true",
                   help="bfloat16 compute on the MXU (f32 params/stats)")
    p.add_argument("--compact-staging", choices=["auto", "on", "off"],
                   default="auto",
                   help="stage batches in raw form (atom vocabulary index "
                        "+ scalar distance, ~12x fewer bytes) and rebuild "
                        "features inside the jitted scan body "
                        "(data/compact.py). Requires --scan-epochs + dense "
                        "layout and an energy/classification task; on one "
                        "device and under --data-parallel alike (each "
                        "device expands its own rows). auto = on when "
                        "supported")
    p.add_argument("--compile-cache", type=str, default=None,
                   metavar="DIR", help=COMPILE_CACHE_HELP)
    p.add_argument("--layout", choices=["dense", "coo"], default="dense",
                   help="edge batch layout: 'dense' (node-major slots, "
                        "scatter-free aggregation — ~2x faster on TPU) "
                        "or 'coo' (flat edge list)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.chunk_steps < 1:
        print(f"--chunk-steps must be >= 1, got {args.chunk_steps}",
              file=sys.stderr)
        return 2

    def init_distributed():
        # multi-host (ISSUE 10): the CGNN_TPU_COORDINATOR/_NUM_PROCESSES/
        # _PROCESS_ID env triple turns this process into one controller of
        # a jax.distributed run — must init BEFORE anything touches a
        # backend
        from cgnn_tpu.parallel import dist

        dist.initialize_from_env(log_fn=print)

    refusal = start_runtime(args.device, args.compile_cache,
                            before_backend=init_distributed)
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    import jax
    import numpy as np

    from cgnn_tpu.parallel import dist

    from cgnn_tpu.config import DataConfig, ModelConfig, build_model
    from cgnn_tpu.data.dataset import (
        load_cif_directory,
        load_synthetic,
        load_synthetic_oc20,
        load_trajectory,
        train_val_test_split,
    )
    from cgnn_tpu.train import (
        CheckpointManager,
        Normalizer,
        create_train_state,
        make_optimizer,
    )
    from cgnn_tpu.train.loop import capacities_for, evaluate, fit

    if args.debug_nans:
        from cgnn_tpu.observe import enable_debug_nans

        enable_debug_nans()
    if args.check_invariants:
        from cgnn_tpu.data import invariants

        invariants.enable()

    devices = jax.devices()
    print(f"devices: {devices}")

    from cgnn_tpu.observe import Telemetry
    from cgnn_tpu.resilience import PreemptionHandler, faultinject

    log_dir = args.log_dir or os.path.join(args.ckpt_dir, "logs")
    telemetry = Telemetry(args.telemetry, log_dir)

    # the live observability plane (ISSUE 6), training flavor: a
    # periodic metrics_live.jsonl appender over the export registry
    # (scrape a run mid-flight by file), and SIGUSR2 -> one bounded
    # on-demand device-profile capture — both host-side only, so the
    # trajectory stays bit-identical with the plane on or off
    live_writer = None
    if args.live_metrics > 0 and telemetry.enabled:
        from cgnn_tpu.observe import LiveMetricsWriter, MetricsRegistry

        # window matched to the telemetry retention (15 min), NOT the
        # serving 60 s default: training observes epoch_time_s once per
        # epoch, and a 60 s window would report an empty series on
        # nearly every tick of a run with multi-minute epochs
        live_writer = LiveMetricsWriter(
            MetricsRegistry(
                window_s=telemetry.series_window_s
            ).attach_telemetry(telemetry),
            os.path.join(log_dir, "metrics_live.jsonl"),
            interval_s=args.live_metrics,
        ).start()
    profiler = None
    if telemetry.enabled:
        from cgnn_tpu.observe import ProfileCapture, install_sigusr2

        profiler = ProfileCapture(log_dir, spans=telemetry.spans)
        install_sigusr2(profiler, log_fn=print)

    # SIGTERM/SIGINT -> checkpoint at the next epoch/chunk boundary and
    # exit resumable (75); a second signal kills immediately
    preempt = None
    if not args.no_preempt_handler:
        preempt = PreemptionHandler.installed(log_fn=print)
    fault_plan = faultinject.plan()
    if fault_plan is not None:
        print(f"FAULT INJECTION ACTIVE: {fault_plan.describe()}",
              file=sys.stderr)
    if args.task in ("blockdiff", "lm"):
        # no graphs: the task packs token batches itself and hands them to
        # fit(), the scan driver and the state of every task
        from cgnn_tpu.train import blockdiff

        try:
            return blockdiff.run(args, telemetry, preempt=preempt)
        finally:
            if live_writer is not None:
                live_writer.stop()
            telemetry.close()

    if (args.device_resident and not args.no_scan_epochs
            and not args.profile):
        # scan dispatch is the device-resident default since r3 (see
        # --scan-epochs help);
        # --no-scan-epochs restores the per-step loop. Not auto-applied
        # for per-step profiling, which scan cannot provide — that keeps
        # the per-step loop rather than erroring on a flag the user
        # never passed.
        args.scan_epochs = True
    if args.scan_epochs and args.no_scan_epochs:
        print("--scan-epochs and --no-scan-epochs are contradictory",
              file=sys.stderr)
        return 2

    data_cfg = DataConfig(
        radius=args.radius, max_num_nbr=args.max_num_nbr,
        dmin=args.dmin, step=args.step, var=args.gauss_var,
    )
    t0 = time.perf_counter()
    # trajectory grouping for the force task's leak-aware split (frames of
    # one MD trajectory are time-autocorrelated; data/trajectory.py)
    traj_groups = None
    if args.cache and os.path.exists(args.cache):
        from cgnn_tpu.data.cache import load_graph_cache

        with telemetry.span("load_cache", path=args.cache):
            graphs = load_graph_cache(args.cache)
        print(f"loaded {len(graphs)} graphs from {args.cache} "
              f"in {time.perf_counter() - t0:.1f}s")
        if args.task == "force":
            from cgnn_tpu.data.trajectory import regroup_by_trajectory

            if any(g.forces is None or g.positions is None for g in graphs):
                print(f"cache {args.cache} lacks force labels/geometry; "
                      f"refeaturize from the trajectory files",
                      file=sys.stderr)
                return 2
            traj_groups = regroup_by_trajectory(graphs)
    elif args.synthetic_oc20:
        graphs = load_synthetic_oc20(
            args.synthetic_oc20, data_cfg.featurize_config(), seed=args.seed
        )
    elif args.synthetic:
        if args.task == "force":
            graphs = load_trajectory(
                args.synthetic, data_cfg.featurize_config(), seed=args.seed,
                num_atoms=args.md_atoms, jitter=args.md_jitter,
            )
            # one trajectory -> the same contiguous-block split policy as
            # on-disk trajectories (frames are per-frame i.i.d. jitters
            # here, but the split policy should not depend on that detail)
            traj_groups = [graphs]
        else:
            graphs = load_synthetic(args.synthetic, data_cfg.featurize_config(),
                                    seed=args.seed)
    elif args.task == "force":
        from cgnn_tpu.data.trajectory import (
            is_trajectory_path,
            load_trajectory_root,
        )

        if not args.root_dir or not is_trajectory_path(args.root_dir):
            print("--task force needs --synthetic N or an on-disk trajectory "
                  "dataset: a .npz file or a directory of them, one file per "
                  "trajectory (key conventions: cgnn_tpu/data/trajectory.py; "
                  "MD17/sGDML R/z/E/F files load unchanged)",
                  file=sys.stderr)
            return 2
        traj_groups = load_trajectory_root(
            args.root_dir, data_cfg.featurize_config()
        )
        graphs = [g for grp in traj_groups for g in grp]
        print(f"loaded {len(traj_groups)} trajectories "
              f"({len(graphs)} frames) from {args.root_dir}")
    elif args.root_dir:
        if args.workers != 1:
            from cgnn_tpu.data.cache import featurize_directory_parallel

            with telemetry.span("featurize", root=args.root_dir):
                graphs, failures = featurize_directory_parallel(
                    args.root_dir, data_cfg.featurize_config(),
                    workers=args.workers or None,
                )
            for cif_id, err in failures[:10]:
                print(f"skipped {cif_id}: {err}", file=sys.stderr)
        else:
            with telemetry.span("featurize", root=args.root_dir):
                graphs = load_cif_directory(
                    args.root_dir, data_cfg.featurize_config())
    else:
        print("either DATA_DIR or --synthetic N is required", file=sys.stderr)
        return 2
    if not (args.cache and os.path.exists(args.cache)):
        print(f"featurized {len(graphs)} structures "
              f"in {time.perf_counter() - t0:.1f}s")
        if args.cache:
            from cgnn_tpu.data.cache import save_graph_cache

            save_graph_cache(graphs, args.cache)
            print(f"wrote cache {args.cache}")

    if traj_groups is not None:
        from cgnn_tpu.data.trajectory import split_trajectory_groups

        train_g, val_g, test_g = split_trajectory_groups(
            traj_groups, args.train_ratio, args.val_ratio, seed=args.seed
        )
        print(f"trajectory-aware split: {len(train_g)}/{len(val_g)}/"
              f"{len(test_g)} frames over {len(traj_groups)} trajectories")
    else:
        train_g, val_g, test_g = train_val_test_split(
            graphs, args.train_ratio, args.val_ratio, seed=args.seed
        )
    if dist.active():
        # multi-host DP: per-host data slicing (the loader side of
        # ISSUE 10). Every process runs the identical split above
        # (same seed, same data), then takes its disjoint strided
        # shard; the global batch is the union across hosts and the
        # cross-host grad allreduce lives in the shard_map step.
        if not args.data_parallel:
            print("multi-host run (jax.distributed) requires "
                  "--data-parallel: without the global-mesh step there "
                  "is no cross-host gradient reduction and the hosts "
                  "would silently train divergent models",
                  file=sys.stderr)
            return 2
        if args.scan_epochs or args.device_resident or args.pack_once:
            print("multi-host DP runs the per-step loop; drop "
                  "--scan-epochs/--device-resident/--pack-once",
                  file=sys.stderr)
            return 2
        train_g = dist.host_shard(train_g)
        val_g = dist.host_shard(val_g)
        print(f"multi-host: process {dist.process_index()}/"
              f"{dist.process_count()} trains {len(train_g)} / "
              f"validates {len(val_g)} structures (strided host shard); "
              f"test eval runs the full split on every host")
    num_targets = int(train_g[0].target.shape[0])
    classification = args.task == "classification"
    force_task = args.task == "force"

    # dense slot layout: scatter-free aggregation (see data/graph.py), the
    # default for every task incl. force (gather_slot_major is declared
    # linear, so the second-order force differentiation composes; parity is
    # pinned to training-step gradients, tests/test_forces.py). The flat COO
    # layout is the reference the tests hold it to (--layout coo).
    use_dense = args.layout == "dense"
    dense_m = args.max_num_nbr if use_dense else 0

    model_cfg = ModelConfig(
        atom_fea_len=args.atom_fea_len, n_conv=args.n_conv,
        h_fea_len=args.h_fea_len, n_h=args.n_h, num_targets=num_targets,
        classification=classification, num_classes=args.num_classes,
        dropout=args.dropout, dtype="bfloat16" if args.bf16 else "float32",
        multi_task_head=args.multi_task_head, dense_m=dense_m,
        node_norm=args.node_norm, pool_softplus=not args.no_pool_softplus,
    )
    model = build_model(model_cfg, data_cfg, args.task, log_fn=print)

    if classification:
        normalizer = Normalizer.identity(num_targets)
    else:
        normalizer = Normalizer.fit(
            np.stack([g.target for g in train_g]),
            np.stack([
                g.target_mask if g.target_mask is not None
                else np.ones_like(g.target) for g in train_g
            ]),
        )

    layout_m = dense_m or None
    snug = args.packing == "snug"
    # bf16 compute reads edge features (the largest staged tensor) straight
    # from bf16 storage: halves their HBM footprint and per-step bytes
    edge_dtype = jax.numpy.bfloat16 if args.bf16 else np.float32
    node_cap, edge_cap = capacities_for(train_g, args.batch_size,
                                        dense_m=layout_m, snug=snug)
    node_cap = args.node_cap or node_cap
    if layout_m and args.edge_cap:
        print(f"warning: --edge-cap {args.edge_cap} ignored by the dense "
              f"layout (edge capacity is node_cap * max_num_nbr = "
              f"{node_cap * dense_m}); use --layout coo to honor it",
              file=sys.stderr)
    edge_cap = (node_cap * dense_m) if layout_m else (args.edge_cap or edge_cap)
    # real batch count (capacity-filled batches split early, so
    # len//batch_size undercounts and milestones would decay too early)
    from cgnn_tpu.data.graph import batch_iterator, count_batches

    steps_per_epoch = max(1, count_batches(
        train_g, args.batch_size, node_cap, edge_cap, snug=snug
    ))
    tx = make_optimizer(
        optim=args.optim.lower(), lr=args.lr, momentum=args.momentum,
        weight_decay=args.weight_decay,
        lr_milestones=[m * steps_per_epoch for m in args.lr_milestones],
    )

    # the iterator respects capacities (direct pack_graphs of an oversize
    # head batch would die with an opaque broadcast error)
    example = next(batch_iterator(train_g, args.batch_size, node_cap, edge_cap,
                                  dense_m=layout_m, snug=snug,
                                  edge_dtype=edge_dtype))
    with telemetry.span("state_init"):
        state = create_train_state(model, example, tx, normalizer,
                                   rng=jax.random.key(args.seed))

    ckpt = CheckpointManager(args.ckpt_dir, telemetry=telemetry,
                             keep=args.keep_ckpts)
    start_epoch = args.start_epoch
    resume_meta = None
    if args.resume:
        from cgnn_tpu.train.checkpoint import CheckpointRestoreError

        auto = args.resume == "auto"
        resume_dir = args.ckpt_dir if auto else args.resume
        resume_mgr = ckpt if os.path.abspath(resume_dir) == ckpt.directory \
            else CheckpointManager(resume_dir)
        if auto and not resume_mgr.exists():
            print(f"--resume auto: no checkpoint under {resume_dir}; "
                  f"starting fresh")
        else:
            try:
                state, meta = resume_mgr.restore(state)
            except CheckpointRestoreError as e:
                print(f"cannot resume from {resume_dir}: {e}",
                      file=sys.stderr)
                if auto:
                    # checkpoints exist but none restored: refusing to
                    # "start fresh" on top of them — that would retrain
                    # from epoch 0 over (and eventually rotate out) a
                    # run's remains; a human should inspect or remove
                    # the directory
                    print("--resume auto: checkpoint directory is "
                          "non-empty but unrestorable; inspect or remove "
                          f"{resume_dir} to start fresh", file=sys.stderr)
                return 2
            if "epoch" not in meta:
                # refusing to guess: silently computing start_epoch = 0
                # would retrain over (and eventually rotate out) the
                # checkpoint the user asked to resume from
                print(f"checkpoint meta under {resume_dir} lacks 'epoch' "
                      f"({meta!r}) — cannot determine the resume point; "
                      f"aborting instead of restarting at epoch 0",
                      file=sys.stderr)
                return 2
            start_epoch = int(meta["epoch"]) + 1
            resume_meta = meta
            print(f"resumed from {resume_dir} at epoch {start_epoch}")

    meta_base = {"model": model_cfg.to_meta(), "data": data_cfg.to_meta(),
                 "task": args.task}
    sel_key = "force_mae" if force_task else (
        "correct" if classification else "mae")

    guard_enabled = args.guard != "off"
    monitor = None
    if args.guard == "rollback":
        from cgnn_tpu.resilience import DivergenceMonitor

        monitor = DivergenceMonitor(
            ckpt, max_skips=args.guard_max_skips, lr_cut=args.guard_lr_cut,
            max_rollbacks=args.guard_max_rollbacks, log_fn=print,
        )
        if resume_meta is not None:
            # resumed: reapply any persisted LR cut / rollback budget —
            # otherwise every preemption requeue restarts at the
            # full-strength LR that caused the divergence with a fresh
            # retry budget (an unbounded diverge->rollback->preempt loop)
            state = monitor.resume_from_meta(state, resume_meta)
    resilience_kw = {
        "guard": guard_enabled, "monitor": monitor, "preempt": preempt,
    }

    _skip_noted = [False]

    def save_cb(s, e, m, b):
        if not dist.is_coordinator():
            # multi-host: checkpoint commits are PROCESS-0-ONLY — two
            # hosts writing the same versioned-save sequence into one
            # shared directory would race the commit protocol. The
            # state is replicated (post-pmean), so process 0's save IS
            # everyone's save; non-zero hosts pick it up via restore /
            # the coordinated hot-reload path (parallel/dist.py).
            if not _skip_noted[0]:
                _skip_noted[0] = True
                print(f"multi-host: process {dist.process_index()} "
                      f"skips checkpoint commits (process 0 is the "
                      f"single committer)")
            return
        extra = monitor.meta() if monitor is not None else {}
        ckpt.save(
            s, dict(meta_base, epoch=e, best_mae=m.get(sel_key, -1.0),
                    **extra),
            is_best=b,
        )

    # run manifest: config + device/mesh inventory + git SHA, written once
    telemetry.write_manifest(
        vars(args),
        task=args.task,
        mesh_shape={"data": len(devices) if args.data_parallel else 1},
    )
    log_epoch_metrics = telemetry.write_epoch

    step_overrides = {}
    eval_step_fn = None
    if force_task:
        from cgnn_tpu.train.force_step import (
            make_force_eval_step,
            make_force_train_step,
        )

        eval_step_fn = make_force_eval_step(args.energy_weight, args.force_weight)
        step_overrides = {"best_metric": "force_mae"}
    loss_fn = None
    if args.loss != "mse":
        if args.task != "regression":
            print(f"--loss {args.loss} is the regression task's",
                  file=sys.stderr)
            return 2
        from cgnn_tpu.train.step import (
            REGRESSION_LOSSES,
            make_eval_step,
            make_train_step,
        )

        loss_fn = REGRESSION_LOSSES[args.loss]
        eval_step_fn = make_eval_step(loss_fn=loss_fn)

    def choose_compact() -> int:
        """Decide the staged form, for one chip and for a 'data' mesh alike
        (the mesh stages what one chip stages) -> exit code, 0 to go on."""
        compact_ok = (args.scan_epochs and layout_m is not None
                      and not force_task)
        if args.compact_staging == "on" and not compact_ok:
            print("--compact-staging on requires --scan-epochs, the dense "
                  "layout, and a non-force task", file=sys.stderr)
            return 2
        if args.compact_staging != "off" and compact_ok:
            from cgnn_tpu.data.compact import CompactSpec, CompactUnsupported

            try:
                step_overrides["compact"] = CompactSpec.build(
                    train_g + val_g + test_g,
                    data_cfg.featurize_config().gdf(),
                    dense_m=layout_m, edge_dtype=edge_dtype,
                )
                print("compact staging: on (raw atoms+distances staged; "
                      "features rebuilt on device)")
            except CompactUnsupported as e:
                if args.compact_staging == "on":
                    raise
                print(f"compact staging unavailable ({e}); using full "
                      f"staging", file=sys.stderr)
        return 0

    if args.data_parallel and len(devices) > 1:
        rc = choose_compact()
        if rc:
            return rc
        from cgnn_tpu.parallel import fit_data_parallel

        if force_task:
            step_overrides |= {
                "train_step_fn": make_force_train_step(
                    args.energy_weight, args.force_weight, axis_name="data",
                    grad_health=telemetry.step_level,
                ),
                "eval_step_fn": make_force_eval_step(
                    args.energy_weight, args.force_weight, axis_name="data"
                ),
            }
        if loss_fn is not None:
            step_overrides |= {
                "train_step_fn": make_train_step(
                    axis_name="data", loss_fn=loss_fn,
                    grad_health=telemetry.step_level),
                "eval_step_fn": make_eval_step(axis_name="data",
                                               loss_fn=loss_fn),
            }
        state, result = fit_data_parallel(
            state, train_g, val_g, epochs=args.epochs,
            batch_size=args.batch_size,
            node_cap=node_cap, edge_cap=edge_cap, classification=classification,
            seed=args.seed, print_freq=args.print_freq,
            on_epoch_end=save_cb, start_epoch=start_epoch,
            on_epoch_metrics=log_epoch_metrics,
            pack_once=args.pack_once, device_resident=args.device_resident,
            dense_m=layout_m, buckets=args.buckets, snug=snug,
            scan_epochs=args.scan_epochs, profile_steps=args.profile,
            profile_dir=log_dir, edge_dtype=edge_dtype,
            chunk_steps=args.chunk_steps, telemetry=telemetry,
            **resilience_kw, **step_overrides,
        )
        if dist.active():
            # post-fit the state is replicated over the GLOBAL mesh;
            # pull host-local copies so the single-device test eval and
            # any further checkpointing run without the mesh
            state = dist.localize(state)
    else:
        if force_task:
            step_overrides |= {
                "train_step_fn": make_force_train_step(
                    args.energy_weight, args.force_weight,
                    grad_health=telemetry.step_level,
                ),
                "eval_step_fn": eval_step_fn,
            }
        if loss_fn is not None:
            step_overrides |= {
                "train_step_fn": make_train_step(
                    loss_fn=loss_fn, grad_health=telemetry.step_level),
                "eval_step_fn": eval_step_fn,
            }
        rc = choose_compact()
        if rc:
            return rc
        state, result = fit(
            state, train_g, val_g, epochs=args.epochs, batch_size=args.batch_size,
            node_cap=node_cap, edge_cap=edge_cap, classification=classification,
            seed=args.seed, print_freq=args.print_freq,
            on_epoch_end=save_cb, start_epoch=start_epoch,
            buckets=args.buckets, on_epoch_metrics=log_epoch_metrics,
            profile_steps=args.profile, profile_dir=log_dir,
            pack_once=args.pack_once, device_resident=args.device_resident,
            dense_m=layout_m, scan_epochs=args.scan_epochs, snug=snug,
            edge_dtype=edge_dtype, chunk_steps=args.chunk_steps,
            telemetry=telemetry,
            **resilience_kw, **step_overrides,
        )

    if result.get("preempted"):
        # the loop already saved a resumable checkpoint at the boundary;
        # surface any failed save LOUDLY (a silent one would strand the
        # requeue), flush telemetry, and exit with the resumable code
        from cgnn_tpu.resilience.preempt import resumable_exit

        ckpt.close()
        if live_writer is not None:
            live_writer.stop()
        if profiler is not None:
            # exiting mid-capture segfaults in the profiler backend
            profiler.wait_idle()
        telemetry.sample_hbm("preempted")
        telemetry.close()
        return resumable_exit(print)

    with telemetry.span("test_eval"):
        test_m = evaluate(state, test_g, args.batch_size, node_cap, edge_cap,
                          classification, eval_step_fn=eval_step_fn,
                          dense_m=layout_m, snug=snug, edge_dtype=edge_dtype)
    print(f"** test {sel_key}: {test_m.get(sel_key, float('nan')):.4f} "
          f"(best val: {result['best']:.4f})")
    if force_task:
        print(f"** test energy mae: {test_m.get('mae', float('nan')):.4f}")
    for t in range(num_targets):
        if f"mae_task{t}" in test_m:
            print(f"** test mae task {t}: {test_m[f'mae_task{t}']:.4f}")

    if classification:
        # full classification metric set (reference surfaces AUC/F1 too);
        # needs raw per-structure scores, so run a predict pass on the host
        from cgnn_tpu.data.graph import batch_iterator as _biter
        from cgnn_tpu.train.metrics import class_eval
        from cgnn_tpu.train.step import make_predict_step

        pstep = jax.jit(make_predict_step())
        scores, labels = [], []
        idx = 0
        # in_cap=0: forward-only pass needs no transpose slots, and packing
        # them would both cost host time and compile a new In shape
        for b in _biter(test_g, args.batch_size, node_cap, edge_cap,
                        dense_m=layout_m, in_cap=0, snug=snug,
                        edge_dtype=edge_dtype):
            out = np.array(jax.device_get(pstep(state, b)))  # copy: GC-ALIAS
            n_real = int(np.asarray(b.graph_mask).sum())
            scores.append(out[:n_real])
            labels.extend(
                int(test_g[idx + k].target[0]) for k in range(n_real)
            )
            idx += n_real
        cls = class_eval(np.concatenate(scores), np.array(labels))
        test_m = dict(test_m, **cls)
        print("** test " + "  ".join(
            f"{k} {v:.4f}" for k, v in cls.items() if v == v))

    telemetry.write_scalars(args.epochs, test_m, prefix="test")
    telemetry.sample_hbm("end_of_run")
    if live_writer is not None:
        live_writer.stop()
    if profiler is not None:
        # exiting mid-capture segfaults in the profiler backend
        profiler.wait_idle()
    telemetry.close()  # flushes gauges/counters; exports trace.json
    ckpt.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
