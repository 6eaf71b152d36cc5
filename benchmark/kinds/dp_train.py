"""Kind ``dp_train``: the production epoch driver under a ``'data'`` mesh,
whole epochs back to back.

What ``fit_data_parallel`` assembles for ``train.py --data-parallel
--device-resident --bf16`` (parallel/data_parallel.py): device groups from
``parallel_batches`` with the compact ``pack_fn``, the shard_map-ped train
step with the divergence guard and the expander inside the per-shard body,
``ScanEpochDriver`` over stacks staged by ``shard_scan_stack``, a replicated
committed state, warmed in set-up; then training epochs with no eval and no
checkpoint, each epoch's metric fetch deferred by one epoch.

``run.py`` hands a kind no device list: the mesh is built over
``jax.devices()[:cell.chips]`` and must agree with the configuration's
``parallel.data``. ``train.batch_size`` is the GLOBAL batch; a device packs
``batch_size / parallel.data`` structures a step.

What ``--seed`` changes: the weights and the order in which an epoch visits
the device groups of a bucket shape. What it does not: the pool, its packing
and its grouping into device groups, hence every compiled shape, and the
epoch's chunk lengths and the turn of the shapes (``train.ScheduleRng``).

Counts are summed over the shards. ``train_rate`` counts the real structures
of all shards; the roofline's least time is one chip's share of a step
against one chip's peaks, as ``step_device_ms`` is one chip's busy time.

The readings that ``limits.dp_train`` are set from (PERF.md section 2), in
ONE process on the four-chip host, at the cell's own size:

    python3 -m benchmark.kinds.dp_train --seeds 1,2,3

For each seed: what a sound run reads against the DDP reference, and what
each of ``CONTROLS`` reads in the program's place (``benchmark/control.py``
knows the float8 control alone). A benchmark run never runs this. With
``--replicas 1`` it is a rehearsal on one chip: the same per-chip batch and
resident set under a mesh of one device, held to the same reference.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from benchmark import system
from benchmark.kinds import train
from benchmark.reference import cgcnn_ref as ref
from benchmark.reference import dp_ref
from benchmark.weights import make_weights

# name -> keywords of ``Driver.check``: the reference computed that way stands
# in the program's place, and a sound comparison has to tell it apart
CONTROLS = {
    "float8": {"control_mm": ref.mm_fp8},
    "grad_unaveraged": {"control_variant": "grad_unaveraged"},
    "stats_unaveraged": {"control_variant": "stats_unaveraged"},
    "sync_bn": {"control_variant": "sync_bn"},
}

DP_COUNTERS = ("dp_replicas", "dp_global_batch", "dp_dropped_batches",
               "allreduce_bytes_per_step", "staged_bytes")


class Driver(train.Driver):
    """``kinds/train.py``'s driver under the mesh: its epoch dispatch, deferred
    fetch and reseeding are inherited; set-up, the first steps, the traced
    slice and the comparison are the mesh's own."""

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax
        from jax.sharding import Mesh

        from cgnn_tpu.data.compact import (
            CompactSpec,
            compact_pack_fn,
            make_expander,
        )
        from cgnn_tpu.data.graph import batch_shape_key, capacities_for
        from cgnn_tpu.observe import Telemetry
        from cgnn_tpu.parallel.data_parallel import (
            count_deployment,
            make_parallel_eval_step,
            make_parallel_train_step,
            parallel_batches,
            replicate_state,
            shard_scan_stack,
        )
        from cgnn_tpu.train.loop import ScanEpochDriver

        ctx, cfg, tr = self.ctx, self.config, self.config["train"]
        n_dev = int(cfg["parallel"]["data"])
        devices = jax.devices()[:ctx.cell.chips]
        if len(devices) != n_dev or ctx.cell.chips != n_dev:
            raise SystemExit(
                f"benchmark: {ctx.cell.name} asks for {ctx.cell.chips} "
                f"chip(s), its configuration for parallel.data = {n_dev}, "
                f"and jax offers {len(devices)} device(s)")
        if int(tr["batch_size"]) % n_dev:
            raise SystemExit(f"benchmark: global batch {tr['batch_size']} "
                             f"does not divide over {n_dev} replicas")
        self.n_dev = n_dev
        self.mesh = mesh = Mesh(np.array(devices), ("data",))
        per_dev = int(tr["batch_size"]) // n_dev
        telemetry = ctx.telemetry or Telemetry.disabled()

        with ctx.span("data"):
            graphs, info = system.load_pool(cfg)
        print(f"pool: {len(graphs)} structures "
              f"({'built' if info['built'] else 'loaded'} in "
              f"{info['seconds']:.1f} s)")
        self.graphs = graphs
        self.t_mean, self.t_std = system.target_stats(graphs)
        dense_m = int(cfg["layout"]["dense_m"])
        edge_dtype = system.edge_dtype(cfg)
        fcfg = system.featurize_config(cfg)

        with ctx.span("pack_stage"):
            compact = CompactSpec.build(graphs, fcfg.gdf(), dense_m=dense_m,
                                        edge_dtype=edge_dtype)
            inner = compact_pack_fn(compact)
            packed: list = []  # (structures, node capacity, shape key)

            def pack(batch_graphs, node_cap, *a, **kw):
                batch = inner(batch_graphs, node_cap, *a, **kw)
                packed.append((list(batch_graphs), int(node_cap),
                               batch_shape_key(batch)))
                return batch

            nc, ec = capacities_for(graphs, per_dev, dense_m=dense_m,
                                    snug=True)
            # the packing rng is the configuration's, not the seed's: group
            # lengths key the compiled scan programs
            batches = list(parallel_batches(
                graphs, n_dev, per_dev, nc, ec, shuffle=True,
                rng=np.random.default_rng(int(cfg["data"]["pack_seed"])),
                dense_m=dense_m, buckets=int(tr["buckets"]), snug=True,
                edge_dtype=edge_dtype, pack_fn=pack, telemetry=telemetry))
            members = self._device_groups(packed, batches)
            copies = int(cfg["data"].get("resident_copies", 1))
            staged = copies * sum(
                np.asarray(x).nbytes for b in batches
                for x in jax.tree_util.tree_leaves(b))
            print(f"staging {len(batches)} device groups x {copies} copies "
                  f"over {n_dev} chips: {staged / 1e6:.1f} MB "
                  f"({staged / n_dev / 1e6:.1f} MB a chip)")
            batches = batches * copies
            members = members * copies
        self.members = members  # [step][shard] -> (structures, node cap)
        self.steps_per_epoch = len(batches)
        self.structures_per_epoch = sum(
            len(m) for group in members for m, _ in group)
        ctx.obs["counts"].update(
            real_nodes=sum(g.num_nodes for group in members
                           for m, _ in group for g in m),
            node_slots=sum(cap for group in members for _, cap in group),
            steps_per_epoch=self.steps_per_epoch,
            structures_per_epoch=self.structures_per_epoch,
        )
        self._note_roofline()
        seen: dict = {}
        self.where = []  # (group key, index in its stack) of every step
        for b in batches:
            k = batch_shape_key(b)
            self.where.append((k, seen.get(k, 0)))
            seen[k] = seen.get(k, 0) + 1

        with ctx.span("init"):
            self.model = system.build_model(cfg)
            self._replicate = lambda s: replicate_state(s, mesh)
            state = self._seeded_state(ctx.seed)
            count_deployment(telemetry, state, n_dev, per_dev)
        expand = make_expander(compact)
        with ctx.span("pack_stage"):
            self.driver = ScanEpochDriver(
                make_parallel_train_step(mesh, guard=True, expand=expand),
                make_parallel_eval_step(mesh, expand=expand),
                batches, [], self._schedule_rng(),
                stage=lambda t: shard_scan_stack(t, mesh),
                chunk_steps=int(self.traffic["chunk_steps"]),
                telemetry=ctx.telemetry, preempt=self.clock,
            )
            # the stacks have arrived before anything is compiled or timed
            jax.block_until_ready(self.driver._train_groups)
        del batches
        if ctx.telemetry is not None:
            counters = ctx.telemetry.counters()
            ctx.obs["counts"].update(
                {k: counters[k] for k in DP_COUNTERS if k in counters})
        with ctx.span("compile"):
            state = self.driver.warm(state)
            jax.block_until_ready(state.params)
        self.state = self._first_steps(state)

    def _device_groups(self, packed: list, batches: list) -> list:
        """Which structures went to which shard of which device group:
        ``parallel_batches``' grouping (same-shape batches, in pack order,
        D at a time, an incomplete tail dropped) replayed over what the
        pack function saw, and checked against the groups it yielded."""
        pending: dict = {}
        groups = []
        for structures, cap, key in packed:
            q = pending.setdefault(key, [])
            q.append((structures, cap))
            if len(q) == self.n_dev:
                groups.append(q)
                pending[key] = []
        real = [[len(m) for m, _ in g] for g in groups]
        want = [[int(round(float(row.sum()))) for row in
                 np.asarray(b.graph_mask)] for b in batches]
        if real != want:
            raise RuntimeError("a batch was split while packing, or device "
                               "groups are not formed in pack order: "
                               "membership no longer lines up")
        return groups

    def _seeded_state(self, seed: int):
        import jax

        g0 = self.graphs[0]
        params, stats = make_weights(seed, self.config["model"],
                                     g0.atom_fea.shape[1],
                                     g0.edge_fea.shape[1])
        # host copies for the reference, before the program touches them
        self.params0 = jax.tree_util.tree_map(np.array, params)
        self.stats0 = jax.tree_util.tree_map(np.array, stats)
        # replicated and committed, as fit_data_parallel hands it on: warm()
        # copies its scratch state from it, and an uncommitted one would
        # miss every jit cache entry that warm filled
        return self._replicate(system.build_state(
            self.config, self.model, params, stats, self.t_mean, self.t_std,
            self.steps_per_epoch))

    def _note_roofline(self) -> None:
        """The least time ONE chip's share of an average step could take on
        one chip (each chip runs a D-th of the rows; the parameters' bytes
        are every chip's own)."""
        import jax

        from benchmark import counts

        if jax.devices()[0].platform != "tpu":
            return  # no roofline off the chip
        g0 = self.graphs[0]
        c = self.ctx.obs["counts"]
        real_edges = sum(g.num_edges for group in self.members
                         for m, _ in group for g in m)
        per_epoch = counts.step_counts(
            c["real_nodes"] / self.n_dev, real_edges / self.n_dev,
            self.structures_per_epoch / self.n_dev, self.config["model"],
            g0.edge_fea.shape[1], g0.atom_fea.shape[1], train=True)
        least, bound = counts.least_seconds(
            per_epoch, counts.peaks_for(jax.devices()[0].device_kind))
        c["least_s_per_traced_steps"] = least / self.steps_per_epoch
        print(f"roofline: least {1e3 * least / self.steps_per_epoch:.4f} "
              f"ms a step on each of {self.n_dev} chips, bound by {bound} "
              f"({per_epoch['flops'] / self.steps_per_epoch:.4g} FLOP, "
              f"{per_epoch['bytes'] / self.steps_per_epoch:.4g} B a step "
              f"and chip)")

    def _first_steps(self, state):
        """Drive the warmed driver's own one-step programs through the first
        steps, one device group of each bucket shape in turn, and keep what
        the comparison reads. The state that comes out is the window's."""
        import jax

        d = self.driver
        keys = list(d._train_groups)
        self.check_steps = []  # index into self.members, per step
        got = {"loss": []}
        for s in range(train.N_CHECK_STEPS):
            key = keys[s % len(keys)]
            pos = s // len(keys)
            self.check_steps.append(self.where.index((key, pos)))
            fn = d._scan_fn(d._train_scans, (key, 1), d._train_body, True)
            perm = jax.device_put(np.array([pos], np.int32))
            state, sums = fn(state, d._train_groups[key], perm)
            sums = jax.tree_util.tree_map(float, jax.device_get(sums))
            got["loss"].append(sums["loss_sum"] / max(sums["count"], 1.0))
            if s == 0:
                # SGD with momentum: after one step the trace IS the
                # gradient the optimizer was given (the all-reduced one)
                got["grad"] = jax.tree_util.tree_map(
                    np.array, self._momentum(state))
                got["grad_norm"] = ref.leaf_norms(got["grad"])
        got["params"] = jax.tree_util.tree_map(np.array, state.params)
        got["batch_stats"] = jax.tree_util.tree_map(np.array,
                                                    state.batch_stats)
        got["delta_norm"] = ref.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, got["params"], self.params0))
        got["replica_diff"] = self._replica_diff(state)
        self.got = got
        self.want = None  # other weights: the reference follows anew
        return state

    @staticmethod
    def _momentum(state):
        import jax

        return [t for t in jax.tree_util.tree_leaves(
            state.opt_state, is_leaf=lambda x: hasattr(x, "trace"))
            if hasattr(t, "trace")][0].trace

    def _replica_diff(self, state) -> float:
        """The largest |difference| between any chip's copy of any entry of
        the parameters, the momentum or the running statistics and chip 0's:
        each copy fetched from its own device."""
        import jax

        worst = 0.0
        for leaf in jax.tree_util.tree_leaves(
                (state.params, self._momentum(state), state.batch_stats)):
            copies = [np.asarray(s.data) for s in leaf.addressable_shards]
            if len(copies) != self.n_dev:
                return float("inf")  # a copy is missing: not replicated
            for c in copies[1:]:
                if c.shape != copies[0].shape:
                    return float("inf")
                worst = max(worst, float(np.max(np.abs(
                    c.astype(np.float64) - copies[0]), initial=0.0)))
        return worst

    # ---- the window ---------------------------------------------------

    def _traced_slice(self, profiler) -> None:
        """``profiler.seconds`` of steady epoch, traced: an epoch of four
        chips is ~7,000 steps of ~800 device operations on each of four
        planes, too many events for one trace, so the slice ends the epoch
        at a chunk boundary (the driver's own preemption poll, which
        ``train.ChunkClock`` answers: the slice holds a whole number of
        chunks and the driver reports how many steps ran), drains the
        pipeline, and counts the steps that ran. That epoch is not whole and
        is in no rate; the window's epochs start after it."""
        timer = threading.Timer(profiler.seconds,
                                setattr, (self.clock, "stop", True))
        profiler.start()
        timer.start()
        try:
            pending, _ = self._epoch(None)
            done = self._drain(pending)
        finally:
            timer.cancel()
            profiler.stop()
            self.clock.stop = False
        self.ctx.obs["counts"]["traced_steps"] = int(done["steps"])
        print(f"traced slice: {int(done['steps'])} steps "
              f"({'cut at a chunk boundary' if self.driver.aborted else 'a whole epoch'})")

    def window(self, seconds: float, profiler=None) -> dict:
        """Whole epochs until ``seconds`` have passed; the rate is the work
        of all finished epochs, over all chips, over the time to the last
        one's fetch."""
        losses: list = []
        stamps: list = []

        def note(m):
            if m is not None:
                losses.append(m.get("loss", float("nan")))
                stamps.append(time.perf_counter())

        if profiler is not None:
            self._traced_slice(profiler)
        pending = None
        t0 = self._open_window()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            pending, m = self._epoch(pending)
            note(m)
        note(self._drain(pending))
        self._note_evidence(t0, stamps[-1])
        elapsed = stamps[-1] - t0
        epochs = len(losses)
        failed = sum(1 for x in losses if not math.isfinite(x))
        structures = (epochs - failed) * self.structures_per_epoch
        self.ctx.obs["counts"]["window_steps"] = epochs * self.steps_per_epoch
        print(f"window: {epochs} epochs, {epochs * self.steps_per_epoch} "
              f"steps, {structures} structures over {self.n_dev} chips in "
              f"{elapsed:.3f} s (epochs done at "
              + ", ".join(f"{t - t0:.2f}" for t in stamps)
              + f" s); last loss {losses[-1]:.5f}")
        return {
            "attempted": epochs, "failed": failed,
            "metrics": {"train_rate": structures / elapsed},
        }

    # ---- the comparison -----------------------------------------------

    def check(self, control_mm=None, control_variant=None) -> list:
        """The DDP reference follows the same first steps from the same
        seeded weights on the same device groups' structures. With
        ``control_mm`` (the lower-precision control) or ``control_variant``
        (a broken collective, ``dp_ref.VARIANTS``) the reference computed
        that way stands in the program's place (``CONTROLS``)."""
        import jax.numpy as jnp

        tr = self.config["train"]
        groups = [[[system.graph_as_ref(g) for g in m]
                   for m, _ in self.members[s]] for s in self.check_steps]

        def follow(**kw):
            return dp_ref.ddp_steps(
                ref.as_jnp(self.params0), ref.as_jnp(self.stats0), groups,
                jnp.float32(self.t_mean), jnp.float32(self.t_std),
                lr=float(tr["lr"]), momentum=float(tr["momentum"]), **kw)

        if self.want is None:
            self.want = follow()
        got = self.got
        if control_mm is not None or control_variant is not None:
            kw = {} if control_mm is None else {"mm": control_mm}
            got = self.control = follow(variant=control_variant, **kw)
        limits = self.config["limits"]["dp_train"]
        rows = train.compare(got, self.want, limits)
        # the 'batchnorm' guarantee: per-shard moments, running statistics
        # the mean over the shards. The replica row cannot see it (a psum in
        # the pmean's place, or rank 0's statistics sent to all, leaves the
        # copies identical)
        rows.append({"name": "stats_rel_worst_leaf",
                     "value": max(stats_diffs(got["batch_stats"],
                                              self.want["batch_stats"])
                                  .values()),
                     "limit": limits["stats_rel_worst_leaf"]})
        rows.append({"name": "replica_param_max_abs_diff",
                     # the reference has one copy: a control reads 0 here
                     "value": got.get("replica_diff", 0.0),
                     "limit": limits["replica_param_max_abs_diff"]})
        return rows


def stats_diffs(got, want) -> dict:
    """{"conv_0/bn1/mean": norm of (got - want) over the reference leaf's
    norm} for the running statistics after the last compared step."""
    import jax

    norms = ref.leaf_norms(want)
    diff = ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
        got, want))
    return {k: diff[k] / max(norms[k], 1e-30) for k in norms}


def main(argv=None) -> int:
    import argparse
    import json
    import os

    from benchmark import run
    from cgnn_tpu.runtime import configure_compile_cache

    p = argparse.ArgumentParser()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    p.add_argument("--manifest", default=os.path.join(root, "BENCHMARK.json"))
    p.add_argument("--workload", default="mp.train-dp4")
    p.add_argument("--seeds", required=True)
    p.add_argument("--replicas", type=int, default=None,
                   help="a mesh of this many devices, each with the "
                        "configuration's share of the batch and of the "
                        "resident set (a rehearsal, not the cell)")
    args = p.parse_args(argv)
    configure_compile_cache(None)
    cell = run.Cell(args.manifest, args.workload)
    if args.replicas is not None:
        was = int(cell.config["parallel"]["data"])
        cell.chips = cell.config["parallel"]["data"] = args.replicas
        for block, key in (("train", "batch_size"),
                           ("data", "resident_copies")):
            cell.config[block][key] = max(
                1, int(cell.config[block][key]) * args.replicas // was)
    seeds = [int(x) for x in args.seeds.split(",")]
    driver = Driver(run.Context(cell, seeds[0], False))
    driver.setup()
    limits = {r["name"]: r["limit"] for r in driver.check()}

    def rows(**kw):
        return {r["name"]: float(f"{r['value']:.5g}")
                for r in driver.check(**kw)}

    def leaves(got):
        return {k: float(f"{v:.3g}") for k, v in stats_diffs(
            got["batch_stats"], driver.want["batch_stats"]).items()}

    out = []
    for k, seed in enumerate(seeds):
        if k:
            driver.reseed(seed)
        t0 = time.perf_counter()
        line = {"seed": seed, "program": rows(),
                "program_stats": leaves(driver.got)}
        for name, kw in CONTROLS.items():
            line[name] = rows(**kw)
            line[name + "_stats"] = leaves(driver.control)
        line["seconds"] = round(time.perf_counter() - t0, 1)
        out.append(line)
        print(json.dumps(line), flush=True)
    for n, limit in limits.items():
        print(f"{n}: limit {limit:.6g}; sound runs' largest "
              f"{max(o['program'][n] for o in out):.6g}; smallest of "
              + ", ".join(f"{c} {min(o[c][n] for o in out):.6g}"
                          for c in CONTROLS))
    for c in ("program", *CONTROLS):
        print(f"{c}: rows over their limits, seed by seed: "
              + json.dumps([[n for n in limits if not o[c][n] <= limits[n]]
                            for o in out]))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
