"""Kind ``train``: the production epoch driver, whole epochs back to back.

``ScanEpochDriver`` with the train body, the divergence guard, compact staging
and ``chunk_steps`` as ``fit`` builds it for ``train.py --device-resident
--bf16`` (train/loop.py), warmed in set-up, then training epochs with no eval
and no checkpoint, each epoch's metric fetch deferred by one epoch as ``fit``
defers it when no checkpoint is due.

What ``--seed`` changes: the weights and the order in which an epoch visits
the batches of a bucket shape. What it does not: the pool and its packing,
hence every compiled shape, and (since PR 34, ``ScheduleRng``) the epoch's
chunk lengths and the turn of the bucket shapes, hence how many launches an
epoch makes, of which programs, in which turn.
"""

from __future__ import annotations

import math
import statistics
import time

import numpy as np

from benchmark import system
from benchmark.reference import cgcnn_ref as ref
from benchmark.weights import make_weights

N_CHECK_STEPS = 3
# name -> keywords of ``Driver.check``: the reference computed that way stands
# in the program's place, and has to come out as not correct
# (``benchmark/control.py`` reads every kind's). float8 e4m3 is the precision
# below the bfloat16 these configurations state.
CONTROLS = {"float8": {"control_mm": ref.mm_fp8}}


class ScheduleRng:
    """The rng the epoch driver draws an epoch's schedule from, in two
    streams. ``permutation`` (the order in which the batches of one bucket
    shape are visited) follows ``--seed``. ``choice`` (each chunk's length,
    one of c/2, c and 2c where there are several buckets, and which bucket's
    chunk goes next) follows the configuration's ``pack_seed``.

    With one stream from the seed, as it was, a seed drew its own number of
    chunks: 5,877 to 6,045 in a window of ``mp.train-dp4``, where the host's
    dispatch is the limit, and the rate followed it (308.2k to 311.6k
    structures/s over six seeds, a spread of 0.6-0.8%, where two runs of one
    seed differ by 0.1%; my chip runs, PR 34). The seed was changing the
    work. Now every seed makes the same launches, as many, of the same
    programs, in the same turn, over its own order of the batches."""

    def __init__(self, seed: int, schedule_seed: int):
        self._order = np.random.default_rng(seed)
        self._schedule = np.random.default_rng(schedule_seed)

    def permutation(self, n):
        return self._order.permutation(n)

    def choice(self, *args, **kwargs):
        return self._schedule.choice(*args, **kwargs)


class ChunkClock:
    """What the driver polls before every chunk's dispatch (its ``preempt``,
    which ``fit`` hands it in production too): each poll leaves the host's
    time, so that the window's longest chunk is known in a run without the
    program's spans, and ``stop`` ends an epoch at a chunk boundary (the
    four-chip cell's traced slice)."""

    def __init__(self):
        self.stamps: list = []
        self.stop = False

    @property
    def requested(self) -> bool:
        self.stamps.append(time.perf_counter())
        return self.stop


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.clock = ChunkClock()
        # when each epoch's dispatch began and the host's seconds inside it
        self.dispatch_at: list = []
        self.dispatch_s: list = []

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax

        from cgnn_tpu.data.compact import (
            CompactSpec,
            compact_pack_fn,
            make_expander,
        )
        from cgnn_tpu.data.graph import (
            batch_iterator,
            batch_shape_key,
            bucketed_batch_iterator,
            capacities_for,
        )
        from cgnn_tpu.resilience.guard import guard_step
        from cgnn_tpu.train.loop import ScanEpochDriver
        from cgnn_tpu.train.step import make_eval_step, make_train_step

        ctx, cfg, tr = self.ctx, self.config, self.config["train"]
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
            members: list = []  # one entry a packed batch, in pack order

            def pack(batch_graphs, node_cap, *a, **kw):
                members.append((list(batch_graphs), int(node_cap)))
                return inner(batch_graphs, node_cap, *a, **kw)

            # the packing rng is the configuration's, not the seed's: group
            # lengths key the compiled scan programs
            rng = np.random.default_rng(int(cfg["data"]["pack_seed"]))
            bsz, buckets = int(tr["batch_size"]), int(tr["buckets"])
            if buckets > 1:
                it = bucketed_batch_iterator(
                    graphs, bsz, buckets, shuffle=True, rng=rng,
                    dense_m=dense_m, snug=True, edge_dtype=edge_dtype,
                    pack_fn=pack)
            else:
                nc, ec = capacities_for(graphs, bsz, dense_m=dense_m,
                                        snug=True)
                it = batch_iterator(graphs, bsz, nc, nc * dense_m,
                                    shuffle=True, rng=rng, dense_m=dense_m,
                                    snug=True, edge_dtype=edge_dtype,
                                    pack_fn=pack)
            batches = list(it)
            # the data set a deployment keeps on the chip is far larger than
            # the pool that set-up can featurize: every packed batch is
            # staged ``resident_copies`` times (distinct buffers, the same
            # structures), and an epoch visits every copy
            copies = int(cfg["data"].get("resident_copies", 1))
            staged = copies * sum(
                np.asarray(x).nbytes for b in batches
                for x in jax.tree_util.tree_leaves(b))
            print(f"staging {len(batches)} batches x {copies} copies: "
                  f"{staged / 1e6:.1f} MB on the device")
            batches = batches * copies
            members = members * copies
        if len(members) != len(batches):
            raise RuntimeError("a batch was split while packing: membership "
                               "no longer lines up with the packed batches")
        self.members = members
        self.steps_per_epoch = len(batches)
        self.structures_per_epoch = sum(len(m) for m, _ in members)
        ctx.obs["counts"].update(
            real_nodes=sum(g.num_nodes for m, _ in members for g in m),
            node_slots=sum(cap for _, cap in members),
            steps_per_epoch=self.steps_per_epoch,
            structures_per_epoch=self.structures_per_epoch,
        )
        self._note_roofline()
        # (group key, index in its stack) of every batch, as the driver
        # stacks them: same-shape batches in pack order
        seen: dict = {}
        self.where = []
        for b in batches:
            k = batch_shape_key(b)
            self.where.append((k, seen.get(k, 0)))
            seen[k] = seen.get(k, 0) + 1

        with ctx.span("init"):
            self.model = system.build_model(cfg)
            state = self._seeded_state(ctx.seed)
        with ctx.span("pack_stage"):
            self.driver = ScanEpochDriver(
                guard_step(make_train_step(False)), make_eval_step(False),
                batches, [], self._schedule_rng(),
                expand=make_expander(compact),
                chunk_steps=int(self.traffic["chunk_steps"]),
                telemetry=ctx.telemetry, preempt=self.clock,
            )
        del batches
        with ctx.span("compile"):
            state = self.driver.warm(state)
            jax.block_until_ready(state.params)
        self.state = self._first_steps(state)

    def _schedule_rng(self) -> ScheduleRng:
        return ScheduleRng(self.ctx.seed, int(self.config["data"]["pack_seed"]))

    def _seeded_state(self, seed: int):
        import jax

        g0 = self.graphs[0]
        params, stats = make_weights(seed, self.config["model"],
                                     g0.atom_fea.shape[1],
                                     g0.edge_fea.shape[1])
        # host copies for the reference, before the program touches them
        self.params0 = jax.tree_util.tree_map(np.array, params)
        self.stats0 = jax.tree_util.tree_map(np.array, stats)
        # committed to its device, as warm()'s scratch copy is: an
        # uncommitted state would miss every jit cache entry that warm
        # filled and compile them all again inside the window
        return jax.device_put(
            system.build_state(self.config, self.model, params, stats,
                               self.t_mean, self.t_std,
                               self.steps_per_epoch),
            jax.devices()[0])

    def reseed(self, seed: int) -> None:
        """Other weights through the same compiled programs (the limits'
        readings take a dozen seeds in one process)."""
        self.state = self._first_steps(self._seeded_state(seed))

    def _note_roofline(self) -> None:
        """The least time an average step could take on this chip."""
        import jax

        from benchmark import counts

        g0 = self.graphs[0]
        real_edges = sum(g.num_edges for m, _ in self.members for g in m)
        per_epoch = counts.step_counts(
            self.ctx.obs["counts"]["real_nodes"], real_edges,
            self.structures_per_epoch,
            self.config["model"], g0.edge_fea.shape[1],
            g0.atom_fea.shape[1], train=True)
        kind = jax.devices()[0].device_kind
        if jax.devices()[0].platform != "tpu":
            return  # no roofline off the chip
        least, bound = counts.least_seconds(per_epoch,
                                            counts.peaks_for(kind))
        self.ctx.obs["counts"]["least_s_per_traced_steps"] = (
            least / self.steps_per_epoch)
        print(f"roofline: least {1e3 * least / self.steps_per_epoch:.4f} "
              f"ms a step, bound by {bound} "
              f"({per_epoch['flops'] / self.steps_per_epoch:.4g} FLOP, "
              f"{per_epoch['bytes'] / self.steps_per_epoch:.4g} B a step)")

    def _first_steps(self, state):
        """Drive the warmed driver's own one-step programs through the first
        steps, one batch of each bucket shape in turn, and keep what the
        comparison reads. The state that comes out is the window's."""
        import jax

        d = self.driver
        keys = list(d._train_groups)
        self.check_batches = []  # index into self.members, per step
        got = {"loss": []}
        for s in range(N_CHECK_STEPS):
            key = keys[s % len(keys)]
            pos = s // len(keys)
            self.check_batches.append(self.where.index((key, pos)))
            fn = d._scan_fn(d._train_scans, (key, 1), d._train_body, True)
            perm = jax.device_put(np.array([pos], np.int32))
            state, sums = fn(state, d._train_groups[key], perm)
            sums = jax.tree_util.tree_map(float, jax.device_get(sums))
            got["loss"].append(sums["loss_sum"] / max(sums["count"], 1.0))
            if s == 0:
                # SGD with momentum: after one step the trace IS the
                # gradient the optimizer was given
                trace = [t for t in jax.tree_util.tree_leaves(
                    state.opt_state, is_leaf=lambda x: hasattr(x, "trace"))
                    if hasattr(t, "trace")][0].trace
                got["grad"] = jax.tree_util.tree_map(np.array, trace)
                got["grad_norm"] = ref.leaf_norms(got["grad"])
        after = jax.tree_util.tree_map(np.array, state.params)
        got["delta_norm"] = ref.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, after, self.params0))
        self.got = got
        return state

    # ---- the window ---------------------------------------------------

    def _epoch(self, pending_prev):
        """Dispatch one epoch; resolve the one before it (fit's deferred
        fetch). -> (pending, finished epoch's metrics or None)."""
        t0 = time.perf_counter()
        with self.ctx.annotate("epoch_dispatch"):
            self.state, pending = self.driver.run_epoch_pair(
                self.state, first=False, async_fetch=True)
        self.dispatch_at.append(t0)
        self.dispatch_s.append(time.perf_counter() - t0)
        done = None
        if pending_prev is not None:
            with self.ctx.annotate("epoch_fetch"):
                done = pending_prev.result()[0]
        return pending, done

    def _drain(self, pending):
        if pending is None:
            return None
        with self.ctx.annotate("epoch_fetch"):
            return pending.result()[0]

    def _open_window(self) -> float:
        """-> the window's start, with the evidence's clocks at nought."""
        self.clock.stamps.clear()
        self.dispatch_at.clear()
        self.dispatch_s.clear()
        self._cpu0 = time.process_time()
        return time.perf_counter()

    def _note_evidence(self, t0: float, t_end: float) -> None:
        """Where a far-off run lost its time, for whoever reads its last
        line (``run.py`` prints ``obs["evidence"]`` there; no metric reads
        it). An epoch's metrics are fetched one epoch late, so the host sees
        no epoch end; it does see each epoch's dispatch begin, and never
        runs more than a few chunks ahead of the device:

        - ``epoch_s``: from an epoch's first dispatch (the window's start, for
          the first) to the next epoch's (to the last fetch, for the last):
          they add up to the rate's own time;
        - ``epoch_dispatch_s``: the host's seconds inside that dispatch
          (blocked on a full queue where the device is the limit);
        - ``chunks``, ``chunk_ms_median``, ``chunk_ms_longest``,
          ``chunk_longest_at_s``: the driver polls its ``preempt`` before
          every chunk; poll to poll is one ``scan.chunk`` dispatch and the
          loop around it, here within an epoch;
        - ``epoch_turn_ms``: the poll-to-poll time across each epoch's end
          (the next schedule's build, the fetch thread's start);
        - ``host_cpu_s``: this process's CPU seconds over the window, all
          threads: a run that waited reads what the others read, a run in
          which the host worked more reads more.
        """
        polls = [t for t in self.clock.stamps if t >= t0]
        starts = self.dispatch_at[1:]
        # (seconds, from when, whether an epoch's dispatch begins inside)
        gaps = [(b - a, a, any(a < at <= b for at in starts))
                for a, b in zip(polls, polls[1:])]
        inside = [g for g in gaps if not g[2]]
        ev = {
            "epoch_s": [b - a for a, b in zip(
                [t0] + starts, starts + [t_end])],
            "epoch_dispatch_s": list(self.dispatch_s),
            "epoch_turn_ms": [1e3 * g[0] for g in gaps if g[2]],
            "host_cpu_s": time.process_time() - self._cpu0,
            "chunks": len(polls),
        }
        if inside:
            longest = max(inside)
            ev.update(
                chunk_ms_median=1e3 * statistics.median(g[0] for g in inside),
                chunk_ms_longest=1e3 * longest[0],
                chunk_longest_at_s=longest[1] - t0)
        self.ctx.obs["evidence"] = ev

    def window(self, seconds: float, profiler=None) -> dict:
        """Whole epochs until ``seconds`` have passed; the rate is the work
        of all finished epochs over the time to the last one's fetch."""
        losses: list = []
        t_done = [0.0]
        stamps: list = []

        def note(m):
            if m is not None:
                losses.append(m.get("loss", float("nan")))
                t_done[0] = time.perf_counter()
                stamps.append(t_done[0])

        pending = None
        t0 = self._open_window()
        deadline = t0 + seconds
        if profiler is not None:
            # the traced slice: the window's first epoch, whole, with the
            # pipeline drained at its end so that its steps are counted
            # exactly (an epoch over the resident set is ~17 s of device
            # time; nothing shorter is a whole unit of this driver)
            profiler.start()
            pending, m = self._epoch(None)
            note(self._drain(pending))
            pending = None
            profiler.stop()
            self.ctx.obs["counts"]["traced_steps"] = self.steps_per_epoch
        while time.perf_counter() < deadline:
            pending, m = self._epoch(pending)
            note(m)
        note(self._drain(pending))
        elapsed = t_done[0] - t0
        epochs = len(losses)
        failed = sum(1 for x in losses if not math.isfinite(x))
        structures = (epochs - failed) * self.structures_per_epoch
        self.ctx.obs["counts"]["window_steps"] = epochs * self.steps_per_epoch
        self._note_evidence(t0, t_done[0])
        print(f"window: {epochs} epochs, {epochs * self.steps_per_epoch} "
              f"steps, {structures} structures in {elapsed:.3f} s (epochs "
              f"done at " + ", ".join(f"{t - t0:.2f}" for t in stamps)
              + f" s); last loss {losses[-1] if losses else float('nan'):.5f}")
        return {
            "attempted": epochs, "failed": failed,
            "metrics": {"train_rate": structures / elapsed},
        }

    # ---- the comparison -----------------------------------------------

    def check(self, control_mm=None) -> list:
        """The reference follows the same first steps from the same seeded
        weights on the same batches' structures. With ``control_mm`` the
        reference computed with that matmul (the lower-precision control)
        stands in the program's place."""
        import jax.numpy as jnp

        tr = self.config["train"]
        batches = [
            ref.coo_batch([system.graph_as_ref(g)
                           for g in self.members[b][0]])
            for b in self.check_batches
        ]

        def follow(**kw):
            return ref.sgd_steps(
                ref.as_jnp(self.params0), ref.as_jnp(self.stats0), batches,
                jnp.float32(self.t_mean), jnp.float32(self.t_std),
                lr=float(tr["lr"]), momentum=float(tr["momentum"]), **kw)

        self.want = follow()
        got = self.got if control_mm is None else follow(mm=control_mm)
        if control_mm is not None:
            self.control = got
        return compare(got, self.want, self.config["limits"]["train"])

    def raw_readings(self) -> dict:
        """Per-leaf norms behind the comparison (control.py prints them when
        a statistic has to be chosen)."""
        def diffs(got):
            import jax

            return ref.leaf_norms(jax.tree_util.tree_map(
                lambda a, b: np.asarray(a) - np.asarray(b), got["grad"],
                self.want["grad"]))

        return {"ref_norm": self.want["grad_norm"],
                "program_diff": diffs(self.got),
                "control_diff": diffs(self.control),
                "program_norm": self.got["grad_norm"],
                "control_norm": self.control["grad_norm"]}


def compare(got: dict, want: dict, limits: dict) -> list:
    rows = [
        {"name": f"loss_step{s + 1}_rel",
         "value": abs(g - w) / max(abs(w), 1e-30),
         "limit": limits["loss_rel"]}
        for s, (g, w) in enumerate(zip(got["loss"], want["loss"]))
    ]
    rows.append({"name": "grad_diff_median_leaf",
                 "value": ref.median_leaf_diff(got["grad"], want["grad"]),
                 "limit": limits["grad_diff_median_leaf"]})
    rows.append({"name": "grad_norm_worst_leaf",
                 "value": max(ref.leaf_gaps(got["grad_norm"],
                                            want["grad_norm"])),
                 "limit": limits["grad_norm_worst_leaf"]})
    # the middle leaf, not the worst: where a leaf's three gradients nearly
    # cancel (momentum overshooting the output bias), its change is a small
    # difference of large numbers and one seed in sixteen read 0.38 there
    # against 0.003-0.03 on the others; a step that returns its state
    # unchanged reads 1 on every leaf either way
    rows.append({"name": "delta_norm_median_leaf",
                 "value": float(np.median(ref.leaf_gaps(
                     got["delta_norm"], want["delta_norm"]))),
                 "limit": limits["delta_norm_median_leaf"]})
    return rows
