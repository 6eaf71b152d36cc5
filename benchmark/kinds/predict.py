"""Kind ``predict``: predict.py's default path on an accelerator, job after job.

Called as ``predict.py``'s ``_run`` calls it: a shape ladder and a raw-wire
spec, structures the raw spec admits through ``run_raw_inference`` (the
in-program neighbour search), the rest through ``run_fast_inference``
(compact staging, pack workers), rows merged back in input order. Two things
differ from one ``predict.py`` process, because a process here runs many jobs:
the ladder and the raw spec are planned ONCE in set-up from the whole pool,
and one jitted predict step is shared by every call (``predict.py`` builds a
new one a call, which would compile in every job).

A job is the traffic file's ``job_size`` structures from in-memory wire
records to fetched predictions in input order. What ``--seed`` changes: the
weights, and the ORDER in which each job sends its structures. Every job sends
the same structures (the pool's first ``job_size``), so every seed does the
same work and no draw changes a compiled shape.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import system
from benchmark.reference import cgcnn_ref as ref
from benchmark.weights import make_weights

# the control (``benchmark/control.py``; see ``kinds/train.py``)
CONTROLS = {"float8": {"control_mm": ref.mm_fp8}}


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.traffic = ctx.traffic

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax

        from cgnn_tpu.data.compact import CompactSpec
        from cgnn_tpu.data.rawbatch import plan_raw_spec, raw_from_graph
        from cgnn_tpu.serve.shapes import plan_shape_set
        from cgnn_tpu.train.step import make_predict_step

        ctx, cfg, tf = self.ctx, self.config, self.traffic
        with ctx.span("data"):
            graphs, info = system.load_pool(cfg)
        print(f"pool: {len(graphs)} structures "
              f"({'built' if info['built'] else 'loaded'} in "
              f"{info['seconds']:.1f} s)")
        self.t_mean, self.t_std = system.target_stats(graphs)
        dense_m = int(cfg["layout"]["dense_m"])
        edge_dtype = system.edge_dtype(cfg)
        fcfg = system.featurize_config(cfg)
        with ctx.span("plan"):
            compact = CompactSpec.build(graphs, fcfg.gdf(), dense_m=dense_m,
                                        edge_dtype=edge_dtype)
            raw_spec = plan_raw_spec(graphs, fcfg.gdf(), fcfg.radius, dense_m)
            self.shape_set = plan_shape_set(
                graphs, int(tf["batch_size"]), rungs=int(tf["rungs"]),
                dense_m=dense_m, edge_dtype=edge_dtype,
                num_targets=int(cfg["model"]["num_targets"]),
                compact=compact, raw=raw_spec)
            # the job's structures, as wire records
            self.job_graphs = graphs[:int(tf["job_size"])]
            self.job_raws = [raw_from_graph(g) for g in self.job_graphs]
            self.rides_raw = np.array([
                r is not None and self.shape_set.admits_raw(r)
                for r in self.job_raws])
        n_raw = int(self.rides_raw.sum())
        ctx.obs["counts"].update(job_structures=len(self.job_graphs),
                                 job_raw=n_raw)
        print(f"job: {len(self.job_graphs)} structures, {n_raw} on the raw "
              f"wire, {len(self.job_graphs) - n_raw} on the featurized wire; "
              f"ladder " + ", ".join(
                  f"({s.graph_cap}g/{s.node_cap}n)" for s in self.shape_set))
        with ctx.span("init"):
            self.model = system.build_model(cfg)
            self.g0 = graphs[0]
            self.state = self._seeded_state(ctx.seed)
            self.predict_step = jax.jit(make_predict_step(
                self.shape_set.expander(), self.shape_set.raw_expander()))
        self.rng = np.random.default_rng(ctx.seed)
        self.pack_s: list = []
        with ctx.span("compile"):
            # two whole jobs in two orders: every (rung, wire form) program
            # and every fetch the window can draw
            for _ in range(2):
                self._job(self.rng.permutation(len(self.job_graphs)))
        self.pack_s.clear()

    def _seeded_state(self, seed: int):
        import jax

        params, stats = make_weights(seed, self.config["model"],
                                     self.g0.atom_fea.shape[1],
                                     self.g0.edge_fea.shape[1])
        self.params0 = jax.tree_util.tree_map(np.array, params)
        self.stats0 = jax.tree_util.tree_map(np.array, stats)
        return system.build_state(self.config, self.model, params, stats,
                                  self.t_mean, self.t_std)

    def reseed(self, seed: int) -> None:
        self.state = self._seeded_state(seed)
        self.rng = np.random.default_rng(seed)

    # ---- one job ------------------------------------------------------

    def _job(self, order: np.ndarray) -> np.ndarray:
        """One job in ``order`` -> predictions [job_size, T] in that order
        (predict.py's merge of its two wires)."""
        from cgnn_tpu.train.infer import run_fast_inference, run_raw_inference

        graphs = [self.job_graphs[int(i)] for i in order]
        raws = [self.job_raws[int(i)] for i in order]
        rides = self.rides_raw[order]
        raw_idx = np.nonzero(rides)[0]
        feat_idx = np.nonzero(~rides)[0]
        preds = np.zeros((len(order), int(
            self.config["model"]["num_targets"])), np.float32)
        if len(raw_idx):
            by_id = {id(raws[i]): graphs[i] for i in raw_idx}
            with self.ctx.annotate("raw_wire"):
                preds[raw_idx], _ = run_raw_inference(
                    self.state, [raws[i] for i in raw_idx], self.shape_set,
                    predict_step=self.predict_step,
                    raw_fallback=lambda rs: by_id[id(rs)])
        if len(feat_idx):
            t0 = time.perf_counter()
            with self.ctx.annotate("featurized_wire"):
                preds[feat_idx], _ = run_fast_inference(
                    self.state, [graphs[i] for i in feat_idx],
                    int(self.traffic["batch_size"]),
                    shape_set=self.shape_set,
                    predict_step=self.predict_step,
                    pack_workers=int(self.traffic["pack_workers"]))
            self.pack_s.append(time.perf_counter() - t0)
        return preds

    # ---- the window ---------------------------------------------------

    def window(self, seconds: float, profiler=None) -> dict:
        jobs = failed = 0
        t0 = time.perf_counter()
        t_done = t0
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            # the traced slice is the window's first job: set-up has run
            # two already, so it is as steady as any
            tracing = profiler is not None and jobs == 0
            if tracing:
                profiler.start()
            order = self.rng.permutation(len(self.job_graphs))
            preds = self._job(order)
            t_done = time.perf_counter()
            if tracing:
                profiler.stop()
                self.ctx.obs["counts"]["traced_jobs"] = 1
            jobs += 1
            failed += int(not np.all(np.isfinite(preds)))
            self.last_order, self.last_preds = order, preds
        elapsed = t_done - t0
        done = (jobs - failed) * len(self.job_graphs)
        self.ctx.obs["counts"]["featurized_wire_s"] = sum(self.pack_s)
        self.ctx.obs["counts"]["window_jobs"] = jobs
        self.ctx.obs["counts"]["window_s"] = elapsed
        print(f"window: {jobs} jobs, {done} structures in {elapsed:.3f} s; "
              f"featurized wire {sum(self.pack_s):.3f} s of it")
        return {"attempted": jobs, "failed": failed,
                "metrics": {"predict_rate": done / elapsed}}

    # ---- the comparison -----------------------------------------------

    def check(self, control_mm=None) -> list:
        """A seeded sample of the last job's answers, the largest structure
        on each wire in it, against the reference's own featurization and
        forward pass from the wire records."""
        import jax.numpy as jnp

        n = int(self.traffic["check_sample"])
        rng = np.random.default_rng(self.ctx.seed + 1)
        pos = set(rng.choice(len(self.last_order), size=n - 2,
                             replace=False).tolist())
        sizes = np.array([self.job_graphs[int(i)].num_nodes
                          for i in self.last_order])
        rides = self.rides_raw[self.last_order]
        for wire in (rides, ~rides):
            if wire.any():
                pos.add(int(np.argmax(np.where(wire, sizes, -1))))
        pos = sorted(pos)
        structures = []
        for p in pos:
            g = self.job_graphs[int(self.last_order[p])]
            r = self.job_raws[int(self.last_order[p])]
            structures.append(ref.from_wire(
                r.lattice, r.frac_coords, g.atom_fea, g.target,
                self.config["featurize"]))
        args = (ref.as_jnp(self.params0), ref.as_jnp(self.stats0),
                ref.coo_batch(structures), jnp.float32(self.t_mean),
                jnp.float32(self.t_std))
        want = ref.predict(*args)[:, 0]
        got = (self.last_preds[pos, 0] if control_mm is None
               else ref.predict(*args, mm=control_mm)[:, 0])
        return compare(got, want, self.config["limits"]["predict"])


def compare(got: np.ndarray, want: np.ndarray, limits: dict) -> list:
    """Errors against the spread of the reference's own answers."""
    spread = max(float(np.std(want)), 1e-30)
    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return [
        {"name": "pred_rms_err_over_spread",
         "value": float(np.sqrt(np.mean(err ** 2))) / spread,
         "limit": limits["pred_rms_err_over_spread"]},
        {"name": "pred_max_err_over_spread",
         "value": float(err.max()) / spread,
         "limit": limits["pred_max_err_over_spread"]},
    ]
