"""Kind ``serve``: an InferenceServer behind its HTTP front, closed-loop load.

The server is built in this process, as ``serve.py``'s ``load_server`` builds
it, around the benchmark's seeded weights (there is no checkpoint): shape
ladder, compact and raw-wire specs planned from the pool, every (rung, form)
program warmed, the HTTP front of ``serve/http.py`` on loopback. The load
comes from ``benchmark/loadgen.py``, a child process that never imports JAX:
``clients`` closed-loop callers, one raw-wire structure a request.

Keys never repeat within a run (a repeat would measure the result cache):
structures are drawn without replacement, and when the pool runs out they are
re-sent with every fractional coordinate shifted by a rigid translation that
is unique to the pass (the same answer, different bytes). What ``--seed``
changes: the weights, the order of the structures and the translations.
"""

from __future__ import annotations

import json
import os
import socket
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import system
from benchmark.reference import cgcnn_ref as ref
from benchmark.weights import make_weights

# the control (``benchmark/control.py``; see ``kinds/train.py``)
CONTROLS = {"float8": {"control_mm": ref.mm_fp8}}

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.httpd = self.server = None

    def setup(self) -> None:
        import jax

        from cgnn_tpu.config import DataConfig
        from cgnn_tpu.data.compact import CompactSpec
        from cgnn_tpu.data.rawbatch import plan_raw_spec, raw_from_graph
        from cgnn_tpu.serve.devices import resolve_devices
        from cgnn_tpu.serve.http import make_http_server
        from cgnn_tpu.serve.server import (
            InferenceServer,
            structure_featurizer,
        )
        from cgnn_tpu.serve.shapes import plan_shape_set

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
            shape_set = plan_shape_set(
                graphs, int(tf["batch_size"]), rungs=int(tf["rungs"]),
                dense_m=dense_m, edge_dtype=edge_dtype,
                num_targets=int(cfg["model"]["num_targets"]),
                compact=compact, raw=raw_spec)
            raws = [raw_from_graph(g) for g in graphs]
            self.sendable = [i for i, r in enumerate(raws)
                             if r is not None and shape_set.admits_raw(r)]
            self.raws, self.graphs = raws, graphs
        with ctx.span("init"):
            model = system.build_model(cfg)
            params, stats = make_weights(
                ctx.seed, cfg["model"], graphs[0].atom_fea.shape[1],
                graphs[0].edge_fea.shape[1])
            self.params0 = jax.tree_util.tree_map(np.array, params)
            self.stats0 = jax.tree_util.tree_map(np.array, stats)
            state = system.build_state(cfg, model, params, stats,
                                       self.t_mean, self.t_std)
            self.server = InferenceServer(
                state, shape_set, version="seed", max_queue=256,
                max_wait_ms=float(tf["max_wait_ms"]),
                default_timeout_ms=1000.0,
                cache_size=int(tf["cache_size"]), pack_workers=1,
                devices=resolve_devices("auto")[:ctx.cell.chips],
                precisions=("f32",), model=model,
                featurizer=structure_featurizer(
                    DataConfig(**cfg["featurize"])),
                log_fn=print)
        with ctx.span("traffic"):
            self._write_bodies()
        with ctx.span("compile"):
            self.server.warm(graphs[0])
            self.server.start()
        self.port = free_port()
        self.httpd = make_http_server(self.server, port=self.port)
        threading.Thread(target=self.httpd.serve_forever, daemon=True,
                         name="bench-http").start()
        self.compiles_before = self.server.stats().get(
            "recompiles_after_warm", 0)

    def _write_bodies(self) -> None:
        """Enough distinct request bodies for the window, from the seed."""
        tf = self.traffic
        rng = np.random.default_rng(self.ctx.seed)
        need = int(tf["max_requests"])
        self.sent_idx, self.shifts = [], []
        lines = []
        while len(lines) < need:
            shift = rng.uniform(0.0, 1.0, 3) if lines else np.zeros(3)
            for i in rng.permutation(self.sendable):
                r = self.raws[int(i)]
                lines.append(json.dumps({"structure": {
                    "id": f"{r.cif_id}.{len(lines)}",
                    "lattice": r.lattice.tolist(),
                    "frac_coords": (r.frac_coords + shift).tolist(),
                    "numbers": r.numbers.tolist()}}))
                self.sent_idx.append(int(i))
                self.shifts.append(shift)
                if len(lines) == need:
                    break
        work = os.path.join(system.POOL_DIR, "serve")
        os.makedirs(work, exist_ok=True)
        self.bodies_path = os.path.join(work, "bodies.jsonl")
        self.out_path = os.path.join(work, "loadgen_out.json")
        with open(self.bodies_path, "w") as f:
            f.write("\n".join(lines))

    def window(self, seconds: float, profiler=None) -> dict:
        tf = self.traffic
        cmd = [sys.executable, os.path.join(BENCH, "loadgen.py"),
               "--port", str(self.port), "--bodies", self.bodies_path,
               "--out", self.out_path, "--clients", str(tf["clients"]),
               "--warm-seconds", str(tf["warm_seconds"]),
               "--seconds", str(seconds)]
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        proc = subprocess.Popen(cmd, cwd=BENCH, env=env)
        try:
            if profiler is not None:
                time.sleep(float(tf["warm_seconds"]) + 1.0)
                profiler.start()
                time.sleep(profiler.seconds)
                profiler.stop()
            rc = proc.wait(timeout=seconds + 120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if rc != 0:
            raise RuntimeError(f"load generator exited {rc}")
        with open(self.out_path) as f:
            out = json.load(f)
        if out["exhausted"]:
            raise RuntimeError("the load generator ran out of distinct "
                               "request bodies: raise max_requests")
        reqs = out["requests"]
        ok = [r for r in reqs if r[1] == 200]
        elapsed = out["t_last"] - out["t_start"]
        lat = sorted(r[2] for r in ok)
        self.answers = {r[0]: r[3] for r in ok}
        stats = self.server.stats()
        after = stats.get("recompiles_after_warm", 0)
        if after != self.compiles_before:
            raise RuntimeError(f"{after - self.compiles_before} compile(s) "
                               f"after warm-up, inside the window")
        hists = self.ctx.obs["hists"]
        for name, h in self.server.hists.items():
            if h.count:
                hists[name] = {"p50": h.quantile(0.5),
                               "mean": h.sum / h.count}
        print(f"window: {len(reqs)} requests, {len(ok)} answered 200 in "
              f"{elapsed:.3f} s; latency p50 "
              f"{statistics.median(lat) if lat else float('nan'):.2f} ms; "
              f"server counts {stats.get('counts')}")
        return {
            "attempted": len(reqs), "failed": len(reqs) - len(ok),
            "metrics": {
                "serve_rate": len(ok) / elapsed,
                "serve_p95_ms": lat[int(0.95 * (len(lat) - 1))] if lat
                else float("nan"),
            },
        }

    def check(self, control_mm=None) -> list:
        """A seeded sample of the answers that came back over HTTP, the
        largest structure answered in it, against the reference's own
        featurization and forward pass of the bytes that were sent."""
        import jax.numpy as jnp

        from benchmark.kinds.predict import compare

        n = int(self.traffic["check_sample"])
        lines = sorted(self.answers)
        rng = np.random.default_rng(self.ctx.seed + 1)
        pick = set(rng.choice(len(lines), size=min(n, len(lines)) - 1,
                              replace=False).tolist())
        sizes = [self.graphs[self.sent_idx[ln]].num_nodes for ln in lines]
        pick.add(int(np.argmax(sizes)))
        pick = [lines[k] for k in sorted(pick)]
        structures = []
        for ln in pick:
            g = self.graphs[self.sent_idx[ln]]
            r = self.raws[self.sent_idx[ln]]
            structures.append(ref.from_wire(
                r.lattice, r.frac_coords + self.shifts[ln], g.atom_fea,
                g.target, self.config["featurize"]))
        args = (ref.as_jnp(self.params0), ref.as_jnp(self.stats0),
                ref.coo_batch(structures), jnp.float32(self.t_mean),
                jnp.float32(self.t_std))
        want = ref.predict(*args)[:, 0]
        if control_mm is None:
            got = np.array([self.answers[ln][0] for ln in pick], np.float64)
        else:
            got = ref.predict(*args, mm=control_mm)[:, 0]
        return compare(got, want, self.config["limits"]["serve"])

    def close(self) -> None:
        if self.httpd is not None:
            self.httpd.shutdown()
            self.httpd.server_close()
        if self.server is not None:
            self.server.drain(timeout_s=10.0)

