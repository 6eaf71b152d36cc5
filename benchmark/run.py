#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip: loads, warms, measures, compares, prints,
exits. It fails (non-zero, no result line) when JAX finds no TPU or fewer
chips than the cell asks for, and when anything compiled inside the window.
The last line of standard output is the result object; everything else
(atoms, padding, counts) is printed on earlier lines. Every number compared
stands beside its limit three times: on a ``compare`` line of standard
output, among the last lines of standard error, and under ``compared``, the
result object's last key. ``evidence``, the key before it, is what a kind
noted of where the window's time went (and, in a traced run, whether the
trace lost events): no metric reads it; it is there so that a far-off run
says where.

Everything that belongs to one cell is data: BENCHMARK.json names the cell's
configuration and traffic, ``traffic/<traffic>.json`` names the kind,
``kinds/<kind>.py`` drives it, ``layer_metrics/<name>.json`` names a reader
in ``readers/``. Adding a cell, a configuration or a metric adds files and
entries and edits none.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import math
import os
import sys
import time

T_PROCESS = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SLICE_S = 2.5


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and
    metrics, resolved from a manifest (BENCHMARK.json, or a test's)."""

    def __init__(self, manifest_path: str, name: str):
        self.root = os.path.dirname(os.path.abspath(manifest_path))
        m = self.manifest = load_json(manifest_path)
        cells = {w["name"]: w for w in m["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in {manifest_path} "
                             f"(known: {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in m["configs"]}
        self.config = load_json(os.path.join(
            self.root, configs[self.entry["config"]]["file"]))
        base = m["paths"][0]
        self.traffic = load_json(os.path.join(
            self.root, base, "traffic", self.entry["traffic"] + ".json"))
        self.layer_dir = os.path.join(HERE, "layer_metrics")

    def _mine(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"] if self._mine(m)]

    def per_layer(self) -> list:
        return [m for m in self.manifest["per_layer"] if self._mine(m)]


class Context:
    """What a kind's driver is handed: the cell's data, the seed, and where
    to leave observations for the per-layer readers."""

    def __init__(self, cell: Cell, seed: int, trace: bool):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.spans: list = []  # (name, start_s, end_s), the benchmark's own
        self.obs: dict = {"counts": {}, "spans": self.spans, "trace": None,
                          "program_spans": [], "hists": {}, "evidence": {}}
        self.telemetry = None  # the program's span tracer, traced runs only
        if trace:
            from cgnn_tpu.observe import Telemetry

            self.telemetry = Telemetry(
                level="epoch", use_clu=False,
                log_dir=os.path.join(HERE, ".cache", "telemetry"))

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            with self.annotate(name):
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))

    def annotate(self, name: str):
        """A span on the profiler's clock (names the idle gaps)."""
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)


class Profiler:
    """A short traced slice inside the window."""

    def __init__(self, out_dir: str, seconds: float):
        self.out_dir, self.seconds = out_dir, seconds

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        # no Python tracer: it records every frame of every host thread,
        # which slows the host it is measuring; TraceAnnotation spans and
        # the device lines are all the reduction reads
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=options)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


class CompileCounter:
    """Counts backend compiles and compile-cache reads while armed."""

    def __init__(self):
        import jax.monitoring as mon

        self.armed = False
        self.events: list = []
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if self.armed and ("backend_compile" in name
                           or "cache_retrieval" in name):
            self.events.append(name)


def device_report(devices) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def read_layer_metrics(cell: Cell, obs: dict) -> dict:
    out = {}
    for m in cell.per_layer():
        spec = load_json(os.path.join(cell.layer_dir, m["name"] + ".json"))
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(spec, obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(manifest_path: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True) -> tuple[dict, int]:
    """-> (result object, exit code). ``require_tpu=False`` is the tests'
    way past the look for a chip; nothing else differs."""
    cell = Cell(manifest_path, workload)
    import jax

    from cgnn_tpu.runtime import configure_compile_cache

    cache_dir = configure_compile_cache(None)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        print(f"benchmark: {workload} needs {cell.chips} TPU chip(s); jax "
              f"found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return {}, 1
    devices = devices[:cell.chips]
    print(f"cell {workload}: config {cell.entry['config']}, traffic "
          f"{cell.entry['traffic']}, seed {seed}, {seconds} s, trace "
          f"{int(trace)}; compile cache {cache_dir}; "
          f"{len(devices)} x {devices[0].device_kind}")
    compiles = CompileCounter()
    ctx = Context(cell, seed, trace)
    kind = importlib.import_module("benchmark.kinds." + cell.traffic["kind"])
    driver = kind.Driver(ctx)
    try:
        driver.setup()
        setup_s = time.perf_counter() - T_PROCESS
        profiler = None
        trace_dir = os.path.join(HERE, ".cache", "trace", workload)
        if trace:
            profiler = Profiler(trace_dir, min(TRACE_SLICE_S, seconds / 2))
        compiles.armed = True
        window = driver.window(float(seconds), profiler)
        compiles.armed = False
        device = device_report(devices)
        # the reference runs now, after the window and after the peak was
        # read: its time is in no metric and its memory in no reading
        t_ref = time.perf_counter()
        compared = driver.check()
        ref_s = time.perf_counter() - t_ref
    finally:
        close = getattr(driver, "close", None)
        if close is not None:
            close()
    ok = True
    for row in compared:
        row_ok = bool(row["value"] <= row["limit"])  # NaN fails
        ok &= row_ok
        print(f"compare {row['name']}: {row['value']:.6g} "
              f"(limit {row['limit']:.6g}) {'ok' if row_ok else 'FAIL'}")
    print(f"reference: {ref_s:.1f} s; set-up {setup_s:.1f} s: " + ", ".join(
        f"{n} {e - s:.1f}" for n, s, e in ctx.spans))
    if compiles.events:
        print(f"benchmark: {len(compiles.events)} compile(s) inside the "
              f"timed window ({sorted(set(compiles.events))}): no "
              f"steady-state number", file=sys.stderr)
        return {}, 4
    window["metrics"]["setup_s"] = setup_s
    result = {"correct": ok, "attempted": window["attempted"],
              "failed": window["failed"], "device": device}
    if not trace:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        result["metrics"] = {
            k: {"value": float(v), "unit": units[k]}
            for k, v in window["metrics"].items() if k in units}
    else:
        from benchmark.reduce import trace as reduce_trace

        planes = reduce_trace.from_xplane(reduce_trace.find_xplane(trace_dir))
        summary = reduce_trace.summarize(planes)
        lost = reduce_trace.events_lost(planes)
        del planes
        print(f"trace: {lost['launches']} launches, {lost['short_launches']} "
              f"at or under {reduce_trace.LOST_SHARE} of their program's median "
              f"operation count (least {lost['least_share']:.3f}), "
              f"{lost['ops_outside_launches']} operations outside every "
              f"launch" + ("; THE TRACE LOST EVENTS: this run's per-layer "
                           "numbers are not to be compared"
                           if lost["lost"] else ""))
        ctx.obs["evidence"].update(
            trace_events_lost=lost["lost"], trace_launches=lost["launches"],
            trace_short_launches=lost["short_launches"],
            trace_least_launch_share=lost["least_share"])
        ctx.obs["trace"] = summary
        if ctx.telemetry is not None and ctx.telemetry.spans is not None:
            ctx.obs["program_spans"] = list(ctx.telemetry.spans.events)
        result["metrics"] = read_layer_metrics(cell, ctx.obs)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if ctx.obs["evidence"]:
        result["evidence"] = ctx.obs["evidence"]
    # a NaN is no JSON number: it reads null here, and has failed above
    result["compared"] = {
        row["name"]: {"value": (float(row["value"])
                                if math.isfinite(row["value"]) else None),
                      "limit": float(row["limit"])}
        for row in compared}
    sys.stdout.flush()
    for row in compared:
        print(f"compared {row['name']} {row['value']:.6g} limit "
              f"{row['limit']:.6g}", file=sys.stderr)
    return result, 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    result, code = run_cell(os.path.join(ROOT, "BENCHMARK.json"),
                            args.workload, args.seed, args.seconds,
                            bool(args.trace))
    if code == 0:
        sys.stdout.flush()
        print(json.dumps(result, allow_nan=False))
    return code


if __name__ == "__main__":
    sys.exit(main())
