"""Device time by model phase, and how far ahead of the device the host runs.

A second reduction of the traced slice, beside ``reduce/trace.py`` (which
``run.py`` calls and which keeps only an operation's name and shape). It
reads the same ``.xplane.pb`` again for three things that one drops:

- ``XLA Modules``: one event a program launch, named ``jit_<fn>(<hash>)``.
  The scan driver names its programs (``jit_scan_train_n23944_l2``), so a
  launch says which bucket and chunk length it was.
- ``XLA Ops``: each operation is assigned to the launch whose interval holds
  its start, and its instruction (``fusion.652``) is looked up in that
  program's phase table. The device events of this runtime carry the HLO
  text of the instruction and no ``op_name``; the table comes from the
  program, which emits one ``scan.program`` instant a program in warm-up
  (``ScanEpochDriver.warm``; ``cgnn_tpu/observe/phases.py`` is the one
  classifier). Times are self times (a ``while`` does not count its body).
- the host plane's ``cgnn:`` events: the program's own spans on the
  profiler's clock (``observe/spans.py``), of which ``cgnn:scan.chunk`` is
  one dispatch.

A program without such instants (the parent of the PR that added them) gives
no table: the phase sums are then not reported, and nothing raises.
"""

from __future__ import annotations

import glob
import os
import statistics
import time

from benchmark.reduce.trace import (
    DEVICE_PREFIX,
    HOST_PLANE,
    MODULES_LINE,
    OPS_LINE,
    _self_times,
)

HOST_PREFIX = "cgnn:"
NO_TABLE = "no_table"
UNNAMED = ("other", NO_TABLE)  # time these hold is in no phase of the model
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache", "trace")


def newest_xplane(root: str = TRACE_ROOT):
    """The trace ``run.Profiler`` wrote last (it clears its directory before
    each slice, so the newest file is this run's), or None."""
    paths = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_scan_train_n8_l2(16473556867225079226)`` -> the name."""
    return event_name.partition("(")[0]


def from_xplane(path: str) -> dict:
    """The first device's launches and operations and the host's ``cgnn:``
    events as plain data: ``{"modules", "ops", "host"}``, each a list of
    ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    out: dict = {"modules": [], "ops": [], "host": []}
    seen_device = False
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX) and not seen_device:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["ops"] = [[instruction(ev.name), int(ev.start_ns),
                                   int(ev.duration_ns)]
                                  for ev in line.events]
                elif line.name == MODULES_LINE:
                    out["modules"] = [[module_name(ev.name),
                                       int(ev.start_ns),
                                       int(ev.duration_ns)]
                                      for ev in line.events]
            seen_device = bool(out["ops"])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [[ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)]
                                for ev in line.events
                                if ev.name.startswith(HOST_PREFIX)]
    return out


def tables_from_spans(program_spans: list) -> dict:
    """{module name: {instruction: [phase, direction]}} from the program's
    ``scan.program`` instants; a later instant of one name wins."""
    return {ev["args"]["module"]: ev["args"]["table"]
            for ev in program_spans
            if ev.get("name") == "scan.program" and "table" in ev.get(
                "args", {})}


def phase_times(data: dict, tables: dict) -> dict:
    """-> {"by_phase": {(phase, direction): ns}, "busy_ns", "n_ops",
    "unnamed": {(module, instruction): ns}}.

    An operation inside no launch, in a launch of a program without a table,
    or whose instruction the table lacks, counts under ``(NO_TABLE, "")``.
    """
    modules = sorted(data["modules"], key=lambda ev: ev[1])
    tagged = []
    k = 0
    for instr, s, d in sorted(data["ops"], key=lambda ev: ev[1]):
        while k + 1 < len(modules) and modules[k + 1][1] <= s:
            k += 1
        inside = modules and modules[k][1] <= s < modules[k][1] + max(
            modules[k][2], 1)
        tagged.append([(modules[k][0] if inside else "", instr), s, d])
    by_phase: dict = {}
    unnamed: dict = {}  # what has no phase, by launch's program and name
    busy = 0
    for (module, instr), ns in _self_times(tagged):
        row = tables.get(module, {}).get(instr)
        key = tuple(row) if row else (NO_TABLE, "")
        by_phase[key] = by_phase.get(key, 0) + ns
        if key[0] in UNNAMED:
            unnamed[module, instr] = unnamed.get((module, instr), 0) + ns
        busy += ns
    return {"by_phase": by_phase, "busy_ns": busy, "n_ops": len(tagged),
            "unnamed": unnamed}


def dispatch_lead(data: dict, host_name: str, module_prefix: str):
    """How far ahead of the device the host runs, chunk by chunk.

    The k-th host span named ``host_name`` is the dispatch of the k-th
    launch of a program whose name starts with ``module_prefix``. Its lead is
    the launch's device start less the span's end: large while the device
    works through a queue the host has filled, near zero when the device
    waits for each dispatch. -> {"lead_ms": [...], "skew_ms": float} or None
    when the two counts differ (the slice cut a dispatch from its launch).

    ``skew_ms`` is the least of (device start - span start). A launch cannot
    start before its own dispatch began, so a negative value is how far, at
    least, the two planes' clocks disagree; a lead is known no better.
    """
    host = sorted((ev for ev in data["host"] if ev[0] == host_name),
                  key=lambda ev: ev[1])
    launches = sorted((ev for ev in data["modules"]
                       if ev[0].startswith(module_prefix)),
                      key=lambda ev: ev[1])
    if not host or len(host) != len(launches):
        return None
    lead = [(m[1] - (h[1] + h[2])) / 1e6 for h, m in zip(host, launches)]
    skew = min((m[1] - h[1]) / 1e6 for h, m in zip(host, launches))
    return {"lead_ms": lead, "skew_ms": skew}


def observed(obs: dict):
    """The reduction of this run's trace, made once and kept in ``obs``
    (every reader is handed the same ``obs``). None when the run was not
    traced or left no trace file."""
    if "phase_trace" not in obs:
        obs["phase_trace"] = _observe(obs)
    return obs["phase_trace"]


def _observe(obs: dict):
    if not obs.get("trace"):
        return None
    path = newest_xplane()
    if path is None:
        return None
    t0 = time.perf_counter()
    data = from_xplane(path)
    tables = tables_from_spans(obs.get("program_spans", []))
    out = phase_times(data, tables)
    out["has_tables"] = bool(tables)
    out["lead"] = dispatch_lead(data, HOST_PREFIX + "scan.chunk",
                                "jit_scan_")
    out["host_spans"] = len(data["host"])
    report(out, obs["counts"].get("traced_steps", 0),
           time.perf_counter() - t0, sorted({m[0] for m in data["modules"]}))
    report_set_up(obs.get("program_spans", []))
    return out


def report_set_up(program_spans: list) -> None:
    """The program's set-up spans, summed by name (``warm.phase_map`` is
    in no metric: it is what a traced run's set-up grows by)."""
    sums: dict = {}
    for ev in program_spans:
        if ev.get("ph") == "X" and ev["name"].startswith(("warm.",
                                                          "scan.stage")):
            n, s = sums.get(ev["name"], (0, 0.0))
            sums[ev["name"]] = (n + 1, s + ev["dur"] / 1e6)
    if sums:
        print("set-up spans: " + ", ".join(
            f"{name} {s:.3f} s ({n})" for name, (n, s) in sorted(
                sums.items())))


def report(out: dict, steps: int, seconds: float, modules: list) -> None:
    """The whole phase x direction table, on lines of its own."""
    print(f"phases: {out['n_ops']} device operations in "
          f"{len(modules)} programs ({', '.join(modules)}); "
          f"{out['host_spans']} cgnn: host spans; second parse "
          f"{seconds:.1f} s")
    busy = max(out["busy_ns"], 1)
    per = 1e6 * max(steps, 1)
    for (phase, direction), ns in sorted(out["by_phase"].items(),
                                         key=lambda kv: -kv[1]):
        print(f"phase {phase:<15} {direction:<3} {ns / per:9.4f} ms/step "
              f"{100.0 * ns / busy:6.2f} %")
    for (module, instr), ns in sorted(out["unnamed"].items(),
                                      key=lambda kv: -kv[1])[:8]:
        print(f"unnamed {module}/{instr}: {ns / per:.4f} ms/step")
    lead = out["lead"]
    if lead:
        ms = lead["lead_ms"]
        print(f"dispatch lead: median {statistics.median(ms):.3f} ms, min "
              f"{min(ms):.3f}, max {max(ms):.3f} over {len(ms)} chunks; "
              f"least (device start - host span start) "
              f"{lead['skew_ms']:.3f} ms (negative: the planes' clocks "
              f"disagree by at least that)")
