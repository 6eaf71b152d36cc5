"""From a profiler trace to device busy/idle time, the top device operations
and the longest idle gaps named by what the host was doing.

``from_xplane`` reads the ``.xplane.pb`` the JAX profiler writes
(``jax.profiler.ProfileData``) into plain data: a list of planes
``{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}``.
``summarize`` works on that plain data alone, so it is tested on a small
recorded trace kept as JSON (tests/benchmark/fixtures).

On this runtime (jax 0.9.0, libtpu 0.0.34, TPU v5 lite) a device is a plane
named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event for every
operation that ran on the chip; ``XLA Modules`` holds one for every program
launched, and ``Steps`` groups them. Host threads are lines of the plane
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land there under their
own names.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# a launch that holds this share of its program's median operation count, or
# less, lost events (``events_lost``)
LOST_SHARE = 0.9


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_SHAPE = re.compile(r"^\(?([a-z]+[0-9]*\[[0-9,]*\])")


def short_name(name: str) -> str:
    """A device event is named by its whole HLO text
    (``%fusion.12 = bf16[14648,12,128]{...} fusion(...), kind=kLoop``): keep
    the name and the first result's shape, which says what it works on."""
    head, _, rest = name.partition(" = ")
    shape = _SHAPE.match(rest)
    head = head.lstrip("%")[:100]
    return f"{head}:{shape.group(1)}" if shape else head


def from_xplane(path: str, host_prefix: str = "bench:") -> list:
    """Planes as plain data. Device planes keep every event of every line;
    the host plane keeps only events named ``host_prefix...`` (the
    benchmark's own annotations) — the rest is thousands of runtime frames."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        if not is_dev and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [
                [short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if is_dev or ev.name.startswith(host_prefix)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events: list):
    """(name, self ns) of every event: its duration less that of the events
    nested inside it. A ``while`` loop is one event that spans the
    operations of its body, which are events of the same line."""
    out = []
    stack: list = []  # [end, index into out]
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d
        out.append([name, d])
        stack.append([s + d, len(out) - 1])
    return out


def _covering(spans: list, s: int, e: int) -> str:
    """The host annotation that covers most of [s, e]."""
    best, best_cov = "unannotated", 0
    for name, hs, hd in spans:
        cov = min(e, hs + hd) - max(s, hs)
        if cov > best_cov:
            best, best_cov = name, cov
    return best


def summarize(planes: list, top: int = 10) -> dict:
    """-> {"busy_s", "window_s", "n_devices", "device_ops": [[name, s]...],
    "idle_gaps": [[host span name, s]...]}.

    Busy is the union of the intervals in which an operation ran on a device,
    averaged over the devices that ran anything; the window runs from the
    first device operation's start to the last one's end, over all devices.
    An operation's time is its self time (a loop does not count its body).
    """
    host = [ev for p in planes if p["name"] == HOST_PLANE
            for ln in p["lines"] for ev in ln["events"]]
    per_dev = []
    ops_ns: dict = {}
    for p in planes:
        if not p["name"].startswith(DEVICE_PREFIX):
            continue
        evs = [ev for ln in p["lines"] if ln["name"] == OPS_LINE
               for ev in ln["events"]]
        if not evs:
            continue
        for name, d in _self_times(evs):
            ops_ns[name] = ops_ns.get(name, 0) + d
        per_dev.append(_union([[s, s + d] for _n, s, d in evs]))
    if not per_dev:
        return {"busy_s": 0.0, "window_s": 0.0, "n_devices": 0,
                "device_ops": [], "idle_gaps": []}
    t_lo = min(u[0][0] for u in per_dev)
    t_hi = max(u[-1][1] for u in per_dev)
    busy_ns = sum(sum(e - s for s, e in u) for u in per_dev) / len(per_dev)
    gaps: dict = {}
    first = per_dev[0]
    for (_s0, e0), (s1, _e1) in zip(first, first[1:]):
        name = _covering(host, e0, s1)
        gaps[name] = gaps.get(name, 0) + (s1 - e0)
    n = len(per_dev)

    def rank(ns: dict, share: int) -> list:
        return sorted(([k, v / share / 1e9] for k, v in ns.items()),
                      key=lambda kv: -kv[1])[:top]

    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (t_hi - t_lo) / 1e9,
        "n_devices": n,
        "device_ops": rank(ops_ns, n),
        "idle_gaps": rank(gaps, 1),
    }


def events_lost(planes: list) -> dict:
    """Whether the trace lost device events, read off the trace alone.

    One program runs the same operations at every launch, so on each device
    the launches of one program (``XLA Modules`` events of one name, the
    hash left off) hold equally many ``XLA Ops`` events. A launch that holds
    ``LOST_SHARE`` of its program's median count or less lost some: the
    profiler dropped them, the gaps they leave read as idle time, and the
    run's per-layer numbers are not to be compared (two such runs stand in
    the ledger: 726 operations a step against 842, and 46% idle in a cell
    that reads 0.2%). It flags; nothing is retried, dropped or re-weighed.

    -> {"lost": bool, "launches", "short_launches", "least_share",
    "ops_outside_launches"}; ``lost`` is None where no launch was traced.
    """
    launches = short = outside = 0
    least = 1.0
    for p in planes:
        if not p["name"].startswith(DEVICE_PREFIX):
            continue
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda ev: ev[1])
        starts = sorted(ev[1] for ev in lines.get(OPS_LINE, []))
        if not mods:
            continue
        by_program: dict = {}
        inside = 0
        for name, s, d in mods:
            n = (bisect.bisect_left(starts, s + max(d, 1))
                 - bisect.bisect_left(starts, s))
            inside += n
            by_program.setdefault(name.partition("(")[0], []).append(n)
        outside += len(starts) - inside
        for counts in by_program.values():
            median = statistics.median(counts)
            launches += len(counts)
            if median <= 0:
                continue
            short += sum(1 for n in counts if n <= LOST_SHARE * median)
            least = min(least, min(counts) / median)
    return {"lost": short > 0 if launches else None, "launches": launches,
            "short_launches": short, "least_share": least,
            "ops_outside_launches": outside}
