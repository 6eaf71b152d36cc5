"""The device's idle gaps by the program span the host was in, on one clock.

`reduce/gaps.py` | the same trace -> each idle gap of the first device put
down to the innermost `cgnn:` span of the dispatch thread that covers most of
it, after the two planes' clocks are brought together | `readers/gaps.py`

A third reduction of the traced slice, beside ``reduce/trace.py`` (whose
``idle_gaps`` names a gap by the benchmark's ``bench:`` spans: every gap of
every training cell falls under ``bench:epoch_dispatch``) and
``reduce/phases.py`` (device time by model phase). It reads the same
``.xplane.pb`` again for what those drop: the threads of the host plane and
the nesting of the program's own spans on them (``observe/spans.py`` opens a
``cgnn:<name>`` annotation for every span; the epoch driver's are
``scan.epoch`` > ``scan.chunk``, ``scan.accumulate``, ``epoch.sched`` >
``epoch.sched.put``, and ``epoch.fetch_start``; ``train/loop.py``).

Plain data in, plain data out (tested on JSON, tests/benchmark):

    {"busy": [[start_ns, end_ns], ...],      merged, the first device's XLA Ops
     "launches": [[module, start_ns, dur_ns], ...],     its XLA Modules
     "threads": [[[name, start_ns, dur_ns], ...], ...]}  cgnn: events a host line

- **One thread.** Only the spans of the host thread that carries
  ``cgnn:scan.chunk`` name a gap. ``cgnn:epoch.fetch`` on the fetch thread
  lasts an epoch and would cover every gap of it.
- **The clock first.** The host plane's clock and the device plane's
  disagree (a recorded slice reads -0.44 ms, the size of a whole lead in the
  cell the host limits). ``skew_ms`` is ``phases.dispatch_lead``'s: the least
  of (launch start - start of the ``scan.chunk`` span that dispatched it),
  launches and spans paired by place. A launch cannot start before its
  dispatch began, so where the skew is negative every host event is shifted
  by it before anything is covered. Where the counts of spans and launches
  differ nothing is paired, and nothing is reported: no guess.
- **Innermost, by time.** Every instant of a gap belongs to the innermost
  span open on the thread at that instant; the gap goes, whole, to the span
  that holds most of its instants so. Instants under ``scan.epoch`` alone
  (the loop's own lines, between a chunk's two dispatches) or under no span
  count as ``unspanned``.
- **Closure.** Every gap between the device's merged busy intervals goes to
  exactly one name, so the shares add up to that device's idle share of its
  traced window (first operation's start to last one's end): on one chip,
  ``device_idle_pct.train``. Gaps on the chips after the first are not read.

A program that opens no ``cgnn:scan.epoch`` (the parent of the PR that added
it) gives no table: the four idle metrics are then not reported, and nothing
raises.
"""

from __future__ import annotations

import time

from benchmark.reduce import phases
from benchmark.reduce.trace import (
    DEVICE_PREFIX,
    HOST_PLANE,
    MODULES_LINE,
    OPS_LINE,
    _union,
)

CHUNK = phases.HOST_PREFIX + "scan.chunk"
# the span whose own time (no child open) is the loop's: not a name of a gap
PARENT = phases.HOST_PREFIX + "scan.epoch"
UNSPANNED = "unspanned"
LAUNCH_PREFIX = "jit_scan_"


def from_xplane(path: str) -> dict:
    """The first device's merged busy intervals and launches, and the
    ``cgnn:`` events of each host thread, as plain data."""
    from jax.profiler import ProfileData

    out: dict = {"busy": [], "launches": [], "threads": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX) and not out["busy"]:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["busy"] = _union(
                        [[int(ev.start_ns),
                          int(ev.start_ns) + int(ev.duration_ns)]
                         for ev in line.events])
                elif line.name == MODULES_LINE:
                    out["launches"] = [
                        [phases.module_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)] for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                          for ev in line.events
                          if ev.name.startswith(phases.HOST_PREFIX)]
                if events:
                    out["threads"].append(events)
    return out


def dispatch_thread(threads: list):
    """The events of the thread that dispatches the chunks, or None."""
    best = max(threads, default=None,
               key=lambda evs: sum(1 for ev in evs if ev[0] == CHUNK))
    if best is None or not any(ev[0] == CHUNK for ev in best):
        return None
    return best


def segments(events: list) -> list:
    """[[start, end, name], ...] in time order, without overlap: at every
    instant the innermost of the thread's spans open then. Spans of one
    thread nest (a context manager closes before its parent does)."""
    out: list = []
    stack: list = []  # [end, name] of the open spans, outermost first
    t = None  # up to where the segments are written

    def emit(upto):
        nonlocal t
        if t is not None and upto <= t:
            return  # a child that outlasts its parent by a rounding
        if stack:
            out.append([t, upto, stack[-1][1]])
        t = upto

    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append([s + d, name])
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def attribute(data: dict):
    """-> {"window_ns", "idle_ns", "gaps", "chunks", "skew_ms",
    "shift_ms", "least_lead_ms", "by_span": {name: {"ns", "gaps",
    "longest_ns"}}}, names without the ``cgnn:`` prefix and ``unspanned``
    among them; or {"refused": why} where nothing can be said."""
    thread = dispatch_thread(data["threads"])
    if thread is None or not any(ev[0] == PARENT for ev in thread):
        return {"refused": "the program opens no cgnn:scan.epoch around "
                           "its cgnn:scan.chunk spans"}
    if not data["busy"]:
        return {"refused": "no device operation in the trace"}
    lead = phases.dispatch_lead(
        {"host": thread, "modules": data["launches"]}, CHUNK, LAUNCH_PREFIX)
    if lead is None:
        n_host = sum(1 for ev in thread if ev[0] == CHUNK)
        n_dev = sum(1 for ev in data["launches"]
                    if ev[0].startswith(LAUNCH_PREFIX))
        return {"refused": f"{n_host} cgnn:scan.chunk spans against {n_dev} "
                           f"launches of {LAUNCH_PREFIX}*: not paired, so "
                           f"the clocks' skew is not known"}
    shift_ns = min(0, round(lead["skew_ms"] * 1e6))
    segs = segments([[n, s + shift_ns, d] for n, s, d in thread])
    busy = data["busy"]
    by_span: dict = {}
    k = 0
    for (_s0, a), (b, _e1) in zip(busy, busy[1:]):
        while k < len(segs) and segs[k][1] <= a:
            k += 1
        held = {UNSPANNED: 0}
        covered = 0
        j = k
        while j < len(segs) and segs[j][0] < b:
            s, e, name = segs[j]
            ns = min(b, e) - max(a, s)
            covered += ns
            name = UNSPANNED if name == PARENT else name
            held[name] = held.get(name, 0) + ns
            j += 1
        held[UNSPANNED] += (b - a) - covered
        # the most instants; a tie goes to a span before ``unspanned``
        name = max(held, key=lambda n: (held[n], n != UNSPANNED))
        row = by_span.setdefault(
            name.removeprefix(phases.HOST_PREFIX),
            {"ns": 0, "gaps": 0, "longest_ns": 0})
        row["ns"] += b - a
        row["gaps"] += 1
        row["longest_ns"] = max(row["longest_ns"], b - a)
    window = busy[-1][1] - busy[0][0]
    return {
        "window_ns": window,
        "idle_ns": window - sum(e - s for s, e in busy),
        "gaps": len(busy) - 1,
        "chunks": len(lead["lead_ms"]),
        "skew_ms": lead["skew_ms"],
        "shift_ms": shift_ns / 1e6,
        "least_lead_ms": min(lead["lead_ms"]) - shift_ns / 1e6,
        "by_span": by_span,
    }


def share_pct(table: dict, names) -> float:
    """100 x the idle time under ``names`` (a list of span names, or
    ``"unspanned"``) over the traced window."""
    if isinstance(names, str):
        names = [names]
    ns = sum(table["by_span"].get(n, {"ns": 0})["ns"] for n in names)
    return 100.0 * ns / table["window_ns"]


def observed(obs: dict):
    """The table of this run's trace, made once and kept in ``obs`` under
    ``gap_trace`` (every reader is handed the same ``obs``). None when the
    run was not traced, left no trace file, or ``attribute`` refused."""
    if "gap_trace" not in obs:
        obs["gap_trace"] = _observe(obs)
    return obs["gap_trace"]


def _observe(obs: dict):
    if not obs.get("trace"):
        return None
    path = phases.newest_xplane()
    if path is None:
        return None
    t0 = time.perf_counter()
    table = attribute(from_xplane(path))
    if "refused" in table:
        print(f"idle gaps by program span: nothing, {table['refused']}")
        return None
    report(table, time.perf_counter() - t0)
    return table


def report(table: dict, seconds: float) -> None:
    """The whole table, on lines of its own."""
    window = max(table["window_ns"], 1)
    print(f"idle gaps by program span: {table['gaps']} gaps of the first "
          f"device, {table['idle_ns'] / 1e9:.6f} s idle of "
          f"{window / 1e9:.6f} s ({100.0 * table['idle_ns'] / window:.4f} "
          f"%), {table['chunks']} chunks paired; third parse "
          f"{seconds:.1f} s")
    print(f"idle clock: least (device start - host span start) "
          f"{table['skew_ms']:.3f} ms, host events shifted by "
          f"{table['shift_ms']:.3f} ms, least lead after it "
          f"{table['least_lead_ms']:.3f} ms")
    for name, row in sorted(table["by_span"].items(),
                            key=lambda kv: -kv[1]["ns"]):
        print(f"idle {name:<18} {row['ns'] / 1e9:10.6f} s "
              f"{100.0 * row['ns'] / window:8.4f} % {row['gaps']:8d} gaps, "
              f"longest {row['longest_ns'] / 1e6:.3f} ms")
