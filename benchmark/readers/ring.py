"""Reader ``ring``: what the program's span ring (``observe/spans.py``, under
``obs["program_spans"]``) says beyond one span's duration, which reader
``span`` reads.

`readers/ring.py` | `read(spec, obs)` over the ring's events: how spans of one name follow each other, and what a span's children leave of it | `reader` in the metric file

``what``:
- ``period``: the median, in milliseconds, of start to start of consecutive
  spans named ``span`` (matching ``where``) that share the arg ``group`` and
  follow each other in the arg ``order`` (``scan.chunk`` spans of one
  ``epoch``, by ``chunk``): the host's whole time a chunk, the dispatches
  and the loop around them.
- ``self_pct``: 100 x the self time of the spans named ``span`` (matching
  ``where``) over their duration; self time is a span's duration less that
  of its direct children (same thread, one level deeper, inside its
  interval) named in ``children``.

A ring without such spans, or whose spans lack the args (the parent of the
PR that added them), reports nothing.
"""

from __future__ import annotations

import statistics


def _spans(obs: dict, name: str, where: dict) -> list:
    return [ev for ev in obs["program_spans"]
            if ev.get("name") == name and ev.get("ph") == "X"
            and all(ev.get("args", {}).get(k) == v for k, v in where.items())]


def read(spec: dict, obs: dict):
    spans = _spans(obs, spec["span"], spec.get("where", {}))
    if spec["what"] == "period":
        group, order = spec["group"], spec["order"]
        at: dict = {}  # (group, ordinal) -> start
        for ev in spans:
            args = ev["args"]
            if group in args and order in args:
                at[args[group], args[order]] = ev["ts"]
        periods = [at[g, k + 1] - ts for (g, k), ts in at.items()
                   if (g, k + 1) in at]
        return statistics.median(periods) / 1e3 if periods else None
    if spec["what"] == "self_pct":
        total = sum(ev["dur"] for ev in spans)
        if total <= 0:
            return None
        children = [ev for name in spec["children"]
                    for ev in _spans(obs, name, {})]
        covered = 0.0
        for parent in spans:
            lo, hi = parent["ts"], parent["ts"] + parent["dur"]
            depth = parent["args"]["depth"] + 1
            covered += sum(
                ev["dur"] for ev in children
                if ev["tid"] == parent["tid"] and lo <= ev["ts"]
                and ev["ts"] + ev["dur"] <= hi
                and ev["args"].get("depth") == depth)
        return 100.0 * (total - covered) / total
    raise ValueError(f"ring reader: unknown 'what' {spec['what']!r}")
