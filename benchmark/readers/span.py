"""Reader ``span``: host-clock spans, the benchmark's own
(``source: "benchmark"``, ``(name, start_s, end_s)``) or the program's
(``source: "program"``, Chrome-trace events of ``observe/spans.py``).

``reduce``: ``median`` | ``mean`` | ``sum``; ``unit``: ``ms`` | ``s``.
"""

from __future__ import annotations

import statistics


def read(spec: dict, obs: dict):
    if spec.get("source", "benchmark") == "program":
        secs = [ev["dur"] / 1e6 for ev in obs["program_spans"]
                if ev.get("name") == spec["what"] and ev.get("ph") == "X"
                and all(ev.get("args", {}).get(k) == v
                        for k, v in spec.get("where", {}).items())]
    else:
        secs = [e - s for n, s, e in obs["spans"] if n == spec["what"]]
    if not secs:
        return None
    value = {"median": statistics.median, "mean": statistics.fmean,
             "sum": sum}[spec["reduce"]](secs)
    return value * (1e3 if spec["unit"] == "ms" else 1.0)
