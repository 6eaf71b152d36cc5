"""Reader ``trace``: numbers from the reduced profiler trace
(``reduce/trace.py``'s summary, under ``obs["trace"]``).

``what``:
- ``idle_pct``: 100 * (1 - device busy time / traced window).
- ``busy_ms_per``: device busy milliseconds per unit of work, the unit count
  being ``obs["counts"][spec["per"]]`` (steps or batches inside the slice).
"""

from __future__ import annotations


def read(spec: dict, obs: dict):
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    if spec["what"] == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if spec["what"] == "busy_ms_per":
        units = obs["counts"].get(spec["per"], 0)
        return 1e3 * tr["busy_s"] / units if units else None
    raise ValueError(f"trace reader: unknown 'what' {spec['what']!r}")
