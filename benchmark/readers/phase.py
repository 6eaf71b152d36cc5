"""Reader ``phase``: device time by model phase (``reduce/phases.py``).

``what``:
- ``ms_per``: device milliseconds per unit (``obs["counts"][spec["per"]]``)
  in the phases listed under ``phases``, both directions. Needs the program's
  phase tables; a program that emits none reports nothing.
- ``unattributed_pct``: share of the busy time in phase ``other`` or in no
  table at all. Needs the tables too.
- ``ops_per``: device operations per unit.
"""

from __future__ import annotations

from benchmark.reduce import phases


def read(spec: dict, obs: dict):
    seen = phases.observed(obs)
    if not seen or not seen["n_ops"]:
        return None
    units = obs["counts"].get(spec.get("per", ""), 0)
    if spec["what"] == "ops_per":
        return seen["n_ops"] / units if units else None
    if not seen["has_tables"] or seen["busy_ns"] <= 0:
        return None
    by_phase = seen["by_phase"]
    if spec["what"] == "unattributed_pct":
        lost = sum(ns for (phase, _d), ns in by_phase.items()
                   if phase in phases.UNNAMED)
        return 100.0 * lost / seen["busy_ns"]
    if spec["what"] == "ms_per":
        ns = sum(v for (phase, _d), v in by_phase.items()
                 if phase in spec["phases"])
        return ns / 1e6 / units if units else None
    raise ValueError(f"phase reader: unknown 'what' {spec['what']!r}")
