"""Reader ``phase_roofline``: the least time the chip could take for one
phase's work (a kernel and what surrounds it under its named scope), over
the device time the traced steps spent in that phase.

The least time is the larger of the phase's operations over peak FLOP/s and
its bytes over peak bytes/s (the configuration's counts file, ``peaks.json``),
which the kind leaves under ``obs["counts"][spec["least"]]``, seconds a
``spec["per"]``; the device time is ``reduce/phases.py``'s, both directions.
A program without the phase (or without phase tables: the parent of the PR
that added it) reports nothing.
"""

from __future__ import annotations

from benchmark.reduce import phases


def read(spec: dict, obs: dict):
    counts = obs["counts"]
    units = counts.get(spec["per"], 0)
    least = counts.get(spec["least"])
    seen = phases.observed(obs)
    if not seen or not seen["has_tables"] or not units or least is None:
        return None
    ns = sum(v for (phase, _d), v in seen["by_phase"].items()
             if phase in spec["phases"])
    if ns <= 0:
        return None
    return 100.0 * least / (ns / 1e9 / units)
