"""Reader ``roofline``: the least time the chip could take for the traced
work, over the device time it took.

The least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s (``counts.py``, ``peaks.json``), which the kind leaves under
``obs["counts"]["least_s_per_<per>"]`` with the bound that binds; the device
time is the trace's busy time per unit.
"""

from __future__ import annotations


def read(spec: dict, obs: dict):
    tr = obs.get("trace")
    counts = obs["counts"]
    units = counts.get(spec["per"], 0)
    least = counts.get("least_s_per_" + spec["per"])
    if not tr or tr["busy_s"] <= 0 or not units or least is None:
        return None
    return 100.0 * least / (tr["busy_s"] / units)
