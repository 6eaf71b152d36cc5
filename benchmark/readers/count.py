"""Reader ``count``: counts the kind left under ``obs["counts"]``.

``what`` names a count; with ``over`` the value is 100 * what / over (a
share in percent), and with ``per`` it is what / per (scaled by ``scale``).
"""

from __future__ import annotations


def read(spec: dict, obs: dict):
    counts = obs["counts"]
    if spec["what"] not in counts:
        return None
    value = float(counts[spec["what"]])
    if "over" in spec:
        den = counts.get(spec["over"], 0)
        return 100.0 * value / den if den else None
    if "per" in spec:
        den = counts.get(spec["per"], 0)
        return spec.get("scale", 1.0) * value / den if den else None
    return value
