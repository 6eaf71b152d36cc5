"""Reader ``hist``: the program's own histograms (``observe/hist.py``), as
the kind left them under ``obs["hists"][<what>]`` after the window:
``reduce`` is ``p50`` or ``mean``; ``scale`` multiplies (an occupancy share
becomes a percentage)."""

from __future__ import annotations


def read(spec: dict, obs: dict):
    h = obs["hists"].get(spec["what"])
    if not h:
        return None
    return spec.get("scale", 1.0) * h[spec["reduce"]]
