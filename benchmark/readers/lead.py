"""Reader ``lead``: how far ahead of the device the host dispatches
(``reduce/phases.dispatch_lead``): the median over the traced slice's
chunks of the device start of a chunk's program less the end of the
host span that dispatched it, in milliseconds. Needs the program's
``cgnn:scan.chunk`` spans on the profiler's clock; without them, nothing."""

from __future__ import annotations

import statistics

from benchmark.reduce import phases


def read(spec: dict, obs: dict):
    seen = phases.observed(obs)
    if not seen or not seen["lead"]:
        return None
    return statistics.median(seen["lead"]["lead_ms"])
