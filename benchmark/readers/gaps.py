"""Reader ``gaps``: the first device's idle time by the program span the
host was in (``reduce/gaps.py``), as a share of the traced window in percent.

`readers/gaps.py` | `read(spec, obs)` over `reduce/gaps.py`'s table | `reader` in the metric file

``what``: a list of span names (``["epoch.sched", "epoch.sched.put"]``: the
gaps that went to any of them), or ``"unspanned"`` (the gaps under
``scan.epoch`` alone or under no span of the dispatch thread). A name under
which no gap fell reads 0; the shares of all names and ``unspanned`` add up
to the device's idle share. Needs a traced run of a program that opens
``cgnn:scan.epoch``; without one, nothing.
"""

from __future__ import annotations

from benchmark.reduce import gaps


def read(spec: dict, obs: dict):
    table = gaps.observed(obs)
    if not table:
        return None
    return gaps.share_pct(table, spec["what"])
