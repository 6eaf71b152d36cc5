#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (PERF.md section 2).

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--window S]
                                 [--manifest <a test's>]

In ONE process, at the cell's own size: for each seed, what a sound run of the
program reads against the plain reference, and what each control of the
cell's kind reads: ``CONTROLS`` in ``kinds/<kind>.py`` maps a name to the
keywords of ``Driver.check`` under which the reference, computed wrongly,
stands in the program's place (float8 e4m3 for the bfloat16 cells, bfloat16
for ``force_train``'s float32, the broken collectives of ``dp_train``, whose
own ``python3 -m benchmark.kinds.dp_train`` prints the statistics leaf by
leaf besides). A benchmark run never runs this; it is for the chip, by hand,
when a limit is set or questioned.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--window", type=float, default=3.0)
    args = p.parse_args(argv)
    from benchmark import run
    from benchmark.kinds import train
    from cgnn_tpu.runtime import configure_compile_cache

    configure_compile_cache(None)
    cell = run.Cell(args.manifest, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = run.Context(cell, seeds[0], False)
    kind = importlib.import_module("benchmark.kinds." + cell.traffic["kind"])
    driver = kind.Driver(ctx)
    driver.setup()
    out = []
    try:
        for k, seed in enumerate(seeds):
            if k:
                driver.reseed(seed)
            if not isinstance(driver, train.Driver):
                # answers come from a window; a training kind's readings
                # are the first steps', which set-up and reseed() have driven
                driver.window(args.window, None)
            line = {"seed": seed, "program": {
                r["name"]: r["value"] for r in driver.check()}}
            for name, kw in kind.CONTROLS.items():
                line[name] = {r["name"]: r["value"]
                              for r in driver.check(**kw)}
            out.append(line)
            print(json.dumps(line), flush=True)
            raw = getattr(driver, "raw_readings", None)
            if raw is not None:
                print("RAW " + json.dumps({"seed": seed, **raw()}),
                      flush=True)
    finally:
        close = getattr(driver, "close", None)
        if close is not None:
            close()
    for n in out[0]["program"]:
        hi = max(o["program"][n] for o in out)
        print(f"{n}: sound runs' largest {hi:.6g}; smallest of " + ", ".join(
            f"{c} {min(o[c][n] for o in out):.6g}" for c in kind.CONTROLS))
    return 0


if __name__ == "__main__":
    sys.exit(main())
