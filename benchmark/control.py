#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (PERF.md section 2).

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--window S]

In ONE process, at the cell's own size: for each seed, what a sound run of the
program reads against the plain reference, and what the control reads — the
reference put in the program's place and computed in float8 e4m3, the
precision below the configuration's bfloat16. A benchmark run never runs
this; it is for the chip, by hand, when a limit is set or questioned.
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
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--window", type=float, default=3.0)
    args = p.parse_args(argv)
    from benchmark import run
    from benchmark.reference import cgcnn_ref as ref
    from cgnn_tpu.runtime import configure_compile_cache

    configure_compile_cache(None)
    cell = run.Cell(os.path.join(ROOT, "BENCHMARK.json"), args.workload)
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
            if cell.traffic["kind"] != "train":
                driver.window(args.window, None)
            sound = {r["name"]: r["value"] for r in driver.check()}
            control = {r["name"]: r["value"]
                       for r in driver.check(control_mm=ref.mm_fp8)}
            out.append({"seed": seed, "program": sound, "control": control})
            print(json.dumps(out[-1]), flush=True)
            raw = getattr(driver, "raw_readings", None)
            if raw is not None:
                print("RAW " + json.dumps({"seed": seed, **raw()}),
                      flush=True)
    finally:
        close = getattr(driver, "close", None)
        if close is not None:
            close()
    names = list(out[0]["program"])
    for n in names:
        hi = max(o["program"][n] for o in out)
        lo = min(o["control"][n] for o in out)
        print(f"{n}: sound runs' largest {hi:.6g}, control's smallest "
              f"{lo:.6g}, ratio {lo / hi if hi else float('inf'):.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
