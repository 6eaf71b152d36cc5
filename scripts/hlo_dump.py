#!/usr/bin/env python
"""Dump the optimized HLO of the flagship train step and name the
surviving relayout/copy ops with their byte counts (VERDICT r3 #6).

PERF.md attributes a ~2.16 ms/step "data formatting" residual (~25% of
the step) to XLA/Mosaic layout assignment without an on-disk artifact.
This script produces the artifact: the post-optimization HLO for the
bench-shape train step on the REAL device, plus a ranked table of
copy/transpose/reshape-bearing instructions and their output bytes.

Writes:
  HLO_TRAIN_STEP.txt   full optimized HLO (the evidence)
  prints one JSON line with the ranked formatting ops

Usage: python scripts/hlo_dump.py [--n 8192]

``--cell NAME`` (PR 25) is another job on the same idea: compile a
benchmark cell's largest scan program at its REAL size for the DESCRIBED
chip (no chip needed: the TPU compiler is installed here and compiles for
``topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")``;
~1 min on this machine's CPU), write its text, and print, a model phase,
the instructions whose output is [E, F]-sized and those whose output is
[E, 2F]-sized (z and the rows the gather writes) — the check an issue
about ``conv.gather`` is judged by before any chip run. Run it with
``JAX_PLATFORMS=cpu``; one such process at a time (libtpu's lock file).
No cell runs it.

Usage: JAX_PLATFORMS=cpu python scripts/hlo_dump.py --cell mp.train \
           [--steps 1] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _elements(dims: str) -> int:
    """'6144,12,256' -> element count."""
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def shape_bytes(shape_str: str) -> int:
    """'bf16[6144,12,256]{2,1,0:T(8,128)(2,1)}' -> byte count."""
    m = _SHAPE_RE.match(shape_str)
    if not m or m.group(1) not in _DTYPE_BYTES:
        return 0
    return _elements(m.group(2)) * _DTYPE_BYTES[m.group(1)]


def compile_cell_for_described_chip(cell_name: str, steps: int):
    """The largest scan program of a benchmark cell, compiled for the
    described v5e -> (program name, optimized HLO text, E, F).

    The cell's own driver builds the state and the stacked groups (one
    resident copy: shapes are all that is lowered), with ``warm`` and the
    reference's first steps skipped — nothing is executed."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the cache but can never
    # be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)

    import importlib

    from benchmark import run
    from cgnn_tpu.train import loop

    cell = run.Cell(os.path.join(root, "BENCHMARK.json"), cell_name)
    cell.config["data"]["resident_copies"] = 1
    kind = importlib.import_module(
        "benchmark.kinds." + cell.traffic["kind"])
    bench = kind.Driver(run.Context(cell, 0, False))
    # set-up builds the scan driver itself, so its warm-up (a whole epoch)
    # is switched off on the class; this process does nothing else
    loop.ScanEpochDriver.warm = lambda self, state: state
    bench._first_steps = lambda state: state
    bench._note_roofline = lambda: None
    bench.setup()
    drv = bench.driver

    def node_capacity(key):
        return int(loop.program_name((key, steps), True)
                   .split("_n")[1].split("_")[0])

    key = max(drv._train_groups, key=node_capacity)
    name = loop.program_name((key, steps), True)
    fn = drv._window_fn(drv._train_scans, (key, steps), drv._train_body,
                        True)
    stacked = drv._train_groups[key]
    stack = int(jax.tree_util.tree_leaves(stacked)[0].shape[0])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (bench.state, stacked, np.zeros(stack, np.int32),
         np.zeros((), np.int32)))
    text = fn.lower(*shapes).compile().as_text()
    n = node_capacity(key)
    m = int(cell.config["layout"]["dense_m"])
    f = int(cell.config["model"]["atom_fea_len"])
    return name, text, n * m, f


_RESULT_RE = re.compile(r"^(\(?[\w\[\],{}:()\s*]*?\)?)\s([a-z][\w\-]*)\(")
_INNER_OPS = ("gather", "scatter", "select", "reduce", "copy", "transpose",
              "dynamic-slice", "dynamic-update-slice", "dot", "convolution")


def sized_instructions(text: str, elements: int) -> list[dict]:
    """Instructions the device reports as events (phases.phase_table's set)
    with an output of exactly ``elements`` elements, in program order, each
    with its phase and, for a fusion, the data-moving ops inside."""
    from cgnn_tpu.observe import phases

    comps = phases._parse(text)
    table = phases.phase_table(text)
    rows = []
    for comp in comps.values():
        for name, rest in comp["instrs"].items():
            if name not in table:
                continue
            m = _RESULT_RE.match(rest)
            if not m or m.group(2) in ("parameter", "get-tuple-element",
                                       "tuple", "bitcast", "while"):
                continue
            if elements not in {_elements(dims) for _, dims
                                in _SHAPE_RE.findall(m.group(1))}:
                continue
            inside = []
            called = phases._CALLED.search(rest)
            if called and called.group(2) in comps:
                body = comps[called.group(2)]["instrs"].values()
                inside = [op for op in _INNER_OPS
                          if any(re.search(rf"\s{op}\(", r) for r in body)]
            rows.append({
                "phase": "/".join(table[name]), "name": name,
                "op": m.group(2), "inside": inside,
                "shape": m.group(1).strip(),
            })
    return rows


def dump_cell(args) -> int:
    name, text, e, f = compile_cell_for_described_chip(args.cell, args.steps)
    out = args.out or f"{args.cell}_{name}.hlo.txt"
    with open(out, "w") as fh:
        fh.write(text)
    print(f"{name}: E = {e}, F = {f}; {len(text.splitlines())} lines -> {out}")
    # [E, F]: the gate's halves and the messages; [E, 2F]: z and the rows
    # the gather writes
    for width in (f, 2 * f):
        # identical (op, shape) lines of a phase fold into one with a
        # count, in order of first appearance: the convs repeat each chain
        by_phase: dict = {}
        for r in sized_instructions(text, e * width):
            inside = f" {{{','.join(r['inside'])}}}" if r["inside"] else ""
            line = f"{r['op']}{inside}  {r['shape']}"
            names = by_phase.setdefault(r["phase"], {}).setdefault(line, [])
            names.append(r["name"])
        print(f"== outputs of E*{width} elements")
        for phase in sorted(by_phase):
            print(f"{phase}: {sum(map(len, by_phase[phase].values()))}")
            for line, names in by_phase[phase].items():
                print(f"  {len(names)} x {names[0]:<30} {line}")
    takes = len(re.findall(r'op_name="[^"]*jit\(_take\)[^"]*select_n', text))
    print(f"select_n under jit(_take): {takes}")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--cell", type=str, default=None,
                   help="a train cell of BENCHMARK.json: compile its largest "
                        "scan program for the described v5e instead")
    p.add_argument("--steps", type=int, default=1,
                   help="--cell: the scan program's chunk length")
    p.add_argument("--n", type=int, default=8192)
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--out", type=str, default=None)
    p.add_argument("--top", type=int, default=20)
    args = p.parse_args(argv)
    if args.cell:
        return dump_cell(args)
    args.out = args.out or "HLO_TRAIN_STEP.txt"

    import jax
    import numpy as np

    from cgnn_tpu.analysis.program_audit import lower_train_program
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
    from cgnn_tpu.data.graph import bucketed_batch_iterator
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    graphs = load_synthetic_mp(args.n, cfg, seed=0)
    batches = list(bucketed_batch_iterator(
        graphs, args.batch_size, 3, shuffle=True,
        rng=np.random.default_rng(0), dense_m=12, snug=True,
        edge_dtype=jax.numpy.bfloat16,
    ))
    # largest bucket shape = the dominant cost
    batch = max(batches, key=lambda b: b.edge_capacity)
    model = CrystalGraphConvNet(
        atom_fea_len=64, n_conv=3, h_fea_len=128, dtype=jax.numpy.bfloat16,
        dense_m=12,
    )
    tx = make_optimizer(optim="sgd", lr=0.01, lr_milestones=[10**9])
    state = create_train_state(
        model, batch, tx, Normalizer.fit(np.stack([g.target for g in graphs]))
    )
    # ONE lowering path for train programs (ISSUE 8): the same
    # jit_train_step/abstract-aval plumbing graftaudit audits, so the
    # HLO this dumps is byte-for-byte the program the auditor gates
    compiled = lower_train_program(state, batch).compile()
    txt = compiled.as_text()
    with open(args.out, "w") as f:
        f.write(txt)

    # rank formatting instructions: explicit copies/transposes/bitcasts and
    # kLoop fusions whose root is one of those
    findings = []
    for line in txt.splitlines():
        s = line.strip()
        m = re.match(r"%?([\w.\-]+) = (\S+) (copy|transpose|bitcast(?:-convert)?)\(",
                     s)
        if m:
            findings.append({
                "op": m.group(3),
                "name": m.group(1),
                "shape": m.group(2),
                "bytes": shape_bytes(m.group(2)),
            })
    findings.sort(key=lambda d: -d["bytes"])
    total = sum(d["bytes"] for d in findings)
    out = {
        "metric": "hlo_formatting_ops",
        "device": str(jax.devices()[0].device_kind),
        "hlo_file": args.out,
        "hlo_instructions": len(txt.splitlines()),
        "explicit_formatting_ops": len(findings),
        "explicit_formatting_bytes": total,
        "top": findings[: args.top],
    }
    print(json.dumps(jsonfinite(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
