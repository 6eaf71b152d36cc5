"""The second reduction of a traced slice (benchmark/reduce/phases.py) and
its readers: device time by model phase, and the host's dispatch lead.

Nothing here reports a device metric: the numbers are hand-worked on a few
events, and checked for consistency on a slice of a recorded trace.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.readers import lead as lead_reader  # noqa: E402
from benchmark.readers import phase as phase_reader  # noqa: E402
from benchmark.reduce import phases  # noqa: E402
from benchmark.reduce import trace as reduce_trace  # noqa: E402

# two launches of two programs; the loop of each spans its body's operations
DATA = {
    "modules": [["jit_scan_train_n8_l1", 100, 100],
                ["jit_scan_train_n8_l2", 300, 200],
                ["jit_other", 600, 10]],
    "ops": [["copy.1", 100, 10], ["while.2", 110, 90],
            ["fusion.3", 110, 40], ["fusion.4", 150, 30],
            ["while.9", 300, 200], ["fusion.3", 300, 120],
            ["fusion.7", 420, 60], ["add.1", 600, 10]],
    "host": [["cgnn:scan.chunk", 90, 20], ["cgnn:scan.chunk", 120, 30],
             ["cgnn:warm.epoch", 0, 5]],
}
TABLES = {
    "jit_scan_train_n8_l1": {"copy.1": ["other", "fwd"],
                             "while.2": ["scan", "fwd"],
                             "fusion.3": ["conv.bn1", "fwd"],
                             "fusion.4": ["conv.gate", "bwd"]},
    # the same instruction name in another program is another instruction
    "jit_scan_train_n8_l2": {"while.9": ["scan", "fwd"],
                             "fusion.3": ["conv.fc_full", "bwd"],
                             "fusion.7": ["conv.bn2", "fwd"]},
}


def _obs(tables=TABLES, steps=3):
    seen = phases.phase_times(DATA, tables)
    seen["has_tables"] = bool(tables)
    seen["lead"] = phases.dispatch_lead(DATA, "cgnn:scan.chunk", "jit_scan_")
    return {"phase_trace": seen, "counts": {"traced_steps": steps},
            "trace": {"busy_s": 1.0}, "program_spans": []}


def test_phase_times_by_hand():
    got = phases.phase_times(DATA, TABLES)
    assert got["n_ops"] == 8
    assert got["by_phase"] == {
        ("other", "fwd"): 10,
        ("scan", "fwd"): (90 - 70) + (200 - 180),  # the loops' own time
        ("conv.bn1", "fwd"): 40, ("conv.gate", "bwd"): 30,
        ("conv.fc_full", "bwd"): 120, ("conv.bn2", "fwd"): 60,
        (phases.NO_TABLE, ""): 10,  # jit_other has no table
    }
    # self times add up to the time an operation ran at all
    assert got["busy_ns"] == 10 + 90 + 200 + 10


@pytest.mark.parametrize("spec,want", [
    ({"what": "ms_per", "phases": ["conv.bn1", "conv.bn2"],
      "per": "traced_steps"}, (40 + 60) / 1e6 / 3),
    ({"what": "ms_per", "phases": ["conv.gather", "conv.gate",
                                   "conv.aggregate"],
      "per": "traced_steps"}, 30 / 1e6 / 3),
    ({"what": "ms_per", "phases": ["conv.fc_full"], "per": "traced_steps"},
     120 / 1e6 / 3),
    ({"what": "ms_per", "phases": ["expand", "embed", "pool_head", "loss",
                                   "optimizer", "scan"],
      "per": "traced_steps"}, 40 / 1e6 / 3),
    ({"what": "unattributed_pct"}, 100.0 * (10 + 10) / 310),
    ({"what": "ops_per", "per": "traced_steps"}, 8 / 3),
])
def test_phase_reader_by_hand(spec, want):
    assert phase_reader.read(spec, _obs()) == pytest.approx(want)


def test_the_four_sums_and_the_unattributed_share_make_the_busy_time():
    obs = _obs()
    parts = [phase_reader.read(json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".train.json"))), obs)
        for name in ("conv_bn_ms", "conv_edge_ms", "conv_matmul_ms",
                     "step_other_ms")]
    lost = phase_reader.read({"what": "unattributed_pct"}, obs)
    busy_ms_per_step = 310 / 1e6 / 3
    assert sum(parts) + lost / 100.0 * busy_ms_per_step == pytest.approx(
        busy_ms_per_step)


def test_lead_by_hand():
    got = phases.dispatch_lead(DATA, "cgnn:scan.chunk", "jit_scan_")
    # launch 1 starts at 100, its dispatch ended at 110: the device did not
    # wait for the span to close; launch 2 at 300, dispatched by 150
    assert got["lead_ms"] == [pytest.approx(-10e-6), pytest.approx(150e-6)]
    assert got["skew_ms"] == pytest.approx(10e-6)
    assert lead_reader.read({}, _obs()) == pytest.approx(
        70e-6)
    # one dispatch more than launches: the slice cut them apart
    cut = dict(DATA, host=DATA["host"] + [["cgnn:scan.chunk", 200, 5]])
    assert phases.dispatch_lead(cut, "cgnn:scan.chunk", "jit_scan_") is None


def test_a_program_without_tables_reports_no_phase_and_raises_nothing():
    """The parent of the PR that added the scopes, under these readers."""
    obs = _obs(tables={})
    assert phases.phase_times(DATA, {})["by_phase"] == {
        (phases.NO_TABLE, ""): 310}
    assert phase_reader.read({"what": "unattributed_pct"}, obs) is None
    assert phase_reader.read({"what": "ms_per", "phases": ["scan"],
                              "per": "traced_steps"}, obs) is None
    assert phase_reader.read({"what": "ops_per", "per": "traced_steps"},
                             obs) == pytest.approx(8 / 3)
    obs["phase_trace"]["lead"] = None  # and no cgnn: spans either
    assert lead_reader.read({}, obs) is None
    # an untraced run reads nothing at all
    untraced = {"trace": None, "counts": {}, "program_spans": []}
    assert phases.observed(untraced) is None
    assert phase_reader.read({"what": "ops_per", "per": "traced_steps"},
                             untraced) is None
    assert lead_reader.read({}, untraced) is None


def test_tables_come_from_the_program_s_scan_program_instants():
    spans = [
        {"name": "scan.chunk", "ph": "X", "args": {"steps": 2}},
        {"name": "scan.program", "ph": "i",
         "args": {"module": "jit_scan_train_n8_l1", "length": 1,
                  "table": {"fusion.3": ["conv.bn1", "fwd"]}}},
    ]
    assert phases.tables_from_spans(spans) == {
        "jit_scan_train_n8_l1": {"fusion.3": ["conv.bn1", "fwd"]}}
    assert phases.instruction(
        "%fusion.31 = (bf16[]{:T(256)}, bf16[512,512]{1,0}) fusion(bf16[512,"
        "512]{1,0} %copy.15), kind=kOutput, calls=%fused_computation.17"
    ) == "fusion.31"
    assert phases.module_name(
        "jit_scan_train_n8_l2(16473556867225079226)"
    ) == "jit_scan_train_n8_l2"


def test_on_a_recorded_slice_the_phase_sums_are_the_busy_time():
    """Two launches of two programs out of a traced epoch on the chip: every
    nanosecond an operation ran is in exactly one (phase, direction), and
    the lead is what the three pairs of stamps give by hand."""
    with open(os.path.join(HERE, "fixtures",
                           "trace_phases_mp_train.json")) as f:
        fixture = json.load(f)
    data, tables = fixture["data"], fixture["tables"]
    assert len({m[0] for m in data["modules"][:2]}) == 2 == len(tables)
    got = phases.phase_times(data, tables)
    union = reduce_trace._union([[s, s + d] for _n, s, d in data["ops"]])
    busy = sum(e - s for s, e in union)
    assert got["busy_ns"] == busy == fixture["expect"]["busy_ns"]
    assert sum(got["by_phase"].values()) == busy
    assert got["n_ops"] == len(data["ops"]) == fixture["expect"]["n_ops"]
    assert (phases.NO_TABLE, "") not in got["by_phase"]
    named = {p for (p, _d), ns in got["by_phase"].items() if ns > 0}
    assert {"conv.gather", "conv.fc_full", "conv.bn1", "scan"} <= named
    # the loop, cut to the slice, keeps only what its body does not cover
    loops = [o for o in data["ops"] if o[0].startswith("while")]
    assert len(loops) == 2
    assert got["by_phase"]["scan", "fwd"] < sum(o[2] for o in loops) / 5

    lead = phases.dispatch_lead(data, "cgnn:scan.chunk", "jit_scan_")
    by_hand = [(m[1] - (h[1] + h[2])) / 1e6
               for h, m in zip(data["host"], data["modules"])]
    assert lead["lead_ms"] == pytest.approx(by_hand)
    assert lead["lead_ms"] == pytest.approx(fixture["expect"]["lead_ms"])
    # the first launch starts before the span that dispatched it opens: the
    # planes' clocks agree to no better than this
    assert lead["skew_ms"] == pytest.approx(
        (data["modules"][0][1] - data["host"][0][1]) / 1e6)
    assert lead["skew_ms"] == pytest.approx(fixture["expect"]["skew_ms"])
    assert -1.0 < lead["skew_ms"] < 0.0
