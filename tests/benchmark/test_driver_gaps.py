"""The epoch driver's host time as the benchmark reads it: the third
reduction of a traced slice (benchmark/reduce/gaps.py: the first device's idle
gaps by the program span the host was in, after the planes' clocks are brought
together) and the ring reader (benchmark/readers/ring.py: a chunk's period,
and what the chunks leave of their epoch's span).

Nothing here reports a device metric: the numbers are hand-worked on a dozen
events, and checked for closure on a slice of a recorded trace.
"""

from __future__ import annotations

import copy
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from manifest_cases import (  # noqa: E402,F401
    by_name,
    manifest,
    manifest_path,
)

from benchmark import run  # noqa: E402
from benchmark.readers import gaps as gaps_reader  # noqa: E402
from benchmark.readers import ring as ring_reader  # noqa: E402
from benchmark.readers import span as span_reader  # noqa: E402
from benchmark.reduce import gaps  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny_driver.json")
RING = ("chunk_period_ms.train", "chunk_accumulate_ms.train",
        "epoch_sched_ms.train", "host_unspanned_pct.train")
IDLE = ("idle_in_chunk_pct.train", "idle_in_accumulate_pct.train",
        "idle_in_turn_pct.train", "idle_unspanned_pct.train")
CELLS = ["mp.train", "oc20.train", "force.train", "mp.train-dp4",
         "ocp.train"]

# One epoch of two chunks and its turn on the dispatch thread, the fetch on a
# thread of its own; nine busy intervals of the device, so eight gaps:
#   (150,170) inside chunk 0            (215,250) inside accumulate 0
#   (270,290) between accumulate 0 and chunk 1: the loop's own
#   (390,430) 10 of chunk 1, 20 of the loop's own, 10 of accumulate 1
#   (620,690) under epoch.sched before its put   (710,840) under the put
#   (1015,1045) under epoch.fetch_start (and the other thread's epoch.fetch)
#   (1060,1100) under no span of the dispatch thread (epoch.fetch covers it)
DATA = {
    "busy": [[130, 150], [170, 215], [250, 270], [290, 390], [430, 620],
             [690, 710], [840, 1015], [1045, 1060], [1100, 1200]],
    "launches": [["jit_scan_train_n8_l2", 130, 60],
                 ["jit_scan_train_n8_l2", 305, 80], ["jit_other", 700, 5]],
    "threads": [
        [["cgnn:scan.epoch", 100, 900], ["cgnn:scan.chunk", 110, 90],
         ["cgnn:scan.accumulate", 210, 50], ["cgnn:scan.chunk", 300, 100],
         ["cgnn:scan.accumulate", 420, 60], ["cgnn:epoch.sched", 600, 300],
         ["cgnn:epoch.sched.put", 700, 150],
         ["cgnn:epoch.fetch_start", 1010, 40]],
        [["cgnn:epoch.fetch", 1020, 980]],
    ],
}


def _row(ns, n, longest):
    return {"ns": ns, "gaps": n, "longest_ns": longest}


def test_segments_are_the_innermost_span_at_every_instant():
    assert gaps.segments(DATA["threads"][0]) == [
        [100, 110, "cgnn:scan.epoch"], [110, 200, "cgnn:scan.chunk"],
        [200, 210, "cgnn:scan.epoch"], [210, 260, "cgnn:scan.accumulate"],
        [260, 300, "cgnn:scan.epoch"], [300, 400, "cgnn:scan.chunk"],
        [400, 420, "cgnn:scan.epoch"], [420, 480, "cgnn:scan.accumulate"],
        [480, 600, "cgnn:scan.epoch"], [600, 700, "cgnn:epoch.sched"],
        [700, 850, "cgnn:epoch.sched.put"], [850, 900, "cgnn:epoch.sched"],
        [900, 1000, "cgnn:scan.epoch"],
        [1010, 1050, "cgnn:epoch.fetch_start"]]


def test_gaps_by_hand():
    """A gap under each span, one under the parent alone, one under a span of
    another thread: each goes to the innermost span of the dispatch thread
    that holds most of it, and all of them add up to the idle time."""
    got = gaps.attribute(DATA)
    assert got["by_span"] == {
        "scan.chunk": _row(20, 1, 20),
        "scan.accumulate": _row(35, 1, 35),
        "unspanned": _row(20 + 40 + 40, 3, 40),
        "epoch.sched": _row(70, 1, 70),
        "epoch.sched.put": _row(130, 1, 130),
        "epoch.fetch_start": _row(30, 1, 30),
    }
    assert "epoch.fetch" not in got["by_span"]  # another thread's
    assert got["window_ns"] == 1200 - 130 and got["gaps"] == 8
    assert got["idle_ns"] == 385 == sum(
        r["ns"] for r in got["by_span"].values())
    assert got["chunks"] == 2
    # launches start 20 and 5 ns after their dispatch began: no shift
    assert got["skew_ms"] == pytest.approx(5e-6) and got["shift_ms"] == 0
    assert got["least_lead_ms"] == pytest.approx((305 - 400) / 1e6)


def test_a_negative_skew_shifts_the_host_before_anything_is_covered():
    """Launches that start 10 and 30 ns BEFORE their dispatch began: the
    host plane's clock is at least 30 ns ahead, so every host event is moved
    30 ns back first, and four gaps change hands: (215,250) from
    accumulate 0 to the loop's own, (270,290) from the loop's own to chunk 1,
    (390,430) to accumulate 1, (1015,1045) out of epoch.fetch_start."""
    data = copy.deepcopy(DATA)
    data["launches"][0][1] = 100
    data["launches"][1][1] = 270
    got = gaps.attribute(data)
    assert got["skew_ms"] == pytest.approx(-30e-6)
    assert got["shift_ms"] == pytest.approx(-30e-6)
    # (270 - 400) before the shift, 30 ns more after it
    assert got["least_lead_ms"] == pytest.approx(-100e-6)
    assert got["by_span"] == {
        "scan.chunk": _row(20 + 20, 2, 20),
        "scan.accumulate": _row(40, 1, 40),
        "unspanned": _row(35 + 30 + 40, 3, 40),
        "epoch.sched": _row(70, 1, 70),
        "epoch.sched.put": _row(130, 1, 130),
    }
    assert sum(r["ns"] for r in got["by_span"].values()) == got["idle_ns"]


@pytest.mark.parametrize("case,why", [
    ("a_launch_more", "2 cgnn:scan.chunk spans against 3 launches"),
    ("a_span_more", "3 cgnn:scan.chunk spans against 2 launches"),
    ("no_epoch_span", "opens no cgnn:scan.epoch"),
    ("no_chunk_span", "opens no cgnn:scan.epoch"),
    ("no_device", "no device operation")])
def test_where_nothing_can_be_paired_nothing_is_reported(case, why, capsys):
    data = copy.deepcopy(DATA)
    if case == "a_launch_more":
        data["launches"].append(["jit_scan_train_n8_l1", 900, 10])
    elif case == "a_span_more":
        data["threads"][0].append(["cgnn:scan.chunk", 500, 20])
    elif case == "no_epoch_span":  # the parent of the PR that added it
        data["threads"][0] = [ev for ev in data["threads"][0]
                              if ev[0] != "cgnn:scan.epoch"]
    elif case == "no_chunk_span":
        data["threads"] = data["threads"][1:]
    else:
        data["busy"] = []
    got = gaps.attribute(data)
    assert set(got) == {"refused"} and why in got["refused"]
    # and the readers say nothing, without raising
    obs = {"gap_trace": None}
    for name in IDLE:
        assert gaps_reader.read(_spec(name), obs) is None


def _spec(name: str) -> dict:
    return run.load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                      name + ".json"))


def _idle_metrics(table: dict) -> dict:
    obs = {"gap_trace": table}
    return {name: gaps_reader.read(_spec(name), obs) for name in IDLE}


def test_the_four_idle_metrics_close_on_the_idle_share():
    table = gaps.attribute(DATA)
    got = _idle_metrics(table)
    assert got == {
        "idle_in_chunk_pct.train": pytest.approx(100 * 20 / 1070),
        "idle_in_accumulate_pct.train": pytest.approx(100 * 35 / 1070),
        "idle_in_turn_pct.train": pytest.approx(100 * (70 + 130 + 30) / 1070),
        "idle_unspanned_pct.train": pytest.approx(100 * 100 / 1070),
    }
    assert sum(got.values()) == pytest.approx(100 * 385 / 1070, rel=1e-12)
    # a span under which no gap fell reads 0, not nothing
    table["by_span"].pop("scan.accumulate")
    assert _idle_metrics(table)["idle_in_accumulate_pct.train"] == 0.0


def test_the_table_is_printed_with_the_skew_applied(capsys):
    gaps.report(gaps.attribute(DATA), 0.0)
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("idle gaps by program span: 8 gaps")
    assert "host events shifted by 0.000 ms" in out[1]
    assert [ln.split()[1] for ln in out[2:]] == [
        "epoch.sched.put", "unspanned", "epoch.sched", "scan.accumulate",
        "epoch.fetch_start", "scan.chunk"]


def test_closure_on_a_recorded_slice():
    """A slice of a traced ``mp.train-dp4`` run (the cell the host limits):
    the planes' clocks disagree there, every gap finds one name, and the
    four metrics add up to the slice's idle share."""
    with open(os.path.join(HERE, "fixtures",
                           "trace_gaps_mp_train_dp4.json")) as f:
        fixture = json.load(f)
    table = gaps.attribute(fixture["data"])
    assert table == fixture["table"]  # as the cut recorded it
    assert table["chunks"] == sum(
        1 for ev in gaps.dispatch_thread(fixture["data"]["threads"])
        if ev[0] == gaps.CHUNK) >= 20
    assert table["idle_ns"] == sum(
        r["ns"] for r in table["by_span"].values()) > 0
    got = _idle_metrics(table)
    assert sum(got.values()) == pytest.approx(
        100.0 * table["idle_ns"] / table["window_ns"], rel=1e-12)
    assert set(table["by_span"]) <= {
        "scan.chunk", "scan.accumulate", "unspanned", "epoch.sched",
        "epoch.sched.put", "epoch.fetch_start"}
    # a launch cannot start before its dispatch began: after the shift none
    # does, and the host still leads the device by less than a chunk
    assert table["shift_ms"] == min(0.0, table["skew_ms"])
    assert table["least_lead_ms"] > -10.0


# ---- the ring reader ---------------------------------------------------


def _ev(name, ts, dur, depth, tid=0, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 0,
            "tid": tid, "args": {**args, "depth": depth}}


def _ring():
    """Two train epochs and an eval epoch (microseconds): the first holds
    three chunks that start 200 and 300 apart, a schedule built on the miss
    with its put inside, and 350 of its 1,000 in no child; the second two
    chunks 100 apart and 320 of its 500 in no child."""
    evs = [_ev("scan.epoch", 0, 1000, 0, epoch=0, train=True),
           _ev("epoch.sched", 5, 200, 1, train=True, prebuilt=False),
           _ev("epoch.sched.put", 50, 150, 2, perms=3, bytes=24)]
    for k, ts in enumerate((210, 410, 710)):
        evs += [_ev("scan.chunk", ts, 100, 1, epoch=0, chunk=k, train=True,
                    steps=2, program="scan_train_n8_l2"),
                _ev("scan.accumulate", ts + 110, 50, 1, epoch=0, chunk=k)]
    evs += [_ev("epoch.sched", 1100, 600, 0, train=True, prebuilt=True),
            _ev("epoch.fetch", 1050, 900, 0, tid=1, epoch=0),
            _ev("scan.epoch", 2000, 500, 0, epoch=1, train=True)]
    for k, ts in enumerate((2010, 2110)):
        evs += [_ev("scan.chunk", ts, 60, 1, epoch=1, chunk=k, train=True,
                    steps=2, program="scan_train_n8_l2"),
                _ev("scan.accumulate", ts + 60, 30, 1, epoch=1, chunk=k)]
    evs += [_ev("scan.epoch", 3000, 100, 0, epoch=2, train=False),
            _ev("scan.chunk", 3010, 80, 1, epoch=2, chunk=0, train=False,
                steps=1, program="scan_eval_n8_l1"),
            {"name": "scan.program", "ph": "i", "ts": 1.0, "args": {}}]
    return {"program_spans": evs}


def test_ring_reader_by_hand():
    obs = _ring()
    # start to start within an epoch: 200, 300 and 100; never across epochs
    assert ring_reader.read(_spec("chunk_period_ms.train"), obs) \
        == pytest.approx(0.2)
    # (1000 - 300 - 150 - 200) + (500 - 120 - 60) of 1,500: the put is a
    # grandchild, the prebuilt schedule and the fetch are not inside
    assert ring_reader.read(_spec("host_unspanned_pct.train"), obs) \
        == pytest.approx(100 * (350 + 320) / 1500)
    # the two that reader ``span`` reads as it stands
    assert span_reader.read(_spec("chunk_accumulate_ms.train"), obs) \
        == pytest.approx(0.05)
    assert span_reader.read(_spec("epoch_sched_ms.train"), obs) \
        == pytest.approx(0.4)
    with pytest.raises(ValueError):
        ring_reader.read({"what": "mean", "span": "scan.chunk"}, obs)


def test_a_ring_without_the_ids_reports_nothing():
    """The parent's program: ``scan.chunk`` with ``steps`` and ``train``
    alone, no ``scan.epoch``."""
    obs = {"program_spans": [
        _ev("scan.chunk", ts, 100, 0, train=True, steps=2)
        for ts in (10, 210, 410)], "gap_trace": None}
    for name in RING:
        spec = _spec(name)
        reader = importlib.import_module("benchmark.readers."
                                         + spec["reader"])
        assert reader.read(spec, obs) is None, name
    # one chunk an epoch has no period either
    obs = {"program_spans": [_ev("scan.chunk", 10, 100, 1, epoch=0, chunk=0,
                                 train=True)]}
    assert ring_reader.read(_spec("chunk_period_ms.train"), obs) is None


# ---- the manifest's entries, found by name -------------------------------


def test_the_new_metrics_entries(manifest):
    """Eight entries, each on all five training cells (and on a cell a later
    PR appends to ``mp.train``'s lists), moving ``train_rate``; each file
    loads, carries its layer and names a reader that exists."""
    entries = by_name(manifest["per_layer"])
    for name in RING + IDLE:
        m = entries[name]
        spec = _spec(name)
        assert spec["name"] == name and spec["layer"] == m["layer"]
        assert m["layer"] == ("epoch driver" if name in RING else "device")
        assert m["source"] == ("program_span" if name in RING
                               else "device_trace")
        assert m["moves"] == "train_rate" and m["better"] == "lower"
        assert m["unit"] == ("ms" if name.endswith("_ms.train") else "%")
        assert m["workloads"][:5] == CELLS
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    # the turn's list lives in the metric's file
    assert _spec("idle_in_turn_pct.train")["what"] == [
        "epoch.sched", "epoch.sched.put", "epoch.fetch_start"]
    # the same cells report the metrics these stand beside
    for old in ("chunk_dispatch_ms.train", "device_idle_pct.train"):
        assert set(CELLS) <= set(entries[old]["workloads"])


def test_the_cells_report_them_where_a_trace_exists(manifest_path):
    """Through ``run.read_layer_metrics``, as a traced run reads them: with
    the ring and a gaps table every cell's line holds all eight; with
    neither (the parent's program under these files) none of them, and the
    metrics that were there before read as before."""
    table = gaps.attribute(DATA)
    for name in CELLS:
        cell = run.Cell(manifest_path, name)
        obs = {"counts": {}, "spans": [], "hists": {}, "trace": None,
               "phase_trace": None, **_ring(), "gap_trace": table}
        got = run.read_layer_metrics(cell, obs)
        assert set(RING + IDLE) <= set(got)
        assert got["chunk_dispatch_ms.train"]["value"] == pytest.approx(0.1)
        parent = {**obs, "gap_trace": None, "program_spans": [
            _ev("scan.chunk", 10, 100, 0, train=True, steps=2)]}
        got = run.read_layer_metrics(cell, parent)
        assert not set(RING + IDLE) & set(got)
        assert got["chunk_dispatch_ms.train"]["value"] == pytest.approx(0.1)


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.train-dp4",
                                  "tiny.force", "tiny.ocp"])
def test_a_traced_tiny_run_reports_the_ring_metrics(cell, capsys):
    """A whole traced run of every training kind on the CPU: no device plane,
    so the idle table is refused (and says why), and the four metrics the
    ring alone gives are in the line, consistent with each other."""
    result, code = run.run_cell(TINY, cell, 11, 1.0, True, require_tpu=False)
    assert code == 0 and result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(RING) <= set(got) and not set(IDLE) & set(got)
    assert "idle gaps by program span: nothing, no device operation" \
        in capsys.readouterr().out
    assert got["chunk_period_ms.train"] > 0
    assert got["chunk_accumulate_ms.train"] > 0
    assert got["epoch_sched_ms.train"] > 0
    assert 0 <= got["host_unspanned_pct.train"] < 100
    json.dumps(result, allow_nan=False)
