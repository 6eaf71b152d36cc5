"""The benchmark's own tests: the manifest, the harness without a chip, the
reference against the system, the control, the counts and the trace reducer.

Sizes here are what a CPU test run can hold; nothing in this file reports a
time, a rate or a device metric.
"""

from __future__ import annotations

import importlib
import inspect
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from manifest_cases import (  # noqa: E402,F401
    CASES,
    REAL,
    by_name,
    load,
    manifest,
    manifest_path,
    root_of,
)

from benchmark import counts, counts_force, run  # noqa: E402
from benchmark.reduce import trace as reduce_trace  # noqa: E402
from benchmark.reference import cgcnn_ref as ref  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ---- the manifest, as committed and with a later PR's entries appended ---
# (``manifest_cases.py``: every test that takes ``manifest`` or
# ``manifest_path`` runs once a case)


def test_a_rehearsal_only_appends(manifest, manifest_path):
    """What the driver takes from a later PR: every list of the committed
    manifest is a prefix of the rehearsal's, entry for entry, but for names
    appended at the END of a metric's ``workloads``; each rehearsal adds a
    cell, and the second a configuration and a per-layer metric too."""
    real = load(REAL)
    for key in ("command", "paths", "run_seconds"):
        assert manifest[key] == real[key]
    added = {}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(real[group], manifest[group]):
            lists = was.get("workloads", []), now.get("workloads", [])
            assert lists[1][:len(lists[0])] == lists[0]
            assert {**now, "workloads": None} == {**was, "workloads": None}
        added[group] = len(manifest[group]) - len(real[group])
    if manifest_path == REAL:
        assert not any(added.values())
    else:
        assert added["workloads"] == 1 and added["end_to_end"] == 0
        assert added["configs"] == added["per_layer"] <= 1
        new = manifest["workloads"][len(real["workloads"])]["name"]
        assert new in by_name(manifest["end_to_end"])[
            "train_rate"]["workloads"]


def test_manifest_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in manifest[group]]
        assert len(seen) == len(set(seen)), f"duplicate name in {group}"
        names += seen
    for n in names:
        assert NAME.match(n), n
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_manifest_pairs_and_files(manifest, manifest_path):
    root = root_of(manifest_path)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    configs = {c["name"]: c for c in manifest["configs"]}
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs), "every configuration is used by some cell"
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(root, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank", "_len"))
                       for k in c["reduced"]), "no width is ever reduced"
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files)), "a file is one configuration's"
    for w in manifest["workloads"]:
        cell = run.Cell(manifest_path, w["name"])
        kind = importlib.import_module(
            "benchmark.kinds." + cell.traffic["kind"])
        assert cell.config["limits"][cell.traffic["kind"]], \
            "every configuration states the limits of its kinds"
        # what ``control.py`` puts in the program's place comes from the kind
        takes = inspect.signature(kind.Driver.check).parameters
        assert kind.CONTROLS and all(
            set(kw) <= set(takes) for kw in kind.CONTROLS.values())


def test_every_layer_metric_moves_a_metric_its_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)
        spec_path = os.path.join(ROOT, "benchmark", "layer_metrics",
                                 m["name"] + ".json")
        with open(spec_path) as f:
            spec = json.load(f)
        assert spec["layer"] == m["layer"]
        importlib.import_module("benchmark.readers." + spec["reader"])
    for cell in cells:
        reports = [n for n, ws in e2e.items() if cell in ws]
        assert "setup_s" in reports and len(reports) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


# ---- the harness without a chip ---------------------------------------


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "mp.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


@pytest.fixture(scope="module")
def tiny_f32(monkeypatch_module):
    """Cell() that computes in float32 at the test size, so that the only
    gap to the float32 reference is the order of the arithmetic."""
    real = run.Cell

    def make(manifest_path, name):
        cell = real(manifest_path, name)
        cell.config["precision"]["compute"] = "float32"
        cell.config["limits"] = {
            kind: {k: 1e-3 for k in lim}
            for kind, lim in cell.config["limits"].items()}
        return cell

    monkeypatch_module.setattr(run, "Cell", make)


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


@pytest.mark.parametrize("cell,seed,seconds", [
    ("tiny.train", 3_000_000_001, 1.0),
    ("tiny.predict", 17, 1.0),
    # four seconds: in one, on a loaded machine, one request was answered,
    # and one answer has no spread to measure an error against (PR 32)
    ("tiny.serve", 2**31 + 5, 4.0)])
def test_a_whole_run_agrees_with_the_reference(tiny_f32, cell, seed, seconds,
                                               capsys):
    """Past the look for a chip, a run is driven end to end: forward, loss,
    gradients and updates (train) or answers (predict) of the timed path
    agree with the plain reference, and the result line is whole: every
    number compared stands beside its limit under the line's last key and
    on the last lines of standard error, and a train run says where its
    window's time went."""
    result, code = run.run_cell(TINY, cell, seed, seconds, False,
                                require_tpu=False)
    assert code == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert json.loads(json.dumps(result, allow_nan=False)) == result
    assert list(result)[-1] == "compared" and result["compared"]
    assert all(row["value"] <= row["limit"]
               for row in result["compared"].values())
    err = capsys.readouterr().err.splitlines()
    assert [ln.split()[1] for ln in err[-len(result["compared"]):]] \
        == list(result["compared"])
    if cell == "tiny.train":
        ev = result["evidence"]
        assert len(ev["epoch_s"]) == len(ev["epoch_dispatch_s"]) \
            == result["attempted"]
        # the epochs' seconds are the rate's own time: the pool's 96
        # structures an epoch over their sum
        assert sum(ev["epoch_s"]) * result["metrics"]["train_rate"][
            "value"] == pytest.approx(96 * result["attempted"], rel=1e-9)
        assert len(ev["epoch_turn_ms"]) == result["attempted"] - 1
        assert ev["chunks"] >= result["attempted"] and ev["host_cpu_s"] > 0
        assert 0 < ev["chunk_ms_median"] <= ev["chunk_ms_longest"]
        assert 0 <= ev["chunk_longest_at_s"] <= sum(ev["epoch_s"])
        assert "trace_events_lost" not in ev  # an untraced run has no trace


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_f32, monkeypatch):
    from cgnn_tpu.train import step as step_mod

    real = step_mod.make_train_step

    def broken(*a, **kw):
        body = real(*a, **kw)

        def train_step(state, batch):
            _new, metrics = body(state, batch)
            return state, metrics  # the update is dropped

        return train_step

    monkeypatch.setattr(step_mod, "make_train_step", broken)
    result, code = run.run_cell(TINY, "tiny.train", 5, 0.5, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        tiny_f32, monkeypatch):
    from cgnn_tpu.train import step as step_mod

    real = step_mod.make_predict_step

    def broken(*a, **kw):
        body = real(*a, **kw)

        def predict_step(state, batch):
            out = body(state, batch)
            if isinstance(out, tuple):
                return (out[0] * 1.01,) + tuple(out[1:])
            return out * 1.01

        return predict_step

    monkeypatch.setattr(step_mod, "make_predict_step", broken)
    result, code = run.run_cell(TINY, "tiny.predict", 5, 0.5, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False


def test_the_load_generator_never_imports_jax():
    """The serve kind's parent holds the chip; its child must not touch it."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'benchmark'); import loadgen; "
         "print(sorted(m for m in sys.modules if m.split('.')[0] in "
         "('jax', 'jaxlib', 'cgnn_tpu', 'numpy')))"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0 and proc.stdout.strip() == "[]", proc


def test_compiled_shapes_and_work_do_not_depend_on_the_seed():
    """Two seeds: the same batches in the same groups (train) and the same
    structures on the same wires (predict); only weights and order differ."""
    from benchmark.kinds import predict, train

    def built(kind, name, seed):
        d = kind.Driver(run.Context(run.Cell(TINY, name), seed, False))
        d.setup()
        return d

    a, b = built(train, "tiny.train", 1), built(train, "tiny.train", 2**31 + 7)
    assert a.where == b.where
    assert [[g.cif_id for g in m] for m, _ in a.members] == \
        [[g.cif_id for g in m] for m, _ in b.members]
    assert not np.allclose(a.params0["fc_out"]["kernel"],
                           b.params0["fc_out"]["kernel"])
    # an epoch's launches are the configuration's: as many chunks, of the
    # same lengths, the bucket shapes in the same turn; the seed's own is
    # the order of the batches inside them (``train.ScheduleRng``)

    def schedule(d):
        queues, tails, _steps, picks = d.driver._build_sched(
            d.driver._train_groups, True, False)
        lengths = [[len(ch) for ch in chunks] for _k, _s, chunks in queues]
        order = np.concatenate([np.asarray(ch) for _k, _s, chunks in
                                queues + tails for ch in chunks])
        return lengths, picks, order

    (la, pa, oa), (lb, pb, ob) = schedule(a), schedule(b)
    assert la == lb and pa == pb and len(pa) > 1
    assert sorted(oa) == sorted(ob) and not np.array_equal(oa, ob)
    p, q = built(predict, "tiny.predict", 1), built(predict, "tiny.predict", 2)
    assert np.array_equal(p.rides_raw, q.rides_raw)
    assert [s.to_meta() for s in p.shape_set] == \
        [s.to_meta() for s in q.shape_set]
    # traffic is a pure function of (traffic file, seed)
    again = built(predict, "tiny.predict", 1)
    n = len(p.job_graphs)
    assert np.array_equal(p.rng.permutation(n), again.rng.permutation(n))
    assert not np.array_equal(again.rng.permutation(n), q.rng.permutation(n))


# ---- the control ------------------------------------------------------


def _structures(n: int):
    from benchmark import system

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mp-flagship.json")) as f:
        cfg = json.load(f)
    cfg["data"].update(n=n)
    graphs, _ = system.load_pool(cfg)
    return cfg, graphs


def test_the_float8_control_fails_the_training_limits():
    """The reference computed in float8 e4m3 (the precision below the
    configuration's bfloat16), put in the program's place at published
    widths, reads over a limit of the train cells; the float32 reference
    itself reads zero."""
    import jax.numpy as jnp

    from benchmark import system
    from benchmark.kinds import train
    from benchmark.weights import make_weights

    cfg, graphs = _structures(192)
    limits = cfg["limits"]["train"]
    params, stats = make_weights(11, cfg["model"], 92, 41)
    batches = [ref.coo_batch([system.graph_as_ref(g)
                              for g in graphs[s:s + 64]])
               for s in (0, 64, 128)]
    t_mean, t_std = system.target_stats(graphs)
    args = (params, stats, batches, jnp.float32(t_mean), jnp.float32(t_std))
    want = ref.sgd_steps(*args, lr=0.01, momentum=0.9)
    same = train.compare(want, want, limits)
    assert all(r["value"] == 0 for r in same)
    (control,) = train.CONTROLS.values()
    ctrl = train.compare(ref.sgd_steps(*args, lr=0.01, momentum=0.9,
                                       mm=control["control_mm"]),
                         want, limits)
    assert any(r["value"] > r["limit"] for r in ctrl), ctrl


def test_control_py_reads_every_control_of_the_kind(tiny_f32, capsys):
    """``control.py`` at the test size: one line a seed with the sound run's
    rows and those of each of the kind's ``CONTROLS``; in float32 the sound
    run is inside every limit and the float8 control over one."""
    from benchmark import control
    from benchmark.kinds import train

    assert control.main(["--manifest", TINY, "--workload", "tiny.train",
                         "--seeds", "3000000019,11"]) == 0
    out = capsys.readouterr().out.splitlines()
    lines = [json.loads(ln) for ln in out if ln.startswith("{")]
    assert [ln["seed"] for ln in lines] == [3000000019, 11]
    for ln in lines:
        assert set(ln) == {"seed", "program", *train.CONTROLS}
        assert max(ln["program"].values()) <= 1e-3
        assert max(ln["float8"].values()) > 1e-3
    assert any(ln.startswith("grad_diff_median_leaf: sound runs' largest")
               and "float8" in ln for ln in out)


def test_the_float8_control_fails_the_prediction_limits():
    import jax.numpy as jnp

    from benchmark import system
    from benchmark.kinds import predict
    from benchmark.weights import make_weights

    cfg, graphs = _structures(192)
    limits = cfg["limits"]["predict"]
    params, stats = make_weights(12, cfg["model"], 92, 41)
    batch = ref.coo_batch([system.graph_as_ref(g) for g in graphs[:128]])
    t_mean, t_std = system.target_stats(graphs)
    args = (params, stats, batch, jnp.float32(t_mean), jnp.float32(t_std))
    want = ref.predict(*args)[:, 0]
    (control,) = predict.CONTROLS.values()
    ctrl = predict.compare(ref.predict(*args, mm=control["control_mm"])[:, 0],
                           want, limits)
    assert any(r["value"] > r["limit"] for r in ctrl), ctrl


def test_reference_neighbour_search_is_the_dataset_s():
    """The reference's own brute-force search finds the edges and distances
    the data set carries (so its featurization stands alone)."""
    _cfg, graphs = _structures(192)
    from cgnn_tpu.data.rawbatch import raw_from_graph

    for g in graphs[:8]:
        r = raw_from_graph(g)
        c, nb, d = ref.neighbor_list(r.lattice, r.frac_coords, 8.0, 12)
        assert np.array_equal(c, g.centers)
        assert np.allclose(np.sort(d.reshape(-1, 12), axis=1),
                           np.sort(np.asarray(g.distances).reshape(-1, 12),
                                   axis=1), atol=1e-4)
        fea = ref.gaussian_expand(d, 8.0, 0.2)
        assert fea.shape == g.edge_fea.shape


# ---- counts and the trace reducer -------------------------------------


def test_counts_against_hand_worked_numbers():
    model = {"atom_fea_len": 2, "h_fea_len": 3, "n_conv": 1, "n_h": 1,
             "num_targets": 1}
    # N=10 atoms, E=40 edges, G=2 crystals, K=5 filters, A=7 atom features
    fwd = counts.step_counts(10, 40, 2, model, 5, 7, train=False)
    # node 2*10*2*4=160; nbr 2*10*2*4=160, once an ATOM (v @ K_j, then the
    # gather: the program's algorithm since PR 30; once an edge it was 640);
    # edge 2*40*5*4=1600; head 2*2*2*3 + 2*2*3*1 = 36
    assert fwd["flops"] == 160 + 160 + 1600 + 36
    p = (7 + 1) * 2 + ((4 + 5) * 4 + 4 + 8 + 4) + 3 * 3 + 4
    assert counts.n_params(model, 7, 5) == p
    nf = 10 * 2 * 2
    assert fwd["bytes"] == (2 * nf + 8 * 40) + (40 + nf) + nf + 4 * p
    trn = counts.step_counts(10, 40, 2, model, 5, 7, train=True)
    assert trn["flops"] == 3 * (160 + 160) + 2 * 1600 + 3 * 36
    assert trn["bytes"] == (5 * nf + 16 * 40) + (80 + 2 * nf) + 2 * nf \
        + 16 * p
    # the neighbour term does not grow with the edges
    more = counts.step_counts(10, 80, 2, model, 5, 7, train=True)
    assert more["flops"] - trn["flops"] == 2 * 1600
    least, bound = counts.least_seconds(
        {"flops": 197e12, "bytes": 819e9 / 2},
        counts.peaks_for("TPU v5 lite"))
    assert (least, bound) == (1.0, "flops")
    with pytest.raises(SystemExit):
        counts.peaks_for("some other chip")


def test_force_counts_against_hand_worked_numbers():
    model = {"atom_fea_len": 2, "h_fea_len": 3, "n_conv": 2}
    # N=10 atoms, E=40 edges, K=5 filters, A=7 atom features; in units of a
    # matmul's own FLOPs the edge term runs 5 times in every conv, the node
    # and neighbour terms 3 times in the first conv and 6 in the second, the
    # readout 6 (``counts_force.py``)
    got = counts_force.step_counts(10, 40, model, 5, 7, nbr_per_edge=False)
    node = nbr = 2 * 10 * 2 * 4  # both once an atom
    edge = 2 * 40 * 5 * 4
    head = 2 * 10 * 2 * 3 + 2 * 10 * 3
    assert got["flops"] == 2 * 5 * edge + (3 + 6) * (node + nbr) + 6 * head
    p = (7 + 1) * 2 + 2 * ((4 + 5) * 4 + 4) + 3 * 3 + 3 + 1
    assert counts_force.n_params(model, 7, 5) == p
    nf = 10 * 2 * 2
    assert got["bytes"] == 2 * (13 * nf + 28 * 40) + 48 * 10 \
        + (4 * 10 + 2 * nf) + 4 * nf + 24 * p
    more = counts_force.step_counts(10, 80, model, 5, 7, nbr_per_edge=False)
    assert more["flops"] - got["flops"] == 2 * 5 * edge
    # the default still counts the term an edge (2*40*2*4 = 640), which a
    # test outside the benchmark's paths pins (``counts_force.step_counts``)
    old = counts_force.step_counts(10, 40, model, 5, 7)
    assert old["flops"] - got["flops"] == (3 + 6) * (640 - 160)
    assert old["bytes"] == got["bytes"]


@pytest.mark.parametrize("cell,least_ms", [("mp.train", 0.0797),
                                           ("oc20.train", 0.0753),
                                           ("force.train", 0.0677)])
def test_the_cells_least_time_a_step(cell, least_ms):
    """The yardstick of ``step_roofline.train`` at the cell's own size, from
    shapes alone (the chip's peaks are a table; nothing here is measured):
    what PERF.md quotes, within 1%. ``mp.train``'s mean step is ISSUE 34's
    14,921 atoms and 179,055 edges: 1.57e10 FLOP."""
    from benchmark import system

    c = run.Cell(REAL, cell)
    cfg = c.config
    cfg["data"].update(n=256)
    graphs, _ = system.load_pool(cfg)
    n = np.mean([g.num_nodes for g in graphs])
    a, k = graphs[0].atom_fea.shape[1], graphs[0].edge_fea.shape[1]
    peaks = counts.peaks_for("TPU v5 lite")
    if cell == "force.train":
        b = 257
        got = counts_force.step_counts(b * n, b * n * 12, cfg["model"], k, a,
                                       nbr_per_edge=False)
    else:
        if cell == "mp.train":
            flagship = counts.step_counts(14921, 179055, 512, cfg["model"],
                                          41, a, train=True)
            assert flagship["flops"] == pytest.approx(1.57e10, rel=0.01)
            assert counts.least_seconds(flagship, peaks) == (
                pytest.approx(0.0797e-3, rel=0.01), "flops")
        b = cfg["train"]["batch_size"]
        got = counts.step_counts(b * n, b * n * 12, b, cfg["model"], k, a,
                                 train=True)
    least, bound = counts.least_seconds(got, peaks)
    assert bound == "flops"
    # 256 structures of the pool stand for its mean size to a few percent
    assert 1e3 * least == pytest.approx(least_ms, rel=0.08)


def test_trace_reducer_on_a_recorded_trace():
    with open(os.path.join(HERE, "fixtures", "trace_mp_train.json")) as f:
        fixture = json.load(f)
    s = reduce_trace.summarize(fixture["planes"])
    want = fixture["expect"]
    assert s["n_devices"] == want["n_devices"]
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["device_ops"][0][0] == want["top_op"]
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    # a hand-made pair of overlapping operations: busy is their union
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
               "events": [["a", 0, 10], ["b", 5, 10], ["c", 30, 10]]}]},
              {"name": "/host:CPU", "lines": [{"name": "t", "events": [
                  ["bench:fetch", 14, 20]]}]}]
    s = reduce_trace.summarize(planes)
    assert s["busy_s"] == pytest.approx(25e-9)
    assert s["window_s"] == pytest.approx(40e-9)
    assert s["idle_gaps"] == [["bench:fetch", pytest.approx(15e-9)]]


def _tiled(events: list, launches: int, period: int) -> tuple[list, list]:
    """The fixture's one step, launched ``launches`` times over."""
    t0 = min(ev[1] for ev in events)
    ops = [[n, s - t0 + k * period, d] for k in range(launches)
           for n, s, d in events]
    mods = [["jit_scan_train_n23944_l1(123)", k * period, period - 1]
            for k in range(launches)]
    return ops, mods


@pytest.mark.parametrize("removed,lost", [(0.0, False), (0.05, False),
                                          (0.1, True)])
def test_a_trace_that_lost_events_says_so(removed, lost):
    """The recorded ``mp.train`` step launched eight times over, whole, and
    with a share of ONE launch's events removed: a tenth is flagged (the
    ledger's two damaged runs had lost 14% of a step's operations and more),
    a twentieth is not (``reduce_trace.LOST_SHARE``). Nothing else about the
    summary changes its kind: the flag re-weighs no number."""
    with open(os.path.join(HERE, "fixtures", "trace_mp_train.json")) as f:
        fixture = json.load(f)
    dev = fixture["planes"][0]
    events = [ln["events"] for ln in dev["lines"] if ln["name"] == "XLA Ops"][0]
    period = 2 * max(s + d for _n, s, d in events)
    ops, mods = _tiled(events, 8, period)
    cut = int(round(removed * len(events)))
    third = range(3 * len(events), 3 * len(events) + cut)
    ops = [ev for i, ev in enumerate(ops) if i not in third]
    planes = [{"name": dev["name"], "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}]
    got = reduce_trace.events_lost(planes)
    assert got["lost"] is lost
    assert got["launches"] == 8 and got["short_launches"] == int(lost)
    assert got["least_share"] == pytest.approx(1 - cut / len(events))
    assert got["ops_outside_launches"] == 0
    # the fixture as recorded, one launch: nothing to compare it with
    assert reduce_trace.events_lost(fixture["planes"])["lost"] is False
    assert reduce_trace.events_lost([])["lost"] is None
    assert reduce_trace.summarize(planes)["busy_s"] > 0
