"""The benchmark's own tests: the manifest, the harness without a chip, the
reference against the system, the control, the counts and the trace reducer.

Sizes here are what a CPU test run can hold; nothing in this file reports a
time, a rate or a device metric.
"""

from __future__ import annotations

import importlib
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

from benchmark import counts, run  # noqa: E402
from benchmark.reduce import trace as reduce_trace  # noqa: E402
from benchmark.reference import cgcnn_ref as ref  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---- the manifest -----------------------------------------------------


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


def test_manifest_pairs_and_files(manifest):
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    configs = {c["name"]: c for c in manifest["configs"]}
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs), "every configuration is used by some cell"
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank", "_len"))
                       for k in c["reduced"]), "no width is ever reduced"
    for w in manifest["workloads"]:
        cell = run.Cell(os.path.join(ROOT, "BENCHMARK.json"), w["name"])
        importlib.import_module("benchmark.kinds." + cell.traffic["kind"])
        assert cell.config["limits"][cell.traffic["kind"]], \
            "every configuration states the limits of its kinds"


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


@pytest.mark.parametrize("cell,seed", [("tiny.train", 3_000_000_001),
                                       ("tiny.predict", 17),
                                       ("tiny.serve", 2**31 + 5)])
def test_a_whole_run_agrees_with_the_reference(tiny_f32, cell, seed):
    """Past the look for a chip, a run is driven end to end: forward, loss,
    gradients and updates (train) or answers (predict) of the timed path
    agree with the plain reference, and the result line is whole."""
    result, code = run.run_cell(TINY, cell, seed, 1.0, False,
                                require_tpu=False)
    assert code == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    json.dumps(result, allow_nan=False)


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
    ctrl = train.compare(ref.sgd_steps(*args, lr=0.01, momentum=0.9,
                                       mm=ref.mm_fp8), want, limits)
    assert any(r["value"] > r["limit"] for r in ctrl), ctrl


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
    ctrl = predict.compare(ref.predict(*args, mm=ref.mm_fp8)[:, 0], want,
                           limits)
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
    # node 2*10*2*4=160, nbr 2*40*2*4=640, edge 2*40*5*4=1600,
    # head 2*2*2*3 + 2*2*3*1 = 36
    assert fwd["flops"] == 160 + 640 + 1600 + 36
    p = (7 + 1) * 2 + ((4 + 5) * 4 + 4 + 8 + 4) + 3 * 3 + 4
    assert counts.n_params(model, 7, 5) == p
    nf = 10 * 2 * 2
    assert fwd["bytes"] == (2 * nf + 8 * 40) + (40 + nf) + nf + 4 * p
    trn = counts.step_counts(10, 40, 2, model, 5, 7, train=True)
    assert trn["flops"] == 3 * (160 + 640) + 2 * 1600 + 3 * 36
    assert trn["bytes"] == (5 * nf + 16 * 40) + (80 + 2 * nf) + 2 * nf \
        + 16 * p
    least, bound = counts.least_seconds(
        {"flops": 197e12, "bytes": 819e9 / 2},
        counts.peaks_for("TPU v5 lite"))
    assert (least, bound) == (1.0, "flops")
    with pytest.raises(SystemExit):
        counts.peaks_for("some other chip")


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
