"""Kind ``force_train`` (cell ``force.train``) without a chip: the manifest's
entries for it (found by name, in the manifest as committed and in the
rehearsals of ``manifest_cases.py``), a whole run of the kind at a tiny size
through ``run.run_cell``, what breaks ``correct``, and the staging counters
its per-layer metric reads. Nothing here reports a time or a device metric.
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

from manifest_cases import by_name, manifest, manifest_path  # noqa: E402,F401

from benchmark import run  # noqa: E402
from benchmark.readers import count, phase  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny_force.json")
NEW_METRICS = ("edge_geom_ms.train", "force_head_ms.train",
               "staged_dead_pct.train")


def test_the_cell_and_its_configuration_as_the_manifest_has_them(
        manifest, manifest_path):
    cell = run.Cell(manifest_path, "force.train")
    entry = by_name(manifest["workloads"])["force.train"]
    assert cell.entry == entry and cell.chips == 1
    assert entry["config"] == "md17-force"
    assert cell.traffic["kind"] == "force_train"
    cfg = cell.config
    assert cfg["source"] == by_name(manifest["configs"])[
        "md17-force"]["source"]
    assert cfg["task"] == "force" and cfg["reduced"] == ["dataset_size"]
    # every width as published, none cut
    assert cfg["model"] == {"atom_fea_len": 64, "n_conv": 3,
                            "h_fea_len": 128, "n_h": 1, "num_targets": 1}
    assert cfg["featurize"] == {"radius": 8.0, "max_num_nbr": 12,
                                "dmin": 0.0, "step": 0.2}
    assert cfg["data"]["n"] * cfg["data"]["resident_copies"] >= 211_762
    assert (cfg["train"]["energy_weight"], cfg["train"]["force_weight"]) \
        == (1.0, 10.0)
    limits = cfg["limits"]["force_train"]
    assert set(limits) == set(cfg["limits_why"]) == {
        "loss_rel", "grad_diff_median_leaf", "grad_norm_worst_leaf",
        "delta_norm_median_leaf", "grad_diff_off_energy_median_leaf",
        "force_diff_rel"}


def test_the_cell_s_metrics(manifest):
    """It reports train_rate, every *.train per-layer metric but the
    BatchNorm one (the trunk has none: without BatchNorm the conv's residual
    and its softplus carry ``conv.aggregate``'s phase, so the phase sums
    still add up to the step) and its own three, which no other cell
    lists."""
    mine = {m["name"] for m in manifest["per_layer"]
            if "force.train" in m.get("workloads", [])}
    train = {m["name"] for m in manifest["per_layer"]
             if "mp.train" in m.get("workloads", [])}
    assert mine == (train - {"conv_bn_ms.train"}) | set(NEW_METRICS)
    metrics = by_name(manifest["per_layer"])
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == ["force.train"]
        assert metrics[name]["moves"] == "train_rate"
    assert "force.train" in by_name(manifest["end_to_end"])[
        "train_rate"]["workloads"]


@pytest.fixture(scope="module")
def tiny_f32():
    """Cell() that computes in float32 at the test size, so that the only
    gap to the float32 reference is the order of the arithmetic."""
    real = run.Cell

    def make(manifest_path, name):
        cell = real(manifest_path, name)
        cell.config["precision"]["compute"] = "float32"
        cell.config["limits"]["force_train"] = {
            k: 1e-3 for k in cell.config["limits"]["force_train"]}
        return cell

    mp = pytest.MonkeyPatch()
    mp.setattr(run, "Cell", make)
    yield
    mp.undo()


@pytest.mark.parametrize("seed", [3_000_000_019, 23])
def test_a_whole_run_agrees_with_the_reference(tiny_f32, seed):
    result, code = run.run_cell(TINY, "tiny.force", seed, 1.0, False,
                                require_tpu=False)
    assert code == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_rate", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    json.dumps(result, allow_nan=False)


def test_a_force_step_that_drops_its_update_is_not_correct(tiny_f32,
                                                            monkeypatch):
    from cgnn_tpu.train import force_step

    real = force_step.make_force_train_step

    def broken(*a, **kw):
        body = real(*a, **kw)

        def train_step(state, batch):
            _new, metrics = body(state, batch)
            return state, metrics

        return train_step

    monkeypatch.setattr(force_step, "make_force_train_step", broken)
    result, code = run.run_cell(TINY, "tiny.force", 5, 0.5, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False


def test_forces_altered_where_they_are_produced_are_not_correct(
        tiny_f32, monkeypatch, capsys):
    """One percent on the inner gradient, in the train body and the predict
    step alike (both read it from ``_energy_and_grad_pos``)."""
    from cgnn_tpu.train import force_step

    real = force_step._energy_and_grad_pos

    def broken(*a, **kw):
        energies, grad_pos, stats = real(*a, **kw)
        return energies, grad_pos * 1.01, stats

    monkeypatch.setattr(force_step, "_energy_and_grad_pos", broken)
    result, code = run.run_cell(TINY, "tiny.force", 5, 0.5, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False
    out = capsys.readouterr().out
    assert "compare force_diff_rel" in out
    assert [ln for ln in out.splitlines()
            if ln.startswith("compare force_diff_rel")][0].endswith("FAIL")


def test_staging_counters_reach_the_dead_share(tiny_f32):
    """A traced run's telemetry counts what the scan stacks stage and how
    much of it is edge features; the kind copies both for the ``count``
    reader, and a program without them (the parent) reports nothing."""
    from benchmark.kinds import force_train

    cell = run.Cell(TINY, "tiny.force")
    ctx = run.Context(cell, 7, True)
    driver = force_train.Driver(ctx)
    driver.setup()
    counts = ctx.obs["counts"]
    batch = driver._first_batch()
    per_batch = sum(int(x.nbytes) for x in _leaves(batch))
    assert counts["staged_bytes"] == per_batch * driver.steps_per_epoch
    assert counts["staged_edge_fea_bytes"] \
        == int(batch.edges.nbytes) * driver.steps_per_epoch
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "staged_dead_pct.train.json")) as f:
        spec = json.load(f)
    share = count.read(spec, ctx.obs)
    assert 40 < share < 80
    assert share == pytest.approx(
        100 * batch.edges.nbytes / per_batch)
    assert count.read(spec, {"counts": {"real_nodes": 1}}) is None
    # the scan stage span carries both too, for whoever reads the trace
    stage = [ev for ev in ctx.telemetry.spans.events
             if ev.get("name") == "scan.stage" and ev.get("ph") == "X"]
    assert stage[-1]["args"]["edge_fea_bytes"] \
        == counts["staged_edge_fea_bytes"]
    ctx.telemetry.close()


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


@pytest.mark.parametrize("name", NEW_METRICS[:2])
def test_the_new_phase_metrics_read_their_phase_in_all_directions(name):
    """``ms_per`` sums a phase over fwd, bwd and bwd2; without the program's
    tables (the parent) it reports nothing and does not raise."""
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    (only,) = spec["phases"]
    by_phase = {(only, "fwd"): 1e6, (only, "bwd"): 2e6, (only, "bwd2"): 4e6,
                ("conv.gather", "bwd2"): 8e6}
    obs = {"counts": {"traced_steps": 7},
           "phase_trace": {"n_ops": 9, "has_tables": True,
                           "busy_ns": 15e6, "by_phase": by_phase}}
    assert phase.read(spec, obs) == pytest.approx(1.0)
    obs["phase_trace"]["has_tables"] = False
    assert phase.read(spec, obs) is None
    assert phase.read(spec, {"counts": {}, "phase_trace": None}) is None
