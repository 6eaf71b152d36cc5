"""Kind ``ocp_train`` (cell ``ocp.train``) without a chip: the manifest's
entries for it (found by name, in the manifest as committed and in the
rehearsals of ``manifest_cases.py``), a whole run of the kind at a tiny size
through ``run.run_cell``, what breaks ``correct``, its counts by hand, and
what its per-layer metric and counters read. Nothing here reports a time or
a device metric.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from manifest_cases import by_name, manifest, manifest_path  # noqa: E402,F401

from benchmark import counts, counts_ocp, run  # noqa: E402
from benchmark.kinds import ocp_train  # noqa: E402
from benchmark.readers import phase  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny_ocp.json")
CELL, CONFIG, NEW_METRIC = "ocp.train", "oc20-cgcnn-ocp", "conv_ln_ms.train"


def test_the_cell_and_its_configuration_as_the_manifest_has_them(
        manifest, manifest_path):
    cell = run.Cell(manifest_path, CELL)
    entry = by_name(manifest["workloads"])[CELL]
    assert cell.entry == entry and cell.chips == 1
    assert (entry["config"], entry["traffic"]) == (CONFIG, "ocp-train")
    assert len(entry["why"]) <= 200
    assert cell.traffic["kind"] == "ocp_train"
    assert cell.traffic["chunk_steps"] == 2 and cell.traffic["who"]
    cfg = cell.config
    listed = by_name(manifest["configs"])[CONFIG]
    assert cfg["source"] == listed["source"] and len(cfg["source"]) <= 200
    assert cfg["reduced"] == listed["reduced"] == ["dataset_size"]
    # every width as the source has it, none cut: one chip holds the model
    assert cfg["model"] == {
        "atom_fea_len": 384, "n_conv": 6, "h_fea_len": 512, "n_h": 4,
        "num_targets": 1, "num_gaussians": 100, "edge_norm": "batch",
        "node_norm": "layer", "pool_softplus": False}
    assert cfg["source_keys"] == {
        "atom_embedding_size": 384, "num_graph_conv_layers": 6,
        "fc_feat_size": 512, "num_fc_layers": 4, "cutoff": 6.0,
        "max_neighbors": 50, "num_gaussians": 100}
    f = cfg["featurize"]
    assert (f["radius"], f["max_num_nbr"], f["dmin"]) == (6.0, 50, 0.0)
    assert cfg["layout"]["dense_m"] == 50
    # exactly 100 centres, the last at the cutoff; GaussianSmearing's width
    mu = np.arange(f["dmin"], f["radius"] + f["step"], f["step"],
                   dtype=np.float32)
    assert len(mu) == 100 and mu[-1] == pytest.approx(6.0)
    assert f["step"] == pytest.approx(6 / 99)
    assert f["var"] == pytest.approx(2 ** 0.5 * 6 / 99)
    tr = cfg["train"]
    assert (tr["optim"], tr["loss"], tr["lr"], tr["batch_size"],
            tr["buckets"]) == ("adam", "l1", 0.01, 32, 2)
    assert {"momentum", "lr_milestones_epochs"} <= set(tr)  # build_state's
    data = cfg["data"]
    assert data["generator"] == "load_synthetic_oc20_ocp"
    assert (data["n"], data["pool_seed"], data["pack_seed"]) == (2048, 0, 0)
    assert data["resident_copies"] >= 3 and data["a0"] == 3.0
    assert cfg["precision"]["compute"] == "bfloat16"
    assert len(cfg["assumed"]) >= 4
    limits = cfg["limits"]["ocp_train"]
    assert set(limits) == set(cfg["limits_why"]) == {
        "loss_rel", "grad_diff_median_leaf", "grad_norm_worst_leaf",
        "delta_norm_median_leaf"}
    assert limits["delta_norm_median_leaf"] < 1  # an unchanged state's


def test_the_cell_s_metrics(manifest):
    """It reports train_rate, every per-layer metric that ``oc20.train``
    lists (``conv_bn_ms.train`` reads bn1 alone here) and its own, which no
    other cell lists; each list it joined has it at its end."""
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    oc20 = {m["name"] for m in manifest["per_layer"]
            if "oc20.train" in m.get("workloads", [])}
    assert mine == oc20 | {NEW_METRIC}
    metrics = by_name(manifest["per_layer"])
    new = metrics[NEW_METRIC]
    assert new["workloads"] == [CELL] and new["moves"] == "train_rate"
    assert new["layer"] == metrics["conv_bn_ms.train"]["layer"]
    assert CELL in by_name(manifest["end_to_end"])["train_rate"]["workloads"]
    # run.Cell resolves the same set, the manifest-wide metrics besides
    cell_metrics = {m["name"] for m in run.Cell(
        os.path.join(ROOT, "BENCHMARK.json"), CELL).per_layer()}
    assert mine <= cell_metrics and "step_roofline.train" in cell_metrics


def test_parameters_and_operations_by_hand():
    """The configuration's 5.04 M parameters leaf by leaf, and one step's
    FLOPs and bytes at round sizes: the neighbour term once an atom, three
    hidden head layers, LayerNorm nothing, Adam's 24 bytes a parameter."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        model = json.load(f)["model"]
    conv = 868 * 768 + 768 + 2 * 768 + 2 * 384
    assert conv == 669_696
    p = 93 * 384 + 6 * conv + 385 * 512 + 3 * 513 * 512 + 513
    assert p == 5_039_489
    assert counts_ocp.n_params(model, 92, 100) == p
    n, e, g = 3600.0, 158_000.0, 32.0
    got = counts_ocp.step_counts(n, e, g, model, 100, 92)
    node = 2 * n * 384 * 768
    edge = 2 * e * 100 * 768
    head = 2 * g * 384 * 512 + 3 * 2 * g * 512 * 512 + 2 * g * 512
    assert got["flops"] == pytest.approx(
        6 * (3 * 2 * node + 2 * edge) + 3 * head)
    nf = n * 384 * 2
    assert got["bytes"] == pytest.approx(
        6 * (5 * nf + 16 * e) + 8 * n + 2 * nf + 2 * nf + 24 * p)
    least, bound = counts.least_seconds(got, counts.peaks_for("TPU v5 lite"))
    assert bound == "flops" and 1.5e-3 < least < 2.5e-3
    # the lineage's count is this one's at n_h = 1 but for LayerNorm's
    # parameters being BatchNorm's and the optimizer's 16 bytes
    one = dict(model, n_h=1)
    assert counts_ocp.step_counts(n, e, g, one, 100, 92)["flops"] \
        == pytest.approx(counts.step_counts(n, e, g, one, 100, 92,
                                            train=True)["flops"])


@pytest.mark.parametrize("seed", [3_000_000_019, 35])
def test_a_whole_run_agrees_with_the_reference(seed, capsys):
    result, code = run.run_cell(TINY, "tiny.ocp", seed, 1.0, False,
                                require_tpu=False)
    assert code == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_rate", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["compared"]) == {
        "loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "grad_diff_median_leaf", "grad_norm_worst_leaf",
        "delta_norm_median_leaf"}
    assert "conv: rows of 32 lanes, 8 slots a node, 9 Gaussians" \
        in capsys.readouterr().out
    json.dumps(result, allow_nan=False)


def test_the_window_is_kind_train_s_whole_epochs(capsys):
    """Nothing of the window is this kind's own: kind ``train``'s window,
    clock and schedule rng (the batches' order from the seed, the chunk
    lengths from ``pack_seed``) serve as they are, so a window is whole
    epochs and every structure of an epoch is counted once, whatever the
    seed."""
    from benchmark.kinds import train

    for name in ("window", "_schedule_rng", "_epoch", "_drain", "__init__"):
        assert getattr(ocp_train.Driver, name) is getattr(train.Driver, name)
    result, code = run.run_cell(TINY, "tiny.ocp", 11, 0.4, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is True
    out = capsys.readouterr().out
    line = [ln for ln in out.splitlines() if ln.startswith("window:")][0]
    epochs, steps, structures = (
        int(line.split(f" {word}")[0].split()[-1])
        for word in ("epochs", "steps", "structures"))
    # 24 slabs x 2 copies, batches of 4 (snug): 12 steps an epoch
    assert epochs >= 1 and result["attempted"] == epochs
    assert (steps, structures) == (12 * epochs, 48 * epochs)
    assert len(result["evidence"]["epoch_s"]) == epochs


@pytest.mark.parametrize("key, value, says", [
    (("data", "a0"), 3.9, "a0"), (("model", "edge_norm"), "layer", "bn1")])
def test_a_key_the_loaders_cannot_follow_is_refused(monkeypatch, key, value,
                                                    says):
    """``system.load_pool`` hands the loader no keyword, so the slabs'
    spacing is the loader's default: a configuration that states another is
    refused before a pool is built; so is an edge normalisation other than
    the BatchNorm the conv has."""
    from benchmark import system

    loaded = []
    monkeypatch.setattr(system, "load_pool",
                        lambda cfg: loaded.append(cfg) or ([], {}))
    cell = run.Cell(TINY, "tiny.ocp")
    assert cell.config["data"]["a0"] == 3.0
    cell.config[key[0]][key[1]] = value
    driver = ocp_train.Driver(run.Context(cell, 7, False))
    with pytest.raises(ValueError, match=says):
        driver.setup()
    assert not loaded


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from cgnn_tpu.train import step

    real = step.make_train_step

    def broken(*a, **kw):
        body = real(*a, **kw)

        def train_step(state, batch):
            _new, metrics = body(state, batch)
            return state, metrics

        return train_step

    monkeypatch.setattr(step, "make_train_step", broken)
    result, code = run.run_cell(TINY, "tiny.ocp", 5, 0.5, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False
    row = result["compared"]["delta_norm_median_leaf"]
    assert row["value"] > 0.5 > row["limit"]


def test_a_layernorm_left_out_is_not_correct(monkeypatch, capsys):
    """The sum goes on to the residual as it is: the first loss is already
    another, and LayerNorm's own parameters get no gradient."""
    from cgnn_tpu.models import cgcnn

    class Nothing:
        def __init__(self, **_kw):
            pass

        def __call__(self, x, mask=None):
            return x

    monkeypatch.setattr(cgcnn, "MaskedLayerNorm", Nothing)
    result, code = run.run_cell(TINY, "tiny.ocp", 5, 0.5, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False
    out = capsys.readouterr().out
    for row in ("loss_step1_rel", "grad_diff_median_leaf"):
        assert [ln for ln in out.splitlines()
                if ln.startswith(f"compare {row}")][0].endswith("FAIL")


def test_the_l2_loss_in_the_l1_s_place_is_not_correct(monkeypatch):
    from cgnn_tpu.train import step

    monkeypatch.setattr(step, "l1_regression_loss", step.regression_loss)
    result, code = run.run_cell(TINY, "tiny.ocp", 5, 0.5, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False


@pytest.fixture(scope="module")
def set_up():
    """The kind's driver at the tiny size, set up under a traced context."""
    ctx = run.Context(run.Cell(TINY, "tiny.ocp"), 7, True)
    driver = ocp_train.Driver(ctx)
    driver.setup()
    yield ctx, driver
    ctx.telemetry.close()


# control -> rows it has to fail (float32 against float32 at this size, so
# every limit is the arithmetic's order, 1e-3)
CONTROL_FAILS = {
    "float8": {"grad_diff_median_leaf"},
    "half_batch": {"loss_step1_rel", "grad_diff_median_leaf"},
    "raw_targets": {"loss_step1_rel", "loss_step2_rel", "loss_step3_rel"},
}


def test_every_control_has_its_rows():
    assert set(ocp_train.CONTROLS) == set(CONTROL_FAILS)


@pytest.mark.parametrize("name", sorted(CONTROL_FAILS))
def test_a_control_is_not_correct(set_up, name):
    _ctx, driver = set_up
    sound = {r["name"]: r for r in driver.check()}
    assert all(r["value"] <= r["limit"] for r in sound.values())
    control = {r["name"]: r
               for r in driver.check(**ocp_train.CONTROLS[name])}
    print({n: r["value"] for n, r in control.items()})
    assert CONTROL_FAILS[name] <= {
        n for n, r in control.items() if r["value"] > r["limit"]}
    if name == "float8":
        assert control["grad_diff_median_leaf"]["value"] \
            > 100 * sound["grad_diff_median_leaf"]["value"]


def test_the_conv_s_sizes_and_the_overflow_tier_are_counted(set_up):
    """What the configuration changes in the conv, beside the staging
    counters: the gathered row's lanes (2F), the slots a node, the
    Gaussians, and how far the transpose's overflow tier is engaged."""
    from cgnn_tpu.train import loop

    ctx, driver = set_up
    seen = ctx.obs["counts"]
    assert (seen["conv_row_lanes"], seen["dense_m"],
            seen["edge_gaussians"]) == (32, 8, 9)
    assert 0 < seen["transpose_overflow_rows"] \
        <= seen["transpose_overflow_cap"]
    assert seen["transpose_overflow_max_run"] >= 1
    # the program's own counters say the same of what the driver staged
    staged = ctx.telemetry.counters()
    copies = int(ctx.config["data"]["resident_copies"])
    assert staged["transpose_overflow_rows"] \
        == copies * seen["transpose_overflow_rows"]
    assert loop.conv_shape_gauges(driver.params0, 8) == {
        "conv_row_lanes": 32, "dense_m": 8, "edge_gaussians": 9}
    # the lineage's conv at its own size, and a tree without a conv
    assert loop.conv_shape_gauges(
        {"conv_0": {"fc_full": {"kernel": np.zeros((169, 128))}}}, 12) == {
        "conv_row_lanes": 128, "dense_m": 12, "edge_gaussians": 41}
    assert loop.conv_shape_gauges({"embedding": {}}, None) == {}


def test_a_program_without_the_model_fails_before_the_pool(monkeypatch):
    """On the parent of the PR that added the kind ``ModelConfig`` has no
    ``node_norm``: set-up raises at once, before a pool is featurized."""
    from benchmark import system
    from cgnn_tpu import config

    class Parent:
        def __init__(self, atom_fea_len=64, n_conv=3, h_fea_len=128, n_h=1,
                     num_targets=1, dtype="float32", dense_m=0):
            pass

    loaded = []
    monkeypatch.setattr(config, "ModelConfig", Parent)
    monkeypatch.setattr(system, "load_pool",
                        lambda cfg: loaded.append(cfg) or ([], {}))
    driver = ocp_train.Driver(run.Context(run.Cell(TINY, "tiny.ocp"), 7,
                                          False))
    with pytest.raises(TypeError, match="node_norm"):
        driver.setup()
    assert not loaded


def test_the_new_phase_metric_reads_its_phase_in_both_directions():
    """``ms_per`` sums ``conv.ln`` over fwd and bwd; without the program's
    tables it reports nothing and does not raise."""
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           NEW_METRIC + ".json")) as f:
        spec = json.load(f)
    assert spec["phases"] == ["conv.ln"]
    by_phase = {("conv.ln", "fwd"): 1e6, ("conv.ln", "bwd"): 2e6,
                ("conv.bn2", "fwd"): 4e6, ("conv.bn1", "bwd"): 8e6}
    obs = {"counts": {"traced_steps": 3},
           "phase_trace": {"n_ops": 9, "has_tables": True,
                           "busy_ns": 15e6, "by_phase": by_phase}}
    assert phase.read(spec, obs) == pytest.approx(1.0)
    obs["phase_trace"]["has_tables"] = False
    assert phase.read(spec, obs) is None
    assert phase.read(spec, {"counts": {}, "phase_trace": None}) is None
    from cgnn_tpu.observe import phases

    assert phases.CONV_LN == "conv.ln" and phases.CONV_LN in phases.PHASES
