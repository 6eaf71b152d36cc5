"""Kind ``dp_train`` (cell ``mp.train-dp4``) without a chip: the manifest's
entries for it (found by name, in the manifest as committed and in the
rehearsals of ``manifest_cases.py``), and a whole run of the kind at a tiny
size on four of the suite's virtual devices through ``run.run_cell``. Nothing
here reports a time or a device metric.
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
from benchmark.readers import phase  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny_dp.json")


def test_the_cell_and_its_configuration_as_the_manifest_has_them(
        manifest, manifest_path):
    cell = run.Cell(manifest_path, "mp.train-dp4")
    entry = by_name(manifest["workloads"])["mp.train-dp4"]
    assert cell.entry == entry and cell.chips == 4
    assert entry["config"] == "mp-flagship-dp4"
    assert cell.traffic["kind"] == "dp_train"
    cfg = cell.config
    assert cfg["source"] == by_name(manifest["configs"])[
        "mp-flagship-dp4"]["source"]
    assert len(cfg["source"]) <= 200 and cfg["reduced"] == ["dataset_size"]
    flagship = run.Cell(manifest_path, "mp.train").config
    # the flagship's model, featurisation, precision, layout and optimizer,
    # letter for letter; the same pool, hence the same cache file
    for key in ("builder", "model", "featurize", "precision", "layout"):
        assert cfg[key] == flagship[key], key
    for key in ("optim", "lr", "momentum", "buckets", "batch_size"):
        assert cfg["train"][key] == flagship["train"][key], key
    for key in ("generator", "n", "pool_seed", "pack_seed", "keep_geometry"):
        assert cfg["data"][key] == flagship["data"][key], key
    assert cfg["parallel"]["data"] == cell.chips == 4
    assert cfg["train"]["batch_size"] // cfg["parallel"]["data"] == 128
    # one chip holds what mp.train's one chip holds
    assert cfg["data"]["resident_copies"] == 4 * \
        flagship["data"]["resident_copies"]
    assert set(cfg["guarantees"]) == {"replicas", "gradient", "batchnorm",
                                      "drop_last", "metrics"}
    assert cfg["limits"]["dp_train"]["replica_param_max_abs_diff"] == 0
    assert cell.traffic["chunk_steps"] == run.Cell(
        manifest_path, "mp.train").traffic["chunk_steps"]


def test_the_cell_s_metrics(manifest, manifest_path):
    """It reports train_rate, every per-layer metric ``mp.train`` reports
    and its own one; every one of them resolves to a file; it is among the
    cells that ask for four chips, which number what the driver allows: a
    quarter of the cells, rounded down, and one always."""
    mine = {m["name"] for m in manifest["per_layer"]
            if "mp.train-dp4" in m.get("workloads", [])}
    flagship = {m["name"] for m in manifest["per_layer"]
                if "mp.train" in m.get("workloads", [])}
    assert mine == flagship | {"allreduce_ms.train"}
    assert by_name(manifest["per_layer"])["allreduce_ms.train"] == {
        "name": "allreduce_ms.train", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "mesh", "moves": "train_rate",
        "workloads": ["mp.train-dp4"]}
    cell = run.Cell(manifest_path, "mp.train-dp4")
    for m in cell.per_layer():
        spec = run.load_json(os.path.join(cell.layer_dir,
                                          m["name"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py")), m
    assert "mp.train-dp4" in by_name(manifest["end_to_end"])[
        "train_rate"]["workloads"]
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert "mp.train-dp4" in four
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


@pytest.mark.parametrize("seed,trace", [(3_000_000_019, False), (23, True)])
def test_a_whole_run_agrees_with_the_reference(seed, trace):
    result, code = run.run_cell(TINY, "tiny.train-dp4", seed, 1.0, trace,
                                require_tpu=False)
    assert code == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["count"] == 4
    if not trace:
        assert set(result["metrics"]) == {"train_rate", "setup_s"}
        assert all(v["value"] > 0 for v in result["metrics"].values())
    else:
        # what needs no device plane: the spans and the counts
        assert {"chunk_dispatch_ms.train", "pad_eff_pct.train",
                "warm_epoch_s.train", "warm_programs_s.train",
                "stage_s.train"} <= set(result["metrics"])
    json.dumps(result, allow_nan=False)


def test_a_traced_run_counts_the_deployment_and_names_the_allreduce():
    """The program's counters reach ``obs['counts']``, the staged bytes are
    the total over the chips, and warm-up's phase tables (what
    ``allreduce_ms.train`` reads a trace with) hold the ``dp.allreduce``
    phase for every train program of the mesh."""
    import jax

    from benchmark.kinds import dp_train
    from benchmark.reduce import phases as reduce_phases

    cell = run.Cell(TINY, "tiny.train-dp4")
    ctx = run.Context(cell, 7, True)
    driver = dp_train.Driver(ctx)
    driver.setup()
    counts = ctx.obs["counts"]
    assert counts["dp_replicas"] == 4
    assert counts["dp_global_batch"] == cell.config["train"]["batch_size"]
    assert counts["dp_dropped_batches"] >= 0
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        (driver.state.params, driver.state.batch_stats)))
    assert counts["allreduce_bytes_per_step"] == 4 * n_params
    staged = sum(x.nbytes for g in driver.driver._train_groups.values()
                 for x in jax.tree_util.tree_leaves(g))
    assert counts["staged_bytes"] == staged  # the global arrays: all chips
    tables = reduce_phases.tables_from_spans(
        list(ctx.telemetry.spans.events))
    train = {k: v for k, v in tables.items() if "scan_train" in k}
    assert len(train) >= 4
    for name, table in train.items():
        assert any(row[0] == "dp.allreduce" for row in table.values()), name
    # the reader sums that phase; a program without tables reads nothing
    spec = run.load_json(os.path.join(cell.layer_dir,
                                      "allreduce_ms.train.json"))
    obs = {"counts": {"traced_steps": 4},
           "phase_trace": {"n_ops": 9, "has_tables": True, "busy_ns": 8e6,
                           "by_phase": {("dp.allreduce", "fwd"): 2e6,
                                        ("conv.gather", "bwd"): 6e6}}}
    assert phase.read(spec, obs) == pytest.approx(0.5)
    obs["phase_trace"]["has_tables"] = False
    assert phase.read(spec, obs) is None
    ctx.telemetry.close()


def test_a_step_that_skips_the_gradient_s_all_reduce_is_not_correct(
        monkeypatch):
    """Each chip applying its own shard's gradient is what a lost
    collective looks like: the replicas drift apart (limit 0) and the
    gradient is no longer the mean."""
    from cgnn_tpu.parallel import data_parallel
    from cgnn_tpu.train import step

    real = step.make_train_step

    def broken(*a, **kw):
        return real(*a, **{**kw, "pmean_grads": False})

    monkeypatch.setattr(data_parallel, "make_train_step", broken)
    result, code = run.run_cell(TINY, "tiny.train-dp4", 5, 0.5, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False


def test_statistics_summed_where_they_are_averaged_are_not_correct(
        monkeypatch):
    """A ``psum`` in the place of the running statistics' ``pmean`` leaves
    every chip's copy identical (the replica row reads 0) and touches no
    loss and no gradient: only the row that holds the statistics to the
    'batchnorm' guarantee can see it."""
    import jax
    from jax import lax

    from benchmark.kinds import dp_train

    real = lax.pmean

    def pmean(tree, axis_name):
        names = {str(getattr(path[-1], "key", "")) for path, _ in
                 jax.tree_util.tree_flatten_with_path(tree)[0]}
        fn = lax.psum if names == {"mean", "var"} else real
        return fn(tree, axis_name)

    monkeypatch.setattr(lax, "pmean", pmean)
    cell = run.Cell(TINY, "tiny.train-dp4")
    driver = dp_train.Driver(run.Context(cell, 5, False))
    driver.setup()
    over = {r["name"] for r in driver.check() if not r["value"] <= r["limit"]}
    assert over == {"stats_rel_worst_leaf"}


@pytest.fixture(scope="module")
def readings():
    """``python3 -m benchmark.kinds.dp_train`` at the test size, once: what
    the limits' readings are taken with on the chip. -> (a line a seed, who
    failed which rows seed by seed)."""
    import contextlib
    import io

    from benchmark.kinds import dp_train

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert dp_train.main(["--manifest", TINY, "--workload",
                              "tiny.train-dp4", "--seeds",
                              "3000000019,11"]) == 0
    out = out.getvalue().splitlines()
    lines = [json.loads(ln) for ln in out if ln.startswith("{")]
    verdicts = {ln.split(":")[0]: json.loads(ln.split(": ", 2)[-1])
                for ln in out if "rows over their limits" in ln}
    return lines, verdicts


def test_the_readings_the_limits_are_set_from(readings):
    """One line a seed with the sound run's rows, every control's and the
    statistics leaf by leaf; a sound run is inside every limit."""
    from benchmark.kinds import dp_train

    lines, verdicts = readings
    assert [ln["seed"] for ln in lines] == [3000000019, 11]
    for ln in lines:
        assert set(dp_train.CONTROLS) | {"program"} <= set(ln)
        assert ln["program"]["replica_param_max_abs_diff"] == 0
        assert max(ln["stats_unaveraged_stats"].values()) \
            == pytest.approx(ln["stats_unaveraged"]["stats_rel_worst_leaf"],
                             rel=1e-2)
    assert verdicts["program"] == [[], []]


@pytest.mark.parametrize("control,must_fail", [
    ("float8", "grad_diff_median_leaf"),
    ("grad_unaveraged", "grad_diff_median_leaf"),
    ("stats_unaveraged", "stats_rel_worst_leaf"),
    ("sync_bn", "grad_diff_median_leaf"),
])
def test_each_control_in_the_program_s_place_is_not_correct(
        readings, control, must_fail):
    """Each of ``CONTROLS`` fails the row that reads it, on every seed.
    Rank 0's statistics kept for all (``stats_unaveraged``) fails the
    statistics' row and no other."""
    for over in readings[1][control]:
        assert must_fail in over
        if control == "stats_unaveraged":
            assert over == ["stats_rel_worst_leaf"]
