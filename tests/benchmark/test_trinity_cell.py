"""Kind ``lm_train`` (cell ``trinity.train``) without a chip: the manifest's
entries for it (found by name, in the manifest as committed and in the
rehearsals of ``manifest_cases.py``), the configuration against the source's
published keys, a whole run of the kind at a tiny size through
``run.run_cell``, what breaks ``correct``, what ``--seed`` changes and what it
does not, and the counts by hand. Nothing here reports a time or a device
metric.
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

from benchmark import counts, counts_afmoe, run  # noqa: E402
from benchmark.kinds import bd_train, lm_train, train  # noqa: E402
from benchmark.readers import count as count_reader  # noqa: E402
from benchmark.readers import phase_roofline  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny_trinity.json")
CELL, CONFIG = "trinity.train", "trinity-mini-ep16"
S, F = "sliding_attention", "full_attention"
NEW_METRICS = {
    "attn_window_ms.train", "attn_full_ms.train", "dense_mlp_ms.train",
    "moe_shared_ms.train", "attn_window_tiles_live_pct.train",
    "attn_full_tiles_live_pct.train", "moe_compact_pct.train",
    "attn_causal_roofline.train", "afmoe_attn_proj_ms.train",
    "afmoe_moe_route_ms.train", "afmoe_moe_expert_ms.train",
    "afmoe_lm_head_ms.train", "afmoe_rows_vs_balanced_pct.train",
    "afmoe_moe_expert_roofline.train"}
# the source's config.json as the model-configs catalog has it
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [S, S, S, F] * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
# the widths no cut may touch
WIDTHS = ("head_dim", "hidden_size", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok", "sliding_window")
LIMITS = {"loss_rel", "grad_diff_median_leaf", "grad_norm_worst_leaf",
          "delta_norm_median_leaf", "bias_diff_share"}


def test_the_cell_and_its_configuration_as_the_manifest_has_them(
        manifest, manifest_path):
    cell = run.Cell(manifest_path, CELL)
    entry = by_name(manifest["workloads"])[CELL]
    assert cell.entry == entry and cell.chips == 1
    assert (entry["config"], entry["traffic"]) == (CONFIG, "lm-train")
    assert len(entry["why"]) <= 200 and "16x its share" in entry["why"]
    assert cell.traffic["kind"] == "lm_train"
    assert cell.traffic["chunk_steps"] == 2 and cell.traffic["who"]
    cfg = cell.config
    listed = by_name(manifest["configs"])[CONFIG]
    assert cfg["source"] == listed["source"] and len(listed["why"]) <= 200
    assert cfg["reduced"] == listed["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "dataset_size", "layer_types"]
    # every key of the source at the top level, under its own name; the
    # ones that differ are listed in ``reduced`` and none is a width
    differs = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert differs == {"num_hidden_layers", "num_dense_layers",
                       "num_experts", "vocab_size", "layer_types"}
    assert differs <= set(cfg["reduced"]) and not differs & set(WIDTHS)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in differs}
    # the floors of a model_config PR, and the share written out
    assert cfg["num_dense_layers"] == 1 and cfg["num_hidden_layers"] == 5
    assert cfg["layer_types"] == [S, S, S, S, F]  # a whole period, in ratio
    assert cfg["num_experts"] == 8 and cfg["vocab_size"] == 25024 \
        == 200192 // 8
    share = cfg["share"]
    assert (share["chips_a_layer"], share["pipeline_stages"]) == (16, 4)
    assert share["experts_held"] == [0, 8]
    assert share["num_experts_published"] == 128
    assert share["vocab_rows_held"] == [0, 25024]
    assert cfg["model"]["weights_seed"] == 42
    tr = cfg["train"]
    assert (tr["optim"], tr["lr"], tr["b1"], tr["b2"], tr["weight_decay"],
            tr["batch_size"]) == ("adamw", 1e-5, 0.9, 0.95, 0.1, 2)
    data = cfg["data"]
    assert (data["sequence_length"], data["pool_seed"],
            data["pack_seed"]) == (8192, 0, 0)
    assert data["n"] == 16  # ISSUE 45's pool: an epoch of 8 steps
    assert (data["n"] // tr["batch_size"]) % cell.traffic["chunk_steps"] == 0
    assert data["documents"] == {"median": 4096, "sigma": 1.0, "min": 64,
                                 "max": 8192}
    assert cfg["init"] == {**cfg["init"], "std": 0.02, "published_layers": 32}
    assert cfg["precision"]["compute"] == "bfloat16"
    assert len(cfg["assumed"]) >= 10
    limits = cfg["limits"]["lm_train"]
    assert set(limits) == set(cfg["limits_why"]) == LIMITS
    # between the sound runs' largest and float8's smallest on the chip
    # (PERF.md section 2), not under 1, an unchanged state's
    assert 3 * 5.7e-5 < limits["delta_norm_median_leaf"] < 1.69e-3 / 3
    # no control reads reliably over the loss: held near the sound runs
    assert 3 * 6.0e-5 <= limits["loss_rel"] <= 4 * 6.0e-5
    assert limits["bias_diff_share"] < 0.5
    # the program's model, the reference's and the counts' read it alike
    mc = lm_train.model_config(cfg)
    assert (mc.n_experts, mc.experts_held, mc.num_experts_per_tok) \
        == (128, (0, 8), 8)
    assert mc.period == (S, S, S, F) and mc.n_periods == 1
    assert (mc.route_scale, mc.load_balance_coeff, mc.score_func) \
        == (2.826, 0.001, "sigmoid")
    assert mc.n_params() == counts_afmoe.n_params(lm_train.counts_model(cfg))
    assert lm_train.reference_model(cfg)["experts_held"] == (0, 8)


def test_the_cell_s_metrics(manifest):
    """It reports train_rate, the generic training metrics every cell lists
    (not the conv's, not ``sdar.train``'s own, whose lists
    ``test_sdar_cell.py`` pins), and its own fourteen, which no other cell
    lists; step_roofline.train stays the one whole-step share."""
    metrics = by_name(manifest["per_layer"])
    mine = {n for n, m in metrics.items() if CELL in m.get("workloads", [])}
    sdar = {n for n, m in metrics.items()
            if "sdar.train" in m.get("workloads", [])}
    ocp = {n for n, m in metrics.items()
           if "ocp.train" in m.get("workloads", [])}
    assert mine == (sdar & ocp) | NEW_METRICS
    assert len(sdar & ocp) == 20
    for name in NEW_METRICS:
        m = metrics[name]
        assert CELL in m["workloads"] and m["moves"] == "train_rate"
        assert m["layer"] == metrics["step_device_ms.train"]["layer"]
        spec = run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert spec["name"] == name and spec["layer"] == m["layer"]
        if name.endswith("_roofline.train"):
            assert (m["unit"], m["better"]) == ("%", "higher")
            assert spec["reader"] == "phase_roofline"
    assert CELL in by_name(manifest["end_to_end"])["train_rate"]["workloads"]
    assert sorted(n for n in mine if "roofline" in n) == [
        "afmoe_moe_expert_roofline.train", "attn_causal_roofline.train",
        "step_roofline.train"]
    cell_metrics = {m["name"] for m in run.Cell(
        os.path.join(ROOT, "BENCHMARK.json"), CELL).per_layer()}
    assert mine <= cell_metrics


def test_counts_by_hand():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        model = lm_train.counts_model(json.load(f))
    assert counts_afmoe.expert_params(model) == 6_291_456
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 4 * 2048
    assert counts_afmoe.attention_params(model) == attn == 27_271_424
    assert counts_afmoe.n_params(model) == (
        attn + 3 * 2048 * 6144 + 4 * (attn + 2048 * 128 + 9 * 6_291_456)
        + 2 * 25024 * 2048 + 2048) == 504_147_200
    positions, weighted, rows = 16384.0, 16000.0, 9000.0
    pairs = {S: 2.0e7, F: 4.0e7}
    got = counts_afmoe.step_counts(model, positions=positions,
                                   weighted=weighted, pairs=pairs, rows=rows)
    proj = 2 * 2048 * (2 * 4096 + 1024) + 2 * 4096 * 2048
    assert proj == 54_525_952
    attention = 3 * 4 * 128 * 32 * (4 * pairs[S] + pairs[F])
    experts = 4 * 3 * 2 * 6_291_456 * rows
    shared = 4 * 3 * positions * 2 * 6_291_456
    dense = 3 * positions * 2 * 3 * 2048 * 6144
    router = 4 * 3 * positions * 2 * 2048 * 128
    head = 3 * 2 * 2048 * 25024 * weighted
    assert got["flops"] == pytest.approx(
        5 * 3 * positions * proj + attention + experts + shared + dense
        + router + head)
    assert counts_afmoe.causal_attention_counts(
        model, positions, pairs)["flops"] == attention
    assert counts_afmoe.expert_counts(model, rows)["flops"] == experts / 4
    assert got["bytes"] > 24 * counts_afmoe.n_params(model)
    least, bound = counts.least_seconds(got, counts.peaks_for("TPU v5 lite"))
    assert bound == "flops" and 0.05 < least < 0.3


def test_visible_pairs_are_the_dense_masks_():
    from benchmark.reference import afmoe_ref as ref
    from cgnn_tpu.data import tokens

    pool = tokens.make_pool(5, 32, vocab_size=64, seed=2, doc_median=12,
                            doc_min=2, doc_max=32, kind="causal")
    for window in (0, 8, 1, 32, 100):
        dense = sum(int(np.asarray(ref.dense_mask(row, window)).sum())
                    for row in pool.segment_ids)
        assert counts_afmoe.visible_pairs(pool.segment_ids, window) == dense
    one = np.zeros((1, 8192), np.int32)
    assert counts_afmoe.visible_pairs(one) == 8192 * 8193 // 2
    assert counts_afmoe.visible_pairs(one, 2048) \
        == 2048 * 2049 // 2 + 6144 * 2048
    # a sequence one document fills: the full layer sees 2.3x the window's
    assert counts_afmoe.visible_pairs(one) / counts_afmoe.visible_pairs(
        one, 2048) == pytest.approx(2.29, abs=0.01)


@pytest.mark.parametrize("seed", [3_000_000_019])
def test_a_whole_run_agrees_with_the_reference(seed, capsys):
    result, code = run.run_cell(TINY, "tiny.trinity-train", seed, 1.0, False,
                                require_tpu=False)
    assert code == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_rate", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["compared"]) == {
        "loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "grad_diff_median_leaf", "grad_norm_worst_leaf",
        "delta_norm_median_leaf", "bias_diff_share"}
    assert result["compared"]["bias_diff_share"] == {"value": 0.0,
                                                     "limit": 0.0}
    out = capsys.readouterr().out
    assert "pool: 8 sequences of 32 tokens" in out
    line = [ln for ln in out.splitlines() if ln.startswith("window:")][0]
    epochs, steps, sequences = (
        int(line.split(f" {word}")[0].split()[-1])
        for word in ("epochs", "steps", "structures"))
    assert (steps, sequences) == (4 * epochs, 8 * epochs)
    json.dumps(result, allow_nan=False)


def test_the_window_is_kind_train_s_and_the_rows_bd_train_s():
    for name in ("window", "_schedule_rng", "_epoch", "_drain", "__init__",
                 "_note_evidence"):
        assert getattr(lm_train.Driver, name) is getattr(train.Driver, name)
    assert lm_train.leaf_rows is bd_train.compare
    assert lm_train.first_gradient is bd_train.first_gradient


@pytest.fixture(scope="module")
def set_up():
    """The kind's driver at the tiny size, set up under a traced context."""
    ctx = run.Context(run.Cell(TINY, "tiny.trinity-train"), 7, True)
    driver = lm_train.Driver(ctx)
    driver.setup()
    yield ctx, driver
    ctx.telemetry.close()


# control -> rows it has to fail (float32 against float32 at this size, so
# every limit is the arithmetic's order)
CONTROL_FAILS = {
    "float8": {"grad_diff_median_leaf"},
    "no_window": {"grad_diff_median_leaf"},
    "rope_everywhere": {"grad_diff_median_leaf"},
    "bias_unused": {"grad_diff_median_leaf", "bias_diff_share"},
    "softmax_scores": {"grad_diff_median_leaf", "bias_diff_share"},
    "no_shared": {"grad_diff_median_leaf"},
    "ungated": {"grad_diff_median_leaf"},
}


def test_every_control_has_its_rows():
    assert set(lm_train.CONTROLS) == set(CONTROL_FAILS)


@pytest.mark.parametrize("name", sorted(CONTROL_FAILS))
def test_a_control_is_not_correct(set_up, name):
    _ctx, driver = set_up
    sound = {r["name"]: r for r in driver.check()}
    assert all(r["value"] <= r["limit"] for r in sound.values())
    control = {r["name"]: r
               for r in driver.check(**lm_train.CONTROLS[name])}
    print({n: r["value"] for n, r in control.items()})
    assert CONTROL_FAILS[name] <= {
        n for n, r in control.items() if r["value"] > r["limit"]}
    # the sound reference was computed once for the seed
    assert driver.want is not None and driver.state is None


def test_the_seed_changes_the_compared_weights_and_the_order_alone():
    """Two seeds: other compared weights and biases (another first loss),
    the same counters (read off warm()'s epoch over the configuration's
    weights in pack order), the same launches, the same window weights, the
    window's biases at 0."""
    import jax

    seen = []
    for seed in (11, 2_500_000_001):
        ctx = run.Context(run.Cell(TINY, "tiny.trinity-train"), seed, False)
        driver = lm_train.Driver(ctx)
        driver.setup()
        window_bias = np.asarray(jax.device_get(
            driver.maker.make(42).batch_stats["router_bias"]))
        window = driver.window(0.3, None)
        seen.append({
            "counts": {k: v for k, v in ctx.obs["counts"].items()
                       if k != "window_steps"},  # this host's epochs
            "first_loss": driver.got["loss"][0],
            "chunks_an_epoch": (ctx.obs["evidence"]["chunks"]
                                / window["attempted"]),
            "programs": sorted(k[1] for k in driver.driver._train_scans),
            "params0": np.asarray(driver.params0["head"]),
            "bias0": driver.bias0, "window_bias": window_bias,
            "window": np.asarray(jax.device_get(
                driver.maker.make(42).params["head"])),
        })
    a, b = seen
    assert a["counts"] == b["counts"]
    for name in lm_train.EPOCH_TOTALS + lm_train.EPOCH_MEANS:
        assert a["counts"][name] > 0
    # 4 steps x 3 expert layers x 64 positions x 4 choices, a quarter held
    assert a["counts"]["moe_rows_all"] == 4 * 3 * 64 * 4
    assert a["counts"]["moe_rows_balanced"] == 4 * 3 * 64 * 4 * 4 / 16
    assert a["chunks_an_epoch"] == b["chunks_an_epoch"] == 2
    assert a["programs"] == b["programs"] == [1, 2, 4]
    assert a["first_loss"] != b["first_loss"]
    assert (a["params0"] != b["params0"]).any()
    assert (a["bias0"] != b["bias0"]).any()
    assert 0.05 < np.abs(a["bias0"]).max() <= 0.1
    assert (a["window"] == b["window"]).all()
    assert not a["window_bias"].any() and not b["window_bias"].any()


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from cgnn_tpu.train import lm_step

    real = lm_step.make_lm_train_step

    def broken(*a, **kw):
        body = real(*a, **kw)

        def train_step(state, batch):
            new, metrics = body(state, batch)
            return new.replace(params=state.params,
                               batch_stats=state.batch_stats), metrics

        return train_step

    monkeypatch.setattr(lm_step, "make_lm_train_step", broken)
    result, code = run.run_cell(TINY, "tiny.trinity-train", 5, 0.3, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False
    row = result["compared"]["delta_norm_median_leaf"]
    assert row["value"] > 0.5 > row["limit"]
    row = result["compared"]["bias_diff_share"]
    assert row["value"] > 0.5 > row["limit"]


def test_a_program_without_the_model_fails_at_once(monkeypatch):
    """On the parent of the PR that added the kind there is no
    ``cgnn_tpu.models.afmoe``: set-up raises before anything is built."""
    import builtins

    real = builtins.__import__

    def parent(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "cgnn_tpu.models" and "afmoe" in (fromlist or ()):
            raise ImportError("cannot import name 'afmoe'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", parent)
    driver = lm_train.Driver(run.Context(
        run.Cell(TINY, "tiny.trinity-train"), 7, False))
    with pytest.raises(ImportError, match="afmoe"):
        driver.setup()


def test_the_counters_and_the_readers(set_up):
    ctx, driver = set_up
    seen = ctx.obs["counts"]
    gauges = ctx.telemetry.gauges()
    for name in lm_train.EPOCH_TOTALS + lm_train.EPOCH_MEANS:
        assert gauges[name] == pytest.approx(seen[name])
    # one tile at this size: 3 window layers (the dense one too), 1 full;
    # 4 heads, 4 steps of 2 sequences
    assert seen["attn_window_tiles_live"] == seen["attn_window_tiles_grid"] \
        == 4 * 3 * 4 * 2
    assert seen["attn_full_tiles_live"] == 4 * 1 * 4 * 2
    assert seen["real_nodes"] == seen["node_slots"] == 4 * 2 * 32
    assert 0 < seen["weighted_tokens"] < 4 * 2 * 32
    # the window's biases start at 0 and a step moves them by 0.001
    assert 0 < seen["expert_bias_abs_max"] < 0.02
    for name, want in (
            ("moe_compact_pct.train",
             100.0 * seen["moe_rows_capacity"] / seen["moe_rows_all"]),
            ("attn_window_tiles_live_pct.train", 100.0),
            ("afmoe_rows_vs_balanced_pct.train",
             100.0 * seen["moe_rows_here"] / seen["moe_rows_balanced"])):
        spec = run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert count_reader.read(spec, ctx.obs) == pytest.approx(want)
    # a program without the counters (the parent) reports nothing
    assert count_reader.read(spec, {"counts": {}}) is None
    spec = run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", "attn_causal_roofline.train.json"))
    obs = {"counts": {"traced_steps": 4, spec["least"]: 0.002},
           "phase_trace": {"has_tables": True, "n_ops": 9, "busy_ns": 1,
                           "by_phase": {("attn.window", "fwd"): 8_000_000,
                                        ("attn.full", "bwd"): 24_000_000,
                                        ("attn.bd", "fwd"): 5}}}
    assert phase_roofline.read(spec, obs) == pytest.approx(25.0)
    obs["phase_trace"]["by_phase"] = {("attn.bd", "fwd"): 5}
    assert phase_roofline.read(spec, obs) is None
