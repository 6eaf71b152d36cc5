"""Kind ``lfm2_train`` (cell ``lfm2.train``) without a chip: the manifest's
entries for it (found by name, in the manifest as committed and in the
rehearsals of ``manifest_cases.py``), the configuration against the catalog's
row, a whole run of the kind at a tiny size through ``run.run_cell``, what
breaks ``correct``, what ``--seed`` changes and what it does not, and the
counts by hand. Nothing here reports a time or a device metric.
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

from benchmark import counts, counts_lfm2, run  # noqa: E402
from benchmark.kinds import bd_train, lfm2_train, lm_train, train  # noqa: E402
from benchmark.readers import count as count_reader  # noqa: E402
from benchmark.readers import phase_roofline  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny_lfm2.json")
CELL, CONFIG = "lfm2.train", "lfm2-24b-a2b-ep8"
C, F = "conv", "full_attention"
NEW_METRICS = {"sconv_proj_ms.train", "sconv_mix_ms.train",
               "sconv_mix_roofline.train", "sconv_taps_cut_pct.train"}
# what the cell shares with ``trinity.train`` through the same readers
SHARED_METRICS = {
    "attn_full_ms.train", "attn_full_tiles_live_pct.train",
    "attn_causal_roofline.train", "dense_mlp_ms.train",
    "moe_compact_pct.train", "afmoe_attn_proj_ms.train",
    "afmoe_moe_route_ms.train", "afmoe_moe_expert_ms.train",
    "afmoe_lm_head_ms.train", "afmoe_rows_vs_balanced_pct.train",
    "afmoe_moe_expert_roofline.train"}
# the source's config.json as the model-configs catalog has it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": [C, C] + [F, C, C, C] * 9 + [F, C],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
# the widths no cut may touch
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok", "num_attention_heads",
          "num_key_value_heads", "conv_L_cache")
LIMITS = {"loss_rel", "grad_diff_median_leaf", "grad_norm_worst_leaf",
          "delta_norm_median_leaf", "bias_moved_share"}


def test_the_cell_and_its_configuration_as_the_manifest_has_them(
        manifest, manifest_path):
    cell = run.Cell(manifest_path, CELL)
    entry = by_name(manifest["workloads"])[CELL]
    assert cell.entry == entry and cell.chips == 1
    assert (entry["config"], entry["traffic"]) == (CONFIG, "lfm2-train")
    assert len(entry["why"]) <= 200 and "8x their share" in entry["why"]
    assert "convolution" in entry["why"] and "5.63 GB" in entry["why"]
    assert cell.traffic["kind"] == "lfm2_train"
    assert cell.traffic["chunk_steps"] == 2 and cell.traffic["who"]
    cfg = cell.config
    listed = by_name(manifest["configs"])[CONFIG]
    assert cfg["source"] == listed["source"] and len(listed["why"]) <= 200
    assert cfg["reduced"] == listed["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "layer_types", "dataset_size"]
    # every key of the catalog's row at the top level, under its own name;
    # the ones that differ are listed in ``reduced`` and none is a width
    differs = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert differs == {"num_hidden_layers", "num_dense_layers",
                       "num_experts", "vocab_size", "layer_types"}
    assert differs <= set(cfg["reduced"]) and not differs & set(WIDTHS)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in differs}
    # the floors of a model_config PR, and the share written out
    assert cfg["num_dense_layers"] == 1 and cfg["num_hidden_layers"] == 5
    assert cfg["layer_types"] == [C, F, C, C, C]  # a whole period, in ratio
    assert cfg["layer_types"][1:] == PUBLISHED["layer_types"][2:6]
    assert cfg["num_experts"] == 8 and cfg["vocab_size"] == 8192 \
        == 65536 // 8
    assert cfg["head_dim"] == 64 == cfg["hidden_size"] \
        // cfg["num_attention_heads"]
    share = cfg["share"]
    assert (share["chips_a_layer"], share["pipeline_stages"]) == (8, 8)
    assert share["experts_held"] == [0, 8]
    assert share["num_experts_published"] == 64
    assert share["vocab_rows_held"] == [0, 8192]
    assert cfg["model"]["weights_seed"] == 42
    tr = cfg["train"]
    assert (tr["optim"], tr["lr"], tr["b1"], tr["b2"], tr["weight_decay"],
            tr["batch_size"]) == ("adamw", 1e-5, 0.9, 0.95, 0.1, 2)
    # the same traffic as trinity.train's
    trinity = run.load_json(os.path.join(
        ROOT, "benchmark", "configs", "trinity-mini-ep16.json"))
    for key in ("n", "sequence_length", "pool_seed", "pack_seed",
                "documents"):
        assert cfg["data"][key] == trinity["data"][key], key
    assert tr == {**trinity["train"], "optim_why": tr["optim_why"]}
    assert cell.traffic["chunk_steps"] == run.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "lm-train.json"))["chunk_steps"]
    assert cfg["init"] == {**cfg["init"], "std": 0.02, "published_layers": 40}
    assert cfg["precision"]["compute"] == "bfloat16"
    assert len(cfg["assumed"]) >= 10
    assert any("head_dim 64" in a for a in cfg["assumed"])
    assert any("tied" in a for a in cfg["assumed"])
    limits = cfg["limits"]["lfm2_train"]
    assert set(limits) == set(cfg["limits_why"]) == LIMITS
    assert limits["bias_moved_share"] == 0.0
    assert all(len(why) > 40 for why in cfg["limits_why"].values())
    # the program's model, the reference's and the counts' read it alike
    mc = lfm2_train.model_config(cfg)
    assert (mc.n_experts, mc.experts_held, mc.num_experts_per_tok) \
        == (64, (0, 8), 4)
    assert mc.period == (F, C, C, C) and mc.n_periods == 1
    assert (mc.head_dim, mc.rope_theta, mc.norm_eps) == (64, 1e6, 1e-5)
    assert mc.n_params() == counts_lfm2.n_params(
        lfm2_train.counts_model(cfg)) == 469_284_992
    assert lfm2_train.reference_model(cfg)["experts_held"] == (0, 8)
    from cgnn_tpu.train import blockdiff

    assert mc == blockdiff.model_config("lm", CONFIG, bf16=False)[1]
    # the filter's length is the op's constant: the file's is held to it
    with pytest.raises(ValueError, match="3 taps"):
        lfm2_train.model_config({**cfg, "conv_L_cache": 4})


def test_the_cell_s_metrics(manifest):
    """It reports train_rate, the generic training metrics every cell lists,
    the eleven it shares with ``trinity.train`` and its own four, which no
    other cell lists (membership only: a later cell may join any)."""
    metrics = by_name(manifest["per_layer"])
    mine = {n for n, m in metrics.items() if CELL in m.get("workloads", [])}
    sdar = {n for n, m in metrics.items()
            if "sdar.train" in m.get("workloads", [])}
    ocp = {n for n, m in metrics.items()
           if "ocp.train" in m.get("workloads", [])}
    assert mine == (sdar & ocp) | SHARED_METRICS | NEW_METRICS
    assert len(sdar & ocp) == 20
    for name in NEW_METRICS | SHARED_METRICS:
        m = metrics[name]
        assert CELL in m["workloads"] and m["moves"] == "train_rate"
        assert m["layer"] == metrics["step_device_ms.train"]["layer"]
        spec = run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert spec["name"] == name and spec["layer"] == m["layer"]
        if name.endswith("_roofline.train"):
            assert (m["unit"], m["better"]) == ("%", "higher")
            assert spec["reader"] == "phase_roofline"
    for name in SHARED_METRICS:
        assert "trinity.train" in metrics[name]["workloads"]
    for name in NEW_METRICS:
        assert "trinity.train" not in metrics[name]["workloads"]
    assert CELL in by_name(manifest["end_to_end"])["train_rate"]["workloads"]
    assert sorted(n for n in mine if "roofline" in n) == [
        "afmoe_moe_expert_roofline.train", "attn_causal_roofline.train",
        "sconv_mix_roofline.train", "step_roofline.train"]
    cell_metrics = {m["name"] for m in run.Cell(
        os.path.join(ROOT, "BENCHMARK.json"), CELL).per_layer()}
    assert mine <= cell_metrics


def test_counts_by_hand():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        model = lfm2_train.counts_model(json.load(f))
    assert counts_lfm2.expert_params(model) == 9_437_184
    assert counts_lfm2.conv_params(model) == 16_783_360
    assert counts_lfm2.attention_params(model) == 10_485_888
    dense = 16_783_360 + 72_351_744 + 4096
    conv = 16_783_360 + 75_497_472 + 131_072 + 4096
    attn = 10_485_888 + 75_497_472 + 131_072 + 4096
    assert (dense, conv, attn) == (89_139_200, 92_416_000, 86_118_528)
    assert counts_lfm2.n_params(model) == (
        dense + 3 * conv + attn + 16_777_216 + 2048) == 469_284_992
    positions, weighted, rows, pairs = 16384.0, 16000.0, 9000.0, 4.0e7
    got = counts_lfm2.step_counts(model, positions=positions,
                                  weighted=weighted, pairs=pairs, rows=rows)
    conv_proj = 2 * 2048 * 6144 + 2 * 2048 * 2048
    attn_proj = 2 * 2048 * (2048 + 2 * 512) + 2 * 2048 * 2048
    attention = 3 * 4 * 64 * 32 * pairs  # 64 lanes a head, not 128
    experts = 4 * 3 * 2 * 9_437_184 * rows
    dense_mlp = 3 * positions * 2 * 3 * 2048 * 11776
    router = 4 * 3 * positions * 2 * 2048 * 64
    head = 3 * 2 * 2048 * 8192 * weighted
    assert got["flops"] == pytest.approx(
        3 * positions * (4 * conv_proj + attn_proj) + attention + experts
        + dense_mlp + router + head)
    assert counts_lfm2.attention_counts(
        model, positions, pairs)["flops"] == attention
    assert counts_lfm2.expert_counts(model, rows)["flops"] == experts / 4
    # the taps: 3H in and H out forward, 4H in and 3H out in reverse
    taps = counts_lfm2.sconv_mix_counts(model, positions)
    assert taps == {"flops": 0.0, "bytes": 11 * 2048 * 2 * positions}
    assert got["bytes"] > 24 * 469_284_992 + 4 * taps["bytes"]
    peaks = counts.peaks_for("TPU v5 lite")
    least, bound = counts.least_seconds(got, peaks)
    assert bound == "flops" and 0.03 < least < 0.3
    assert counts.least_seconds(taps, peaks) == (
        pytest.approx(taps["bytes"] / 819e9), "bytes")


@pytest.mark.parametrize("seed", [3_000_000_019])
def test_a_whole_run_agrees_with_the_reference(seed, capsys):
    result, code = run.run_cell(TINY, "tiny.lfm2-train", seed, 1.0, False,
                                require_tpu=False)
    assert code == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_rate", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["compared"]) == {
        "loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "grad_diff_median_leaf", "grad_norm_worst_leaf",
        "delta_norm_median_leaf", "bias_moved_share"}
    assert result["compared"]["bias_moved_share"] == {"value": 0.0,
                                                      "limit": 0.0}
    out = capsys.readouterr().out
    assert "pool: 8 sequences of 32 tokens" in out
    line = [ln for ln in out.splitlines() if ln.startswith("window:")][0]
    epochs, steps, sequences = (
        int(line.split(f" {word}")[0].split()[-1])
        for word in ("epochs", "steps", "structures"))
    assert (steps, sequences) == (4 * epochs, 8 * epochs)
    json.dumps(result, allow_nan=False)


def test_the_window_is_kind_train_s_and_the_steps_lm_train_s():
    for name in ("window", "_schedule_rng", "_epoch", "_drain", "__init__",
                 "_note_evidence"):
        assert getattr(lfm2_train.Driver, name) is getattr(train.Driver,
                                                           name)
    for name in ("reseed", "raw_readings", "_compared_steps"):
        assert getattr(lfm2_train.Driver, name) is getattr(lm_train.Driver,
                                                           name)
    assert lfm2_train.leaf_rows is bd_train.compare
    assert lm_train.COMPARED_BIAS == 0.1 and lfm2_train.WINDOW_BIAS == 0.01


@pytest.fixture(scope="module")
def set_up():
    """The kind's driver at the tiny size, set up under a traced context."""
    ctx = run.Context(run.Cell(TINY, "tiny.lfm2-train"), 7, True)
    driver = lfm2_train.Driver(ctx)
    driver.setup()
    yield ctx, driver
    ctx.telemetry.close()


# control -> rows it has to fail (float32 against float32 at this size, so
# every limit is the arithmetic's order)
CONTROL_FAILS = {name: {"grad_diff_median_leaf"}
                 for name in lfm2_train.CONTROLS}
# the third tap's weight is one leaf's column: the worst leaf's norm shows it
CONTROL_FAILS["conv_two_taps"] = {"grad_norm_worst_leaf"}


def test_every_control_has_its_rows():
    """Every fault of the reference is a control but the one no row over
    the timed steps can tell from bfloat16 at the cell's traffic (30
    document starts in 131,072 positions): every row of ``correct`` comes
    from the warmed one-step program."""
    from benchmark.reference import lfm2_ref

    assert lfm2_train.UNDECIDED == ("conv_crosses_documents",)
    assert set(lfm2_train.CONTROLS) == {"float8", *lfm2_ref.FAULTS} - set(
        lfm2_train.UNDECIDED)
    assert len(lfm2_ref.FAULTS) == 7


@pytest.mark.parametrize("name", sorted(CONTROL_FAILS))
def test_a_control_is_not_correct(set_up, name):
    _ctx, driver = set_up
    sound = {r["name"]: r for r in driver.check()}
    assert all(r["value"] <= r["limit"] for r in sound.values())
    control = {r["name"]: r
               for r in driver.check(**lfm2_train.CONTROLS[name])}
    print({n: r["value"] for n, r in control.items()})
    assert CONTROL_FAILS[name] <= {
        n for n, r in control.items() if r["value"] > r["limit"]}
    # the reference holds no biases to move
    assert control["bias_moved_share"]["value"] == 0.0
    # the sound reference was computed once for the seed
    assert driver.want is not None and driver.state is None


def test_the_undecided_fault_shows_where_documents_are_short(set_up):
    """``conv_crosses_documents`` is no control of the cell; at this
    fixture's documents (a start every few positions) the timed step's
    gradient tells it, so the step's ``segment_ids`` reach the op."""
    _ctx, driver = set_up
    (fault,) = lfm2_train.UNDECIDED
    rows = {r["name"]: r for r in driver.check(fault=fault)}
    over = {n for n, r in rows.items() if r["value"] > r["limit"]}
    assert {"grad_diff_median_leaf", "grad_norm_worst_leaf"} <= over, rows
    assert rows["grad_norm_worst_leaf"]["value"] > 10 * rows[
        "grad_norm_worst_leaf"]["limit"]


def test_the_seed_changes_the_compared_weights_and_the_order_alone():
    """Two seeds: other compared weights and biases (another first loss),
    the same counters (read off warm()'s epoch over the configuration's
    weights in pack order), the same launches, the same window weights and
    window biases (uniform in +-0.01)."""
    import jax

    seen = []
    for seed in (11, 2_500_000_001):
        ctx = run.Context(run.Cell(TINY, "tiny.lfm2-train"), seed, False)
        driver = lfm2_train.Driver(ctx)
        driver.setup()
        window_bias = np.asarray(jax.device_get(
            driver.state.batch_stats["router_bias"]))
        window_embed = np.asarray(jax.device_get(
            driver.state.params["embed"]))
        window = driver.window(0.3, None)
        seen.append({
            "counts": {k: v for k, v in ctx.obs["counts"].items()
                       if k != "window_steps"},  # this host's epochs
            "first_loss": driver.got["loss"][0],
            "chunks_an_epoch": (ctx.obs["evidence"]["chunks"]
                                / window["attempted"]),
            "programs": sorted(k[1] for k in driver.driver._train_scans),
            "params0": np.asarray(driver.params0["embed"]),
            "bias0": driver.bias0, "window_bias": window_bias,
            "window": window_embed,
            # the window's state after training: its biases as they were
            "bias_after": np.asarray(jax.device_get(
                driver.state.batch_stats["router_bias"])),
        })
    a, b = seen
    assert a["counts"] == b["counts"]
    for name in lfm2_train.EPOCH_TOTALS + lfm2_train.EPOCH_MEANS:
        assert a["counts"][name] > 0, name
    # 4 steps x 3 expert layers x 64 positions x 4 choices, a quarter held
    assert a["counts"]["moe_rows_all"] == 4 * 3 * 64 * 4
    assert a["counts"]["moe_rows_balanced"] == 4 * 3 * 64 * 4 * 4 / 16
    # 3 conv layers (the dense one too) over 4 steps of 64 positions
    assert a["counts"]["sconv_positions"] == 4 * 3 * 64
    assert 0 < a["counts"]["sconv_taps_cut"] < 2 * 4 * 3 * 64
    assert a["chunks_an_epoch"] == b["chunks_an_epoch"] == 2
    assert a["programs"] == b["programs"] == [1, 2, 4]
    assert a["first_loss"] != b["first_loss"]
    assert (a["params0"] != b["params0"]).any()
    assert (a["bias0"] != b["bias0"]).any()
    assert 0.05 < np.abs(a["bias0"]).max() <= 0.1
    assert (a["window"] == b["window"]).all()
    assert (a["window_bias"] == b["window_bias"]).all()
    assert 0.005 < np.abs(a["window_bias"]).max() <= 0.01
    assert (a["bias_after"] == a["window_bias"]).all()


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from cgnn_tpu.train import lm_step

    real = lm_step.make_lm_train_step

    def broken(*a, **kw):
        body = real(*a, **kw)

        def train_step(state, batch):
            new, metrics = body(state, batch)
            return new.replace(params=state.params), metrics

        return train_step

    monkeypatch.setattr(lm_step, "make_lm_train_step", broken)
    result, code = run.run_cell(TINY, "tiny.lfm2-train", 5, 0.3, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False
    row = result["compared"]["delta_norm_median_leaf"]
    assert row["value"] > 0.5 > row["limit"]
    assert result["compared"]["bias_moved_share"]["value"] == 0.0


def test_a_step_that_moves_the_biases_is_not_correct(monkeypatch):
    """The biases have to come back bit for bit: a step that nudges them,
    as models/afmoe.py's does, fails ``bias_moved_share`` alone."""
    from cgnn_tpu.train import lm_step

    real = lm_step.make_lm_train_step

    def broken(*a, **kw):
        body = real(*a, **kw)

        def train_step(state, batch):
            new, metrics = body(state, batch)
            stats = {"router_bias": new.batch_stats["router_bias"] + 1e-6}
            return new.replace(batch_stats=stats), metrics

        return train_step

    monkeypatch.setattr(lm_step, "make_lm_train_step", broken)
    result, code = run.run_cell(TINY, "tiny.lfm2-train", 5, 0.3, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False
    failed = {n for n, r in result["compared"].items()
              if r["value"] > r["limit"]}
    assert failed == {"bias_moved_share"}
    assert result["compared"]["bias_moved_share"]["value"] > 0.9


def test_a_program_without_the_model_fails_at_once(monkeypatch):
    """On the parent of the PR that added the kind there is no
    ``cgnn_tpu.models.lfm2``: set-up raises before anything is built."""
    import builtins

    real = builtins.__import__

    def parent(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "cgnn_tpu.models" and "lfm2" in (fromlist or ()):
            raise ImportError("cannot import name 'lfm2'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", parent)
    driver = lfm2_train.Driver(run.Context(
        run.Cell(TINY, "tiny.lfm2-train"), 7, False))
    with pytest.raises(ImportError, match="lfm2"):
        driver.setup()


def test_the_counters_and_the_readers(set_up):
    ctx, driver = set_up
    seen = ctx.obs["counts"]
    gauges = ctx.telemetry.gauges()
    for name in lfm2_train.EPOCH_TOTALS + lfm2_train.EPOCH_MEANS:
        assert gauges[name] == pytest.approx(seen[name])
    # one tile at this size: 1 attention layer; 4 heads, 4 steps of 2
    assert seen["attn_full_tiles_live"] == seen["attn_full_tiles_grid"] \
        == 4 * 1 * 4 * 2
    assert "attn_window_tiles_live" not in seen
    assert "expert_bias_abs_max" not in seen
    assert seen["real_nodes"] == seen["node_slots"] == 4 * 2 * 32
    assert 0 < seen["weighted_tokens"] < 4 * 2 * 32
    for name, want in (
            ("moe_compact_pct.train",
             100.0 * seen["moe_rows_capacity"] / seen["moe_rows_all"]),
            ("attn_full_tiles_live_pct.train", 100.0),
            ("afmoe_rows_vs_balanced_pct.train",
             100.0 * seen["moe_rows_here"] / seen["moe_rows_balanced"]),
            ("sconv_taps_cut_pct.train",
             100.0 * seen["sconv_taps_cut"] / seen["sconv_positions"])):
        spec = run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert count_reader.read(spec, ctx.obs) == pytest.approx(want)
    # a program without the counters (the parent) reports nothing
    assert count_reader.read(spec, {"counts": {}}) is None
    spec = run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", "sconv_mix_roofline.train.json"))
    obs = {"counts": {"traced_steps": 4, spec["least"]: 0.002},
           "phase_trace": {"has_tables": True, "n_ops": 9, "busy_ns": 1,
                           "by_phase": {("sconv.mix", "fwd"): 8_000_000,
                                        ("sconv.mix", "bwd"): 24_000_000,
                                        ("sconv.proj", "fwd"): 5}}}
    assert phase_roofline.read(spec, obs) == pytest.approx(25.0)
    # a program without the phase (the parent) reports nothing
    obs["phase_trace"]["by_phase"] = {("attn.full", "fwd"): 5}
    assert phase_roofline.read(spec, obs) is None
