"""Kind ``nemotron_train`` (cell ``nemotron.train``) without a chip: the
manifest's entries for it (found by name, in the manifest as committed and in
the rehearsals of ``manifest_cases.py``), the configuration against the
catalog's row, a whole run of the kind at a tiny size through
``run.run_cell``, what breaks ``correct``, what ``--seed`` changes and what
it does not, and the counts by hand. Nothing here reports a time or a device
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

from benchmark import counts, counts_nemotron, run  # noqa: E402
from benchmark.kinds import (  # noqa: E402
    bd_train, lfm2_train, lm_train, nemotron_train, train,
)
from benchmark.readers import count as count_reader  # noqa: E402
from benchmark.readers import phase_roofline  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny_nemotron.json")
CELL, CONFIG = "nemotron.train", "nemotron-3-nano-30b-a3b-ep16"
TINY_CELL = "tiny.nemotron-train"
NEW_METRICS = {"ssm_proj_ms.train", "ssm_conv_ms.train", "ssm_scan_ms.train",
               "ssm_gate_ms.train", "ssm_scan_roofline.train",
               "ssm_chunks_cut_pct.train"}
# what the cell shares with ``trinity.train`` through the same readers
SHARED_METRICS = {
    "attn_full_ms.train", "attn_full_tiles_live_pct.train",
    "attn_causal_roofline.train", "moe_shared_ms.train",
    "moe_compact_pct.train", "afmoe_attn_proj_ms.train",
    "afmoe_moe_route_ms.train", "afmoe_moe_expert_ms.train",
    "afmoe_lm_head_ms.train", "afmoe_rows_vs_balanced_pct.train",
    "afmoe_moe_expert_roofline.train"}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the source's config.json as the model-configs catalog has it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
# the widths no cut may touch
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "moe_intermediate_size", "moe_shared_expert_intermediate_size",
          "mamba_head_dim", "mamba_num_heads", "n_groups", "ssm_state_size",
          "conv_kernel", "chunk_size", "expand", "num_experts_per_tok",
          "num_attention_heads", "num_key_value_heads")
LIMITS = {"loss_rel", "grad_diff_median_leaf", "grad_norm_worst_leaf",
          "delta_norm_median_leaf", "bias_moved_share"}
ROWS = (LIMITS - {"loss_rel"}) | {"loss_step1_rel", "loss_step2_rel",
                                  "loss_step3_rel"}


def test_the_cell_and_its_configuration_as_the_manifest_has_them(
        manifest, manifest_path):
    cell = run.Cell(manifest_path, CELL)
    entry = by_name(manifest["workloads"])[CELL]
    assert cell.entry == entry and cell.chips == 1
    assert (entry["config"], entry["traffic"]) == (CONFIG, "nemotron-train")
    assert len(entry["why"]) <= 200 and "16x their share" in entry["why"]
    assert "4 packed sequences of 4,096" in entry["why"]
    assert "Mamba-2" in entry["why"] and "6.34 GB" in entry["why"]
    assert cell.traffic["kind"] == "nemotron_train"
    assert cell.traffic["chunk_steps"] == 2 and cell.traffic["who"]
    cfg = cell.config
    listed = by_name(manifest["configs"])[CONFIG]
    assert cfg["source"] == listed["source"] and len(listed["why"]) <= 200
    assert cfg["reduced"] == listed["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "dataset_size"]
    # every key of the catalog's row at the top level, under its own name;
    # the ones that differ are listed in ``reduced`` and none is a width
    differs = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert differs == {"num_hidden_layers", "hybrid_override_pattern",
                       "n_routed_experts", "vocab_size"}
    assert differs <= set(cfg["reduced"]) and not differs & set(WIDTHS)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in differs}
    # the floors of a model_config PR, and the share written out
    assert cfg["num_hidden_layers"] == 7
    assert cfg["hybrid_override_pattern"] == "EMEMEM*" == PATTERN[27:34]
    assert PATTERN[6:34] == "EMEMEM*" * 4  # the group that repeats
    assert cfg["n_routed_experts"] == 8 and cfg["vocab_size"] == 16384 \
        == 131072 // 8
    share = cfg["share"]
    assert (share["chips_a_layer"], share["pipeline_stages"]) == (16, 7)
    assert share["stage_layers"] == [6, 7, 7, 7, 7, 9, 9]
    assert sum(share["stage_layers"]) == 52 == len(PATTERN)
    assert share["layers_held"] == [27, 33]
    assert share["experts_held"] == [0, 8]
    assert share["num_experts_published"] == 128
    assert share["vocab_rows_held"] == [0, 16384]
    assert cfg["model"]["weights_seed"] == 42
    tr = cfg["train"]
    assert (tr["optim"], tr["lr"], tr["b1"], tr["b2"], tr["weight_decay"],
            tr["batch_size"]) == ("adamw", 1e-5, 0.9, 0.95, 0.1, 4)
    # trinity.train's and lfm2.train's positions a step, positions
    # resident, steps an epoch and optimizer; the sequences half as long
    # and twice as many (2 of 8,192 do not fit: ``data.sequence_why``)
    data = cfg["data"]
    assert (data["n"], data["sequence_length"]) == (32, 4096)
    assert data["documents"] == {"median": 4096, "sigma": 1.0, "min": 64,
                                 "max": 4096}
    assert "16.36 GB" in data["sequence_why"]
    assert "15.32 GB" in data["sequence_why"]
    for other in ("trinity-mini-ep16", "lfm2-24b-a2b-ep8"):
        theirs = run.load_json(os.path.join(
            ROOT, "benchmark", "configs", other + ".json"))
        for key in ("pool_seed", "pack_seed"):
            assert data[key] == theirs["data"][key], key
        assert data["n"] * data["sequence_length"] == theirs["data"]["n"] \
            * theirs["data"]["sequence_length"]
        assert tr["batch_size"] * data["sequence_length"] == theirs[
            "train"]["batch_size"] * theirs["data"]["sequence_length"]
        assert data["n"] // tr["batch_size"] == theirs["data"]["n"] \
            // theirs["train"]["batch_size"] == 8
        assert {**tr, "batch_size": 2} == {**theirs["train"],
                                           "optim_why": tr["optim_why"]}
    assert cell.traffic["chunk_steps"] == run.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "lm-train.json"))["chunk_steps"]
    assert cfg["init"] == {**cfg["init"], "std": 0.02, "published_layers": 52}
    assert cfg["precision"]["compute"] == "bfloat16"
    assert len(cfg["assumed"]) >= 12
    assert "no rotary embedding" in cfg["assumed"][0]
    limits = cfg["limits"]["nemotron_train"]
    # every row has its limit, the three losses one; none is left out
    assert set(limits) == set(cfg["limits_why"]) == LIMITS
    assert "limits_left_out" not in cfg
    assert 7.0e-5 < limits["loss_rel"] < 2.65e-4
    assert limits["bias_moved_share"] == 0.0
    assert all(len(why) > 40 for why in cfg["limits_why"].values())
    # the program's model, the reference's and the counts' read it alike
    mc = nemotron_train.model_config(cfg)
    assert (mc.n_experts, mc.experts_held, mc.num_experts_per_tok) \
        == (128, (0, 8), 6)
    assert mc.groups == ((("moe", "mamba"), 3), (("attention",), 1))
    assert mc.n_periods == 1
    assert (mc.head_dim, mc.layer_norm_epsilon, mc.chunk_size) \
        == (128, 1e-5, 128)
    assert mc.n_params() == counts_nemotron.n_params(
        nemotron_train.counts_model(cfg)) == 528_092_736
    ref_model = nemotron_train.reference_model(cfg)
    assert ref_model["experts_held"] == (0, 8)
    assert ref_model["rope_theta"] == 10000.0  # the fault's alone
    from cgnn_tpu.train import blockdiff

    assert mc == blockdiff.model_config("lm", CONFIG, bf16=False)[1]
    # the file is held to what the model is
    for key, other in (("use_conv_bias", False), ("mlp_hidden_act", "silu"),
                       ("n_shared_experts", 2), ("topk_group", 2),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="models/nemotron_h.py"):
            nemotron_train.model_config({**cfg, key: other})
    with pytest.raises(ValueError, match="n_routed_experts"):
        nemotron_train.model_config({**cfg, "n_routed_experts": 16})


def test_the_cell_s_metrics(manifest):
    """It reports train_rate, the generic training metrics every cell lists,
    the eleven it shares with ``trinity.train`` and its own six, which no
    other cell lists (membership only: a later cell may join any)."""
    metrics = by_name(manifest["per_layer"])
    mine = {n for n, m in metrics.items() if CELL in m.get("workloads", [])}
    sdar = {n for n, m in metrics.items()
            if "sdar.train" in m.get("workloads", [])}
    ocp = {n for n, m in metrics.items()
           if "ocp.train" in m.get("workloads", [])}
    assert (sdar & ocp) | SHARED_METRICS | NEW_METRICS <= mine
    assert len(sdar & ocp) >= 20
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
        assert "lfm2.train" not in metrics[name]["workloads"]
    # it has no dense MLP, no convolution layer, and is no sdar.train
    for name in ("dense_mlp_ms.train", "sconv_mix_ms.train",
                 "sconv_taps_cut_pct.train", "attn_proj_ms.train",
                 "attn_window_ms.train"):
        assert CELL not in metrics[name]["workloads"], name
    assert CELL in by_name(manifest["end_to_end"])["train_rate"]["workloads"]
    assert {n for n in mine if "roofline" in n} >= {
        "afmoe_moe_expert_roofline.train", "attn_causal_roofline.train",
        "ssm_scan_roofline.train", "step_roofline.train"}
    cell_metrics = {m["name"] for m in run.Cell(
        os.path.join(ROOT, "BENCHMARK.json"), CELL).per_layer()}
    assert mine <= cell_metrics


def test_counts_by_hand():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        model = nemotron_train.counts_model(json.load(f))
    h = 2688
    assert counts_nemotron.expert_params(model) == 2 * h * 1856 == 9_977_856
    assert counts_nemotron.moe_params(model) == (
        8 * 9_977_856 + 344_064 + 19_955_712 + h) == 100_125_312
    assert counts_nemotron.mamba_params(model) == (
        27_697_152 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 11_010_048 + h) \
        == 38_744_896
    assert counts_nemotron.attention_params(model) == (
        2 * 11_010_048 + 2 * 688_128 + h) == 23_399_040
    assert counts_nemotron.n_params(model) == (
        3 * 100_125_312 + 3 * 38_744_896 + 23_399_040 + 2 * 44_040_192
        + h) == 528_092_736
    positions, weighted, rows, pairs = 16384.0, 16000.0, 6000.0, 4.0e7
    got = counts_nemotron.step_counts(
        model, positions=positions, weighted=weighted, pairs=pairs, rows=rows)
    mamba_proj = 2 * h * 10304 + 2 * 4096 * h
    attn_proj = 2 * h * (4096 + 2 * 256) + 2 * 4096 * h
    attention = 3 * 4 * 128 * 32 * pairs
    experts = 3 * 3 * 2 * 9_977_856 * rows  # 1,856 lanes, not 1,920
    shared = 3 * 3 * positions * 2 * 2 * h * 3712
    router = 3 * 3 * positions * 2 * h * 128
    head = 3 * 2 * h * 16384 * weighted
    # a position's scan: 64.5 causal pairs inside its chunk of 128
    scan = 3 * 3 * positions * (2 * 8 * 128 * 64.5 + 2 * 64 * 64 * 64.5
                                + 4 * 64 * 64 * 128)
    assert got["flops"] == pytest.approx(
        3 * positions * (3 * mamba_proj + attn_proj) + scan + attention
        + experts + shared + router + head)
    assert counts_nemotron.attention_counts(
        model, positions, pairs)["flops"] == attention
    assert counts_nemotron.expert_counts(model, rows)["flops"] == experts / 3
    one_scan = counts_nemotron.ssm_scan_counts(model, positions)
    assert one_scan["flops"] == pytest.approx(scan / 3)
    # x, B, C, dt in and y out, three times over (forward, and the reverse
    # pass's reads and writes)
    assert one_scan["bytes"] == 3 * (2 * 4096 + 2 * 1024 + 64) * 2 * positions
    assert got["bytes"] > 24 * 528_092_736 + 3 * one_scan["bytes"]
    peaks = counts.peaks_for("TPU v5 lite")
    least, bound = counts.least_seconds(got, peaks)
    assert bound == "flops" and 0.05 < least < 0.3
    assert counts.least_seconds(one_scan, peaks) == (
        pytest.approx(one_scan["bytes"] / 819e9), "bytes")


@pytest.mark.parametrize("seed", [3_000_000_019])
def test_a_whole_run_agrees_with_the_reference(seed, capsys):
    result, code = run.run_cell(TINY, TINY_CELL, seed, 1.0, False,
                                require_tpu=False)
    assert code == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_rate", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["compared"]) == ROWS
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
        assert getattr(nemotron_train.Driver, name) is getattr(train.Driver,
                                                               name)
    for name in ("reseed", "raw_readings", "_compared_steps"):
        assert getattr(nemotron_train.Driver, name) is getattr(
            lm_train.Driver, name)
    # the rows are kinds/lfm2_train.py's, the losses among them: the kind
    # has no compare of its own that a configuration could take rows out of
    assert not hasattr(nemotron_train, "compare")
    assert lfm2_train.leaf_rows is bd_train.compare
    assert lm_train.COMPARED_BIAS == 0.1
    assert nemotron_train.WINDOW_BIAS == 0.01


@pytest.fixture(scope="module")
def set_up():
    """The kind's driver at the tiny size, set up under a traced context."""
    ctx = run.Context(run.Cell(TINY, TINY_CELL), 7, True)
    driver = nemotron_train.Driver(ctx)
    driver.setup()
    yield ctx, driver
    ctx.telemetry.close()


def test_every_control_has_its_rows():
    """Every fault of the reference and both lower precisions are a control
    or are named as what the cell's traffic and weights cannot decide: every
    row of ``correct`` comes from the warmed one-step program."""
    from benchmark.reference import nemotron_ref

    assert len(nemotron_ref.FAULTS) == 15
    both = {**nemotron_train.CONTROLS, **nemotron_train.UNDECIDED}
    assert set(both) == {"float8", "state_bfloat16", *nemotron_ref.FAULTS}
    assert not set(nemotron_train.CONTROLS) & set(nemotron_train.UNDECIDED)
    assert set(nemotron_train.UNDECIDED) == {
        "state_bfloat16", "state_crosses_documents",
        "conv_crosses_documents", "attention_rotated"}
    assert both["state_bfloat16"] == {"state_dtype": "bfloat16"}
    assert both["float8"] == {"control_mm": nemotron_ref.mm_fp8}
    assert all(both[f] == {"fault": f} for f in nemotron_ref.FAULTS)


# float32 against float32 at this size, so every limit is the arithmetic's
# order and a control fails the timed step's gradient
@pytest.mark.parametrize("name", sorted(nemotron_train.CONTROLS))
def test_a_control_is_not_correct(set_up, name):
    _ctx, driver = set_up
    sound = {r["name"]: r for r in driver.check()}
    assert all(r["value"] <= r["limit"] for r in sound.values())
    control = {r["name"]: r
               for r in driver.check(**nemotron_train.CONTROLS[name])}
    print({n: r["value"] for n, r in control.items()})
    assert {n for n, r in control.items() if r["value"] > r["limit"]} & {
        "grad_diff_median_leaf", "grad_norm_worst_leaf"}
    # the reference holds no biases to move
    assert control["bias_moved_share"]["value"] == 0.0
    # the sound reference was computed once for the seed
    assert driver.want is not None and driver.state is None


def test_the_undecided_show_where_documents_are_short(set_up):
    """What the cell's traffic and weights cannot decide is no control of
    the cell; at this fixture's documents (a start every few positions) and
    weights the timed step's gradient tells each of the three faults, so
    the step's ``segment_ids`` reach the ops and nothing rotates the
    attention's heads; the rounded state moves it by less than the limit
    (documents of a dozen tokens) and shows in the raw gap alone."""
    _ctx, driver = set_up
    sound = {r["name"]: r["value"] for r in driver.check()}
    for name, kw in nemotron_train.UNDECIDED.items():
        rows = {r["name"]: r for r in driver.check(**kw)}
        over = {n for n, r in rows.items() if r["value"] > r["limit"]}
        if name == "state_bfloat16":
            assert rows["grad_diff_median_leaf"]["value"] \
                > 3 * sound["grad_diff_median_leaf"]
        else:
            assert {"grad_diff_median_leaf",
                    "grad_norm_worst_leaf"} & over, (name, rows)


def test_the_seed_changes_the_compared_weights_and_the_order_alone():
    """Two seeds: other compared weights and biases (another first loss),
    the same counters (read off warm()'s epoch over the configuration's
    weights in pack order), the same launches, the same window weights and
    window biases (uniform in +-0.01)."""
    import jax

    seen = []
    for seed in (11, 2_500_000_001):
        ctx = run.Context(run.Cell(TINY, TINY_CELL), seed, False)
        driver = nemotron_train.Driver(ctx)
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
    for name in nemotron_train.EPOCH_TOTALS + nemotron_train.EPOCH_MEANS:
        assert a["counts"][name] > 0, name
    # 4 steps x 2 expert layers x 64 positions x 4 choices, a quarter held
    assert a["counts"]["moe_rows_all"] == 4 * 2 * 64 * 4
    assert a["counts"]["moe_rows_balanced"] == 4 * 2 * 64 * 4 * 4 / 16
    # 2 Mamba layers over 4 steps of 64 positions, in chunks of 8
    assert a["counts"]["ssm_positions"] == 4 * 2 * 64
    assert a["counts"]["ssm_chunks"] == 4 * 2 * 64 / 8
    assert 0 < a["counts"]["ssm_chunks_cut"] < a["counts"]["ssm_chunks"]
    assert a["counts"]["ssm_resets"] >= 4 * 2 * 2
    assert a["bias0"].shape == (1, 2, 16)
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
    result, code = run.run_cell(TINY, TINY_CELL, 5, 0.3, False,
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
    result, code = run.run_cell(TINY, TINY_CELL, 5, 0.3, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False
    failed = {n for n, r in result["compared"].items()
              if r["value"] > r["limit"]}
    assert failed == {"bias_moved_share"}
    assert result["compared"]["bias_moved_share"]["value"] > 0.9


def test_a_program_without_the_model_fails_at_once(monkeypatch):
    """On the parent of the PR that added the kind there is no
    ``cgnn_tpu.models.nemotron_h``: set-up raises before anything is
    built."""
    import builtins

    real = builtins.__import__

    def parent(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "cgnn_tpu.models" and "nemotron_h" in (fromlist or ()):
            raise ImportError("cannot import name 'nemotron_h'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", parent)
    driver = nemotron_train.Driver(run.Context(
        run.Cell(TINY, TINY_CELL), 7, False))
    with pytest.raises(ImportError, match="nemotron_h"):
        driver.setup()


def test_the_counters_and_the_readers(set_up):
    ctx, driver = set_up
    seen = ctx.obs["counts"]
    gauges = ctx.telemetry.gauges()
    for name in nemotron_train.EPOCH_TOTALS + nemotron_train.EPOCH_MEANS:
        assert gauges[name] == pytest.approx(seen[name])
    # one tile at this size: 1 attention layer; 4 heads, 4 steps of 2
    assert seen["attn_full_tiles_live"] == seen["attn_full_tiles_grid"] \
        == 4 * 1 * 4 * 2
    assert "attn_window_tiles_live" not in seen
    assert "sconv_positions" not in seen
    assert "expert_bias_abs_max" not in seen
    assert seen["real_nodes"] == seen["node_slots"] == 4 * 2 * 32
    assert 0 < seen["weighted_tokens"] < 4 * 2 * 32
    for name, want in (
            ("moe_compact_pct.train",
             100.0 * seen["moe_rows_capacity"] / seen["moe_rows_all"]),
            ("attn_full_tiles_live_pct.train", 100.0),
            ("afmoe_rows_vs_balanced_pct.train",
             100.0 * seen["moe_rows_here"] / seen["moe_rows_balanced"]),
            ("ssm_chunks_cut_pct.train",
             100.0 * seen["ssm_chunks_cut"] / seen["ssm_chunks"])):
        spec = run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert count_reader.read(spec, ctx.obs) == pytest.approx(want)
    # a program without the counters (the parent) reports nothing
    assert count_reader.read(spec, {"counts": {}}) is None
    spec = run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", "ssm_scan_roofline.train.json"))
    obs = {"counts": {"traced_steps": 4, spec["least"]: 0.002},
           "phase_trace": {"has_tables": True, "n_ops": 9, "busy_ns": 1,
                           "by_phase": {("ssm.scan", "fwd"): 8_000_000,
                                        ("ssm.scan", "bwd"): 24_000_000,
                                        ("ssm.proj", "fwd"): 5}}}
    assert phase_roofline.read(spec, obs) == pytest.approx(25.0)
    # a program without the phase (the parent) reports nothing
    obs["phase_trace"]["by_phase"] = {("attn.full", "fwd"): 5}
    assert phase_roofline.read(spec, obs) is None
