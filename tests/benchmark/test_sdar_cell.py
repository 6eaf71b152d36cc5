"""Kind ``bd_train`` (cell ``sdar.train``) without a chip: the manifest's
entries for it (found by name, in the manifest as committed and in the
rehearsals of ``manifest_cases.py``), the configuration against the source's
published keys, a whole run of the kind at a tiny size through
``run.run_cell``, what breaks ``correct``, what ``--seed`` changes and what it
does not, the counts by hand, and the new reader. Nothing here reports a time
or a device metric.
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

from benchmark import counts, counts_sdar, run  # noqa: E402
from benchmark.kinds import bd_train, train  # noqa: E402
from benchmark.readers import phase_roofline  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "manifest_tiny_sdar.json")
CELL, CONFIG = "sdar.train", "sdar-30b-a3b-ep8"
NEW_METRICS = {
    "attn_bd_ms.train", "attn_proj_ms.train", "moe_route_ms.train",
    "moe_expert_ms.train", "lm_head_ms.train",
    "moe_rows_vs_balanced_pct.train", "bd_tiles_live_pct.train",
    "attn_bd_roofline.train", "moe_expert_roofline.train"}
# the source's config.json as the model-configs catalog has it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
# the widths no cut may touch
WIDTHS = ("head_dim", "hidden_size", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok")


def test_the_cell_and_its_configuration_as_the_manifest_has_them(
        manifest, manifest_path):
    cell = run.Cell(manifest_path, CELL)
    entry = by_name(manifest["workloads"])[CELL]
    assert cell.entry == entry and cell.chips == 1
    assert (entry["config"], entry["traffic"]) == (CONFIG, "bd-train")
    assert len(entry["why"]) <= 200 and "8x its share" in entry["why"]
    assert cell.traffic["kind"] == "bd_train"
    assert cell.traffic["chunk_steps"] == 2 and cell.traffic["who"]
    cfg = cell.config
    listed = by_name(manifest["configs"])[CONFIG]
    assert cfg["source"] == listed["source"] and len(listed["why"]) <= 200
    assert cfg["reduced"] == listed["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "dataset_size"]
    # every key of the source at the top level, under its own name; the
    # ones that differ are listed in ``reduced`` and none is a width
    differs = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert differs <= set(cfg["reduced"]) and not differs & set(WIDTHS)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in differs}
    # the floors of a model_config PR, and the share written out
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] == 16
    assert cfg["vocab_size"] == 18992 == 151936 // 8
    share = cfg["share"]
    assert (share["chips_a_layer"], share["pipeline_stages"]) == (8, 8)
    assert share["experts_held"] == [0, 16]
    assert share["num_experts_published"] == 128
    assert share["vocab_rows_held"] == [0, 18992]
    assert cfg["model"]["weights_seed"] == 42
    assert cfg["diffusion"]["block_length"] == 4
    assert cfg["diffusion"]["mask_id"] == cfg["vocab_size"] - 1
    tr = cfg["train"]
    assert (tr["optim"], tr["lr"], tr["b1"], tr["b2"], tr["weight_decay"],
            tr["batch_size"]) == ("adamw", 1e-5, 0.9, 0.95, 0.1, 2)
    data = cfg["data"]
    assert (data["sequence_length"], data["pool_seed"],
            data["pack_seed"]) == (4096, 0, 0)
    assert data["n"] % tr["batch_size"] == 0
    assert (data["n"] // tr["batch_size"]) % cell.traffic["chunk_steps"] == 0
    assert data["documents"] == {"median": 2048, "sigma": 1.0, "min": 64,
                                 "max": 4096}
    assert cfg["init"] == {**cfg["init"], "std": 0.02, "published_layers": 48}
    assert cfg["precision"]["compute"] == "bfloat16"
    assert len(cfg["assumed"]) >= 6
    limits = cfg["limits"]["bd_train"]
    assert set(limits) == set(cfg["limits_why"]) == {
        "loss_rel", "grad_diff_median_leaf", "grad_norm_worst_leaf",
        "delta_norm_median_leaf"}
    assert limits["delta_norm_median_leaf"] < 1  # an unchanged state's
    # the program's model, the reference's and the counts' read it alike
    mc = bd_train.model_config(cfg)
    assert (mc.n_experts, mc.experts_held, mc.num_experts_per_tok) \
        == (128, (0, 16), 8)
    assert mc.n_params() == counts_sdar.n_params(bd_train.counts_model(cfg))
    assert bd_train.reference_model(cfg)["experts_held"] == (0, 16)


def test_the_cell_s_metrics(manifest):
    """It reports train_rate, the generic training metrics every cell lists
    (not the conv's), and its own nine, which no other cell lists; each list
    it joined has it at its end; step_roofline.train stays the one
    whole-step share."""
    metrics = by_name(manifest["per_layer"])
    mine = {n for n, m in metrics.items() if CELL in m.get("workloads", [])}
    ocp = {n for n, m in metrics.items()
           if "ocp.train" in m.get("workloads", [])}
    conv = {n for n in ocp if n.startswith("conv_")}
    assert conv == {"conv_bn_ms.train", "conv_edge_ms.train",
                    "conv_matmul_ms.train", "conv_ln_ms.train"}
    assert mine == (ocp - conv) | NEW_METRICS
    for name in NEW_METRICS:
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_rate"
        assert m["layer"] == metrics["step_device_ms.train"]["layer"]
        spec = run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert spec["name"] == name and spec["layer"] == m["layer"]
        if name.endswith("_roofline.train"):
            assert (m["unit"], m["better"]) == ("%", "higher")
            assert spec["reader"] == "phase_roofline"
    assert CELL in by_name(manifest["end_to_end"])["train_rate"]["workloads"]
    assert [n for n in mine if "roofline" in n and n not in NEW_METRICS] \
        == ["step_roofline.train"]
    cell_metrics = {m["name"] for m in run.Cell(
        os.path.join(ROOT, "BENCHMARK.json"), CELL).per_layer()}
    assert mine <= cell_metrics


def test_counts_by_hand():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        model = bd_train.counts_model(json.load(f))
    assert counts_sdar.expert_params(model) == 4_718_592
    layer = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128 + 2048
             + 2048 + 2048 * 128 + 16 * 4_718_592)
    assert counts_sdar.layer_params(model) == layer == 94_638_336
    assert counts_sdar.n_params(model) \
        == model["num_hidden_layers"] * layer + 2 * 18992 * 2048 + 2048
    # a position and layer forward: projections 37.7 MFLOP, router 0.5
    positions, pairs, rows = 16384.0, 2.0e7, 16000.0
    got = counts_sdar.step_counts(model, positions=positions,
                                  noised=positions / 2, pairs=pairs,
                                  rows=rows)
    proj = 2 * 2048 * (4096 + 1024) + 2 * 4096 * 2048
    assert proj == 37_748_736
    attn = 3 * 4 * 128 * 32 * pairs
    experts = 3 * 2 * 4_718_592 * rows
    head = 3 * 2 * 2048 * 18992 * positions / 2
    layers = model["num_hidden_layers"]
    assert got["flops"] == pytest.approx(
        layers * (3 * positions * (proj + 2 * 2048 * 128) + attn + experts)
        + head)
    assert counts_sdar.attention_counts(model, positions, pairs)["flops"] \
        == attn
    assert counts_sdar.expert_counts(model, rows)["flops"] == experts
    assert got["bytes"] > 24 * counts_sdar.n_params(model)
    least, bound = counts.least_seconds(got, counts.peaks_for("TPU v5 lite"))
    assert bound == "flops" and 0.02 < least < 0.2


def test_visible_pairs_are_the_dense_mask_s():
    from benchmark.reference import sdar_ref as ref
    from cgnn_tpu.data import tokens

    pool = tokens.make_pool(5, 32, vocab_size=64, block=4, seed=2,
                            doc_median=12, doc_min=4, doc_max=32)
    dense = sum(int(np.asarray(ref.dense_mask(32, 4, row)).sum())
                for row in pool.segment_ids)
    assert counts_sdar.visible_pairs(pool.segment_ids, 4) == dense
    # one document of the whole length: B^2 b (b + 1) with b = 8 blocks
    assert counts_sdar.visible_pairs(np.zeros((1, 32), np.int32), 4) \
        == 16 * 8 * 9


@pytest.mark.parametrize("seed", [3_000_000_019, 35])
def test_a_whole_run_agrees_with_the_reference(seed, capsys):
    result, code = run.run_cell(TINY, "tiny.sdar-train", seed, 1.0, False,
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
    out = capsys.readouterr().out
    assert "pool: 8 sequences of 32 tokens" in out
    line = [ln for ln in out.splitlines() if ln.startswith("window:")][0]
    epochs, steps, sequences = (
        int(line.split(f" {word}")[0].split()[-1])
        for word in ("epochs", "steps", "structures"))
    assert (steps, sequences) == (4 * epochs, 8 * epochs)
    json.dumps(result, allow_nan=False)


def test_the_window_is_kind_train_s():
    for name in ("window", "_schedule_rng", "_epoch", "_drain", "__init__",
                 "_note_evidence"):
        assert getattr(bd_train.Driver, name) is getattr(train.Driver, name)


@pytest.fixture(scope="module")
def set_up():
    """The kind's driver at the tiny size, set up under a traced context."""
    ctx = run.Context(run.Cell(TINY, "tiny.sdar-train"), 7, True)
    driver = bd_train.Driver(ctx)
    driver.setup()
    yield ctx, driver
    ctx.telemetry.close()


# control -> rows it has to fail (float32 against float32 at this size, so
# every limit is the arithmetic's order)
CONTROL_FAILS = {
    "float8": {"grad_diff_median_leaf"},
    "causal_mask": {"loss_step1_rel", "grad_diff_median_leaf"},
    "unweighted": {"loss_step1_rel", "grad_diff_median_leaf"},
    "dropped_rows": {"grad_diff_median_leaf"},
}


def test_every_control_has_its_rows():
    assert set(bd_train.CONTROLS) == set(CONTROL_FAILS)


@pytest.mark.parametrize("name", sorted(CONTROL_FAILS))
def test_a_control_is_not_correct(set_up, name):
    _ctx, driver = set_up
    sound = {r["name"]: r for r in driver.check()}
    assert all(r["value"] <= r["limit"] for r in sound.values())
    control = {r["name"]: r
               for r in driver.check(**bd_train.CONTROLS[name])}
    print({n: r["value"] for n, r in control.items()})
    assert CONTROL_FAILS[name] <= {
        n for n, r in control.items() if r["value"] > r["limit"]}
    # the sound reference was computed once for the seed
    assert driver.want is not None and driver.state is None


def test_the_seed_changes_the_compared_weights_and_the_order_alone():
    """Two seeds: other compared weights (another first loss), the same
    counters (read off warm()'s epoch over the configuration's weights in
    pack order), the same launches, the same window weights."""
    import jax

    seen = []
    for seed in (11, 2_500_000_001):
        ctx = run.Context(run.Cell(TINY, "tiny.sdar-train"), seed, False)
        driver = bd_train.Driver(ctx)
        driver.setup()
        window = driver.window(0.3, None)
        seen.append({
            "counts": {k: v for k, v in ctx.obs["counts"].items()
                       if k != "window_steps"},  # this host's epochs
            "first_loss": driver.got["loss"][0],
            "chunks_an_epoch": (ctx.obs["evidence"]["chunks"]
                                / window["attempted"]),
            "programs": sorted(k[1] for k in driver.driver._train_scans),
            "params0": np.asarray(driver.params0["head"]),
            "window": np.asarray(jax.device_get(
                driver.maker.make(42).params["head"])),
        })
    a, b = seen
    assert a["counts"] == b["counts"]
    for name in ("moe_rows_here", "moe_rows_balanced", "bd_tiles_live",
                 "bd_tiles_grid", "masked_tokens",
                 "expert_load_max_over_mean"):
        assert a["counts"][name] > 0
    assert a["counts"]["moe_rows_balanced"] == 2 * 4 * 128 * 4 * 4 / 16
    assert a["chunks_an_epoch"] == b["chunks_an_epoch"] == 2
    assert a["programs"] == b["programs"] == [1, 2, 4]
    assert a["first_loss"] != b["first_loss"]
    assert (a["params0"] != b["params0"]).any()
    assert (a["window"] == b["window"]).all()


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
    result, code = run.run_cell(TINY, "tiny.sdar-train", 5, 0.3, False,
                                require_tpu=False)
    assert code == 0 and result["correct"] is False
    row = result["compared"]["delta_norm_median_leaf"]
    assert row["value"] > 0.5 > row["limit"]


def test_a_program_without_the_model_fails_at_once(monkeypatch):
    """On the parent of the PR that added the kind there is no
    ``cgnn_tpu.models.sdar``: set-up raises before anything is built."""
    import builtins

    real = builtins.__import__

    def parent(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "cgnn_tpu.models" and "sdar" in (fromlist or ()):
            raise ImportError("cannot import name 'sdar'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", parent)
    driver = bd_train.Driver(run.Context(run.Cell(TINY, "tiny.sdar-train"),
                                         7, False))
    with pytest.raises(ImportError, match="sdar"):
        driver.setup()


def test_the_counters_and_the_new_reader(set_up):
    ctx, driver = set_up
    seen = ctx.obs["counts"]
    gauges = ctx.telemetry.gauges()
    for name in bd_train.EPOCH_TOTALS + ("expert_load_max_over_mean",):
        assert gauges[name] == pytest.approx(seen[name])
    assert seen["bd_tiles_live"] == seen["bd_tiles_grid"]  # one tile here
    assert seen["real_nodes"] == seen["node_slots"] == 4 * 2 * 64
    # the reader: least time over the phase's device time, nothing where
    # the program has no such phase or no tables
    spec = run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", "attn_bd_roofline.train.json"))
    obs = {"counts": {"traced_steps": 4, spec["least"]: 0.002},
           "phase_trace": {"has_tables": True, "n_ops": 9, "busy_ns": 1,
                           "by_phase": {("attn.bd", "fwd"): 8_000_000,
                                        ("attn.bd", "bwd"): 24_000_000,
                                        ("moe.expert", "fwd"): 5}}}
    assert phase_roofline.read(spec, obs) == pytest.approx(25.0)
    obs["phase_trace"]["by_phase"] = {("conv.gather", "fwd"): 5}
    assert phase_roofline.read(spec, obs) is None
    obs["phase_trace"]["has_tables"] = False
    assert phase_roofline.read(spec, obs) is None
    assert phase_roofline.read(spec, {"counts": {}, "trace": None}) is None
