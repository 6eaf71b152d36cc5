"""Multi-task head end-to-end (BASELINE config #3): masked multi-column CSV
-> CIF directory -> MultiTaskHead model -> per-task MAE metrics."""

import csv
import os

import numpy as np
import pytest

from cgnn_tpu.config import DataConfig, ModelConfig
from cgnn_tpu.data.cif import write_cif_file
from cgnn_tpu.data.dataset import FeaturizeConfig, load_cif_directory
from cgnn_tpu.data.graph import batch_iterator, capacities_for
from cgnn_tpu.data.synthetic import random_structure, synthetic_target


@pytest.fixture(scope="module")
def multitask_dir(tmp_path_factory):
    """24 CIFs + id_prop.csv with 3 target columns, ~25% cells empty."""
    root = tmp_path_factory.mktemp("mtdata")
    rng = np.random.default_rng(11)
    rows = []
    for i in range(24):
        s = random_structure(rng, 3, 9)
        cid = f"mt-{i:03d}"
        write_cif_file(s, os.path.join(root, cid + ".cif"), cid)
        # three correlated-but-distinct targets (fake E_f / gap / modulus)
        base = synthetic_target(s)
        t = [base, 2.0 * base + 0.5, -0.7 * base + float(s.num_atoms) / 10.0]
        cells = [f"{v:.6f}" if rng.uniform() > 0.25 else "" for v in t]
        # guarantee at least one label per row
        if all(c == "" for c in cells):
            cells[0] = f"{t[0]:.6f}"
        rows.append([cid] + cells)
    with open(os.path.join(root, "id_prop.csv"), "w", newline="") as f:
        csv.writer(f).writerows(rows)
    return str(root)


def test_masked_multicolumn_csv_loads(multitask_dir):
    graphs = load_cif_directory(
        multitask_dir, FeaturizeConfig(radius=6.0, max_num_nbr=10)
    )
    assert len(graphs) == 24
    for g in graphs:
        assert g.target.shape == (3,)
        assert g.target_mask.shape == (3,)
    masks = np.stack([g.target_mask for g in graphs])
    assert 0 < masks.mean() < 1  # some labels genuinely missing
    assert (masks.sum(axis=1) >= 1).all()


def test_multitask_head_trains_with_per_task_metrics(multitask_dir):
    import jax

    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.loop import evaluate, fit

    graphs = load_cif_directory(
        multitask_dir, FeaturizeConfig(radius=6.0, max_num_nbr=10)
    )
    train_g, val_g = graphs[:20], graphs[20:]
    cfg = ModelConfig(
        atom_fea_len=32, n_conv=2, h_fea_len=32, num_targets=3,
        multi_task_head=True,
    )
    model = cfg.build()
    # the head really is per-task stacks, not a shared fc_out
    nc, ec = capacities_for(graphs, 8)
    example = next(batch_iterator(train_g, 8, nc, ec))
    variables = model.init(jax.random.key(0), example)
    head_params = variables["params"].get("head", variables["params"])
    assert any("task2_out" in k for k in head_params)

    norm = Normalizer.fit(
        np.stack([g.target for g in train_g]),
        np.stack([g.target_mask for g in train_g]),
    )
    state = create_train_state(
        model, example, make_optimizer(optim="adam", lr=3e-3), norm,
        rng=jax.random.key(1),
    )
    state, res = fit(
        state, train_g, val_g, epochs=10, batch_size=8,
        node_cap=nc, edge_cap=ec, print_freq=0, log_fn=lambda *_: None,
    )
    m = evaluate(state, val_g, 8, nc, ec)
    for t in range(3):
        assert f"mae_task{t}" in m
        assert np.isfinite(m[f"mae_task{t}"])
    losses = [h["train"]["loss"] for h in res["history"]]
    assert losses[-1] < 0.7 * losses[0]


def test_multitask_meta_roundtrip():
    cfg = ModelConfig(num_targets=3, multi_task_head=True)
    back = ModelConfig.from_meta(cfg.to_meta())
    assert back.multi_task_head is True
    assert back.num_targets == 3


_LEGACY_METAS = {
    # as ModelConfig.to_meta() wrote them before the kernel switches went
    # (PR 29): every key present, 'aggregation' as a sentinel string
    "fused-epilogue": dict(dense_m=8, aggregation="__none__",
                           fused_epilogue="xla", cgconv_impl="",
                           cgconv_window=0),
    "whole-conv-kernel": dict(dense_m=8, aggregation="__none__",
                              fused_epilogue="", cgconv_impl="pallas",
                              cgconv_window=384),
    "aggregation-sort": dict(dense_m=0, aggregation="sort",
                             fused_epilogue="", cgconv_impl="",
                             cgconv_window=0),
    "all-off": dict(dense_m=8, aggregation="__none__", fused_epilogue="",
                    cgconv_impl="", cgconv_window=0),
}


@pytest.mark.parametrize("meta", sorted(_LEGACY_METAS))
def test_legacy_checkpoint_meta_builds_the_one_model(meta):
    """A checkpoint whose meta carries the removed kernel switches restores
    as the one model: ``from_meta`` drops the keys and ``build`` gives the
    parameter tree (paths, shapes, initial values) a config written today
    gives — the switches never changed the tree."""
    import jax

    from cgnn_tpu.data.dataset import load_synthetic

    kept = dict(atom_fea_len=16, n_conv=2, h_fea_len=24, n_h=1,
                num_targets=1, classification=0, num_classes=2, dropout=0.0,
                dtype="float32", multi_task_head=0)
    legacy = kept | _LEGACY_METAS[meta]
    cfg = ModelConfig.from_meta(legacy)
    today = ModelConfig(atom_fea_len=16, n_conv=2, h_fea_len=24,
                        dense_m=legacy["dense_m"])
    assert cfg == today
    assert not set(cfg.to_meta()) & (set(_LEGACY_METAS[meta]) - {"dense_m"})

    dense_m = cfg.dense_m or None
    graphs = load_synthetic(6, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                            seed=4, max_atoms=6)
    nc, ec = capacities_for(graphs, 6, dense_m=dense_m)
    batch = next(batch_iterator(graphs, 6, nc, ec, dense_m=dense_m))
    got = cfg.build().init(jax.random.key(0), batch)
    want = today.build().init(jax.random.key(0), batch)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the scopes every kernel fork declared, so its checkpoints restore
    assert set(got["params"]) == {"embedding", "conv_0", "conv_1",
                                  "conv_to_fc", "fc_out"}
    assert set(got["params"]["conv_0"]) == {"fc_full", "bn1", "bn2"}
    out = cfg.build().apply(got, batch)
    assert np.isfinite(np.asarray(out)).all()
