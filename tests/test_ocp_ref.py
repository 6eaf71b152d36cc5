"""The Open Catalyst CGCNN (``models/cgcnn.py`` with LayerNorm after the
neighbour sum, a hidden stack in the head, no softplus on the pooled vector;
the L1 loss; Adam) against its plain reference
(``benchmark/reference/ocp_ref.py``), at a small size on the CPU in float32:
outputs, loss, every leaf of the first gradient and the parameters after
three Adam steps, through the dense body (real padding, a real overflow
tier) and the COO body; the featurization against the source's
``GaussianSmearing``; LayerNorm's padded rows.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import system, weights_ocp  # noqa: E402
from benchmark.kinds.ocp_train import first_gradient  # noqa: E402
from benchmark.reference import ocp_ref as ref  # noqa: E402
from benchmark.reference.cgcnn_ref import ZERO_LEAF  # noqa: E402

SEED = 2**31 + 35
M, STEP = 8, 0.75
MODEL = {"atom_fea_len": 32, "n_conv": 3, "h_fea_len": 24, "n_h": 3,
         "num_targets": 1}
LR = 0.01


def _featurize_config():
    from cgnn_tpu.data.dataset import FeaturizeConfig

    return FeaturizeConfig(radius=6.0, max_num_nbr=M, dmin=0.0, step=STEP,
                           var=2.0 ** 0.5 * STEP)


@pytest.fixture(scope="module")
def graphs():
    from cgnn_tpu.data.dataset import load_synthetic_oc20_ocp

    return load_synthetic_oc20_ocp(12, _featurize_config(), seed=3)


def test_the_default_featurization_is_the_source_s_smearing():
    """6 A, 50 neighbours, exactly 100 Gaussians whose numbers are
    ``GaussianSmearing(0, 6, 100)``'s: mu = linspace(0, 6, 100) and
    exp(-0.5 (d - mu)^2 / D^2), which this repo's exp(-(d - mu)^2 / var^2)
    gives at var = sqrt(2) D; and the slabs are dense enough for the cap of
    50 to bind."""
    from cgnn_tpu.data.dataset import load_synthetic_oc20_ocp

    pool = load_synthetic_oc20_ocp(3)
    for g in pool:
        assert g.edge_fea.shape[1] == 100
        np.testing.assert_allclose(
            g.edge_fea, ref.gaussian_smearing(g.distances), atol=2e-5)
        assert g.distances.max() <= 6.0
    degree = np.concatenate([np.bincount(g.centers, minlength=g.num_nodes)
                             for g in pool])
    assert degree.max() == 50 and (degree == 50).mean() > 0.2
    assert 40 < degree.mean() < 50


def _program(graphs, body: str, out_bias=None):
    """The model, its seeded state and three packed batches of four slabs
    (with the slabs of each) for ``body`` 'dense' or 'coo'."""
    from cgnn_tpu.config import ModelConfig
    from cgnn_tpu.data.graph import batch_iterator, capacities_for, pack_graphs
    from cgnn_tpu.train import Normalizer, make_optimizer
    from cgnn_tpu.train.state import TrainState

    dense_m = M if body == "dense" else None
    model = ModelConfig(dense_m=dense_m or 0, node_norm="layer",
                        pool_softplus=False, **MODEL).build()
    params, stats = weights_ocp.make_weights(
        SEED, MODEL, graphs[0].atom_fea.shape[1], graphs[0].edge_fea.shape[1])
    if out_bias is not None:
        params["fc_out"]["bias"] = jnp.full_like(params["fc_out"]["bias"],
                                                 out_bias)
    t_mean, t_std = system.target_stats(graphs)
    node_cap, edge_cap = capacities_for(graphs, 4, dense_m=dense_m)
    members = []

    def pack(batch_graphs, *a, **kw):
        members.append(list(batch_graphs))
        return pack_graphs(batch_graphs, *a, **kw)

    batches = list(batch_iterator(graphs, 4, node_cap, edge_cap,
                                  dense_m=dense_m, pack_fn=pack))
    assert len(batches) == len(members) == 3
    tx = make_optimizer(optim="adam", lr=LR, lr_milestones=[10**9])
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats=stats,
        opt_state=tx.init(params),
        normalizer=Normalizer(mean=jnp.asarray([t_mean], jnp.float32),
                              std=jnp.asarray([t_std], jnp.float32)),
        rng=jax.random.key(0), apply_fn=model.apply, tx=tx)
    coo = [ref.coo_batch([system.graph_as_ref(g) for g in m])
           for m in members]
    return model, state, batches, coo, (t_mean, t_std)


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def _leaves(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(x)
            for path, x in flat}


@pytest.mark.parametrize("out_bias", [None, 0.0],
                         ids=["residuals-one-sign", "residuals-both-signs"])
@pytest.mark.parametrize("body", ["dense", "coo"])
def test_three_adam_steps_agree_with_the_reference(graphs, body, out_bias):
    """Outputs, loss, every leaf of the first gradient (LayerNorm's scale
    and bias and the hidden layers among them) and the parameters after
    three steps, the program's own train step against ``ocp_ref``. With the
    seeded output bias every first residual has one sign; with it at zero
    the L1 loss's sign(output - label) takes both."""
    from cgnn_tpu.train.step import l1_regression_loss, make_train_step

    model, state, batches, coo, (t_mean, t_std) = _program(
        graphs, body, out_bias)
    if body == "dense":
        from cgnn_tpu.data.graph import overflow_rows

        b0 = batches[0]
        assert np.asarray(b0.edges).ndim == 3 and overflow_rows(b0) > 0
        assert 0 < np.asarray(b0.node_mask).sum() < b0.node_capacity
    params0 = jax.tree_util.tree_map(np.array, state.params)
    stats0 = jax.tree_util.tree_map(np.array, state.batch_stats)
    want = ref.adam_steps(ref.as_jnp(params0), ref.as_jnp(stats0), coo,
                          jnp.float32(t_mean), jnp.float32(t_std), lr=LR)

    n_real = len(coo[0]["targets"])
    out, _ = model.apply({"params": state.params, "batch_stats": stats0},
                         batches[0], train=True, mutable=["batch_stats"])
    assert _rel(np.asarray(out)[:n_real], want["out"]) < 2e-5
    assert not np.asarray(out)[n_real:].any()
    signs = np.sign(want["out"][:, 0]
                    - (np.asarray(coo[0]["targets"])[:, 0] - t_mean) / t_std)
    assert (len(set(signs)) == 2) == (out_bias is not None)

    step = jax.jit(make_train_step(loss_fn=l1_regression_loss))
    losses = []
    for k, batch in enumerate(batches):
        state, sums = step(state, batch)
        losses.append(float(sums["loss_sum"]) / float(sums["count"]))
        if k == 0:
            grad = first_gradient(state.opt_state)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)

    got_g, want_g = _leaves(grad), _leaves(want["grad"])
    assert set(got_g) == set(want_g)
    assert {"conv_0/ln/scale", "conv_2/ln/bias", "fc_0/kernel",
            "fc_1/bias"} <= set(got_g)
    floor = np.median([np.linalg.norm(w) for w in want_g.values()])
    for name, w in want_g.items():
        if np.linalg.norm(w) < ZERO_LEAF * floor:
            continue  # fc_full's bias: BatchNorm removes it
        assert _rel(got_g[name], w) < 5e-4, name

    # Adam moves an entry by ~lr whatever its gradient's size, so where a
    # gradient is zero by construction the two sides step by the sign of
    # their own rounding: those leaves are left out, the rest compared by
    # the norm of the whole leaf's change
    got_p, want_p = _leaves(state.params), _leaves(want["params"])
    start = _leaves(params0)
    for name, w in want_p.items():
        if name.endswith("fc_full/bias"):
            continue
        assert _rel(got_p[name] - start[name], w - start[name]) < 2e-2, name
        assert np.linalg.norm(w - start[name]) > 0


def test_dense_and_coo_bodies_share_one_parameter_tree(graphs):
    dense = _program(graphs, "dense")[1]
    coo = _program(graphs, "coo")[1]
    assert (jax.tree_util.tree_map(np.shape, dense.params)
            == jax.tree_util.tree_map(np.shape, coo.params))
    assert set(dense.params["conv_0"]) == {"fc_full", "bn1", "ln"}
    assert set(dense.batch_stats["conv_0"]) == {"bn1"}


def test_layernorm_s_padded_rows_are_zeroed(graphs):
    """A row's moments are its own, so padding pollutes nothing; left
    unmasked a padded row would read LayerNorm's bias, and the conv's
    output on padding would be softplus(bias), not zero."""
    from cgnn_tpu.ops.norm import MaskedLayerNorm

    model, state, batches, _coo, _ = _program(graphs, "dense")
    batch = batches[0]
    _out, nodes = model.apply(
        {"params": state.params, "batch_stats": state.batch_stats}, batch,
        train=False, return_node_features=True)
    pad = np.asarray(batch.node_mask) == 0
    assert pad.any() and not np.asarray(nodes)[pad].any()
    assert np.asarray(nodes)[~pad].all()

    ln = MaskedLayerNorm()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(6, 16)),
                    jnp.float32).at[4:].set(0.0)
    mask = jnp.asarray([1, 1, 1, 1, 0, 0], jnp.float32)
    variables = {"params": {"scale": jnp.full((16,), 1.5),
                            "bias": jnp.full((16,), 0.25)}}
    masked = np.asarray(ln.apply(variables, x, mask=mask))
    leaky = np.asarray(ln.apply(variables, x))
    assert not masked[4:].any() and np.allclose(leaky[4:], 0.25)
    np.testing.assert_allclose(masked[:4], leaky[:4])
    want = np.asarray(ref._ln(x[:4], variables["params"]))
    np.testing.assert_allclose(masked[:4], want, rtol=1e-5, atol=1e-6)


def test_model_config_round_trips_the_new_fields():
    """Old checkpoints (no such keys) load as the lineage's model; the new
    fields survive ``to_meta``/``from_meta``, the Gaussians' width too."""
    from cgnn_tpu.config import DataConfig, ModelConfig

    old = ModelConfig.from_meta({"atom_fea_len": 64, "n_conv": 3})
    assert (old.node_norm, old.pool_softplus) == ("batch", True)
    new = ModelConfig(node_norm="layer", pool_softplus=False, n_h=4)
    assert ModelConfig.from_meta(new.to_meta()) == new
    assert DataConfig.from_meta({"radius": 8.0}).var is None
    data = DataConfig(radius=6.0, step=6 / 99, var=2 ** 0.5 * 6 / 99)
    again = DataConfig.from_meta(data.to_meta())
    assert again == data
    gdf = again.featurize_config().gdf()
    assert gdf.num_features == 100 and gdf.var == pytest.approx(data.var)
    assert DataConfig().featurize_config().gdf().var == pytest.approx(0.2)
