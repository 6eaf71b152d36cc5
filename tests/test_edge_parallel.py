"""Edge-sharded graph parallelism (SP analog; SURVEY.md §5 long-context).

All tests run on the 8 virtual CPU devices from conftest. The bar is exact
agreement with the unsharded step — sharding is a layout change, not a
numerics change.
"""

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic
from cgnn_tpu.data.graph import batch_iterator, capacities_for
from cgnn_tpu.models import CrystalGraphConvNet
from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
from cgnn_tpu.train.step import make_train_step
from cgnn_tpu.parallel.data_parallel import (
    make_parallel_train_step,
    shard_leading_axis,
    stack_batches,
)
from cgnn_tpu.parallel.edge_parallel import (
    batch_specs,
    make_dp_edge_parallel_train_step,
    make_edge_parallel_eval_step,
    make_edge_parallel_train_step,
    pad_edges_divisible,
    prepare_dense_sharded,
    shard_batch,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)

def _setup(batch_size=16, n_graphs=16):
    graphs = load_synthetic(
        n_graphs, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=0
    )
    nc, ec = capacities_for(graphs, batch_size)
    batch = next(batch_iterator(graphs, batch_size, nc, ec))
    targets = np.stack([g.target for g in graphs])
    tx = make_optimizer(optim="sgd", lr=0.01, lr_milestones=[100])
    return graphs, batch, targets, tx


def _states(model_ref, model_gp, batch, targets, tx):
    """Two identically-initialized states (no shared buffers — donation on
    CPU aliases device_put, so shared leaves would be deleted)."""
    a = create_train_state(
        model_ref, batch, tx, Normalizer.fit(targets), rng=jax.random.key(0)
    )
    b = create_train_state(
        model_ref, batch, tx, Normalizer.fit(targets), rng=jax.random.key(0)
    ).replace(apply_fn=model_gp.apply)
    return a, b


def test_pad_edges_divisible_preserves_semantics():
    _, batch, _, _ = _setup()
    padded = pad_edges_divisible(batch, 8)
    assert padded.edge_capacity % 8 == 0
    e = batch.edge_capacity
    np.testing.assert_array_equal(padded.edges[:e], batch.edges)
    assert (np.asarray(padded.edge_mask[e:]) == 0).all()
    assert (np.asarray(padded.centers[e:]) == batch.node_capacity - 1).all()
    # sortedness invariant survives
    assert (np.diff(np.asarray(padded.centers)) >= 0).all()
def test_edge_parallel_train_step_matches_single_device():
    _, batch, targets, tx = _setup()
    batch = pad_edges_divisible(batch, 8)
    model_ref = CrystalGraphConvNet(atom_fea_len=32, n_conv=2, h_fea_len=32)
    model_gp = CrystalGraphConvNet(
        atom_fea_len=32, n_conv=2, h_fea_len=32, edge_axis_name="graph"
    )
    state_ref, state_gp = _states(model_ref, model_gp, batch, targets, tx)

    s1, m1 = jax.jit(make_train_step())(state_ref, batch)

    mesh = Mesh(np.array(jax.devices()), ("graph",))
    s2, m2 = make_edge_parallel_train_step(mesh)(
        state_gp, shard_batch(batch, mesh)
    )
    assert float(m1["loss_sum"]) == pytest.approx(float(m2["loss_sum"]), abs=1e-4)
    for a, b in zip(
        jtu.tree_leaves(jax.device_get(s1.params)),
        jtu.tree_leaves(jax.device_get(s2.params)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for a, b in zip(
        jtu.tree_leaves(jax.device_get(s1.batch_stats)),
        jtu.tree_leaves(jax.device_get(s2.batch_stats)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_edge_parallel_eval_matches_single_device():
    _, batch, targets, tx = _setup()
    batch = pad_edges_divisible(batch, 8)
    model_ref = CrystalGraphConvNet(atom_fea_len=32, n_conv=2, h_fea_len=32)
    model_gp = CrystalGraphConvNet(
        atom_fea_len=32, n_conv=2, h_fea_len=32, edge_axis_name="graph"
    )
    state_ref, state_gp = _states(model_ref, model_gp, batch, targets, tx)
    from cgnn_tpu.train.step import make_eval_step

    m1 = jax.jit(make_eval_step())(state_ref, batch)
    mesh = Mesh(np.array(jax.devices()), ("graph",))
    m2 = make_edge_parallel_eval_step(mesh)(state_gp, shard_batch(batch, mesh))
    assert float(m1["mae_sum"]) == pytest.approx(float(m2["mae_sum"]), rel=1e-5)
def test_fit_data_parallel_2d_mesh_matches_plain_dp():
    """Full fit loop through a ('data','graph') mesh == plain-DP fit:
    same seed -> same batch order -> identical training trajectory."""
    from cgnn_tpu.parallel.data_parallel import fit_data_parallel
    from cgnn_tpu.parallel.mesh import make_2d_mesh, make_mesh

    graphs = load_synthetic(
        48, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=0
    )
    train_g, val_g = graphs[:32], graphs[32:]
    targets = np.stack([g.target for g in train_g])
    nc, ec = capacities_for(train_g, 4)
    batch = next(batch_iterator(train_g, 4, nc, ec))
    tx = make_optimizer(optim="sgd", lr=0.01, lr_milestones=[100])
    model_ref = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16)
    model_gp = CrystalGraphConvNet(
        atom_fea_len=16, n_conv=2, h_fea_len=16, edge_axis_name="graph"
    )
    state_a, state_b = _states(model_ref, model_gp, batch, targets, tx)

    quiet = lambda *a, **k: None  # noqa: E731
    s1, r1 = fit_data_parallel(
        state_a, train_g, val_g, epochs=2, batch_size=4, node_cap=nc,
        edge_cap=ec, seed=7, mesh=make_mesh(4), log_fn=quiet,
    )
    s2, r2 = fit_data_parallel(
        state_b, train_g, val_g, epochs=2, batch_size=4, node_cap=nc,
        edge_cap=ec, seed=7, mesh=make_2d_mesh(2, data_shards=4),
        log_fn=quiet,
    )
    for e1, e2 in zip(r1["history"], r2["history"]):
        assert e1["train_loss"] == pytest.approx(e2["train_loss"], rel=1e-4)
        assert e1["val"]["mae"] == pytest.approx(e2["val"]["mae"], rel=1e-4)
    for a, b in zip(
        jtu.tree_leaves(jax.device_get(s1.params)),
        jtu.tree_leaves(jax.device_get(s2.params)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-4)


def _dense_setup(n_graphs=16, batch_size=16, n_shards=4):
    """Dense-layout batch with shard-divisible node capacity + two models."""
    graphs = load_synthetic(
        n_graphs, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=0
    )
    nc, ec = capacities_for(graphs, batch_size, dense_m=8,
                            node_multiple=8 * n_shards)
    batch = next(batch_iterator(graphs, batch_size, nc, ec, dense_m=8))
    targets = np.stack([g.target for g in graphs])
    tx = make_optimizer(optim="sgd", lr=0.01, lr_milestones=[100])
    model_ref = CrystalGraphConvNet(
        atom_fea_len=32, n_conv=2, h_fea_len=32, dense_m=8
    )
    model_gp = CrystalGraphConvNet(
        atom_fea_len=32, n_conv=2, h_fea_len=32, dense_m=8,
        edge_axis_name="graph",
    )
    return graphs, batch, targets, tx, model_ref, model_gp


def test_shard_transpose_slots_checks_node_cap_divisibility():
    """The raise fires at the REAL precondition (node_cap % n_shards) with
    a message that matches it — not only when the edge capacity happens to
    be indivisible too (ADVICE r5: node_cap=6, dense_m=8, n_shards=4 has
    e_cap=48 divisible by 4, yet strips would cut mid node-row and die
    later as an opaque shard_map error)."""
    from cgnn_tpu.data.graph import shard_transpose_slots

    node_cap, dense_m, n_shards = 6, 8, 4
    e_cap = node_cap * dense_m
    assert e_cap % n_shards == 0  # the case the old check let through
    neighbors = np.zeros(e_cap, np.int32)
    edge_real = np.zeros(e_cap, bool)
    with pytest.raises(ValueError, match="node_cap 6 not divisible"):
        shard_transpose_slots(neighbors, edge_real, node_cap, dense_m,
                              n_shards, over_cap=8)


def test_shard_transpose_mapping_is_complete():
    """Per-shard mappings pass the same completeness invariant as the flat
    mapping (invariants._check_transpose_mapping understands both), and a
    corrupted shard mapping fails it."""
    from cgnn_tpu.data import invariants

    _, batch, *_ = _dense_setup()
    prepped = prepare_dense_sharded(batch, 4, train=True)
    assert prepped.in_mask.ndim == 3 and prepped.in_mask.shape[0] == 4
    invariants.check_batch(prepped)  # raises on any broken invariant

    import dataclasses

    bad_slots = np.array(prepped.in_slots)
    first = tuple(np.argwhere(np.asarray(prepped.in_mask).reshape(
        4, -1) > 0)[0])
    bad_slots[first[0], first[1]] += 1  # duplicate/missing edge slot
    with pytest.raises(invariants.BatchInvariantError):
        invariants.check_batch(
            dataclasses.replace(prepped, in_slots=bad_slots))
def test_dense_sharded_train_step_matches_single_device():
    """The dense fast path composed with graph sharding: one training step
    on a 4-shard mesh == the unsharded dense step (params, stats, loss)."""
    _, batch, targets, tx, model_ref, model_gp = _dense_setup()
    state_ref, state_gp = _states(model_ref, model_gp, batch, targets, tx)

    s1, m1 = jax.jit(make_train_step())(state_ref, batch)

    mesh = Mesh(np.array(jax.devices()[:4]), ("graph",))
    prepped = prepare_dense_sharded(batch, 4, train=True)
    s2, m2 = make_edge_parallel_train_step(mesh, dense=True)(
        state_gp, shard_batch(prepped, mesh)
    )
    assert float(m1["loss_sum"]) == pytest.approx(
        float(m2["loss_sum"]), abs=1e-4)
    for a, b in zip(
        jtu.tree_leaves(jax.device_get(s1.params)),
        jtu.tree_leaves(jax.device_get(s2.params)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for a, b in zip(
        jtu.tree_leaves(jax.device_get(s1.batch_stats)),
        jtu.tree_leaves(jax.device_get(s2.batch_stats)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_dense_sharded_eval_matches_single_device():
    from cgnn_tpu.train.step import make_eval_step

    _, batch, targets, tx, model_ref, model_gp = _dense_setup()
    state_ref, state_gp = _states(model_ref, model_gp, batch, targets, tx)
    m1 = jax.jit(make_eval_step())(state_ref, batch)
    mesh = Mesh(np.array(jax.devices()[:4]), ("graph",))
    prepped = prepare_dense_sharded(batch, 4, train=False)
    assert prepped.in_slots is None  # eval batches carry no mapping
    m2 = make_edge_parallel_eval_step(mesh, dense=True)(
        state_gp, shard_batch(prepped, mesh)
    )
    assert float(m1["mae_sum"]) == pytest.approx(float(m2["mae_sum"]),
                                                 rel=1e-5)
def test_fit_dense_graph_sharded_matches_plain_dp():
    """Full fit through ('data','graph') with the DENSE layout == plain-DP
    dense fit: same capacities -> same batches -> identical trajectory.
    This is the VERDICT r4 #3 acceptance: the fast path composes with
    graph sharding instead of falling back to COO."""
    from cgnn_tpu.parallel.data_parallel import fit_data_parallel
    from cgnn_tpu.parallel.mesh import make_2d_mesh, make_mesh

    graphs = load_synthetic(
        96, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=0
    )
    train_g, val_g = graphs[:80], graphs[80:]
    targets = np.stack([g.target for g in train_g])
    tx = make_optimizer(optim="sgd", lr=0.02, lr_milestones=[100])
    nc, ec = capacities_for(train_g, 4, dense_m=8, snug=True,
                            node_multiple=16)
    batch = next(batch_iterator(train_g, 4, nc, ec, dense_m=8, snug=True))
    model_ref = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                    dense_m=8)
    model_gp = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                   dense_m=8, edge_axis_name="graph")
    state_a, state_b = _states(model_ref, model_gp, batch, targets, tx)

    quiet = lambda *a, **k: None  # noqa: E731
    s1, r1 = fit_data_parallel(
        state_a, train_g, val_g, epochs=3, batch_size=4, node_cap=nc,
        edge_cap=ec, seed=5, mesh=make_mesh(4), log_fn=quiet, snug=True,
        dense_m=8,
    )
    s2, r2 = fit_data_parallel(
        state_b, train_g, val_g, epochs=3, batch_size=4, node_cap=nc,
        edge_cap=ec, seed=5, mesh=make_2d_mesh(2, data_shards=4),
        log_fn=quiet, snug=True, dense_m=8,
    )
    for e1, e2 in zip(r1["history"], r2["history"]):
        assert e1["train_loss"] == pytest.approx(e2["train_loss"], rel=1e-4)
        assert e1["val"]["mae"] == pytest.approx(e2["val"]["mae"], rel=1e-4)
    for a, b in zip(
        jtu.tree_leaves(jax.device_get(s1.params)),
        jtu.tree_leaves(jax.device_get(s2.params)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_fit_dense_graph_sharded_buckets_snug_trains():
    """The FULL fast-path composition — dense + snug + 2 size-class buckets
    + DP x graph shards — trains with decreasing loss (capacities differ
    from plain DP by the strip rounding, so the bar is convergence, not
    trajectory identity)."""
    from cgnn_tpu.parallel.data_parallel import fit_data_parallel
    from cgnn_tpu.parallel.mesh import make_2d_mesh

    graphs = load_synthetic(
        96, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=0
    )
    train_g, val_g = graphs[:80], graphs[80:]
    targets = np.stack([g.target for g in train_g])
    tx = make_optimizer(optim="sgd", lr=0.05, lr_milestones=[100])
    nc, ec = capacities_for(train_g, 4, dense_m=8, snug=True,
                            node_multiple=16)
    batch = next(batch_iterator(train_g, 4, nc, ec, dense_m=8, snug=True))
    model_ref = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                    dense_m=8)
    model_gp = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                   dense_m=8, edge_axis_name="graph")
    state = create_train_state(
        model_ref, batch, tx, Normalizer.fit(targets), rng=jax.random.key(0)
    ).replace(apply_fn=model_gp.apply)

    quiet = lambda *a, **k: None  # noqa: E731
    _, result = fit_data_parallel(
        state, train_g, val_g, epochs=6, batch_size=4, node_cap=0,
        edge_cap=0, seed=5, mesh=make_2d_mesh(2, data_shards=4),
        log_fn=quiet, buckets=2, snug=True, dense_m=8,
    )
    h = result["history"]
    assert np.isfinite(h[-1]["train_loss"])
    assert h[-1]["train_loss"] < h[0]["train_loss"]


def test_fit_dense_graph_sharded_scan_matches_per_step():
    """ScanEpochDriver composes with graph sharding (r5): on the same
    ('data','graph') mesh, the scan path reproduces the per-step
    device-resident path exactly (single shape group, same seed)."""
    from cgnn_tpu.parallel.data_parallel import fit_data_parallel
    from cgnn_tpu.parallel.mesh import make_2d_mesh

    graphs = load_synthetic(
        96, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=0
    )
    train_g, val_g = graphs[:80], graphs[80:]
    targets = np.stack([g.target for g in train_g])
    tx = make_optimizer(optim="sgd", lr=0.02, lr_milestones=[100])
    nc, ec = capacities_for(train_g, 4, dense_m=8, snug=True,
                            node_multiple=16)
    batch = next(batch_iterator(train_g, 4, nc, ec, dense_m=8, snug=True))
    model_ref = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                    dense_m=8)
    model_gp = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                   dense_m=8, edge_axis_name="graph")

    def fresh():
        return create_train_state(
            model_ref, batch, tx, Normalizer.fit(targets),
            rng=jax.random.key(0),
        ).replace(apply_fn=model_gp.apply)

    quiet = lambda *a, **k: None  # noqa: E731
    mesh = make_2d_mesh(2, data_shards=4)
    _, r1 = fit_data_parallel(
        fresh(), train_g, val_g, epochs=2, batch_size=4, node_cap=nc,
        edge_cap=ec, seed=5, mesh=mesh, log_fn=quiet, snug=True,
        dense_m=8, device_resident=True,
    )
    _, r2 = fit_data_parallel(
        fresh(), train_g, val_g, epochs=2, batch_size=4, node_cap=nc,
        edge_cap=ec, seed=5, mesh=mesh, log_fn=quiet, snug=True,
        dense_m=8, scan_epochs=True,
    )
    for e1, e2 in zip(r1["history"], r2["history"]):
        assert e1["train_loss"] == pytest.approx(e2["train_loss"], rel=1e-5)
        assert e1["val"]["mae"] == pytest.approx(e2["val"]["mae"], rel=1e-5)


def test_fit_coo_graph_sharded_scan_matches_per_step():
    """The COO layout's graph-sharded runs also take the scan path now
    (train.py's device-resident scan default applies to --layout coo
    too): scan == per-step on the same 2-D mesh."""
    from cgnn_tpu.parallel.data_parallel import fit_data_parallel
    from cgnn_tpu.parallel.mesh import make_2d_mesh

    graphs = load_synthetic(
        64, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=0
    )
    train_g, val_g = graphs[:48], graphs[48:]
    targets = np.stack([g.target for g in train_g])
    tx = make_optimizer(optim="sgd", lr=0.02, lr_milestones=[100])
    nc, ec = capacities_for(train_g, 4)
    batch = next(batch_iterator(train_g, 4, nc, ec))
    model_ref = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16)
    model_gp = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                   edge_axis_name="graph")

    def fresh():
        return create_train_state(
            model_ref, batch, tx, Normalizer.fit(targets),
            rng=jax.random.key(0),
        ).replace(apply_fn=model_gp.apply)

    quiet = lambda *a, **k: None  # noqa: E731
    mesh = make_2d_mesh(2, data_shards=4)
    _, r1 = fit_data_parallel(
        fresh(), train_g, val_g, epochs=2, batch_size=4, node_cap=nc,
        edge_cap=ec, seed=5, mesh=mesh, log_fn=quiet,
        device_resident=True,
    )
    _, r2 = fit_data_parallel(
        fresh(), train_g, val_g, epochs=2, batch_size=4, node_cap=nc,
        edge_cap=ec, seed=5, mesh=mesh, log_fn=quiet, scan_epochs=True,
    )
    for e1, e2 in zip(r1["history"], r2["history"]):
        assert e1["train_loss"] == pytest.approx(e2["train_loss"], rel=1e-5)
        assert e1["val"]["mae"] == pytest.approx(e2["val"]["mae"], rel=1e-5)


def test_fit_dense_graph_sharded_scan_buckets_trains():
    """The full flagship composition on a sharded mesh: scan driver + 2
    size-class buckets + snug dense node-strip sharding trains with
    decreasing loss across epoch boundaries."""
    from cgnn_tpu.parallel.data_parallel import fit_data_parallel
    from cgnn_tpu.parallel.mesh import make_2d_mesh

    graphs = load_synthetic(
        96, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=0
    )
    train_g, val_g = graphs[:80], graphs[80:]
    targets = np.stack([g.target for g in train_g])
    tx = make_optimizer(optim="sgd", lr=0.05, lr_milestones=[100])
    nc, ec = capacities_for(train_g, 4, dense_m=8, snug=True,
                            node_multiple=16)
    batch = next(batch_iterator(train_g, 4, nc, ec, dense_m=8, snug=True))
    model_ref = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                    dense_m=8)
    model_gp = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                   dense_m=8, edge_axis_name="graph")
    state = create_train_state(
        model_ref, batch, tx, Normalizer.fit(targets), rng=jax.random.key(0)
    ).replace(apply_fn=model_gp.apply)

    quiet = lambda *a, **k: None  # noqa: E731
    _, result = fit_data_parallel(
        state, train_g, val_g, epochs=6, batch_size=4, node_cap=0,
        edge_cap=0, seed=5, mesh=make_2d_mesh(2, data_shards=4),
        log_fn=quiet, buckets=2, snug=True, dense_m=8, scan_epochs=True,
    )
    h = result["history"]
    assert np.isfinite(h[-1]["train_loss"])
    assert h[-1]["train_loss"] < h[0]["train_loss"]
def test_2d_data_x_graph_mesh_matches_plain_dp():
    graphs, _, targets, tx = _setup(batch_size=8, n_graphs=32)
    nc, ec = capacities_for(graphs, 8)
    batches = [
        pad_edges_divisible(b, 2)
        for b in list(batch_iterator(graphs, 8, nc, ec))[:4]
    ]
    stacked = stack_batches(batches)
    model_ref = CrystalGraphConvNet(atom_fea_len=32, n_conv=2, h_fea_len=32)
    model_gp = CrystalGraphConvNet(
        atom_fea_len=32, n_conv=2, h_fea_len=32, edge_axis_name="graph"
    )
    state_a, state_b = _states(model_ref, model_gp, batches[0], targets, tx)

    mesh_dp = Mesh(np.array(jax.devices()[:4]), ("data",))
    mesh2d = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "graph"))
    state_a = jtu.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh_dp, P())), state_a
    )
    state_b = jtu.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh2d, P())), state_b
    )

    s1, m1 = make_parallel_train_step(mesh_dp)(
        state_a, shard_leading_axis(stacked, mesh_dp)
    )
    specs = batch_specs(graph_axis="graph", data_axis="data")
    sb = jtu.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh2d, s)),
        stacked, specs, is_leaf=lambda x: isinstance(x, P),
    )
    s2, m2 = make_dp_edge_parallel_train_step(mesh2d)(state_b, sb)
    assert float(m1["loss_sum"]) == pytest.approx(float(m2["loss_sum"]), abs=1e-3)
    for a, b in zip(
        jtu.tree_leaves(jax.device_get(s1.params)),
        jtu.tree_leaves(jax.device_get(s2.params)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)
