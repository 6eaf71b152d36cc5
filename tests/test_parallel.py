"""Data-parallel tests on the 8-virtual-device CPU mesh (SURVEY.md §4.5).

The fake-NCCL analog: assert the shard_map DP step reproduces the
single-device step exactly when every device sees the same batch, and that
eval padding batches contribute nothing.
"""

import numpy as np
import pytest

import jax

from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic
from cgnn_tpu.data.graph import pack_graphs
from cgnn_tpu.models import CrystalGraphConvNet
from cgnn_tpu.parallel import (
    empty_batch_like,
    make_parallel_eval_step,
    make_parallel_train_step,
    parallel_batches,
    replicate_state,
    shard_leading_axis,
    stack_batches,
)
from cgnn_tpu.parallel.mesh import make_mesh
from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
from cgnn_tpu.train.step import make_eval_step, make_train_step

N_DEV = 8


# function scope: the DP train step donates its (replicated) state, and
# replication aliases the device-0 shard — a module-scoped state would be
# deleted for later tests
@pytest.fixture()
def setup():
    assert len(jax.devices()) >= N_DEV, "conftest must provide 8 CPU devices"
    graphs = load_synthetic(16, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                            seed=9, max_atoms=6)
    node_cap, edge_cap = 96, 768
    batch = pack_graphs(graphs[:4], node_cap, edge_cap, 4)
    model = CrystalGraphConvNet(atom_fea_len=12, n_conv=2, h_fea_len=16)
    tx = make_optimizer(optim="sgd", lr=0.05)
    normalizer = Normalizer.fit(np.stack([g.target for g in graphs]))
    state = create_train_state(model, batch, tx, normalizer)
    return graphs, batch, model, state, (node_cap, edge_cap)


class TestDataParallel:
    def test_replicated_batch_matches_single_device(self, setup):
        """Same batch on all 8 devices -> pmean(grads)==grads, so the DP
        step must equal the single-device step; metric sums are 8x."""
        graphs, batch, model, state, _ = setup
        mesh = make_mesh(N_DEV)

        single_step = jax.jit(make_train_step())  # no donation: reuse state
        s_single, m_single = single_step(state, batch)

        dp_step = make_parallel_train_step(mesh)
        stacked = stack_batches([batch] * N_DEV)
        s_dp, m_dp = dp_step(
            replicate_state(state, mesh), shard_leading_axis(stacked, mesh)
        )

        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
            jax.device_get(s_dp.params), jax.device_get(s_single.params),
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
            jax.device_get(s_dp.batch_stats), jax.device_get(s_single.batch_stats),
        )
        np.testing.assert_allclose(
            float(m_dp["loss_sum"]), N_DEV * float(m_single["loss_sum"]),
            rtol=1e-6,
        )
        np.testing.assert_allclose(float(m_dp["count"]), N_DEV * 4.0)

    def test_eval_padding_contributes_zero(self, setup):
        graphs, batch, model, state, _ = setup
        mesh = make_mesh(N_DEV)
        eval_single = jax.jit(make_eval_step())
        m_single = jax.device_get(eval_single(state, batch))

        # one real batch + 7 empty padding batches
        stacked = stack_batches([batch] + [empty_batch_like(batch)] * (N_DEV - 1))
        dp_eval = make_parallel_eval_step(mesh)
        m_dp = jax.device_get(
            dp_eval(replicate_state(state, mesh), shard_leading_axis(stacked, mesh))
        )
        for k in m_single:
            np.testing.assert_allclose(
                float(m_dp[k]), float(m_single[k]), rtol=1e-6, atol=1e-8
            )

    def test_parallel_batches_grouping(self, setup):
        graphs, _, _, _, (node_cap, edge_cap) = setup
        stacked_list = list(
            parallel_batches(graphs, 4, 2, node_cap, edge_cap, pad_incomplete=True)
        )
        assert all(s.nodes.shape[0] == 4 for s in stacked_list)
        total_real = sum(float(np.sum(s.graph_mask)) for s in stacked_list)
        assert total_real == len(graphs)
        # without padding, incomplete trailing groups are dropped
        stacked_drop = list(parallel_batches(graphs, 5, 2, node_cap, edge_cap))
        assert all(s.nodes.shape[0] == 5 for s in stacked_drop)

    def test_hierarchical_dcn_mesh_matches_flat_dp(self, setup):
        """A multi-host-style ('dcn', 'data') 2x4 mesh must produce exactly
        the same step as a flat 8-device ('data',) mesh: the reductions span
        both axes, XLA just routes them over different fabrics."""
        import jax.tree_util as jtu
        from jax.sharding import Mesh

        graphs, batch, model, state, (node_cap, edge_cap) = setup
        state2 = create_train_state(
            model, batch, state.tx,
            Normalizer.fit(np.stack([g.target for g in graphs])),
        )
        stacked = next(
            parallel_batches(graphs, 8, 2, node_cap, edge_cap)
        )

        mesh_flat = make_mesh(N_DEV)
        s1, m1 = make_parallel_train_step(mesh_flat)(
            replicate_state(state, mesh_flat),
            shard_leading_axis(stacked, mesh_flat),
        )

        mesh_dcn = Mesh(
            np.array(jax.devices()[:8]).reshape(2, 4), ("dcn", "data")
        )
        s2, m2 = make_parallel_train_step(mesh_dcn)(
            replicate_state(state2, mesh_dcn),
            shard_leading_axis(stacked, mesh_dcn),
        )
        m1, m2 = jax.device_get((m1, m2))
        assert float(m1["loss_sum"]) == pytest.approx(
            float(m2["loss_sum"]), rel=1e-6)
        for a, b in zip(
            jtu.tree_leaves(jax.device_get(s1.params)),
            jtu.tree_leaves(jax.device_get(s2.params)),
        ):
            np.testing.assert_allclose(a, b, atol=1e-6)

    def test_fit_dp_device_resident_matches_streaming(self, setup):
        """DP fit with pack_once/device_resident: first epoch identical to
        the streaming path (same seed), later epochs keep training."""
        from cgnn_tpu.parallel import fit_data_parallel

        graphs, batch, model, state, (node_cap, edge_cap) = setup
        quiet = lambda *a, **k: None  # noqa: E731

        def run(**kw):
            s = create_train_state(
                model, batch, state.tx,
                Normalizer.fit(np.stack([g.target for g in graphs])),
            )
            _, result = fit_data_parallel(
                s, graphs, graphs[:8], epochs=2, batch_size=2,
                node_cap=node_cap, edge_cap=edge_cap, seed=5,
                mesh=make_mesh(4), log_fn=quiet, **kw,
            )
            return result["history"]

        h_stream = run()
        h_dr = run(device_resident=True)
        assert h_dr[0]["train_loss"] == pytest.approx(
            h_stream[0]["train_loss"], rel=1e-6)
        assert h_dr[0]["val"]["mae"] == pytest.approx(
            h_stream[0]["val"]["mae"], rel=1e-6)
        assert np.isfinite(h_dr[1]["train_loss"])

    def test_sharded_train_progresses(self, setup):
        """Distinct per-device batches: loss goes down over DP steps."""
        graphs, batch, model, state, (node_cap, edge_cap) = setup
        mesh = make_mesh(N_DEV)
        dp_step = make_parallel_train_step(mesh)
        state = replicate_state(state, mesh)
        losses = []
        for _ in range(6):
            for stacked in parallel_batches(
                graphs, N_DEV, 2, node_cap, edge_cap, pad_incomplete=False,
                shuffle=True, rng=np.random.default_rng(0),
            ):
                state, m = dp_step(state, shard_leading_axis(stacked, mesh))
                m = jax.device_get(m)
                losses.append(float(m["loss_sum"]) / max(float(m["count"]), 1))
        assert losses[-1] < losses[0]


class TestDPFeatureParity:
    """VERDICT r2 #3: buckets / snug / scan_epochs inside the DP loop."""

    @staticmethod
    def _fresh(graphs, dense_m=8):
        """A factory of new states for ``graphs`` in one layout (``dense_m``
        None: the COO body)."""
        from cgnn_tpu.data.graph import bucketed_batch_iterator

        model = CrystalGraphConvNet(
            atom_fea_len=12, n_conv=2, h_fea_len=16, dense_m=dense_m
        )
        eb = next(iter(bucketed_batch_iterator(
            graphs, 2, 2, dense_m=dense_m, snug=True
        )))
        tx = make_optimizer(optim="sgd", lr=0.05)

        def fresh():
            return create_train_state(
                model, eb, tx,
                Normalizer.fit(np.stack([g.target for g in graphs])),
            )

        return fresh

    @pytest.mark.parametrize("scan_epochs", [False, True],
                             ids=["per_step", "scan"])
    def test_fit_dp_bucketed_snug_trains(self, setup, scan_epochs):
        from cgnn_tpu.parallel import fit_data_parallel

        graphs, *_ = setup
        fresh = self._fresh(graphs)
        quiet = lambda *a, **k: None  # noqa: E731
        _, result = fit_data_parallel(
            fresh(), graphs, graphs[:8], epochs=6, batch_size=2,
            node_cap=0, edge_cap=0, seed=5, mesh=make_mesh(4), log_fn=quiet,
            buckets=2, snug=True, dense_m=8, scan_epochs=scan_epochs,
        )
        h = result["history"]
        assert np.isfinite(h[-1]["train_loss"])
        assert h[-1]["train_loss"] < h[0]["train_loss"]

    @pytest.mark.parametrize("buckets", [1, 3])
    @pytest.mark.parametrize("dense_m", [8, None], ids=["dense", "coo"])
    def test_fit_dp_scan_epochs_matches_per_step(self, dense_m, buckets):
        """DP scan_epochs against per-step DP, same seed and batches, in
        both layouts. One shape group: the orders coincide and the first
        epoch is the same to rounding (the scan folds dispatches, not
        math). Three size classes: the scan's order is chunk-granular by
        design (ScanEpochDriver docstring), so what must agree is what
        order cannot move, the structures each epoch trains on and scores,
        and both runs train."""
        from cgnn_tpu.data.graph import capacities_for
        from cgnn_tpu.parallel import fit_data_parallel

        graphs = load_synthetic(
            96, FeaturizeConfig(radius=5.0, max_num_nbr=8), seed=9,
            max_atoms=6)
        train_g, val_g = graphs[:80], graphs[80:]
        fresh = self._fresh(train_g, dense_m)
        quiet = lambda *a, **k: None  # noqa: E731
        nc, ec = capacities_for(train_g, 2, dense_m=dense_m, snug=True)

        def run(**kw):
            seen = []
            _, result = fit_data_parallel(
                fresh(), train_g, val_g, epochs=3, batch_size=2,
                node_cap=nc, edge_cap=ec, seed=5, mesh=make_mesh(4),
                log_fn=quiet, snug=True, dense_m=dense_m, buckets=buckets,
                on_epoch_metrics=lambda e, t, v: seen.append(
                    (t["count"], v["count"])),
                **kw,
            )
            return result["history"], seen

        h_step, n_step = run(device_resident=True)
        h_scan, n_scan = run(scan_epochs=True)
        assert n_scan == n_step and n_step[0][0] > 0
        if buckets == 1:
            assert h_scan[0]["train_loss"] == pytest.approx(
                h_step[0]["train_loss"], rel=1e-5)
            assert h_scan[0]["val"]["mae"] == pytest.approx(
                h_step[0]["val"]["mae"], rel=1e-5)
        for h in (h_step, h_scan):
            assert np.isfinite(h[-1]["train_loss"])
            assert h[-1]["train_loss"] < h[0]["train_loss"]


def _two_axis_mesh(names):
    return jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), names)


class TestRefusals:
    """What the data-parallel layer refuses, by name and before it runs."""

    @pytest.mark.parametrize("entry", ["train_step", "eval_step", "fit"])
    def test_mesh_with_unknown_axis_is_refused(self, setup, entry):
        """A mesh comes from outside: an axis other than 'data' / 'dcn'
        is named in a ValueError, not folded into the replica count."""
        from cgnn_tpu.parallel import fit_data_parallel

        graphs, batch, model, state, (node_cap, edge_cap) = setup
        mesh = _two_axis_mesh(("data", "graph"))
        with pytest.raises(ValueError, match="mesh axis 'graph'"):
            if entry == "train_step":
                make_parallel_train_step(mesh)
            elif entry == "eval_step":
                make_parallel_eval_step(mesh)
            else:
                fit_data_parallel(
                    state, graphs, graphs[:8], epochs=1, batch_size=2,
                    node_cap=node_cap, edge_cap=edge_cap, mesh=mesh,
                )

    @pytest.mark.parametrize("case", [
        "compact_without_scan", "compact_without_dense", "custom_step_dcn"])
    def test_fit_dp_standing_refusals(self, setup, case):
        from cgnn_tpu.data.compact import CompactSpec
        from cgnn_tpu.parallel import fit_data_parallel

        graphs, batch, model, state, (node_cap, edge_cap) = setup
        kw = dict(epochs=1, batch_size=2, node_cap=node_cap,
                  edge_cap=edge_cap, mesh=make_mesh(4))
        if case == "custom_step_dcn":
            # a custom body was built with axis_name='data' alone: on a
            # hierarchical mesh its gradient would skip the 'dcn' reduce
            kw |= dict(mesh=_two_axis_mesh(("dcn", "data")),
                       train_step_fn=make_train_step(axis_name="data"))
            raises = pytest.raises(NotImplementedError,
                                   match="custom step bodies")
        else:
            spec = CompactSpec.build(
                graphs, FeaturizeConfig(radius=5.0, max_num_nbr=8).gdf(),
                dense_m=8)
            kw |= dict(compact=spec)
            kw |= (dict(dense_m=8) if case == "compact_without_scan"
                   else dict(scan_epochs=True))
            raises = pytest.raises(ValueError, match="compact staging")
        with raises:
            fit_data_parallel(state, graphs, graphs[:8], **kw)
