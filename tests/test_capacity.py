"""Device-resident capacity precheck (VERDICT r4 missing #3): a dataset
that cannot fit HBM must fall back LOUDLY to host-side pack-once staging
and still train — never an opaque XLA OOM mid-staging."""

import jax
import numpy as np
import pytest

from cgnn_tpu.data.compact import CompactSpec
from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
from cgnn_tpu.data.graph import capacities_for, pack_graphs
from cgnn_tpu.models import CrystalGraphConvNet
from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
from cgnn_tpu.train import loop as loop_mod
from cgnn_tpu.train.loop import check_device_resident_fit, fit

CFG = FeaturizeConfig(radius=6.0, max_num_nbr=12)


def _fit_scan(graphs, compact=None, epochs=2):
    train_g, val_g = graphs[:64], graphs[64:]
    model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=32,
                                dense_m=12)
    nc, ec = capacities_for(train_g, 16, dense_m=12, snug=True)
    state = create_train_state(
        model, pack_graphs(train_g[:4], nc, ec, 16, dense_m=12),
        make_optimizer(optim="adam", lr=0.01, lr_milestones=[10**9]),
        Normalizer.fit(np.stack([g.target for g in train_g])),
        rng=jax.random.key(0),
    )
    logs = []
    state, res = fit(
        state, train_g, val_g, epochs=epochs, batch_size=16,
        node_cap=nc, edge_cap=ec, seed=0, print_freq=0,
        scan_epochs=True, snug=True, dense_m=12, compact=compact,
        log_fn=lambda m: logs.append(str(m)),
    )
    return res, logs


def test_cpu_backend_has_no_device_budget():
    # host memory IS the CPU backend's device memory: nothing to size
    assert loop_mod.device_hbm_budget() is None
    assert check_device_resident_fit(10**15)


def test_accelerator_without_memory_stats_is_an_error():
    class Opaque:
        platform = "tpu"

        def memory_stats(self):
            return None

    with pytest.raises(RuntimeError, match="memory_stats"):
        loop_mod.device_hbm_budget(Opaque())


def test_check_math(monkeypatch):
    monkeypatch.setattr(loop_mod, "device_hbm_budget", lambda *a: 1000)
    assert check_device_resident_fit(1000)
    assert not check_device_resident_fit(1001, log_fn=lambda m: None)
    # per-device share: 8 devices carry 1/8 each
    assert check_device_resident_fit(8000, n_devices=8,
                                     log_fn=lambda m: None)


def test_oversize_dataset_falls_back_and_trains(monkeypatch):
    graphs = load_synthetic_mp(96, CFG, seed=21)
    monkeypatch.setattr(loop_mod, "device_hbm_budget", lambda *a: 1024)
    res, logs = _fit_scan(graphs)
    assert res["staging"]["fallback"] == "host_pack_once"
    assert any("FALLING BACK" in m for m in logs)
    assert len(res["history"]) == 2
    assert np.isfinite(res["best"])


def test_oversize_compact_falls_back_with_expanded_steps(monkeypatch):
    graphs = load_synthetic_mp(96, CFG, seed=21)
    spec = CompactSpec.build(graphs, CFG.gdf(), dense_m=12)
    monkeypatch.setattr(loop_mod, "device_hbm_budget", lambda *a: 1024)
    res, logs = _fit_scan(graphs, compact=spec)
    assert res["staging"]["fallback"] == "host_pack_once"
    assert len(res["history"]) == 2
    assert np.isfinite(res["best"])


def test_fitting_dataset_keeps_scan_driver(monkeypatch):
    graphs = load_synthetic_mp(96, CFG, seed=21)
    monkeypatch.setattr(loop_mod, "device_hbm_budget",
                        lambda *a: 64 << 30)
    res, logs = _fit_scan(graphs)
    assert "fallback" not in res["staging"]
    assert "stack_stage_dispatch_s" in res["staging"]


def test_dp_oversize_falls_back_and_trains(monkeypatch):
    from cgnn_tpu.parallel import fit_data_parallel
    from cgnn_tpu.parallel.mesh import make_mesh

    graphs = load_synthetic_mp(64, CFG, seed=22)
    monkeypatch.setattr(loop_mod, "device_hbm_budget", lambda *a: 1024)
    model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=32,
                                dense_m=12)
    nc, ec = capacities_for(graphs, 4, dense_m=12, snug=True)
    state = create_train_state(
        model, pack_graphs(graphs[:4], nc, ec, 8, dense_m=12),
        make_optimizer(optim="adam", lr=0.01, lr_milestones=[10**9]),
        Normalizer.fit(np.stack([g.target for g in graphs])),
        rng=jax.random.key(0),
    )
    logs = []
    _, res = fit_data_parallel(
        state, graphs, graphs[:8], epochs=2, batch_size=4,
        node_cap=nc, edge_cap=ec, seed=0, mesh=make_mesh(4),
        snug=True, dense_m=12, scan_epochs=True,
        log_fn=lambda m: logs.append(str(m)),
    )
    assert any("FALLING BACK" in m for m in logs)
    assert len(res["history"]) == 2
    assert np.isfinite(res["best"])
