"""Fault-tolerance tests (ISSUE 2; cgnn_tpu.resilience).

The load-bearing guarantees, pinned:

- a crash at ANY point of a checkpoint save (fault-injected at the
  finalizer's crash points) leaves every previously committed save
  restorable — the temp-dir + atomic-rename protocol;
- corruption of the newest save (data garble, truncation, meta damage)
  makes restore FALL BACK to the previous valid save, with a report of
  what was skipped and why;
- the in-graph divergence guard is bit-identical to the unguarded body
  when no fault fires (like the telemetry tap), and an injected NaN
  batch is skipped exactly — the faulted run equals a run that never saw
  that batch, bit for bit;
- preemption requests stop training at the epoch boundary (chunk
  boundary under the epoch scan) with a resumable checkpoint, and the
  resumed run reaches the same epoch count as an uninterrupted one;
- the divergence monitor rolls back to the last good checkpoint with an
  LR cut, bounded by its retry budget;
- the prefetch producer thread exits when the consumer abandons the
  iterator mid-epoch.
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import jax

from cgnn_tpu.data.dataset import (
    FeaturizeConfig,
    load_synthetic,
    train_val_test_split,
)
from cgnn_tpu.data.graph import batch_iterator, pack_graphs
from cgnn_tpu.data.loader import prefetch_to_device
from cgnn_tpu.models import CrystalGraphConvNet
from cgnn_tpu.resilience import (
    DivergenceError,
    DivergenceMonitor,
    IntegrityError,
    PreemptionHandler,
    faultinject,
    guard_step,
    tree_manifest,
    verify_tree,
)
from cgnn_tpu.train import (
    CheckpointManager,
    Normalizer,
    create_train_state,
    make_optimizer,
)
from cgnn_tpu.train.checkpoint import CheckpointRestoreError
from cgnn_tpu.train.loop import capacities_for, fit
from cgnn_tpu.train.step import make_train_step


@pytest.fixture(scope="module")
def tiny_dataset():
    graphs = load_synthetic(60, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                            seed=3, max_atoms=6)
    return train_val_test_split(graphs, 0.7, 0.15, seed=0)


@pytest.fixture(autouse=True)
def _clear_fault_plan():
    yield
    faultinject.set_plan(None)


def _caps(train_g):
    return capacities_for(train_g, 16)


def _fresh_state(train_g, node_cap, edge_cap, seed=1, optim="adam"):
    """A new state with its OWN normalizer/optimizer buffers: the train
    steps donate the state argument, so sharing arrays across states
    would poison later runs with deleted buffers."""
    # small on purpose: these tests pin mechanics (bit-identity, skip
    # selects, restores), not learning, and compile time dominates
    model = CrystalGraphConvNet(atom_fea_len=8, n_conv=1, h_fea_len=16)
    tx = make_optimizer(optim=optim, lr=0.01)
    norm = Normalizer.fit(np.stack([g.target for g in train_g]))
    example = pack_graphs(train_g[:16], node_cap, edge_cap, 16)
    return create_train_state(model, example, tx, norm,
                              rng=jax.random.key(seed))


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


class TestIntegrity:
    def test_manifest_round_trip_and_bit_flip(self):
        tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
                "b": {"c": np.ones(5, dtype=np.int32)}}
        m = tree_manifest(tree)
        verify_tree(tree, m)  # clean tree verifies
        flipped = {"a": tree["a"].copy(), "b": {"c": tree["b"]["c"].copy()}}
        flipped["a"][1, 2] += 1.0
        with pytest.raises(IntegrityError, match="crc32"):
            verify_tree(flipped, m)
        with pytest.raises(IntegrityError, match="shape"):
            verify_tree({"a": tree["a"][:2], "b": tree["b"]}, m)
        with pytest.raises(IntegrityError, match="leaf set"):
            verify_tree({"a": tree["a"]}, m)

    def test_typed_and_raw_trees_share_paths(self):
        """The manifest must verify a raw orbax round trip of a TYPED
        tree (optax namedtuples deserialize as plain dicts)."""
        import collections

        Point = collections.namedtuple("Point", ["x", "y"])
        typed = {"p": Point(np.ones(2), np.zeros(3))}
        raw = {"p": {"x": np.ones(2), "y": np.zeros(3)}}
        verify_tree(raw, tree_manifest(typed))


class TestCrashSafeCheckpoint:
    def test_versioned_commit_and_round_trip(self, tiny_dataset, tmp_path):
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        state = _fresh_state(train_g, nc, ec)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(state, {"epoch": 0, "task": "regression"}, is_best=True)
        mgr.save(state, {"epoch": 1, "task": "regression"})
        mgr.wait()
        names = sorted(n for n in os.listdir(tmp_path)
                       if n.startswith("ckpt-"))
        assert names == ["ckpt-00000000", "ckpt-00000001"]
        for n in names:  # committed = meta + manifest inside the save dir
            assert os.path.exists(tmp_path / n / "meta.json")
            assert os.path.exists(tmp_path / n / "MANIFEST.json")
        assert mgr.exists("latest") and mgr.exists("best")
        assert mgr.exists("previous")
        assert mgr.read_meta()["epoch"] == 1
        assert mgr.read_meta("best")["epoch"] == 0

        restored, meta = mgr.restore(
            _fresh_state(train_g, nc, ec, seed=9))
        assert meta["epoch"] == 1
        _assert_trees_equal(restored.params, state.params)
        inf = mgr.restore_for_inference(
            _fresh_state(train_g, nc, ec, seed=9), "best")
        _assert_trees_equal(inf.params, state.params)
        mgr.close()

    @pytest.mark.parametrize("crash_at", ["after_write", "before_commit"])
    def test_crash_mid_save_previous_still_restorable(
            self, tiny_dataset, tmp_path, crash_at):
        """The kill-9-mid-save guarantee: a crash before the atomic
        commit leaves an uncommitted temp dir that restore never sees;
        the previous checkpoint stays the resume point."""
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        state = _fresh_state(train_g, nc, ec)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(state, {"epoch": 0})
        mgr.wait()
        faultinject.set_plan(
            faultinject.FaultPlan.parse(f"crash={crash_at}:1"))
        mgr.save(state, {"epoch": 1})
        with pytest.raises(faultinject.InjectedCrash):
            mgr.wait()
        faultinject.set_plan(None)
        # crash state on disk: epoch-1's temp never committed
        assert any(n.startswith(".tmp-") for n in os.listdir(tmp_path))
        mgr2 = CheckpointManager(str(tmp_path))  # post-crash process
        restored, meta = mgr2.restore(_fresh_state(train_g, nc, ec, seed=9))
        assert meta["epoch"] == 0
        _assert_trees_equal(restored.params, state.params)
        # the stale temp is swept by the first SAVE (writers own the
        # directory; a mere reader like predict.py must never delete a
        # live trainer's in-progress temp) and the resumed run commits
        mgr2.save(restored, {"epoch": 1})
        mgr2.wait()
        assert not any(n.startswith(".tmp-") for n in os.listdir(tmp_path))
        assert mgr2.read_meta()["epoch"] == 1
        mgr.close()
        mgr2.close()

    @pytest.mark.parametrize("mode", ["garble", "truncate", "meta"])
    def test_corrupt_latest_falls_back_with_report(
            self, tiny_dataset, tmp_path, mode):
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        s0 = _fresh_state(train_g, nc, ec, seed=0)
        s1 = _fresh_state(train_g, nc, ec, seed=1)
        mgr = CheckpointManager(str(tmp_path), log_fn=lambda m: None)
        mgr.save(s0, {"epoch": 0})
        mgr.save(s1, {"epoch": 1})
        mgr.wait()
        faultinject.corrupt_checkpoint(
            str(tmp_path / "ckpt-00000001"), mode=mode)
        restored, meta = mgr.restore(_fresh_state(train_g, nc, ec, seed=9))
        assert meta["epoch"] == 0  # fell back to the previous valid save
        _assert_trees_equal(restored.params, s0.params)
        assert mgr.last_restore_report  # the skip was reported
        assert "ckpt-00000001" in mgr.last_restore_report[0]
        mgr.close()

    def test_all_candidates_corrupt_raises(self, tiny_dataset, tmp_path):
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        mgr = CheckpointManager(str(tmp_path), log_fn=lambda m: None)
        mgr.save(_fresh_state(train_g, nc, ec), {"epoch": 0})
        mgr.wait()
        faultinject.corrupt_checkpoint(
            str(tmp_path / "ckpt-00000000"), mode="truncate")
        with pytest.raises(CheckpointRestoreError):
            mgr.restore(_fresh_state(train_g, nc, ec, seed=9))
        mgr.close()

    def test_retention_keeps_k_plus_best(self, tiny_dataset, tmp_path):
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        state = _fresh_state(train_g, nc, ec)
        mgr = CheckpointManager(str(tmp_path), keep=2)
        mgr.save(state, {"epoch": 0}, is_best=True)
        for e in range(1, 5):
            mgr.save(state, {"epoch": e})
        mgr.wait()
        names = sorted(n for n in os.listdir(tmp_path)
                       if n.startswith("ckpt-"))
        # newest two plus the best-pointer target survive
        assert names == ["ckpt-00000000", "ckpt-00000003", "ckpt-00000004"]
        assert mgr.read_meta("best")["epoch"] == 0
        mgr.close()

    def test_legacy_tag_layout_still_restores(self, tiny_dataset, tmp_path):
        """Pre-ISSUE-2 checkpoints (orbax tag dirs + meta-<tag>.json)
        remain readable as the fallback chain's last resort."""
        import orbax.checkpoint as ocp

        from cgnn_tpu.train.checkpoint import _state_pytree

        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        state = _fresh_state(train_g, nc, ec)
        tree = jax.device_get(_state_pytree(state))
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(str(tmp_path / "latest"), tree)
        with open(tmp_path / "meta-latest.json", "w") as f:
            json.dump({"epoch": 7}, f)
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.exists("latest")
        restored, meta = mgr.restore(_fresh_state(train_g, nc, ec, seed=9))
        assert meta["epoch"] == 7
        _assert_trees_equal(restored.params, state.params)
        mgr.close()

    def test_legacy_missing_meta_refuses_blind_resume(
            self, tiny_dataset, tmp_path):
        """A legacy checkpoint with no meta must NOT restore silently
        (train.py used to compute start_epoch = 0 and retrain over it)."""
        import orbax.checkpoint as ocp

        from cgnn_tpu.train.checkpoint import _state_pytree

        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        state = _fresh_state(train_g, nc, ec)
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(str(tmp_path / "latest"),
                       jax.device_get(_state_pytree(state)))
        mgr = CheckpointManager(str(tmp_path), log_fn=lambda m: None)
        with pytest.raises(CheckpointRestoreError, match="resume blind"):
            mgr.restore(_fresh_state(train_g, nc, ec, seed=9))
        mgr.close()


class TestDivergenceGuard:
    def test_guard_noop_leaves_trajectory_unchanged(self, tiny_dataset):
        """No fault -> the guard's select is the identity and nothing is
        skipped, per-step loop and whole-epoch scan alike. Guarded and
        unguarded steps are two XLA programs, and the compiler may round
        the same update differently in each (1 ulp per step on jax
        0.9.0's CPU backend; Adam turns that into O(lr) drift on
        parameters whose exact gradient is zero, e.g. fc_full's bias
        ahead of a BatchNorm) — so the pin is on what the model computes,
        to 1e-3 (Adam amplifies the ulps), not on parameter bits. The bit-exact pin
        lives in the skip test below, where both sides are ONE program."""
        train_g, val_g, _ = tiny_dataset
        nc, ec = _caps(train_g)

        def run(guard, scan):
            state, result = fit(
                _fresh_state(train_g, nc, ec), train_g, val_g, epochs=2,
                batch_size=16, node_cap=nc, edge_cap=ec, print_freq=0,
                seed=4, scan_epochs=scan, guard=guard,
                log_fn=lambda *a: None,
            )
            return state, result

        for scan in (False, True):
            _, r_off = run(False, scan)
            _, r_on = run(True, scan)
            for h0, h1 in zip(r_off["history"], r_on["history"]):
                assert h1["train"]["guard_skipped"] == 0.0
                for phase in ("train", "val"):
                    np.testing.assert_allclose(
                        h1[phase]["loss"], h0[phase]["loss"], rtol=1e-3)

    def test_nan_batch_skip_equals_manual_skip_bit_exact(self, tiny_dataset):
        """A NaN batch under the guard leaves the state EXACTLY as if the
        batch had never been dispatched: same params, same step count."""
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        batches = list(batch_iterator(train_g, 16, nc, ec))
        assert len(batches) >= 3
        step = jax.jit(guard_step(make_train_step()), donate_argnums=0)
        j = 1
        faulted = [
            faultinject.poison_nan(b) if i == j else b
            for i, b in enumerate(batches)
        ]
        s1 = _fresh_state(train_g, nc, ec, seed=2)
        skips = 0.0
        for b in faulted:
            s1, m = step(s1, b)
            skips += float(np.asarray(m["guard_skipped_sum"]))
        s2 = _fresh_state(train_g, nc, ec, seed=2)
        for i, b in enumerate(batches):
            if i == j:
                continue
            s2, _ = step(s2, b)
        assert skips == 1.0
        assert int(np.asarray(s1.step)) == int(np.asarray(s2.step))
        _assert_trees_equal(s1.params, s2.params)
        _assert_trees_equal(s1.opt_state, s2.opt_state)

    def test_scan_nan_batch_skipped_and_counted(self, tiny_dataset):
        """The acceptance fault: a NaN batch injected mid-scan. The
        staged batch replays every epoch; the guard skips it every epoch,
        losses stay finite, and the skip count reaches telemetry via the
        epoch aggregates."""
        train_g, val_g, _ = tiny_dataset
        nc, ec = _caps(train_g)
        faultinject.set_plan(faultinject.FaultPlan.parse("nan_batch=1"))
        state, result = fit(
            _fresh_state(train_g, nc, ec), train_g, val_g, epochs=2,
            batch_size=16, node_cap=nc, edge_cap=ec, print_freq=0, seed=4,
            scan_epochs=True, guard=True, log_fn=lambda *a: None,
        )
        faultinject.set_plan(None)
        for h in result["history"]:
            assert np.isfinite(h["train"]["loss"])
            assert h["train"]["guard_skipped"] * h["train"]["steps"] == 1.0
        assert all(np.isfinite(x).all() for x in _leaves(state.params))

        # control: without the guard the same fault reaches the params
        faultinject.set_plan(faultinject.FaultPlan.parse("nan_batch=1"))
        state_n, _ = fit(
            _fresh_state(train_g, nc, ec), train_g, val_g, epochs=2,
            batch_size=16, node_cap=nc, edge_cap=ec, print_freq=0, seed=4,
            scan_epochs=True, guard=False, log_fn=lambda *a: None,
        )
        faultinject.set_plan(None)
        assert not all(np.isfinite(x).all() for x in _leaves(state_n.params))


class TestPreemption:
    def test_handler_latches_real_sigterm(self):
        hits = []
        handler = PreemptionHandler(log_fn=hits.append).install()
        try:
            assert not handler.requested
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.time() + 2
            while not handler.requested and time.time() < deadline:
                time.sleep(0.01)
            assert handler.requested
            assert hits and "SIGTERM" in hits[0]
        finally:
            handler.uninstall()

    def test_epoch_boundary_preempt_then_resume_full_count(
            self, tiny_dataset, tmp_path):
        """The acceptance cycle, in-process: preempt after epoch 1, save
        at the boundary, resume with the checkpoint's epoch and reach the
        same epoch count as an uninterrupted run."""
        train_g, val_g, _ = tiny_dataset
        nc, ec = _caps(train_g)
        ckpt = CheckpointManager(str(tmp_path))
        save_cb = lambda s, e, m, b: ckpt.save(s, {"epoch": e}, is_best=b)  # noqa: E731
        pre = PreemptionHandler(log_fn=lambda m: None)

        def request_at_1(epoch, tm, vm):
            if epoch == 1:
                pre.request()

        _, result = fit(
            _fresh_state(train_g, nc, ec), train_g, val_g, epochs=5,
            batch_size=16, node_cap=nc, edge_cap=ec, print_freq=0, seed=4,
            on_epoch_end=save_cb, on_epoch_metrics=request_at_1,
            preempt=pre, log_fn=lambda *a: None,
        )
        assert result["preempted"] is True
        assert [h["epoch"] for h in result["history"]] == [0, 1]
        ckpt.wait()
        meta = ckpt.read_meta()
        assert meta["epoch"] == 1

        resumed, meta2 = ckpt.restore(
            _fresh_state(train_g, nc, ec, seed=9))
        _, r2 = fit(
            resumed, train_g, val_g, epochs=5, batch_size=16,
            node_cap=nc, edge_cap=ec, print_freq=0, seed=4,
            start_epoch=meta2["epoch"] + 1, log_fn=lambda *a: None,
        )
        assert [h["epoch"] for h in r2["history"]] == [2, 3, 4]
        assert "preempted" not in r2
        ckpt.close()

    def test_scan_driver_aborts_at_chunk_boundary(self, tiny_dataset):
        """A request arriving MID-epoch stops the scan driver at the next
        chunk boundary: fewer steps dispatched, ``aborted`` set."""
        from cgnn_tpu.train.loop import ScanEpochDriver
        from cgnn_tpu.train.step import make_eval_step

        class RequestAfterPolls:
            """Looks requested from the (n+1)-th poll on — a signal that
            lands while the n-th chunk is in flight."""

            def __init__(self, n):
                self.polls, self.n = 0, n

            @property
            def requested(self):
                self.polls += 1
                return self.polls > self.n

        train_g, val_g, _ = tiny_dataset
        nc, ec = _caps(train_g)
        batches = list(batch_iterator(train_g, 8, nc, ec))
        vbatches = list(batch_iterator(val_g, 8, nc, ec, in_cap=0))
        assert len(batches) >= 4
        drv = ScanEpochDriver(
            make_train_step(), make_eval_step(), batches, vbatches,
            np.random.default_rng(7), preempt=RequestAfterPolls(1),
        )
        state = _fresh_state(train_g, nc, ec, seed=2)
        state, train_m, val_m = drv.run_epoch_pair(state, first=True)
        assert drv.aborted
        # exactly one chunk (chunk_steps=2 -> 2 steps) ran before the
        # boundary check fired; eval was skipped outright
        assert train_m["steps"] == drv.chunk_steps
        assert train_m["steps"] < len(batches)
        assert val_m == {"count": 0.0, "steps": 0}

        # a request landing during EVAL must NOT mark the (completed)
        # train epoch aborted — the caller would otherwise checkpoint it
        # under epoch-1 and retrain the whole epoch on resume
        n_train_chunks = -(-len(batches) // 2)  # single bucket, chunk 2
        drv2 = ScanEpochDriver(
            make_train_step(), make_eval_step(), batches, vbatches,
            np.random.default_rng(7),
            preempt=RequestAfterPolls(n_train_chunks),
        )
        state2 = _fresh_state(train_g, nc, ec, seed=2)
        state2, train_m2, val_m2 = drv2.run_epoch_pair(state2, first=True)
        assert not drv2.aborted
        assert train_m2["steps"] == len(batches)  # full train epoch
        assert val_m2["steps"] < len(vbatches)  # eval cut short

    def test_fit_scan_preempted_mid_epoch_saves_last_completed(
            self, tiny_dataset, tmp_path):
        """fit() handling of a chunk-boundary abort: the partial epoch's
        state is checkpointed under the last COMPLETED epoch, so resume
        redoes the interrupted epoch instead of skipping its tail."""
        train_g, val_g, _ = tiny_dataset
        nc, ec = _caps(train_g)
        ckpt = CheckpointManager(str(tmp_path))
        saved_epochs = []

        def save_cb(s, e, m, b):
            saved_epochs.append(e)
            ckpt.save(s, {"epoch": e}, is_best=b)

        pre = PreemptionHandler(log_fn=lambda m: None)
        pre.request()  # lands before epoch 0's first chunk
        _, result = fit(
            _fresh_state(train_g, nc, ec), train_g, val_g, epochs=4,
            batch_size=16, node_cap=nc, edge_cap=ec, print_freq=0, seed=4,
            scan_epochs=True, on_epoch_end=save_cb, preempt=pre,
            log_fn=lambda *a: None,
        )
        assert result["preempted"] is True
        assert result["history"] == []  # no epoch completed
        assert saved_epochs == [-1]  # resume restarts at epoch 0
        ckpt.wait()
        assert ckpt.read_meta()["epoch"] == -1
        ckpt.close()


class TestDivergenceMonitor:
    def test_rollback_lr_cut_and_bounded_retries(
            self, tiny_dataset, tmp_path):
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        state = _fresh_state(train_g, nc, ec)
        ckpt = CheckpointManager(str(tmp_path))
        ckpt.save(state, {"epoch": 0})
        ckpt.wait()
        mon = DivergenceMonitor(ckpt, max_skips=2, lr_cut=0.5,
                                max_rollbacks=2, log_fn=lambda m: None)
        bad = {"loss": 1.0, "guard_skipped": 0.5, "steps": 4}  # 2 skips
        good = {"loss": 1.0, "guard_skipped": 0.0, "steps": 4}

        s0, rolled = mon.observe(state, 0, good)
        assert not rolled and s0 is state

        s1, rolled = mon.observe(state, 1, bad)
        assert rolled and mon.rollbacks == 1 and mon.lr_scale == 0.5
        _assert_trees_equal(s1.params, state.params)  # restored weights
        # the cut tx halves the update for identical grads, with the
        # optimizer STATE structure untouched (checkpoint compatibility)
        g = jax.tree_util.tree_map(np.ones_like, state.params)
        u_base, _ = state.tx.update(g, state.tx.init(state.params),
                                    state.params)
        u_cut, _ = s1.tx.update(g, s1.tx.init(s1.params), s1.params)
        for a, b in zip(_leaves(u_base), _leaves(u_cut)):
            np.testing.assert_allclose(b, a * 0.5, rtol=1e-6)
        assert (jax.tree_util.tree_structure(s1.opt_state)
                == jax.tree_util.tree_structure(state.opt_state))

        s2, rolled = mon.observe(s1, 2, bad)
        assert rolled and mon.lr_scale == 0.25
        with pytest.raises(DivergenceError):
            mon.observe(s2, 3, bad)
        ckpt.close()

    def test_progress_survives_requeue_via_meta(self, tiny_dataset, tmp_path):
        """The LR cut and rollback budget persist through checkpoint
        meta: a preemption requeue must NOT restart at the full-strength
        LR that caused the divergence with a fresh retry budget."""
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        state = _fresh_state(train_g, nc, ec)
        ckpt = CheckpointManager(str(tmp_path))
        ckpt.save(state, {"epoch": 0})
        ckpt.wait()
        mon = DivergenceMonitor(ckpt, max_skips=2, lr_cut=0.5,
                                max_rollbacks=3, log_fn=lambda m: None)
        rolled_state, _ = mon.observe(
            state, 1, {"loss": 1.0, "guard_skipped": 0.5, "steps": 4})
        saved_meta = {"epoch": 1, **mon.meta()}
        assert saved_meta["guard_lr_scale"] == 0.5
        assert saved_meta["guard_rollbacks"] == 1

        # "new process": fresh monitor + fresh state, resumed from meta
        mon2 = DivergenceMonitor(ckpt, max_skips=2, lr_cut=0.5,
                                 max_rollbacks=3, log_fn=lambda m: None)
        state2 = _fresh_state(train_g, nc, ec, seed=9)
        state2 = mon2.resume_from_meta(state2, saved_meta)
        assert mon2.lr_scale == 0.5 and mon2.rollbacks == 1
        g = jax.tree_util.tree_map(np.ones_like, state2.params)
        u_base, _ = state.tx.update(g, state.tx.init(state.params),
                                    state.params)
        u_res, _ = state2.tx.update(g, state2.tx.init(state2.params),
                                    state2.params)
        for a, b in zip(_leaves(u_base), _leaves(u_res)):
            np.testing.assert_allclose(b, a * 0.5, rtol=1e-6)
        # no cut recorded -> state untouched
        state3 = _fresh_state(train_g, nc, ec, seed=3)
        mon3 = DivergenceMonitor(ckpt, log_fn=lambda m: None)
        assert mon3.resume_from_meta(state3, {"epoch": 0}) is state3
        ckpt.close()

    def test_nonfinite_loss_triggers_and_no_ckpt_continues(
            self, tiny_dataset, tmp_path):
        train_g, _, _ = tiny_dataset
        nc, ec = _caps(train_g)
        state = _fresh_state(train_g, nc, ec)
        ckpt = CheckpointManager(str(tmp_path / "empty"))
        mon = DivergenceMonitor(ckpt, log_fn=lambda m: None)
        nan_epoch = {"loss": float("nan"), "steps": 4}
        # divergence before any checkpoint exists: log and continue
        s, rolled = mon.observe(state, 0, nan_epoch)
        assert not rolled and s is state and mon.rollbacks == 0
        ckpt.save(state, {"epoch": 0})
        ckpt.wait()
        _, rolled = mon.observe(state, 1, nan_epoch)
        assert rolled and mon.rollbacks == 1
        ckpt.close()


class TestLoaderShutdown:
    @staticmethod
    def _alive_producers():
        return [t for t in threading.enumerate()
                if t.name == "cgnn-prefetch" and t.is_alive()]

    def test_producer_exits_when_consumer_abandons(self):
        """The epoch-abandonment fix: a consumer that stops mid-epoch
        (exception in the train loop) must not leave the producer
        blocked forever on a full queue."""
        batches = [np.zeros((4, 4)) for _ in range(64)]
        it = prefetch_to_device(iter(batches), size=2,
                                device_put=lambda x: x)
        next(it)
        assert self._alive_producers()
        it.close()  # what an exception in the consumer does via GC
        deadline = time.time() + 5
        while self._alive_producers() and time.time() < deadline:
            time.sleep(0.02)
        assert not self._alive_producers(), \
            "prefetch producer still alive after the consumer left"

    def test_normal_path_and_error_propagation_unchanged(self):
        batches = [np.full((2, 2), i) for i in range(16)]
        out = list(prefetch_to_device(iter(batches), size=2,
                                      device_put=lambda x: x))
        assert len(out) == 16
        np.testing.assert_array_equal(out[7], batches[7])

        def exploding():
            yield np.zeros(3)
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            list(prefetch_to_device(exploding(), device_put=lambda x: x))

    def test_injected_loader_exception_propagates(self, tiny_dataset):
        """faultinject.loader_exc surfaces through the prefetch thread
        to the consumer (and the producer still shuts down)."""
        faultinject.set_plan(faultinject.FaultPlan.parse("loader_exc=3"))
        batches = [np.zeros(2) for _ in range(8)]
        wrapped = faultinject.poison_batches(iter(batches))
        with pytest.raises(faultinject.InjectedLoaderError):
            list(prefetch_to_device(wrapped, device_put=lambda x: x))
        faultinject.set_plan(None)
        assert not self._alive_producers()


class TestFaultPlan:
    def test_parse_and_describe(self):
        p = faultinject.FaultPlan.parse(
            "nan_batch=5;sigterm_epoch=1;crash=after_write:2:exit")
        assert p.nan_batch == 5 and p.sigterm_epoch == 1
        assert p.crash_point == "after_write" and p.crash_hit == 2
        assert p.crash_exit is True
        desc = p.describe()
        assert "after_write" in desc and "os._exit" in desc

    def test_unknown_key_rejected(self):
        with pytest.raises(ValueError, match="unknown fault key"):
            faultinject.FaultPlan.parse("chaos_monkey=1")

    def test_no_plan_is_a_passthrough(self):
        faultinject.set_plan(None)
        batches = [np.zeros(1)]
        out = list(faultinject.poison_batches(iter(batches)))
        assert len(out) == 1 and out[0] is batches[0]  # unwrapped passthrough
        faultinject.crash_point("after_write")  # no-op
        faultinject.maybe_sigterm(0)  # no-op
