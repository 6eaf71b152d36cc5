"""Closed-loop continual learning tests (cgnn_tpu.continual; ISSUE 18).

The load-bearing guarantees, pinned:

- the label journal joins late ground truth EXACTLY ONCE — per trace id
  (hedged/retried requests share one), across duplicate POSTs, and
  across a process restart replaying the same stream;
- the canary gate is a pure decision core: promote / hold / rollback
  are deterministic functions of injected clock + samples, latency
  breaches out-rank MAE, and an undecided window is never promotable;
- the reload watcher's gate holds fleet replicas at the approved
  version while a trainer commits candidates into the SAME directory,
  and a pin overrides everything (including downgrades — the rollback
  path);
- a canary rollback dumps a flight-recorder bundle NAMING the
  regressing version, and the rejected candidate is never re-evaluated;
- per-version labeled histogram families render under one family
  declaration and merge label-set by label-set;
- training while serving holds the lock discipline (racecheck clean).
"""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from cgnn_tpu.analysis import racecheck
from cgnn_tpu.continual import (
    CanaryController,
    CanaryGate,
    ContinualTrainer,
    GateConfig,
    GateStats,
    JournalTail,
    LabelJournal,
)
from cgnn_tpu.continual.journal import iter_labeled_graphs
from cgnn_tpu.observe import flightrec
from cgnn_tpu.observe.export import MetricsRegistry, parse_prometheus_text
from cgnn_tpu.observe.hist import merge_snapshot_maps


# ---------------------------------------------------------------- journal


def _serve(j, tid, pred=1.0, fp=None, payload=None, version="ckpt-00000001"):
    j.note_served(trace_id=tid, payload=payload, prediction=pred,
                  param_version=version, fingerprint=fp, ts=123.0)


class TestLabelJournal:
    def test_round_trip_and_exactly_once(self):
        j = LabelJournal()
        _serve(j, "t1", pred=2.0)
        assert j.join(2.5, trace_id="t1") == "joined"
        recs = j.labeled_records()
        assert len(recs) == 1
        rec = recs[0]
        assert rec["trace_id"] == "t1"
        assert rec["prediction"] == 2.0 and rec["label"] == 2.5
        assert rec["param_version"] == "ckpt-00000001"
        assert rec["join_seq"] == 1 == j.join_seq
        # a retransmitted label is acknowledged, never applied: the
        # stored value is immutable and the duplicate is counted
        assert j.join(9.9, trace_id="t1") == "already"
        assert j.labeled_records()[0]["label"] == 2.5
        s = j.stats()
        assert s["joined"] == 1 and s["duplicate_joins"] == 1

    def test_hedged_retry_shares_one_record(self):
        # hedged/retried attempts re-report under the SAME trace id:
        # the journal keeps one record, so one label joins exactly once
        j = LabelJournal()
        _serve(j, "t1", pred=1.0)
        _serve(j, "t1", pred=1.0)  # the hedge's duplicate report
        assert j.stats()["served"] == 1
        assert j.join(1.5, trace_id="t1") == "joined"
        assert j.join(1.5, trace_id="t1") == "already"
        assert j.stats()["joined"] == 1

    def test_fingerprint_join_lands_oldest_unlabeled(self):
        j = LabelJournal()
        _serve(j, "t1", fp="fp-a")
        _serve(j, "t2", fp="fp-a")
        assert j.join(1.0, fingerprint="fp-a") == "joined"
        assert j.labeled_records()[0]["trace_id"] == "t1"
        assert j.join(2.0, fingerprint="fp-a") == "joined"
        assert {r["trace_id"] for r in j.labeled_records()} == {"t1", "t2"}
        # all records for the print labeled: the next one is a duplicate
        assert j.join(3.0, fingerprint="fp-a") == "already"

    def test_unmatched_label(self):
        j = LabelJournal()
        assert j.join(1.0, trace_id="nope") == "unmatched"
        assert j.stats()["unmatched_labels"] == 1
        with pytest.raises(ValueError):
            j.join(1.0)

    def test_capacity_eviction(self):
        j = LabelJournal(capacity=2)
        for i in range(3):
            _serve(j, f"t{i}", fp=f"fp{i}")
        s = j.stats()
        assert s["evicted"] == 1 and s["resident"] == 2
        # the evicted record (and its fingerprint index entry) is gone
        assert j.join(1.0, trace_id="t0") == "unmatched"
        assert j.join(1.0, fingerprint="fp0") == "unmatched"
        assert j.join(1.0, trace_id="t2") == "joined"

    def test_labeled_records_after_seq(self):
        j = LabelJournal()
        for i in range(4):
            _serve(j, f"t{i}")
        for i in range(3):
            j.join(float(i), trace_id=f"t{i}")
        assert [r["trace_id"] for r in j.labeled_records(after_seq=1)] == [
            "t1", "t2"]

    def test_replay_preserves_exactly_once(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        j = LabelJournal(path)
        _serve(j, "t1", pred=1.0)
        _serve(j, "t2", pred=2.0)
        assert j.join(1.5, trace_id="t1") == "joined"
        assert j.join(1.5, trace_id="t1") == "already"
        j.close()
        # restart: rebuild from the stream through the SAME apply path
        j2 = LabelJournal.replay(path)
        assert j2.stats()["served"] == 2 and j2.stats()["joined"] == 1
        assert j2.labeled_records()[0]["label"] == 1.5
        # the replayed duplicate did not double-apply, and a NEW
        # retransmission still answers 'already'
        assert j2.join(9.0, trace_id="t1") == "already"
        assert j2.join(2.5, trace_id="t2") == "joined"

    def test_tail_survives_rotation(self, tmp_path):
        # writer rotates mid-stream (several times); a tail polling
        # faster than the rotation cadence must deliver every line
        # exactly once across each os.replace
        path = str(tmp_path / "rot.jsonl")
        writer = LabelJournal(path, max_bytes=2048)
        tail = JournalTail(path)
        follower = LabelJournal()
        n = 40
        for k in range(n):
            _serve(writer, f"t{k}", pred=float(k))
            writer.join(float(k) + 0.5, trace_id=f"t{k}")
            tail.follow_into(follower)
        tail.follow_into(follower)
        assert os.path.exists(path + ".1")  # rotation actually happened
        ws, fs = writer.stats(), follower.stats()
        assert fs["served"] == ws["served"] == n
        assert fs["joined"] == ws["joined"] == n
        assert fs["duplicate_joins"] == 0
        writer.close()
        tail.close()

    def test_iter_labeled_graphs_round_trip(self):
        from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic

        g = load_synthetic(1, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                           seed=3, max_atoms=8)[0]
        payload = {"graph": {
            "atom_fea": np.asarray(g.atom_fea).tolist(),
            "edge_fea": np.asarray(g.edge_fea).tolist(),
            "centers": np.asarray(g.centers).tolist(),
            "neighbors": np.asarray(g.neighbors).tolist(),
            "id": g.cif_id,
        }}
        j = LabelJournal()
        _serve(j, "t1", payload=payload)
        _serve(j, "t2", payload=None)        # accounting-only: skipped
        _serve(j, "t3", payload={"structure": {}})  # raw wire: skipped
        for t in ("t1", "t2", "t3"):
            j.join(7.25, trace_id=t)
        out = list(iter_labeled_graphs(j.labeled_records()))
        assert len(out) == 1
        g2, rec = out[0]
        assert rec["trace_id"] == "t1"
        # the replayed graph carries the TRUE target, not the prediction
        np.testing.assert_allclose(g2.target, [7.25])
        np.testing.assert_allclose(g2.atom_fea, g.atom_fea)
        np.testing.assert_array_equal(g2.neighbors, g.neighbors)


# ------------------------------------------------------------------ gate


def _stats(cand_n=100, cand_mae=1.0, cand_p99=10.0, base_n=100,
           base_mae=1.0):
    return GateStats(candidate_count=cand_n, candidate_mae=cand_mae,
                     candidate_p99_ms=cand_p99, baseline_count=base_n,
                     baseline_mae=base_mae)


class TestCanaryGate:
    CFG = GateConfig(min_samples=10, min_baseline=10, max_mae_ratio=1.05,
                     rollback_mae_ratio=1.25, p99_budget_ms=100.0,
                     min_window_s=2.0, max_window_s=60.0)

    def test_config_validation(self):
        with pytest.raises(ValueError):
            GateConfig(max_mae_ratio=1.3, rollback_mae_ratio=1.2)
        with pytest.raises(ValueError):
            GateConfig(min_samples=0)
        with pytest.raises(ValueError):
            GateConfig(min_window_s=10.0, max_window_s=5.0)

    def test_promote_within_ratio(self):
        g = CanaryGate(self.CFG)
        g.begin("ckpt-00000002", now=0.0)
        assert g.active == "ckpt-00000002"
        d = g.poll(3.0, _stats(cand_mae=1.02, base_mae=1.0))
        assert d.action == "promote" and d.reason == "ok"
        assert d.version == "ckpt-00000002"
        assert d.mae_ratio == pytest.approx(1.02)
        assert g.active is None  # one decision per window

    def test_holds_before_min_samples_and_min_window(self):
        g = CanaryGate(self.CFG)
        g.begin("v", now=0.0)
        # starved of shadow samples: hold
        assert g.poll(3.0, _stats(cand_n=5)) is None
        # starved of baseline: hold
        assert g.poll(3.0, _stats(base_n=5)) is None
        # inside min_window even with samples: hold (no verdict faster
        # than the floor, however good it looks)
        assert g.poll(1.0, _stats(cand_mae=0.5)) is None
        assert g.active == "v"

    def test_rollback_on_mae_ratio(self):
        g = CanaryGate(self.CFG)
        g.begin("v", now=0.0)
        d = g.poll(3.0, _stats(cand_mae=1.5, base_mae=1.0))
        assert d.action == "rollback" and d.reason == "mae"

    def test_latency_outranks_good_mae(self):
        g = CanaryGate(self.CFG)
        g.begin("v", now=0.0)
        d = g.poll(3.0, _stats(cand_mae=0.5, cand_p99=250.0))
        assert d.action == "rollback" and d.reason == "latency"

    def test_inconclusive_band_holds_then_window_expires(self):
        g = CanaryGate(self.CFG)
        g.begin("v", now=0.0)
        mid = _stats(cand_mae=1.15, base_mae=1.0)  # between 1.05 and 1.25
        assert g.poll(3.0, mid) is None
        assert g.poll(30.0, mid) is None
        d = g.poll(60.0, mid)
        assert d.action == "rollback" and d.reason == "window_expired"

    def test_starved_window_expires_to_rollback(self):
        # undecided is NOT promotable: no samples ever -> rollback
        g = CanaryGate(self.CFG)
        g.begin("v", now=0.0)
        d = g.poll(61.0, _stats(cand_n=0, base_n=0,
                                cand_mae=float("nan"),
                                base_mae=float("nan")))
        assert d.action == "rollback" and d.reason == "window_expired"

    def test_one_candidate_at_a_time(self):
        g = CanaryGate(self.CFG)
        g.begin("v1", now=0.0)
        with pytest.raises(RuntimeError):
            g.begin("v2", now=0.0)


# ---------------------------------------------------- watcher pin / gate
# (tiny real checkpoint dir + ParamStore: the satellite-b regression —
# a gated watcher must NOT auto-swap to an unevaluated trainer commit)


@pytest.fixture(scope="module")
def watch_parts():
    import jax

    from cgnn_tpu.config import DataConfig, ModelConfig, build_model
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic
    from cgnn_tpu.serve import plan_shape_set
    from cgnn_tpu.train import (
        Normalizer,
        create_train_state,
        make_optimizer,
    )

    graphs = load_synthetic(16, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                            seed=11, max_atoms=8)
    ss = plan_shape_set(graphs, 8, rungs=1)
    model_cfg = ModelConfig(atom_fea_len=8, n_conv=1, h_fea_len=16)
    model = build_model(model_cfg, DataConfig(radius=5.0, max_num_nbr=8))
    state = create_train_state(
        model, ss.pack([graphs[0]]), make_optimizer(),
        Normalizer.fit(np.stack([g.target for g in graphs])),
        rng=jax.random.key(7),
    )
    return model_cfg, state


def _commit(mgr, state, model_cfg, nudge=0.0):
    import jax

    from cgnn_tpu.config import DataConfig

    params = state.params
    if nudge:
        params = jax.tree_util.tree_map(
            lambda x: (np.asarray(x) + nudge).astype(np.asarray(x).dtype)
            if np.issubdtype(np.asarray(x).dtype, np.floating) else x,
            params,
        )
    mgr.save(state.replace(params=params),
             {"model": model_cfg.to_meta(),
              "data": DataConfig(radius=5.0, max_num_nbr=8).to_meta(),
              "task": "regression", "epoch": 0})
    mgr.wait()
    return mgr.newest_committed()


class TestWatcherPromotionGuard:
    def test_gate_holds_ungated_candidate(self, watch_parts, tmp_path):
        from cgnn_tpu.serve.reload import CheckpointWatcher, ParamStore
        from cgnn_tpu.train import CheckpointManager

        model_cfg, state = watch_parts
        mgr = CheckpointManager(str(tmp_path / "ckpt"),
                                log_fn=lambda m: None)
        v1 = _commit(mgr, state, model_cfg)
        store = ParamStore(state, v1)
        w = CheckpointWatcher(mgr, store, state, gate=v1,
                              log_fn=lambda m: None)
        # a continual trainer commits a CANDIDATE into the same dir:
        # the gated watcher must hold the line, not chase newest
        v2 = _commit(mgr, state, model_cfg, nudge=0.25)
        assert not w.poll_once()
        assert store.version == v1 and w.gate_holds == 1
        # the promotion broadcast raises the gate -> the swap happens
        w.set_gate(v2)
        assert w.poll_once()
        assert store.version == v2 and w.swaps == 1
        mgr.close()

    def test_gate_newer_than_current_converges_on_gate(self, watch_parts,
                                                       tmp_path):
        from cgnn_tpu.serve.reload import CheckpointWatcher, ParamStore
        from cgnn_tpu.train import CheckpointManager

        model_cfg, state = watch_parts
        mgr = CheckpointManager(str(tmp_path / "ckptg"),
                                log_fn=lambda m: None)
        v1 = _commit(mgr, state, model_cfg)
        v2 = _commit(mgr, state, model_cfg, nudge=0.25)
        v3 = _commit(mgr, state, model_cfg, nudge=0.5)
        store = ParamStore(state, v1)
        w = CheckpointWatcher(mgr, store, state, gate=v2,
                              log_fn=lambda m: None)
        # newest is v3 but the gate says v2: converge on the GATE —
        # the rolling-promotion step, never past the approved version
        assert w.poll_once()
        assert store.version == v2
        assert not w.poll_once()  # v3 still held
        assert store.version == v2 and w.gate_holds == 1
        assert mgr.newest_committed() == v3
        mgr.close()

    def test_pin_overrides_and_allows_downgrade(self, watch_parts,
                                                tmp_path):
        from cgnn_tpu.serve.reload import CheckpointWatcher, ParamStore
        from cgnn_tpu.train import CheckpointManager

        model_cfg, state = watch_parts
        mgr = CheckpointManager(str(tmp_path / "ckptp"),
                                log_fn=lambda m: None)
        v1 = _commit(mgr, state, model_cfg)
        v2 = _commit(mgr, state, model_cfg, nudge=0.25)
        store = ParamStore(state, v1)
        w = CheckpointWatcher(mgr, store, state, gate=v1,
                              log_fn=lambda m: None)
        # canary path: pin PAST the gate to the candidate
        w.set_pin(v2)
        assert w.poll_once() and store.version == v2
        # rollback path: pin DOWN to the fleet version
        w.set_pin(v1)
        assert w.poll_once() and store.version == v1
        # an uncommitted pin just retries (mid-commit candidate)
        w.set_pin("ckpt-99999999")
        assert not w.poll_once() and store.version == v1
        # clearing the pin resumes gate behaviour (gate v1 holds v2)
        w.set_pin(None)
        assert not w.poll_once() and store.version == v1
        ctl = w.control()
        assert ctl["pin"] is None and ctl["gate"] == v1
        assert ctl["version"] == v1
        mgr.close()


# ------------------------------------------------------------ controller


class FakeFleet:
    """Duck-typed fleet adapter: instant pin convergence, scripted
    shadow answers."""

    def __init__(self, fleet_v="ckpt-00000001", shadow_fn=None):
        self.fleet_v = fleet_v
        self.pinned = None          # what the canary replica serves
        self.shadow_fn = shadow_fn or (lambda payload: 1.1)
        self.shadow_latency_ms = 5.0
        self.calls = []

    def fleet_version(self):
        return self.fleet_v

    def begin_canary(self, version):
        self.calls.append(("begin", version))
        self.pinned = version
        return "r-canary"

    def canary_version(self, rid):
        return self.pinned

    def shadow_predict(self, rid, payload, timeout_s):
        self.calls.append(("shadow", rid))
        return self.shadow_fn(payload), self.shadow_latency_ms

    def promote(self, rid, version):
        self.calls.append(("promote", version))
        self.fleet_v = version
        self.pinned = None

    def abort_canary(self, rid, to_version):
        self.calls.append(("abort", to_version))
        self.pinned = to_version

    def end_canary(self, rid):
        self.calls.append(("end", rid))
        self.pinned = None


def _controller(journal, fleet, newest, tmp_path=None, **kw):
    gate = CanaryGate(GateConfig(
        min_samples=4, min_baseline=4, max_mae_ratio=1.05,
        rollback_mae_ratio=1.25, p99_budget_ms=1000.0,
        min_window_s=0.0, max_window_s=60.0))
    rec = None
    if tmp_path is not None:
        rec = flightrec.FlightRecorder(str(tmp_path / "flightrec"),
                                       role="test", log_fn=lambda m: None)
    return CanaryController(
        gate=gate, journal=journal, fleet=fleet, newest_fn=lambda: newest,
        flightrec=rec, log_fn=lambda m: None, **kw), rec


def _feed_labels(journal, n, *, pred=1.0, label=1.1, version=None,
                 start=0):
    for i in range(start, start + n):
        journal.note_served(trace_id=f"t{i}", payload={"graph": {"i": i}},
                            prediction=pred, param_version=version,
                            fingerprint=None, ts=None)
        journal.join(label, trace_id=f"t{i}")


class TestCanaryController:
    CAND = "ckpt-00000002"
    FLEET = "ckpt-00000001"

    def test_promote_flow(self):
        j = LabelJournal()
        fleet = FakeFleet(self.FLEET, shadow_fn=lambda p: 1.1)  # == label
        ctl, _ = _controller(j, fleet, self.CAND)
        ctl.tick(now=0.0)    # idle -> pinning (one replica pulled)
        assert ("begin", self.CAND) in fleet.calls
        ctl.tick(now=0.1)    # pin converged -> evaluating, gate opens
        assert ctl.gate.active == self.CAND
        # labeled live traffic arrives: live err 0.1, shadow err 0.0
        _feed_labels(j, 6, pred=1.0, label=1.1, version=self.FLEET)
        ctl.tick(now=0.5)
        # decision landed THIS tick: ratio 0 <= 1.05 -> fleet-wide gate
        assert ("promote", self.CAND) in fleet.calls
        assert fleet.fleet_v == self.CAND
        s = ctl.stats()
        assert s["state"] == "idle" and s["candidate"] is None
        assert s["shadow_sent"] == 6 and s["live_observed"] == 6
        kinds = [e["kind"] for e in s["events"]]
        assert kinds == ["canary_begin", "canary_pinned", "promoted"]

    def test_mirror_fraction_subsamples(self):
        j = LabelJournal()
        fleet = FakeFleet(self.FLEET)
        ctl, _ = _controller(j, fleet, self.CAND, mirror_fraction=0.5)
        ctl.tick(now=0.0)
        ctl.tick(now=0.1)
        _feed_labels(j, 8, version=self.FLEET)
        ctl.tick(now=0.2)
        # deterministic accumulator: exactly half the eligible records
        # mirrored; every label still counts toward the live baseline
        assert ctl.shadow_sent == 4 and ctl.live_observed == 8

    def test_rollback_names_version_in_bundle(self, tmp_path):
        j = LabelJournal()
        # the regressing candidate: shadow answers are far off truth
        fleet = FakeFleet(self.FLEET, shadow_fn=lambda p: 11.0)
        ctl, rec = _controller(j, fleet, self.CAND, tmp_path=tmp_path)
        ctl.tick(now=0.0)
        ctl.tick(now=0.1)
        _feed_labels(j, 6, pred=1.0, label=1.1, version=self.FLEET)
        ctl.tick(now=0.5)    # ratio ~99 >= 1.25 -> rollback begins
        assert ("abort", self.FLEET) in fleet.calls
        assert self.CAND in ctl.rejected
        ctl.tick(now=0.6)    # canary converged back -> returned to pool
        assert ("end", "r-canary") in fleet.calls
        assert ctl.stats()["state"] == "idle"
        assert fleet.fleet_v == self.FLEET  # fleet never moved
        # a rejected candidate is never re-evaluated
        begins = [c for c in fleet.calls if c[0] == "begin"]
        ctl.tick(now=1.0)
        assert [c for c in fleet.calls if c[0] == "begin"] == begins
        # the accountability pin: the bundle dir NAMES the version
        deadline = time.monotonic() + 10.0
        pat = os.path.join(str(tmp_path / "flightrec"),
                           f"bundle-*canary_rollback_{self.CAND}",
                           "manifest.json")
        while not glob.glob(pat) and time.monotonic() < deadline:
            time.sleep(0.05)
        manifests = glob.glob(pat)
        assert manifests, f"no rollback bundle matching {pat}"
        # the recorder's thread may still be writing the file it has just
        # created (seen once under six workers, PR 42): read it whole
        while True:
            try:
                with open(manifests[0]) as f:
                    manifest = json.load(f)
                break
            except json.JSONDecodeError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.05)
        assert self.CAND in json.dumps(manifest)

    def test_pin_timeout_rejects_candidate(self):
        j = LabelJournal()
        fleet = FakeFleet(self.FLEET)
        ctl, _ = _controller(j, fleet, self.CAND)
        # the pin never converges (dead replica / corrupt save)
        fleet.canary_version = lambda rid: None
        ctl.tick(now=0.0)           # -> pinning
        ctl.tick(now=30.0)          # inside the deadline: still waiting
        assert ctl.stats()["state"] == "pinning"
        ctl.tick(now=61.0)          # past max_window_s: reject
        assert self.CAND in ctl.rejected
        assert ("abort", self.FLEET) in fleet.calls

    def test_idle_when_no_new_candidate(self):
        j = LabelJournal()
        fleet = FakeFleet(self.FLEET)
        # newest == fleet version: nothing to evaluate
        ctl, _ = _controller(j, fleet, self.FLEET)
        ctl.tick(now=0.0)
        assert ctl.stats()["state"] == "idle"
        assert not fleet.calls


# ----------------------------------------- per-version labeled metrics


class TestPerVersionMetrics:
    def test_labeled_families_render_and_merge(self):
        j = LabelJournal()
        fleet = FakeFleet("ckpt-00000001")
        ctl, _ = _controller(j, fleet, "ckpt-00000002")
        ctl._observe_live("ckpt-00000001", 0.1)
        ctl._observe_live("ckpt-00000001", 0.2)
        ctl._observe_shadow("ckpt-00000002", 0.15, 5.0)
        reg = MetricsRegistry(namespace="fleet")
        reg.add_provider("canary",
                         lambda: {"histograms": ctl.metrics_histograms()})
        text = reg.prometheus_text()
        # ONE family declaration, labels riding every sample
        assert text.count("# TYPE fleet_fleet_label_mae_hist histogram") == 1
        assert 'param_version="ckpt-00000001"' in text
        assert 'param_version="ckpt-00000002"' in text
        fams = parse_prometheus_text(text)
        mae = fams["fleet_fleet_label_mae_hist"]["histogram"]
        assert len(mae) == 2  # one snapshot per label set
        counts = sorted(int(s["count"]) for s in mae.values())
        assert counts == [1, 2]
        # the fleet merge is label-set-aware: two replicas' expositions
        # pool per version, never across versions
        merged = merge_snapshot_maps([mae, mae])
        assert sorted(int(s["count"]) for s in merged.values()) == [2, 4]


# ------------------------------------- trainer + concurrent racecheck


@pytest.fixture
def rc_enabled():
    was = racecheck.enabled()
    racecheck.enable(True)
    racecheck.reset()
    yield racecheck
    racecheck.reset()
    racecheck.enable(was)


def _graph_payload(g):
    return {"graph": {
        "atom_fea": np.asarray(g.atom_fea).tolist(),
        "edge_fea": np.asarray(g.edge_fea).tolist(),
        "centers": np.asarray(g.centers).tolist(),
        "neighbors": np.asarray(g.neighbors).tolist(),
        "id": g.cif_id,
    }}


class TestContinualTrainer:
    def test_requires_exactly_one_journal(self):
        with pytest.raises(ValueError):
            ContinualTrainer("/tmp/x")
        with pytest.raises(ValueError):
            ContinualTrainer("/tmp/x", journal=LabelJournal(),
                             journal_path="/tmp/y")

    def test_gates_hold_without_labels_or_interval(self, tmp_path):
        j = LabelJournal()
        t = ContinualTrainer(str(tmp_path / "ckpt"), journal=j,
                             min_new_labels=4, min_interval_s=100.0,
                             clock=lambda: 0.0, log_fn=lambda m: None)
        # no labels: the cadence gate holds before any train-side boot
        assert t.poll_once(now=1000.0) is None
        assert t.rounds == 0 and t.stats()["commits"] == []

    def test_train_while_serving_racecheck_clean(self, rc_enabled,
                                                 tmp_path):
        """The first workload that trains WHILE the same process
        serves: journal appends + label joins + canary ticks race a
        real fine-tune round under the instrumented locks; the run
        must finish with zero inversions and zero shared-field
        violations, and the round must actually COMMIT a candidate."""
        from cgnn_tpu.config import DataConfig
        from cgnn_tpu.data.dataset import load_synthetic
        from cgnn_tpu.train import CheckpointManager
        from scripts.serve_loadgen import make_synth_ckpt

        ckpt = str(tmp_path / "ckpt")
        make_synth_ckpt(ckpt)
        mgr = CheckpointManager(ckpt)
        v1 = mgr.newest_committed()
        graphs = load_synthetic(
            32, DataConfig(radius=6.0, max_num_nbr=12).featurize_config(),
            seed=5)
        journal = LabelJournal()
        trainer = ContinualTrainer(
            ckpt, journal=journal, min_new_labels=24, min_interval_s=0.0,
            batch_size=8, epochs_per_round=1, max_rounds=1,
            log_fn=lambda m: None)
        fleet = FakeFleet(v1)
        ctl, _ = _controller(journal, fleet, None)
        stop = threading.Event()

        def serve_side():
            # the serving hook's exact append path: note_served on every
            # answer, a late join per trace — while training runs
            for i, g in enumerate(graphs):
                journal.note_served(
                    trace_id=f"s{i}", payload=_graph_payload(g),
                    prediction=float(np.asarray(g.target).reshape(-1)[0]),
                    param_version=v1, fingerprint=None, ts=None)
                journal.join(float(np.asarray(g.target).reshape(-1)[0]),
                             trace_id=f"s{i}")
                time.sleep(0.002)

        def canary_side():
            while not stop.wait(0.01):
                racecheck.heartbeat()
                ctl.tick()

        threads = [threading.Thread(target=serve_side, name="serve-feed"),
                   threading.Thread(target=canary_side, name="canary-tick")]
        for t in threads:
            t.start()
        try:
            deadline = time.monotonic() + 120.0
            name = None
            while name is None and time.monotonic() < deadline:
                name = trainer.poll_once()
                time.sleep(0.05)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert name is not None, "trainer never committed a candidate"
        assert name != v1 and mgr.is_committed(name)
        assert trainer.stats()["rounds"] == 1
        # the committed meta records its continual provenance
        meta = mgr.read_meta(name)
        assert meta.get("continual_round") == 1
        assert meta.get("replay_labels", 0) >= 24
        trainer.close()
        mgr.close()
        rep = racecheck.report()
        assert rep["inversions"] == [], rep["inversions"]
        assert rep["violations"] == [], rep["violations"]
