"""Telemetry subsystem tests (cgnn_tpu.observe).

The load-bearing guarantees, pinned:

- metrics.jsonl schema round-trips (epoch records, step records, events);
- the span trace is valid Chrome-trace JSON with consistent nesting;
- the run manifest carries config + device inventory;
- the in-scan step stream delivers per-step records from INSIDE the
  whole-epoch ``lax.scan`` whose weighted sum reconciles exactly with the
  epoch aggregates, and the scan trajectory (final params, per-epoch
  losses) is BIT-IDENTICAL with step telemetry on vs off;
- telemetry off is a true no-op: no callback is staged into the compiled
  HLO (off/epoch levels), while step level stages exactly the tap.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cgnn_tpu.data.dataset import (
    FeaturizeConfig,
    load_synthetic,
    train_val_test_split,
)
from cgnn_tpu.data.graph import PaddingStats, pack_graphs
from cgnn_tpu.models import CrystalGraphConvNet
from cgnn_tpu.observe import (
    MetricsLogger,
    SpanTracer,
    StepStream,
    Telemetry,
    hbm_gauges,
    padding_gauges,
    read_jsonl,
    write_manifest,
)
from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
from cgnn_tpu.train.loop import capacities_for, fit
from cgnn_tpu.train.step import make_train_step


@pytest.fixture(scope="module")
def tiny_dataset():
    graphs = load_synthetic(60, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                            seed=3, max_atoms=6)
    return train_val_test_split(graphs, 0.7, 0.15, seed=0)


class TestMetricsLogger:
    def test_schema_round_trip(self, tmp_path):
        log = MetricsLogger(str(tmp_path), use_clu=False)
        log.write(0, {"loss": 1.5, "mae": 0.25, "nan": float("nan")},
                  prefix="train")
        log.event("step", {"phase": "train", "step": 3, "loss": 0.5})
        log.event("hbm", {"device": "d0", "bytes_in_use": 123})
        log.close()
        recs = read_jsonl(str(tmp_path / "metrics.jsonl"))
        assert len(recs) == 3
        epoch = recs[0]
        assert epoch["step"] == 0 and epoch["train/loss"] == 1.5
        assert "train/nan" not in epoch  # NaNs dropped, as before
        assert recs[1]["event"] == "step" and recs[1]["loss"] == 0.5
        assert recs[2]["event"] == "hbm" and recs[2]["bytes_in_use"] == 123
        assert all("time" in r for r in recs)

    def test_append_and_thread_safety_smoke(self, tmp_path):
        import threading

        log = MetricsLogger(str(tmp_path), use_clu=False)

        def writer(i):
            for j in range(50):
                log.event("step", {"phase": "t", "step": i * 50 + j})

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        log.close()
        recs = read_jsonl(str(tmp_path / "metrics.jsonl"))
        assert len(recs) == 200  # no torn/interleaved lines


class TestSpans:
    def test_trace_json_valid_and_nested(self, tmp_path):
        tracer = SpanTracer()
        with tracer.span("outer", kind="test"):
            with tracer.span("inner", epoch=0):
                pass
            with tracer.span("inner", epoch=1):
                pass
        path = tracer.export(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len(events) == 3
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
            # chrome trace required fields
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        outer, = by_name["outer"]
        for inner in by_name["inner"]:
            # inner spans nest inside outer's interval, one level deeper
            assert inner["ts"] >= outer["ts"]
            assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
            assert inner["args"]["depth"] == outer["args"]["depth"] + 1
        assert by_name["inner"][0]["args"]["epoch"] == 0


class TestManifest:
    def test_write_manifest(self, tmp_path):
        path = write_manifest(str(tmp_path), {"batch_size": 32, "lr": 0.01},
                              task="regression",
                              mesh_shape={"data": 1, "graph": 1})
        m = json.load(open(path))
        assert m["config"]["batch_size"] == 32
        assert m["device_count"] == len(jax.devices())
        assert m["devices"][0]["platform"] == "cpu"
        assert m["task"] == "regression"
        assert m["mesh_shape"] == {"data": 1, "graph": 1}
        # this repo is a git checkout, so the SHA must be present here
        assert len(m.get("git_sha", "")) == 40


class TestGauges:
    def test_padding_gauges_per_bucket(self, tiny_dataset):
        from cgnn_tpu.data.graph import bucketed_batch_iterator

        train_g, _, _ = tiny_dataset
        stats = PaddingStats()
        batches = list(stats.wrap(bucketed_batch_iterator(train_g, 8, 2)))
        assert len(batches) >= 2
        gauges = padding_gauges(stats)
        buckets = [g for g in gauges if g["bucket"] != "overall"]
        overall = [g for g in gauges if g["bucket"] == "overall"]
        assert len(buckets) == len(stats.shapes) and len(overall) == 1
        for g in buckets:
            assert 0.0 < g["node_efficiency"] <= 1.0
            assert 0.0 < g["edge_efficiency"] <= 1.0
        assert sum(g["batches"] for g in buckets) == stats.batches
        # per-bucket accumulators reconcile with the overall figures
        tot_real = sum(stats.per_shape[s][0] for s in stats.per_shape)
        assert tot_real == stats.real_nodes

    def test_hbm_gauges_cpu_has_no_device_memory(self):
        recs = hbm_gauges()
        assert len(recs) == len(jax.devices())
        # the CPU backend's memory_stats() is None: nothing to report
        assert all(r["source"] == "none" and "bytes_limit" not in r
                   for r in recs)


class TestStepStream:
    def test_tap_inside_jit_and_scan(self, tmp_path):
        log = MetricsLogger(str(tmp_path), use_clu=False)
        stream = StepStream(log)

        def body(carry, x):
            metrics = {"loss_sum": x * 2.0, "count": jnp.float32(4.0)}
            stream.tap(metrics, "train", step=carry)
            return carry + 1, metrics["loss_sum"]

        @jax.jit
        def run(carry, xs):
            return jax.lax.scan(body, carry, xs)

        xs = jnp.arange(5, dtype=jnp.float32)
        run(jnp.int32(0), xs)
        jax.effects_barrier()
        recs = stream.records("train")
        assert len(recs) == 5
        by_step = {r["step"]: r for r in recs}
        # derived per-step mean: loss_sum / count
        assert by_step[2]["loss"] == pytest.approx(2 * 2.0 / 4.0)
        assert by_step[0]["count"] == 4.0
        log.close()
        file_steps = [r for r in read_jsonl(log.path)
                      if r.get("event") == "step"]
        assert len(file_steps) == 5

    def test_muted_drops_records(self):
        stream = StepStream(None)

        @jax.jit
        def f(x):
            stream.tap({"loss_sum": x, "count": jnp.float32(1.0)}, "train",
                       step=jnp.int32(1))
            return x + 1

        with stream.muted():
            f(jnp.float32(3.0))
            jax.effects_barrier()
        assert stream.records() == []
        f(jnp.float32(3.0))
        jax.effects_barrier()
        assert len(stream.records()) == 1


def _fresh_state(train_g, node_cap, edge_cap, batch_size=8):
    model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=24)
    tx = make_optimizer(optim="adam", lr=0.01)
    normalizer = Normalizer.fit(np.stack([g.target for g in train_g]))
    example = pack_graphs(train_g[:batch_size], node_cap, edge_cap,
                          batch_size)
    return create_train_state(model, example, tx, normalizer,
                              rng=jax.random.key(0))


class TestScanParityAndNoOp:
    def _run(self, tiny_dataset, tmp_path, level, epochs=3):
        train_g, val_g, _ = tiny_dataset
        node_cap, edge_cap = capacities_for(train_g, 8)
        state = _fresh_state(train_g, node_cap, edge_cap)
        telemetry = Telemetry(level, str(tmp_path / level))
        state, result = fit(
            state, train_g, val_g, epochs=epochs, batch_size=8,
            node_cap=node_cap, edge_cap=edge_cap, print_freq=0, seed=11,
            scan_epochs=True, log_fn=lambda *a: None, telemetry=telemetry,
        )
        telemetry.close()
        params = jax.tree_util.tree_map(np.asarray, state.params)
        return params, result, telemetry

    def test_scan_trajectory_bit_identical_with_step_telemetry(
            self, tiny_dataset, tmp_path):
        """The acceptance criterion: --telemetry step on the scan path
        must not move the trajectory AT ALL (the tap only reads metric
        scalars; grad-health metrics are extra outputs)."""
        p_off, r_off, _ = self._run(tiny_dataset, tmp_path, "off")
        p_step, r_step, t_step = self._run(tiny_dataset, tmp_path, "step")
        for a, b in zip(jax.tree_util.tree_leaves(p_off),
                        jax.tree_util.tree_leaves(p_step)):
            assert np.array_equal(a, b)  # bitwise
        for h_off, h_step in zip(r_off["history"], r_step["history"]):
            assert h_off["train"]["loss"] == h_step["train"]["loss"]
            assert h_off["val"]["mae"] == h_step["val"]["mae"]

        # per-step records streamed from inside the scan reconcile with
        # the epoch aggregates exactly (same (sum, count) arithmetic)
        recs = read_jsonl(os.path.join(str(tmp_path / "step"),
                                       "metrics.jsonl"))
        steps = [r for r in recs
                 if r.get("event") == "step" and r["phase"] == "train"]
        total_steps = sum(h["train"]["steps"] for h in r_step["history"])
        assert len(steps) == total_steps
        w_stream = sum(r["loss"] * r["count"] for r in steps)
        c_stream = sum(r["count"] for r in steps)
        w_epoch = sum(h["train"]["loss"] * h["train"]["count"]
                      for h in r_step["history"])
        assert w_stream / c_stream == pytest.approx(
            w_epoch / c_stream, rel=1e-5)
        # grad health rode along every step record
        assert all("grad_norm" in r and "nonfinite_grads" in r
                   for r in steps)
        assert all(r["nonfinite_grads"] == 0.0 for r in steps)
        # optimizer step numbers are the in-graph counter: a contiguous
        # 1..N run regardless of callback arrival order
        assert sorted(r["step"] for r in steps) == list(
            range(1, total_steps + 1))
        # eval records streamed too
        assert any(r.get("event") == "step" and r["phase"] == "eval"
                   for r in recs)

    def test_epoch_level_writes_epochs_and_summary_but_no_steps(
            self, tiny_dataset, tmp_path):
        _, _, _ = self._run(tiny_dataset, tmp_path, "epoch", epochs=1)
        recs = read_jsonl(os.path.join(str(tmp_path / "epoch"),
                                       "metrics.jsonl"))
        assert not any(r.get("event") == "step" for r in recs)
        summaries = [r for r in recs if r.get("event") == "run_summary"]
        assert len(summaries) == 1
        assert summaries[0]["counters"]["scan_steps"] > 0
        assert summaries[0]["gauges"]["scan_dispatch_share"] == 1.0
        paddings = [r for r in recs if r.get("event") == "padding"]
        assert any(p["bucket"] == "overall" for p in paddings)
        # trace exported with the epoch spans
        trace = json.load(open(os.path.join(str(tmp_path / "epoch"),
                                            "trace.json")))
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"pack", "stage_scan_stacks", "epoch"} <= names

    def test_off_level_stages_no_callback_into_hlo(self, tiny_dataset,
                                                   tmp_path):
        """--telemetry off/epoch is a true no-op: the compiled step HLO
        contains no host callback; step level stages exactly the tap."""
        train_g, _, _ = tiny_dataset
        node_cap, edge_cap = capacities_for(train_g, 8)
        state = _fresh_state(train_g, node_cap, edge_cap)
        batch = pack_graphs(train_g[:8], node_cap, edge_cap, 8)

        plain = jax.jit(make_train_step())
        text_off = plain.lower(state, batch).as_text()
        assert "callback" not in text_off.lower()

        stream = StepStream(None)
        tapped = jax.jit(stream.wrap_train(make_train_step()))
        text_step = tapped.lower(state, batch).as_text()
        assert "callback" in text_step.lower()

        # and through the driver: telemetry below step level stages none
        from cgnn_tpu.train.loop import ScanEpochDriver
        from cgnn_tpu.train.step import make_eval_step

        batches = [batch]
        drv = ScanEpochDriver(
            make_train_step(), make_eval_step(), batches, [],
            np.random.default_rng(0),
            telemetry=Telemetry("epoch", str(tmp_path / "drv")),
        )
        assert drv._tap is None
        key = next(iter(drv._train_groups))
        fn = drv._scan_fn(drv._train_scans, (key, 1), drv._train_body, True)
        text_scan = fn.lower(
            state, drv._train_groups[key],
            jnp.zeros(1, jnp.int32),
        ).as_text()
        assert "callback" not in text_scan.lower()


class TestGradHealth:
    def test_metrics_present_and_finite(self, tiny_dataset):
        train_g, _, _ = tiny_dataset
        node_cap, edge_cap = capacities_for(train_g, 8)
        state = _fresh_state(train_g, node_cap, edge_cap)
        batch = pack_graphs(train_g[:8], node_cap, edge_cap, 8)
        step = jax.jit(make_train_step(grad_health=True))
        state, metrics = step(state, batch)
        for k in ("grad_norm_sum", "update_norm_sum", "nonfinite_grads_sum",
                  "nonfinite_loss_sum"):
            assert k in metrics
        assert float(metrics["grad_norm_sum"]) > 0.0
        assert float(metrics["update_norm_sum"]) > 0.0
        assert float(metrics["nonfinite_grads_sum"]) == 0.0
        assert float(metrics["nonfinite_loss_sum"]) == 0.0

    def test_nan_onset_is_counted(self, tiny_dataset):
        """Poisoned inputs surface as nonfinite grad/loss counts — the
        signal that used to be invisible inside the epoch scan."""
        import dataclasses

        train_g, _, _ = tiny_dataset
        node_cap, edge_cap = capacities_for(train_g, 8)
        state = _fresh_state(train_g, node_cap, edge_cap)
        batch = pack_graphs(train_g[:8], node_cap, edge_cap, 8)
        bad = dataclasses.replace(
            batch, targets=np.full_like(batch.targets, np.nan)
        )
        step = jax.jit(make_train_step(grad_health=True))
        _, metrics = step(state, bad)
        assert float(metrics["nonfinite_loss_sum"]) == 1.0
        assert float(metrics["nonfinite_grads_sum"]) > 0.0


class TestLoaderTelemetry:
    def test_prefetch_counters(self, tmp_path):
        from cgnn_tpu.data.loader import prefetch_to_device

        telemetry = Telemetry("epoch", str(tmp_path))
        batches = [jnp.ones(4) * i for i in range(5)]
        out = list(prefetch_to_device(iter(batches), telemetry=telemetry))
        assert len(out) == 5
        counters = telemetry.counters()
        assert counters.get("loader_put_s", 0.0) >= 0.0
        assert "loader_wait_s" in counters
        telemetry.close()


class TestDataParallelStepStream:
    def test_dp_per_step_loop_streams(self, tiny_dataset, tmp_path):
        """The PR-1 known gap, closed (ISSUE 3): the DP PER-STEP loop
        (scan_epochs=False) now emits per-step stream records — the tap
        rides an outer jit around the shard_map step, carrying the
        replicated post-psum metric sums (one record per step, not one
        per device)."""
        from cgnn_tpu.parallel import fit_data_parallel
        from cgnn_tpu.parallel.mesh import make_mesh
        from cgnn_tpu.train.loop import capacities_for

        train, val, _ = tiny_dataset
        telemetry = Telemetry("step", str(tmp_path), use_clu=False)
        nc, ec = capacities_for(train, 4)
        state = _fresh_state(train, nc, ec, batch_size=4)
        fit_data_parallel(
            state, train, val, epochs=1, batch_size=4,
            node_cap=nc, edge_cap=ec, mesh=make_mesh(2),
            print_freq=0, log_fn=lambda *a, **k: None,
            telemetry=telemetry, scan_epochs=False,
        )
        recs = telemetry.stream.records("train")
        assert recs, "DP per-step loop emitted no stream records"
        n_steps = max(r["step"] for r in recs)
        # one record per optimizer step (not per device)
        assert len(recs) == len({r["step"] for r in recs})
        assert all("loss" in r for r in recs)
        telemetry.close()
        events = [r for r in read_jsonl(str(tmp_path / "metrics.jsonl"))
                  if r.get("event") == "step" and r.get("phase") == "train"]
        assert len(events) >= n_steps
