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
                              mesh_shape={"dcn": 1, "data": 1})
        m = json.load(open(path))
        assert m["config"]["batch_size"] == 32
        assert m["device_count"] == len(jax.devices())
        assert m["devices"][0]["platform"] == "cpu"
        assert m["task"] == "regression"
        assert m["mesh_shape"] == {"dcn": 1, "data": 1}
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
        fn = drv._window_fn(drv._train_scans, (key, 1), drv._train_body,
                            True)
        text_scan = fn.lower(
            state, drv._train_groups[key],
            jnp.zeros(1, jnp.int32), jnp.int32(0),
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


# op_names as XLA records them: the flax-scoped ones are lines of
# HLO_TRAIN_STEP.txt.gz (a compiled flagship step), the named-scope ones
# are what today's scan program compiles to
_SCAN = "jit(scan_train_n672_l2)/while/body/closed_call/"
_FWD = _SCAN + "jvp(CrystalGraphConvNet)/"
_BWD = _SCAN + "transpose(jvp(CrystalGraphConvNet))/"
_F2 = "jit(scan_train_n192_l2)/while/body/closed_call/"
_LM = "jit(scan_train_n16384_l2)/while/body/closed_call/"
_CLASSIFIED = [
    ("jit(train_step)/jvp(CrystalGraphConvNet)/conv_1/bn1/mul",
     ("conv.bn1", "fwd")),
    ("jit(train_step)/transpose(jvp(CrystalGraphConvNet))/conv_2/bn1/"
     "reduce_sum", ("conv.bn1", "bwd")),
    ("jit(train_step)/jvp(CrystalGraphConvNet)/conv_0/bn2/jit(_where)/"
     "select_n", ("conv.bn2", "fwd")),
    (_BWD + "conv_0/bn2/add_any", ("conv.bn2", "bwd")),
    (_FWD + "conv_1/conv.bn2/jit(softplus)/log1p", ("conv.bn2", "fwd")),
    # LayerNorm after the sum in bn2's place (the Open Catalyst CGCNN): the
    # flax module "ln", and the scope its residual and softplus run under
    (_FWD + "conv_4/ln/rsqrt", ("conv.ln", "fwd")),
    (_BWD + "conv_4/ln/mul", ("conv.ln", "bwd")),
    (_FWD + "conv_5/conv.ln/jit(softplus)/log1p", ("conv.ln", "fwd")),
    ("jit(train_step)/jvp(CrystalGraphConvNet)/conv_0/fc_full/dot_general",
     ("conv.fc_full", "fwd")),
    ("jit(train_step)/transpose(jvp(CrystalGraphConvNet))/conv_1/fc_full/"
     "dot_general", ("conv.fc_full", "bwd")),
    (_FWD + "conv_0/conv.fc_full/convert_element_type",
     ("conv.fc_full", "fwd")),
    (_FWD + "conv_0/conv.gather/jit(_take)/gather", ("conv.gather", "fwd")),
    (_BWD + "conv_0/conv.gather/reduce_sum", ("conv.gather", "bwd")),
    (_FWD + "conv_0/conv.gate/exp", ("conv.gate", "fwd")),
    (_BWD + "conv_0/conv.gate/jit(softplus)/mul", ("conv.gate", "bwd")),
    (_FWD + "conv_1/conv.aggregate/reduce_sum", ("conv.aggregate", "fwd")),
    (_BWD + "conv_1/conv.aggregate/broadcast_in_dim",
     ("conv.aggregate", "bwd")),
    ("jit(train_step)/jvp(CrystalGraphConvNet)/embedding/"
     "convert_element_type", ("embed", "fwd")),
    (_BWD + "embed/embedding/dot_general", ("embed", "bwd")),
    ("jit(train_step)/jvp(CrystalGraphConvNet)/conv_to_fc/add",
     ("pool_head", "fwd")),
    ("jit(train_step)/transpose(jvp(CrystalGraphConvNet))/fc_out/"
     "dot_general", ("pool_head", "bwd")),
    (_FWD + "pool_head/fc_0/dot_general", ("pool_head", "fwd")),
    (_FWD + "pool_head/broadcast_in_dim;scan/squeeze", ("pool_head", "fwd")),
    (_SCAN + "expand/exp", ("expand", "fwd")),
    (_SCAN + "jvp(loss)/abs", ("loss", "fwd")),
    (_SCAN + "transpose(jvp(loss))/div", ("loss", "bwd")),
    (_SCAN + "optimizer/is_finite", ("optimizer", "fwd")),
    (_SCAN + "scan/dynamic_slice", ("scan", "fwd")),
    ("jit(scan_train_n672_l2)/scan/reduce_sum", ("scan", "fwd")),
    ("jit(scan_train_n672_l2)/while/body/dynamic_update_slice",
     ("scan", "fwd")),
    ("jit(scan_train_n672_l2)/while/cond/lt", ("scan", "fwd")),
    ("jit(scan_train_n672_l2)/while", ("scan", "fwd")),
    # the second-order (force) step, as its scan program compiles: the
    # direction is the number of reverse passes the instruction lies under
    (_F2 + "jvp(jvp(ForceFieldCGCNN))/edge_geom/sqrt", ("edge_geom", "fwd")),
    (_F2 + "jvp(transpose(jvp(ForceFieldCGCNN)))/edge_geom/add_any",
     ("edge_geom", "bwd")),
    (_F2 + "transpose(jvp(transpose(jvp(ForceFieldCGCNN))))/edge_geom/mul",
     ("edge_geom", "bwd2")),
    # the dense geometry (PR 28): one lattice an atom, the slot-major
    # position gather, its declared transpose (a row gather by in_slots, a
    # masked sum, the overflow tier's segment-sum) and that one's transpose
    (_F2 + "jvp(jvp(ForceFieldCGCNN))/edge_geom/gather", ("edge_geom", "fwd")),
    (_F2 + "jvp(jvp(ForceFieldCGCNN))/edge_geom/nmk,nkj->nmj/dot_general",
     ("edge_geom", "fwd")),
    (_F2 + "jvp(jvp(ForceFieldCGCNN))/edge_geom/jit(_take)/gather",
     ("edge_geom", "fwd")),
    (_F2 + "jvp(transpose(jvp(ForceFieldCGCNN)))/edge_geom/jit(_take)/gather",
     ("edge_geom", "bwd")),
    (_F2 + "jvp(transpose(jvp(ForceFieldCGCNN)))/edge_geom/reduce_sum",
     ("edge_geom", "bwd")),
    (_F2 + "jvp(transpose(jvp(ForceFieldCGCNN)))/edge_geom/scatter-add",
     ("edge_geom", "bwd")),
    (_F2 + "transpose(jvp(transpose(jvp(ForceFieldCGCNN))))/edge_geom/"
     "jit(_take)/gather", ("edge_geom", "bwd2")),
    (_F2 + "jvp(jvp(ForceFieldCGCNN))/force_readout/mul",
     ("force_readout", "fwd")),
    (_F2 + "transpose(jvp(jvp(ForceFieldCGCNN)))/force_readout/ForceHead_0/"
     "out/add_any", ("force_readout", "bwd")),
    (_F2 + "transpose(jvp(transpose(jvp(ForceFieldCGCNN))))/force_readout/"
     "ForceHead_0/jit(softplus)/mul", ("force_readout", "bwd2")),
    (_F2 + "jvp(transpose(jvp(ForceFieldCGCNN)))/conv_1/conv.gather/"
     "convert_element_type", ("conv.gather", "bwd")),
    (_F2 + "transpose(jvp(transpose(jvp(ForceFieldCGCNN))))/conv_1/"
     "conv.gather/jit(_take)/gather", ("conv.gather", "bwd2")),
    (_F2 + "transpose(jvp(transpose(jvp(ForceFieldCGCNN))))/conv_1/"
     "conv.fc_full/fc_full/broadcast_in_dim", ("conv.fc_full", "bwd2")),
    (_F2 + "transpose(jvp(transpose(jvp(ForceFieldCGCNN))))/conv_0/"
     "conv.gate/split", ("conv.gate", "bwd2")),
    (_F2 + "transpose(jvp(transpose(jvp(ForceFieldCGCNN))))/conv_0/"
     "conv.aggregate/convert_element_type", ("conv.aggregate", "bwd2")),
    # without BatchNorm the conv's residual reads the aggregate's output
    (_F2 + "transpose(jvp(transpose(jvp(ForceFieldCGCNN))))/conv_0/"
     "conv.aggregate/jit(softplus)/mul", ("conv.aggregate", "bwd2")),
    # jnp's own transpose primitive is no reverse pass
    (_F2 + "transpose(jvp(jvp(ForceFieldCGCNN)))/embed/embedding/transpose",
     ("embed", "bwd")),
    (_F2 + "jvp(loss)/mul", ("loss", "fwd")),
    (_F2 + "transpose(jvp(loss))/broadcast_in_dim", ("loss", "bwd")),
    # unscoped: the optimizer and loss of the step before it had scopes,
    # a reducer's parameters, an argument's name
    # the data-parallel step's collectives (train/step.py), as the
    # four-device scan program of parallel/data_parallel.py names them
    (_SCAN + "jit(shmap_body)/dp.allreduce/psum", ("dp.allreduce", "fwd")),
    (_SCAN + "jit(shmap_body)/dp.allreduce/div", ("dp.allreduce", "fwd")),
    # the block-diffusion decoder (models/sdar.py): scopes under the layer
    # scan, the per-sequence map and their checkpoints
    (_LM + "jvp(lm.embed)/gather", ("lm.embed", "fwd")),
    (_LM + "transpose(jvp(lm.embed))/scatter-add", ("lm.embed", "bwd")),
    (_LM + "jvp(while)/body/while/body/checkpoint/attn.proj/dot_general",
     ("attn.proj", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/checkpoint/"
     "rematted_computation/attn.proj/dot_general", ("attn.proj", "bwd")),
    (_LM + "jvp(while)/body/while/body/checkpoint/attn.bd/vmap(vmap("
     "jit(splash_mqa)))/pallas_call", ("attn.bd", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/checkpoint/attn.bd/"
     "pallas_call", ("attn.bd", "bwd")),
    (_LM + "jvp(while)/body/while/body/checkpoint/moe.route/sort",
     ("moe.route", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/checkpoint/moe.route/"
     "gather", ("moe.route", "bwd")),
    (_LM + "jvp(while)/body/while/body/checkpoint/moe.expert/"
     "jit(gmm)/pallas_call", ("moe.expert", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/checkpoint/moe.expert/"
     "jit(tgmm)/pallas_call", ("moe.expert", "bwd")),
    # the expert layer's switch over its rungs (ops/moe.py): what was
    # moe.route stays moe.route, forward and in the reverse pass's own
    # switch, whose branches rebuild the rows (``jvp(...)`` under the outer
    # ``transpose(``: bwd) and pull the cotangent back through them (a
    # second ``transpose(``: bwd2)
    (_LM + "jvp(while)/body/while/body/moe.route/cond", ("moe.route", "fwd")),
    (_LM + "jvp(while)/body/while/body/moe.route/cond/branch_0_fun/"
     "moe.route/jit(_take)/gather", ("moe.route", "fwd")),
    (_LM + "jvp(while)/body/while/body/moe.route/cond/branch_2_fun/"
     "moe.expert/jit(gmm)/pallas_call", ("moe.expert", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/checkpoint/moe.route/"
     "cond/branch_0_fun/jvp(moe.route)/sort", ("moe.route", "bwd")),
    (_LM + "transpose(jvp(while))/body/while/body/checkpoint/moe.route/"
     "cond/branch_1_fun/transpose(jvp(moe.route))/jit(_take)/gather",
     ("moe.route", "bwd2")),
    (_LM + "transpose(jvp(while))/body/while/body/checkpoint/moe.route/"
     "cond/branch_1_fun/transpose(jvp(moe.expert))/jit(tgmm)/pallas_call",
     ("moe.expert", "bwd2")),
    (_LM + "jvp(lm.head)/while/body/checkpoint/dot_general",
     ("lm.head", "fwd")),
    (_LM + "transpose(jvp(lm.head))/while/body/checkpoint/"
     "rematted_computation/dot_general", ("lm.head", "bwd")),
    # models/afmoe.py: the masked attention by layer kind, the leading dense
    # layer's MLP and the shared expert; the periods' runs are scans in a scan
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/attn.window/"
     "vmap(vmap(jit(splash_mqa)))/pallas_call", ("attn.window", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "attn.window/pallas_call", ("attn.window", "bwd")),
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/attn.full/"
     "vmap(vmap(jit(splash_mqa)))/pallas_call", ("attn.full", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "attn.full/pallas_call", ("attn.full", "bwd")),
    (_LM + "jvp(while)/body/while/body/checkpoint/mlp.dense/dot_general",
     ("mlp.dense", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/checkpoint/"
     "rematted_computation/mlp.dense/dot_general", ("mlp.dense", "bwd")),
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/moe.shared/"
     "dot_general", ("moe.shared", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "rematted_computation/moe.shared/dot_general", ("moe.shared", "bwd")),
    # the splash kernels' tables refined by the call's documents
    # (ops/masked_attention.py): the forward pass's are no part of what is
    # differentiated; the reverse pass makes its own in the checkpoint
    (_LM + "while/body/closed_call/attn.bd/vmap()/reduce_max",
     ("attn.bd", "fwd")),
    (_LM + "while/body/closed_call/while/body/closed_call/attn.full/"
     "vmap(jit(_where))/select_n", ("attn.full", "fwd")),
    (_LM + "transpose(jvp())/while/body/closed_call/while/body/closed_call/"
     "checkpoint/rematted_computation/attn.bd/vmap(jit(_where))/select_n",
     ("attn.bd", "bwd")),
    (_LM + "transpose(jvp(scan))/while/body/closed_call/while/body/"
     "closed_call/checkpoint/rematted_computation/attn.window/vmap()/"
     "reduce_min", ("attn.window", "bwd")),
    # the held experts' weight casts are the expert phase's there
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/moe.expert/"
     "convert_element_type", ("moe.expert", "fwd")),
    # models/lfm2.py: the convolution layers' projections and their taps
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/sconv.proj/"
     "dot_general", ("sconv.proj", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "rematted_computation/sconv.proj/dot_general", ("sconv.proj", "bwd")),
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/sconv.mix/"
     "mul", ("sconv.mix", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "sconv.mix/select_n", ("sconv.mix", "bwd")),
    # models/nemotron_h.py: the Mamba-2 layers' projections, filter, scan
    # (its two passes a block of chunks at a time are loops of their own)
    # and gate
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/ssm.proj/"
     "dot_general", ("ssm.proj", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "rematted_computation/ssm.proj/dot_general", ("ssm.proj", "bwd")),
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/ssm.conv/"
     "jit(silu)/mul", ("ssm.conv", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "ssm.conv/select_n", ("ssm.conv", "bwd")),
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/ssm.scan/"
     "while/body/checkpoint/zchij,zcjhp->zcihp/dot_general",
     ("ssm.scan", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "ssm.scan/while/body/checkpoint/rematted_computation/exp",
     ("ssm.scan", "bwd")),
    (_LM + "jvp(while)/body/while/body/while/body/checkpoint/ssm.gate/"
     "rsqrt", ("ssm.gate", "fwd")),
    (_LM + "transpose(jvp(while))/body/while/body/while/body/checkpoint/"
     "ssm.gate/mul", ("ssm.gate", "bwd")),
    ("jit(train_step)/add", ("other", "fwd")),
    (_SCAN + "mul", ("other", "fwd")),
    ("reduce_sum", ("other", "fwd")),
    ("state.params['conv_0']['bn1']['scale']", ("other", "fwd")),
]


class TestPhases:
    @pytest.mark.parametrize("op_name,want", _CLASSIFIED)
    def test_classify(self, op_name, want):
        from cgnn_tpu.observe import phases

        assert phases.classify(op_name) == want
        assert want[0] in phases.PHASES

    def test_classified_names_cover_every_phase_both_ways(self):
        from cgnn_tpu.observe import phases

        seen = {want for _, want in _CLASSIFIED}
        assert {p for p, _ in seen} == set(phases.PHASES)
        two_way = {"conv.gather", "conv.fc_full", "conv.bn1", "conv.gate",
                   "conv.aggregate", "conv.bn2", "conv.ln", "embed",
                   "pool_head", "loss", "edge_geom", "force_readout",
                   "lm.embed", "attn.proj", "attn.bd", "moe.route",
                   "moe.expert", "lm.head", "attn.window", "attn.full",
                   "mlp.dense", "moe.shared", "sconv.proj", "sconv.mix",
                   "ssm.proj", "ssm.conv", "ssm.scan", "ssm.gate"}
        assert {p for p, d in seen if d == "bwd"} == two_way
        # what the force step differentiates twice: the trunk without
        # BatchNorm, the geometry and the readout (not the embedding, which
        # no position moves, nor the loss); and the expert layer, whose
        # reverse pass differentiates its rung's body inside its own switch
        assert {p for p, d in seen if d == "bwd2"} == {
            "edge_geom", "force_readout", "conv.gather", "conv.fc_full",
            "conv.gate", "conv.aggregate", "moe.route", "moe.expert"}

    def test_a_kernel_s_custom_call_spans_lines(self):
        """A Pallas kernel's ``backend_config`` runs over several lines and
        its last one (``}}, metadata=...``) starts in the first column: it
        closes no computation, and the instruction's ``op_name`` is on it
        (the chip's first trace of ``sdar.train`` lost 71% of its time to
        this: PERF.md section 6, PR 42)."""
        from cgnn_tpu.observe import phases

        text = """HloModule jit_step

%body (p: (f32[8,8])) -> (f32[8,8]) {
  %p = (f32[8,8]{1,0}) parameter(0)
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=0
  %gmm.7 = f32[8,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "abc
def
}}, metadata={op_name="jit(step)/jvp()/while/body/moe.expert/jit(gmm)/pallas_call"}
  %fusion.9 = f32[8,8]{1,0} fusion(%gmm.7), kind=kLoop, calls=%fused.1, metadata={op_name="jit(step)/jvp()/while/body/moe.route/mul"}
  ROOT %t = (f32[8,8]{1,0}) tuple(%fusion.9)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  ROOT %w = f32[8,8]{1,0} copy(%a), metadata={op_name="jit(step)/lm.head/add"}
}
"""
        table = phases.phase_table(text)
        assert table["gmm.7"] == ("moe.expert", "fwd")
        assert table["fusion.9"] == ("moe.route", "fwd")
        assert table["w"] == ("lm.head", "fwd")
        assert table["x"] == ("other", "fwd")

    def test_phase_table_on_a_compiled_program(self):
        """Instructions of the entry and of the loop body are in the table
        under the phase of their scope; a fusion takes its root's; what is
        inside a fusion or a reducer is not an event and not in it."""
        from cgnn_tpu.observe import phases

        def loss(w, x):
            with jax.named_scope("Model"):
                with jax.named_scope("conv.gate"):
                    y = jnp.tanh(x @ w)
            with jax.named_scope("loss"):
                return (y ** 2).sum()

        def scan_tiny(w, xs):
            def body(c, x):
                g = jax.grad(loss)(c, x)
                with jax.named_scope("optimizer"):
                    return c - 0.1 * g, g.sum()
            return jax.lax.scan(body, w, xs)

        text = jax.jit(scan_tiny).lower(
            jnp.ones((4, 4)), jnp.ones((3, 5, 4))).compile().as_text()
        table = phases.phase_table(text)
        assert text.startswith("HloModule jit_scan_tiny")
        found = set(table.values())
        assert {("conv.gate", "fwd"), ("conv.gate", "bwd"),
                ("optimizer", "fwd"), ("scan", "fwd")} <= found
        comps = phases._parse(text)
        fusions = [n for c in comps.values()
                   for n, rest in c["instrs"].items() if " fusion(" in rest]
        assert fusions, "the CPU compiler fuses this program"
        assert {table[n] for n in fusions if n in table} >= {
            ("conv.gate", "bwd"), ("optimizer", "fwd"), ("scan", "fwd")}
        # a fused computation's own instructions are nobody's events
        inner = {n for cname, c in comps.items() if "fused" in cname
                 for n in c["instrs"]}
        assert inner and not inner & set(table)
        assert all(len(v) == 2 and v[0] in phases.PHASES
                   and v[1] in ("fwd", "bwd") for v in table.values())

    def test_phase_table_by_hand(self):
        """TPU-style text: a fusion without a name takes its root's, a
        tuple root its first named operand's; a prefetch and a layout copy
        take the phase of what they move (through the layout's own
        parentheses); what hangs on a parameter alone is ``other``."""
        from cgnn_tpu.observe import phases

        bn1 = 'metadata={op_name="jit(f)/jvp(M)/conv_0/bn1/mul"}'
        gate = ('metadata={op_name="jit(f)/transpose(jvp(M))/conv_0/'
                'conv.gate/mul" stack_frame_id=4}')
        text = f"""HloModule jit_f, is_scheduled=true

%fused_computation.1 (p.1: f32[4]) -> f32[4] {{
  %p.1 = f32[4]{{0:T(128)}} parameter(0)
  ROOT %multiply.3 = f32[4]{{0:T(128)}} multiply(%p.1, %p.1), {bn1}
}}

%fused_computation.2 (p.2: f32[4]) -> (f32[4], f32[4]) {{
  %p.2 = f32[4]{{0:T(128)}} parameter(0)
  %negate.1 = f32[4]{{0:T(128)}} negate(%p.2)
  %multiply.5 = f32[4]{{0:T(128)}} multiply(%p.2, %p.2), {gate}
  ROOT %tuple.9 = (f32[4]{{0:T(128)}}, f32[4]{{0:T(128)}}) tuple(%negate.1, %multiply.5)
}}

ENTRY %main.7 (a.1: f32[4]) -> f32[4] {{
  %a.1 = f32[4]{{0:T(128)}} parameter(0)
  %fusion.1 = f32[4]{{0:T(128)}} fusion(%a.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = (f32[4]{{0:T(128)}}, f32[4]{{0:T(128)}}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %get-tuple-element.1 = f32[4]{{0:T(128)}} get-tuple-element(%fusion.2), index=0
  %copy-start.1 = (f32[4]{{0:T(128)S(1)}}, f32[4]{{0:T(128)}}, u32[]{{:S(2)}}) copy-start(%get-tuple-element.1)
  %copy-done.1 = f32[4]{{0:T(8,128)(2,1)S(1)}} copy-done(%copy-start.1)
  %copy.4 = f32[4]{{0:T(8,128)(2,1)}} copy(%fusion.1), backend_config={{"x":"(1)"}}
  %copy.5 = f32[4]{{0:T(128)}} copy(%a.1)
  ROOT %add.2 = f32[4]{{0:T(128)}} add(%copy-done.1, %copy.4), metadata={{op_name="jit(f)/add"}}
}}
"""
        assert phases.phase_table(text) == {
            "a.1": ("other", "fwd"),
            "fusion.1": ("conv.bn1", "fwd"),
            "fusion.2": ("conv.gate", "bwd"),
            "get-tuple-element.1": ("conv.gate", "bwd"),
            "copy-start.1": ("conv.gate", "bwd"),
            "copy-done.1": ("conv.gate", "bwd"),
            "copy.4": ("conv.bn1", "fwd"),
            "copy.5": ("other", "fwd"),
            "add.2": ("other", "fwd"),
        }

    def test_scopes_cover_the_step_and_change_only_metadata(
            self, tiny_dataset, monkeypatch):
        """Under 5% of the op_names the traced step gives its instructions
        fall in ``other``, and the optimized HLO with every named scope
        taken out (flax's own too) is the same program."""
        import contextlib
        import re

        from cgnn_tpu.observe import phases
        from cgnn_tpu.resilience.guard import guard_step

        train_g, _, _ = tiny_dataset
        node_cap, edge_cap = capacities_for(train_g, 8)
        state = _fresh_state(train_g, node_cap, edge_cap)
        batch = pack_graphs(train_g[:8], node_cap, edge_cap, 8)

        def compiled_text():
            step = jax.jit(guard_step(make_train_step()))
            return step.lower(state, batch).compile().as_text()

        scoped = compiled_text()
        names = [n for n in re.findall(r'op_name="([^"]*)"', scoped)
                 if n.startswith("jit(")]
        other = [n for n in names if phases.classify(n)[0] == "other"]
        assert len(names) > 500
        assert len(other) < 0.05 * len(names), sorted(set(other))[:20]
        got = {phases.classify(n) for n in names}
        assert {"conv.gather", "conv.fc_full", "conv.bn1", "conv.gate",
                "conv.aggregate", "conv.bn2", "embed", "pool_head", "loss",
                "optimizer"} <= {p for p, _ in got}

        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = compiled_text()
        assert "conv.gate" in scoped and "conv.gate" not in bare

        def code(text):
            lines = [re.sub(r",? ?metadata=\{[^}]*\}", "", ln)
                     for ln in text.splitlines()]
            return [ln for ln in lines
                    if ln.startswith((" ", "%", "ENTRY", "}"))]

        assert code(scoped) == code(bare)


    def test_force_step_scopes_directions_and_metadata_only(self, monkeypatch):
        """The compiled force step (train/force_step.py, guard on) names
        every phase it has in all three directions, leaves under 5% of its
        op_names in ``other``, and is the same program with every named
        scope taken out, the three it added among them."""
        import contextlib
        import re

        from cgnn_tpu.config import DataConfig, ModelConfig, build_model
        from cgnn_tpu.data.dataset import load_synthetic_md17
        from cgnn_tpu.observe import phases
        from cgnn_tpu.resilience.guard import guard_step
        from cgnn_tpu.train import Normalizer, create_train_state
        from cgnn_tpu.train.force_step import make_force_train_step

        graphs = load_synthetic_md17(8)
        node_cap, edge_cap = capacities_for(graphs, 8, dense_m=12)
        batch = pack_graphs(graphs, node_cap, edge_cap, 8, dense_m=12,
                            over_cap=512)
        model = build_model(
            ModelConfig(atom_fea_len=16, n_conv=2, h_fea_len=32, dense_m=12),
            DataConfig(), "force")
        state = create_train_state(
            model, batch, make_optimizer(optim="sgd", lr=1e-3,
                                         lr_milestones=[10**9]),
            Normalizer(mean=jnp.zeros(1), std=jnp.ones(1)))

        def compiled_text():
            step = jax.jit(guard_step(make_force_train_step()))
            return step.lower(state, batch).compile().as_text()

        scoped = compiled_text()
        names = [n for n in re.findall(r'op_name="([^"]*)"', scoped)
                 if n.startswith("jit(")]
        got = {phases.classify(n) for n in names}
        other = [n for n in names if phases.classify(n)[0] == "other"]
        assert len(names) > 500
        assert len(other) < 0.05 * len(names), sorted(set(other))[:20]
        for phase in ("edge_geom", "force_readout", "conv.gather",
                      "conv.fc_full", "conv.gate"):
            assert {d for p, d in got if p == phase} == {
                "fwd", "bwd", "bwd2"}, phase
        assert {d for p, d in got if p == "loss"} == {"fwd", "bwd"}
        assert ("optimizer", "fwd") in got and ("embed", "bwd") in got
        # the dense geometry's data movement carries its scope through
        # linear_call's transposes: the position gather in every
        # direction, the overflow tier's run sums (a matmul a block of the
        # list, no scatter: ops/segment.py _run_totals) under one reverse
        # pass; and no gather or scatter of the step is left unnamed
        moves = {(phases.classify(n), n.rsplit("/", 1)[-1]) for n in names
                 if n.endswith(("/gather", "/scatter-add", "/dot_general"))}
        for direction in ("fwd", "bwd", "bwd2"):
            assert (("edge_geom", direction), "gather") in moves
        for phase in ("edge_geom", "conv.gather"):
            assert ((phase, "bwd"), "dot_general") in moves
            assert not [m for m in moves
                        if m[0][0] == phase and m[1] == "scatter-add"]
        assert not [m for m in moves if m[0][0] == "other"]
        # no BatchNorm in this trunk: nothing of it carries a BatchNorm
        # phase, the residual after the neighbour sum included
        assert not {p for p, _ in got} & {"conv.bn1", "conv.bn2"}

        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = compiled_text()
        for scope in ("edge_geom", "force_readout", "loss"):
            assert scope in scoped and f"/{scope}/" not in bare

        def code(text):
            lines = [re.sub(r",? ?metadata=\{[^}]*\}", "", ln)
                     for ln in text.splitlines()]
            return [ln for ln in lines
                    if ln.startswith((" ", "%", "ENTRY", "}"))]

        assert code(scoped) == code(bare)


# the spans of run work the epoch driver opens (train/loop.py), all muted
# in warm-up; benchmark/readers/{span,ring,gaps}.py read them by these names
RUN_SPANS = {"scan.epoch", "scan.chunk", "scan.accumulate", "epoch.sched",
             "epoch.sched.put", "epoch.fetch_start", "epoch.fetch"}


def _inside(child: dict, parent: dict) -> bool:
    """``child`` is a direct child of ``parent`` in the ring: same thread,
    one level deeper, within its interval."""
    return (child["tid"] == parent["tid"]
            and child["args"]["depth"] == parent["args"]["depth"] + 1
            and parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


class TestDriverSpans:
    def _driver(self, tiny_dataset, telemetry, copies=1):
        from cgnn_tpu.resilience.guard import guard_step
        from cgnn_tpu.train.loop import ScanEpochDriver
        from cgnn_tpu.train.step import make_eval_step

        train_g, val_g, _ = tiny_dataset
        node_cap, edge_cap = capacities_for(train_g, 8)
        state = _fresh_state(train_g, node_cap, edge_cap)
        pack = lambda gs: pack_graphs(gs, node_cap, edge_cap, 8)  # noqa: E731
        train_b = [pack(train_g[i:i + 8]) for i in (0, 8, 16)] * copies
        drv = ScanEpochDriver(
            guard_step(make_train_step()), make_eval_step(), train_b,
            [pack(val_g[:8])], np.random.default_rng(0),
            telemetry=telemetry)
        return drv, state

    def test_warm_emits_set_up_spans_and_no_chunk(self, tiny_dataset,
                                                  tmp_path):
        telemetry = Telemetry("epoch", str(tmp_path / "t"))
        drv, state = self._driver(tiny_dataset, telemetry)
        state = drv.warm(state)
        events = telemetry.spans.events
        by_name: dict = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        n_train, n_eval = len(drv._train_scans), len(drv._eval_scans)
        assert n_train == 2 and n_eval == 1  # lengths {1, 2} of 3 batches
        assert len(by_name["warm.program"]) == n_train
        assert len(by_name["warm.phase_map"]) == n_train + n_eval
        assert len(by_name["warm.epoch"]) == 1
        assert len(by_name["scan.stage"]) == 1
        assert "scan.chunk" not in by_name
        stage = by_name["scan.stage"][0]["args"]
        assert stage["groups"] == 1 and stage["batches"] == 4
        assert stage["bytes"] > 0
        for e in by_name["warm.program"]:
            assert e["args"]["compiled"] or e["args"]["cache_read"]
        programs = by_name["scan.program"]
        modules = sorted(e["args"]["module"] for e in programs)
        n = next(iter(drv._train_groups))[0][0]
        assert modules == [f"jit_scan_eval_n{n}_l1", f"jit_scan_train_n{n}_l1",
                           f"jit_scan_train_n{n}_l2"]
        for e in programs:
            assert e["ph"] == "i" and e["args"]["length"] in (1, 2)
            phases_seen = {v[0] for v in e["args"]["table"].values()}
            assert {"conv.bn1", "conv.gate", "scan"} <= phases_seen
        json.dumps(events)  # the ring must stay exportable
        # the window's dispatches are spans again, one a chunk
        state, _, _ = drv.run_epoch_pair(state, first=False)
        chunks = [e for e in telemetry.spans.events
                  if e["name"] == "scan.chunk"]
        assert sum(e["args"]["steps"] for e in chunks
                   if e["args"]["train"]) == 3
        assert sum(1 for e in chunks if not e["args"]["train"]) == 1
        assert telemetry.counters()["scan_steps"] == 4
        telemetry.close()

    @pytest.mark.parametrize("staging", ["coo", "dense", "compact", "mesh"])
    def test_scan_stage_says_how_far_the_overflow_tier_engages(
            self, tmp_path, staging):
        """The ``scan.stage`` span and the run summary carry what share of
        the staged edges reaches its node through the overflow tier of the
        gather's transpose (ops/segment.py _run_totals): real entries,
        capacity, and the run capacity the programs are compiled for —
        summed over batches, and over the chips of a mesh's device axis;
        zeros for batches that carry no mapping (flat COO)."""
        from cgnn_tpu.data.compact import CompactSpec, compact_pack_fn
        from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
        from cgnn_tpu.data.graph import (
            batch_iterator,
            overflow_run_cap,
        )
        from cgnn_tpu.parallel.data_parallel import stack_batches
        from cgnn_tpu.train.loop import ScanEpochDriver
        from cgnn_tpu.train.step import make_eval_step

        m = 12
        cfg = FeaturizeConfig(radius=6.0, max_num_nbr=m)
        train_g = load_synthetic_mp(24, cfg, seed=3)
        dense = staging != "coo"
        nc, ec = capacities_for(train_g, 8, dense_m=m if dense else None)
        pack_fn = None
        if staging == "compact":
            pack_fn = compact_pack_fn(CompactSpec.build(
                train_g, cfg.gdf(), dense_m=m))
        batches = list(batch_iterator(
            train_g, 8, nc, ec, dense_m=m if dense else None,
            pack_fn=pack_fn))
        assert len(batches) >= 2
        want = {"transpose_overflow_rows": 0, "transpose_overflow_cap": 0,
                "transpose_overflow_max_run": 0}
        if dense:
            neighbors = [np.asarray(b.neighbors)[
                np.asarray(b.edge_mask).reshape(-1) > 0] for b in batches]
            want = {
                "transpose_overflow_rows": int(sum(
                    np.maximum(np.bincount(nb) - m, 0).sum()
                    for nb in neighbors)),
                "transpose_overflow_cap": sum(
                    len(b.over_slots) for b in batches),
                "transpose_overflow_max_run": overflow_run_cap(train_g, m),
            }
            assert 0 < want["transpose_overflow_rows"] \
                < want["transpose_overflow_cap"]
        if staging == "mesh":  # a device axis of two: totals over chips
            batches = [stack_batches(batches[:2])]
        telemetry = Telemetry("epoch", str(tmp_path / "t"))
        ScanEpochDriver(make_train_step(), make_eval_step(), batches, [],
                        np.random.default_rng(0), telemetry=telemetry,
                        stage=lambda x: x)
        (stage,) = [e for e in telemetry.spans.events
                    if e["name"] == "scan.stage"]
        if staging == "mesh":
            want["transpose_overflow_rows"] = int(sum(
                np.maximum(np.bincount(nb) - m, 0).sum()
                for nb in neighbors[:2]))
            want["transpose_overflow_cap"] = 2 * len(batches[0].over_slots[0])
        assert {k: stage["args"][k] for k in want} == want
        summary = {**telemetry.counters(), **telemetry.gauges()}
        assert {k: int(summary[k]) for k in want} == want
        telemetry.close()

    def test_without_telemetry_the_dispatch_opens_no_annotation(
            self, tiny_dataset, tmp_path, monkeypatch):
        opened, kwargs = [], []
        real = jax.profiler.TraceAnnotation

        class Counting(real):
            def __init__(self, name, **kw):
                opened.append(name)
                kwargs.append(kw)
                super().__init__(name, **kw)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        drv, state = self._driver(tiny_dataset, None)
        state = drv.warm(state)
        state, _, _ = drv.run_epoch_pair(state, first=False)
        assert opened == []

        # with it: the spans of run work and no other, each annotation
        # with the ids its ring event has (a cgnn: event of the xplane
        # says which chunk of which epoch it is)
        telemetry = Telemetry("epoch", str(tmp_path / "t"))
        drv, state = self._driver(tiny_dataset, telemetry)
        state = drv.warm(state)
        opened.clear()
        kwargs.clear()
        state, pending = drv.run_epoch_pair(state, first=False,
                                            async_fetch=True)
        pending.result()
        assert set(opened) == {"cgnn:" + n for n in RUN_SPANS}
        ring = [e for e in telemetry.spans.events if e["name"] in RUN_SPANS]
        assert len(ring) == len(opened)
        by_ids = {(e["name"], e["args"].get("epoch"), e["args"].get("chunk"))
                  for e in ring}
        for name, kw in zip(opened, kwargs):
            name = name.removeprefix("cgnn:")
            assert (name, kw.get("epoch"), kw.get("chunk")) in by_ids
            if name == "scan.chunk":
                assert set(kw) == {"steps", "train", "epoch", "chunk",
                                   "program"}
                assert kw["program"].startswith(
                    "scan_train_n" if kw["train"] else "scan_eval_n")
            elif name == "scan.accumulate":
                assert set(kw) == {"epoch", "chunk"}
            elif name == "epoch.sched.put":
                assert kw["perms"] > 0 and kw["bytes"] > 0
                assert kw["transfers"] == 2  # one bucket shape
        telemetry.close()

    def test_every_span_of_a_driven_epoch_carries_its_ids(
            self, tiny_dataset, tmp_path):
        """The epoch driver accounts for its own host time: one
        ``scan.epoch`` a ``_drive`` call, under it one ``scan.chunk`` and
        one ``scan.accumulate`` a chunk with the same ``(epoch, chunk)``,
        ``epoch.sched`` around every schedule build (with the staging of
        its perms inside), and the fetch's start and the fetch itself, on
        whichever thread; warm-up leaves none of them."""
        telemetry = Telemetry("epoch", str(tmp_path / "t"))
        drv, state = self._driver(tiny_dataset, telemetry, copies=2)
        state = drv.warm(state)
        assert not RUN_SPANS & {e["name"] for e in telemetry.spans.events}
        assert not {"scan_chunks", "sched_builds", "sched_perms_staged",
                    "sched_transfers", "scan_steps"} & set(
                        telemetry.counters())

        # fit's pair with the deferred fetch, then the synchronous pair
        state, pending = drv.run_epoch_pair(state, first=False,
                                            async_fetch=True)
        pending.result()
        drv._sched_cache.pop((id(drv._train_groups), True, False))
        state, _, _ = drv.run_epoch_pair(state, first=False)
        by_name: dict = {}
        for e in telemetry.spans.events:
            if e["name"] in RUN_SPANS:
                by_name.setdefault(e["name"], []).append(e)
        assert set(by_name) == RUN_SPANS

        epochs = by_name["scan.epoch"]
        assert [(e["args"]["epoch"], e["args"]["train"]) for e in epochs] \
            == [(0, True), (1, False), (2, True), (3, False)]
        n_nodes = next(iter(drv._train_groups))[0][0]
        for ep in epochs:
            def mine(name, epoch=ep["args"]["epoch"]):
                return sorted((e for e in by_name[name]
                               if e["args"]["epoch"] == epoch),
                              key=lambda e: e["args"]["chunk"])

            chunks, accs = mine("scan.chunk"), mine("scan.accumulate")
            n = ep["args"]["chunks"]
            assert n == len(chunks) == len(accs) > 0
            assert [c["args"]["chunk"] for c in chunks] == list(range(n)) \
                == [a["args"]["chunk"] for a in accs]
            assert sum(c["args"]["steps"] for c in chunks) \
                == ep["args"]["steps"] == (6 if ep["args"]["train"] else 1)
            kind = "train" if ep["args"]["train"] else "eval"
            for c, a in zip(chunks, accs):
                assert _inside(c, ep) and _inside(a, ep)
                assert c["args"]["train"] == ep["args"]["train"]
                # the accumulate is dispatched after the chunk's program
                assert c["ts"] + c["dur"] <= a["ts"]
                assert c["args"]["program"] == (
                    f"scan_{kind}_n{n_nodes}_l{c['args']['steps']}")

        # every schedule build, and the staging of its perms inside: the
        # async pair defers the prebuild to run_epoch_pair (under no
        # scan.epoch); the cache was then emptied, so the sync pair builds
        # on the miss at its head and prebuilds at its end. The eval
        # schedule was built in warm-up, once, and is reused.
        scheds = by_name["epoch.sched"]
        assert [(e["args"]["train"], e["args"]["prebuilt"])
                for e in scheds] == [(True, True), (True, False),
                                     (True, True)]
        assert not any(_inside(scheds[0], ep) for ep in epochs)
        assert _inside(scheds[1], epochs[2]) and _inside(scheds[2],
                                                         epochs[2])
        assert scheds[1]["ts"] < by_name["scan.chunk"][-4]["ts"]
        puts = by_name["epoch.sched.put"]
        assert len(puts) == len(scheds)
        for sched, put in zip(scheds, puts):
            assert _inside(put, sched)
            assert put["args"]["perms"] == sched["args"]["perms"] == 3
            assert sched["args"]["chunks"] == 3
            assert put["args"]["bytes"] == 4 * 6  # six int32 steps
            # one array and one zero cursor, whatever the chunks
            assert put["args"]["transfers"] == 2

        # the fetch: started on the dispatch thread (the async pair only),
        # run on the fetch thread there and inline in the sync pair
        (start,) = by_name["epoch.fetch_start"]
        first, second = by_name["epoch.fetch"]
        assert start["args"]["epoch"] == first["args"]["epoch"] == 0
        assert second["args"]["epoch"] == 2
        assert start["tid"] == epochs[0]["tid"] == second["tid"]
        assert first["tid"] != start["tid"]
        assert start["args"]["depth"] == first["args"]["depth"] == 0

        counters = telemetry.counters()
        assert counters["scan_chunks"] == len(by_name["scan.chunk"]) == 8
        assert counters["scan_steps"] == 14
        assert counters["sched_builds"] == 3
        assert counters["sched_perms_staged"] == 9
        assert counters["sched_transfers"] == 6
        json.dumps(telemetry.spans.events)
        telemetry.close()

    @pytest.mark.parametrize("chunk_steps", [1, 4])
    def test_a_schedule_reaches_the_device_in_two_transfers_a_shape(
            self, tiny_dataset, tmp_path, chunk_steps):
        """The mechanism of PR 40, pinned: a schedule's perms go over as
        one array and one zero cursor a bucket shape, however many chunks
        they are cut into (``chunk_steps`` 1 makes twelve chunks an epoch
        here, 4 makes three), and outside the schedule's own staging a
        warmed driver's epoch moves NOTHING from the host to the device,
        stated or implied: no per-chunk perm, offset or scalar."""
        telemetry = Telemetry("epoch", str(tmp_path / "t"))
        drv, state = self._driver(tiny_dataset, telemetry, copies=4)
        drv.chunk_steps = chunk_steps
        state = drv.warm(state)

        sched = drv._sched

        def staging_allowed(*a, **kw):
            with jax.transfer_guard_host_to_device("allow"):
                return sched(*a, **kw)

        drv._sched = staging_allowed
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            state, pending = drv.run_epoch_pair(state, first=False,
                                                async_fetch=True)
            pending.result()
            state, _, _ = drv.run_epoch_pair(state, first=False)

        events = telemetry.spans.events
        chunks = [e for e in events if e["name"] == "scan.chunk"
                  and e["args"]["train"]]
        assert len(chunks) == 2 * -(-12 // chunk_steps)
        puts = [e["args"] for e in events if e["name"] == "epoch.sched.put"]
        # the async pair's deferred prebuild, the sync pair's prebuild (the
        # first epoch's own schedule and eval's were staged in warm-up)
        assert len(puts) == 2
        for put in puts:
            assert put["transfers"] == 2  # one bucket shape
            assert put["perms"] == -(-12 // chunk_steps)
            assert put["bytes"] == 4 * 12
        counters = telemetry.counters()
        assert counters["sched_transfers"] == 2 * counters["sched_builds"] == 4
        assert counters["sched_perms_staged"] == sum(
            put["perms"] for put in puts)
        telemetry.close()

    def test_without_telemetry_the_chunk_loop_builds_nothing(
            self, tiny_dataset, monkeypatch):
        """Off means off: an epoch of two chunks and an epoch of seven
        construct equally many context managers (the epoch's own, through
        ``_run_span``), and no span or annotation at all."""
        import contextlib
        import types

        from cgnn_tpu.train import loop

        made = {"span": 0, "annotation": 0, "nullcontext": 0}

        def counting(kind, real):
            def make(*a, **kw):
                made[kind] += 1
                return real(*a, **kw)
            return make

        counts = []
        for copies in (1, 4):
            drv, state = self._driver(tiny_dataset, None, copies=copies)
            state, _, _ = drv.run_epoch_pair(state, first=True)
            with monkeypatch.context() as mp:
                mp.setattr(SpanTracer, "span",
                           counting("span", SpanTracer.span))
                mp.setattr(jax.profiler, "TraceAnnotation", counting(
                    "annotation", jax.profiler.TraceAnnotation))
                # loop.py's own view of contextlib: jax's use of it inside
                # a dispatch is not the driver's
                mp.setattr(loop, "contextlib", types.SimpleNamespace(
                    nullcontext=counting("nullcontext",
                                         contextlib.nullcontext)))
                state, pending = drv.run_epoch_pair(state, first=False,
                                                    async_fetch=True)
                pending.result()
            counts.append(dict(made))
            made.update(dict.fromkeys(made, 0))
        assert counts[0] == counts[1]
        assert counts[0]["span"] == counts[0]["annotation"] == 0
        assert 0 < counts[0]["nullcontext"] <= 8

    def test_telemetry_changes_no_schedule_trajectory_or_metric(
            self, tiny_dataset, tmp_path):
        """Spans are the host's notes: the chunks dispatched (which
        program, which batches, in which order), the parameters after two
        epochs and the fetched metrics are bit-identical with telemetry on
        and off, through the deferred fetch and the synchronous one."""
        def run(telemetry):
            drv, state = self._driver(tiny_dataset, telemetry, copies=2)
            real, seen = drv._window_fn, []

            def recording(cache, key, body, train):
                fn = real(cache, key, body, train)

                def dispatch(state, stacked, perm_all, cursor):
                    at = int(cursor)
                    seen.append((fn.__name__, np.asarray(
                        perm_all)[at:at + key[1]].tolist()))
                    return fn(state, stacked, perm_all, cursor)

                dispatch.__name__ = fn.__name__
                return dispatch

            drv._window_fn = recording
            state, _, _ = drv.run_epoch_pair(state, first=True)
            seen.clear()
            state, pending = drv.run_epoch_pair(state, first=False,
                                                async_fetch=True)
            state, train_m, val_m = drv.run_epoch_pair(state, first=False)
            params = jax.tree_util.tree_map(np.asarray, state.params)
            return seen, params, (pending.result(), train_m, val_m)

        telemetry = Telemetry("epoch", str(tmp_path / "t"))
        seen_on, params_on, metrics_on = run(telemetry)
        assert RUN_SPANS <= {e["name"] for e in telemetry.spans.events}
        telemetry.close()
        seen_off, params_off, metrics_off = run(None)
        assert seen_on == seen_off and len(seen_on) == 8
        assert metrics_on == metrics_off
        jax.tree_util.tree_map(np.testing.assert_array_equal,
                               params_on, params_off)


class TestCheckpointSpans:
    def test_save_and_restore_are_spans_of_the_calling_thread(
            self, tiny_dataset, tmp_path):
        """``checkpoint_save`` times what a save holds the training thread
        for (the device fetch and the finalizer's dispatch, not the write,
        which the finalizer thread does), ``checkpoint_restore`` a whole
        restore; the README's ``trace.json`` row promises both."""
        from cgnn_tpu.train.checkpoint import CheckpointManager

        train_g, _, _ = tiny_dataset
        node_cap, edge_cap = capacities_for(train_g, 8)
        state = _fresh_state(train_g, node_cap, edge_cap)
        telemetry = Telemetry("epoch", str(tmp_path / "t"))
        mgr = CheckpointManager(str(tmp_path / "ckpt"), telemetry=telemetry)
        with telemetry.span("epoch", epoch=0):
            mgr.save(state, {"epoch": 0}, is_best=True)
        mgr.wait()
        mgr.restore(state)
        mgr.close()
        by_name = {e["name"]: e for e in telemetry.spans.events}
        save, restore = by_name["checkpoint_save"], by_name[
            "checkpoint_restore"]
        assert save["args"]["is_best"] is True and _inside(
            save, by_name["epoch"])
        assert restore["args"]["tag"] == "latest"
        assert restore["args"]["depth"] == 0 and restore["ts"] >= save["ts"]
        assert save["dur"] > 0 and restore["dur"] > 0
        telemetry.close()
