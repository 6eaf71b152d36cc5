"""The data-parallel step against its plain reference (benchmark/reference/
dp_ref.py), on 4 of the suite's 8 virtual devices, tiny and in float32.

The program's side is cell ``mp.train-dp4``'s own assembly at the test size
(benchmark/kinds/dp_train.py: what ``fit_data_parallel`` builds — device
groups from ``parallel_batches``, compact staging, the expander inside the
per-shard body, the guard, ``ScanEpochDriver`` over mesh-sharded stacks),
driven through the warmed driver's own one-step programs. What is pinned:
the step IS the DDP step (per-shard BatchNorm moments, gradients and running
statistics averaged, metric sums added), every replica holds the same bits,
and the comparison tells each broken collective from a sound one.
"""

from __future__ import annotations

import functools
import os
import sys

import numpy as np
import pytest

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.kinds import dp_train  # noqa: E402
from benchmark.reference import cgcnn_ref, dp_ref  # noqa: E402

TINY = os.path.join(HERE, "benchmark", "fixtures", "manifest_tiny_dp.json")
TOL = 2e-5  # float32 round-off over three steps, relative to a leaf's norm


def _float32_cell():
    cell = run.Cell(TINY, "tiny.train-dp4")
    cell.config["precision"]["compute"] = "float32"
    return cell


@pytest.fixture(scope="module")
def sound():
    """-> (driver after set-up and its first three steps, the reference's
    reading of the same steps)."""
    driver = dp_train.Driver(run.Context(_float32_cell(), 11, False))
    driver.setup()
    driver.check()
    return driver, driver.want


def _rel(got, want) -> dict:
    """Per leaf, the norm of the difference over the reference's norm (or
    the median leaf's, where a leaf is nearly zero)."""
    diff = cgcnn_ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        got, want))
    norm = cgcnn_ref.leaf_norms(want)
    floor = float(np.median(list(norm.values())))
    return {k: diff[k] / max(norm[k], floor) for k in norm}


@pytest.mark.parametrize("what", ["params", "batch_stats", "grad"])
def test_the_sharded_step_is_the_ddp_step(sound, what):
    """Parameters and running statistics after three steps, and the first
    step's applied gradient (read off the momentum), equal the reference's
    to float32 round-off: the gradient is the mean of the shards', BatchNorm
    normalised with each shard's own moments, the statistics were
    averaged."""
    driver, want = sound
    worst = max(_rel(driver.got[what], want[what]).values())
    assert worst < TOL, worst


def test_metric_sums_are_the_sums_over_the_shards(sound):
    driver, want = sound
    np.testing.assert_allclose(driver.got["loss"], want["loss"], rtol=TOL)
    # every shard's real structures, counted exactly
    for step, count in zip(driver.check_steps, want["count"]):
        assert count == sum(len(m) for m, _ in driver.members[step])


def test_replicas_are_bit_identical(sound):
    driver, _ = sound
    assert driver.got["replica_diff"] == 0.0
    for leaf in jax.tree_util.tree_leaves(
            (driver.state.params, driver.state.opt_state,
             driver.state.batch_stats)):
        shards = leaf.addressable_shards
        assert len(shards) == 4
        for s in shards[1:]:
            np.testing.assert_array_equal(np.asarray(s.data),
                                          np.asarray(shards[0].data))


@pytest.mark.parametrize("variant,reads", [
    ("grad_unaveraged", ("params", "grad")),
    ("sync_bn", ("params", "grad", "batch_stats")),
    ("stats_unaveraged", ("batch_stats",)),
])
def test_each_broken_collective_is_told_from_the_sound_step(sound, variant,
                                                            reads):
    """What makes the agreement above a statement about the collectives:
    the reference computed with shard 0's gradient unaveraged, with the
    moments of all shards' rows together (SyncBatchNorm), or with shard 0's
    running statistics alone differs from the sound reference, hence from
    the program, by far more than the round-off the program is held to."""
    driver, want = sound
    driver.check(control_variant=variant)
    broken = driver.control
    for what in reads:
        worst = max(_rel(broken[what], want[what]).values())
        assert worst > 100 * TOL, (what, worst)
    if variant == "stats_unaveraged":
        # running statistics steer no training step: only they differ
        assert max(_rel(broken["params"], want["params"]).values()) == 0.0
    else:
        rows = {r["name"]: r for r in driver.check(control_variant=variant)}
        assert rows["grad_diff_median_leaf"]["value"] > \
            rows["grad_diff_median_leaf"]["limit"]


def test_the_reference_s_forward_is_cgcnn_ref_s(sound):
    """dp_ref carries the forward a second time to read the BatchNorm
    moments off it: the outputs are the published forward's."""
    import jax.numpy as jnp

    from benchmark import system

    driver, _ = sound
    batch = cgcnn_ref.coo_batch(
        [system.graph_as_ref(g) for g in driver.members[0][0][0]])
    params, stats = (cgcnn_ref.as_jnp(driver.params0),
                     cgcnn_ref.as_jnp(driver.stats0))
    with jax.default_matmul_precision("highest"):
        out, moments = dp_ref.forward(params, batch)
        want = cgcnn_ref.forward(params, stats, batch, train=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert len(moments) == 2 * int(driver.config["model"]["n_conv"])
    assert jnp.isfinite(out).all()


@functools.cache  # two tests read the compact run
def _three_steps(compact: bool, warmed: bool = False):
    """Three one-step programs over the same device groups from the same
    weights, staged compact or in full -> (params, statistics, losses, what
    XLA compiled for the three steps). ``warmed``: the driver's ``warm()``
    ran first, as in the benchmark's set-up."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from benchmark import system
    from benchmark.weights import make_weights
    from cgnn_tpu.data.compact import (
        CompactSpec,
        compact_pack_fn,
        make_expander,
    )
    from cgnn_tpu.data.graph import capacities_for
    from cgnn_tpu.parallel.data_parallel import (
        make_parallel_eval_step,
        make_parallel_train_step,
        parallel_batches,
        replicate_state,
        shard_scan_stack,
    )
    from cgnn_tpu.train.loop import ScanEpochDriver, _compile_events

    cfg = _float32_cell().config
    graphs, _ = system.load_pool(cfg)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    spec = CompactSpec.build(graphs, system.featurize_config(cfg).gdf(),
                             dense_m=12, edge_dtype=np.float32)
    expand = make_expander(spec)
    nc, ec = capacities_for(graphs, 8, dense_m=12, snug=True)
    batches = list(parallel_batches(
        graphs, 4, 8, nc, ec, shuffle=True, rng=np.random.default_rng(0),
        dense_m=12, buckets=2, snug=True, pack_fn=compact_pack_fn(spec)))
    if not compact:
        # the same rows in full: every device row expanded beforehand by
        # the expander the compact path runs inside its step
        row = jax.jit(expand)
        batches = [jax.tree_util.tree_map(
            lambda *xs: np.stack(xs),
            *[jax.tree_util.tree_map(
                np.asarray, row(jax.tree_util.tree_map(
                    lambda x, d=d: x[d], b))) for d in range(4)])
            for b in batches]
        expand = None
    driver = ScanEpochDriver(
        make_parallel_train_step(mesh, guard=True, expand=expand),
        make_parallel_eval_step(mesh, expand=expand), batches, [],
        np.random.default_rng(0), stage=lambda t: shard_scan_stack(t, mesh),
        chunk_steps=2)
    g0 = graphs[0]
    params, stats = make_weights(5, cfg["model"], g0.atom_fea.shape[1],
                                 g0.edge_fea.shape[1])
    state = replicate_state(system.build_state(
        cfg, system.build_model(cfg), params, stats, 0.0, 1.0), mesh)
    if warmed:
        state = driver.warm(state)
    losses = []
    perm = jnp.zeros(1, jnp.int32)
    with _compile_events() as seen:
        for key, stacked in list(driver._train_groups.items()) * 2:
            fn = driver._scan_fn(driver._train_scans, (key, 1),
                                 driver._train_body, True)
            state, sums = fn(state, stacked, perm)
            losses.append(float(sums["loss_sum"]))
            if len(losses) == 3:
                break
    return (jax.tree_util.tree_map(np.asarray, state.params),
            jax.tree_util.tree_map(np.asarray, state.batch_stats), losses,
            seen)


def test_compact_staging_under_the_mesh_is_full_staging_bit_for_bit():
    """The staged form changes no arithmetic: three steps over compact rows
    expanded inside the per-shard body and over the same rows staged in
    full give the same bits."""
    compact, full = _three_steps(True), _three_steps(False)
    assert compact[2] == full[2]
    for a, b in zip(jax.tree_util.tree_leaves(compact[:2]),
                    jax.tree_util.tree_leaves(full[:2])):
        np.testing.assert_array_equal(a, b)


def test_the_compared_steps_run_the_warmed_programs_under_the_mesh():
    """``_scan_fn``'s ``(state, stacked, perm)`` form, which the kinds'
    ``check`` drives, is the program an epoch runs: on a warmed driver under
    the mesh it compiles nothing and reads nothing from the compile cache
    (its perm and cursor are staged as ``warm()`` staged them, replicated),
    and the three steps leave the bits an unwarmed driver's leave."""
    cold, warm = _three_steps(True), _three_steps(True, warmed=True)
    assert cold[3]["compiled"] or cold[3]["cache_read"]
    assert warm[3] == {"compiled": False, "cache_read": False}
    assert cold[2] == warm[2]
    for a, b in zip(jax.tree_util.tree_leaves(cold[:2]),
                    jax.tree_util.tree_leaves(warm[:2])):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("staging,says", [
    ("on", "compact staging: on"), ("off", None)])
def test_train_cli_data_parallel_device_resident(tmp_path, staging, says):
    """``train.py --data-parallel --device-resident --compact-staging on``
    over four virtual devices: the deployment cell ``mp.train-dp4`` measures,
    from the CLI, two epochs through the scan driver, with the counters of
    the deployment in the run's summary; ``off`` stages in full as before."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, "train.py", "--synthetic", "96", "--device", "cpu",
           "--epochs", "2", "-b", "8", "--buckets", "2", "--radius", "5",
           "--data-parallel", "--device-resident", "--bf16",
           "--compact-staging", staging, "--print-freq", "0",
           "--ckpt-dir", str(tmp_path / "ckpt")]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Epoch 1 [dp x4]" in proc.stdout, proc.stdout[-2000:]
    assert "** test mae:" in proc.stdout
    assert (says in proc.stdout) if says else (
        "compact staging: on" not in proc.stdout)
    with open(tmp_path / "ckpt" / "logs" / "metrics.jsonl") as f:
        summary = [json.loads(ln) for ln in f if "run_summary" in ln][-1]
    counters = summary["counters"]
    assert counters["dp_replicas"] == 4
    assert counters["dp_global_batch"] == 32
    assert counters["allreduce_bytes_per_step"] > 0
    assert 0 <= counters["dp_dropped_batches"] <= 2 * 3  # < D a shape


def test_a_compact_stack_is_staged_with_flat_rows():
    """Under the mesh the per-slot members go over as ``[B, D, N*M]`` (the
    same fields and bytes; on the chip a ``[B, 1, N, 12]`` share made the
    compiler re-lay out the whole resident stack once a launch), and the
    expander rebuilds the same batch from either shape, bit for bit."""
    from jax.sharding import Mesh

    from benchmark import system
    from cgnn_tpu.data.compact import (
        CompactSpec,
        compact_pack_fn,
        flat_rows,
        make_expander,
    )
    from cgnn_tpu.data.graph import capacities_for
    from cgnn_tpu.parallel.data_parallel import (
        parallel_batches,
        shard_scan_stack,
    )

    cfg = _float32_cell().config
    graphs, _ = system.load_pool(cfg)
    spec = CompactSpec.build(graphs, system.featurize_config(cfg).gdf(),
                             dense_m=12, edge_dtype=np.float32)
    nc, ec = capacities_for(graphs, 8, dense_m=12, snug=True)
    group = next(iter(parallel_batches(
        graphs, 4, 8, nc, ec, dense_m=12, snug=True,
        pack_fn=compact_pack_fn(spec))))
    stack = jax.tree_util.tree_map(lambda x: np.stack([x, x]), group)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    staged = shard_scan_stack(stack, mesh)
    n, m = group.distances.shape[-2:]
    for name in ("distances", "edge_mask", "in_mask"):
        assert getattr(staged, name).shape == (2, 4, n * m), name
        np.testing.assert_array_equal(
            np.asarray(getattr(staged, name)).reshape(2, 4, n, m),
            getattr(stack, name))
    assert sum(x.nbytes for x in jax.tree_util.tree_leaves(staged)) == \
        sum(x.nbytes for x in jax.tree_util.tree_leaves(stack))
    assert {len(s.data.shape) and s.data.shape[1] for x in
            jax.tree_util.tree_leaves(staged)
            for s in x.addressable_shards} == {1}  # a chip holds its row
    row = jax.tree_util.tree_map(lambda x: x[0], group)
    expand = jax.jit(make_expander(spec))
    for a, b in zip(jax.tree_util.tree_leaves(expand(row)),
                    jax.tree_util.tree_leaves(expand(flat_rows(row)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
