"""Entrypoint regression tests (SURVEY.md §4.4, VERDICT.md next-step #9).

These run the driver-facing and user-facing entrypoints the way their real
callers do — in subprocesses with realistic (sometimes hostile) environments
— to catch the platform/env bug class that unit tests cannot see.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, env_overrides=None, timeout=600):
    env = dict(os.environ)
    # simulate the driver env: no pytest-conftest CPU pinning
    env.pop("_CGNN_DRYRUN_CHILD", None)
    if env_overrides:
        env.update(env_overrides)
    return subprocess.run(
        cmd, cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


def test_dryrun_multichip_survives_pinned_tpu_platform():
    """The environment may pin JAX_PLATFORMS to the chip; the dry run
    must self-provision a virtual CPU mesh anyway (round-1 red check)."""
    code = "import __graft_entry__ as g; g.dryrun_multichip(2)"
    proc = _run(
        [sys.executable, "-c", code],
        env_overrides={"JAX_PLATFORMS": "tpu", "XLA_FLAGS": ""},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "step ok" in proc.stdout, proc.stdout


def test_train_resume_predict_cycle(tmp_path):
    """The reference workflow end to end, as subprocesses with a clean env:
    train 2 epochs -> --resume 1 more -> predict.py -> CSV rows match.
    Catches the platform/env regression class (VERDICT round 1 weak #1)."""
    ckpt = str(tmp_path / "ckpt")
    env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    base = [
        sys.executable, "train.py", "--synthetic", "64", "--device", "cpu",
        "--epochs", "2", "--optim", "Adam", "-b", "16", "--radius", "5",
        "--ckpt-dir", ckpt, "--print-freq", "0",
    ]
    p1 = _run(base, env_overrides=env)
    assert p1.returncode == 0, p1.stderr[-2000:]
    assert "Epoch 1:" in p1.stdout and "** test mae:" in p1.stdout

    # machine-readable metrics were produced (SURVEY.md §5)
    metrics_file = os.path.join(ckpt, "logs", "metrics.jsonl")
    assert os.path.exists(metrics_file)
    lines = open(metrics_file).read().strip().splitlines()
    assert len(lines) >= 4  # train+val per epoch (+ test)
    import json

    rec = json.loads(lines[0])
    assert "train/loss" in rec and rec["step"] == 0

    assert base[6] == "--epochs"
    p2 = _run(
        base[:7] + ["3"] + base[8:] + ["--resume", ckpt],
        env_overrides=env,
    )
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "resumed from" in p2.stdout and "at epoch 2" in p2.stdout
    assert "Epoch 2:" in p2.stdout
    assert "Epoch 0:" not in p2.stdout  # numbering continued, not restarted

    out_csv = str(tmp_path / "preds.csv")
    p3 = _run(
        [sys.executable, "predict.py", ckpt, "unused", "--device", "cpu",
         "--synthetic", "16", "-b", "16", "--out", out_csv],
        env_overrides=env,
    )
    assert p3.returncode == 0, p3.stderr[-2000:]
    rows = open(out_csv).read().strip().splitlines()
    assert len(rows) == 16
    cid, target, pred = rows[0].split(",")
    float(target), float(pred)  # numeric columns
    assert cid.startswith("synth-")

    # the raw wire over a graph cache: a cache built with geometry
    # round-trips species and rides it; one built without cannot, and an
    # explicit --wire raw that admits nothing is an error, not a
    # "0 structures/sec" success
    from cgnn_tpu.data.cache import save_graph_cache
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic

    cfg = FeaturizeConfig(radius=5.0)
    for geometry in (True, False):
        cache = str(tmp_path / f"cache_{int(geometry)}.npz")
        save_graph_cache(
            load_synthetic(16, cfg, seed=1, keep_geometry=geometry), cache)
        p4 = _run(
            [sys.executable, "predict.py", ckpt, "--device", "cpu",
             "--cache", cache, "--wire", "raw", "-b", "16",
             "--out", out_csv],
            env_overrides=env,
        )
        if geometry:
            assert p4.returncode == 0, p4.stderr[-2000:]
            assert "(raw wire, in-program neighbor search" in p4.stdout
            assert " 0/16 structures raw-staged" not in p4.stdout
        else:
            assert p4.returncode == 2, p4.stdout[-2000:]
            assert "--wire raw: 0/16 structures can ride" in p4.stderr


def test_train_cli_data_parallel_checkpoint_predicts_on_one_device(tmp_path):
    """--data-parallel at its defaults over 8 virtual devices (the per-step
    loop, a 1-D 'data' mesh) trains end to end from the CLI, and what it
    saved restores where there is no mesh."""
    proc = _run(
        [sys.executable, "train.py", "--synthetic", "48", "--device", "cpu",
         "--epochs", "1", "-b", "2", "--radius", "5", "--data-parallel",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--print-freq", "0"],
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Epoch 0 [dp x8]" in proc.stdout, proc.stdout
    assert "** test mae:" in proc.stdout

    # a checkpoint saved from the 8-device mesh must restore in a plain
    # single-device predict process (topology-independent saves)
    out_csv = str(tmp_path / "preds.csv")
    p2 = _run(
        [sys.executable, "predict.py", str(tmp_path / "ckpt"), "unused",
         "--device", "cpu", "--synthetic", "8", "-b", "8", "--out", out_csv],
        env_overrides={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert len(open(out_csv).read().strip().splitlines()) == 8


def test_dryrun_multichip_child_guard_runs_inline():
    """With the child guard set, dryrun must execute inline (no recursion)."""
    code = (
        "import __graft_entry__ as g; g.dryrun_multichip(2); "
        "import sys; print('CHILDMODE-DONE')"
    )
    proc = _run(
        [sys.executable, "-c", code],
        env_overrides={
            "_CGNN_DRYRUN_CHILD": "1",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CHILDMODE-DONE" in proc.stdout
