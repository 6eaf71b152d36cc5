"""Bring-up guards (ISSUE 21): nothing may hide the device.

The chip itself is exercised by ``chip_smoke.py`` through the chip tool;
these pin, on CPU, the refusals and placements that keep a CPU run from
passing for a chip run.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=300, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    env.update(env_overrides)
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_chip_smoke_refuses_a_non_tpu_platform():
    proc = _run([sys.executable, "chip_smoke.py"])
    assert proc.returncode not in (0, 3), proc.stdout
    assert "JAX found platform 'cpu', not 'tpu'" in proc.stderr
    # no result line: nothing a driver could read as a pass
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert "missing beside it" in proc.stderr and proc.stdout == ""


@pytest.mark.parametrize("cmd", [
    ["train.py", "--synthetic", "8"],
    ["predict.py", "no-such-ckpt", "--synthetic", "8"],
    ["serve.py", "no-such-ckpt"],
    ["continual.py", "no-such-ckpt", "--journal", "no-such-journal"],
])
def test_entry_points_refuse_device_tpu_on_cpu(cmd):
    proc = _run([sys.executable, *cmd, "--device", "tpu"])
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "--device=tpu requested but jax found cpu" in proc.stderr


_CACHE_PROBE = (
    "import jax, json; from cgnn_tpu.runtime import configure_compile_cache;"
    "r = configure_compile_cache({flag!r});"
    "print(json.dumps([r, jax.config.jax_compilation_cache_dir,"
    " jax.config.jax_enable_compilation_cache]))"
)


def _cache_probe(flag, **env):
    proc = _run([sys.executable, "-c", _CACHE_PROBE.format(flag=flag)],
                **env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_is_placed_from_outside(tmp_path):
    from cgnn_tpu.runtime import DEFAULT_COMPILE_CACHE

    assert DEFAULT_COMPILE_CACHE == os.path.join(REPO, ".jax_cache")
    env_dir, flag_dir = str(tmp_path / "env"), str(tmp_path / "flag")
    # unset: the one fixed in-checkout directory, or the flag's
    assert _cache_probe(None, JAX_COMPILATION_CACHE_DIR="") == [
        DEFAULT_COMPILE_CACHE, DEFAULT_COMPILE_CACHE, True]
    assert _cache_probe(flag_dir, JAX_COMPILATION_CACHE_DIR="") == [
        flag_dir, flag_dir, True]
    # set: JAX's own reading of the variable stands, whatever the flag
    for flag in (None, flag_dir):
        assert _cache_probe(flag, JAX_COMPILATION_CACHE_DIR=env_dir) == [
            env_dir, env_dir, True]
    # '' turns the cache off either way
    assert _cache_probe("", JAX_COMPILATION_CACHE_DIR=env_dir)[2] is False


def test_graph_cache_reads_each_member_once(tmp_path):
    """A per-graph ``z[key]`` re-reads the whole member and the slice
    keeps it alive: 8,192 geometry-carrying graphs cost graphs x array
    bytes and were OOM-killed at 40 GB (ISSUE 21 bring-up). Every graph's
    field must be a view of ONE shared array."""
    from cgnn_tpu.data.cache import load_graph_cache, save_graph_cache
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic

    graphs = load_synthetic(6, FeaturizeConfig(radius=5.0), seed=0,
                            keep_geometry=True)
    path = str(tmp_path / "c.npz")
    save_graph_cache(graphs, path)
    loaded = load_graph_cache(path)
    for field in ("positions", "offsets", "numbers", "atom_fea",
                  "edge_fea", "distances"):
        bases = [getattr(g, field).base for g in loaded]
        assert bases[0] is not None, field
        assert all(b is bases[0] for b in bases), field
    for g, h in zip(graphs, loaded):
        np.testing.assert_array_equal(g.numbers, h.numbers)
        np.testing.assert_array_equal(g.lattice, h.lattice)


def test_fleet_refuses_more_replicas_than_chips(monkeypatch):
    from cgnn_tpu.fleet import spawn

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(
        spawn.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 0, "tpu 1\n", ""))
    spawn.require_chips(1)
    with pytest.raises(RuntimeError, match="3 replica processes .* 1 tpu"):
        spawn.require_chips(3)
    # CPU fleets (tests, smoke scripts) have no chip to contend for
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    spawn.require_chips(64)


def test_failed_native_build_is_an_error(monkeypatch, tmp_path):
    from cgnn_tpu import native

    def fail(cmd, **kw):
        raise subprocess.CalledProcessError(1, cmd, stderr="boom")

    monkeypatch.setattr(native, "_LIB_PATH", str(tmp_path / "lib.so"))
    monkeypatch.setattr(native.subprocess, "run", fail)
    with pytest.raises(RuntimeError, match="(?s)failed to build.*boom"):
        native._build()
    # no compiler at all is the one case that falls back to numpy
    monkeypatch.setattr(native.shutil, "which", lambda name: None)
    assert native._build() is None
