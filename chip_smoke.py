#!/usr/bin/env python3
"""Chip smoke: train -> predict -> serve on the accelerator, once, through
the entry points a user calls, at the flagship width (F=64, 3 convs, h=128,
M=12, radius 8 / 41 Gaussians, bf16) on 8,192 MP-like synthetic structures.

    python3 chip_smoke.py            # on a machine with a TPU; exit 0 = pass
    python3 chip_smoke.py --dry-run  # tiny, any platform; never exits 0

It is the quickest proof that the system still starts on the chip, not a
benchmark: no timing it prints is a result. It fails (non-zero exit, no
result line) when JAX finds no TPU, when any phase fails, and in a
directory that holds nothing else of the repo.

This parent process never imports JAX: a chip belongs to one process at a
time, so every phase that needs it is a child that exits before the next
one starts. The two children that need no device (featurization, the CPU
reference) are pinned to JAX_PLATFORMS=cpu.

The last line of standard output is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import csv
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ENTRY_POINTS = ("train.py", "predict.py", "serve.py")
# large artefacts (graph cache, checkpoints) stay out of chiprun_out/,
# which the chip tool copies back; the child logs are small and go there
WORK = os.path.join(HERE, ".chip_smoke")
LOGS = os.path.join(HERE, "chiprun_out", "chip_smoke")

N_STRUCTURES = 8192
N_DRY_RUN = 512
N_REFERENCE = 64      # structures the CPU reference predicts
N_REQUESTS = 6        # per wire form
# bf16 forward on two backends (or one backend at two batch shapes)
# differs in the last bits of every accumulation: 2.0e-4 and 0.9e-4
# measured on the v5e (PERF.md, bring-up). A wrong program is off by the
# scale of the targets themselves (std ~0.2).
CHIP_VS_CPU_ATOL = 0.01
SERVE_VS_PREDICT_ATOL = 0.005
MODEL_IMPL_DEFAULT = "dtype=bfloat16 layout=dense"

_PROBE = ("import jax, json; d = jax.devices(); "
          "print(json.dumps({'platform': d[0].platform, "
          "'kind': d[0].device_kind, 'count': len(d)}))")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def run_child(name: str, cmd: list, *, cpu: bool = False,
              timeout: float = 900.0) -> tuple[str, float]:
    """Run one child to completion -> (its output, seconds). Output is
    kept in LOGS/<name>.log; a non-zero exit fails the smoke with the
    log's tail."""
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    log_path = os.path.join(LOGS, name + ".log")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        try:
            proc = subprocess.run(cmd, cwd=HERE, env=env, stdout=log,
                                  stderr=subprocess.STDOUT, timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{name}: no exit within {timeout:.0f} s "
                f"(log: {log_path})") from None
    dt = time.monotonic() - t0
    with open(log_path) as f:
        out = f.read()
    if proc.returncode != 0:
        raise SmokeFailure(
            f"{name}: exit code {proc.returncode}\n"
            + "\n".join(out.splitlines()[-25:]))
    return out, dt


def model_impl_line(name: str, out: str, platform: str) -> str:
    """The child's 'model impl:' line; it must name the platform's
    backend, bf16 compute and the dense layout."""
    lines = [ln for ln in out.splitlines() if "model impl:" in ln]
    check(lines, f"{name}: no 'model impl:' line in its output")
    line = lines[0][lines[0].index("model impl:"):]
    check(f"backend={platform}" in line and MODEL_IMPL_DEFAULT in line,
          f"{name}: unexpected backend, dtype or layout: {line}")
    return line


# ---- phase 0: what does JAX find -------------------------------------


def probe_device() -> dict:
    proc = subprocess.run([sys.executable, "-c", _PROBE], cwd=HERE,
                          capture_output=True, text=True, timeout=300)
    check(proc.returncode == 0,
          "device probe failed (JAX found no usable backend):\n"
          + proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---- phase 1 (child, CPU-pinned): featurize + cache -------------------


def prep_child(n: int) -> int:
    """Runs in a child: build the graph cache, the reference subset and
    the request bodies. Host-side numpy/C++ only."""
    import numpy as np

    from cgnn_tpu.data.cache import save_graph_cache
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
    from cgnn_tpu.data.rawbatch import host_image_counts, raw_from_graph
    from cgnn_tpu.native import native_available

    cfg = FeaturizeConfig()  # train.py's defaults: radius 8, M=12, step 0.2
    backend = "native" if native_available() else "numpy"
    graphs = load_synthetic_mp(n, cfg, seed=0, keep_geometry=True)
    save_graph_cache(graphs, os.path.join(WORK, "mp.npz"))
    save_graph_cache(graphs[:N_REFERENCE], os.path.join(WORK, "ref.npz"))

    # structure requests must ride the raw wire, so take them from the
    # comfortable middle of the distribution the raw rung caps are
    # calibrated on (0.95 coverage of atom count and periodic images)
    sizes = np.array([g.num_nodes for g in graphs])
    images = np.array([host_image_counts(g.lattice, cfg.radius)
                       for g in graphs])
    middle = np.nonzero((sizes <= np.median(sizes))
                        & (images <= np.median(images, axis=0)).all(axis=1)
                        )[0][:2 * N_REQUESTS]
    if len(middle) < 2 * N_REQUESTS:
        raise SystemExit("too few mid-sized structures for the requests")
    bodies = []
    for k, i in enumerate(middle):
        g = graphs[int(i)]
        if k < N_REQUESTS:
            rs = raw_from_graph(g)
            body = {"structure": {
                "id": g.cif_id, "lattice": rs.lattice.tolist(),
                "frac_coords": rs.frac_coords.tolist(),
                "numbers": rs.numbers.tolist()}}
        else:
            body = {"graph": {
                "id": g.cif_id,
                "atom_fea": np.asarray(g.atom_fea).tolist(),
                "edge_fea": np.asarray(g.edge_fea).tolist(),
                "centers": g.centers.tolist(),
                "neighbors": g.neighbors.tolist()}}
        bodies.append(body)
    with open(os.path.join(WORK, "requests.json"), "w") as f:
        json.dump(bodies, f, allow_nan=False)
    print(json.dumps({
        "neighbor_backend": backend, "structures": len(graphs),
        "mean_atoms": float(sizes.mean()),
        "target_std": float(np.std([g.target[0] for g in graphs])),
    }, allow_nan=False))
    return 0


# ---- phase 2: train ---------------------------------------------------


def phase_train(platform: str, dry_run: bool) -> dict:
    ckpt = os.path.join(WORK, "ckpt")
    out, dt = run_child("train", [
        sys.executable, "train.py", "--cache", os.path.join(WORK, "mp.npz"),
        "--device", "auto" if dry_run else "tpu", "--bf16", "--buckets", "3",
        "-b", "512", "--device-resident", "--epochs", "2",
        "--ckpt-dir", ckpt,
    ])
    impl = model_impl_line("train", out, platform)
    check("compact staging: on" in out,
          "train: the scan driver did not take compact staging")
    with open(os.path.join(ckpt, "logs", "manifest.json")) as f:
        manifest = json.load(f)
    losses, epoch_s, hbm = [], [], []
    with open(os.path.join(ckpt, "logs", "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "train/loss" in rec:
                losses.append(rec["train/loss"])
            if rec.get("event") == "hbm":
                hbm.append(rec)
    for line in out.splitlines():
        if line.startswith("Epoch ") and line.rstrip().endswith("s)"):
            epoch_s.append(float(line.rsplit("(", 1)[1].rstrip("s) \n")))
    check(len(losses) == 2 and all(math.isfinite(x) for x in losses),
          f"train: expected 2 finite epoch losses, got {losses}")
    check(losses[1] < losses[0], f"train: loss did not fall: {losses}")
    saves = sorted(d for d in os.listdir(ckpt) if d.startswith("ckpt-")
                   and os.path.exists(os.path.join(ckpt, d, "MANIFEST.json")))
    check(saves, f"train: no committed checkpoint under {ckpt}")
    say(f"[train] {impl}")
    say(f"[train] manifest: backend={manifest['backend']} "
        f"kind={manifest['devices'][0]['kind']} "
        f"count={manifest['device_count']} jax={manifest['jax_version']}")
    if hbm:
        say(f"[train] memory_stats at end of run: "
            f"{ {k: hbm[-1].get(k) for k in ('source', 'bytes_limit', 'bytes_in_use')} }")
    # the first epoch carries the compiles; the second is steady state
    say(f"[train] loss {losses[0]:.4f} -> {losses[1]:.4f}; committed "
        f"{saves[-1]}; child {dt:.1f} s, of which epoch 0 (compile + "
        f"first dispatch) {epoch_s[0]:.1f} s, epoch 1 {epoch_s[1]:.1f} s")
    return {"manifest": manifest, "ckpt": ckpt, "seconds": dt,
            "first_epoch_s": epoch_s[0]}


# ---- phase 3: predict (+ CPU reference on a small input) --------------


def read_predictions(path: str) -> dict:
    with open(path, newline="") as f:
        return {row[0]: float(row[-1]) for row in csv.reader(f)}


def phase_predict(ckpt: str, platform: str, n: int, dry_run: bool) -> dict:
    out_csv = os.path.join(WORK, "pred.csv")
    cmd = [sys.executable, "predict.py", ckpt,
           "--cache", os.path.join(WORK, "mp.npz"),
           "--device", "auto" if dry_run else "tpu", "--out", out_csv]
    if dry_run:  # take the accelerator branches 'auto' skips on CPU
        cmd += ["--compact", "on", "--wire", "raw", "--pack-workers", "2"]
    out, dt = run_child("predict", cmd)
    model_impl_line("predict", out, platform)
    paths = [ln for ln in out.splitlines()
             if ln.startswith("inference throughput:")]
    check(paths, "predict: no 'inference throughput' line")
    check(any("raw wire" in ln and " 0/" not in ln for ln in paths),
          f"predict: the raw wire staged nothing: {paths}")
    preds = read_predictions(out_csv)
    check(len(preds) == n, f"predict: {len(preds)} predictions for {n}")
    check(all(math.isfinite(v) for v in preds.values()),
          "predict: non-finite predictions")
    for ln in paths:
        say(f"[predict] path: {ln.split('(', 1)[1].rstrip(')')}")
    say(f"[predict] {len(preds)} finite predictions; child {dt:.1f} s "
        f"(compile included)")

    ref_csv = os.path.join(WORK, "ref.csv")
    _, ref_dt = run_child("reference", [
        sys.executable, "predict.py", ckpt,
        "--cache", os.path.join(WORK, "ref.npz"), "--device", "cpu",
        "--out", ref_csv], cpu=True)
    ref = read_predictions(ref_csv)
    check(len(ref) == N_REFERENCE, f"reference: {len(ref)} predictions")
    worst = max(abs(preds[k] - v) for k, v in ref.items())
    check(worst <= CHIP_VS_CPU_ATOL,
          f"predict: {platform} and the CPU reference differ by {worst:.4f} "
          f"(> {CHIP_VS_CPU_ATOL}) on the {N_REFERENCE}-structure input")
    say(f"[predict] agrees with the CPU reference on {N_REFERENCE} "
        f"structures: max |diff| {worst:.5f} (<= {CHIP_VS_CPU_ATOL}); "
        f"reference child {ref_dt:.1f} s")
    return {"preds": preds, "seconds": dt}


# ---- phase 4: serve ---------------------------------------------------


def http_json(url: str, body: dict | None = None, timeout: float = 60.0):
    data = (None if body is None
            else json.dumps(body, allow_nan=False).encode())
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def phase_serve(ckpt: str, preds: dict, platform: str,
                dry_run: bool) -> dict:
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "serve.py", ckpt,
           "--device", "auto" if dry_run else "tpu", "--port", str(port),
           "--calibration-cache", os.path.join(WORK, "mp.npz")]
    if dry_run:
        cmd += ["--compact", "on", "--wire", "raw", "--pack-workers", "1"]
    with open(os.path.join(WORK, "requests.json")) as f:
        bodies = json.load(f)
    log_path = os.path.join(LOGS, "serve.log")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, stdout=log,
                                stderr=subprocess.STDOUT)
    try:
        # ready = every (rung, form) program compiled
        while True:
            check(proc.poll() is None,
                  f"serve: exited {proc.returncode} before ready "
                  f"(log: {log_path})")
            check(time.monotonic() - t0 < 600,
                  "serve: not ready within 600 s")
            try:
                status, health = http_json(base + "/healthz", timeout=5)
                if status == 200 and health.get("ready"):
                    break
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(1.0)
        warm_s = time.monotonic() - t0
        t1 = time.monotonic()
        worst = 0.0
        for body in bodies:
            form = "structure" if "structure" in body else "graph"
            sid = body[form]["id"]
            status, resp = http_json(base + "/predict", body)
            check(status == 200, f"serve: {form} request {sid}: HTTP "
                                 f"{status} {resp}")
            pred = resp.get("prediction")
            check(isinstance(pred, list) and len(pred) == 1
                  and math.isfinite(pred[0]),
                  f"serve: {sid}: bad prediction {pred!r}")
            # a repeated body would be answered by the result cache
            # (device_id -1) and prove nothing about the device
            check(not resp["cached"] and resp["device_id"] >= 0,
                  f"serve: {sid} was not answered by a device: {resp}")
            want = "raw" if form == "structure" else "featurized"
            check(resp["wire"] == want,
                  f"serve: {form} request {sid} rode the {resp['wire']!r} "
                  f"wire, expected {want!r}")
            worst = max(worst, abs(pred[0] - preds[sid]))
        check(worst <= SERVE_VS_PREDICT_ATOL,
              f"serve: answers differ from predict.py's by {worst:.4f} "
              f"(> {SERVE_VS_PREDICT_ATOL})")
        run_s = time.monotonic() - t1
        _, stats = http_json(base + "/stats")
        devs = stats["devices"]
        check(all(d["platform"] == platform for d in devs),
              f"serve: /stats devices are not {platform}: {devs}")
        check(stats["recompiles_after_warm"] == 0,
              f"serve: {stats['recompiles_after_warm']} compiles after "
              f"warm-up")
        # flush errors are caught per flush and the process keeps
        # serving, so judge by the counters, not by the exit code alone
        counts = stats["counts"]
        check(counts["responses"] == len(bodies)
              and counts.get("responses_raw", 0) == N_REQUESTS
              and sum(d["dispatches"] for d in devs) >= 1,
              f"serve: unexpected counters {counts}")
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("serve: no exit within 90 s of SIGTERM")
        check(rc == 0, f"serve: drain exited {rc} (log: {log_path})")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    with open(log_path) as f:
        out = f.read()
    model_impl_line("serve", out, platform)
    check("batch failed" not in out and "pipeline error" not in out,
          f"serve: a flush failed (log: {log_path})")
    warmed = [ln for ln in out.splitlines() if "serve: warmed" in ln]
    say(f"[serve] /stats: platform={devs[0]['platform']} "
        f"kind={devs[0]['kind']} count={len(devs)} "
        f"engine={stats['engine']}")
    if warmed:
        say(f"[serve] {warmed[0][warmed[0].index('serve: warmed'):]}")
    say(f"[serve] ready after {warm_s:.1f} s (set-up: restore + warm); "
        f"{len(bodies)} distinct requests ({N_REQUESTS} structure -> raw "
        f"wire, {N_REQUESTS} graph -> featurized) all 200 in {run_s:.2f} s;"
        f" max |serve - predict| {worst:.5f}; 0 post-warm-up compiles; "
        f"drain exit 0")
    return {"devices": devs, "warm_s": warm_s}


# ---- driver -----------------------------------------------------------


def main(argv=None) -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "_prep":
        return prep_child(int(sys.argv[2]))
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--dry-run", action="store_true",
                   help=f"orchestration check: {N_DRY_RUN} structures on "
                        "whatever platform JAX finds, accelerator branches "
                        "forced by flag. Never exits 0 and never prints "
                        "ok=true — it proves nothing about the chip")
    args = p.parse_args(argv)

    missing = [f for f in ENTRY_POINTS + ("cgnn_tpu",)
               if not os.path.exists(os.path.join(HERE, f))]
    if missing:
        print(f"chip_smoke.py drives the repository's entry points; "
              f"missing beside it: {', '.join(missing)}", file=sys.stderr)
        return 2
    os.makedirs(LOGS, exist_ok=True)
    t_start = time.monotonic()
    try:
        device = probe_device()
        platform = device["platform"]
        say(f"[probe] platform={platform} device_kind={device['kind']} "
            f"count={device['count']}")
        if platform != "tpu" and not args.dry_run:
            raise SmokeFailure(
                f"JAX found platform {platform!r}, not 'tpu': this smoke "
                f"proves the program on the chip and refuses any other "
                f"device (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
        n = N_DRY_RUN if args.dry_run else N_STRUCTURES

        # a fresh work directory: stale checkpoints must not answer
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        out, prep_s = run_child(
            "prep", [sys.executable, os.path.abspath(__file__), "_prep",
                     str(n)], cpu=True)
        prep = json.loads(out.strip().splitlines()[-1])
        say(f"[cache] {prep['structures']} structures (mean "
            f"{prep['mean_atoms']:.1f} atoms) featurized by the "
            f"{prep['neighbor_backend']} neighbor search and cached in "
            f"{prep_s:.1f} s (set-up)")
        check(prep["neighbor_backend"] == "native",
              "cache: the C++ neighbor search did not build; the numpy "
              "fallback is not the production featurizer")

        train = phase_train(platform, args.dry_run)
        predict = phase_predict(train["ckpt"], platform, n, args.dry_run)
        serve = phase_serve(train["ckpt"], predict["preds"], platform,
                            args.dry_run)

        reported = {
            (train["manifest"]["backend"],
             train["manifest"]["devices"][0]["kind"],
             train["manifest"]["device_count"]),
            (serve["devices"][0]["platform"], serve["devices"][0]["kind"],
             len(serve["devices"])),
            (device["platform"], device["kind"], device["count"]),
        }
        check(len(reported) == 1,
              f"children disagree on the device: {sorted(reported)}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    say(f"[total] {time.monotonic() - t_start:.1f} s; set-up: cache "
        f"{prep_s:.1f} s, train first epoch {train['first_epoch_s']:.1f} s, "
        f"serve warm {serve['warm_s']:.1f} s")
    if args.dry_run:
        say("dry run complete: orchestration works; this is NOT a chip "
            "result")
        print(json.dumps({"ok": False, "dry_run": True, "device": device},
                         allow_nan=False))
        return 3
    print(json.dumps({"ok": True, "device": device}, allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
