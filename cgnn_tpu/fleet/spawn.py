"""Replica process lifecycle: boot serve.py subprocesses, wait for
readiness, kill them (the chaos harness's kill -9 leg), restart them.

Each replica is a REAL process running the existing serve.py entrypoint
against the SAME checkpoint directory — which is exactly what makes
rolling promotion work with no new machinery: every replica's own
CheckpointWatcher (PR 3) polls that directory, so one committed save
rolls across the fleet within a poll interval, each replica swapping
atomically mid-load like the single-process invariant always promised.

``wait_ready`` polls ``GET /healthz`` until it reports ``ready`` (the
ISSUE-14 readiness split: a warming replica answers 503, so the fleet
never routes traffic into cold-compile latency).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import Callable

from cgnn_tpu.fleet.replica import FleetTransportError, http_get_json

_SERVE_PY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "serve.py")


_PROBE = ("import jax; d = jax.local_devices(); "
          "print(d[0].platform, len(d))")


def require_chips(n_replicas: int) -> None:
    """Refuse a fleet that needs more accelerator chips than the host has.

    A chip belongs to one process at a time and every replica is its own
    serve.py process, so ``n_replicas`` processes need ``n_replicas``
    chips (an in-process replica mode is ROADMAP B3). The platform and
    chip count come from a short-lived child: the fleet parent must
    never initialise a JAX backend itself, or it would hold the chip its
    replicas need. ``JAX_PLATFORMS=cpu`` (tests, the smoke scripts) has
    no chips to contend for and skips the probe. Raises RuntimeError."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return
    probe = subprocess.run([sys.executable, "-c", _PROBE],
                           capture_output=True, text=True, timeout=300)
    if probe.returncode != 0:
        raise RuntimeError(
            f"fleet: device probe failed:\n{probe.stderr[-2000:]}")
    platform, count = probe.stdout.split()[-2:]
    if platform != "cpu" and n_replicas > int(count):
        raise RuntimeError(
            f"fleet: {n_replicas} replica processes requested but this "
            f"host has {count} {platform} chip(s); a chip belongs to one "
            f"process at a time (in-process replicas: ROADMAP B3). Lower "
            f"--replicas/--max-replicas, or set JAX_PLATFORMS=cpu for a "
            f"CPU fleet"
        )


class ReplicaProcess:
    """One serve.py subprocess bound to a fixed port (stable across
    restarts, so the router's endpoint list never changes)."""

    def __init__(
        self,
        rid: int,
        ckpt_dir: str,
        port: int,
        *,
        host: str = "127.0.0.1",
        log_path: str | None = None,
        serve_args: list | None = None,
        env: dict | None = None,
        serve_py: str = _SERVE_PY,
    ):
        self.rid = int(rid)
        self.ckpt_dir = ckpt_dir
        self.host = host
        self.port = int(port)
        self.base_url = f"http://{host}:{port}"
        self.log_path = log_path
        self.serve_args = list(serve_args or [])
        self.env = dict(env) if env is not None else None
        self.serve_py = serve_py
        self.proc: subprocess.Popen | None = None
        self.starts = 0
        self.kills = 0

    def start(self) -> "ReplicaProcess":
        if self.proc is not None and self.proc.poll() is None:
            return self
        cmd = [sys.executable, self.serve_py, self.ckpt_dir,
               "--host", self.host, "--port", str(self.port),
               *self.serve_args]
        env = dict(os.environ if self.env is None else self.env)
        log = (open(self.log_path, "ab")
               if self.log_path else subprocess.DEVNULL)
        try:
            self.proc = subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT, env=env)
        finally:
            if self.log_path:
                log.close()  # the child holds its own fd now
        self.starts += 1
        return self

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def wait_ready(self, timeout_s: float = 300.0,
                   poll_s: float = 0.25) -> bool:
        """Poll /healthz until ready (True) or the process dies / the
        timeout passes (False)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if not self.alive():
                return False
            try:
                status, payload = http_get_json(
                    self.base_url + "/healthz", timeout_s=2.0)
                if status == 200 and payload.get("ready", True):
                    return True
            except FleetTransportError:
                pass  # not listening yet
            time.sleep(poll_s)
        return False

    def kill9(self) -> None:
        """The chaos leg: SIGKILL, no drain, no cleanup — in-flight
        requests die with their sockets, exactly like a machine loss."""
        if self.proc is not None and self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGKILL)
            self.proc.wait(timeout=30)
            self.kills += 1

    def terminate(self, timeout_s: float = 60.0) -> int | None:
        """SIGTERM -> the replica's graceful drain; returns its exit
        code (None if it had to be killed after the timeout)."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                return self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
                return None
        return self.proc.poll()

    def restart(self) -> "ReplicaProcess":
        """Bring a (dead) replica back on its port."""
        if self.alive():
            self.kill9()
        return self.start()


class RestartBackoff:
    """The crash-loop guard (ISSUE 17): a replica that dies during
    boot/warmup waits exponentially longer before each retry and the
    supervisor GIVES UP after ``give_up`` attempts — a broken
    checkpoint or a poisoned flag must never hot-loop respawns.

    Pure arithmetic on an injectable clock; ``next_delay()`` returns
    the seconds to wait before the next attempt or None when the
    budget is spent. ``reset()`` on the first healthy boot restores
    the full budget (an occasional preemption is not a crash loop)."""

    def __init__(self, *, base_s: float = 0.5, mult: float = 2.0,
                 max_s: float = 30.0, give_up: int = 5,
                 clock: Callable[[], float] = time.monotonic):
        if give_up < 1:
            raise ValueError(f"give_up must be >= 1, got {give_up}")
        self.base_s = float(base_s)
        self.mult = float(mult)
        self.max_s = float(max_s)
        self.give_up = int(give_up)
        self._clock = clock
        self.failures = 0
        self.last_failure_t: float | None = None

    def next_delay(self) -> float | None:
        """Record one boot failure; -> seconds to back off before the
        next attempt, or None when the give-up cap is spent."""
        self.failures += 1
        self.last_failure_t = self._clock()
        if self.failures >= self.give_up:
            return None
        return min(self.base_s * self.mult ** (self.failures - 1),
                   self.max_s)

    def reset(self) -> None:
        self.failures = 0
        self.last_failure_t = None

    def stats(self) -> dict:
        return {"failures": self.failures, "give_up": self.give_up,
                "base_s": self.base_s, "max_s": self.max_s}


def boot_with_retries(
    proc: ReplicaProcess,
    *,
    wait_ready_s: float = 300.0,
    backoff: RestartBackoff | None = None,
    log_fn: Callable = print,
    sleep: Callable[[float], None] = time.sleep,
) -> bool:
    """Supervised boot: start ``proc`` and wait for readiness,
    restarting through ``backoff`` when it dies during boot/warmup;
    -> True once healthy, False when the backoff gives up (the proc is
    reaped). The ``boot_crash=N`` fault point pins this: N boots die
    during warmup, the N+1st succeeds — under the default budget the
    supervisor outlasts the fault without hot-looping."""
    backoff = backoff or RestartBackoff()
    while True:
        proc.start()
        if proc.wait_ready(wait_ready_s):
            backoff.reset()
            return True
        delay = backoff.next_delay()
        if proc.alive():
            # ready-timeout, not a crash: a wedged warmup retries too,
            # but the old process must die first
            proc.kill9()
        if delay is None:
            log_fn(f"fleet: replica{proc.rid} crash-looped "
                   f"{backoff.failures}x during boot; giving up")
            proc.terminate(timeout_s=5.0)
            return False
        log_fn(f"fleet: replica{proc.rid} died during boot "
               f"(attempt {backoff.failures}); retrying in {delay:.2f}s")
        sleep(delay)


def spawn_fleet(
    ckpt_dir: str,
    n: int,
    *,
    base_port: int = 8441,
    host: str = "127.0.0.1",
    log_dir: str | None = None,
    serve_args: list | None = None,
    wait_ready_s: float = 300.0,
) -> list:
    """Boot ``n`` replicas on consecutive ports and wait until every
    one reports ready. Raises RuntimeError (after terminating the
    stragglers) when any replica fails to come up, or when the host has
    fewer accelerator chips than replicas (:func:`require_chips`)."""
    require_chips(n)
    procs = []
    for i in range(n):
        log_path = (os.path.join(log_dir, f"replica-{i}.log")
                    if log_dir else None)
        procs.append(ReplicaProcess(
            i, ckpt_dir, base_port + i, host=host, log_path=log_path,
            serve_args=serve_args,
        ).start())
    failed = [p.rid for p in procs if not p.wait_ready(wait_ready_s)]
    if failed:
        for p in procs:
            p.terminate(timeout_s=5.0)
        raise RuntimeError(
            f"replicas {failed} never became ready within "
            f"{wait_ready_s:.0f} s (logs: {log_dir or 'discarded'})"
        )
    return procs
