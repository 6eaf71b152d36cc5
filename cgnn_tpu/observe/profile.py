"""On-demand device profiling: capture a bounded ``jax.profiler`` trace
from a LIVE process — no restart, no always-on overhead.

The pre-existing profiling story (``train.py --profile N``,
``observe.metrics_io.profile_trace``) decides at LAUNCH whether to
trace; a production server that starts misbehaving on Tuesday cannot be
relaunched with a flag. :class:`ProfileCapture` turns profiling into a
runtime request:

- ``POST /profile`` (serve/http.py) and ``SIGUSR2`` (both entrypoints)
  trigger ``capture()``: start a ``jax.profiler`` trace into a fresh
  timestamped directory under the run dir, hold it for a bounded window
  (capped at ``max_duration_s`` — an operator typo must not leave the
  profiler running for an hour), stop it, and — when a span tracer is
  attached — export the CURRENT host span buffer alongside it, so the
  device trace and the host orchestration window land together. They
  are on two clocks: ``host_trace.json`` is the span ring's
  (``perf_counter`` since the tracer was built; what ``/trace`` and
  ``trace_join`` read), the ``.xplane.pb`` is the profiler's. The spans
  opened during the capture are ALSO in the ``.xplane.pb``, as
  ``cgnn:<name>`` events of the ``/host:CPU`` plane (observe/spans.py):
  lay those, not ``host_trace.json``, over the device lines.
- The gate is a non-blocking lock: a capture that arrives while one is
  running is REJECTED (:class:`ProfileBusy`) rather than stacked —
  ``jax.profiler`` supports one trace at a time, and queueing captures
  would turn a monitoring poke into a profiling marathon.

Host-side only: starting/stopping the profiler never retraces any jitted
program, so the serving zero-recompile pin and trajectory bit-exactness
are untouched (pinned by tests).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable


class ProfileBusy(RuntimeError):
    """A capture was requested while another is still running."""


def _dir_stats(root: str) -> tuple[int, int]:
    """(file count, total bytes) under ``root``."""
    files = 0
    total = 0
    for dirpath, _, names in os.walk(root):
        for name in names:
            files += 1
            try:
                total += os.path.getsize(os.path.join(dirpath, name))
            except OSError:
                pass
    return files, total


class ProfileCapture:
    """One-at-a-time bounded device-trace captures into ``out_dir``."""

    def __init__(self, out_dir: str, *, spans=None,
                 default_duration_s: float = 1.0,
                 max_duration_s: float = 10.0,
                 log_fn: Callable = print):
        self.out_dir = out_dir
        self.spans = spans  # an observe.spans.SpanTracer, or None
        self.default_duration_s = float(default_duration_s)
        self.max_duration_s = float(max_duration_s)
        self._log = log_fn
        self._gate = threading.Lock()
        self.captures = 0
        self.rejected = 0
        self.last: dict | None = None

    @property
    def busy(self) -> bool:
        if self._gate.acquire(blocking=False):
            self._gate.release()
            return False
        return True

    def wait_idle(self, timeout_s: float = 60.0) -> bool:
        """Block until no capture is running (or the timeout passes).

        Shutdown paths call this before process exit: tearing the
        process down while ``jax.profiler`` holds an active trace
        segfaults in the profiler backend, so a drain must wait out an
        in-flight capture. Returns True when idle was reached.
        """
        if self._gate.acquire(timeout=timeout_s):
            self._gate.release()
            return True
        return False

    def capture(self, duration_s: float | None = None) -> dict:
        """Run one bounded capture; returns the artifact record
        ``{"dir", "duration_s", "files", "bytes", "host_trace"}``.

        Raises :class:`ProfileBusy` when a capture is already running
        (the non-stacking gate) and re-raises profiler start failures
        after releasing the gate.
        """
        duration = self.default_duration_s if duration_s is None \
            else float(duration_s)
        duration = max(0.05, min(duration, self.max_duration_s))
        if not self._gate.acquire(blocking=False):
            self.rejected += 1
            raise ProfileBusy(
                "a profile capture is already running; retry when it "
                "finishes (captures are rejected, never stacked)"
            )
        try:
            import jax

            stamp = time.strftime("%Y%m%d-%H%M%S")
            target = os.path.join(self.out_dir,
                                  f"profile-{stamp}-{self.captures:03d}")
            os.makedirs(target, exist_ok=True)
            t0 = time.perf_counter()
            jax.profiler.start_trace(target)
            try:
                # the capture window: whatever the process is doing runs
                # under the profiler for this long — dispatches from the
                # serving workers / the train loop, not synthetic work
                time.sleep(duration)
            finally:
                jax.profiler.stop_trace()
            record = {
                "dir": target,
                "duration_s": round(time.perf_counter() - t0, 3),
            }
            files, total = _dir_stats(target)
            record["files"], record["bytes"] = files, total
            if self.spans is not None:
                # the matching host window: the span buffer as of now,
                # exported NEXT TO the device trace (the Chrome-trace
                # stream keeps accumulating in the main trace.json)
                record["host_trace"] = self.spans.export(
                    os.path.join(target, "host_trace.json")
                )
            self.captures += 1
            self.last = record
            self._log(
                f"profile: captured {record['duration_s']:.2f}s device "
                f"trace -> {target} ({files} files, {total} bytes)"
            )
            return record
        finally:
            self._gate.release()


def install_sigusr2(capture: ProfileCapture,
                    log_fn: Callable = print) -> bool:
    """SIGUSR2 -> one default-duration capture on a background thread.

    The handler itself only spawns the thread (signal context must stay
    quick); a signal landing mid-capture is logged and dropped by the
    gate. Returns False (and installs nothing) off the main thread or on
    platforms without SIGUSR2 — callers treat profiling-by-signal as
    best-effort.
    """
    import signal

    if not hasattr(signal, "SIGUSR2"):
        return False

    def _run() -> None:
        try:
            capture.capture()
        except ProfileBusy as e:
            log_fn(f"profile: SIGUSR2 ignored ({e})")
        except Exception as e:  # noqa: BLE001 — a failed capture must
            log_fn(f"profile: capture failed: {e!r}")  # not kill the run

    def _handler(signum, frame):  # noqa: ARG001 — signal API
        threading.Thread(target=_run, daemon=True,
                         name="cgnn-profile-sigusr2").start()

    try:
        signal.signal(signal.SIGUSR2, _handler)
    except ValueError:  # not the main thread
        return False
    return True
