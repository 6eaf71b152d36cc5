"""Device inventory + thread-per-device dispatch accounting (ISSUE 5).

The CGCNN workload is embarrassingly parallel at inference — independent
graphs, no cross-request state — yet until this module both forward
paths dispatched every batch to ``jax.devices()[0]``, idling every other
chip on a multi-chip host. ``DeviceSet`` made the device dimension a
first-class part of the dispatch layer.

ENGINE NOTE (ISSUE 10): thread-per-device dispatch is no longer the
only — or the default — multi-device engine. The default for a
multi-device set is the MESH engine (``parallel/executor.py``): one
``Mesh`` + ``NamedSharding`` jitted program per (rung, form, tier)
whose single batch-sharded dispatch covers every device — no router,
no per-device threads, compile count = programs (not programs x N),
one sharded param tree per tier, and the same layer extends multi-host
via ``jax.distributed`` (``parallel/dist.py``). The DeviceSet dispatch
path stays available behind ``--engine threads`` as the A/B baseline,
and this module's ACCOUNTING (per-device dispatch/occupancy stats)
serves both engines — under mesh dispatch the "device" rows are the
mesh shards. The replica-dispatch description below documents the
threads engine:

- **Replicated programs.** ONE jitted ``predict_step`` is shared across
  the set. Dispatch targets a device by computation-follows-data: the
  per-device param replica is committed to its device, the host batch is
  uncommitted, so the call runs where the params live — no explicit
  placement per dispatch. Tracing happens once per (rung, staging form)
  regardless of N (the jit trace cache keys on abstract values, not
  devices); XLA then builds one executable per device at WARMUP, because
  a compiled artifact is bound to its device assignment. After warmup
  nothing ever compiles — the same pin as ISSUE 3, now × N devices (and,
  with precision tiers, × tiers — serve/quantize.py: a tier is its own
  traced program, warmed on every device like any other): the jit cache
  size is ``programs * len(devices)`` and must not grow under load
  (checked per flush by the server, by the loadgen, and by tests).

- **Replicated params** live in :class:`serve.reload.ParamStore` (one
  replica per device, swapped atomically under a single version — see
  reload.py); this module only carries the device inventory and the
  dispatch bookkeeping.

- **Dispatcher accounting.** ``pick()`` chooses the least-loaded device
  (fewest in-flight dispatches, round-robin tie-break), and per-device
  counters (dispatches, busy seconds, window depth) feed the
  ``device_gauges`` rollup in observe/gauges.py.

Device-awareness default (the PR-4 lesson, third time paying off):
``resolve_devices('auto')`` is ALL local devices on an accelerator
backend but a SINGLE device on CPU — host-platform "devices" are slices
of the same cores, so fanning out over them just adds dispatch overhead
and thread contention to the compute they share. The CPU ``auto`` rule
applies to WHICH devices are used; the ``--engine`` flag picks how a
multi-device set is driven (mesh by default, threads for the A/B). An
explicit count (``--devices N``) forces distribution anywhere, which is
how the 8-host-device dryruns
(``--xla_force_host_platform_device_count=8``, the MULTICHIP pattern)
prove distribution, parity, and swap invariants for both engines
in-container.
"""

from __future__ import annotations

import time
from typing import Sequence

from cgnn_tpu.analysis import racecheck


def resolve_devices(spec="auto"):
    """``spec`` -> a concrete list of local jax devices.

    - ``'auto'`` (default): all local devices on accelerator backends;
      just ``[devices()[0]]`` on a CPU backend, where the "devices" are
      slices of the host's own cores (see module docstring);
    - an int (or numeric string) N: the first N local devices, forced
      regardless of backend — errors if fewer exist (a silent clamp
      would fake the distribution a dryrun is trying to prove).
    """
    import jax

    local = list(jax.local_devices())
    if spec is None or spec == "auto":
        if jax.default_backend() == "cpu":
            return local[:1]
        return local
    n = int(spec)
    if n < 1:
        raise ValueError(f"--devices must be >= 1, got {n}")
    if n > len(local):
        raise ValueError(
            f"--devices {n} requested but only {len(local)} local "
            f"device(s) exist (JAX_PLATFORMS="
            f"{jax.default_backend()}; use "
            f"--xla_force_host_platform_device_count for CPU dryruns)"
        )
    return local[:n]


def replicate_state(state, devices: Sequence):
    """One committed copy of ``state`` per device (pytree device_put).

    Replica 0 of a state already resident on ``devices[0]`` is a no-copy
    alias — fine here: replicas are read-only under the forward path.
    """
    import jax

    return tuple(jax.device_put(state, d) for d in devices)


class DeviceSet:
    """The device inventory + dispatch accounting for one forward path.

    Thread-safe: serving runs one dispatch worker PER device plus a
    router; every mutation here is under one lock. The accounting feeds
    ``stats()`` (the server's /stats payload) and ``flush_gauges``
    (telemetry counters/gauges that ``observe.gauges.device_gauges``
    rolls up into run_summary).
    """

    def __init__(self, devices: Sequence | None = None, *, window: int = 16):
        if devices is None:
            devices = resolve_devices("auto")
        devices = list(devices)
        if not devices:
            raise ValueError("a DeviceSet needs at least one device")
        self.devices = tuple(devices)
        self.window = max(1, int(window))
        self._lock = racecheck.make_lock("serve.devices")
        n = len(self.devices)
        self._inflight = [0] * n     # routed or dispatched, not yet fetched
        self._dispatches = [0] * n
        self._busy_s = [0.0] * n     # dispatch->fetch wall per device
        self._max_depth = [0] * n
        self._rr = 0
        self._t0 = time.perf_counter()

    def __len__(self) -> int:
        return len(self.devices)

    # ---- dispatcher ----

    def pick(self) -> int:
        """Least-loaded device index (in-flight count; round-robin tie
        break so idle sets still rotate instead of pinning device 0)."""
        with self._lock:
            n = len(self.devices)
            best, best_load = None, None
            for off in range(n):
                i = (self._rr + off) % n
                load = self._inflight[i]
                if best_load is None or load < best_load:
                    best, best_load = i, load
            self._rr = (best + 1) % n
            return best

    def note_enqueue(self, i: int) -> None:
        with self._lock:
            self._inflight[i] += 1
            self._max_depth[i] = max(self._max_depth[i], self._inflight[i])

    def note_complete(self, i: int, busy_s: float, ok: bool = True) -> None:
        """Retire one routed flush. The in-flight count always drops;
        dispatch/busy accounting only accrues for flushes that actually
        ran (``ok``) — a device whose flushes all FAILED must read as
        idle in the distribution gauges, not as serving work."""
        with self._lock:
            self._inflight[i] = max(0, self._inflight[i] - 1)
            if ok:
                self._dispatches[i] += 1
                self._busy_s[i] += float(busy_s)

    def inflight(self, i: int) -> int:
        with self._lock:
            return self._inflight[i]

    def inflight_depths(self) -> list[int]:
        """Every device's in-flight depth in one lock acquisition — the
        live-observability view (/stats rolling + the /metrics scrape):
        routed-but-unfetched flushes per device, right now."""
        with self._lock:
            return list(self._inflight)

    # ---- accounting ----

    def stats(self) -> list[dict]:
        """One record per device (the /stats + run-summary payload):
        the dispatch accounting, and the device memory the backend
        reports (``hbm_*``; absent on the CPU backend)."""
        from cgnn_tpu.observe.gauges import hbm_gauges

        wall = max(time.perf_counter() - self._t0, 1e-9)
        hbm = hbm_gauges(self.devices)
        with self._lock:
            return [
                {
                    "device_id": i,
                    "device": str(d),
                    "platform": d.platform,
                    "kind": d.device_kind,
                    "dispatches": self._dispatches[i],
                    "busy_s": round(self._busy_s[i], 4),
                    "occupancy": min(1.0, self._busy_s[i] / wall),
                    "inflight": self._inflight[i],
                    "max_window_depth": self._max_depth[i],
                    **{f"hbm_{k}": hbm[i][k]
                       for k in ("bytes_in_use", "peak_bytes_in_use",
                                 "bytes_limit") if k in hbm[i]},
                }
                for i, d in enumerate(self.devices)
            ]

    def flush_gauges(self, telemetry) -> None:
        """Write per-device gauges into ``telemetry`` under the
        ``device{i}_*`` names ``observe.gauges.device_gauges`` rolls up
        (gauges overwrite, so repeated flushes stay idempotent)."""
        if telemetry is None:
            return
        for rec in self.stats():
            i = rec["device_id"]
            telemetry.set_gauge(f"device{i}_dispatches",
                                float(rec["dispatches"]))
            telemetry.set_gauge(f"device{i}_occupancy", rec["occupancy"])
            telemetry.set_gauge(f"device{i}_window_depth",
                                float(rec["max_window_depth"]))
        telemetry.set_gauge("device_count", float(len(self.devices)))
