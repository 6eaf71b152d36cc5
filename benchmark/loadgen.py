#!/usr/bin/env python3
"""Closed-loop HTTP load for the ``serve`` kind. Never imports JAX: the
parent holds the chip, and a child that touched it would fail or hang.

    python3 benchmark/loadgen.py --port P --bodies FILE --out FILE
        --clients N --warm-seconds W --seconds S

``FILE`` of bodies: one JSON request body a line; client k sends lines
k, k+N, k+2N, ... each once, waiting for every reply before its next send (a
closed loop: the callers are scripts that wait for each answer). It first
sends for ``W`` seconds (warm-up, not recorded), then for ``S`` seconds, then
lets the requests in flight finish. Writes one JSON object: per request its
line number, status, latency in ms and prediction; and the window's start and
the last completion on this process's clock.
"""

from __future__ import annotations

import argparse
import http.client
import json
import threading
import time


def client(k, n, port, bodies, state, out):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    headers = {"Content-Type": "application/json"}
    i = k
    while i < len(bodies):
        phase = state["phase"]
        if phase == "stop":
            break
        t0 = time.perf_counter()
        status, pred = 0, None
        try:
            conn.request("POST", "/predict", body=bodies[i], headers=headers)
            resp = conn.getresponse()
            data = resp.read()
            status = resp.status
            if status == 200:
                pred = json.loads(data)["prediction"]
        except (OSError, http.client.HTTPException, ValueError):
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        t1 = time.perf_counter()
        if phase == "measure":
            out.append((i, status, (t1 - t0) * 1e3, pred, t1))
        i += n
    else:
        state["exhausted"] = True
    conn.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--bodies", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--clients", type=int, required=True)
    p.add_argument("--warm-seconds", type=float, default=1.0)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    with open(args.bodies, "rb") as f:
        bodies = f.read().splitlines()
    state = {"phase": "warm", "exhausted": False}
    outs = [[] for _ in range(args.clients)]
    threads = [threading.Thread(target=client, daemon=True, args=(
        k, args.clients, args.port, bodies, state, outs[k]))
        for k in range(args.clients)]
    for t in threads:
        t.start()
    time.sleep(args.warm_seconds)
    t_start = time.perf_counter()
    state["phase"] = "measure"
    time.sleep(args.seconds)
    state["phase"] = "stop"
    for t in threads:
        t.join(timeout=60)
    alive = sum(t.is_alive() for t in threads)
    rows = [r for o in outs for r in o]
    result = {
        "t_start": t_start,
        "t_last": max((r[4] for r in rows), default=t_start),
        "exhausted": state["exhausted"], "stuck_clients": alive,
        "requests": [[r[0], r[1], r[2], r[3]] for r in rows],
    }
    with open(args.out, "w") as f:
        json.dump(result, f)
    return 0 if not alive else 1


if __name__ == "__main__":
    raise SystemExit(main())
