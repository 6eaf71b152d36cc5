#!/usr/bin/env python
"""Cross-host coordinated hot-reload probe (ISSUE 10; multihost smoke
leg 2, one process of N).

Run under the ``CGNN_TPU_COORDINATOR``/``_NUM_PROCESSES``/``_PROCESS_ID``
env triple on every process, all pointed at ONE shared checkpoint
directory (leg 1's training output). Each process:

1. restores the newest committed checkpoint into a ParamStore (the
   serving hot-swap holder),
2. lockstep-polls a ``CheckpointWatcher`` wired to
   ``dist.ReloadCoordinator`` — every ``poll_once`` on every process is
   one collective round: process 0 broadcasts the newest committed save
   it sees, non-zero processes wait until their own filesystem view
   shows that save's commit marker, and everyone swaps only after the
   shared barrier;
3. process 0 commits a perturbed save mid-loop (the "trainer published
   new weights" event);
4. prints ``RELOAD_RESULT version=<v> round=<k>`` — the smoke script
   asserts every process reports the SAME version at the SAME round
   (the version-consistent cross-host reload pin).

Exit non-zero if the swap never lands.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ckpt_dir = sys.argv[1]
    from cgnn_tpu.parallel import dist

    if not dist.initialize_from_env(log_fn=print):
        print("CGNN_TPU_COORDINATOR env triple required", file=sys.stderr)
        return 2
    import jax
    import numpy as np

    from cgnn_tpu.config import build_model
    from cgnn_tpu.data.dataset import load_synthetic
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.serve.reload import CheckpointWatcher, ParamStore
    from cgnn_tpu.serve.server import plan_from_state
    from cgnn_tpu.train import (
        CheckpointManager,
        Normalizer,
        create_train_state,
        make_optimizer,
    )

    pid = dist.process_index()
    mgr = CheckpointManager(ckpt_dir, log_fn=print)
    meta = mgr.read_meta("latest")
    cfg = plan_from_state(meta)
    model = build_model(cfg["model_cfg"], cfg["data_cfg"], cfg["task"])
    graphs = load_synthetic(16, cfg["data_cfg"].featurize_config(), seed=0)
    dense_m = cfg["model_cfg"].dense_m or None
    nc, ec = capacities_for(graphs, 8, dense_m=dense_m, snug=True)
    example = next(batch_iterator(graphs, 8, nc, ec, dense_m=dense_m,
                                  in_cap=0, snug=True))
    state = create_train_state(
        model, example, make_optimizer(),
        Normalizer.identity(cfg["model_cfg"].num_targets),
        rng=jax.random.key(0),
    )
    state = mgr.restore_for_inference(state, "latest")
    version = mgr.last_restored or "latest"
    store = ParamStore(state, version)
    watcher = CheckpointWatcher(
        mgr, store, state,
        coordinator=dist.ReloadCoordinator(mgr, log_fn=print),
        log_fn=print,
    )
    print(f"proc {pid}: serving params {store.version}", flush=True)

    swapped_round = -1
    for rnd in range(60):
        if pid == 0 and rnd == 3:
            # the "trainer published new weights" event, process-0-only
            def nudge(x):
                a = np.asarray(x)
                if np.issubdtype(a.dtype, np.floating):
                    return (a * 1.05 + 0.01).astype(a.dtype)
                return a

            new_state = state.replace(
                params=jax.tree_util.tree_map(nudge, state.params))
            mgr.save(new_state, dict(meta, epoch=-1))
            mgr.wait()
            print(f"proc 0: committed {mgr.newest_committed()}", flush=True)
        # LOCKSTEP poll: each round is one collective on every process
        if watcher.poll_once():
            swapped_round = rnd
            break
        time.sleep(0.05)
    dist.barrier("reload-probe-done")
    if swapped_round < 0:
        print(f"proc {pid}: hot reload never landed", file=sys.stderr)
        return 1
    print(f"RELOAD_RESULT version={store.version} round={swapped_round}",
          flush=True)
    mgr.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
