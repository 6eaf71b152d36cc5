#!/usr/bin/env python
"""MAE parity harness: JAX framework vs the in-tree torch CGCNN oracle.

BASELINE.md's acceptance row has two halves: throughput (benchmark/run.py) and
"formation-energy MAE <= GPU baseline". The reference tree is unavailable
(SURVEY.md §0), so the GPU baseline is *measured* here by training the
in-tree torch oracle (tests/oracle/torch_cgcnn.py — the lineage
architecture, SURVEY.md §4.3) and the JAX model on the SAME dataset with
the SAME hyperparameters, from independent inits, and comparing test MAE.

Two datasets: ``--dataset tiny`` (8-atom fully-coordinated cells, the
round-2 harness) and ``--dataset mp`` (the MP-like ~30-atom lognormal
distribution INCLUDING under-coordinated structures — the oracle masks
its dense [N, M] padding slots with the exact semantics of the
framework's packing, pinned by tests/test_parity.py
TestMaskedOracleParity at 1e-8).

Prints one JSON line:
  {"torch_oracle_test_mae", "jax_test_mae", "ratio", ...}
Exit code 1 if the JAX model is more than --tolerance worse than the
oracle.

Usage: python scripts/mae_parity.py [--n 1024] [--epochs 50] [--device cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402


def torch_train_eval(graphs, split, *, epochs, batch_size, lr, seed,
                     max_num_nbr):
    """Train the oracle on (train, val, test) index lists -> test MAE."""
    import numpy as np
    import torch

    from tests.oracle.torch_cgcnn import TorchCGCNN

    train_g, val_g, test_g = split
    m = max_num_nbr
    gdim = graphs[0].edge_fea.shape[1]

    from cgnn_tpu.data.graph import dense_neighbor_views

    def dense_views(g):
        """dense_neighbor_views, cached on the graph: under-coordinated
        nodes (real MP ~30-atom cells) have < M neighbors; their padding
        slots carry mask 0 and are excluded from BN statistics and the
        message sum by the masked oracle — the EXACT semantics of the
        framework's packing."""
        cached = getattr(g, "_dense_views", None)
        if cached is None:
            cached = g._dense_views = dense_neighbor_views(g, m)
        return cached

    def collate(batch_graphs):
        """Lineage-style collate: concat nodes, offset dense [N, M] idx."""
        atom, nbr, idx, masks, ranges, ys = [], [], [], [], [], []
        off = 0
        for g in batch_graphs:
            n = g.num_nodes
            dn, di, dm = dense_views(g)
            atom.append(np.asarray(g.atom_fea, np.float32))
            nbr.append(dn)
            idx.append(di + off)
            masks.append(dm)
            ranges.append(torch.arange(off, off + n))
            ys.append(float(g.target[0]))
            off += n
        return (
            torch.from_numpy(np.concatenate(atom)),
            torch.from_numpy(np.concatenate(nbr)),
            torch.from_numpy(np.concatenate(idx)).long(),
            torch.from_numpy(np.concatenate(masks)),
            ranges,
            torch.tensor(ys, dtype=torch.float32),
        )

    torch.manual_seed(seed)
    model = TorchCGCNN(
        orig_atom_fea_len=graphs[0].atom_fea.shape[1],
        nbr_fea_len=graphs[0].edge_fea.shape[1],
        atom_fea_len=64,
        n_conv=3,
        h_fea_len=128,
        n_h=1,
    )
    opt = torch.optim.Adam(model.parameters(), lr=lr)
    t_mean = float(np.mean([g.target[0] for g in train_g]))
    t_std = float(np.std([g.target[0] for g in train_g]) + 1e-8)

    shuffle_rng = np.random.default_rng(seed)

    def run(split_graphs, train=False):
        model.train(train)
        # one generator across epochs: fresh shuffle each training epoch,
        # matching the JAX loop's reshuffling (train/loop.py)
        order = (shuffle_rng.permutation(len(split_graphs)) if train
                 else np.arange(len(split_graphs)))
        ae_sum = count = 0.0
        for i in range(0, len(order), batch_size):
            bg = [split_graphs[j] for j in order[i:i + batch_size]]
            atom, nbr, idx, mask, ranges, y = collate(bg)
            out = model(atom, nbr, idx, ranges, nbr_mask=mask)[:, 0]
            if train:
                loss = torch.nn.functional.mse_loss(out, (y - t_mean) / t_std)
                opt.zero_grad()
                loss.backward()
                opt.step()
            with torch.no_grad():
                ae_sum += float((out * t_std + t_mean - y).abs().sum())
            count += len(bg)
        return ae_sum / max(count, 1)

    best_val, best_state = float("inf"), None
    for _epoch in range(epochs):
        run(train_g, train=True)
        with torch.no_grad():
            val_mae = run(val_g)
        if val_mae < best_val:
            best_val = val_mae
            best_state = {k: v.clone() for k, v in model.state_dict().items()}
    model.load_state_dict(best_state)
    with torch.no_grad():
        return run(test_g), best_val


def jax_train_eval(split, *, epochs, batch_size, lr, seed,
                   matched_init=False):
    import numpy as np

    import jax

    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.loop import evaluate, fit

    train_g, val_g, test_g = split
    model = CrystalGraphConvNet(atom_fea_len=64, n_conv=3, h_fea_len=128, n_h=1)
    tx = make_optimizer(optim="adam", lr=lr, lr_milestones=[10**9])
    normalizer = Normalizer.fit(np.stack([g.target for g in train_g]))
    node_cap, edge_cap = capacities_for(train_g, batch_size)
    example = next(batch_iterator(train_g, batch_size, node_cap, edge_cap))
    state = create_train_state(
        model, example, tx, normalizer, rng=jax.random.key(seed)
    )
    if matched_init:
        # draw the init from the SAME distribution the lineage trains
        # from (torch Linear defaults: kaiming_uniform(a=sqrt(5)) +
        # fan-in uniform bias) by transplanting a fresh UNTRAINED oracle
        # — an independent draw (different torch seed than the oracle
        # run), isolating framework-vs-framework optimization from the
        # flax-lecun_normal vs torch-kaiming init lottery
        import torch

        from tests.oracle.torch_cgcnn import TorchCGCNN, variables_from_torch

        torch.manual_seed(seed + 7919)
        fresh = TorchCGCNN(
            orig_atom_fea_len=train_g[0].atom_fea.shape[1],
            nbr_fea_len=train_g[0].edge_fea.shape[1],
            atom_fea_len=64, n_conv=3, h_fea_len=128, n_h=1,
        )
        variables = variables_from_torch(
            fresh, {"params": state.params, "batch_stats": state.batch_stats}
        )
        state = state.replace(
            params=jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.float32), variables["params"]
            ),
            batch_stats=jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.float32),
                variables["batch_stats"],
            ),
        )
    best = {"params": state.params, "batch_stats": state.batch_stats,
            "val": float("inf")}

    def on_epoch_end(s, _epoch, val_m, is_best):
        if is_best:
            # true host SNAPSHOTS, not just fetches: on CPU, device_get
            # returns views ALIASING the device buffers, which the
            # donated train step mutates in later epochs (the PR-2
            # checkpoint-corruption incident) — without the np.array
            # copy, "best" params silently drift to the last epoch's
            best.update(
                params=jax.tree_util.tree_map(
                    np.array, jax.device_get(s.params)),
                batch_stats=jax.tree_util.tree_map(
                    np.array, jax.device_get(s.batch_stats)),
                val=val_m["mae"])

    state, result = fit(
        state, train_g, val_g, epochs=epochs, batch_size=batch_size,
        node_cap=node_cap, edge_cap=edge_cap, seed=seed, print_freq=0,
        on_epoch_end=on_epoch_end, log_fn=lambda *a, **k: None,
    )
    state = state.replace(params=best["params"], batch_stats=best["batch_stats"])
    test_m = evaluate(state, test_g, batch_size, node_cap, edge_cap)
    return float(test_m["mae"]), float(result["best"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--n", type=int, default=1024)
    p.add_argument("--epochs", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--repeats", type=int, default=1,
                   help="average over this many seeds (seed..seed+R-1); a "
                        "~100-structure test set has ~10%% MAE standard "
                        "error, so single-seed ratios are noise-dominated")
    p.add_argument("--device", choices=["auto", "cpu"], default="auto")
    p.add_argument("--tolerance", type=float, default=0.05,
                   help="max allowed (jax_mae / torch_mae - 1)")
    p.add_argument("--matched-init", action="store_true",
                   help="initialize the JAX model from a fresh UNTRAINED "
                        "torch oracle (independent draw) so both "
                        "frameworks start from the lineage's init "
                        "distribution")
    p.add_argument("--dataset", choices=["tiny", "mp"], default="tiny",
                   help="'mp': the realistic MP-like lognormal ~30-atom "
                        "distribution (radius 6), UNDER-COORDINATED "
                        "structures included — the oracle masks its dense "
                        "padding slots so the comparison is exact "
                        "(VERDICT r2 #4). 'tiny': 8-atom fully-coordinated "
                        "cells (radius 8), the round-2 harness")
    args = p.parse_args(argv)
    if args.device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from cgnn_tpu.data.dataset import (
        FeaturizeConfig,
        load_synthetic,
        load_synthetic_mp,
        train_val_test_split,
    )

    if args.dataset == "mp":
        # radius 4.5: ~9% of atoms under-coordinated (radius 6 saturates
        # max_num_nbr on this distribution and would mask nothing)
        cfg = FeaturizeConfig(radius=4.5, max_num_nbr=12)
        full = load_synthetic_mp(args.n, cfg, seed=11)
    else:
        cfg = FeaturizeConfig(radius=8.0, max_num_nbr=12)
        graphs = load_synthetic(args.n, cfg, seed=11, max_atoms=8)
        # round-2 precondition: dense [N, M] layout == flat COO edge set
        # (the masked oracle no longer needs it, kept for comparability)
        full = [
            g for g in graphs
            if np.all(np.bincount(g.centers, minlength=g.num_nodes)
                      == cfg.max_num_nbr)
        ]
        if len(full) < args.n * 0.9:
            print(f"only {len(full)}/{args.n} fully-coordinated structures",
                  file=sys.stderr)
            return 1
    runs = []
    t_torch = t_jax = 0.0
    for seed in range(args.seed, args.seed + args.repeats):
        split = train_val_test_split(full, 0.8, 0.1, seed=seed)
        t0 = time.perf_counter()
        torch_mae, torch_val = torch_train_eval(
            full, split, epochs=args.epochs, batch_size=args.batch_size,
            lr=args.lr, seed=seed, max_num_nbr=cfg.max_num_nbr,
        )
        t_torch += time.perf_counter() - t0
        t0 = time.perf_counter()
        jax_mae, jax_val = jax_train_eval(
            split, epochs=args.epochs, batch_size=args.batch_size,
            lr=args.lr, seed=seed, matched_init=args.matched_init,
        )
        t_jax += time.perf_counter() - t0
        runs.append({"seed": seed,
                     "torch_test_mae": round(torch_mae, 5),
                     "jax_test_mae": round(jax_mae, 5),
                     "torch_val_mae": round(torch_val, 5),
                     "jax_val_mae": round(jax_val, 5)})

    mean_torch = float(np.mean([r["torch_test_mae"] for r in runs]))
    mean_jax = float(np.mean([r["jax_test_mae"] for r in runs]))
    ratio = mean_jax / mean_torch
    # per-seed ratio band: the pooled ratio alone invites over-reading a
    # lucky 2-3-seed draw as superiority (VERDICT r4 weak #3) — report
    # mean +/- sample std so the claim strength is visible in the artifact
    per_seed = [r["jax_test_mae"] / r["torch_test_mae"] for r in runs]
    print(json.dumps(jsonfinite({
        "metric": "formation_energy_mae_parity",
        "dataset": args.dataset,
        "matched_init": bool(args.matched_init),
        "torch_oracle_test_mae": round(mean_torch, 5),
        "jax_test_mae": round(mean_jax, 5),
        "ratio": round(ratio, 4),
        "per_seed_ratios": [round(r, 4) for r in per_seed],
        "ratio_mean": round(float(np.mean(per_seed)), 4),
        "ratio_std": round(
            float(np.std(per_seed, ddof=1)) if len(per_seed) > 1 else 0.0,
            4),
        "repeats": args.repeats,
        "runs": runs,
        "n_structures": len(full),
        "epochs": args.epochs,
        "torch_train_s": round(t_torch, 1),
        "jax_train_s": round(t_jax, 1),
    })))
    return 0 if ratio <= 1.0 + args.tolerance else 1


if __name__ == "__main__":
    sys.exit(main())
