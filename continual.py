#!/usr/bin/env python
"""Continual-learning trainer entrypoint (cgnn_tpu.continual; ISSUE 18).

Tails a label journal (the fleet router's ``--journal`` JSONL, or a
single replica's), fine-tunes from the newest committed checkpoint on
the labeled replay set, and commits versioned CANDIDATE saves into the
shared checkpoint directory on a doubly-gated cadence (at least
``--min-new-labels`` new joins AND ``--min-interval`` seconds apart).
Nothing here promotes: the fleet's canary gate (``fleet.py --canary``)
decides which candidates ever serve, and gated reload watchers hold
every replica until it does.

Run it BESIDE the serving fleet, against the same checkpoint dir:

    python fleet.py CKPT --journal /tmp/labels.jsonl --canary &
    python continual.py CKPT --journal /tmp/labels.jsonl
"""

from __future__ import annotations

import argparse
import sys
import threading

from cgnn_tpu.runtime import start as start_runtime


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("ckpt_dir",
                   help="shared checkpoint directory (must hold a "
                        "committed save with model meta — the "
                        "fine-tune starting point)")
    p.add_argument("--journal", required=True, metavar="PATH",
                   help="label journal JSONL to tail (the fleet "
                        "router's --journal file)")
    p.add_argument("--min-new-labels", type=int, default=64,
                   help="newly joined labels required per round")
    p.add_argument("--min-interval", type=float, default=5.0,
                   help="min seconds between committed candidates")
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--epochs-per-round", type=int, default=2,
                   help="fine-tune epochs over the replay set per round")
    p.add_argument("--lr", type=float, default=0.01,
                   help="fine-tune learning rate")
    p.add_argument("--max-replay", type=int, default=4096,
                   help="newest labeled records replayed per round")
    p.add_argument("--max-rounds", type=int, default=0,
                   help="exit after this many committed rounds "
                        "(0 = run until SIGTERM)")
    p.add_argument("--poll-interval", type=float, default=1.0,
                   help="journal poll cadence (seconds)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--device", choices=["auto", "cpu", "tpu"],
                   default="auto")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    refusal = start_runtime(args.device, None)
    if refusal:
        print(refusal, file=sys.stderr)
        return 2

    from cgnn_tpu.continual import ContinualTrainer
    from cgnn_tpu.resilience.preempt import PreemptionHandler

    trainer = ContinualTrainer(
        args.ckpt_dir,
        journal_path=args.journal,
        min_new_labels=args.min_new_labels,
        min_interval_s=args.min_interval,
        batch_size=args.batch_size,
        epochs_per_round=args.epochs_per_round,
        lr=args.lr,
        max_replay=args.max_replay,
        max_rounds=args.max_rounds,
        seed=args.seed,
    )
    # SIGTERM/SIGINT -> finish the in-flight round, then exit clean
    # (the same preempt plumbing train.py uses)
    stop = threading.Event()
    handler = PreemptionHandler(
        log_fn=print,
        action="finishing the in-flight round, then exiting",
    )
    handler.add_callback(stop.set)
    handler.install()
    print(f"continual: tailing {args.journal} -> {args.ckpt_dir} "
          f"(>= {args.min_new_labels} labels AND >= "
          f"{args.min_interval:g}s between commits)")
    try:
        trainer.run(poll_interval_s=args.poll_interval, stop=stop)
    finally:
        handler.uninstall()
        trainer.close()
    s = trainer.stats()
    print(f"continual: exiting — {s['rounds']} rounds, "
          f"{len(s['commits'])} commits "
          f"({', '.join(s['commits']) or 'none'}), "
          f"{s['labels_trained']} labels trained, "
          f"{s['divergence_rollbacks']} divergence rollbacks")
    return 0


if __name__ == "__main__":
    sys.exit(main())
