#!/usr/bin/env python
"""Bench-round regression diff: the latest ``BENCH_r*.json`` vs the
previous one, failing loudly on >20% regression of any named key.

A BENCH_r*.json trajectory is a repo's performance memory,
but nothing READ it — a silent 20% throughput slide would ship (PERF.md
§8 only caught the r3->r4 drift because a human went looking). This
script is the automated reader:

- flattens each round's ``parsed`` payload (nested sections join with
  '.'), selects the named higher-is-better keys (default: every
  throughput figure plus MFU and padding efficiency),
- prints the full old/new/delta table,
- emits a GitHub annotation line (``::error``/``::notice``) per
  regressed key, and exits 1 when any named key regressed beyond the
  threshold.

``--ledger BASELINE NEW`` additionally diffs two ``AUDIT_LEDGER.json``
payloads (ISSUE 8) through the same budget semantics with the sign
flipped: the gated keys (bytes, peak temp memory, bytes/FLOP) are
LOWER-is-better, and a program or key that disappears from the new
ledger is a regression — a budget that stopped being measured is how a
regression hides. The diff logic lives in
``cgnn_tpu.analysis.program_audit.diff_ledgers`` (stdlib-only), shared
with ``graftaudit.py --ci``.

CI wires it as a NON-BLOCKING annotation step (continue-on-error: the
bench numbers come from whatever machine ran the round, so a regression
is a flag for the next bench run on real hardware, not a merge gate).

Usage::

    python scripts/bench_regress.py                 # repo-root BENCH_r*
    python scripts/bench_regress.py --dir /path --threshold 0.2
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# higher-is-better keys checked against the threshold; everything else
# in the flattened payload is printed for context only
DEFAULT_KEYS = (
    "value",
    "atoms_per_sec",
    "mfu",
    "epoch_driver_structs_per_sec",
    "inference_structs_per_sec",
    "inference_e2e_structs_per_sec",
    "inference_e2e_multidev_structs_per_sec",
    # ISSUE 11: raw-wire ingest — the e2e rate through the in-program
    # neighbor search and the structural bytes-on-wire win (both
    # higher-is-better; dropping either from a bench round is how the
    # raw path would silently rot)
    "inference_e2e_raw_structs_per_sec",
    "ingest_wire_bytes_ratio",
    "ingest_raw_admit_share",
    "padding_eff_nodes",
    "padding_eff_edges",
    # ISSUE 19: priority serving — aggregate goodput under a mixed-class
    # load and the share of would-be padding that backfill converted to
    # answers (both higher-is-better; a bench round that stops measuring
    # them is how the front-door scheduler would silently rot)
    "serve_goodput_structs_per_sec",
    "serve_padding_fill_share",
    # ISSUE 20: one fleet cache — the partitioned fleet's effective hit
    # ratio on the Zipf keyset and its gain over the replicated
    # baseline (both higher-is-better; a bench round that stops
    # measuring them is how cache partitioning would silently rot).
    # The host-dependent fingerprint_blake2b_speedup is deliberately
    # NOT gated: it flips below 1 on SHA-NI hosts by design.
    "median_effective_hit_ratio.cachepart",
    "effective_hit_ratio_gain",
    "oc20.oc20_structs_per_sec",
    "tiny.tiny_structs_per_sec",
    "coo_layout.coo_structs_per_sec",
    "force_task.force_coo_structs_per_sec",
    "force_task.force_dense_structs_per_sec",
)

_ROUND = re.compile(r"BENCH_r(\d+)\.json$")


def find_rounds(bench_dir: str) -> list[tuple[int, str]]:
    """[(round number, path)] sorted ascending."""
    out = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r*.json")):
        m = _ROUND.search(os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def flatten(payload: dict, prefix: str = "") -> dict:
    """Nested dicts -> {'a.b': v} for every numeric leaf."""
    out = {}
    for k, v in payload.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, prefix=f"{key}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    return out


def load_parsed(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    return flatten(doc.get("parsed", doc))


def diff_rounds(old: dict, new: dict, keys, threshold: float) -> dict:
    """-> {"rows": [...], "regressions": [...]} (rows cover every named
    key present in either round; a key missing from the NEW round is a
    regression too — a bench that stopped measuring something is how a
    regression hides)."""
    rows, regressions = [], []
    for key in keys:
        o, n = old.get(key), new.get(key)
        if o is None and n is None:
            continue
        row = {"key": key, "old": o, "new": n}
        if o is None:
            row["note"] = "new key"
        elif n is None:
            row["note"] = "DROPPED from latest round"
            regressions.append(row)
        elif o > 0:
            ratio = n / o
            row["ratio"] = round(ratio, 4)
            if ratio < 1.0 - threshold:
                row["note"] = (
                    f"REGRESSION: {100 * (1 - ratio):.1f}% below previous"
                )
                regressions.append(row)
        rows.append(row)
    return {"rows": rows, "regressions": regressions}


def diff_ledger_files(baseline_path: str, new_path: str,
                      threshold: float, github: bool) -> int:
    """AUDIT_LEDGER budget diff (lower-is-better, dropped key =
    regression) -> number of hard regressions. Shares
    program_audit.diff_ledgers with graftaudit --ci."""
    from cgnn_tpu.analysis.program_audit import diff_ledgers, load_ledger

    diff = diff_ledgers(load_ledger(baseline_path), load_ledger(new_path),
                        threshold=threshold)
    print(f"bench_regress: audit ledger {os.path.basename(baseline_path)} "
          f"-> {os.path.basename(new_path)} (threshold {threshold:.0%}, "
          f"lower-is-better)")
    for row in diff["rows"]:
        o = "-" if row["old"] is None else f"{row['old']}"
        n = "-" if row["new"] is None else f"{row['new']}"
        ratio = f"{row['ratio']:.3f}x" if "ratio" in row else ""
        print(f"  {row['key']:<45} {o:>14} -> {n:>14}  {ratio:>8}  "
              f"{row.get('note', '')}")
    for row in diff["regressions"]:
        msg = (f"audit budget {row['key']}: {row.get('note', '')} "
               f"(baseline {row['old']}, new {row['new']})")
        if github:
            print(f"::error title=audit budget::{msg}")
        print(f"bench_regress: {msg}", file=sys.stderr)
    for row in diff["warnings"]:
        msg = (f"audit budget {row['key']} drifted under a different jax "
               f"than the baseline's: {row.get('note', '')}")
        if github:
            print(f"::warning title=audit budget skew::{msg}")
        print(f"bench_regress: {msg}")
    if not diff["regressions"]:
        print(f"bench_regress: audit budgets ok ({len(diff['rows'])} keys"
              f"{', version skew' if diff['version_skew'] else ''})")
    return len(diff["regressions"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    p.add_argument("--threshold", type=float, default=0.2,
                   help="fractional drop that counts as a regression")
    p.add_argument("--keys", default="",
                   help="comma-separated override of the named keys")
    p.add_argument("--github", action="store_true",
                   help="emit GitHub workflow annotation lines")
    p.add_argument("--ledger", nargs=2, metavar=("BASELINE", "NEW"),
                   help="also budget-diff two AUDIT_LEDGER.json files "
                        "(lower-is-better keys; dropped key = regression)")
    args = p.parse_args(argv)

    ledger_regressions = 0
    if args.ledger:
        ledger_regressions = diff_ledger_files(
            args.ledger[0], args.ledger[1], args.threshold, args.github)

    rounds = find_rounds(args.dir)
    if not rounds:
        print(f"bench_regress: no BENCH_r*.json under {args.dir} — "
              f"nothing to do")
        return 1 if ledger_regressions else 0
    if len(rounds) == 1:
        # exactly one round is NOT a silent pass: it is the baseline
        # every later round will be judged against — say so explicitly
        # (an empty-looking step that "succeeded" is how a broken glob
        # or a wiped artifact dir hides)
        n, path = rounds[0]
        named = len([k for k in DEFAULT_KEYS
                     if k in load_parsed(path)])
        msg = (f"single bench round r{n:02d} "
               f"({os.path.basename(path)}, {named} named keys present) "
               f"— baseline recorded, nothing to diff yet")
        if args.github:
            print(f"::notice title=bench baseline recorded::{msg}")
        print(f"bench_regress: {msg}")
        return 1 if ledger_regressions else 0
    (old_n, old_path), (new_n, new_path) = rounds[-2], rounds[-1]
    keys = ([k.strip() for k in args.keys.split(",") if k.strip()]
            or list(DEFAULT_KEYS))
    result = diff_rounds(load_parsed(old_path), load_parsed(new_path),
                         keys, args.threshold)

    print(f"bench_regress: r{old_n:02d} -> r{new_n:02d} "
          f"(threshold {args.threshold:.0%})")
    for row in result["rows"]:
        o = "-" if row["old"] is None else f"{row['old']:.4g}"
        n = "-" if row["new"] is None else f"{row['new']:.4g}"
        ratio = f"{row['ratio']:.3f}x" if "ratio" in row else ""
        note = row.get("note", "")
        print(f"  {row['key']:<45} {o:>12} -> {n:>12}  {ratio:>8}  {note}")

    if result["regressions"]:
        for row in result["regressions"]:
            msg = (f"BENCH r{old_n:02d}->r{new_n:02d} {row['key']}: "
                   f"{row.get('note', '')} "
                   f"(old {row['old']}, new {row['new']})")
            if args.github:
                print(f"::error title=bench regression::{msg}")
            print(f"bench_regress: {msg}", file=sys.stderr)
        return 1
    msg = (f"no >{args.threshold:.0%} regressions across "
           f"{len(result['rows'])} named keys (r{old_n:02d}->r{new_n:02d})")
    if args.github:
        print(f"::notice title=bench regression check::{msg}")
    print(f"bench_regress: {msg}")
    return 1 if ledger_regressions else 0


if __name__ == "__main__":
    sys.exit(main())
