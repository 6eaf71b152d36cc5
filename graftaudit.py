#!/usr/bin/env python
"""graftaudit: the compiled-program auditor (cgnn_tpu/analysis/program_audit).

graftcheck lints what the SOURCE says; graftaudit verifies what XLA
actually COMPILES. It lowers the repo's real entry programs — the train
step (plain / guard / telemetry-tapped / dense / data-parallel where
the backend allows), every (rung, staging form) predict program
in the warm shape ladder, and the compact expander — on abstract args,
then audits the artifacts: donation applied (GA-DONATION), no f64
anywhere (GA-F64), no host calls beyond the sanctioned telemetry tap
(GA-HOSTCALL), exact program identity across the ladder (GA-IDENT),
and a per-program FLOP/byte/temp-memory roofline ledger written to
AUDIT_LEDGER.json and gated as a budget: a key that disappears or a
lower-is-better key (bytes, peak temp memory, bytes/FLOP) regressing
>20% fails the run, mirroring scripts/bench_regress.py.

Usage::

    python graftaudit.py                  # audit + ledger, human output
    python graftaudit.py --ci             # concise; exit 1 on findings
    python graftaudit.py --no-compile     # StableHLO checks only (fast)
    python graftaudit.py --list-checks

Exit status: 0 clean, 1 findings or budget regressions, 2 usage
errors. The CI ``program-audit`` job runs ``--ci`` BLOCKING under
JAX_PLATFORMS=cpu (lowering needs no accelerator) and uploads the
fresh ledger as an artifact. The committed AUDIT_LEDGER.json is the
budget baseline: regenerate it deliberately (rerun this script in the
repo root and commit the diff), never to make CI green. Numeric
budget drift under a DIFFERENT jax version than the baseline's is
reported as a warning (XLA's cost model moves between releases);
structural drops fail regardless.
"""

from __future__ import annotations

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)

from cgnn_tpu.analysis.program_audit import (  # noqa: E402
    CHECKS,
    diff_ledgers,
    load_ledger,
    run_audit,
    write_ledger,
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--ci", action="store_true",
                   help="concise output + GitHub annotations; exit 1 on "
                        "any finding or budget regression")
    p.add_argument("--list-checks", action="store_true",
                   help="print the check catalog and exit")
    p.add_argument("--no-compile", action="store_true",
                   help="StableHLO-level checks only: skip XLA "
                        "compilation, the compiled-donation check, the "
                        "ledger, and the budget gate")
    p.add_argument("--ledger-out",
                   default=os.path.join(_ROOT, "AUDIT_LEDGER.json"),
                   help="where to write the fresh roofline ledger "
                        "(default: the repo baseline; deterministic "
                        "shapes make a clean re-run a no-op diff)")
    p.add_argument("--baseline",
                   default=os.path.join(_ROOT, "AUDIT_LEDGER.json"),
                   help="budget baseline to diff against (loaded BEFORE "
                        "--ledger-out is written)")
    p.add_argument("--threshold", type=float, default=0.2,
                   help="fractional increase of a lower-is-better key "
                        "that counts as a budget regression")
    args = p.parse_args(argv)

    if args.list_checks:
        for check in sorted(CHECKS):
            print(f"{check}\n    {CHECKS[check]}\n")
        return 0

    # deterministic device inventory for CPU audits: the mesh-sharded
    # predict programs (ISSUE 10) need >= 2 devices to lower, and the
    # committed ledger carries their GA-SHARD-budgeted rows — a
    # 1-device run would report them as DROPPED (a budget regression).
    # 8 virtual host devices matches CI's program-audit job and the
    # test suite's conftest; a user-provided XLA_FLAGS wins.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    baseline = None
    if not args.no_compile and os.path.exists(args.baseline):
        baseline = load_ledger(args.baseline)

    findings, ledger, programs = run_audit(compile=not args.no_compile)

    lowered = [p for p in programs if p.lowered is not None]
    skipped = {p.name: p.skip for p in programs if p.skip is not None}
    for name, reason in sorted(skipped.items()):
        print(f"graftaudit: SKIP {name}: {reason}")

    for f in findings:
        if args.ci:
            print(f"::error title={f.check}::{f.program}: {f.message}")
        print(f.format())

    rc = 1 if findings else 0
    if not args.no_compile:
        write_ledger(ledger, args.ledger_out)
        n_prog = len(ledger["programs"])
        print(f"graftaudit: ledger {args.ledger_out} "
              f"({n_prog} programs)")
        if baseline is not None:
            diff = diff_ledgers(baseline, ledger,
                                threshold=args.threshold)
            for row in diff["regressions"]:
                msg = (f"budget {row['key']}: {row.get('note', '')} "
                       f"(baseline {row['old']}, now {row['new']})")
                if args.ci:
                    print(f"::error title=audit budget::{msg}")
                print(f"graftaudit: {msg}", file=sys.stderr)
                rc = 1
            for row in diff["warnings"]:
                msg = (f"budget {row['key']} drifted under a different "
                       f"jax than the baseline's: {row.get('note', '')} "
                       f"(baseline {row['old']}, now {row['new']})")
                if args.ci:
                    print(f"::warning title=audit budget skew::{msg}")
                print(f"graftaudit: {msg}")
            if not diff["regressions"]:
                print(f"graftaudit: budgets ok "
                      f"({len(diff['rows'])} keys vs {args.baseline}"
                      f"{', version skew' if diff['version_skew'] else ''})")

    if rc:
        print(f"\ngraftaudit: {len(findings)} finding(s); see "
              f"INVARIANTS.md 'IR-level invariants' for the catalog",
              file=sys.stderr)
    else:
        print(f"graftaudit: clean ({len(lowered)} programs lowered, "
              f"{len(skipped)} backend skips, {len(CHECKS)} checks)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
