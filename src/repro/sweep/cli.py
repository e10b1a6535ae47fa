"""``repro-sweep`` / ``python -m repro sweep`` — the sweep front door.

Usage::

    repro-sweep list
    repro-sweep run roofline-all-archs                 # resumable grid run
    repro-sweep run ci-tiny --limit 2                  # stop after 2 cells
    repro-sweep report serve-precision-ablation        # refresh tables only

``run`` executes every cell of a named preset that its JSONL store
(``results/sweep_<name>.jsonl``) doesn't already hold, then refreshes the
sweep's marker-delimited table block in EXPERIMENTS.md.  Interrupt it at any
point and re-run: completed cells are skipped by content hash.
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro-sweep", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("list", help="list the named sweep presets")
    for c in ("run", "report"):
        p = sub.add_parser(c)
        p.add_argument("preset")
        p.add_argument("--store-dir", default="results")
        p.add_argument("--experiments", default="EXPERIMENTS.md",
                       help="markdown file to refresh ('' disables)")
        if c == "run":
            p.add_argument("--limit", type=int, default=0,
                           help="execute at most N cells this invocation")
            p.add_argument("--timeout", type=float, default=1800.0,
                           help="per-cell subprocess timeout (seconds)")
            p.add_argument("--keep-failed", action="store_true",
                           help="do not re-run error/timeout cells")
            p.add_argument("--force", action="store_true",
                           help="re-run every cell, ignoring the store")
    args = ap.parse_args(argv)

    from repro.sweep.grid import PRESETS, get_preset

    if args.cmd == "list":
        for name in PRESETS:
            sweep = get_preset(name)
            print(f"{name:28s} {len(sweep.cells()):3d} cells "
                  f"({sweep.base.get('workload', 'mixed')})")
        return 0

    sweep = get_preset(args.preset)
    from repro.sweep.report import write_experiments
    from repro.sweep.runner import ResultsStore, SweepRunner

    store = ResultsStore.for_sweep(sweep, args.store_dir)
    if args.cmd == "run":
        runner = SweepRunner(sweep, store, timeout_s=args.timeout)
        summary = runner.run(max_cells=args.limit or None,
                             rerun_failed=not args.keep_failed,
                             force=args.force)
        print(f"\n{sweep.name}: {len(summary['ran'])} ran, "
              f"{len(summary['skipped'])} skipped, "
              f"{len(summary['failed'])} failed "
              f"of {summary['n_cells']} cells")
    if args.experiments:
        write_experiments(args.experiments, sweep, store)
        print(f"refreshed sweep:{sweep.name} tables in {args.experiments}")
    if args.cmd == "run" and summary["failed"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
