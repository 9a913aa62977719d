"""`ig-tpu bench` — the perf-record store's verbs.

compare  newest record per series vs a noise-aware baseline from the
         last K same-config NON-degraded records; exit 1 on regression,
         exit 3 when a TPU claim has only degraded/CPU history (refused)
report   ledger history rendered through the column system
import   seed the ledger from driver-written BENCH_r*.json artifacts

The ledger path defaults to benchmarks/ledger/PERF.jsonl (override with
--ledger or $IG_PERF_LEDGER).
"""

from __future__ import annotations

import argparse
import glob
import json
import sys


def add_bench_parser(sub) -> None:
    bp = sub.add_parser("bench", help="perf ledger and regression gates "
                        "(compare / report / import)")
    bp.set_defaults(func=lambda a: (bp.print_help(), 0)[1])
    bsub = bp.add_subparsers(dest="bench_verb")

    def _ledger_arg(p):
        p.add_argument("--ledger", default=None,
                       help="perf ledger path (default "
                            "benchmarks/ledger/PERF.jsonl or $IG_PERF_LEDGER)")

    cp = bsub.add_parser("compare", help="gate the newest record per series "
                         "against its noise-aware ledger baseline")
    cp.add_argument("--config", action="append", default=[],
                    help="restrict to these configs (repeatable)")
    cp.add_argument("--k", type=int, default=5,
                    help="baseline pool size (last K non-degraded records)")
    cp.add_argument("--band", type=float, default=0.15,
                    help="relative noise band floor (0.15 = ±15%%)")
    cp.add_argument("--candidate-file", default="",
                    help="compare this record/BENCH JSON file instead of "
                         "the ledger's newest records")
    _ledger_arg(cp)
    cp.set_defaults(func=cmd_bench_compare)

    pp = bsub.add_parser("report", help="render ledger history (column "
                         "system)")
    pp.add_argument("--last", type=int, default=10)
    pp.add_argument("--config", action="append", default=[])
    pp.add_argument("-o", "--output", default="table",
                    choices=["table", "json"])
    _ledger_arg(pp)
    pp.set_defaults(func=cmd_bench_report)

    ip = bsub.add_parser("import", help="import driver BENCH_r*.json "
                         "artifacts into the ledger (idempotent)")
    ip.add_argument("paths", nargs="*", default=[],
                    help="files or globs (default: BENCH_r*.json)")
    _ledger_arg(ip)
    ip.set_defaults(func=cmd_bench_import)


def cmd_bench_compare(args) -> int:
    from ..perf import read_ledger
    from ..perf.compare import (
        RC_USAGE, compare_ledger, compare_record, render_compare,
    )
    from ..perf.ledger import bench_json_to_record
    from ..perf.schema import validate_record
    lr = read_ledger(args.ledger)
    for s in lr.skipped:
        print(f"warning: ledger {s}", file=sys.stderr)
    if args.candidate_file:
        try:
            with open(args.candidate_file, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"error: {args.candidate_file}: {e}", file=sys.stderr)
            return RC_USAGE
        if validate_record(doc):
            # not a PerfRecord — try the driver BENCH shape
            try:
                doc = bench_json_to_record(doc, source=args.candidate_file)
            except ValueError as e:
                print(f"error: {e}", file=sys.stderr)
                return RC_USAGE
        results = [compare_record(doc, lr.records, k=args.k, band=args.band)]
    else:
        if not lr.records:
            print("perf ledger is empty — nothing to compare",
                  file=sys.stderr)
            return 0
        results = compare_ledger(lr.records, configs=args.config or None,
                                 k=args.k, band=args.band)
    print(render_compare(results))
    return max((r.rc for r in results), default=0)


def cmd_bench_report(args) -> int:
    from ..perf import read_ledger, render_report
    lr = read_ledger(args.ledger)
    for s in lr.skipped:
        print(f"warning: ledger {s}", file=sys.stderr)
    if args.output == "json":
        recs = [r for r in lr.records
                if not args.config or r.get("config") in args.config]
        print(json.dumps(recs[-args.last:] if args.last else recs,
                         sort_keys=True))
        return 0
    print(render_report(lr.records, last=args.last,
                        configs=args.config or None))
    return 0


def cmd_bench_import(args) -> int:
    from ..perf import import_bench_files
    paths: list[str] = []
    for pat in (args.paths or ["BENCH_r*.json"]):
        hits = sorted(glob.glob(pat))
        paths.extend(hits if hits else [pat])
    n, skipped = import_bench_files(paths, args.ledger)
    for s in skipped:
        print(f"skipped {s}", file=sys.stderr)
    print(f"imported {n} record(s)")
    return 0


def main(argv: list[str] | None = None) -> int:
    """Standalone entry (python -m inspektor_gadget_tpu.cli.bench ...)."""
    ap = argparse.ArgumentParser(prog="ig-tpu bench")
    sub = ap.add_subparsers()
    add_bench_parser(sub)
    args = ap.parse_args(["bench", *(argv if argv is not None
                                     else sys.argv[1:])])
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
