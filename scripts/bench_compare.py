#!/usr/bin/env python3
"""Compare a bench history (plus an optional fresh run) and emit a
markdown trend report with a regress/improve verdict.  No record is
committed today, so the default glob is empty: that prints "no history"
and exits 0.

    python scripts/bench_compare.py                      # history only
    GLT_BENCH_OUT=fresh.json python bench.py
    python scripts/bench_compare.py --fresh fresh.json   # judge the run
    python scripts/bench_compare.py --out report.md --json report.json

Advisory by default (always exits 0 so the CI ``bench-compare`` job
never fails the build); ``--strict`` exits 1 on regressions for local
pre-merge checks.  Logic: :mod:`glt_tpu.obs.regress` (direction-aware,
noise-tolerant thresholds, stuck-metric detection).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from glt_tpu.obs.regress import (  # noqa: E402  (stdlib-only import)
    compare,
    load_bench_metrics,
    markdown_report,
)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--history", default="BENCH_r*.json",
                        help="glob of committed bench snapshots "
                             "(default: BENCH_r*.json, repo root)")
    parser.add_argument("--fresh", default=None,
                        help="a fresh bench.py result to judge against "
                             "the history (wrapper, raw JSON line, or "
                             "GLT_BENCH_OUT file)")
    parser.add_argument("--out", default=None,
                        help="write the markdown report here")
    parser.add_argument("--json", dest="json_out", default=None,
                        help="write the machine-readable report here")
    parser.add_argument("--rel-tol", type=float, default=0.05)
    parser.add_argument("--noise-k", type=float, default=3.0)
    parser.add_argument("--strict", action="store_true",
                        help="exit 1 on regressions (default: advisory, "
                             "always 0)")
    args = parser.parse_args(argv)

    runs = []
    for path in sorted(glob.glob(args.history)):
        metrics = load_bench_metrics(path)
        if metrics is None:
            print(f"WARNING: {path}: no bench JSON found, skipped",
                  file=sys.stderr)
            continue
        label = os.path.splitext(os.path.basename(path))[0]
        label = label.replace("BENCH_", "")
        runs.append((label, metrics))
    if not runs:
        print(f"no history (glob {args.history!r} matched no bench "
              f"snapshot): nothing to compare")
        return 0
    if args.fresh:
        metrics = load_bench_metrics(args.fresh)
        if metrics is None:
            print(f"ERROR: {args.fresh}: no bench JSON found",
                  file=sys.stderr)
            return 2
        runs.append(("fresh", metrics))
    if len(runs) < 2:
        print(f"ERROR: need >= 2 runs to compare, found {len(runs)} "
              f"(history glob {args.history!r})", file=sys.stderr)
        return 2

    report = compare(runs, rel_tol=args.rel_tol, noise_k=args.noise_k)
    md = markdown_report(report)
    print(md)
    # Atomic publishes (GLT011): CI uploads these as artifacts while the
    # job may still be appending — a torn report reads as a clean pass.
    if args.out:
        tmp = f"{args.out}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(md + "\n")
        os.replace(tmp, args.out)
    if args.json_out:
        tmp = f"{args.json_out}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2)
        os.replace(tmp, args.json_out)
    if args.strict and report["regressions"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
