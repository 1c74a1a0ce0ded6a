#!/usr/bin/env bash
# Throughput benches: headline sampler (bench.py), feature gather, and
# epoch-time configs.  Run on the real TPU chip (no JAX_PLATFORMS
# override); each prints JSON lines.  One process at a time holds the
# chip: the three run in sequence, and bench.py / bench_epoch.py exit
# non-zero on failure, which stops the script here (set -e).
set -euo pipefail
cd "$(dirname "$0")/.."
python bench.py
python benchmarks/bench_feature.py
python benchmarks/bench_epoch.py "$@"
