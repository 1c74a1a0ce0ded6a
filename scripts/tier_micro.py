#!/usr/bin/env python3
"""The host's half of a tiered feature gather, piece by piece, on the chip's
own machine.

    chiprun --timeout 900 -- python scripts/tier_micro.py

The go / no-go of the two-tier cell (``sage-papers100m-tiered-chip1``,
PERF.md §6, PR 32, has its table): can this host hold the cold tail, and
how long does one batch's host stage take beside about 80 ms of device
work?  At the cell's own sizes (a 7 GB host array of 512 B rows; 60 k /
100 k / 140 k random rows of it a batch) it times

* the host gather ``np.take(cold, ids, axis=0, out=buf, mode="clip")``
  (``mode="raise"`` buffers ``out``: a second copy), on one thread and
  split over a thread pool (numpy releases the GIL inside ``take``);
* the padded buffer's fill as the gather did it before (``np.zeros`` of
  the next power of two, then a copy) beside an uninitialised buffer of a
  calibrated width written once;
* ``jax.device_put`` of the buffer, ended by ``block_until_ready``;
* a 1.5 MB device-to-host fetch (a node list of 344 k to 400 k int32
  ids) and a 0.5 MB one (a plan's cold row ids alone).

Nothing is asserted and no benchmark cell runs it.  Host times are
``perf_counter`` medians over ``--reps`` calls.  Prints a table and writes
``chiprun_out/tier_micro.json``; refuses to time anything but a TPU's
host unless ``--any-platform`` (then sizes shrink: a CPU rehearsal).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def meminfo() -> dict:
    out = {}
    with open("/proc/meminfo") as fh:
        for line in fh:
            key, _, rest = line.partition(":")
            if key in ("MemTotal", "MemFree", "MemAvailable"):
                out[key] = int(rest.split()[0]) * 1024
    return out


def median_ms(fn, reps: int) -> float:
    fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def pooled_take(pool, threads: int, cold, ids, out) -> None:
    """``np.take`` split into ``threads`` contiguous runs of ``ids``."""
    n = ids.shape[0]
    step = -(-n // threads)
    futs = [pool.submit(np.take, cold, ids[lo: lo + step], 0,
                        out[lo: lo + step], "clip")
            for lo in range(0, n, step)]
    for f in futs:
        f.result()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--cold-gb", type=float, default=7.0)
    ap.add_argument("--any-platform", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.any_platform:
        print(f"tier_micro times a TPU's host; found {dev.platform!r}",
              file=sys.stderr)
        return 1
    dim = 128
    rows = int(args.cold_gb * 1e9) // (dim * 4)
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "cores": os.cpu_count(),
              "affinity": len(os.sched_getaffinity(0)),
              "meminfo_before": meminfo(), "cold_rows": rows, "table": []}
    t0 = time.perf_counter()
    # Written once so the pages exist: a lazily mapped np.empty would
    # time page faults, not DRAM reads.
    cold = np.empty((rows, dim), np.float32)
    block = 1 << 20
    filler = np.random.default_rng(0).random((block, dim), np.float32)
    for lo in range(0, rows, block):
        cold[lo: lo + block] = filler[: min(block, rows - lo)]
    report["alloc_fill_s"] = time.perf_counter() - t0
    report["meminfo_after"] = meminfo()
    print(json.dumps({k: report[k] for k in
                      ("device", "cores", "affinity", "meminfo_before",
                       "meminfo_after", "alloc_fill_s")}), flush=True)

    rng = np.random.default_rng(1)
    pools = {t: ThreadPoolExecutor(t) for t in (2, 4, 8)}
    for n in (60_000, 100_000, 140_000):
        ids = rng.integers(0, rows, n).astype(np.int32)
        ids64 = ids.astype(np.int64)
        width = int(n * 1.08)                  # a calibrated width's room
        pow2 = 1 << (n - 1).bit_length()
        buf = np.empty((width, dim), np.float32)
        row = {"rows": n, "mb": n * dim * 4 / 1e6, "width": width,
               "pow2": pow2}
        row["take_1t_ms"] = median_ms(
            lambda: np.take(cold, ids, axis=0, out=buf[:n], mode="clip"), args.reps)
        row["take_1t_int64_fancy_ms"] = median_ms(
            lambda: cold[ids64], args.reps)
        srt = np.sort(ids)
        row["take_1t_sorted_ms"] = median_ms(
            lambda: np.take(cold, srt, axis=0, out=buf[:n], mode="clip"), args.reps)
        for t, pool in pools.items():
            row[f"take_{t}t_ms"] = median_ms(
                lambda: pooled_take(pool, t, cold, ids, buf[:n]), args.reps)

        def old_fill():
            pad = np.zeros((pow2, dim), np.float32)
            pad[:n] = buf[:n]
            return pad

        def new_fill():
            fresh = np.empty((width, dim), np.float32)
            np.take(cold, ids, axis=0, out=fresh[:n],
                    mode="clip")
            return fresh

        row["fill_zeros_pow2_copy_ms"] = median_ms(old_fill, args.reps)
        row["fresh_empty_take_1t_ms"] = median_ms(new_fill, args.reps)
        padded = old_fill()
        row["put_pow2_ms"] = median_ms(
            lambda: jax.block_until_ready(jax.device_put(padded, dev)),
            args.reps)
        row["put_width_ms"] = median_ms(
            lambda: jax.block_until_ready(jax.device_put(buf, dev)),
            args.reps)
        row["put_width_gb_s"] = width * dim * 4 / 1e6 / row["put_width_ms"]
        report["table"].append(row)
        print(json.dumps(row), flush=True)

    # Device-to-host: a node list, and a plan's cold ids alone.
    for name, count in (("d2h_1p5mb_ms", 393_216), ("d2h_0p5mb_ms", 131_072)):
        make = jax.jit(lambda k, count=count: jax.random.randint(
            k, (count,), 0, 1 << 30, jnp.int32))
        keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

        def fetch():
            return np.asarray(jax.block_until_ready(make(next(keys))))

        def ready_only():
            return jax.block_until_ready(make(next(keys)))

        report[name] = median_ms(fetch, args.reps)
        report[name.replace("_ms", "_program_alone_ms")] = median_ms(
            ready_only, args.reps)
    for pool in pools.values():
        pool.shutdown()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "tier_micro.json"), "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "table"}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
