"""Find the knee of an open-loop cell once, on the chip.

    python chipbench/sweep.py --workload sage-products.serve-ego \\
        --seed 1 --seconds 10

One process builds the cell's driver once and offers windows at rising
rates: doubling from ``--start`` while the system sustains them, then
steps of 1.25 from the last sustained rate.  A rate is sustained when no
request is rejected, dropped or failed, completions keep up with
arrivals to the end of the window (the last reply comes within
``--backlog-s`` seconds of its end), and the median stays under twice
the uncontended median (one client, one request at a time, taken first).
The median, not p99: with 1-100 seeds a request the uncontended p99 is
already five times the uncontended median, so a limit on p99 of a 10 s
window is crossed by chance at any rate (PERF.md section 6).
The knee is the highest sustained rate; the cell's traffic file gets 0.8
of it, as a number, by hand.  Prints one JSON line per window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--start", type=float, default=25.0)
    ap.add_argument("--backlog-s", type=float, default=0.5)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)

    import jax

    from chipbench import run as harness
    from chipbench.common import Env
    from chipbench.drivers.serve_open_loop import draw_requests
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    cell, config, traffic, _ = harness.load_cell(args.workload)
    driver = harness.build_driver(Env(
        config, traffic, args.seed, jax.devices()[: cell["chips"]], False,
        harness.log))
    try:
        rng = np.random.default_rng(args.seed)
        lat = []
        for seeds in draw_requests(traffic, driver.d.shapes.num_nodes, 100,
                                   rng):
            t0 = time.perf_counter()
            driver.clients[0].subgraph(seeds)
            lat.append(time.perf_counter() - t0)
        base_ms = float(np.median(lat)) * 1e3
        print(json.dumps({"uncontended_median_ms": base_ms,
                          "uncontended_p99_ms":
                          float(np.percentile(lat, 99)) * 1e3}), flush=True)

        def sustained(rate: float) -> bool:
            traffic["rate_rps"] = rate
            win = driver.window(args.seconds)
            c = win.counters
            backlog_s = c["last_done_s"] - args.seconds
            ok = (win.failed == 0 and backlog_s < args.backlog_s
                  and win.metrics["latency_p50_ms"] < 2 * base_ms)
            print(json.dumps({
                "rate_rps": rate, "sustained": bool(ok),
                "attempted": win.attempted, "failed": win.failed,
                **{k: round(v, 2) for k, v in win.metrics.items()},
                "done_after_window_s": backlog_s,
                "gen_late_ms_p99": c["gen_late_ms_p99"],
                "outcomes": c["outcomes"]}), flush=True)
            time.sleep(1.5)         # let the queue drain between windows
            return ok

        rate, best = args.start, None
        while sustained(rate):
            best, rate = rate, rate * 2
        if best is None:
            rate, best = args.start, 0.0
            while rate > 1 and not sustained(rate / 2):
                rate /= 2
            best = rate / 2 if rate > 1 else 0.0
        hi = best * 2
        rate = best * 1.25
        while rate < hi and sustained(rate):
            best, rate = rate, rate * 1.25
        print(json.dumps({"knee_rps": best, "rate_at_0.8": 0.8 * best}),
              flush=True)
    finally:
        driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
