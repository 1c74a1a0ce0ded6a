"""What the metrics registry costs a cell when it is on.

    python scripts/registry_cost.py --workload <cell> --seed <n> \\
        [--seconds 20] [--registry 0|1]

One untraced window of a ``chipbench`` cell, as ``chipbench/run.py
--trace 0`` runs it, with the registry on or off and no profiler
session: ``--trace 1`` turns both on, so its ``seeds_per_s`` cannot tell
the registry's cost (``obs.metrics.defer``'s copy and poll, the timers
and counters of the host loops) from the profiler's.  Prints one JSON
line: ``seeds_per_s``, the window's steps, the compilations in it and,
with the registry on, what the ``glt.sample.*`` counters read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--registry", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()

    from chipbench import run
    from chipbench.common import Env

    cell, config, traffic, _ = run.load_cell(args.workload)
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax

    from glt_tpu.obs import compilewatch
    from glt_tpu.obs import metrics as registry

    # chipbench/run.py's own refusals: a cell is measured on its chips.
    devices = jax.devices()
    if not config.get("rehearsal") and devices[0].platform != "tpu":
        print(f"cell {args.workload} measures a TPU; JAX found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < int(cell["chips"]):
        print(f"cell {args.workload} needs {cell['chips']} chips; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[: int(cell["chips"])]
    compilewatch.install()
    # The drivers take ``env.trace`` to mean "the registry is on".
    env = Env(config=config, traffic=traffic, seed=args.seed,
              devices=devices, trace=bool(args.registry), log=run.log)
    if args.registry:
        registry.enable()
    driver = run.build_driver(env)
    try:
        before = registry.snapshot()
        compiles0 = compilewatch.total_compiles()
        win = driver.window(args.seconds)
        compiles = compilewatch.total_compiles() - compiles0
        after = registry.snapshot()
    finally:
        driver.close()
    moved = {k: v - before.get(k, 0.0) for k, v in after.items()
             if k.startswith("glt.sample.") and v != before.get(k, 0.0)}
    print(json.dumps({
        "workload": args.workload, "registry": bool(args.registry),
        "platform": devices[0].platform,
        "seeds_per_s": win.metrics.get("seeds_per_s"), "steps": win.steps,
        "failed": win.failed, "compiles_in_window": compiles,
        "sample_counters": moved}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
