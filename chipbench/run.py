"""One run of one cell of the benchmark.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything a cell is made of is found by name: the ``workloads`` entry in
``BENCHMARK.json`` (or, for the ``tiny-*`` rehearsal cells, in
``chipbench/rehearsal.json``) names a configuration and a traffic mix;
``chipbench/traffic/<mix>.json`` names its driver,
``chipbench/drivers/<driver>.py``; each per-layer metric of the cell has
a reader, ``chipbench/layer_metrics/<metric>.json``, which names a
``chipbench/reducers/<reducer>.py``.  This file holds no table of its
own.  The last line of standard output is the result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()      # process start, as near as Python gets

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".trace")


def log(msg: str) -> None:
    print(f"[chipbench {time.perf_counter() - _T_START:7.2f}] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def find_cell(workload: str):
    """The cell, its configuration's entry and the metric lists, out of
    ``BENCHMARK.json`` first, then the rehearsal list."""
    for path in (os.path.join(ROOT, "BENCHMARK.json"),
                 os.path.join(HERE, "rehearsal.json")):
        if not os.path.exists(path):
            continue
        bench = load_json(path)
        for cell in bench["workloads"]:
            if cell["name"] == workload:
                cfg = next(c for c in bench["configs"]
                           if c["name"] == cell["config"])
                return cell, cfg, bench
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json or "
                     f"chipbench/rehearsal.json")


def load_cell(workload: str):
    """``(cell, config, traffic, bench)`` of a workload, by name."""
    cell, cfg_entry, bench = find_cell(workload)
    return (cell, load_json(os.path.join(ROOT, cfg_entry["file"])),
            load_json(os.path.join(HERE, "traffic",
                                   cell["traffic"] + ".json")), bench)


def build_driver(env):
    """The traffic mix's driver, set up and warm."""
    return importlib.import_module(
        f"chipbench.drivers.{env.traffic['driver']}").Driver(env)


def metrics_of(bench: dict, section: str, workload: str) -> list:
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def read_layer_metrics(entries, ctx) -> dict:
    """Each per-layer metric through its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        spec = load_json(os.path.join(HERE, "layer_metrics",
                                      m["name"] + ".json"))
        reader = importlib.import_module(
            f"chipbench.reducers.{spec['reducer']}")
        value = reader.read(ctx, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    cell, config, traffic, bench = load_cell(args.workload)
    try:
        from glt_tpu.utils import enable_compile_cache
    except ImportError as e:
        print(f"the system under test is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    import jax

    from chipbench.common import Env

    rehearsal = bool(config.get("rehearsal"))
    devices = jax.devices()
    if not rehearsal and devices[0].platform != "tpu":
        print(f"cell {args.workload} measures a TPU; JAX found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < int(cell["chips"]):
        print(f"cell {args.workload} needs {cell['chips']} chips; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[: int(cell["chips"])]
    on_chip = devices[0].platform == "tpu"
    log(f"{args.workload} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache_dir}")

    from glt_tpu.obs import compilewatch
    from glt_tpu.obs import metrics as registry

    compilewatch.install()
    env = Env(config=config, traffic=traffic, seed=args.seed,
              devices=devices, trace=bool(args.trace), log=log)
    driver = build_driver(env)
    try:
        seconds = float(args.seconds)
        tracing = bool(args.trace) and on_chip
        if args.trace:
            seconds = min(seconds, float(traffic["trace_seconds"]))
            registry.enable()
        if tracing:
            import shutil

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        before = registry.snapshot()
        compiles0 = compilewatch.total_compiles()
        setup_s = time.perf_counter() - _T_START
        log(f"window opens after {setup_s:.2f} s of set-up")
        with jax.profiler.TraceAnnotation("chipbench.window"):
            win = driver.window(seconds)
        compiles = compilewatch.total_compiles() - compiles0
        after = registry.snapshot()
        device = device_record(devices)     # before the check's programs
        if tracing:
            jax.profiler.stop_trace()
        log(f"window closed: {win.attempted} attempted, {win.failed} "
            f"failed, {win.metrics}, {win.counters}")
        try:
            detail = driver.check()
            correct = compiles == 0
            if compiles:
                log(f"NOT correct: {compiles} compilations in the window")
            log(f"check passed {detail}")
        except Exception:  # noqa: BLE001 — reported as correct: false
            traceback.print_exc()
            correct = False
    finally:
        driver.close()

    line = {"correct": bool(correct), "attempted": int(win.attempted),
            "failed": int(win.failed), "metrics": {},
            "device": device}
    if not args.trace:
        if on_chip:
            values = dict(win.metrics, setup_s=setup_s)
            for m in metrics_of(bench, "end_to_end", args.workload):
                if m["name"] in values:
                    line["metrics"][m["name"]] = {
                        "value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        from chipbench import peaks, tracered

        trace = None
        if tracing:
            path = tracered.find_xplane(TRACE_DIR)
            trace = tracered.load_xplane(path) if path else None
        ctx = {"trace": trace, "window": win, "config": config,
               "traffic": traffic, "chips": len(devices),
               "compiles": compiles, "registry": (before, after),
               "memory_peak_bytes": line["device"]["memory_peak_bytes"],
               "peaks": peaks.peaks_of(devices[0].device_kind)
               if on_chip else None}
        line["metrics"] = read_layer_metrics(
            metrics_of(bench, "per_layer", args.workload), ctx)
        if trace is not None:
            from chipbench import breakdown

            line["device"].update(breakdown.busy_record(trace, len(devices)))
            line["breakdown"] = breakdown.breakdown(trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
