"""Share of a published peak: the work a step needs (a function of
``peaks.py`` applied to the cell's shapes) over the device time the
matching XLA modules took, over the peak."""
from chipbench import peaks, tracered
from chipbench.reducers._util import device0

_UNIT = {"hbm_gb_s": 1e9, "bf16_tflops": 1e12}


def _work(ctx, params):
    cfg, win = ctx["config"], ctx["window"]
    rows = win.counters.get("node_rows")
    if rows is None:
        return None
    if params["work"] == "gather_bytes":
        return peaks.gather_bytes(rows, cfg["data"]["feature_dim"])
    raise ValueError(f"unknown work function {params['work']!r}")


def read(ctx, params):
    found = device0(ctx)
    work = _work(ctx, params)
    if found is None or work is None or not ctx["window"].steps:
        return None
    dev, window = found
    ns, _ = tracered.module_time(dev["modules"], params["module_regex"],
                                 window)
    if not ns:
        return None
    per_s = work * ctx["window"].steps / (ns / 1e9)
    return 100.0 * per_s / (ctx["peaks"][params["peak"]]
                            * _UNIT[params["peak"]])
