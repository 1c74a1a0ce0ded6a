"""Share of the HBM peak that a heterogeneous step's feature gather
reaches: the bytes the gather has to move (every node row of every type
read once and written once, at the table's own width and item size; the
row counts are the program's ``glt.hetero.node_rows{type}`` gauges, set
when the scanned step is built) over the exclusive device time of the ops
under the ``glt.gather.feat`` scope, over the published peak.

None where there is nothing to read: no device trace, no scope in it, or
a program that sets no such gauge (a checkout from before the gauges)."""
import re

import numpy as np

from chipbench import scopes


def gather_bytes(node_rows: dict, dim: int, itemsize: int) -> int:
    """HBM bytes of one step's per-type row gathers: read + write."""
    return 2 * int(sum(node_rows.values())) * int(dim) * int(itemsize)


def read(ctx, params):
    path = scopes.traced_file()
    steps = ctx["window"].steps
    if not path or not steps or ctx["peaks"] is None:
        return None
    rx = re.compile(params["scope_regex"])
    ns = sum(t for scope, t in scopes.scoped_self_times(
        ctx["trace"], scopes.scope_map(path)) if scope and rx.search(scope))
    _, after = ctx["registry"]
    rows = {k: v for k, v in after.items()
            if k.startswith(params["gauge"] + "{") and v > 0}
    if not ns or not rows:
        return None
    data = ctx["config"]["data"]
    name = data["feature_dtype"]            # numpy knows no bfloat16
    work = gather_bytes(rows, data["feature_dim"],
                        2 if name == "bfloat16" else np.dtype(name).itemsize)
    return 100.0 * work * steps / (ns / 1e9) \
        / (ctx["peaks"][params["peak"]] * 1e9)
