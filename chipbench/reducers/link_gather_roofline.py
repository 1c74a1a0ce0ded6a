"""Share of the HBM peak that a link step's feature gather reaches: the
bytes the gather has to move (every row of the seed union's node list
read once and written once, at the table's width and item size; the row
count is the program's ``glt.link.node_rows`` gauge, set when the
scanned link step is built) over the exclusive device time of the ops
under the ``glt.gather.feat`` scope, over the published peak.

None where there is nothing to read: no device trace, no scope in it, or
a program that sets no such gauge (a checkout from before the gauge)."""
import re

import numpy as np

from chipbench import peaks, scopes


def read(ctx, params):
    path = scopes.traced_file()
    steps = ctx["window"].steps
    if not path or not steps or ctx["peaks"] is None:
        return None
    rx = re.compile(params["scope_regex"])
    ns = sum(t for scope, t in scopes.scoped_self_times(
        ctx["trace"], scopes.scope_map(path)) if scope and rx.search(scope))
    _, after = ctx["registry"]
    rows = after.get(params["gauge"], 0)
    if not ns or not rows:
        return None
    data = ctx["config"]["data"]
    work = peaks.gather_bytes(rows, data["feature_dim"],
                        np.dtype(data["feature_dtype"]).itemsize)
    return 100.0 * work * steps / (ns / 1e9) \
        / (ctx["peaks"][params["peak"]] * 1e9)
