"""Share of the HBM peak that a tiered gather's hot half reaches: the
bytes the hot rows have to move (each row served from HBM read once and
written once, at the table's width and item size; the row count is the
program's ``glt.feature.hot_rows`` counter over the window) over the
exclusive device time of the ops under the ``glt.gather.feat`` scope in
that window (the ``id2index`` lookup and the row gather; the cold rows'
placement is ``glt.gather.merge``), over the published peak.  No share
of the host link is reported: ``peaks.py`` has no published peak for it.

None where there is nothing to read: no device trace, no scope in it, or
a program without the counter (a checkout from before the tiers'
instrumentation)."""
import re

import numpy as np

from chipbench import peaks, scopes


def hot_gather_bytes(hot_rows: float, dim: int, itemsize: int) -> float:
    """HBM bytes of the window's hot-row gathers: read + write."""
    return peaks.gather_bytes(hot_rows, dim, itemsize)


def read(ctx, params):
    path = scopes.traced_file()
    if not path or ctx["peaks"] is None or not ctx["window"].steps:
        return None
    rx = re.compile(params["scope_regex"])
    ns = sum(t for scope, t in scopes.scoped_self_times(
        ctx["trace"], scopes.scope_map(path)) if scope and rx.search(scope))
    before, after = ctx["registry"]
    rows = after.get(params["counter"], 0) - before.get(params["counter"], 0)
    if not ns or rows <= 0:
        return None
    data = ctx["config"]["data"]
    work = hot_gather_bytes(rows, data["feature_dim"],
                            np.dtype(data["feature_dtype"]).itemsize)
    return 100.0 * work / (ns / 1e9) / (ctx["peaks"][params["peak"]] * 1e9)
