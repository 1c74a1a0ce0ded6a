"""Share of the HBM peak that the dense update of the embedding tables
reaches: the bytes the update has to move, over the exclusive device
time of the ops under the ``glt.embed.update`` scope, over the published
peak.

The work is the update's own, whatever implements it: every float of
every table has its parameter and both Adam moments read once and
written once, 24 B (the gradient's bytes are not counted).  The rows are
the program's ``glt.embed.table_rows{type}`` gauges, summed (set when
the step is built), the width the configuration's ``model.hidden``.

None where there is nothing to read: no device trace, no scope in it, or
a program that sets no such gauge (a checkout from before the tables)."""
import re

from chipbench import scopes

#: Bytes a table float costs the update: p, m and v, read and written.
BYTES_PER_FLOAT = 24


def update_bytes(rows: int, width: int) -> int:
    return BYTES_PER_FLOAT * int(rows) * int(width)


def read(ctx, params):
    path = scopes.traced_file()
    steps = ctx["window"].steps
    if not path or not steps or ctx["peaks"] is None:
        return None
    rx = re.compile(params["scope_regex"])
    ns = sum(t for scope, t in scopes.scoped_self_times(
        ctx["trace"], scopes.scope_map(path)) if scope and rx.search(scope))
    _, after = ctx["registry"]
    gauge = re.compile(params["gauge_regex"])
    rows = sum(v for n, v in after.items() if gauge.search(n))
    if not ns or not rows:
        return None
    work = update_bytes(rows, ctx["config"]["model"]["hidden"])
    return 100.0 * work * steps / (ns / 1e9) \
        / (ctx["peaks"][params["peak"]] * 1e9)
