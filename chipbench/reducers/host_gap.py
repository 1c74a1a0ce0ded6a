"""Device-idle time that falls between two XLA programs (not inside one),
as a share of the traced window: what the host's loop costs."""
from chipbench import tracered
from chipbench.reducers._util import device0


def read(ctx, params):
    found = device0(ctx)
    if found is None:
        return None
    dev, window = found
    as_ops = [[n, "", a, d] for n, a, d in dev["modules"]]
    _, gaps = tracered.busy_and_gaps(as_ops, window)
    return 100.0 * sum(hi - lo for lo, hi in gaps) \
        / max(window[1] - window[0], 1.0)
