"""1 less the union of device-op intervals over the traced window."""
from chipbench import tracered
from chipbench.reducers._util import device0


def read(ctx, params):
    found = device0(ctx)
    if found is None:
        return None
    dev, window = found
    busy, _ = tracered.busy_and_gaps(dev["ops"], window)
    return 100.0 * (1.0 - busy / max(window[1] - window[0], 1.0))
