"""Device time per step in which some op ran: the union of the op
intervals of the traced window over the steps it finished."""
from chipbench import tracered
from chipbench.reducers._util import device0, per_step_ms


def read(ctx, params):
    found = device0(ctx)
    if found is None:
        return None
    dev, window = found
    busy, _ = tracered.busy_and_gaps(dev["ops"], window)
    return per_step_ms(busy, ctx)
