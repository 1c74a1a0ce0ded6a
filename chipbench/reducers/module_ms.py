"""Device time per step of the XLA modules whose name matches
``module_regex``."""
from chipbench import tracered
from chipbench.reducers._util import device0, per_step_ms


def read(ctx, params):
    found = device0(ctx)
    if found is None:
        return None
    dev, window = found
    ns, runs = tracered.module_time(dev["modules"], params["module_regex"],
                                    window)
    return per_step_ms(ns, ctx) if runs else None
