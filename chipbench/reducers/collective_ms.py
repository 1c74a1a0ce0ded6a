"""Time per step inside collectives on device 0; with ``exposed`` the
part of it during which nothing else runs there."""
from chipbench import tracered
from chipbench.reducers._util import device0, per_step_ms


def read(ctx, params):
    found = device0(ctx)
    if found is None:
        return None
    dev, window = found
    total, exposed = tracered.collective_times(dev["ops"], dev["async"],
                                               window)
    if not total:
        return None
    return per_step_ms(exposed if params.get("exposed") else total, ctx)
