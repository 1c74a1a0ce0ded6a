"""Exclusive device time a step of the ops under ``scope_regex``, as
``scope_ms`` reads it, over what the program counted a batch in the
window: the difference of the registry counters matching ``counter``
over that of ``glt.sample.batches``, in ns a count.  The time is the
per-slot metric's; the denominator is the work, whatever the slots.

None without a device trace or its scopes, and where the counters did
not move (a program without them)."""
from chipbench.reducers import scope_ms
from chipbench.reducers.registry_share import moved


def read(ctx, params):
    if not ctx["trace"] or not ctx["window"].steps:
        return None
    ms = scope_ms.read(ctx, {"scope_regex": params["scope_regex"]})
    batches = moved(ctx, r"^glt\.sample\.batches$")
    count = moved(ctx, params["counter"])
    if ms is None or not batches or not count:
        return None
    return ms * 1e6 / (count / batches)
