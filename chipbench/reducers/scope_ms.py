"""Exclusive device time per step of the ops whose ``glt.*`` scope (the
program's ``jax.named_scope``, read back through ``chipbench/scopes.py``)
matches ``scope_regex``.  With ``unscoped_share`` instead: the time of
the ops that carry no scope over the time of all ops, in %.

None when no op of the window has a scope: a trace whose programs were
compiled before the scopes existed (a compile cache filled by an older
checkout serves them with their old metadata) holds nothing to read,
which is not the same as 0."""
import re

from chipbench import scopes
from chipbench.reducers._util import per_step_ms


def read(ctx, params):
    path = scopes.traced_file()
    times = scopes.scoped_self_times(
        ctx["trace"], scopes.scope_map(path)) if path else []
    if not any(scope for scope, _ in times):
        return None
    if params.get("unscoped_share"):
        return 100.0 * sum(ns for scope, ns in times if scope is None) \
            / max(sum(ns for _, ns in times), 1.0)
    rx = re.compile(params["scope_regex"])
    return per_step_ms(sum(ns for scope, ns in times
                           if scope and rx.search(scope)), ctx)
