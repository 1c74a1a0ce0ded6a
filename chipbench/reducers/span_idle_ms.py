"""Device-idle time per step that falls under the program's host span
``span`` (``glt_tpu.obs.span``, on the profiler's clock as ``glt.<name>``):
the intersection of device 0's idle gaps with the union of those spans,
so a gap that two spans share is split between them by time, where
``tracered.attribute_gaps`` gives the whole gap to one.

None when the trace holds no span of that name."""
from chipbench import scopes, tracered
from chipbench.reducers._util import device0, per_step_ms


def read(ctx, params):
    found = device0(ctx)
    if found is None:
        return None
    dev, window = found
    path = scopes.traced_file()
    spans = [(start, start + dur)
             for name, start, dur in (scopes.program_spans(path)
                                      if path else [])
             if name == params["span"]]
    if not spans:
        return None
    _, gaps = tracered.busy_and_gaps(dev["ops"], window)
    return per_step_ms(intersection_ns(gaps, spans), ctx)


def intersection_ns(gaps, spans) -> float:
    """Nanoseconds inside both some gap and some span; ``gaps`` are
    disjoint, ``spans`` may nest or overlap (threads)."""
    merged = []
    for lo, hi in sorted(spans):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    total, i = 0.0, 0
    for glo, ghi in gaps:                  # both sorted: one sweep
        while i < len(merged) and merged[i][1] <= glo:
            i += 1
        j = i
        while j < len(merged) and merged[j][0] < ghi:
            total += min(ghi, merged[j][1]) - max(glo, merged[j][0])
            j += 1
    return total
