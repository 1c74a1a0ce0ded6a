"""``memory_stats()["peak_bytes_in_use"]``, the largest over the chips,
when the window closes."""


def read(ctx, params):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
