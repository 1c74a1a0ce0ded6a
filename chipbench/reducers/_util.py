"""Shared by the readers: the traced device, and per-step division."""
from __future__ import annotations


def device0(ctx):
    """Device 0 (the one the metrics read) as its ``ops``, ``async`` and
    ``modules`` lists, and the window; None when the run has no device
    trace."""
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    dev = trace["devices"].get("0") or next(iter(trace["devices"].values()))
    return dev, trace["window"]


def per_step_ms(ns: float, ctx):
    steps = ctx["window"].steps
    return None if not steps else ns / 1e6 / steps
