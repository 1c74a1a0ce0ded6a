"""What a traced line carries besides its metrics: the device's busy
seconds, and where the time went (top device ops, idle gaps by the
benchmark's host span)."""
from __future__ import annotations

from chipbench import tracered


def busy_record(trace: dict, chips: int) -> dict:
    """``busy_s`` (op-running seconds, averaged over the chips used) and
    ``window_s`` (length of the traced window)."""
    window = trace["window"]
    busy = [tracered.busy_and_gaps(dev["ops"], window)[0]
            for dev in trace["devices"].values()]
    return {"busy_s": sum(busy) / max(chips, 1) / 1e9,
            "window_s": (window[1] - window[0]) / 1e9}


def breakdown(trace: dict, device: str = "0") -> dict:
    dev = trace["devices"].get(device) or next(
        iter(trace["devices"].values()), {"ops": []})
    window = trace["window"]
    _, gaps = tracered.busy_and_gaps(dev["ops"], window)
    return {
        "device_ops": [[n, t / 1e9] for n, t in
                       tracered.top_ops(dev["ops"], window)],
        "idle_gaps": [[n, t / 1e9] for n, t in
                      tracered.attribute_gaps(gaps, trace["host"])],
    }
