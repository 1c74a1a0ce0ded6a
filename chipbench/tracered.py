"""From a profiler trace to numbers: the reduction every PR shares.

``load_xplane`` turns JAX's ``.xplane.pb`` into a small plain structure
(``normalise`` below documents it; the tests keep one as JSON), and the
functions after it compute on that structure only:

* ``busy_and_gaps``  — union of the device-op intervals inside the
  window, and the idle gaps between them;
* ``self_times``     — per-op exclusive time (an enclosing ``while`` does
  not count its body twice);
* ``module_time``    — device time of the XLA modules whose name matches;
* ``op_time``        — exclusive time of the ops whose name or result
  type matches;
* ``collective_times`` — total and exposed time of the collectives,
  blocking and asynchronous;
* ``attribute_gaps`` — each idle gap given to the benchmark's host span
  (``jax.profiler.TraceAnnotation`` from the drivers) that covers most of
  it.

Times are nanoseconds on the profile's own clock; a traced TPU plane and
the host plane share it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVE_KINDS = ("all-to-all", "all-gather", "all-reduce",
                    "collective-permute", "reduce-scatter")
HOST_SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
#: Device-plane lines that hold XLA ops, asynchronous ops (copies,
#: slices, collectives that run beside the ops) and XLA modules.
_OPS_LINE = "XLA Ops"
_ASYNC_LINE = "Async XLA Ops"
_MODULES_LINE = "XLA Modules"


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_ARRAY = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")


def split_hlo(event_name: str) -> Tuple[str, str]:
    """A TPU op event is named by its whole HLO instruction,
    ``%fusion.7 = f32[8,128]{1,0} fusion(...)``: the instruction's name
    (``fusion.7``) and its opcode with its result type (``fusion
    f32[8,128]``).  The name is JAX's (``all_to_all.3``), the opcode is
    XLA's (``all-to-all``): kinds of op are told by the opcode.  The v5e
    trace carries no framework name (no JAX name stack, no Flax scope) on
    op events, so an op cannot be given to a module of the program."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name.lstrip("%"), ""
    opcode = _OPCODE.search(" " + rest)
    first = _ARRAY.search(rest)
    result = (first.group(0) if first else "") + \
        ("+" if rest.startswith("(") else "")      # "+": first of a tuple
    return head.lstrip("%"), \
        f"{opcode.group(1) if opcode else ''} {result}".strip()


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path: str) -> dict:
    """Read ``path`` with ``jax.profiler.ProfileData`` and normalise."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            dev = devices.setdefault(
                m.group(1), {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                if line.name in (_OPS_LINE, _ASYNC_LINE):
                    key = "ops" if line.name == _OPS_LINE else "async"
                    for ev in line.events:
                        name, text = split_hlo(ev.name)
                        dev[key].append([name, text, ev.start_ns,
                                         ev.duration_ns])
                elif line.name == _MODULES_LINE:
                    for ev in line.events:
                        dev["modules"].append([ev.name, ev.start_ns,
                                               ev.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return normalise({"devices": devices, "host": host})


def normalise(trace: dict) -> dict:
    """The structure every function below takes::

        {"devices": {"0": {"ops":     [[name, "opcode type", start, dur]],
                           "async":   [[name, "opcode type", start, dur]],
                           "modules": [[name, start_ns, dur_ns]]}},
         "host":    [[name, start_ns, dur_ns]],
         "window":  [start_ns, end_ns]}

    Lists are sorted by start.  The window is the host span
    ``chipbench.window`` when the trace has one, else the extent of the
    device ops."""
    for dev in trace["devices"].values():
        dev["ops"].sort(key=lambda e: (e[2], -e[3]))
        dev.setdefault("async", []).sort(key=lambda e: e[2])
        dev["modules"].sort(key=lambda e: e[1])
    trace["host"].sort(key=lambda e: e[1])
    if "window" not in trace:
        spans = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
        if spans:
            trace["window"] = [spans[0][1], spans[0][1] + spans[0][2]]
        else:
            ops = [e for d in trace["devices"].values() for e in d["ops"]]
            trace["window"] = ([min(e[2] for e in ops),
                                max(e[2] + e[3] for e in ops)]
                               if ops else [0, 0])
    return trace


def _clip(start, dur, window) -> Tuple[float, float]:
    return max(start, window[0]), min(start + dur, window[1])


def busy_and_gaps(ops, window) -> Tuple[float, List[Tuple[float, float]]]:
    """Nanoseconds in which some op ran inside ``window`` and the idle
    gaps ``(start, end)`` between them (window edges included)."""
    busy, gaps, cursor = 0.0, [], window[0]
    for _, _, start, dur in ops:
        lo, hi = _clip(start, dur, window)
        if hi <= lo:
            continue
        if lo > cursor:
            gaps.append((cursor, lo))
        if hi > cursor:
            busy += hi - max(lo, cursor)
            cursor = hi
    if window[1] > cursor:
        gaps.append((cursor, window[1]))
    return busy, gaps


def self_times(ops, window) -> List[Tuple[str, str, float]]:
    """``(name, scope, exclusive_ns)`` per op inside ``window``: an op's
    time less the time of the ops nested in it (``ops`` sorted by start,
    longer first)."""
    out, stack = [], []          # stack of [end, index into out]
    for name, scope, start, dur in ops:
        lo, hi = _clip(start, dur, window)
        if hi <= lo:
            continue
        while stack and stack[-1][0] <= lo:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[2] -= min(hi, stack[-1][0]) - lo
        out.append([name, scope, hi - lo])
        stack.append([hi, len(out) - 1])
    return [(n, s, max(t, 0.0)) for n, s, t in out]


def module_time(modules, pattern: str, window) -> Tuple[float, int]:
    """Nanoseconds and runs of the XLA modules whose name matches."""
    rx = re.compile(pattern)
    total, runs = 0.0, 0
    for name, start, dur in modules:
        lo, hi = _clip(start, dur, window)
        if hi > lo and rx.search(name):
            total += hi - lo
            runs += 1
    return total, runs


def op_time(ops, pattern: str, window) -> float:
    """Exclusive nanoseconds of the ops whose name or result type
    matches."""
    rx = re.compile(pattern)
    return sum(t for n, s, t in self_times(ops, window)
               if rx.search(n) or rx.search(s))


def is_collective(op, kinds=COLLECTIVE_KINDS) -> bool:
    """Whether an op entry ``[name, "opcode type", ...]`` is a collective
    of ``kinds`` (``all-reduce-start`` counts as ``all-reduce``)."""
    return op[1].startswith(tuple(kinds))


def collective_times(ops, async_ops, window, kinds=COLLECTIVE_KINDS
                     ) -> Tuple[float, float]:
    """``(total_ns, exposed_ns)``: time inside collectives of ``kinds``
    (blocking ones among ``ops``, and ``-start`` to ``-done`` spans among
    ``async_ops``), and the part of it during which no other op runs on
    that device.  Enclosing control flow (an op that contains a blocking
    collective) is not "another op"."""
    blocking = [e for e in ops if is_collective(e, kinds)]
    coll = sorted(blocking + [e for e in async_ops
                              if is_collective(e, kinds)],
                  key=lambda e: e[2])
    starts = sorted(e[2] for e in blocking)
    other = []
    for e in ops:
        if is_collective(e, kinds):
            continue
        i = bisect.bisect_left(starts, e[2])
        if i < len(starts) and starts[i] < e[2] + e[3]:
            continue                       # encloses a collective
        other.append(e)
    total, _ = busy_and_gaps(coll, window)
    both, _ = busy_and_gaps(sorted(coll + other, key=lambda e: e[2]),
                            window)
    other_busy, _ = busy_and_gaps(other, window)
    return total, both - other_busy


def attribute_gaps(gaps, host, top: int = 10) -> List[Tuple[str, float]]:
    """Idle nanoseconds by the benchmark host span that overlaps each gap
    most (``"no benchmark span"`` where none does), largest first."""
    spans = [e for e in host if e[0] != WINDOW_SPAN]
    by: Dict[str, float] = {}
    for lo, hi in gaps:
        best, best_ov = "no benchmark span", 0.0
        for name, start, dur in spans:
            if start >= hi:
                break
            ov = min(hi, start + dur) - max(lo, start)
            if ov > best_ov:
                best, best_ov = name, ov
        by[best] = by.get(best, 0.0) + (hi - lo)
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]


def top_ops(ops, window, top: int = 10) -> List[Tuple[str, float]]:
    """Exclusive nanoseconds by opcode and result type (``fusion
    f32[402944,256]``: every fusion of that shape is one row), largest
    first."""
    by: Dict[str, float] = {}
    for name, text, t in self_times(ops, window):
        key = (text or re.sub(r"[.][0-9]+$", "", name))[:80]
        by[key] = by.get(key, 0.0) + t
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]


def describe(path: str, samples: int = 6) -> str:
    """Planes, lines and a few events with their stats: what to read by
    hand before trusting the reduction on a new kind of trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:samples]:
                out.append(f"    {ev.name!r} start {ev.start_ns:.0f} dur "
                           f"{ev.duration_ns:.0f} {dict(ev.stats)}")
    return "\n".join(out)


def cut(trace: dict, max_ops: int) -> dict:
    """The first ``max_ops`` ops of every device, with the modules and
    host spans that start before the last of them ends, rounded to whole
    nanoseconds: a trace small enough to keep beside the tests."""
    end = 0
    devices = {}
    for key, dev in trace["devices"].items():
        ops = dev["ops"][:max_ops]
        end = max([end] + [e[2] + e[3] for e in ops])
        devices[key] = {"ops": ops}
    for key, dev in trace["devices"].items():
        devices[key]["modules"] = [m for m in dev["modules"] if m[1] < end]
        devices[key]["async"] = [a for a in dev["async"] if a[2] < end]
    start = min([e[2] for d in devices.values() for e in d["ops"]] or [0])
    return {"devices": devices,
            "host": [h for h in trace["host"]
                     if h[1] < end and h[1] + h[2] > start
                     and h[0] != WINDOW_SPAN],
            "window": [start, end]}


if __name__ == "__main__":
    import json
    import sys

    if sys.argv[1] == "describe":
        print(describe(find_xplane(sys.argv[2]) or sys.argv[2]))
    elif sys.argv[1] == "cut":
        small = cut(load_xplane(find_xplane(sys.argv[2]) or sys.argv[2]),
                    int(sys.argv[4]))
        with open(sys.argv[3], "w") as fh:
            json.dump(small, fh)
