"""From a profiler trace to the program's own names.

The program marks its device work with ``jax.named_scope("glt.<layer>.
<stage>")`` and its host work with ``glt_tpu.obs.span`` (which opens a
``jax.profiler.TraceAnnotation("glt.<name>")``).  Both end up in the
``.xplane.pb`` that ``jax.profiler`` writes:

* plane ``/host:metadata`` holds the optimised HLO of every module that
  ran, one event-metadata entry per module, named ``<module>(<program
  id>)`` as the module's events on the device plane are, with one stat
  ``Hlo Proto`` whose bytes are an ``HloProto``.  Every instruction in it
  carries ``metadata.op_name``, the JAX name stack
  (``jit(step)/while/body/jvp(M)/glt.model.agg/scatter-add``); the device
  plane names an op event by its instruction (``tracered.split_hlo``), so
  *(module, instruction) -> scope* is a lookup;
* the host planes hold the ``glt.*`` annotations on the device's clock.

``jax.profiler.ProfileData`` shows the metadata plane with no lines and
no way to its event metadata, so the plane is read here from the
protobuf wire format directly, with no dependency: only the fields named
in ``_F`` are followed, everything else is skipped by its wire type.

Nothing here raises into ``run.py``: a file that cannot be read gives an
empty result and one line on standard error.
"""
from __future__ import annotations

import bisect
import functools
import os
import re
import sys
from collections import Counter
from typing import Dict, Iterator, List, Optional, Tuple

if __name__ == "__main__":          # run as a script: find the package
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import tracered  # noqa: E402

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
PROGRAM_SPAN_PREFIX = "glt."
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".trace")

#: Field numbers followed (proto3; checked against the ``_pb2``
#: descriptors of tensorflow's ``xplane.proto`` and ``hlo.proto``).
_F = {
    "XSpace.planes": 1,
    "XPlane.name": 2, "XPlane.event_metadata": 4, "XPlane.stat_metadata": 5,
    "map.value": 2,
    "XEventMetadata.name": 2, "XEventMetadata.stats": 5,
    "XStatMetadata.id": 1, "XStatMetadata.name": 2,
    "XStat.metadata_id": 1, "XStat.bytes_value": 6,
    "HloProto.hlo_module": 1,
    "HloModuleProto.computations": 3,
    "HloComputationProto.instructions": 2, "HloComputationProto.id": 5,
    "HloInstructionProto.name": 1, "HloInstructionProto.opcode": 2,
    "HloInstructionProto.metadata": 7,
    "HloInstructionProto.called_computation_ids": 38,
    "OpMetadata.op_name": 2,
}

_SCOPE = re.compile(r"glt\.[a-z0-9_]+(?:\.[a-z0-9_]+)*")
#: Control flow runs its callees as op events of their own: what is left
#: of a ``while`` is the loop's overhead, not its body's work.
_CONTROL_FLOW = ("while", "conditional", "call")


def _warn(msg: str) -> None:
    print(f"[chipbench.scopes] {msg}", file=sys.stderr, flush=True)


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, pos: int) -> Tuple[int, int]:
    value, shift = 0, 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an int for a
    varint (wire type 0), a memoryview for a length-delimited field (2);
    fixed 64- and 32-bit fields (1, 5) are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, wire, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            if pos + size > end:
                raise ValueError("length-delimited field runs past its "
                                 "message")
            yield number, wire, buf[pos:pos + size]
            pos += size
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _packed(buf) -> List[int]:
    """The varints of a packed repeated field."""
    out, pos = [], 0
    while pos < len(buf):
        value, pos = _varint(buf, pos)
        out.append(value)
    return out


def _map_entries(plane, number: int):
    for n, wire, entry in _fields(plane):
        if n == number and wire == 2:
            for k, w, value in _fields(entry):
                if k == _F["map.value"] and w == 2:
                    yield value


def _hlo_bytes_of_plane(plane) -> Dict[str, memoryview]:
    """``{"<module>(<program id>)": HloProto bytes}`` of the metadata
    plane."""
    hlo_stat_ids = set()
    for meta in _map_entries(plane, _F["XPlane.stat_metadata"]):
        sid, name = None, ""
        for n, wire, value in _fields(meta):
            if n == _F["XStatMetadata.id"] and wire == 0:
                sid = value
            elif n == _F["XStatMetadata.name"] and wire == 2:
                name = _text(value)
        if name == HLO_STAT:
            hlo_stat_ids.add(sid)
    out = {}
    for meta in _map_entries(plane, _F["XPlane.event_metadata"]):
        name, blob = "", None
        for n, wire, value in _fields(meta):
            if n == _F["XEventMetadata.name"] and wire == 2:
                name = _text(value)
            elif n == _F["XEventMetadata.stats"] and wire == 2:
                sid, data = None, None
                for k, w, v in _fields(value):
                    if k == _F["XStat.metadata_id"] and w == 0:
                        sid = v
                    elif k == _F["XStat.bytes_value"] and w == 2:
                        data = v
                if data is not None and sid in hlo_stat_ids:
                    blob = data
        if blob is not None:
            out[name] = blob
    return out


def _computations(hlo_proto):
    """``[(computation id, [(name, opcode, op_name, called ids)])]`` of
    an ``HloProto``."""
    out = []
    for n, wire, module in _fields(hlo_proto):
        if n != _F["HloProto.hlo_module"] or wire != 2:
            continue
        for n2, w2, comp in _fields(module):
            if n2 != _F["HloModuleProto.computations"] or w2 != 2:
                continue
            cid, instrs = None, []
            for n3, w3, value in _fields(comp):
                if n3 == _F["HloComputationProto.id"] and w3 == 0:
                    cid = value
                elif n3 == _F["HloComputationProto.instructions"] \
                        and w3 == 2:
                    instrs.append(_instruction(value))
            out.append((cid, instrs))
    return out


def _instruction(buf):
    name, opcode, op_name, called = "", "", "", []
    for n, wire, value in _fields(buf):
        if n == _F["HloInstructionProto.name"] and wire == 2:
            name = _text(value)
        elif n == _F["HloInstructionProto.opcode"] and wire == 2:
            opcode = _text(value)
        elif n == _F["HloInstructionProto.metadata"] and wire == 2:
            for k, w, v in _fields(value):
                if k == _F["OpMetadata.op_name"] and w == 2:
                    op_name = _text(v)
        elif n == _F["HloInstructionProto.called_computation_ids"]:
            called.extend([value] if wire == 0 else _packed(value))
    return name, opcode, op_name, called


# -- scopes ------------------------------------------------------------------

def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The program's scope in a JAX name stack: the first ``glt.a.b``
    anywhere in it, so ``jit(f)/transpose(jvp(glt.model.msg))/mul`` is
    ``glt.model.msg``; None where there is none."""
    m = _SCOPE.search(op_name or "")
    return m.group(0) if m else None


def resolve(computations) -> Dict[str, str]:
    """``{instruction name: op_name}`` over every computation of one
    module (instruction names are unique in a module).  An instruction
    whose own ``op_name`` has no scope and which calls a computation (a
    fusion, above all) gets the commonest scope among that computation's
    instructions in its place; control flow does not (its callees are op
    events of their own)."""
    by_id = dict(computations)
    out = {}
    for _, instrs in computations:
        for name, opcode, op_name, called in instrs:
            if called and opcode not in _CONTROL_FLOW \
                    and scope_of(op_name) is None:
                inner = Counter(
                    s for cid in called
                    for _, _, inner_name, _ in by_id.get(cid, ())
                    for s in [scope_of(inner_name)] if s)
                if inner:
                    op_name = inner.most_common(1)[0][0]
            out[name] = op_name
    return out


@functools.lru_cache(maxsize=4)
def scope_map(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """``{"<module>(<program id>)": {instruction name: op_name}}`` from
    plane ``/host:metadata`` of a trace file; ``{}`` for a file that
    cannot be read or holds no HLO.  Parsed once per process and path
    (a profiler session writes a new directory, so a path is never
    rewritten): treat the result as read-only."""
    try:
        with open(xplane_path, "rb") as fh:
            space = memoryview(fh.read())
        out = {}
        for n, wire, plane in _fields(space):
            if n != _F["XSpace.planes"] or wire != 2:
                continue
            name = next((_text(v) for k, w, v in _fields(plane)
                         if k == _F["XPlane.name"] and w == 2), "")
            if name != METADATA_PLANE:
                continue
            for module, blob in _hlo_bytes_of_plane(plane).items():
                out[module] = resolve(_computations(blob))
        return out
    except (OSError, ValueError, IndexError) as e:
        _warn(f"no scope map from {xplane_path!r}: "
              f"{type(e).__name__}: {e}")
        return {}


def _device0(trace: Optional[dict]) -> Optional[dict]:
    """Device 0 of a normalised trace (the one the metrics read)."""
    devices = (trace or {}).get("devices")
    if not devices:
        return None
    return devices.get("0") or next(iter(devices.values()))


def _scoped_ops(trace: Optional[dict], smap: Dict[str, Dict[str, str]]
                ) -> List[list]:
    """``[scope or None, exclusive ns, op]`` per op of device 0 inside
    the trace's window: ``tracered.self_times``' rule (an op's time less
    the time of the ops nested in it; that function drops the start
    times, hence this walk), with each op given to the XLA module event
    that contains its start (a module's events are named as its entry of
    the metadata plane is) and looked up there by instruction name: names
    repeat across modules, so never by name alone."""
    dev = _device0(trace)
    if dev is None:
        return []
    window = trace["window"]
    modules = dev["modules"]                    # sorted by start
    starts = [m[1] for m in modules]
    out, stack = [], []                         # stack of [end, index]
    for op in dev["ops"]:
        name, _, start, dur = op
        lo, hi = max(start, window[0]), min(start + dur, window[1])
        if hi <= lo:
            continue
        while stack and stack[-1][0] <= lo:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(hi, stack[-1][0]) - lo
        scope = None
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < modules[i][1] + modules[i][2]:
            scope = scope_of(smap.get(modules[i][0], {}).get(name))
        out.append([scope, hi - lo, op])
        stack.append([hi, len(out) - 1])
    return out


def scoped_self_times(trace: Optional[dict], smap: Dict[str, Dict[str, str]]
                      ) -> List[Tuple[Optional[str], float]]:
    """``(scope or None, exclusive ns)`` per op of device 0 inside the
    trace's window; ``[]`` for no trace."""
    return [(s, max(t, 0.0)) for s, t, _ in _scoped_ops(trace, smap)]


@functools.lru_cache(maxsize=4)
def program_spans(xplane_path: str) -> List[list]:
    """``[[name, start_ns, dur_ns]]`` of the host events whose name
    starts with ``glt.`` (the program's own spans), sorted by start;
    ``[]`` for a file that cannot be read.  Parsed once per process and
    path: treat the result as read-only."""
    try:
        from jax.profiler import ProfileData

        out = []
        for plane in ProfileData.from_file(xplane_path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_SPAN_PREFIX):
                        out.append([ev.name, ev.start_ns, ev.duration_ns])
        return sorted(out, key=lambda e: e[1])
    except Exception as e:  # noqa: BLE001 — a reader never raises
        _warn(f"no program spans from {xplane_path!r}: "
              f"{type(e).__name__}: {e}")
        return []


def traced_file() -> Optional[str]:
    """The trace file of this process's traced run, where ``run.py``
    left it."""
    return tracered.find_xplane(TRACE_DIR)


# -- by hand -----------------------------------------------------------------

def describe(path: str, samples: int = 10) -> str:
    """What to read before trusting a number from a new kind of trace:
    the modules of the metadata plane with how many of their
    instructions carry a scope, the longest ops of the largest module
    event with their name stacks, exclusive seconds by scope, the largest
    rows with and without one, and the program's spans."""
    smap = scope_map(path)
    out = [f"{METADATA_PLANE}: {len(smap)} modules with an '{HLO_STAT}' stat"]
    for module, table in sorted(smap.items(), key=lambda kv: -len(kv[1])):
        scoped = sum(scope_of(v) is not None for v in table.values())
        out.append(f"  {module}: {len(table)} instructions, "
                   f"{scoped} with a scope")
    try:
        trace = tracered.load_xplane(path)
    except Exception as e:  # noqa: BLE001 — describing, not measuring
        return "\n".join(out + [f"no device trace: {e}"])
    return "\n".join(out + _describe_ops(trace, smap, samples)
                     + _describe_spans(path))


def _describe_ops(trace: dict, smap, samples: int) -> List[str]:
    out = []
    dev = _device0(trace)
    if dev and dev["modules"]:
        big = max(dev["modules"], key=lambda m: m[2])
        table = smap.get(big[0], {})
        out.append(f"longest ops of {big[0]} ({big[2] / 1e6:.3f} ms):")
        longest: Dict[str, list] = {}
        for op in sorted(dev["ops"], key=lambda o: -o[3]):
            if big[1] <= op[2] < big[1] + big[2]:
                longest.setdefault(op[0], op)
        for name, text, _, dur in list(longest.values())[:samples]:
            out.append(f"  {dur / 1e3:10.1f} us  {name}  [{text}]  "
                       f"{scope_of(table.get(name))}  <- "
                       f"{table.get(name, '(not in the module)')[:120]}")
    ops = _scoped_ops(trace, smap)
    total = sum(ns for _, ns, _ in ops) or 1.0
    by: Dict[Optional[str], float] = {}
    rows: Dict[Tuple[Optional[str], str], float] = {}
    for scope, ns, op in ops:
        by[scope] = by.get(scope, 0.0) + ns
        row = (scope, op[1] or op[0])
        rows[row] = rows.get(row, 0.0) + ns
    out.append("exclusive seconds by scope (device 0, the window):")
    for scope, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        out.append(f"  {ns / 1e9:9.4f} s  {100 * ns / total:5.1f} %  {scope}")
    out.append("largest rows by scope, opcode and result type:")
    for (scope, text), ns in sorted(rows.items(),
                                    key=lambda kv: -kv[1])[:4 * samples]:
        out.append(f"  {ns / 1e9:9.4f} s  {100 * ns / total:5.1f} %  "
                   f"{scope}  {text[:70]}")
    out.append("largest rows without a scope:")
    for (scope, text), ns in sorted(
            ((k, v) for k, v in rows.items() if k[0] is None),
            key=lambda kv: -kv[1])[:2 * samples]:
        out.append(f"  {ns / 1e9:9.4f} s  {100 * ns / total:5.1f} %  "
                   f"{text[:90]}")
    return out


def _describe_spans(path: str) -> List[str]:
    out = []
    spans: Dict[str, List[float]] = {}
    for name, _, dur in program_spans(path):
        spans.setdefault(name, []).append(dur)
    out.append("program spans (host):")
    for name, durs in sorted(spans.items()):
        out.append(f"  {name}: {len(durs)} spans, "
                   f"{sum(durs) / 1e9:.4f} s")
    return out


if __name__ == "__main__":
    target = sys.argv[1] if len(sys.argv) > 1 else TRACE_DIR
    print(describe(tracered.find_xplane(target) or target))
