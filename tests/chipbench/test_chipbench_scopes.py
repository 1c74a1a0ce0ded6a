"""``chipbench/scopes.py`` and the two reducers that read through it
(tier-1, CPU): the hand decoder of the trace file's metadata plane, the
scope of an op, exclusive time by scope, and idle time under a program
span.  The metadata plane is the same on every backend, so the tests
record their own trace where they need a real one; device ops exist on
the chip only, so those are hand-made or the recorded chip cut."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

from chipbench import scopes, tracered  # noqa: E402
from chipbench.common import Window  # noqa: E402
from chipbench.reducers import scope_ms, span_idle_ms  # noqa: E402


# -- a protobuf writer, for hand-made trace files -----------------------------

def _varint(n):
    out = b""
    while True:
        low, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([low])
        out += bytes([low | 0x80])


def _field(number, payload):
    """Length-delimited for bytes/str, varint for an int."""
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _instr(name, opcode, op_name, called=(), packed=True):
    out = _field(1, name) + _field(2, opcode)
    out += _varint(5 << 3 | 1) + b"\0" * 8       # a fixed64 to skip over
    out += _field(7, _field(1, "op type") + _field(2, op_name))
    if called and packed:
        out += _field(38, b"".join(_varint(c) for c in called))
    else:
        out += b"".join(_field(38, c) for c in called)
    return out + _varint(9 << 3 | 5) + b"\0" * 4  # and a fixed32


def _hlo(computations):
    module = _field(1, "module")
    for cid, instrs in computations:
        module += _field(3, _field(1, f"comp{cid}")
                         + b"".join(_field(2, i) for i in instrs)
                         + _field(5, cid))
    return _field(1, module)


def _xspace(modules, hlo_stat="Hlo Proto"):
    """A trace file's bytes: a device plane to pass over, then the
    metadata plane with one entry per ``{name: HloProto bytes}``."""
    plane = _field(2, scopes.METADATA_PLANE)
    plane += _field(5, _field(1, 1) + _field(
        2, _field(1, 1) + _field(2, hlo_stat)))
    for i, (name, blob) in enumerate(modules.items(), 1):
        stat = _field(1, 1) + _field(6, blob)
        other = _field(1, 2) + _field(6, b"\xff\xff not an HloProto")
        meta = _field(1, i) + _field(2, name) + _field(5, other) \
            + _field(5, stat)
        plane += _field(4, _field(1, i) + _field(2, meta))
    return _field(1, _field(2, "/device:TPU:0") + _field(1, 7)) \
        + _field(1, plane)


STEP_HLO = _hlo([
    (1, [_instr("gather.1", "gather", "jit(f)/jvp(glt.model.msg)/gather"),
         _instr("mul.2", "multiply", "jit(f)/jvp(glt.model.agg)/mul"),
         _instr("add.3", "add", "jit(f)/jvp(glt.model.agg)/add"),
         _instr("p.4", "parameter", "")]),
    (2, [_instr("sub.5", "subtract", "jit(f)/glt.sample.hop2/sub")]),
    (3, [_instr("neg.6", "negate", "jit(f)/neg")]),
    (9, [_instr("fusion.1", "fusion", "", called=[1]),
         _instr("fusion.2", "fusion", "jit(f)/glt.step.loss/exp",
                called=[1]),
         _instr("fusion.3", "fusion", "jit(f)/mul", called=[3]),
         _instr("sort.4", "sort", "", called=[2, 3], packed=False),
         _instr("while.1", "while", "jit(f)/while", called=[1, 2]),
         _instr("copy.9", "copy", "")]),
])
GATHER_HLO = _hlo([
    (1, [_instr("fusion.1", "fusion", "jit(g)/glt.gather.feat/gather")]),
])


@pytest.fixture()
def hand_file(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_xspace({"jit_step(11)": STEP_HLO,
                              "jit__gather_hot_impl(12)": GATHER_HLO}))
    return str(path)


# -- scope_of, resolve, scope_map ---------------------------------------------

@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/glt.sample.hop1/gather", "glt.sample.hop1"),
    ("jit(step)/jvp(glt.step.loss)/reduce_sum", "glt.step.loss"),
    ("jit(f)/transpose(jvp(glt.model.msg))/scatter-add", "glt.model.msg"),
    ("jit(step)/while/body/closed_call/jvp(GraphSAGE)/conv0/"
     "glt.model.agg/scatter-add", "glt.model.agg"),
    ("jit(s)/glt.gather.feat/glt.sample.induce/sort", "glt.gather.feat"),
    ("jit(f)/jit(_threefry_split)/slice", None),
    ("jit(f)/glt./add", None),
    ("", None),
    (None, None),
])
def test_scope_of_reads_every_form_of_the_name_stack(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scope_map_of_a_hand_made_file(hand_file):
    smap = scopes.scope_map(hand_file)
    assert sorted(smap) == ["jit__gather_hot_impl(12)", "jit_step(11)"]
    step = {k: scopes.scope_of(v) for k, v in smap["jit_step(11)"].items()}
    # an empty op_name takes the commonest scope of the computation called
    assert step["fusion.1"] == "glt.model.agg"
    # ... its own scope where it has one, none where nothing has
    assert step["fusion.2"] == "glt.step.loss"
    assert step["fusion.3"] is None
    # called ids written one by one (not packed), several computations
    assert step["sort.4"] == "glt.sample.hop2"
    # control flow's callees are op events of their own
    assert step["while.1"] is None and step["copy.9"] is None
    assert step["gather.1"] == "glt.model.msg"
    assert smap["jit_step(11)"]["neg.6"] == "jit(f)/neg"
    # one instruction name, two modules, two scopes
    assert scopes.scope_of(smap["jit__gather_hot_impl(12)"]["fusion.1"]) \
        == "glt.gather.feat"


def test_a_stat_of_another_name_is_not_read_as_hlo(tmp_path):
    path = tmp_path / "other.xplane.pb"
    path.write_bytes(_xspace({"jit_step(11)": STEP_HLO},
                             hlo_stat="Something Else"))
    assert scopes.scope_map(str(path)) == {}


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A real trace file: a jitted gradient under two scopes, one
    program span and one benchmark span."""
    import jax
    import jax.numpy as jnp

    def f(x, idx):
        with jax.named_scope("glt.sample.hop1"):
            y = x[idx]
        with jax.named_scope("glt.model.agg"):
            z = jax.ops.segment_sum(y, idx, 16)
        return (z * z).sum()

    g = jax.jit(jax.grad(f))
    x, idx = jnp.ones((64, 8)), jnp.arange(32) % 16
    g(x, idx).block_until_ready()
    trace_dir = str(tmp_path_factory.mktemp("cpu_trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            with jax.profiler.TraceAnnotation("glt.loader.collate"):
                g(x, idx).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return tracered.find_xplane(trace_dir)


def test_scope_map_and_program_spans_of_a_recorded_trace(cpu_trace):
    smap = scopes.scope_map(cpu_trace)
    (module,) = [m for m in smap if m.startswith("jit_f(")]
    found = {scopes.scope_of(v) for v in smap[module].values()}
    assert {"glt.sample.hop1", "glt.model.agg"} <= found
    forms = " ".join(smap[module].values())
    assert "jvp(glt.sample.hop1)" in forms
    assert "transpose(jvp(glt.model.agg))" in forms
    spans = scopes.program_spans(cpu_trace)
    assert [s[0] for s in spans] == ["glt.loader.collate"]
    assert spans[0][2] > 0


def test_the_hand_decoder_agrees_with_the_generated_protobuf_classes(
        cpu_trace, hand_file):
    """The same map through ``xplane_pb2`` + ``hlo_pb2``, where
    TensorFlow imports: the shipped reader depends on neither."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    for path in (cpu_trace, hand_file):
        space = xplane_pb2.XSpace()
        with open(path, "rb") as fh:
            space.ParseFromString(fh.read())
        want = {}
        (plane,) = [p for p in space.planes
                    if p.name == scopes.METADATA_PLANE]
        hlo_ids = {k for k, v in plane.stat_metadata.items()
                   if v.name == scopes.HLO_STAT}
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if stat.metadata_id not in hlo_ids:
                    continue
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(stat.bytes_value)
                comps = {c.id: c for c in proto.hlo_module.computations}
                table = {}
                for comp in comps.values():
                    for ins in comp.instructions:
                        name = ins.metadata.op_name
                        if (ins.called_computation_ids
                                and ins.opcode not in ("while", "conditional",
                                                       "call")
                                and scopes.scope_of(name) is None):
                            inner = [scopes.scope_of(i.metadata.op_name)
                                     for cid in ins.called_computation_ids
                                     for i in comps[cid].instructions]
                            inner = [s for s in inner if s]
                            if inner:
                                name = max(inner, key=inner.count)
                        table[ins.name] = name
                want[meta.name] = table
        assert want and scopes.scope_map(path) == want


def test_a_file_that_cannot_be_read_gives_nothing_and_one_line(
        tmp_path, capsys):
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\x0a\xff\xff\x03abc")
    for path in (str(bad), str(tmp_path / "missing.xplane.pb")):
        assert scopes.scope_map(path) == {}
        assert scopes.program_spans(path) == []
        err = capsys.readouterr().err.strip().splitlines()
        assert len(err) == 2 and all("chipbench.scopes" in e for e in err)
    assert scopes.scoped_self_times(None, {}) == []


# -- exclusive time by scope --------------------------------------------------

def _hand_trace():
    """data/hand_trace.json, with the second module's op renamed so that
    both modules hold a ``fusion.1``."""
    with open(os.path.join(HERE, "data", "hand_trace.json")) as f:
        raw = json.load(f)
    dev = raw["devices"]["0"]
    assert dev["ops"][3][0] == "fusion.7"
    dev["ops"][3][0] = "fusion.1"
    dev["modules"] = [["jit_step(11)", 100, 400],
                      ["jit__gather_hot_impl(12)", 600, 300]]
    return tracered.normalise(raw)


def test_scoped_self_times_on_a_hand_made_trace(hand_file):
    trace = _hand_trace()
    smap = scopes.scope_map(hand_file)
    # while.1 100-500 holds fusion.1 100-300 and all_to_all.2 300-450:
    # its exclusive 50 ns carry no scope; the all-to-all is not in the
    # module's HLO at all; the second module's fusion.1 is the gather.
    assert scopes.scoped_self_times(trace, smap) == [
        (None, 50), ("glt.model.agg", 200), (None, 150),
        ("glt.gather.feat", 300)]
    # an op outside every module event has no module to be looked up in
    trace["devices"]["0"]["modules"] = trace["devices"]["0"]["modules"][:1]
    assert scopes.scoped_self_times(trace, smap)[-1] == (None, 300)
    # the window clips
    trace["window"] = [200, 700]
    assert scopes.scoped_self_times(trace, smap) == [
        (None, 50), ("glt.model.agg", 100), (None, 150), (None, 100)]


def test_scoped_and_unscoped_time_add_up_to_the_busy_time():
    """On the recorded chip cut (no two ops overlap but by nesting)."""
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        trace = tracered.normalise(json.load(f))
    dev = trace["devices"]["0"]
    step = next(m[0] for m in dev["modules"] if m[0].startswith("jit__step"))
    names = sorted({op[0] for op in dev["ops"]})
    smap = {step: {n: ("jit(s)/glt.model.agg/x", "jit(s)/glt.route.bucket/y",
                       "jit(s)/z")[i % 3] for i, n in enumerate(names)}}
    times = scopes.scoped_self_times(trace, smap)
    busy, _ = tracered.busy_and_gaps(dev["ops"], trace["window"])
    by = {}
    for scope, ns in times:
        by[scope] = by.get(scope, 0) + ns
    assert set(by) == {None, "glt.model.agg", "glt.route.bucket"}
    assert all(v > 0 for v in by.values())
    assert abs(sum(by.values()) - busy) / busy < 1e-6


def test_scopes_of_a_recorded_chip_trace():
    """One batch of ``sage-products.train-scan`` as the TPU v5e traced it
    (data/recorded_scoped_trace.json says how it was cut): every scope a
    one-chip step has is found through the module event's own name, the
    times add up, and the order of the stages is the one PERF.md gives."""
    with open(os.path.join(HERE, "data", "recorded_scoped_trace.json")) as f:
        rec = json.load(f)
    trace, smap = tracered.normalise(rec["trace"]), rec["scope_map"]
    dev = trace["devices"]["0"]
    assert [m[0] for m in dev["modules"]] == list(smap)
    by = {}
    for scope, ns in scopes.scoped_self_times(trace, smap):
        by[scope] = by.get(scope, 0) + ns
    assert set(by) == {
        None, "glt.sample.hop1", "glt.sample.hop2", "glt.sample.hop3",
        "glt.sample.induce", "glt.gather.feat", "glt.gather.label",
        "glt.model.msg", "glt.model.agg", "glt.model.dense",
        "glt.step.loss", "glt.step.update"}
    busy, _ = tracered.busy_and_gaps(dev["ops"], trace["window"])
    total = sum(by.values())
    assert abs(total - busy) / busy < 1e-9
    assert by[None] / total < 0.03
    order = sorted((k for k in by if k), key=lambda k: -by[k])
    assert order[:5] == ["glt.model.agg", "glt.model.msg",
                         "glt.sample.induce", "glt.model.dense",
                         "glt.sample.hop3"]
    assert 0.65 < (by["glt.model.agg"] + by["glt.model.msg"]) / total < 0.70
    # backward passes keep their scope: transpose(jvp(...)) name stacks
    names = " ".join(smap[dev["modules"][0][0]].values())
    assert "transpose(jvp(GraphSAGE))/conv1/glt.model.msg" in names
    assert [s[0] for s in rec["program_spans"]] == [
        "glt.train.scanned_epoch"]


# -- the reducers -------------------------------------------------------------

def _ctx(trace, steps=2):
    return {"trace": trace,
            "window": Window(attempted=steps, failed=0, metrics={},
                             steps=steps, counters={})}


def test_scope_ms_and_the_unscoped_share(hand_file, monkeypatch):
    monkeypatch.setattr(scopes, "traced_file", lambda: hand_file)
    ctx = _ctx(_hand_trace())
    assert scope_ms.read(ctx, {"scope_regex": r"^glt\.model\."}) \
        == pytest.approx(200 / 1e6 / 2)
    assert scope_ms.read(ctx, {"scope_regex": r"^glt\.(model|gather)\."}) \
        == pytest.approx(500 / 1e6 / 2)
    assert scope_ms.read(ctx, {"scope_regex": r"^glt\.route\."}) == 0.0
    assert scope_ms.read(ctx, {"unscoped_share": True}) \
        == pytest.approx(100.0 * 200 / 700)


@pytest.mark.parametrize("why", ["no scope in the trace", "unreadable file",
                                 "no trace file", "no device trace"])
def test_both_reducers_find_nothing_to_read(why, tmp_path, monkeypatch,
                                            cpu_trace):
    trace = _hand_trace()
    path = str(tmp_path / "x.xplane.pb")
    if why == "no scope in the trace":
        # programs compiled before the scopes existed, served by a cache
        with open(path, "wb") as f:
            f.write(_xspace({"jit_step(11)": _hlo([(1, [
                _instr("fusion.1", "fusion", "jit(f)/mul")])])}))
    elif why == "unreadable file":
        with open(path, "wb") as f:
            f.write(b"\x0a\xff\xff\x03abc")
    elif why == "no trace file":
        path = None
    else:
        trace, path = None, cpu_trace
    monkeypatch.setattr(scopes, "traced_file", lambda: path)
    ctx = _ctx(trace)
    assert scope_ms.read(ctx, {"scope_regex": "^glt"}) is None
    assert scope_ms.read(ctx, {"unscoped_share": True}) is None
    assert span_idle_ms.read(ctx, {"span": "glt.loader.collate"}) is None


def test_span_idle_ms_takes_the_intersection(cpu_trace, monkeypatch):
    gaps = [(0, 100), (500, 600), (900, 1000)]
    f = span_idle_ms.intersection_ns
    assert f(gaps, [(50, 550)]) == 100
    assert f(gaps, [(50, 550), (60, 70), (950, 2000)]) == 150  # nested
    assert f(gaps, [(100, 500), (600, 900)]) == 0
    assert f(gaps, [(0, 10), (20, 30), (25, 40), (2000, 3000)]) == 30
    assert f([], [(0, 10)]) == 0 and f(gaps, []) == 0
    # through the reducer, under a real program span: device ops cover
    # the middle half of it, so half its length is idle under it
    monkeypatch.setattr(scopes, "traced_file", lambda: cpu_trace)
    (name, start, dur), = scopes.program_spans(cpu_trace)
    quarter = dur / 4
    trace = tracered.normalise({
        "devices": {"0": {"ops": [["fusion.1", "fusion f32[8]",
                                   start + quarter, 2 * quarter]],
                          "async": [], "modules": []}},
        "host": [], "window": [start - 1000, start + dur + 1000]})
    assert span_idle_ms.read(_ctx(trace, steps=1), {"span": name}) \
        == pytest.approx(2 * quarter / 1e6)
    # attribute_gaps would give each whole gap (1000 ns more) to a span
    assert span_idle_ms.read(_ctx(trace), {"span": "glt.loader.none"}) \
        is None


def test_the_new_metrics_name_their_reducers_params():
    """Each ``layer_metrics`` file of this reader holds what its reducer
    takes, and its regex compiles against the taxonomy."""
    import re

    taxonomy = ["glt.sample.hop1", "glt.sample.hop3", "glt.sample.induce",
                "glt.gather.feat", "glt.gather.label", "glt.route.bucket",
                "glt.route.payload", "glt.route.exchange", "glt.model.msg",
                "glt.model.agg", "glt.model.dense", "glt.step.loss",
                "glt.step.update"]
    want = {"sample_hop_ms": 2, "sample_induce_ms": 1, "gather_scope_ms": 2,
            "model_device_ms": 5, "model_agg_ms": 2, "route_device_ms": 2}
    for metric, count in want.items():
        with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                               metric + ".json")) as f:
            spec = json.load(f)
        assert spec["reducer"] == "scope_ms"
        rx = re.compile(spec["params"]["scope_regex"])
        assert sum(bool(rx.search(s)) for s in taxonomy) == count, metric
    for metric, span in [("sample_dispatch_idle_ms", "sample_dispatch"),
                         ("overflow_wait_idle_ms", "overflow_wait"),
                         ("collate_idle_ms", "collate")]:
        with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                               metric + ".json")) as f:
            spec = json.load(f)
        assert spec == {"reducer": "span_idle_ms",
                        "params": {"span": "glt.loader." + span}}
