"""The readers of the live-count metrics (tier-1, CPU):
``registry_share`` and ``scope_ns_per_count`` on hand-made registries and
the hand-made trace of ``test_chipbench_scopes.py``, what they give on a
parent's registry (nothing), the metric files, and a traced ``tiny-*``
run that prints the three shares."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

from chipbench import scopes  # noqa: E402
from chipbench.common import Window  # noqa: E402
from chipbench.reducers import (registry_share,  # noqa: E402
                                scope_ns_per_count)

SHARES = ["sample_edge_live_share", "sample_last_frontier_live_share",
          "node_live_share"]
NEW = SHARES + ["sample_hop_ns_per_edge", "scan_stage_idle_ms",
                "scan_fetch_idle_ms"]


def _spec(metric):
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _helpers():
    """The protobuf writer and the hand-made trace of the scopes' tests."""
    spec = importlib.util.spec_from_file_location(
        "_scopes_tests", os.path.join(HERE, "test_chipbench_scopes.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _registry(batches):
    """A registry after ``batches`` batches of a three-hop sampler."""
    hops = {1: (10, 30, 10, 40), 2: (25, 60, 40, 120), 3: (45, 90, 120, 240)}
    live, slots = {"glt.sample.nodes": 120}, {"glt.sample.node_slots": 200,
                                              "glt.sample.batches": 1}
    for k, (nodes, edges, rows, edge_slots) in hops.items():
        live[f"glt.sample.frontier_nodes{{hop={k}}}"] = nodes
        live[f"glt.sample.edges{{hop={k}}}"] = edges
        slots[f"glt.sample.frontier_slots{{hop={k}}}"] = rows
        slots[f"glt.sample.edge_slots{{hop={k}}}"] = edge_slots
    out = {k: float(v * batches) for k, v in {**live, **slots}.items()}
    # what else lives under the prefix must not be read
    out["glt.sample.induce_sorted_slots{hop=3}"] = 999.0
    out["glt.loader.batches"] = float(batches)
    return out


def _ctx(before, after, trace=None, steps=2):
    return {"trace": trace, "registry": (before, after),
            "window": Window(attempted=steps, failed=0, metrics={},
                             steps=steps, counters={})}


def test_the_shares_are_the_windows_differences():
    ctx = _ctx(_registry(3), _registry(7))
    read = lambda m: registry_share.read(ctx, _spec(m)["params"])  # noqa: E731
    assert read("sample_edge_live_share") == pytest.approx(100 * 180 / 400)
    assert read("sample_last_frontier_live_share") == pytest.approx(
        100 * 45 / 120)
    assert read("node_live_share") == pytest.approx(100 * 120 / 200)
    # the largest label is found as a number, not as text
    after = _registry(7)
    after["glt.sample.frontier_nodes{hop=10}"] = 4.0 + 1
    after["glt.sample.frontier_slots{hop=10}"] = 4.0 + 10
    before = dict(_registry(3), **{
        "glt.sample.frontier_nodes{hop=10}": 4.0,
        "glt.sample.frontier_slots{hop=10}": 4.0})
    assert registry_share.read(
        _ctx(before, after),
        _spec("sample_last_frontier_live_share")["params"]) == 10.0


@pytest.mark.parametrize("before, after", [
    ({}, {}),                                       # registry off
    ({"glt.loader.batches": 1.0}, {"glt.loader.batches": 9.0}),  # a parent
    (_registry(4), _registry(4)),                   # nothing sampled
])
def test_a_registry_without_the_counters_gives_nothing(before, after):
    ctx = _ctx(before, after)
    for metric in SHARES:
        assert registry_share.read(ctx, _spec(metric)["params"]) is None
    assert scope_ns_per_count.read(
        ctx, _spec("sample_hop_ns_per_edge")["params"]) is None


def test_ns_per_edge_is_the_scopes_time_over_live_edges(tmp_path,
                                                        monkeypatch):
    h = _helpers()
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(h._xspace({"jit_step(11)": h.STEP_HLO,
                                "jit__gather_hot_impl(12)": h.GATHER_HLO}))
    monkeypatch.setattr(scopes, "traced_file", lambda: str(path))
    trace = h._hand_trace()
    ctx = _ctx(_registry(3), _registry(7), trace, steps=2)
    # 200 ns under glt.model.agg over 2 steps; 180 edges a batch
    params = {"scope_regex": r"^glt\.model\.agg", "counter":
              _spec("sample_hop_ns_per_edge")["params"]["counter"]}
    got = scope_ns_per_count.read(ctx, params)
    assert got == pytest.approx(200 / 2 / 180)
    # one time, two denominators: times the edges it is scope_ms again
    from chipbench.reducers import scope_ms

    ms = scope_ms.read(ctx, {"scope_regex": params["scope_regex"]})
    assert got * 180 == pytest.approx(ms * 1e6)
    # no device trace, or a parent's registry under a trace: nothing
    assert scope_ns_per_count.read(_ctx(_registry(3), _registry(7)),
                                   params) is None
    assert scope_ns_per_count.read(_ctx({}, {}, trace), params) is None
    assert _spec("sample_hop_ns_per_edge")["params"]["scope_regex"] == \
        _spec("sample_hop_ms")["params"]["scope_regex"]


def test_the_new_metrics_files_and_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
    scan = [w["name"] for w in bench["workloads"]
            if w["traffic"].endswith("train-scan")]
    assert len(scan) == 3
    for name in NEW:
        spec, entry = _spec(name), entries[name]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "reducers", spec["reducer"] + ".py"))
        assert entry["moves"] == "seeds_per_s"
        if name.startswith("scan_"):
            assert spec["reducer"] == "span_idle_ms"
            assert sorted(entry["workloads"]) == sorted(scan)
            assert entry["layer"] == "entry / epoch drivers"
        else:
            assert "workloads" not in entry and entry["layer"] == "sampler"
    assert _spec("scan_stage_idle_ms")["params"]["span"] == \
        "glt.train.seed_stage"
    assert _spec("scan_fetch_idle_ms")["params"]["span"] == \
        "glt.train.epoch_fetch"


@pytest.mark.parametrize("cell", ["tiny-sage.train-scan",
                                  "tiny-sage.loader"])
def test_a_traced_tiny_cell_prints_the_three_shares(cell, tmp_path):
    """The rehearsal list is a file the benchmark has, so the entries are
    appended to a copy of it, as a later PR would append a cell."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    os.symlink(os.path.join(ROOT, "glt_tpu"), os.path.join(root, "glt_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] in NEW]
    path = os.path.join(root, "chipbench", "rehearsal.json")
    with open(path) as f:
        reh = json.load(f)
    for m in entries:
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = ["tiny-sage.train-scan"]
        reh["per_layer"].append(m)
    with open(path, "w") as f:
        json.dump(reh, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", cell, "--seed", "3000000001", "--seconds", "1",
         "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    for name in SHARES:
        got = line["metrics"][name]
        assert got["unit"] == "%" and 0 < got["value"] <= 100, (name, got)
    # no device trace on a CPU: what reads one is left out, not zero
    for name in ("sample_hop_ns_per_edge", "scan_stage_idle_ms",
                 "scan_fetch_idle_ms"):
        assert name not in line["metrics"]
