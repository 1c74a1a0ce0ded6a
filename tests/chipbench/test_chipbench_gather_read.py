"""``dist_gather_read_live_share`` (tier-1, CPU): the request slots the
dist step's served feature read found a node in, over the slots it
visited, through the ``registry_share`` reducer.  On hand-made
registries, on a parent's registry (no ``glt.gather.*`` counters:
nothing, and nothing raised), the metric's file and entry found by name,
and a traced ``tiny-sage-dist4.dist-train`` run that prints it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.common import Window  # noqa: E402
from chipbench.reducers import registry_share  # noqa: E402

NAME = "dist_gather_read_live_share"
CELL = "sage-papers100m-dist4.dist-train"


def _spec():
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           NAME + ".json")) as f:
        return json.load(f)


def _registry(steps, gather=True):
    """A registry after ``steps`` dist steps of four shards, each serving
    93 live slots a step in reads of 128 slots."""
    out = {"glt.sample.batches": 4.0 * steps,
           "glt.sample.nodes": 370.0 * steps}
    if gather:
        out["glt.gather.served_rows"] = 4 * 93.0 * steps
        out["glt.gather.read_rows"] = 4 * 128.0 * steps
    return out


def _ctx(before, after):
    return {"trace": None, "registry": (before, after),
            "window": Window(attempted=2, failed=0, metrics={}, steps=2,
                             counters={})}


def _read(ctx):
    return registry_share.read(ctx, _spec()["params"])


def test_the_share_is_the_served_slots_over_the_slots_read():
    assert _read(_ctx(_registry(3), _registry(7))) == \
        pytest.approx(100 * 93 / 128)


@pytest.mark.parametrize("before, after", [
    ({}, {}),                                       # registry off
    (_registry(2, gather=False), _registry(5, gather=False)),   # a parent
    (_registry(4), _registry(4)),                   # no step in the window
])
def test_a_registry_without_the_counters_gives_nothing(before, after):
    assert _read(_ctx(before, after)) is None


def test_the_metrics_file_and_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "feature gather",
        "moves": "seeds_per_s", "workloads": [CELL]}
    assert entries["gather_scope_ms"]["layer"] == "feature gather"
    assert CELL in {w["name"] for w in bench["workloads"]}
    assert _spec() == {"reducer": "registry_share", "params": {
        "numerator": r"^glt\.gather\.served_rows",
        "denominator": r"^glt\.gather\.read_rows"}}


def test_a_traced_tiny_dist_cell_prints_the_share(tmp_path):
    """The rehearsal list is a file the benchmark has, so the entry is
    appended to a copy of it, as a later PR would append a cell."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    os.symlink(os.path.join(ROOT, "glt_tpu"), os.path.join(root, "glt_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == NAME)
    path = os.path.join(root, "chipbench", "rehearsal.json")
    with open(path) as f:
        reh = json.load(f)
    reh["per_layer"].append(dict(entry,
                                 workloads=["tiny-sage-dist4.dist-train"]))
    with open(path, "w") as f:
        json.dump(reh, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", "tiny-sage-dist4.dist-train", "--seed", "3000000001",
         "--seconds", "1", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = line["metrics"][NAME]
    # 4 x 656 request slots a shard: wider than one chunk of 2,560, so the
    # read visits the live chunks, and none is all padding at this size
    assert got["unit"] == "%" and 0 < got["value"] <= 100, got
