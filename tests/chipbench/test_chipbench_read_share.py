"""``sample_read_live_share`` (PR 35; tier-1, CPU): the frontier's live
rows over the rows the hop's read issued, through the ``registry_share``
reducer that PR 34 brought. On hand-made registries, on a parent's
registry (no ``glt.sample.read_rows``: nothing, and nothing raised), the
metric's file and entry, and a traced ``tiny-*`` run that prints it
beside the share over the static slots."""
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

READ = "sample_read_live_share"
STATIC = "sample_last_frontier_live_share"


def _spec(metric):
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _registry(batches, read_rows=True):
    """A registry after ``batches`` batches of a three-hop sampler whose
    reads run whole chunks of 32 rows over the live prefix (hop 1 is one
    chunk: it reads its static width)."""
    hops = {1: (10, 10, 10), 2: (25, 40, 32), 3: (45, 120, 64)}
    out = {"glt.sample.batches": float(batches)}
    for k, (nodes, slots, read) in hops.items():
        out[f"glt.sample.frontier_nodes{{hop={k}}}"] = float(nodes * batches)
        out[f"glt.sample.frontier_slots{{hop={k}}}"] = float(slots * batches)
        if read_rows:
            out[f"glt.sample.read_rows{{hop={k}}}"] = float(read * batches)
    return out


def _ctx(before, after):
    return {"trace": None, "registry": (before, after),
            "window": Window(attempted=2, failed=0, metrics={}, steps=2,
                             counters={})}


def _read(ctx, metric):
    return registry_share.read(ctx, _spec(metric)["params"])


def test_the_share_is_the_last_hops_live_rows_over_the_rows_read():
    ctx = _ctx(_registry(3), _registry(7))
    assert _read(ctx, READ) == pytest.approx(100 * 45 / 64)
    # the share over the static slots beside it does not move
    assert _read(ctx, STATIC) == pytest.approx(100 * 45 / 120)


@pytest.mark.parametrize("before, after", [
    ({}, {}),                                       # registry off
    ({"glt.loader.batches": 1.0}, {"glt.loader.batches": 9.0}),
    (_registry(4), _registry(4)),                   # nothing sampled
])
def test_a_registry_without_the_counter_gives_nothing(before, after):
    assert _read(_ctx(before, after), READ) is None


def test_pr_34s_registry_gives_its_own_share_and_no_read_share():
    """The parent counts frontiers and slots and no ``read_rows``: under
    this PR's benchmark files its line keeps its share and leaves the new
    one out, and nothing raises."""
    ctx = _ctx(_registry(3, read_rows=False), _registry(7, read_rows=False))
    assert _read(ctx, READ) is None
    assert _read(ctx, STATIC) == pytest.approx(100 * 45 / 120)


def test_the_metrics_file_and_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    entries = {m["name"]: m for m in per_layer}
    assert per_layer[-1]["name"] == READ            # appended, at the end
    # every cell reports it, as the share over the static slots
    assert entries[READ] == dict(entries[STATIC], name=READ)
    assert _spec(READ) == {"reducer": "registry_share", "params": dict(
        _spec(STATIC)["params"], denominator=r"^glt\.sample\.read_rows\{")}


@pytest.mark.parametrize("cell", ["tiny-sage.train-scan",
                                  "tiny-sage.loader"])
def test_a_traced_tiny_cell_prints_the_read_share(cell, tmp_path):
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
                   if m["name"] in (READ, STATIC)]
    path = os.path.join(root, "chipbench", "rehearsal.json")
    with open(path) as f:
        reh = json.load(f)
    reh["per_layer"].extend(entries)
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
    got = line["metrics"][READ]
    assert got["unit"] == "%" and 0 < got["value"] <= 100, got
    # every tiny read is at most one chunk: it reads its static width
    assert got["value"] == pytest.approx(line["metrics"][STATIC]["value"])
