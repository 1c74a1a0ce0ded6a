"""``chipbench/run.py`` end to end at ``tiny-*`` size on the CPU: the last
line of standard output has exactly the contract's keys, and a cell, a
traffic mix and a per-layer metric can each be added as new files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CELLS = ["tiny-sage.train-scan", "tiny-sage.loader", "tiny-sage.serve-ego",
         "tiny-sage-dist4.dist-train"]


def _run(root, workload, trace, seconds="1"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", workload, "--seed", "1", "--seconds", seconds,
         "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    return proc


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_cell_prints_the_contracts_last_line(cell):
    line = _last_line(_run(ROOT, cell, trace=1))
    assert sorted(line) == ["attempted", "correct", "device", "failed",
                            "metrics"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # On a CPU only counts are reported, never a device metric.
    compiles = [v for k, v in line["metrics"].items()
                if k.endswith("compiles_in_window")]
    assert compiles and compiles[0] == {"value": 0.0, "unit": "count"}
    assert "device_idle_share" not in line["metrics"]
    for v in line["metrics"].values():
        assert sorted(v) == ["unit", "value"]


def test_untraced_cpu_run_reports_no_speed():
    line = _last_line(_run(ROOT, "tiny-sage.loader", trace=0))
    assert line["correct"] is True and line["metrics"] == {}


def test_a_real_cell_refuses_a_machine_without_its_chips():
    proc = _run(ROOT, "sage-products.train-scan", trace=0)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def _snapshot(root):
    out = {}
    for base in ("BENCHMARK.json", "chipbench"):
        path = os.path.join(root, base)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if "__pycache__" not in d and ".trace" not in d]
        for f in files:
            with open(f, "rb") as fh:
                out[os.path.relpath(f, root)] = fh.read()
    return out


def test_a_cell_a_mix_and_a_metric_are_added_as_new_files_only(tmp_path):
    """The harness is driven by data: a later PR adds entries to the
    lists and files beside the old ones, and edits no file that is
    there."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    os.symlink(os.path.join(ROOT, "glt_tpu"), os.path.join(root, "glt_tpu"))
    before = _snapshot(root)

    bench_dir = os.path.join(root, "chipbench")
    with open(os.path.join(bench_dir, "traffic", "big-batches.json"),
              "w") as f:
        json.dump({"driver": "loader", "loop": "closed",
                   "trace_seconds": 1}, f)
    with open(os.path.join(bench_dir, "configs", "tiny-sage-wide.json"),
              "w") as f:
        cfg = json.loads(before["chipbench/configs/tiny-sage.json"])
        cfg["name"] = "tiny-sage-wide"
        cfg["sampling"]["batch_size"] = 48
        json.dump(cfg, f)
    with open(os.path.join(bench_dir, "layer_metrics",
                           "batches_per_overflow.json"), "w") as f:
        json.dump({"reducer": "overflow_rate", "params": {"scale": 2.0}}, f)
    with open(os.path.join(bench_dir, "reducers", "overflow_rate.py"),
              "w") as f:
        f.write("def read(ctx, params):\n"
                "    w = ctx['window']\n"
                "    return params['scale'] * w.steps "
                "/ (1 + w.counters['overflow_replayed'])\n")
    # Entries are added to the lists; none that is there changes.
    path = os.path.join(bench_dir, "rehearsal.json")
    reh = json.loads(before["chipbench/rehearsal.json"])
    reh["configs"].append({"name": "tiny-sage-wide", "source": "test",
                           "file": "chipbench/configs/tiny-sage-wide.json",
                           "reduced": [], "why": "test"})
    reh["workloads"].append({"name": "tiny-sage-wide.big-batches",
                             "config": "tiny-sage-wide",
                             "traffic": "big-batches", "chips": 1,
                             "why": "test"})
    reh["per_layer"].append({"name": "batches_per_overflow",
                             "unit": "batches", "better": "higher",
                             "source": "program_counter",
                             "layer": "sampler", "moves": "seeds_per_s",
                             "workloads": ["tiny-sage-wide.big-batches"]})
    with open(path, "w") as f:
        json.dump(reh, f)

    line = _last_line(_run(root, "tiny-sage-wide.big-batches", trace=1))
    assert line["correct"] is True
    got = line["metrics"]["batches_per_overflow"]
    assert got == {"value": 2.0 * line["attempted"], "unit": "batches"}

    after = _snapshot(root)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"chipbench/rehearsal.json"}
    old = json.loads(before["chipbench/rehearsal.json"])
    for key, entries in old.items():       # the old entries, untouched
        assert reh[key][: len(entries)] == entries
