"""Perf-regression harness (ISSUE 7 tentpole part 3).

Direction table, noise-tolerant thresholds, stuck-metric detection over
a five-file BENCH_r0*.json history generated under ``tmp_path`` (no
record is committed), and the ``scripts/bench_compare.py`` CLI.
Stdlib-only — no jax.
"""
import glob
import json
import os
import subprocess
import sys

import pytest

from glt_tpu.obs.regress import (
    DOWN,
    NEUTRAL,
    UP,
    compare,
    direction,
    load_bench_metrics,
    markdown_report,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestDirections:
    @pytest.mark.parametrize("metric,expected", [
        ("value", UP),
        ("gather_gb_s_dedup", UP),
        ("gather_roofline_frac", UP),
        ("memcpy_roofline_gb_s", UP),
        ("train_step_tflops_bf16", UP),
        ("batched_g8_m_edges_s", UP),
        ("subgraphs_per_s", UP),
        ("overlap_speedup", UP),
        ("cache_hit_rate", UP),
        ("sample_ms", DOWN),
        ("gather_xla_ms_d128", DOWN),
        ("dist_sample_ms_sort", DOWN),
        ("serialized_ms_per_batch", DOWN),
        ("epoch_s_config1_measured", DOWN),
        ("epoch_best", DOWN),
        ("obs_noop_ns_per_call", DOWN),
        ("obs_disabled_overhead_frac", DOWN),
        ("sampling_overhead_frac", DOWN),
        ("host_roundtrip_ms", NEUTRAL),
        ("node_cap_calibrated", NEUTRAL),
        ("occupancy_p99", NEUTRAL),
        ("serving_p99_ms", DOWN),
        ("serving_p50_ms", DOWN),
        ("serving_coalesce_speedup", UP),
        ("serving_rps_coalesced", UP),
        ("serving_overload_reject_frac", NEUTRAL),
    ])
    def test_direction_table(self, metric, expected):
        assert direction(metric) == expected

    def test_serving_aspirations_registered(self):
        from glt_tpu.obs.regress import ASPIRATIONS

        op, target = ASPIRATIONS["serving_coalesce_speedup"]
        assert op == ">=" and target >= 1.5
        op, target = ASPIRATIONS["serving_p99_ms"]
        assert op == "<="

    def test_device_telemetry_directions(self):
        # ISSUE 14: peak HBM is informational (shape-dependent), the
        # steady-state compile count tracks DOWN with a == 0 target.
        assert direction("hbm_peak_bytes") == NEUTRAL
        assert direction("hbm_bw_gb_s") == NEUTRAL
        assert direction("hbm_fraction_measured") == UP
        assert direction("compile_count_epoch") == DOWN
        from glt_tpu.obs.regress import ASPIRATIONS
        op, target = ASPIRATIONS["compile_count_epoch"]
        assert op == "<=" and target == 0.0


class TestCompare:
    def test_regression_flagged_beyond_threshold(self):
        runs = [("r1", {"step_ms": 50.0}), ("r2", {"step_ms": 50.5}),
                ("r3", {"step_ms": 49.8}), ("fresh", {"step_ms": 60.0})]
        rep = compare(runs)
        assert rep["verdict"] == "regress"
        assert rep["regressions"] == ["step_ms"]

    def test_improvement_flagged(self):
        runs = [("r1", {"x_gb_s": 10.0}), ("r2", {"x_gb_s": 10.2}),
                ("fresh", {"x_gb_s": 14.0})]
        rep = compare(runs)
        assert rep["improvements"] == ["x_gb_s"]
        assert rep["verdict"] == "improve"

    def test_noise_tolerance_suppresses_jitter(self):
        # History noise (MAD) wider than the latest delta: no verdict.
        runs = [("r1", {"step_ms": 50.0}), ("r2", {"step_ms": 58.0}),
                ("r3", {"step_ms": 44.0}), ("fresh", {"step_ms": 56.0})]
        rep = compare(runs)
        assert rep["verdict"] == "ok"
        assert rep["regressions"] == []

    def test_direction_awareness_ms_down_is_good(self):
        runs = [("r1", {"step_ms": 50.0, "x_gb_s": 10.0}),
                ("fresh", {"step_ms": 40.0, "x_gb_s": 8.0})]
        rep = compare(runs)
        assert "step_ms" in rep["improvements"]   # lower ms = better
        assert "x_gb_s" in rep["regressions"]       # lower gb/s = worse

    def test_neutral_metric_never_verdicted(self):
        runs = [("r1", {"host_roundtrip_ms": 10.0}),
                ("fresh", {"host_roundtrip_ms": 500.0})]
        rep = compare(runs)
        assert rep["verdict"] == "ok"
        (row,) = [r for r in rep["rows"]
                  if r["metric"] == "host_roundtrip_ms"]
        assert row["status"] == "info"

    def test_neutral_ceiling_hbm_peak(self):
        # NEUTRAL normally never verdicts, but a capacity ceiling is
        # absolute: peak HBM past the device limit is a regression no
        # matter which direction "better" points.
        from glt_tpu.obs.regress import CEILINGS

        cap = CEILINGS["hbm_peak_bytes"]
        assert cap == 16 * 2**30
        under = [("r1", {"hbm_peak_bytes": cap * 0.5}),
                 ("fresh", {"hbm_peak_bytes": cap * 0.9})]
        rep = compare(under)
        (row,) = [r for r in rep["rows"]
                  if r["metric"] == "hbm_peak_bytes"]
        assert row["status"] == "info"
        over = [("r1", {"hbm_peak_bytes": cap * 0.5}),
                ("fresh", {"hbm_peak_bytes": cap * 1.1})]
        rep = compare(over)
        (row,) = [r for r in rep["rows"]
                  if r["metric"] == "hbm_peak_bytes"]
        assert row["status"] == "regress"
        assert row["ceiling"] == cap
        assert "hbm_peak_bytes" in rep["regressions"]
        assert rep["verdict"] == "regress"

    def test_compile_count_flat_nonzero_is_stuck(self):
        # The <= 0 aspiration: a steady-state loop that keeps
        # compiling a little every epoch is flat AND unmet -> stuck.
        flat = [("r1", {"compile_count_epoch": 3.0}),
                ("r2", {"compile_count_epoch": 3.0}),
                ("fresh", {"compile_count_epoch": 3.0})]
        assert compare(flat)["stuck"] == ["compile_count_epoch"]
        met = [("r1", {"compile_count_epoch": 0.0}),
               ("r2", {"compile_count_epoch": 0.0}),
               ("fresh", {"compile_count_epoch": 0.0})]
        assert compare(met)["stuck"] == []

    def test_stuck_requires_flat_and_unmet_target(self):
        # best_step_ms carries the headline aspiration (<= 40 ms) the
        # retired overlap_speedup target used to exercise here.
        flat_unmet = [("r1", {"best_step_ms": 51.9}),
                      ("r2", {"best_step_ms": 52.3}),
                      ("fresh", {"best_step_ms": 51.7})]
        assert compare(flat_unmet)["stuck"] == ["best_step_ms"]
        met = [("r1", {"best_step_ms": 38.0}),
               ("r2", {"best_step_ms": 38.4}),
               ("fresh", {"best_step_ms": 37.9})]
        assert compare(met)["stuck"] == []

    def test_new_and_gone_metrics(self):
        runs = [("r1", {"old_ms": 5.0}),
                ("fresh", {"fresh_ms": 1.0})]
        rep = compare(runs)
        by = {r["metric"]: r["status"] for r in rep["rows"]}
        assert by["fresh_ms"] == "new"
        assert by["old_ms"] == "gone"

    def test_strings_skipped(self):
        runs = [("r1", {"gather_path": "dedup", "x_ms": 2.0}),
                ("fresh", {"gather_path": "naive", "x_ms": 2.0})]
        rep = compare(runs)
        assert all(r["metric"] != "gather_path" for r in rep["rows"])


def write_history(root) -> str:
    """Five made-up bench snapshots, ``BENCH_r01``..``r05.json``, in the
    three shapes :func:`load_bench_metrics` accepts.  The values are
    fixtures, not measurements: a flat ``overlap_speedup`` under 1 and a
    slowly improving ``gather_ms``."""
    rounds = [
        {"value": 10.0, "gather_ms": 90.0, "overlap_speedup": 0.966,
         "overlapped_step_ms": 60.0},
        {"value": 11.0, "gather_ms": 88.0, "overlap_speedup": 0.991,
         "overlapped_step_ms": 59.0},
        {"value": 12.0, "gather_ms": 85.0, "overlap_speedup": 0.975,
         "overlapped_step_ms": 58.5},
        {"value": 12.5, "gather_ms": 84.0, "overlap_speedup": 0.981,
         "overlapped_step_ms": 58.0},
        {"value": 13.0, "gather_ms": 81.0, "overlap_speedup": 0.978,
         "overlapped_step_ms": 57.5},
    ]
    for i, metrics in enumerate(rounds, start=1):
        metrics = {"metric": "fixture_throughput", **metrics}
        line = json.dumps(metrics)
        if i <= 2:      # driver wrapper with the parsed dict
            body = json.dumps({"n": i, "rc": 0, "parsed": metrics})
        elif i <= 4:    # driver wrapper with only the captured tail
            body = json.dumps({"n": i, "rc": 0,
                               "tail": f"some warning\n{line}\n"})
        else:           # raw GLT_BENCH_OUT line
            body = line + "\n"
        with open(os.path.join(str(root), f"BENCH_r{i:02d}.json"),
                  "w") as f:
            f.write(body)
    return os.path.join(str(root), "BENCH_r*.json")


class TestHistory:
    """Over a five-round history plus a fresh run, retired metrics show
    as gone and the trend table has one column per run."""

    def _history(self, tmp_path):
        runs = []
        for path in sorted(glob.glob(write_history(tmp_path))):
            metrics = load_bench_metrics(path)
            assert metrics is not None, path
            runs.append((os.path.basename(path), metrics))
        return runs

    def test_history_loads_all_five_rounds(self, tmp_path):
        runs = self._history(tmp_path)
        assert len(runs) == 5
        assert all("value" in m for _, m in runs)

    def test_overlap_speedup_retired_shows_gone(self, tmp_path):
        """The overlapped path was deleted (ISSUE 10c): a fresh run no
        longer emits overlap_speedup / overlapped_step_ms*, and the
        trend table must report those rows as ``gone`` — the retirement
        is visible, not silent — without flagging them stuck."""
        runs = self._history(tmp_path)
        fresh = {k: v for k, v in runs[-1][1].items()
                 if not k.startswith("overlap")}
        runs.append(("fresh", fresh))
        rep = compare(runs)
        by = {r["metric"]: r["status"] for r in rep["rows"]}
        assert by["overlap_speedup"] == "gone"
        assert by["overlapped_step_ms"] == "gone"
        assert "overlap_speedup" not in rep["stuck"]

    def test_markdown_trend_table(self, tmp_path):
        runs = self._history(tmp_path)
        runs.append(("fresh", dict(runs[-1][1])))
        md = markdown_report(compare(runs))
        assert "| `overlap_speedup` |" in md
        assert "Verdict" in md
        # one column per run + metric + delta + status
        header = [ln for ln in md.splitlines()
                  if ln.startswith("| metric")][0]
        assert header.count("|") == len(runs) + 4


class TestCLI:
    CLI = os.path.join(REPO, "scripts", "bench_compare.py")

    def test_bench_compare_cli_advisory(self, tmp_path):
        out_md = str(tmp_path / "report.md")
        out_json = str(tmp_path / "report.json")
        res = subprocess.run(
            [sys.executable, self.CLI,
             "--history", write_history(tmp_path),
             "--out", out_md, "--json", out_json],
            capture_output=True, text=True)
        assert res.returncode == 0, res.stderr
        assert "Bench trend report" in res.stdout
        assert os.path.exists(out_md)
        rep = json.load(open(out_json))
        assert rep["labels"][0] == "r01"
        assert any(r["metric"] == "overlap_speedup" for r in rep["rows"])

    def test_bench_compare_fresh_run_and_strict(self, tmp_path):
        # A fresh GLT_BENCH_OUT-style file (raw bench JSON line) with a
        # clear regression; --strict must exit 1.
        history = write_history(tmp_path)
        base = load_bench_metrics(str(tmp_path / "BENCH_r05.json"))
        fresh = dict(base)
        fresh["gather_ms"] = base["gather_ms"] * 3.0
        fpath = str(tmp_path / "fresh.json")
        with open(fpath, "w") as f:
            f.write(json.dumps(fresh) + "\n")
        res = subprocess.run(
            [sys.executable, self.CLI, "--history", history,
             "--fresh", fpath, "--strict"],
            capture_output=True, text=True)
        assert res.returncode == 1
        assert "`gather_ms`" in res.stdout

    def test_empty_history_is_not_an_error(self, tmp_path):
        """No record is committed: the default glob matches nothing, and
        the advisory CI job must read that as 'no history', exit 0."""
        res = subprocess.run(
            [sys.executable, self.CLI,
             "--history", str(tmp_path / "BENCH_r*.json")],
            capture_output=True, text=True)
        assert res.returncode == 0, res.stderr
        assert "no history" in res.stdout
        res = subprocess.run([sys.executable, self.CLI], cwd=REPO,
                             capture_output=True, text=True)
        assert res.returncode == 0, res.stderr
        assert "no history" in res.stdout
