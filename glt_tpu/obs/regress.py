"""Perf-regression tracking over a history of bench snapshots.

This module turns a ``BENCH_r*.json`` history plus an optional fresh
``bench.py`` run into a markdown trend table and a direction-aware
regress/improve verdict; ``scripts/bench_compare.py`` is the CLI and CI
(advisory job ``bench-compare``) runs it on every push.  No snapshot is
committed today (the driver's record is ``PERF_LEDGER.jsonl``), so the
default history is empty.

Three ideas, all deliberately simple and stdlib-only:

* **Direction awareness.**  ``*_ms`` down is good, ``*_gb_s`` /
  ``*_frac`` up is good; metrics with no inherent direction (capacity
  choices, occupancy counts, host round-trip latency) are tracked but never
  verdicted.  :func:`direction` resolves explicit names first, then
  suffix/infix conventions.

* **Noise tolerance.**  A metric regresses only when the latest value
  is worse than the history's median by more than
  ``max(rel_tol * |median|, noise_k * sigma)`` where ``sigma`` is a
  robust spread (MAD) of the prior rounds — one noisy round does not
  page anyone, a real step change does.

* **Stuck detection.**  Some metrics have a *target*, not just a
  direction (:data:`ASPIRATIONS`): ``best_step_ms`` must reach the
  train-bound ~40 ms for the gather-wall work to be done.  A metric
  that is flat across the recent rounds while failing its target is
  flagged ``stuck`` — the "nothing regressed, but nothing is getting
  better either" state a pure-delta check never reports.  (This is the
  mechanism that finally killed the overlapped path: three flat rounds
  of ``overlap_speedup`` 0.97–0.99 against a >= 1.05 target.)
"""
from __future__ import annotations

import json
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

UP = 1        # bigger is better
DOWN = -1     # smaller is better
NEUTRAL = 0   # tracked, never verdicted

#: Exact-name directions (override every convention below).
#: ``overlap_speedup`` is RETIRED (not merely unlisted): the overlapped
#: epoch driver was deleted after three rounds stuck at 0.97-0.99 — the
#: fused scanned route is the only epoch driver now, and ``best_step_ms``
#: below tracks the headline instead.  The metric will show as ``gone``
#: in trend tables spanning the deletion; that is the honest reading.
EXPLICIT_DIRECTIONS: Dict[str, int] = {
    "value": UP,
    "vs_baseline": UP,
    "vs_ref_cpu": UP,
    "best_step_ms": DOWN,
    "scanned_step_ms": DOWN,
    "dist_scanned_step_ms_tpu": DOWN,
    "cache_hit_rate": UP,
    "cache_hit_rate_cold": UP,
    "est_hbm_fraction": UP,
    "gather_roofline_frac": UP,
    # Per-stage attribution (ISSUE 13, glt_tpu/obs/attrib.py): every
    # stage's achieved fraction of the memcpy ceiling tracks UP — a
    # drop means that stage got further from the machine.
    "sample_roofline_frac": UP,
    "dedup_roofline_frac": UP,
    "train_roofline_frac": UP,
    # Sampling-wall A/B (ISSUE 15, ops/sample_pallas.py +
    # ops/fused_frontier.py): both sides of the neighbor-read kernel
    # race track DOWN (the _xla/_pallas endings dodge the _ms suffix
    # rule, so they are pinned here), each path's delivered fraction of
    # memcpy tracks UP, and the one-dispatch dedup+gather must beat (or
    # at least not lose ground to) its two-pass unfused twin.
    "sample_ms_xla": DOWN,
    "sample_ms_pallas": DOWN,
    "sample_roofline_frac_xla": UP,
    "sample_roofline_frac_pallas": UP,
    "fused_frontier_ms": DOWN,
    "fused_frontier_ms_unfused": DOWN,
    "scanned_fused_step_ms": DOWN,
    "obs_disabled_overhead_frac": DOWN,
    "sampling_overhead_frac": DOWN,
    "sampling_overhead_frac_epoch": DOWN,
    "ckpt_overhead_frac": DOWN,
    "ckpt_bytes": NEUTRAL,
    "overflow_rate": DOWN,
    "dist_routing_overhead": DOWN,
    "obs_noop_ns_per_call": DOWN,
    # Hierarchical ICI/DCN routing A/B (ISSUE 17, parallel/dist_sampler
    # HierarchicalRouting): both step timings track DOWN; the point of
    # the dedup-then-exchange plan is the cross-host byte count, so
    # dcn_bytes_hier tracks DOWN while the flat reference is a workload
    # reading (NEUTRAL), and the measured zipf-frontier dedup factor
    # (flat request slots / host-unique DCN slots) tracks UP.
    "dist_flat_step_ms": DOWN,
    "dist_hier_step_ms": DOWN,
    "dcn_bytes_flat": NEUTRAL,
    "dcn_bytes_hier": DOWN,
    "hier_dedup_factor": UP,
    # Serving SLO metrics (benchmarks/bench_serving.py, docs/serving.md):
    # latency quantiles down-good, the coalescing win up-good.
    "serving_p50_ms": DOWN,
    "serving_p99_ms": DOWN,
    "serving_p99_light_ms": DOWN,
    "serving_single_ms": DOWN,
    "serving_coalesce_speedup": UP,
    "serving_rps_coalesced": UP,
    "serving_rps_per_request": NEUTRAL,
    "serving_overload_reject_frac": NEUTRAL,
    "serving_offered_rps": NEUTRAL,
    # Disk feature tier (benchmarks/bench_cold_tier.py, docs/storage.md):
    # DRAM residency should absorb traffic (hit rate up-good); epoch
    # wall time down-good; raw tier byte counts are workload readings.
    "dram_hit_rate": UP,
    "store_epoch_ms": DOWN,
    "disk_bytes_per_epoch": NEUTRAL,
    "bytes_from_dram": NEUTRAL,
    "bytes_from_disk": NEUTRAL,
    "bytes_from_hbm": NEUTRAL,
    "store_budget_bytes": NEUTRAL,
    # Device telemetry (ISSUE 14, glt_tpu/obs/device.py +
    # compilewatch.py): measured peak HBM use is a workload property
    # (NEUTRAL) but bounded by CEILINGS below; steady-state epochs must
    # recompile ZERO programs, so the per-epoch compile count tracks
    # DOWN with a <= 0 aspiration.
    "hbm_peak_bytes": NEUTRAL,
    "hbm_bw_gb_s": NEUTRAL,
    "hbm_fraction_measured": UP,
    "compile_count_epoch": DOWN,
    # Environment / configuration readings — not better or worse.
    "host_roundtrip_ms": NEUTRAL,
    "dedup_ratio": NEUTRAL,
    "cap_fraction": NEUTRAL,
    "occupancy_p50": NEUTRAL,
    "occupancy_p99": NEUTRAL,
    "node_cap_full": NEUTRAL,
    "node_cap_calibrated": NEUTRAL,
    "cache_capacity_rows": NEUTRAL,
    "epoch_batches": NEUTRAL,
    "scanned_group": NEUTRAL,
    # Compressed tiers + whole-graph refresh (ISSUE 18,
    # benchmarks/bench_cold_tier.py, docs/refresh.md): refresh
    # throughput up-good (the `_per_s` suffix would catch it, pinned
    # for the table's sake); tier byte counts are workload readings;
    # staging errors must be zero, so any count tracks DOWN.  The
    # per-codec effective gather bandwidths (`gather_gb_s_effective_*`,
    # logical f32 bytes per second) resolve UP via the `_gb_s_` infix.
    "refresh_nodes_per_s": UP,
    "refresh_bytes_from_hbm": NEUTRAL,
    "refresh_bytes_from_dram": NEUTRAL,
    "refresh_bytes_from_disk": NEUTRAL,
    "refresh_stage_errors": DOWN,
    "gather_effective_speedup_bf16": UP,
    "gather_effective_speedup_int8": UP,
    # Fleet routing + failover (ISSUE 19, benchmarks/bench_fleet.py,
    # docs/serving.md "Fleet"): affinity hit rate up-good and random is
    # its A/B control (a workload reading); the kill-recovery tail and
    # re-convergence time down-good; the structured-reject fraction is
    # a policy reading, but ANY unstructured error is a bug, so that
    # count tracks DOWN (and the bench asserts it is zero).
    "fleet_affinity_hit_rate": UP,
    "fleet_random_hit_rate": NEUTRAL,
    "fleet_affinity_gain": UP,
    "fleet_p99_ms": DOWN,
    "fleet_recovery_s": DOWN,
    "fleet_structured_reject_frac": NEUTRAL,
    "fleet_unstructured_errors": DOWN,
    "fleet_hit_rate_reconverged": UP,
    "fleet_replica_kills": NEUTRAL,
}

#: ``(suffix, direction)`` checked in order after the explicit table.
_SUFFIX_DIRECTIONS: Tuple[Tuple[str, int], ...] = (
    ("_gb_s", UP),
    ("_m_edges_s", UP),
    ("_edges_s", UP),
    ("_tflops", UP),
    ("_per_s", UP),
    ("_speedup", UP),
    ("_frac", UP),
    ("_ms", DOWN),
    ("_ms_per_batch", DOWN),
)

#: ``(infix, direction)`` for width/variant-suffixed families
#: (``gather_gb_s_naive``, ``gather_xla_ms_d128``, ``epoch_s_config1``).
_INFIX_DIRECTIONS: Tuple[Tuple[str, int], ...] = (
    ("_gb_s_", UP),
    ("tflops", UP),
    ("_ms_", DOWN),
    ("epoch_s_", DOWN),
    ("epoch_best", DOWN),
)

#: Metric targets: flat-while-unmet => ``stuck``.  The roofline
#: fraction is ROADMAP item 1's success metric (~within 2x of memcpy);
#: ``best_step_ms`` is its headline (train-bound means <= ~40 ms at the
#: r05 train_ms of 34.8).  The former ``overlap_speedup >= 1.05``
#: aspiration is retired with its path (see EXPLICIT_DIRECTIONS note).
ASPIRATIONS: Dict[str, Tuple[str, float]] = {
    "best_step_ms": ("<=", 40.0),
    "gather_roofline_frac": (">=", 0.5),
    # Preemption-safety must stay ~free at cadence N=50 (ISSUE 8's
    # acceptance bar; benchmarks/bench_resume.py emits the reading).
    "ckpt_overhead_frac": ("<=", 0.05),
    # Serving acceptance bars (ISSUE 9): coalesced dispatch must beat
    # per-request dispatch by >1.5x at saturating load, and the loaded
    # p99 should stay interactive (tracked so a flat miss flags stuck).
    "serving_coalesce_speedup": (">=", 1.5),
    "serving_p99_ms": ("<=", 50.0),
    # Disk tier (ISSUE 12): the warmed stager must absorb at least half
    # of cold traffic in DRAM on the skewed bench workload.
    "dram_hit_rate": (">=", 0.5),
    # Runtime recompile telemetry (ISSUE 14): a steady-state fused
    # epoch compiles nothing — any flat nonzero count is stuck.
    "compile_count_epoch": ("<=", 0.0),
    # Sampling wall (ISSUE 15): the degree-binned kernel should deliver
    # at least 30% of memcpy on the sample stage's expected-bytes floor
    # — flat below that is stuck, exactly like the gather bar above.
    "sample_roofline_frac_pallas": (">=", 0.3),
    # Hierarchical routing (ISSUE 17): the zipf-skewed bench frontier
    # should collapse at least 1.5x of its flat request slots into
    # host-unique DCN slots — flat below that means the per-host dedup
    # is not earning its extra ICI hop.
    "hier_dedup_factor": (">=", 1.5),
    # Compressed tiers (ISSUE 18): int8 rows move 4x fewer wire bytes,
    # so the effective (logical-f32) gather bandwidth should reach at
    # least 2x the raw arm on the same workload — flat below that means
    # the dequant epilogue is eating the transfer win.
    "gather_effective_speedup_int8": (">=", 2.0),
}

#: NEUTRAL-with-ceiling: metrics with no better/worse direction that
#: must still stay under a hard bound.  A NEUTRAL metric normally
#: short-circuits to ``info``; exceeding its ceiling verdicts
#: ``regress`` instead (measured peak HBM use is a workload reading —
#: until it stops fitting the chip).
CEILINGS: Dict[str, float] = {
    "hbm_peak_bytes": 16 * 2**30,     # v5e HBM capacity
}


def direction(metric: str) -> int:
    if metric in EXPLICIT_DIRECTIONS:
        return EXPLICIT_DIRECTIONS[metric]
    for suffix, d in _SUFFIX_DIRECTIONS:
        if metric.endswith(suffix):
            return d
    for infix, d in _INFIX_DIRECTIONS:
        if infix in metric:
            return d
    return NEUTRAL


def load_bench_metrics(path: str) -> Optional[Dict[str, Any]]:
    """The metrics dict of one bench snapshot, or None if unparseable.

    Accepts three shapes: the driver wrapper (``{"parsed": {...}}`` or
    ``{"tail": "...<one JSON line>..."}``), and a raw ``bench.py``
    output line / JSON object (``{"metric": ..., "value": ...}``) as
    written by ``GLT_BENCH_OUT``.
    """
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return None
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict):
        if isinstance(obj.get("parsed"), dict):
            return obj["parsed"]
        if "metric" in obj or "value" in obj:
            return obj
        text = obj.get("tail", "")
    # Fall back to the last parseable JSON line (bench stdout capture).
    for line in reversed(text.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, dict):
            return parsed
    return None


def _aspiration_met(metric: str, value: float) -> Optional[bool]:
    asp = ASPIRATIONS.get(metric)
    if asp is None:
        return None
    op, target = asp
    return value >= target if op == ">=" else value <= target


def compare(
    runs: Sequence[Tuple[str, Dict[str, Any]]],
    rel_tol: float = 0.05,
    noise_k: float = 3.0,
    flat_tol: float = 0.05,
    flat_window: int = 3,
) -> Dict[str, Any]:
    """Trend + verdict over ``[(label, metrics), ...]`` (oldest first,
    the last run is the one under judgment — typically a fresh bench).

    Returns ``{"labels", "rows", "regressions", "improvements",
    "stuck", "verdict"}``; each row carries the per-run values, the
    baseline (median of prior rounds), the direction-adjusted relative
    delta, and a status in ``regress / improve / stuck / ok / flat /
    new / gone / info``.
    """
    if len(runs) < 2:
        raise ValueError("need at least two runs to compare")
    labels = [label for label, _ in runs]
    ordered: List[str] = []
    seen = set()
    for _, metrics in reversed(runs):      # latest run's order wins
        for k in metrics:
            if k not in seen:
                seen.add(k)
                ordered.append(k)

    rows: List[Dict[str, Any]] = []
    regressions: List[str] = []
    improvements: List[str] = []
    stuck: List[str] = []
    for metric in ordered:
        values: List[Optional[float]] = []
        for _, metrics in runs:
            v = metrics.get(metric)
            values.append(float(v)
                          if isinstance(v, (int, float))
                          and not isinstance(v, bool) else None)
        if all(v is None for v in values):
            continue                        # string metric (paths, units)
        d = direction(metric)
        latest = values[-1]
        prior = [v for v in values[:-1] if v is not None]
        row: Dict[str, Any] = {"metric": metric, "values": values,
                               "direction": d, "baseline": None,
                               "rel_delta": None}
        if latest is None:
            row["status"] = "gone"
            rows.append(row)
            continue
        if not prior:
            row["status"] = "new"
            rows.append(row)
            continue
        baseline = statistics.median(prior)
        row["baseline"] = baseline
        delta = latest - baseline
        rel = delta / abs(baseline) if baseline else (0.0 if not delta
                                                      else float("inf"))
        row["rel_delta"] = rel
        if d == NEUTRAL:
            ceiling = CEILINGS.get(metric)
            if ceiling is not None and latest > ceiling:
                row["status"] = "regress"
                row["ceiling"] = ceiling
                regressions.append(metric)
            else:
                row["status"] = "info"
            rows.append(row)
            continue
        # Robust spread of the history: MAD scaled to sigma.
        if len(prior) >= 2:
            mad = statistics.median(abs(v - baseline) for v in prior)
            sigma = 1.4826 * mad
        else:
            sigma = 0.0
        threshold = max(rel_tol * abs(baseline), noise_k * sigma)
        status = "ok"
        if abs(delta) > threshold:
            status = "improve" if delta * d > 0 else "regress"
        # Stuck: flat over the recent window while missing the target.
        met = _aspiration_met(metric, latest)
        if met is False and status in ("ok", "regress"):
            recent = [v for v in values[-flat_window:] if v is not None]
            if len(recent) >= flat_window:
                center = statistics.median(recent)
                spread = max(recent) - min(recent)
                if abs(center) > 0 and spread <= flat_tol * abs(center):
                    status = "stuck"
        row["status"] = status
        if status == "regress":
            regressions.append(metric)
        elif status == "improve":
            improvements.append(metric)
        elif status == "stuck":
            stuck.append(metric)
        rows.append(row)

    verdict = ("regress" if regressions
               else "improve" if improvements else "ok")
    return {"labels": labels, "rows": rows, "regressions": regressions,
            "improvements": improvements, "stuck": stuck,
            "verdict": verdict}


_STATUS_MARK = {"regress": "🔴 regress", "improve": "🟢 improve",
                "stuck": "🟡 stuck", "ok": "ok", "flat": "ok",
                "new": "new", "gone": "gone", "info": "·"}


def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "—"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.4g}"


def markdown_report(report: Dict[str, Any]) -> str:
    """The trend table + verdict as CI-artifact markdown."""
    labels = report["labels"]
    lines = ["# Bench trend report", ""]
    lines.append(f"**Verdict: {report['verdict']}** — "
                 f"{len(report['regressions'])} regressed, "
                 f"{len(report['improvements'])} improved, "
                 f"{len(report['stuck'])} stuck "
                 f"(latest run: `{labels[-1]}`).")
    lines.append("")
    for kind, names in (("Regressions", report["regressions"]),
                        ("Improvements", report["improvements"]),
                        ("Stuck (flat while missing target)",
                         report["stuck"])):
        if names:
            lines.append(f"**{kind}:** " + ", ".join(
                f"`{n}`" for n in names))
            lines.append("")
    header = ["metric"] + labels + ["Δ vs median", "status"]
    lines.append("| " + " | ".join(header) + " |")
    lines.append("|" + "|".join(["---"] * len(header)) + "|")
    for row in report["rows"]:
        rel = row["rel_delta"]
        rel_s = "—" if rel is None else f"{rel:+.1%}"
        cells = ([f"`{row['metric']}`"]
                 + [_fmt(v) for v in row["values"]]
                 + [rel_s, _STATUS_MARK.get(row["status"],
                                            row["status"])])
        lines.append("| " + " | ".join(cells) + " |")
    lines.append("")
    lines.append("Directions: `*_ms` down-good, `*_gb_s`/`*_frac`/"
                 "throughput up-good; `·` rows are tracked but "
                 "directionless.  Thresholds are noise-tolerant "
                 "(median ± max(rel_tol, 3·MAD)); see "
                 "`glt_tpu/obs/regress.py`.")
    return "\n".join(lines)
