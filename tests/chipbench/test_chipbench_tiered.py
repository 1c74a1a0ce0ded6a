"""The tiered cell's own benchmark code at ``tiny-sage-tiered`` size on
the CPU: the data set made tier by tier against the plain reference, the
driver end to end (``correct`` true, and false under each of three
faults), an overflowing batch replayed under its own layout, the readers
of the ``tier_*`` metrics, the calibration, and the files of
``sage-papers100m-tiered-chip1.train-eager``."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import (checks, data, data_tiered,  # noqa: E402
                       reference_tiered)
from chipbench.common import Env, Window  # noqa: E402

CELL = "sage-papers100m-tiered-chip1.train-eager"
TIER_METRICS = ["tier_cold_row_share", "tier_ids_wait_idle_ms",
                "tier_cold_fetch_idle_ms", "tier_cold_put_idle_ms",
                "tier_merge_ms", "tier_hot_gather_roofline",
                "tier_device_busy_ms", "tier_sample_hop_ms",
                "tier_sample_induce_ms", "tier_model_device_ms",
                "tier_model_agg_ms", "tier_unscoped_share"]


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def _drive(cfg, trace, seconds=1.0):
    import jax

    from glt_tpu.obs import compilewatch

    traffic = _json("chipbench", "traffic", "train-eager.json")
    compilewatch.install()
    env = Env(cfg, traffic, 2 ** 31 + 5, jax.devices()[:1], trace,
              lambda msg: None)
    driver = importlib.import_module(
        "chipbench.drivers." + traffic["driver"]).Driver(env)
    before = compilewatch.total_compiles()
    win = driver.window(seconds)
    assert compilewatch.total_compiles() == before, \
        "a program compiled inside the window"
    return traffic, driver, win


def _correct(driver):
    """``run.py``'s verdict on the comparison: the check's detail, or the
    failure that makes ``correct`` false."""
    try:
        return True, driver.check()
    except checks.CheckFailure as e:
        return False, str(e)


@pytest.fixture(scope="module")
def driven():
    """The rehearsal cell ``tiny-sage-tiered.train-eager`` as ``run.py``
    runs a traced cell: driver, window, registry snapshots around it."""
    from glt_tpu.obs import metrics as registry

    cfg = _json("chipbench", "configs", "tiny-sage-tiered.json")
    registry.enable()
    try:
        before = registry.snapshot()
        traffic, driver, win = _drive(cfg, trace=True)
        after = registry.snapshot()
        yield cfg, traffic, driver, win, before, after
        driver.close()
    finally:
        registry.disable()
        registry.reset()


def test_tiny_cell_runs_the_driver_end_to_end_and_is_correct(driven):
    cfg, traffic, driver, win, before, after = driven
    assert win.attempted == win.steps > 10 and win.failed == 0
    assert win.metrics["seeds_per_s"] > 0
    ok, detail = _correct(driver)
    assert ok, detail
    assert detail["logits_err"] < cfg["check"]["logits_rtol"]
    assert detail["checked_hot_rows"] > 0 < detail["checked_cold_rows"]
    c = win.counters
    assert after["glt.feature.hot_count"] == 2000
    assert after["glt.feature.cold_rows"] > before.get(
        "glt.feature.cold_rows", 0) + c["cold_rows_per_step"] * win.steps - 1
    assert 0 < c["cold_row_share"] < 100
    assert c["cold_row_share"] == pytest.approx(
        100.0 * c["cold_rows_per_step"]
        / (c["hot_rows_per_step"] + c["cold_rows_per_step"]))
    # some batches took a second round: more slots sent than one width
    assert win.counters["cold_rows_sent_per_step"] > \
        cfg["tiering"]["cold_width"]
    assert win.counters["cold_rows_per_step"] < \
        win.counters["cold_rows_sent_per_step"]


def test_cpu_run_reads_the_counter_metric_and_leaves_the_rest_out(driven):
    from chipbench import run

    cfg, traffic, driver, win, before, after = driven
    bench = _json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == TIER_METRICS
    # On a CPU there is no device trace: the counter is read, the device
    # metrics are left out, nothing raises.
    ctx = {"trace": None, "window": win, "config": cfg, "traffic": traffic,
           "chips": 1, "compiles": 0, "registry": (before, after),
           "memory_peak_bytes": 0, "peaks": None}
    got = run.read_layer_metrics(mine, ctx)
    assert list(got) == ["tier_cold_row_share"]
    assert got["tier_cold_row_share"]["value"] == \
        win.counters["cold_row_share"]


def test_correct_turns_false_when_a_cold_row_is_perturbed(driven):
    *_, driver, _, _, _ = driven
    cold = driver.feat._cold
    saved = cold.copy()
    try:
        cold += np.float32(1e-3)        # every cold row, by a little
        ok, why = _correct(driver)
    finally:
        cold[:] = saved
    assert not ok and "features differ" in why
    assert _correct(driver)[0]


def test_correct_turns_false_when_the_hot_set_is_not_the_hottest(driven):
    """The rows of ranks ``hot - 1`` and ``hot`` change tiers, with
    ``id2index`` kept consistent: every gathered row is still the stored
    row, and only the reference's order can tell."""
    import jax.numpy as jnp

    *_, driver, _, _, _ = driven
    feat = driver.feat
    hot = feat.hot_count
    saved = feat._hot, feat._cold[0].copy(), feat._id2index
    i2i = np.asarray(feat.id2index).copy()
    a, b = np.flatnonzero(i2i == hot - 1)[0], np.flatnonzero(i2i == hot)[0]
    try:
        feat._hot = saved[0].at[hot - 1].set(jnp.asarray(saved[1]))
        feat._cold[0] = np.asarray(saved[0][hot - 1])
        i2i[a], i2i[b] = hot, hot - 1
        feat._id2index = jnp.asarray(i2i)
        ids = np.array([a, b, -1])
        np.testing.assert_array_equal(np.asarray(feat.gather(ids)),
                                      driver.d.ref.features(ids))
        ok, why = _correct(driver)
    finally:
        feat._hot, feat._cold[0], feat._id2index = saved
    assert not ok and "hot set is not" in why
    assert _correct(driver)[0]


def test_logits_tolerance_passes_bf16_and_fails_four_mantissa_bits(driven):
    cfg, _, driver, _, _, _ = driven
    rtol = _json("chipbench", "configs",
                 "sage-papers100m-tiered-chip1.json")["check"]["logits_rtol"]
    assert cfg["check"]["logits_rtol"] == rtol
    detail = driver.check()
    assert detail["logits_err"] < rtol < detail["logits_err_4bit"]
    want = np.ones((8,))
    with pytest.raises(checks.CheckFailure, match="logits differ"):
        checks.check_logits(want * (1 + detail["logits_err_4bit"]), want,
                            rtol, "4-bit")


def test_an_overflowing_batch_is_replayed_under_its_own_layout():
    """A capacity low enough that batches overflow: the loader replays
    them at full capacity, the step trains them under the sibling's
    layout, nothing compiles in the window, nothing fails."""
    cfg = _json("chipbench", "configs", "tiny-sage-tiered.json")
    low = dict(cfg, sampling=dict(cfg["sampling"], node_capacity=600))
    _, driver, win = _drive(low, trace=False)
    try:
        assert len(driver.layouts) == 2
        assert 0 < win.counters["overflow_replayed"] < win.steps
        assert win.failed == 0 and np.isfinite(driver.losses).all()
        assert _correct(driver)[0]
    finally:
        driver.close()


def test_tiers_are_generated_as_the_reference_orders_them():
    """``build_tiered`` against plain numpy: the hotness order, the rows
    of both tiers from the generator's counters, and the same rows as
    ``build_one_chip`` makes in one piece."""
    import jax

    cfg = _json("chipbench", "configs", "tiny-sage-tiered.json")
    d = data_tiered.build_tiered(cfg, 9, jax.devices()[0])
    feat = d.dataset.get_node_feature()
    n = d.shapes.num_nodes
    want = reference_tiered.expected_id2index(d.indices, n)
    np.testing.assert_array_equal(np.asarray(feat.id2index), want)
    deg = reference_tiered.in_degree(d.indices, n)
    order = np.argsort(want)
    assert (np.diff(deg[order]) <= 0).all() and deg[order[0]] > 1
    assert d.hot_count == feat.hot_count == 2000
    np.testing.assert_array_equal(np.asarray(feat.hot_rows),
                                  d.ref.features(order[:2000]))
    np.testing.assert_array_equal(feat._cold, d.ref.features(order[2000:]))
    whole = data.build_one_chip(cfg, 9, jax.devices()[0])
    np.testing.assert_array_equal(whole.dataset.get_graph().topo.indices,
                                  d.indices)
    ids = np.arange(-1, n)
    np.testing.assert_array_equal(
        np.asarray(feat.gather(ids)),
        np.asarray(whole.dataset.get_node_feature().gather(ids)))
    np.testing.assert_array_equal(d.dataset.get_node_label(),
                                  whole.dataset.get_node_label())
    np.testing.assert_array_equal(d.train_idx, whole.train_idx)


def test_check_tiers_wants_the_order_and_rows_of_both_tiers():
    rng = np.random.default_rng(0)
    n, hot = 50, 20
    indices = rng.integers(0, n, 400)
    want = reference_tiered.expected_id2index(indices, n)
    node = np.concatenate([np.flatnonzero(want < hot)[:3],
                           np.flatnonzero(want >= hot)[:2], [-1]])
    got = reference_tiered.check_tiers(indices, n, hot, want, hot, node, "t")
    assert got == {"checked_hot_rows": 3, "checked_cold_rows": 2}
    with pytest.raises(checks.CheckFailure, match="rows of both tiers"):
        reference_tiered.check_tiers(indices, n, hot, want, hot, node[:3],
                                     "t")
    with pytest.raises(checks.CheckFailure, match="hot tier holds"):
        reference_tiered.check_tiers(indices, n, hot, want, hot + 1, node,
                                     "t")
    swapped = want.copy()
    i, j = np.flatnonzero(want == 3)[0], np.flatnonzero(want == 4)[0]
    swapped[i], swapped[j] = 4, 3            # the same tiers, another order
    with pytest.raises(checks.CheckFailure, match="id2index differs"):
        reference_tiered.check_tiers(indices, n, hot, swapped, hot, node,
                                     "t")


def test_tier_readers_read_a_scoped_device_trace(monkeypatch):
    """Every ``tier_*`` metric out of a device trace with the program's
    scopes and spans in it (hand-made: device ops exist on the chip
    only); the busy time is the sum of the scoped metrics and the
    unscoped time; the roofline is under 100 %."""
    from chipbench import peaks, run, scopes

    ms = 1e6
    times = [("glt.sample.hop1", 2 * ms), ("glt.sample.hop3", 40 * ms),
             ("glt.sample.induce", 20 * ms), ("glt.gather.feat", 6 * ms),
             ("glt.gather.merge", 4 * ms), ("glt.gather.label", 2 * ms),
             ("glt.model.msg", 30 * ms), ("glt.model.agg", 10 * ms),
             ("glt.model.dense", 8 * ms), ("glt.step.loss", 1 * ms),
             ("glt.step.update", 1 * ms), (None, 6 * ms)]
    busy = sum(t for _, t in times)
    total = busy + 20 * ms                  # 20 ms of idle at the end
    spans = [("glt.loader.collate", int(busy), int(20 * ms)),
             ("glt.feature.ids_wait", int(busy), int(2 * ms)),
             ("glt.feature.cold_fetch", int(busy + 2 * ms), int(8 * ms)),
             ("glt.feature.cold_put", int(busy + 10 * ms), int(10 * ms))]
    monkeypatch.setattr(scopes, "traced_file", lambda: "trace.xplane.pb")
    monkeypatch.setattr(scopes, "scope_map", lambda path: {})
    monkeypatch.setattr(scopes, "scoped_self_times",
                        lambda trace, smap: times)
    monkeypatch.setattr(scopes, "program_spans", lambda path: spans)
    cfg = _json("chipbench", "configs", "sage-papers100m-tiered-chip1.json")
    hot_rows = 2 * 250000
    trace = {"window": [0, int(total)], "devices": {"0": {
        "ops": [["fusion.1", "", 0, int(busy)]], "async": [],
        "modules": []}}}
    win = Window(2, 0, {}, 2, {"cold_row_share": 30.0})
    bench = _json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    ctx = {"trace": trace, "window": win, "config": cfg, "traffic": {},
           "chips": 1, "compiles": 0,
           "registry": ({"glt.feature.hot_rows": 1000.0},
                        {"glt.feature.hot_rows": 1000.0 + hot_rows}),
           "memory_peak_bytes": 0, "peaks": peaks.peaks_of("TPU v5 lite")}
    got = {k: v["value"] for k, v in run.read_layer_metrics(mine, ctx).items()}
    assert sorted(got) == sorted(TIER_METRICS)
    assert got["tier_cold_row_share"] == 30.0
    assert got["tier_ids_wait_idle_ms"] == 1.0
    assert got["tier_cold_fetch_idle_ms"] == 4.0
    assert got["tier_cold_put_idle_ms"] == 5.0
    assert got["tier_merge_ms"] == 6.0          # feat + merge + label
    assert got["tier_sample_hop_ms"] == 21.0
    assert got["tier_model_agg_ms"] == 20.0
    parts = sum(got[k] for k in ("tier_sample_hop_ms",
                                 "tier_sample_induce_ms", "tier_merge_ms",
                                 "tier_model_device_ms"))
    unscoped = got["tier_device_busy_ms"] * got["tier_unscoped_share"] / 100
    assert abs(parts + unscoped - got["tier_device_busy_ms"]) < 1e-6
    # hot rows x 128 x 4 B x 2 over 6 ms of glt.gather.feat, over 819 GB/s
    want = 100.0 * (2 * hot_rows * 128 * 4) / 6e-3 / 819e9
    assert abs(got["tier_hot_gather_roofline"] - want) < 1e-9
    assert 0 < got["tier_hot_gather_roofline"] < 100
    # a program without the counter or the spans (the parent): nothing
    spec = _json("chipbench", "layer_metrics",
                 "tier_hot_gather_roofline.json")
    reader = importlib.import_module("chipbench.reducers." + spec["reducer"])
    assert reader.read(dict(ctx, registry=({}, {})), spec["params"]) is None
    assert reader.read(dict(ctx, peaks=None), spec["params"]) is None
    monkeypatch.setattr(scopes, "program_spans", lambda path: spans[:1])
    left = run.read_layer_metrics(mine, dict(ctx, registry=({}, {})))
    assert "tier_ids_wait_idle_ms" not in left
    assert "tier_hot_gather_roofline" not in left


def test_calibration_prints_a_capacity_and_a_width_for_every_ratio(capsys,
                                                                   monkeypatch):
    from chipbench import calibrate_tiered

    monkeypatch.setattr(sys, "argv", [
        "calibrate_tiered.py", "--config",
        os.path.join(ROOT, "chipbench", "configs", "tiny-sage-tiered.json"),
        "--split-ratio", "0.25", "--split-ratio", "0.5", "--batches", "8",
        "--check-seed", "3"])
    assert calibrate_tiered.main() == 0
    first, second = [json.loads(line) for line in
                     capsys.readouterr().out.strip().splitlines()]
    assert (first["seed"], second["seed"]) == (0, 3)
    assert first["node_capacity"] == second["node_capacity"]
    lo, mid, hi = first["unique_nodes_min_median_max"]
    assert 544 <= lo <= mid <= hi <= first["node_capacity"] <= 1312
    by = first["by_split_ratio"]
    assert [r["split_ratio"] for r in by] == [0.25, 0.5]
    assert by[0]["hot_rows"] == 1000 and by[1]["hot_rows"] == 2000
    # more rows hot, fewer cold; the width stands over the fullest batch
    assert by[0]["cold_rows_min_median_max"][1] > \
        by[1]["cold_rows_min_median_max"][1] > 0
    for r in by:
        assert r["cold_width"] % 1024 == 0
        assert r["cold_rows_min_median_max"][2] <= r["cold_width"]
        assert r["batches_over_width"] == 0


def test_the_cells_files_and_the_configuration_say_what_the_issue_asks():
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train-eager"
    assert "do not all lie in HBM" in cell["why"] and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cfg = _json(entry["file"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "num_shards", "scale"]
    traffic = _json("chipbench", "traffic", cell["traffic"] + ".json")
    assert (traffic["driver"], traffic["prefetch"]) == ("eager_train", 2)
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "drivers", traffic["driver"] + ".py"))
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(TIER_METRICS[0])
    assert names[at: at + len(TIER_METRICS)] == TIER_METRICS
    for m in bench["per_layer"][at: at + len(TIER_METRICS)]:
        assert m["workloads"] == [CELL] and m["moves"] == "seeds_per_s"
        spec = _json("chipbench", "layer_metrics", m["name"] + ".json")
        importlib.import_module("chipbench.reducers." + spec["reducer"])
    dist4 = _json("chipbench", "configs", "sage-papers100m-dist4.json")
    chip1 = _json("chipbench", "configs", "sage-papers100m-chip1.json")
    d, sam, tier = cfg["data"], cfg["sampling"], cfg["tiering"]
    for key in ("num_nodes", "num_edges", "feature_dim", "feature_dtype",
                "max_degree", "num_classes"):
        assert d[key] == dist4["data"][key]       # the quarter's shapes
    assert (d["num_nodes"], d["num_edges"], d["train_seeds"]) == (
        27764989, 403921468, 1207179 // 4)
    assert cfg["model"] == chip1["model"]
    assert cfg["assumed"]["degree_alpha"] == dist4["assumed"]["degree_alpha"]
    assert sam["fanout"] == [15, 10, 5] and sam["frontier_cap"] is None
    assert sam["batch_size"] == 1024
    assert (cfg["scale"], cfg["num_shards"], cfg["chips"]) == (0.25, 1, 1)
    # the split_ratio rule: a multiple of 0.05 under 1, the largest tried
    # that stays within the limit
    ratio = tier["split_ratio"]
    assert 0 < ratio < 1 and abs(ratio * 20 - round(ratio * 20)) < 1e-9
    assert tier["cold_cache"] is False
    tried = {t["split_ratio"]: t for t in tier["tried"]}
    assert tried[ratio]["total_gb"] <= 14.0
    over = [r for r, t in tried.items() if r > ratio]
    assert over and all(tried[r]["total_gb"] > 14.0 for r in over)
    floor = sum(1024 * w for w in (1, 15, 150))
    assert floor <= sam["node_capacity"] <= 1024 * (1 + 15 + 150 + 750)
    assert tier["cold_width"] % 1024 == 0
    assert tier["cold_width"] < sam["node_capacity"]
    assert set(chip1["guarantees"]) < set(cfg["guarantees"])
    assert len(cfg["guarantees"]) == len(chip1["guarantees"]) + 3
    # the table does not fit the chip: that is the configuration
    table_gb = d["num_nodes"] * d["feature_dim"] * 4 / 1e9
    topology_gb = (2 * d["num_edges"] + 3 * d["num_nodes"]) * 4 / 1e9
    assert table_gb + topology_gb > 16 > ratio * table_gb + topology_gb
    assert ratio * table_gb + topology_gb > 4.3          # over 4.00 GiB
