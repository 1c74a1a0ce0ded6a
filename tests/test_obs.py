"""glt_tpu.obs: tracing, metrics, roofline (ISSUE 6).

Covers the acceptance criteria: a Chrome-trace JSON of one instrumented
training step is produced and validated (golden structure: loads, spans
nest, device timings non-negative), and the disabled instrumentation
path is a near-free no-op (overhead smoke).
"""
import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu import obs
from glt_tpu.obs import metrics
from glt_tpu.obs.summarize import format_summary, summarize_trace
from glt_tpu.obs.trace import Tracer, validate_chrome_trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts (and leaves) with tracing off + a fresh registry."""
    obs.install(None)
    metrics.disable()
    metrics.reset()
    yield
    obs.install(None)
    metrics.disable()
    metrics.reset()


# ---------------------------------------------------------------------------
# tracing core
# ---------------------------------------------------------------------------

class TestTrace:
    def test_nested_spans_export_valid_chrome_trace(self, tmp_path):
        tracer = obs.start_trace()
        with obs.span("epoch", epoch=1):
            for _ in range(3):
                with obs.span("step"):
                    with obs.span("gather"):
                        time.sleep(0.001)
                    time.sleep(0.001)
        path = str(tmp_path / "trace.json")
        assert obs.stop_trace(path) is tracer
        obj = json.load(open(path))
        assert validate_chrome_trace(obj) == []
        events = obj["traceEvents"]
        names = [e["name"] for e in events]
        assert names.count("epoch") == 1
        assert names.count("step") == 3
        assert names.count("gather") == 3
        # nesting: every step lies inside the epoch's interval
        epoch = next(e for e in events if e["name"] == "epoch")
        for e in events:
            if e["name"] == "step":
                assert e["ts"] >= epoch["ts"] - 0.5
                assert e["ts"] + e["dur"] <= (epoch["ts"] + epoch["dur"]
                                              + 0.5)
                assert e["args"]["depth"] == 1

    def test_span_is_noop_without_tracer(self):
        sp = obs.span("nothing")
        with sp as inner:
            assert inner.fence(123) == 123   # passthrough
            inner.set(k=1)
        assert obs.current() is None

    def test_fence_records_device_timings(self, tmp_path):
        obs.start_trace()
        f = jax.jit(lambda x: (x * 2.0).sum())
        x = jnp.arange(1024, dtype=jnp.float32)
        with obs.span("jit_call") as sp:
            sp.fence(f(x))
        obj = obs.stop_trace().chrome_trace()
        assert validate_chrome_trace(obj) == []
        (ev,) = obj["traceEvents"]
        assert ev["args"]["dispatch_us"] >= 0
        assert ev["args"]["device_wait_us"] >= 0
        assert ev["dur"] >= ev["args"]["dispatch_us"] - 1e-3

    def test_threaded_spans_keep_separate_stacks(self):
        import threading

        tracer = obs.start_trace()

        def worker():
            with obs.span("worker"):
                time.sleep(0.002)

        with obs.span("main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=5)
        obj = obs.stop_trace().chrome_trace()
        assert validate_chrome_trace(obj) == []
        tids = {e["tid"] for e in obj["traceEvents"]}
        assert len(tids) == 2

    def test_validator_rejects_broken_traces(self):
        assert validate_chrome_trace({}) != []
        assert validate_chrome_trace({"traceEvents": [{}]}) != []
        bad_dur = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "dur": -5, "pid": 1,
             "tid": 1}]}
        assert any("negative dur" in p
                   for p in validate_chrome_trace(bad_dur))
        overlap = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1,
             "tid": 1},
            {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1,
             "tid": 1}]}
        assert any("overlaps" in p
                   for p in validate_chrome_trace(overlap))

    def test_trace_of_instrumented_training_step(self, tmp_path):
        """ISSUE 6 acceptance: a Chrome-trace of ONE instrumented
        training step — loader spans + a fenced step span — exports as
        valid Chrome-trace JSON."""
        from glt_tpu.data import CSRTopo, Dataset
        from glt_tpu.loader import NeighborLoader
        from glt_tpu.models import GraphSAGE, TrainState, make_train_step

        rng = np.random.default_rng(0)
        n, dim, classes = 48, 8, 3
        src = rng.integers(0, n, 4 * n)
        dst = rng.integers(0, n, 4 * n)
        data = (Dataset()
                .init_graph(np.stack([src, dst]), graph_mode="HOST",
                            num_nodes=n)
                .init_node_features(
                    rng.normal(0, 1, (n, dim)).astype(np.float32))
                .init_node_labels(rng.integers(0, classes, n)))
        loader = NeighborLoader(data, [3, 2], np.arange(n),
                                batch_size=8, with_edge=False)
        model = GraphSAGE(hidden_features=8, out_features=classes,
                          num_layers=2)
        tx = optax.adam(1e-3)
        step = make_train_step(model, tx, batch_size=8)

        obs.start_trace()
        batch = next(iter(loader))
        params = model.init({"params": jax.random.PRNGKey(0)},
                            batch.x, batch.edge_index, batch.edge_mask)
        state = TrainState(params=params, opt_state=tx.init(params),
                           step=jnp.zeros((), jnp.int32))
        with obs.span("train.serial_step") as sp:
            state, loss, acc = step(state, batch)
            sp.fence(loss)
        path = str(tmp_path / "step_trace.json")
        obs.stop_trace(path)

        obj = json.load(open(path))
        assert validate_chrome_trace(obj) == []   # loads + spans nest
        names = {e["name"] for e in obj["traceEvents"]}
        assert "loader.sample_dispatch" in names
        assert "loader.collate" in names
        assert "train.serial_step" in names
        step_ev = next(e for e in obj["traceEvents"]
                       if e["name"] == "train.serial_step")
        assert step_ev["args"]["device_wait_us"] >= 0   # fenced, real wait
        assert step_ev["dur"] > 0
        assert np.isfinite(float(np.asarray(loss)))

    def test_summarize_aggregates_and_cli(self, tmp_path):
        obs.start_trace()
        with obs.span("epoch"):
            for _ in range(2):
                with obs.span("step"):
                    time.sleep(0.001)
        path = str(tmp_path / "t.json")
        obs.stop_trace(path)
        rows = summarize_trace(json.load(open(path)))
        by_name = {r["name"]: r for r in rows}
        assert by_name["step"]["count"] == 2
        # self time: epoch's total minus its steps
        assert by_name["epoch"]["self_ms"] <= by_name["epoch"]["total_ms"]
        assert "step" in format_summary(rows)
        out = subprocess.run(
            [sys.executable, "-m", "glt_tpu.obs", "summarize", path],
            capture_output=True, text=True)
        assert out.returncode == 0
        assert "epoch" in out.stdout
        val = subprocess.run(
            [sys.executable, "-m", "glt_tpu.obs", "validate", path],
            capture_output=True, text=True)
        assert val.returncode == 0
        assert "OK" in val.stdout


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_histogram(self):
        metrics.enable()
        c = metrics.counter("glt.t.count", "help")
        c.inc()
        c.inc(2.5)
        g = metrics.gauge("glt.t.gauge")
        g.set(7)
        g.inc(1)
        h = metrics.histogram("glt.t.lat_ms")
        h.observe(0.2)
        h.observe(80.0)
        with h.time():
            pass
        snap = metrics.snapshot()
        assert snap["glt.t.count"] == 3.5
        assert snap["glt.t.gauge"] == 8.0
        assert snap["glt.t.lat_ms.count"] == 3.0
        assert snap["glt.t.lat_ms.sum"] >= 80.2

    def test_same_name_returns_same_instrument(self):
        assert metrics.counter("glt.t.a") is metrics.counter("glt.t.a")
        assert (metrics.counter("glt.t.a", labels={"op": "x"})
                is not metrics.counter("glt.t.a", labels={"op": "y"}))

    def test_disabled_is_frozen(self):
        metrics.enable()
        c = metrics.counter("glt.t.c")
        c.inc(5)
        metrics.disable()
        c.inc(100)
        metrics.gauge("glt.t.g").set(9)
        metrics.histogram("glt.t.h").observe(1)
        snap = metrics.snapshot()
        assert snap["glt.t.c"] == 5.0
        assert snap["glt.t.g"] == 0.0
        assert snap["glt.t.h.count"] == 0.0

    def test_prometheus_exposition_format(self):
        metrics.enable()
        metrics.counter("glt.t.reqs", "requests", labels={"op": "f"}).inc(3)
        metrics.gauge("glt.t.live", "live now").set(2)
        metrics.histogram("glt.t.ms", buckets=(1.0, 10.0)).observe(5.0)
        text = metrics.render_prometheus()
        assert '# TYPE glt_t_reqs_total counter' in text
        assert 'glt_t_reqs_total{op="f"} 3.0' in text
        assert "# HELP glt_t_live live now" in text
        assert 'glt_t_ms_bucket{le="10.0"} 1' in text
        assert 'glt_t_ms_bucket{le="+Inf"} 1' in text
        assert "glt_t_ms_count 1" in text

    def test_prometheus_escapes_hostile_label_values(self):
        """Label values containing quotes, backslashes, and newlines
        must not corrupt the exposition (ISSUE 13 satellite: format
        0.0.4 escaping — backslash first, then quote, then LF)."""
        metrics.enable()
        metrics.counter("glt.t.hostile", "h", labels={
            "path": 'C:\\tmp\\"x"\nEOL'}).inc(2)
        text = metrics.render_prometheus()
        line = [ln for ln in text.splitlines()
                if ln.startswith("glt_t_hostile_total{")][0]
        assert line == ('glt_t_hostile_total'
                        '{path="C:\\\\tmp\\\\\\"x\\"\\nEOL"} 2.0')
        # The exposition stays line-structured: no raw newline leaked
        # out of the label value into the body.
        for ln in text.splitlines():
            assert ln == "" or ln.startswith("#") or " " in ln

    def test_prune_unmeasured(self):
        out = obs.prune_unmeasured(
            {"a": 1.0, "overflow_rate": None, "b": -1.0})
        assert out == {"a": 1.0, "b": -1.0}   # None dropped, values kept

    def test_disabled_overhead_smoke(self):
        """Enabled-vs-disabled cost: the disabled path must be a cheap
        no-op (ISSUE 6: instrumentation costs ~nothing when off).  Bound
        is deliberately loose (CI machines) — the bench reports the real
        number as obs_noop_ns_per_call."""
        metrics.disable()
        obs.install(None)
        c = metrics.counter("glt.t.noop")
        h = metrics.histogram("glt.t.noop_ms")
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("noop"), h.time():
                c.inc()
        disabled_s = time.perf_counter() - t0
        # < 25 us per disabled call triple — two orders of magnitude of
        # slack over the ~0.3 us a warm CPython run measures.
        assert disabled_s / n < 25e-6
        assert metrics.snapshot()["glt.t.noop"] == 0.0


class TestHistogramQuantiles:
    """ISSUE 7 satellite: linear-interpolated quantiles + snapshot
    p50/p95/p99 so the regression harness and serving SLOs read
    latencies without re-deriving from raw buckets."""

    def test_quantile_linear_interpolation(self):
        metrics.enable()
        h = metrics.histogram("glt.t.q_ms", buckets=(1.0, 2.0, 4.0))
        # 4 samples in (1, 2]: cumulative 0 / 4 / 4.
        for v in (1.2, 1.4, 1.6, 1.8):
            h.observe(v)
        # Median rank 2 of 4 -> midpoint of the (1, 2] bucket.
        assert h.quantile(0.5) == pytest.approx(1.5)
        assert h.quantile(1.0) == pytest.approx(2.0)
        assert h.quantile(0.25) == pytest.approx(1.25)

    def test_quantile_across_buckets(self):
        metrics.enable()
        h = metrics.histogram("glt.t.q2_ms", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 0.5, 3.0, 3.0):       # 2 in (0,1], 2 in (2,4]
            h.observe(v)
        assert h.quantile(0.5) == pytest.approx(1.0)   # edge of bucket 1
        assert h.quantile(0.75) == pytest.approx(3.0)  # mid bucket 3
        # +Inf tail clamps to the highest finite edge
        h.observe(100.0)
        assert h.quantile(0.99) == pytest.approx(4.0)

    def test_quantile_empty_is_nan(self):
        metrics.enable()
        h = metrics.histogram("glt.t.q3_ms")
        assert np.isnan(h.quantile(0.5))

    def test_quantile_single_observation(self):
        """One sample: every q resolves inside its bucket with no
        divide-by-zero (ISSUE 13 satellite)."""
        metrics.enable()
        h = metrics.histogram("glt.t.q4_ms", buckets=(1.0, 2.0, 4.0))
        h.observe(3.0)                      # alone in (2, 4]
        assert 2.0 <= h.quantile(0.0) <= 4.0
        assert 2.0 <= h.quantile(0.5) <= 4.0
        assert h.quantile(1.0) == pytest.approx(4.0)

    def test_quantile_extreme_q_clamped(self):
        metrics.enable()
        h = metrics.histogram("glt.t.q5_ms", buckets=(1.0, 2.0))
        for v in (0.5, 1.5):
            h.observe(v)
        # out-of-range q clamps instead of indexing off the ends
        assert h.quantile(-0.5) == h.quantile(0.0)
        assert h.quantile(1.5) == h.quantile(1.0)
        assert h.quantile(0.0) <= h.quantile(1.0)

    def test_quantile_all_in_one_bucket(self):
        """Every sample in a single bucket: the interpolation never
        divides by an empty preceding bucket's zero count."""
        metrics.enable()
        h = metrics.histogram("glt.t.q6_ms", buckets=(1.0, 10.0, 100.0))
        for _ in range(7):
            h.observe(5.0)                  # all in (1, 10]
        for q in (0.0, 0.01, 0.5, 0.99, 1.0):
            v = h.quantile(q)
            assert 1.0 <= v <= 10.0, (q, v)

    def test_quantile_from_counts_module_function(self):
        """The extracted interpolation the SLO monitor feeds windowed
        bucket deltas through (glt_tpu/obs/slo.py)."""
        from glt_tpu.obs.metrics import quantile_from_counts

        buckets = (1.0, 2.0, 4.0)          # finite edges; counts carry
        assert np.isnan(                    # the +Inf tail as entry 4
            quantile_from_counts(buckets, [0, 0, 0, 0], 0.5))
        # 4 in (1, 2] -> median at the bucket midpoint
        assert quantile_from_counts(buckets, [0, 4, 0, 0], 0.5) \
            == pytest.approx(1.5)
        # +Inf tail clamps to the highest finite edge
        assert quantile_from_counts(buckets, [0, 0, 0, 3], 0.99) \
            == pytest.approx(4.0)

    def test_snapshot_reports_percentiles(self):
        metrics.enable()
        h = metrics.histogram("glt.t.lat2_ms", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        snap = metrics.snapshot()
        assert snap["glt.t.lat2_ms.count"] == 3.0
        assert 0 < snap["glt.t.lat2_ms.p50"] <= 10.0
        assert snap["glt.t.lat2_ms.p95"] <= 100.0
        assert snap["glt.t.lat2_ms.p99"] <= 100.0
        assert snap["glt.t.lat2_ms.p50"] <= snap["glt.t.lat2_ms.p99"]
        # empty histograms contribute no percentile keys (no NaN noise)
        metrics.histogram("glt.t.empty_ms")
        assert "glt.t.empty_ms.p50" not in metrics.snapshot()


class TestProcessMetadata:
    """ISSUE 7 satellite: exports carry pid/process_name metadata so
    merged traces render one named track per process in Perfetto."""

    def test_export_names_the_process(self, tmp_path):
        obs.start_trace(process_name="client")
        with obs.span("work"):
            pass
        path = str(tmp_path / "t.json")
        obs.stop_trace(path)
        obj = json.load(open(path))
        assert validate_chrome_trace(obj) == []
        meta = [e for e in obj["traceEvents"] if e.get("ph") == "M"]
        assert meta and meta[0]["name"] == "process_name"
        assert meta[0]["args"]["name"] == "client"
        assert obj["glt"]["process_name"] == "client"
        assert obj["glt"]["pid"] == meta[0]["pid"]

    def test_validator_accepts_instants_and_metadata(self):
        tracer = obs.start_trace(process_name="p")
        tracer.instant("obs.clock_sync", peer_pid=1, t0_us=0.0,
                       t1_us=1.0, t2_us=2.0, t3_us=3.0)
        with obs.span("x"):
            pass
        obj = obs.stop_trace().chrome_trace()
        assert validate_chrome_trace(obj) == []
        phases = {e["ph"] for e in obj["traceEvents"]}
        assert phases == {"M", "i", "X"}

    def test_span_ids_and_local_parent_links(self):
        obs.start_trace()
        with obs.span("outer") as outer:
            ctx = outer.context()
            with obs.span("inner"):
                pass
        events = obs.stop_trace().events
        by_name = {e["name"]: e for e in events}
        assert by_name["inner"]["args"]["parent_span_id"] \
            == by_name["outer"]["args"]["span_id"]
        # context() rooted a trace id; the child inherited it
        assert ctx["tid"] == by_name["outer"]["args"]["trace_id"]
        assert by_name["inner"]["args"]["trace_id"] == ctx["tid"]

    def test_remote_link_sets_parent(self):
        obs.start_trace()
        with obs.span("server_side") as sp:
            sp.link("abcd1234", 777)
        (ev,) = obs.stop_trace().events
        assert ev["args"]["trace_id"] == "abcd1234"
        assert ev["args"]["parent_span_id"] == 777


class TestSummarizeJson:
    def test_summarize_json_cli(self, tmp_path):
        obs.start_trace()
        with obs.span("epoch"):
            with obs.span("step"):
                time.sleep(0.001)
        path = str(tmp_path / "t.json")
        obs.stop_trace(path)
        out = subprocess.run(
            [sys.executable, "-m", "glt_tpu.obs", "summarize", path,
             "--json"], capture_output=True, text=True)
        assert out.returncode == 0
        rows = json.loads(out.stdout)
        by_name = {r["name"]: r for r in rows}
        assert by_name["step"]["count"] == 1
        assert {"total_ms", "self_ms", "mean_ms"} <= set(by_name["epoch"])


# ---------------------------------------------------------------------------
# unified stats namespace (cache + remote loader re-exports)
# ---------------------------------------------------------------------------

class TestStatsReexport:
    def test_cache_stats_publishes_gauges(self):
        from glt_tpu.data.feature_cache import (
            cache_gather,
            cache_init,
            cache_stats,
            publish_cache_stats,
        )

        table = jnp.arange(32, dtype=jnp.float32).reshape(16, 2)
        state = cache_init(16, 4, 2)
        ids = jnp.array([1, 5, -1, 9], jnp.int32)
        state, rows = cache_gather(
            state, ids, lambda i: jnp.take(
                table, jnp.clip(i, 0, 15), axis=0
            ) * (i >= 0)[:, None])
        metrics.enable()
        stats = publish_cache_stats(state)
        snap = metrics.snapshot()
        assert snap["glt.cache.misses"] == stats["misses"] == 3
        assert snap["glt.cache.hits"] == stats["hits"] == 0
        assert snap["glt.cache.resident"] == 3
        # deprecated alias keeps working and publishes the same way
        assert cache_stats(state) == stats

    def test_cache_stats_without_metrics_unchanged(self):
        from glt_tpu.data.feature_cache import cache_init, cache_stats

        metrics.disable()
        stats = cache_stats(cache_init(8, 2, 2))
        assert stats["lookups"] == 0 and stats["capacity"] == 2
        # disabled: gauges either absent (never created) or untouched
        assert metrics.snapshot().get("glt.cache.capacity", 0.0) == 0.0

    def test_publish_epoch_stats_folds_counters(self):
        from glt_tpu.distributed.dist_client import publish_epoch_stats

        metrics.enable()
        stats = {"received": 7, "duplicates": 2, "reconnects": 1,
                 "seqs": set(range(7))}
        assert publish_epoch_stats(stats) is stats
        publish_epoch_stats({"received": 3, "duplicates": 0,
                             "reconnects": 0})
        snap = metrics.snapshot()
        assert snap["glt.remote.batches_received"] == 10.0
        assert snap["glt.remote.duplicates"] == 2.0
        assert snap["glt.remote.reconnects"] == 1.0
        assert snap["glt.remote.epochs"] == 2.0


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

class TestRoofline:
    def test_memcpy_roofline_measures_positive_bandwidth(self):
        r = obs.measure_memcpy_roofline(nbytes=1 << 18, iters=3)
        assert r["memcpy_gb_s"] > 0
        assert r["bytes"] >= 1 << 18
        assert r["elapsed_s"] > 0

    def test_roofline_fraction(self):
        assert obs.roofline_fraction(50.0, 100.0) == pytest.approx(0.5)
        assert obs.roofline_fraction(1.0, 0.0) > 0   # guarded divide

    def test_peak_hbm_env_override(self, monkeypatch):
        from glt_tpu.obs.roofline import peak_hbm_gb_s

        monkeypatch.setenv("GLT_HBM_GBPS", "1228")
        r = peak_hbm_gb_s()
        assert r == {"gb_s": 1228.0, "source": "env"}

    def test_peak_hbm_bad_env_raises(self, monkeypatch):
        from glt_tpu.obs.roofline import peak_hbm_gb_s

        monkeypatch.setenv("GLT_HBM_GBPS", "not-a-number")
        with pytest.raises(ValueError):
            peak_hbm_gb_s()

    def test_peak_hbm_unknown_kind_raises(self, monkeypatch):
        # The CPU's device_kind has no row: an unknown device is an
        # error that names the kind, never a v5e default.
        import jax

        from glt_tpu.obs.roofline import peak_bf16_tflops, peak_hbm_gb_s

        monkeypatch.delenv("GLT_HBM_GBPS", raising=False)
        kind = jax.devices()[0].device_kind
        with pytest.raises(LookupError, match=kind):
            peak_hbm_gb_s()
        with pytest.raises(LookupError, match=kind):
            peak_bf16_tflops(kind)
        assert peak_bf16_tflops("TPU v5 lite") == 197.0

    def test_peak_hbm_device_kind_table(self):
        from glt_tpu.obs.roofline import DEVICE_HBM_GB_S

        table = dict(DEVICE_HBM_GB_S)
        assert table["v5e"] == 819.0
        assert table["v5p"] > table["v5e"]        # newer gen is faster
        assert table["v6e"] > table["v5e"]


# ---------------------------------------------------------------------------
# loader metrics (end to end through NodeLoader)
# ---------------------------------------------------------------------------

def test_loader_counts_batches_when_enabled():
    from glt_tpu.data import Dataset
    from glt_tpu.loader import NeighborLoader

    rng = np.random.default_rng(1)
    n = 32
    data = (Dataset()
            .init_graph(np.stack([rng.integers(0, n, 3 * n),
                                  rng.integers(0, n, 3 * n)]),
                        graph_mode="HOST", num_nodes=n))
    loader = NeighborLoader(data, [2, 2], np.arange(n), batch_size=8,
                            with_edge=False)
    metrics.enable()
    before = metrics.snapshot().get("glt.loader.batches", 0.0)
    batches = list(loader)
    snap = metrics.snapshot()
    assert snap["glt.loader.batches"] - before == len(batches) == 4
    assert snap["glt.loader.sample_dispatch_ms.count"] >= 4


# ---------------------------------------------------------------------------
# crash-time trace flush (ISSUE 8 satellite)
# ---------------------------------------------------------------------------

class TestCrashTimeFlush:
    def test_flush_exports_writes_registered_paths(self, tmp_path,
                                                   monkeypatch):
        from glt_tpu.obs import trace as trace_mod

        monkeypatch.setenv(trace_mod.TRACE_DIR_ENV, str(tmp_path))
        monkeypatch.setattr(trace_mod, "_flush_paths", set())
        path = trace_mod.auto_trace("worker3")
        assert path is not None
        with obs.span("work"):
            time.sleep(0.001)
        written = trace_mod.flush_exports(reason="unit-test")
        assert written == [path] and os.path.isfile(path)
        doc = json.load(open(path))
        assert validate_chrome_trace(doc) == []
        names = [e["name"] for e in doc["traceEvents"]]
        assert "work" in names and "trace.flush" in names
        # Idempotent: a later flush (atexit after a supervisor flush)
        # republishes a complete snapshot.
        assert trace_mod.flush_exports() == [path]
        assert validate_chrome_trace(json.load(open(path))) == []

    def test_flush_exports_noop_without_registration(self, monkeypatch):
        from glt_tpu.obs import trace as trace_mod

        monkeypatch.setattr(trace_mod, "_flush_paths", set())
        obs.start_trace()
        assert trace_mod.flush_exports() == []

    def test_export_is_atomic(self, tmp_path):
        """export never leaves a torn file at the final path — the
        property the SIGTERM-time flush depends on (GLT011)."""
        t = Tracer()
        with t.span("s"):
            pass
        out = tmp_path / "trace.json"
        t.export(str(out))
        assert validate_chrome_trace(json.load(open(out))) == []
        leftovers = [p for p in os.listdir(tmp_path)
                     if p.startswith("trace.json.tmp")]
        assert leftovers == []

    def test_sigterm_flushes_partial_trace_subprocess(self, tmp_path):
        """A SIGTERMed fleet process exports its partial trace before
        dying WITH signal-death exit status (the parent supervisor must
        still see the kill).  SIGKILL is unflushable by design — the
        supervisor's peer-side spans cover that case."""
        script = (
            "import os, sys, time\n"
            "sys.path.insert(0, %r)\n"
            "from glt_tpu.obs import trace\n"
            "path = trace.auto_trace('victim')\n"
            "tr = trace.current()\n"
            "with tr.span('doomed_epoch'):\n"
            "    print('READY', flush=True)\n"
            "    time.sleep(30)\n" % REPO_ROOT
        )
        env = {**os.environ, "GLT_OBS_TRACE_DIR": str(tmp_path)}
        proc = subprocess.Popen([sys.executable, "-c", script], env=env,
                                stdout=subprocess.PIPE, text=True)
        try:
            assert proc.stdout.readline().strip() == "READY"
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert rc == -signal.SIGTERM
        files = [p for p in os.listdir(tmp_path)
                 if p.startswith("trace-victim-")]
        assert len(files) == 1
        doc = json.load(open(os.path.join(str(tmp_path), files[0])))
        args = {e["name"]: e.get("args", {}) for e in doc["traceEvents"]}
        assert args.get("trace.flush", {}).get("reason") == "sigterm"
