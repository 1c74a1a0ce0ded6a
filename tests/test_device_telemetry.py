"""ISSUE 14: device telemetry — HBM accounting, triggered profiler
capture, and recompile-storm detection.

All of it runs on CPU (the tier-1 environment): ``memory_stats()`` is
None here, so the gauges degrade to *absent* (never an exception), the
leak watch rides the ``jax.live_arrays()`` fallback, the triggered
captures produce REAL ``jax.profiler`` traces on disk, and the compile
watch counts actual backend compilations through ``jax.monitoring``.
"""
import gc
import json
import os

import numpy as np
import pytest

from glt_tpu.obs import flight, metrics
from glt_tpu.obs import compilewatch, device, profiler
from glt_tpu.obs.flight import merge_flight_dumps, validate_flight_dump
from glt_tpu.obs.slo import SloMonitor
from glt_tpu.obs.summarize import format_flight_summary, summarize_flight

jax = pytest.importorskip("jax")
jnp = jax.numpy


@pytest.fixture(autouse=True)
def _clean_obs():
    flight.recorder().clear()
    metrics.enable()
    metrics.reset()
    compilewatch.reset_for_tests()
    profiler.disarm()
    yield
    profiler.disarm()
    compilewatch.reset_for_tests()
    flight.recorder().clear()
    metrics.disable()
    metrics.reset()


def _trace_files(root):
    return [os.path.join(r, f)
            for r, _, fs in os.walk(root) for f in fs]


# ---------------------------------------------------------------------------
# device memory accounting
# ---------------------------------------------------------------------------

class TestDeviceStats:
    def test_cpu_degrades_to_no_gauges(self):
        # The acceptance criterion: memory_stats()-absent backends
        # publish NOTHING and never raise — absent data is absent,
        # not zero.
        published = device.publish_device_stats()
        if any(d.memory_stats() for d in jax.devices()):
            pytest.skip("backend reports memory_stats; not the "
                        "degradation path")
        assert published == {}
        assert not any(k.startswith("glt.device.bytes")
                       for k in metrics.snapshot())

    def test_peak_bytes_none_not_zero_on_cpu(self):
        if any(d.memory_stats() for d in jax.devices()):
            pytest.skip("backend reports memory_stats")
        # bench.py prunes None; a fake 0 peak would regress-track.
        assert device.peak_bytes_in_use() is None

    def test_live_bytes_fallback_counts_arrays(self):
        # Earlier tests' arrays held in reference cycles must not be freed
        # between the two readings (it depends on where the collector's
        # counters stand, so on how many tests the worker has seen).
        gc.collect()
        base = device.live_bytes()
        keep = jnp.zeros((256, 8), jnp.float32)
        jax.block_until_ready(keep)
        assert device.live_bytes() >= base + keep.nbytes
        del keep

    def test_owner_classification(self):
        device.reset_owners_for_tests()
        try:
            device.register_owner("feature_cache", shape=(64, 16),
                                  dtype=jnp.float32)
            cache = jnp.ones((64, 16), jnp.float32)
            stray = jnp.arange(7)
            jax.block_until_ready((cache, stray))
            snap = device.snapshot()
            owners = snap["owners"]
            assert owners["feature_cache"]["count"] >= 1
            assert owners["feature_cache"]["bytes"] >= cache.nbytes
            # Unclaimed arrays land in "other"; owners sum to total.
            assert "other" in owners
            assert sum(o["bytes"] for o in owners.values()) \
                == snap["total"]["bytes"]
            del cache, stray
        finally:
            device.reset_owners_for_tests()

    def test_register_owner_first_wins_and_never_raises(self):
        device.reset_owners_for_tests()
        try:
            device.register_owner("first", shape=(3, 3), dtype="float32")
            device.register_owner("second", shape=(3, 3),
                                  dtype=jnp.float32)
            fps = device.owners()
            assert list(fps.values()) == ["first"]
            device.register_owner("broken", array=object())  # no raise
        finally:
            device.reset_owners_for_tests()


class TestLeakWatch:
    def test_fires_on_monotonic_growth(self):
        watch = device.LeakWatch(epochs=3)
        hoard = []
        states = []
        for i in range(1, 5):
            hoard.append(jnp.zeros((1024 * i,), jnp.float32))
            jax.block_until_ready(hoard[-1])
            states.append(watch.observe_epoch())
        # First boundary sets the baseline; growth run then climbs.
        assert [s["run"] for s in states] == [0, 1, 2, 3]
        assert states[-1]["suspect"]
        assert metrics.snapshot()["glt.device.leak_suspect"] == 3
        evs = [e for e in flight.recorder().events()
               if e["kind"] == "device.leak_suspect"]
        assert evs and evs[-1]["growth_epochs"] == 3
        assert evs[-1]["threshold"] == 3
        del hoard

    def test_clears_when_growth_stops(self):
        watch = device.LeakWatch(epochs=2)
        assert watch.observe_epoch(live=100)["run"] == 0
        assert watch.observe_epoch(live=200)["run"] == 1
        s = watch.observe_epoch(live=300)
        assert s["suspect"] and s["run"] == 2
        # Plateau: gauge drops back to 0 the moment growth stops.
        s = watch.observe_epoch(live=300)
        assert not s["suspect"] and s["run"] == 0
        assert metrics.snapshot()["glt.device.leak_suspect"] == 0

    def test_epoch_hook_never_raises(self):
        # The train-loop seam: publish + watch in one call, total
        # degradation on CPU but still a well-formed state dict.
        state = device.observe_epoch()
        assert set(state) == {"live_bytes", "run", "suspect"}


# ---------------------------------------------------------------------------
# triggered profiler capture
# ---------------------------------------------------------------------------

class TestCapture:
    def test_capture_writes_real_trace(self, tmp_path):
        d = str(tmp_path / "cap")
        with profiler.capture(d, reason="unit") as got:
            jax.block_until_ready(jnp.dot(jnp.ones((32, 32)),
                                          jnp.ones((32, 32))))
        assert got == d
        files = _trace_files(d)
        assert any(f.endswith(".xplane.pb") for f in files), files
        evs = [e for e in flight.recorder().events()
               if e["kind"] == "profiler.capture"]
        assert len(evs) == 1
        assert evs[0]["dir"] == d and evs[0]["reason"] == "unit"
        assert metrics.snapshot()["glt.profiler.captures"] == 1

    def test_capture_stops_on_exception(self, tmp_path):
        d = str(tmp_path / "boom")
        with pytest.raises(ValueError):
            with profiler.capture(d, reason="boom"):
                raise ValueError("mid-capture")
        # stop_trace ran in the finally: a second capture can start.
        with profiler.capture(str(tmp_path / "after")):
            pass
        evs = [e for e in flight.recorder().events()
               if e["kind"] == "profiler.capture"]
        assert len(evs) == 2           # both indexed, including the crash

    def test_millis_floor(self, tmp_path):
        import time
        t0 = time.monotonic()
        with profiler.capture(str(tmp_path / "floor"), millis=60.0):
            pass
        assert (time.monotonic() - t0) >= 0.055

    def test_rate_limit_and_cap(self, tmp_path):
        prof = profiler.TriggeredProfiler(str(tmp_path), millis=1.0,
                                          min_interval_s=60.0,
                                          max_captures=2)
        assert prof.trigger("one", now=0.0) is not None
        assert prof.trigger("too-soon", now=1.0) is None     # interval
        assert prof.trigger("two", now=61.0) is not None
        assert prof.trigger("over-cap", now=200.0) is None   # max
        assert len(prof.captures) == 2
        assert metrics.snapshot()["glt.profiler.suppressed"] == 2
        # Reason slugs survive hostile characters.
        assert "capture_001_one" in prof.captures[0]["dir"]

    def test_slo_triggered_capture(self, tmp_path):
        # The acceptance path: an SLO fires -> a REAL capture lands,
        # driven deterministically with injected clocks.
        prof = profiler.TriggeredProfiler(str(tmp_path), millis=1.0,
                                          min_interval_s=0.0)
        from glt_tpu.obs.slo import SloSpec
        bad = metrics.counter("glt.slo_t.rejected")
        good = metrics.counter("glt.slo_t.accepted")
        spec = SloSpec(name="rejects", metric="glt.slo_t.rejected",
                       denom="glt.slo_t.accepted", kind="ratio",
                       objective=0.10,
                       windows=((30.0, 1.0), (5.0, 1.0)))
        downstream = []
        mon = SloMonitor([spec],
                         on_alert=prof.slo_on_alert(downstream.append))
        mon.tick(now=0.0)
        bad.inc(50)
        good.inc(50)
        fired = mon.tick(now=40.0)
        assert fired and fired[0]["state"] == "firing"
        assert len(prof.captures) == 1
        assert prof.captures[0]["reason"] == "slo:rejects"
        assert _trace_files(prof.captures[0]["dir"])
        # The adapter forwards the alert untouched.
        assert downstream == fired

    def test_spike_triggered_capture(self, tmp_path):
        prof = profiler.TriggeredProfiler(str(tmp_path), millis=1.0,
                                          min_interval_s=0.0)
        det = profiler.SpikeDetector(profiler=prof, factor=4.0,
                                     min_samples=8)
        for _ in range(8):
            assert not det.observe(10.0)
        assert det.observe(100.0)                 # 10x the median
        assert len(prof.captures) == 1
        assert prof.captures[0]["reason"].startswith("latency_spike_")
        assert _trace_files(prof.captures[0]["dir"])
        evs = [e for e in flight.recorder().events()
               if e["kind"] == "profiler.spike"]
        assert evs and evs[-1]["baseline_ms"] == 10.0
        assert metrics.snapshot()["glt.profiler.spikes"] == 1

    def test_env_arming_and_spike_hook(self, tmp_path, monkeypatch):
        assert profiler.armed() is None
        assert profiler.spike_observe(5.0) is False     # disarmed no-op
        monkeypatch.setenv("GLT_PROFILE_TRIGGER_DIR", str(tmp_path))
        prof = profiler.maybe_arm_from_env()
        assert prof is not None and profiler.armed() is prof
        assert prof.base_dir == str(tmp_path)
        evs = [e for e in flight.recorder().events()
               if e["kind"] == "profiler.armed"]
        assert evs and evs[0]["dir"] == str(tmp_path)
        # Second call is idempotent, not a re-arm.
        assert profiler.maybe_arm_from_env() is prof

    def test_trigger_failure_degrades(self, tmp_path, monkeypatch):
        prof = profiler.TriggeredProfiler(str(tmp_path), millis=1.0,
                                          min_interval_s=0.0)
        import glt_tpu.obs.profiler as pmod

        def boom(*a, **k):
            raise RuntimeError("profiler backend down")

        monkeypatch.setattr(pmod, "capture", boom)
        assert prof.trigger("doomed") is None           # never raises
        evs = [e for e in flight.recorder().events()
               if e["kind"] == "profiler.error"]
        assert evs and "profiler backend down" in evs[0]["error"]


# ---------------------------------------------------------------------------
# compile watch
# ---------------------------------------------------------------------------

class TestCompileWatch:
    def test_counts_real_compilations_per_label(self):
        assert compilewatch.install()

        @jax.jit
        def f(x):
            return x * 2 + 1

        with compilewatch.label("prog_f"):
            jax.block_until_ready(f(jnp.arange(8.0)))
        n_first = compilewatch.counts("prog_f")
        assert n_first >= 1                    # real backend compiles
        # Cache hit: same shape/dtype compiles nothing new.
        with compilewatch.label("prog_f"):
            jax.block_until_ready(f(jnp.arange(8.0)))
        assert compilewatch.counts("prog_f") == n_first
        snap = metrics.snapshot()
        assert snap["glt.compile.count{program=prog_f}"] == n_first
        assert snap["glt.compile.ms{program=prog_f}.count"] == n_first

    def test_second_epoch_compiles_zero(self):
        # The CI-smoke criterion in miniature: after warmup, a steady
        # loop shows a compile delta of exactly 0.
        assert compilewatch.install()

        @jax.jit
        def step(x):
            return x @ x

        x = jnp.eye(16)
        with compilewatch.label("steady_step"):
            jax.block_until_ready(step(x))     # warm
        before = compilewatch.total_compiles()
        with compilewatch.label("steady_step"):
            for _ in range(4):
                jax.block_until_ready(step(x))
        assert compilewatch.total_compiles() - before == 0

    def test_storm_detection(self):
        # Synthetic injection: the listener seam is jax-global, so we
        # drive _note_compile directly with a deterministic clock.
        for i in range(compilewatch.STORM_K + 1):
            compilewatch._note_compile("churny", 5.0, now=float(i))
        evs = [e for e in flight.recorder().events()
               if e["kind"] == "compile.storm"]
        assert len(evs) == 1                   # reported once per burst
        assert evs[0]["program"] == "churny"
        assert evs[0]["count"] == compilewatch.STORM_K + 1
        snap = metrics.snapshot()
        assert snap["glt.compile.storm{program=churny}"] \
            == compilewatch.STORM_K + 1
        # Still inside the window: no duplicate storm event.
        compilewatch._note_compile("churny", 5.0, now=10.0)
        assert len([e for e in flight.recorder().events()
                    if e["kind"] == "compile.storm"]) == 1

    def test_storm_window_expires(self):
        for i in range(compilewatch.STORM_K + 1):
            compilewatch._note_compile("bursty", 5.0, now=float(i))
        # Far outside the window the burst has drained: a lone compile
        # is healthy and re-arms the reporter.
        compilewatch._note_compile(
            "bursty", 5.0, now=compilewatch.STORM_WINDOW_S * 10)
        for i in range(compilewatch.STORM_K + 1):
            compilewatch._note_compile(
                "bursty", 5.0,
                now=compilewatch.STORM_WINDOW_S * 20 + i)
        assert len([e for e in flight.recorder().events()
                    if e["kind"] == "compile.storm"]) == 2

    def test_first_vs_recompiles(self):
        compilewatch._note_compile("a", 1.0, now=0.0)
        compilewatch._note_compile("b", 1.0, now=0.0)
        compilewatch._note_compile("a", 1.0, now=1.0)
        snap = metrics.snapshot()
        assert snap["glt.compile.first"] == 2
        assert snap["glt.compile.recompiles"] == 1

    def test_storm_ratio_spec_fires(self):
        # First-seen labels count as good; re-compiles burn the SLO.
        spec = compilewatch.storm_ratio_spec(objective=0.10)
        mon = SloMonitor([spec])
        mon.tick(now=0.0)
        compilewatch._note_compile("hot", 1.0, now=0.0)
        for i in range(9):
            compilewatch._note_compile("hot", 1.0, now=float(i))
        fired = mon.tick(now=40.0)
        assert fired and fired[0]["state"] == "firing"
        assert fired[0]["slo"] == "compile_storm"

    def test_wrap_and_nesting(self):
        def inner():
            return compilewatch.current_label()

        assert compilewatch.current_label() == "unlabelled"
        wrapped = compilewatch.wrap(inner, "outer")
        assert wrapped() == "outer"
        with compilewatch.label("a"):
            with compilewatch.label("b"):
                assert compilewatch.current_label() == "b"
            assert compilewatch.current_label() == "a"
        assert compilewatch.current_label() == "unlabelled"


# ---------------------------------------------------------------------------
# postmortem plumbing: summaries + merged capture index
# ---------------------------------------------------------------------------

class TestPostmortem:
    def _dump_with_incidents(self, tmp_path):
        watch = device.LeakWatch(epochs=2)
        for live in (100, 200, 300):
            watch.observe_epoch(live=live)
        for i in range(compilewatch.STORM_K + 1):
            compilewatch._note_compile("churny", 5.0, now=float(i))
        with profiler.capture(str(tmp_path / "cap"), reason="unit"):
            pass
        return flight.recorder().snapshot(reason="test")

    def test_summarize_flight_sections(self, tmp_path):
        snap = self._dump_with_incidents(tmp_path)
        s = summarize_flight(snap)
        assert s["device"]["leak_suspects"] == 1
        assert s["device"]["last_leak"]["live_bytes"] == 300
        assert s["compile"]["storms"] == 1
        assert s["compile"]["storm_programs"] == ["churny"]
        assert [c["reason"] for c in s["captures"]] == ["unit"]
        text = format_flight_summary(s)
        assert "LEAK SUSPECT x1" in text
        assert "RECOMPILE STORM x1" in text
        assert "churny" in text
        assert str(tmp_path / "cap") in text

    def test_summarize_flight_healthy(self):
        flight.record("train.epoch", epoch=0)
        s = summarize_flight(flight.recorder().snapshot(reason="test"))
        assert s["device"]["leak_suspects"] == 0
        assert s["compile"]["storms"] == 0
        assert s["captures"] == []
        text = format_flight_summary(s)
        assert "no leak suspects" in text
        assert "no recompile storms" in text

    def test_cli_summarize_routes_flight_dump(self, tmp_path, capsys):
        from glt_tpu.obs.__main__ import main
        snap = self._dump_with_incidents(tmp_path)
        p = tmp_path / "flight.json"
        p.write_text(json.dumps(snap))
        assert main(["summarize", str(p)]) == 0
        out = capsys.readouterr().out
        assert "LEAK SUSPECT" in out and "RECOMPILE STORM" in out
        assert main(["summarize", str(p), "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["compile"]["storm_programs"] == ["churny"]

    def test_merge_folds_capture_index(self, tmp_path):
        with profiler.capture(str(tmp_path / "c1"), reason="client"):
            pass
        a = flight.recorder().snapshot(reason="test")
        a["role"] = "client"              # two processes' worth of dumps
        flight.recorder().clear()
        with profiler.capture(str(tmp_path / "c2"), reason="server"):
            pass
        b = flight.recorder().snapshot(reason="test")
        b["role"] = "server"
        pa, pb = tmp_path / "a.json", tmp_path / "b.json"
        pa.write_text(json.dumps(a))
        pb.write_text(json.dumps(b))
        merged = merge_flight_dumps([str(pa), str(pb)],
                                    str(tmp_path / "m.json"))
        assert validate_flight_dump(merged) == []
        reasons = {c["reason"] for c in merged["captures"]}
        assert reasons == {"client", "server"}
        # capture_index agrees with the folded list.
        idx = profiler.capture_index(merged["events"])
        assert {c["reason"] for c in idx} == reasons


# ---------------------------------------------------------------------------
# the wired train loop: leak watch + labels fire end-to-end
# ---------------------------------------------------------------------------

class TestTrainLoopWiring:
    def test_scanned_epoch_labels_and_device_hook(self):
        import optax

        from glt_tpu.models import (GraphSAGE, TrainState,
                                    make_scanned_node_train_step,
                                    run_scanned_epoch)
        from glt_tpu.sampler import NeighborSampler
        from tests.test_models import _cluster_dataset

        ds, labels = _cluster_dataset()
        model = GraphSAGE(hidden_features=8, out_features=3,
                          num_layers=2, dropout_rate=0.0)
        tx = optax.adam(1e-2)
        bs, G = 16, 2
        sampler = NeighborSampler(ds.get_graph(), [3, 3], batch_size=bs,
                                  with_edge=False)
        feat = ds.get_node_feature()
        x0 = jnp.zeros((sampler.node_capacity, feat.shape[1]),
                       jnp.float32)
        ei0 = jnp.full((2, sampler.edge_capacity), -1, jnp.int32)
        m0 = jnp.zeros((sampler.edge_capacity,), bool)
        params = model.init({"params": jax.random.PRNGKey(0)},
                            x0, ei0, m0)
        state = TrainState(params=params, opt_state=tx.init(params),
                           step=jnp.zeros((), jnp.int32))
        sstep = make_scanned_node_train_step(model, tx, sampler, feat,
                                             labels, bs)
        run_scanned_epoch(sstep, state, np.arange(40), bs, G,
                          np.random.default_rng(7),
                          jax.random.PRNGKey(3))
        # The jit call site is labelled: compilations landed under the
        # program name, not "unlabelled".
        assert compilewatch.counts("scanned_node_step") >= 1
        snap = metrics.snapshot()
        assert snap["glt.compile.count{program=scanned_node_step}"] >= 1
        # The epoch boundary ran the device hook (gauge exists, 0 =
        # healthy) and fed the spike stream (histogram counted blocks).
        assert snap["glt.device.leak_suspect"] == 0
        assert snap["glt.train.block_ms.count"] >= 1
