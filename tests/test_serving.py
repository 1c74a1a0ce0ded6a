"""glt_tpu.serving tests: coalescer, admission, wire ops, chaos.

Layered like the subsystem: engine unit tests (bucketing, per-request
scatter correctness/isolation on an id-determined ring graph), front
unit tests against a fake engine (coalescing, overload, deadline,
containment — no XLA anywhere), wire tests on a real ``DistServer``
(InferenceClient end-to-end, concurrent serving+training multi-client,
chaos: mid-coalesce disconnect + engine kill), and the per-op RPC
timeout satellite.
"""
import json
import queue
import socket
import threading
import time

import numpy as np
import pytest

from glt_tpu.data import Dataset
from glt_tpu.serving import (
    BadRequest,
    DeadlineExceeded,
    InferenceClient,
    Overloaded,
    ServingDown,
    ServingError,
    ServingFront,
    ServingOptions,
    SubgraphEngine,
)

N = 48
DIM = 4


def build_ring_dataset(n=N, dim=DIM):
    """Ring with out-edges i->i+1, i->i+2 and id-determined features
    (feat[i] == i in every column), so results verify themselves."""
    src = np.repeat(np.arange(n), 2)
    dst = np.concatenate([[(i + 1) % n, (i + 2) % n] for i in range(n)])
    feat = np.arange(n, dtype=np.float32)[:, None] * np.ones((1, dim),
                                                             np.float32)
    labels = np.arange(n, dtype=np.int32) % 3
    return (Dataset()
            .init_graph(np.stack([src, dst]), graph_mode="HOST",
                        num_nodes=n)
            .init_node_features(feat)
            .init_node_labels(labels))


def serving_opts(**kw):
    base = dict(num_neighbors=[2, 2], seed_buckets=(4, 8),
                max_seeds_per_request=4, max_batch_requests=8,
                max_wait_ms=2.0, max_inflight=32,
                default_deadline_ms=60_000.0)
    base.update(kw)
    return ServingOptions(**base)


def check_serving_batch(batch, seeds, n=N):
    """Structural validity of one served Batch on the ring fixture."""
    node = np.asarray(batch.node)
    assert np.asarray(batch.batch).tolist() == list(seeds)
    assert batch.batch_size == len(seeds)
    # Seeds occupy the first batch_size node slots (loader contract).
    assert node[: len(seeds)].tolist() == list(seeds)
    # Features are id-determined: every gathered row matches its id.
    assert np.allclose(np.asarray(batch.x)[:, 0], node.astype(np.float32))
    assert np.asarray(batch.y).tolist() == (node % 3).tolist()
    # Every edge is a real ring edge in message-passing direction
    # (row = neighbor/source side): node[row] - node[col] in {1, 2}.
    ei = np.asarray(batch.edge_index)
    d = (node[ei[0]] - node[ei[1]]) % n
    assert set(d.tolist()) <= {1, 2}, d
    # Isolation: every returned node lies within 2 hops of a seed
    # (forward ring distance <= 4).
    for v in node.tolist():
        assert any((v - s) % n <= 4 for s in seeds), (v, seeds)


# ---------------------------------------------------------------------------
# Engine: bucketing, validation, coalesced scatter correctness
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    return SubgraphEngine(build_ring_dataset(), serving_opts())


class TestEngine:
    def test_validation(self, engine):
        with pytest.raises(BadRequest, match="non-empty"):
            engine.validate_seeds([])
        with pytest.raises(BadRequest, match="lie in"):
            engine.validate_seeds([N + 5])
        with pytest.raises(BadRequest, match="lie in"):
            engine.validate_seeds([-2])
        with pytest.raises(BadRequest, match="exceeds"):
            engine.validate_seeds([0, 1, 2, 3, 4])
        # order-preserving dedup
        assert engine.validate_seeds([7, 3, 7, 3]).tolist() == [7, 3]

    def test_bucket_choice(self, engine):
        assert engine.bucket_for(1) == 4
        assert engine.bucket_for(4) == 4
        assert engine.bucket_for(5) == 8
        with pytest.raises(BadRequest):
            engine.bucket_for(9)

    def test_coalesced_scatter_isolated(self, engine):
        """Three far-apart requests ride one micro-batch; each gets
        exactly its own ego-subgraph back, features verified by id."""
        reqs = [engine.validate_seeds(s)
                for s in ([0], [20, 21], [40, 41, 42])]
        coal = engine.sample(reqs)
        assert coal.bucket == 8          # 6 seeds -> bucket 8
        msgs = engine.scatter(coal)
        assert len(msgs) == 3
        from glt_tpu.distributed.sample_message import message_to_batch

        for msg, seeds in zip(msgs, ([0], [20, 21], [40, 41, 42])):
            check_serving_batch(message_to_batch(msg, to_device=False),
                                seeds)

    def test_shared_nodes_one_draw(self, engine):
        """Overlapping requests share the merged sample: the common
        node's sampled out-edges are identical in both results."""
        reqs = [engine.validate_seeds(s) for s in ([0, 1], [1, 2])]
        msgs = engine.scatter(engine.sample(reqs))

        def edges_from(msg, src):
            node, row, col = msg["node"], msg["row"], msg["col"]
            return sorted(int(node[r]) for r, c in zip(row, col)
                          if int(node[c]) == src)

        assert edges_from(msgs[0], 1) == edges_from(msgs[1], 1)
        for msg, seeds in zip(msgs, ([0, 1], [1, 2])):
            assert msg["node"][: len(seeds)].tolist() == seeds

    def test_bucket_programs_cached(self, engine):
        before = engine.compiled_buckets()
        engine.sample([engine.validate_seeds([3])])
        engine.sample([engine.validate_seeds([9])])
        assert engine.compiled_buckets() == sorted(set(before) | {4})


# ---------------------------------------------------------------------------
# Front: coalescing policy, admission control, deadline, containment.
# A fake engine keeps these pure-threading tests (no XLA, no jax).
# ---------------------------------------------------------------------------

class FakeEngine:
    """Duck-typed SubgraphEngine: validate/sample/scatter, no device."""

    def __init__(self, delay=0.0, buckets=(8,)):
        self.delay = delay
        self.buckets = tuple(buckets)
        self.batches = []

    def validate_seeds(self, seeds):
        arr = np.asarray(seeds, np.int64).ravel()
        if arr.size == 0:
            raise BadRequest("empty")
        return arr.astype(np.int32)

    def compiled_buckets(self):
        return []

    def sample(self, seed_lists, bucket=None):
        if self.delay:
            time.sleep(self.delay)
        self.batches.append([s.copy() for s in seed_lists])
        return seed_lists

    def scatter(self, coal):
        out = []
        for s in coal:
            out.append({
                "node": s.astype(np.int32),
                "row": np.zeros((0,), np.int32),
                "col": np.zeros((0,), np.int32),
                "node_mask": np.ones((s.size,), bool),
                "edge_mask": np.zeros((0,), bool),
                "batch": s.astype(np.int32),
                "#META.batch_size": np.array(s.size, np.int64),
            })
        return out


def make_front(engine, **opt_kw):
    opts = serving_opts(**opt_kw)
    return ServingFront(None, opts, engine=engine)


class TestFront:
    def test_coalesces_queued_burst(self):
        eng = FakeEngine(delay=0.05)
        front = make_front(eng, max_wait_ms=5.0, max_batch_requests=8)
        try:
            first = front.submit([0])
            time.sleep(0.02)           # dispatcher is inside batch 1
            rest = [front.submit([i]) for i in range(1, 5)]
            for p in [first] + rest:
                assert p.done.wait(5.0)
                assert p.error is None
            stats = front.stats()
            assert stats["completed"] == 5
            # the 4 queued-while-busy requests rode one micro-batch
            assert stats["dispatched_batches"] == 2
            assert [len(b) for b in eng.batches] == [1, 4]
        finally:
            front.stop()

    def test_bucket_overflow_leads_next_batch(self):
        eng = FakeEngine(delay=0.05, buckets=(8,))
        front = make_front(eng, max_wait_ms=20.0)
        try:
            front.submit([0])
            time.sleep(0.04)    # past batch 1's 20 ms window, inside its run
            a = front.submit(list(range(1, 7)))    # 6 seeds
            b = front.submit(list(range(10, 14)))  # 4 seeds: 10 > bucket 8
            assert a.done.wait(5.0) and b.done.wait(5.0)
            assert [len(b_) for b_ in eng.batches] == [1, 1, 1]
        finally:
            front.stop()

    def test_overload_rejects_structurally(self):
        eng = FakeEngine(delay=0.3)
        front = make_front(eng, max_inflight=2)
        try:
            front.submit([0])
            time.sleep(0.05)           # dispatcher holds request 1
            front.submit([1])
            front.submit([2])          # queue now full (maxsize 2)
            with pytest.raises(Overloaded) as ei:
                front.submit([3])
            assert ei.value.retry_after_ms is not None
            assert ei.value.retry_after_ms > 0
            assert front.stats()["rejected_overload"] == 1
        finally:
            front.stop()

    def test_deadline_aware_drop(self):
        eng = FakeEngine(delay=0.2)
        front = make_front(eng)
        try:
            a = front.submit([0])
            time.sleep(0.05)
            b = front.submit([1], deadline_ms=10.0)
            assert a.done.wait(5.0) and b.done.wait(5.0)
            assert a.error is None
            assert isinstance(b.error, DeadlineExceeded)
            assert front.stats()["rejected_deadline"] == 1
            # the expired request never reached the engine
            assert all(1 not in [s[0] for s in batch]
                       for batch in eng.batches)
        finally:
            front.stop()

    def test_engine_failure_contained_to_batch(self):
        from glt_tpu.testing.faults import FaultPlan

        plan = FaultPlan(fail_serving_batch=2)
        eng = FakeEngine()
        front = ServingFront(None, serving_opts(), engine=eng,
                             fault_plan=plan)
        try:
            ok1 = front.submit([0])
            assert ok1.done.wait(5.0) and ok1.error is None
            bad = front.submit([1])
            assert bad.done.wait(5.0)
            assert isinstance(bad.error, ServingError)
            assert bad.error.code == "serving_failed"
            # no poisoning: the next micro-batch is served normally
            ok2 = front.submit([2])
            assert ok2.done.wait(5.0) and ok2.error is None
            assert plan.injected_serving_failures == 1
            assert front.stats()["failed"] == 1
        finally:
            front.stop()

    def test_stop_fails_queued_requests(self):
        eng = FakeEngine(delay=0.3)
        front = make_front(eng)
        front.submit([0])
        time.sleep(0.05)
        queued = front.submit([1])
        front.stop()
        assert queued.done.wait(5.0)
        assert isinstance(queued.error, ServingDown)
        with pytest.raises(ServingDown):
            front.submit([2])


# ---------------------------------------------------------------------------
# Wire: InferenceClient against a serving-enabled DistServer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_server():
    from glt_tpu.distributed import init_server

    srv = init_server(build_ring_dataset(), serving=serving_opts())
    # Compile both bucket programs up front so per-test latencies are
    # serving latencies, not XLA compiles.
    srv.serving.engine.warmup()
    yield srv
    srv.shutdown()


def test_subgraph_end_to_end(serving_server):
    cli = InferenceClient(serving_server.addr, timeout=30.0)
    try:
        check_serving_batch(cli.subgraph([5, 7]), [5, 7])
        check_serving_batch(cli.subgraph([30]), [30])
        stats = cli.stats()
        assert stats["enabled"] is True
        assert stats["completed"] >= 2
        assert stats["compiled_buckets"] == [4, 8]
    finally:
        cli.close()


def test_serving_disabled_is_structured():
    from glt_tpu.distributed import init_server
    from glt_tpu.serving import ServingDisabled

    srv = init_server(build_ring_dataset())
    cli = InferenceClient(srv.addr, timeout=5.0)
    try:
        with pytest.raises(ServingDisabled):
            cli.subgraph([1])
        # probe op never needs to catch: enabled=False, no error
        assert cli.stats() == {"enabled": False}
    finally:
        cli.close()
        srv.shutdown()


def test_concurrent_serving_and_training_clients(serving_server):
    """Satellite: N threads with distinct identities drive serving and
    training ops through one DistServer concurrently; per-client results
    stay isolated, and a killed client's producer is lease-reaped."""
    from glt_tpu.distributed import (RemoteNeighborLoader,
                                     RemoteSamplingWorkerOptions,
                                     RemoteServerConnection)

    srv = serving_server
    errors = []
    served = {}

    def serve_worker(idx, seeds_pool):
        try:
            cli = InferenceClient(srv.addr, timeout=30.0)
            got = []
            for s in seeds_pool:
                b = cli.subgraph([s])
                check_serving_batch(b, [s])
                got.append(int(np.asarray(b.batch)[0]))
            served[idx] = got
            cli.close()
        except Exception as e:  # noqa: BLE001 — surfaced by the join
            errors.append(e)

    trained = {}

    def train_worker(idx, lo, hi):
        try:
            loader = RemoteNeighborLoader(
                srv.addr, [2, 2], np.arange(lo, hi), batch_size=6,
                worker_options=RemoteSamplingWorkerOptions(
                    rpc_timeout=60.0))
            seen = []
            for _ in range(2):
                for batch in loader:
                    seen.append(sorted(
                        np.asarray(batch.batch)[:batch.batch_size]
                        .tolist()))
            trained[idx] = seen
            loader.shutdown()
        except Exception as e:  # noqa: BLE001 — surfaced by the join
            errors.append(e)

    threads = [
        threading.Thread(target=serve_worker, args=(0, range(0, 10))),
        threading.Thread(target=serve_worker, args=(1, range(20, 30))),
        threading.Thread(target=serve_worker, args=(2, range(40, 48))),
        threading.Thread(target=train_worker, args=(0, 0, 24)),
        threading.Thread(target=train_worker, args=(1, 24, 48)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert errors == []
    # serving isolation: every client got exactly its own seeds back
    assert served[0] == list(range(0, 10))
    assert served[1] == list(range(20, 30))
    assert served[2] == list(range(40, 48))
    # training isolation: each loader delivered exactly its own seed
    # partition, every epoch (2 epochs x 4 batches of 6)
    for idx, (lo, hi) in ((0, (0, 24)), (1, (24, 48))):
        flat = sorted(s for ep in trained[idx] for s in ep)
        assert flat == sorted(list(range(lo, hi)) * 2)

    # killed client: create a producer with a short lease and vanish
    # without destroy; the reaper collects it (mp fleet included).
    conn = RemoteServerConnection(srv.addr, timeout=10.0)
    before = srv.live_producers()
    conn.request(op="create_sampling_producer", num_neighbors=[2],
                 input_nodes=list(range(12)), batch_size=6,
                 lease_secs=0.4, client_key="doomed-client")
    assert srv.live_producers() == before + 1
    conn.close()                      # "crash": no destroy op
    deadline = time.monotonic() + 10.0
    while srv.live_producers() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert srv.live_producers() == before


# ---------------------------------------------------------------------------
# Chaos (satellite): disconnects and engine faults degrade structurally
# ---------------------------------------------------------------------------

def test_chaos_mid_coalesce_disconnect(serving_server):
    """A client that vanishes after submitting must not poison its
    co-batched neighbors: the batch completes, the live client's result
    is correct, and the server keeps serving."""
    from glt_tpu.distributed.dist_server import _KIND_JSON, send_frame

    srv = serving_server
    front = srv.serving
    old_wait = front.options.max_wait_ms
    front.options.max_wait_ms = 300.0   # hold the batch open for riders
    try:
        before = front.stats()
        raw = socket.create_connection(srv.addr, timeout=10)
        send_frame(raw, _KIND_JSON, json.dumps(
            {"op": "subgraph_request", "seeds": [3],
             "deadline_ms": 60_000}).encode())
        raw.close()                    # vanish mid-coalesce
        cli = InferenceClient(srv.addr, timeout=30.0)
        try:
            t0 = time.monotonic()
            check_serving_batch(cli.subgraph([20]), [20])
            # both requests completed server-side, in ONE micro-batch
            deadline = time.monotonic() + 5.0
            while (front.stats()["completed"] < before["completed"] + 2
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            after = front.stats()
            assert after["completed"] == before["completed"] + 2
            assert (after["dispatched_batches"]
                    == before["dispatched_batches"] + 1)
            assert time.monotonic() - t0 < 5.0
            # the server is alive and still serving
            check_serving_batch(cli.subgraph([10]), [10])
        finally:
            cli.close()
    finally:
        front.options.max_wait_ms = old_wait


def test_chaos_engine_failure_under_load():
    """An engine fault mid-batch under concurrent load fails exactly
    that micro-batch's requests with structured errors; co-arriving and
    later requests are served normally (no poisoning)."""
    from glt_tpu.distributed import init_server
    from glt_tpu.testing.faults import FaultPlan

    plan = FaultPlan(fail_serving_batch=2)
    srv = init_server(build_ring_dataset(), fault_plan=plan,
                      serving=serving_opts(max_wait_ms=150.0))
    try:
        warm = InferenceClient(srv.addr, timeout=60.0)
        check_serving_batch(warm.subgraph([0]), [0])   # batch 1 (compile)

        results, failures = [], []

        def worker(seed):
            cli = InferenceClient(srv.addr, timeout=60.0)
            try:
                b = cli.subgraph([seed])
                check_serving_batch(b, [seed])
                results.append(seed)
            except ServingError as e:
                failures.append((seed, e.code))
            finally:
                cli.close()

        threads = [threading.Thread(target=worker, args=(s,))
                   for s in (8, 16, 24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        # exactly one micro-batch was killed; its riders got structured
        # serving_failed errors, everyone else was served
        assert plan.injected_serving_failures == 1
        assert len(failures) >= 1
        assert all(code == "serving_failed" for _, code in failures)
        assert len(results) + len(failures) == 3
        assert srv.serving.stats()["failed"] == len(failures)
        # no poisoning: the very next request is served cleanly
        check_serving_batch(warm.subgraph([30]), [30])
        warm.close()
    finally:
        srv.shutdown()


def test_overload_and_deadline_over_wire():
    """Structured Overloaded (with retry-after hint) and deadline drops
    round-trip the wire as typed exceptions; the polite retry loop
    eventually lands."""
    from glt_tpu.distributed import init_server

    srv = init_server(build_ring_dataset(),
                      serving=serving_opts(max_inflight=1,
                                           max_wait_ms=1.0))
    # Swap in a slow fake engine BEFORE any request: these tests are
    # about admission + SLO plumbing, not sampling.
    srv.serving.engine = FakeEngine(delay=0.4)
    try:
        outcomes = queue.Queue(maxsize=8)

        def fire(seed, timeout):
            cli = InferenceClient(srv.addr, timeout=timeout)
            try:
                cli.subgraph([seed], timeout=timeout)
                outcomes.put((seed, "ok"))
            except ServingError as e:
                outcomes.put((seed, e.code, e.retry_after_ms))
            finally:
                cli.close()

        t1 = threading.Thread(target=fire, args=(0, 30.0))
        t1.start()
        time.sleep(0.1)                 # engine now busy with seed 0
        t2 = threading.Thread(target=fire, args=(1, 30.0))
        t2.start()
        time.sleep(0.1)                 # queue (maxsize 1) now full
        t3 = threading.Thread(target=fire, args=(2, 30.0))
        t3.start()
        for t in (t1, t2, t3):
            t.join(timeout=30)
            assert not t.is_alive()
        got = {}
        while not outcomes.empty():
            item = outcomes.get_nowait()
            got[item[0]] = item[1:]
        assert got[0] == ("ok",)
        assert got[1] == ("ok",)
        assert got[2][0] == "overloaded"
        assert got[2][1] is not None and got[2][1] > 0
        # deadline-aware drop over the wire: impossible budget while
        # the engine is busy -> typed DeadlineExceeded
        busy = threading.Thread(target=fire, args=(3, 30.0))
        busy.start()
        time.sleep(0.1)
        cli = InferenceClient(srv.addr, timeout=30.0)
        with pytest.raises(DeadlineExceeded):
            cli.subgraph([4], timeout=0.05)
        busy.join(timeout=30)
        # polite retry: honors retry_after and eventually succeeds
        b = cli.subgraph_with_retry([5], timeout=30.0, attempts=10)
        assert np.asarray(b.batch).tolist() == [5]
        cli.close()
        assert srv.serving.stats()["rejected_overload"] >= 1
        assert srv.serving.stats()["rejected_deadline"] >= 1
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Per-op RPC timeout (satellite) + serving metrics
# ---------------------------------------------------------------------------

def test_per_op_rpc_timeout():
    """A latency-sensitive op can bound its socket wait far below the
    connection's rpc_timeout — and the default is restored afterwards."""
    from glt_tpu.distributed import RemoteServerConnection

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", 0))
    listener.listen(2)
    try:
        conn = RemoteServerConnection(listener.getsockname(),
                                      timeout=60.0, max_retries=0)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="exchange failed"):
            conn.request(op="get_dataset_meta", _timeout=0.25)
        elapsed = time.monotonic() - t0
        assert elapsed < 5.0, f"per-op timeout not applied ({elapsed:.1f}s)"
        conn.close()
    finally:
        listener.close()


def test_per_op_timeout_restores_default(serving_server):
    """After a tight-timeout op succeeds, the connection's default
    rpc_timeout is back for later (training-path) ops."""
    from glt_tpu.distributed import RemoteServerConnection

    conn = RemoteServerConnection(serving_server.addr, timeout=60.0)
    try:
        assert conn.request(op="serving_stats",
                            _timeout=5.0)["enabled"] is True
        assert conn.sock.gettimeout() == 60.0
        meta = conn.request(op="get_dataset_meta")
        assert meta["num_nodes"] == N
    finally:
        conn.close()


def test_serving_metrics_namespace(serving_server):
    """glt.serving.* histograms cover the whole path: queue wait,
    coalesce width, batch, scatter, e2e — with derived SLO quantiles."""
    from glt_tpu.obs import metrics

    metrics.enable()
    try:
        before = metrics.snapshot()
        cli = InferenceClient(serving_server.addr, timeout=30.0)
        for s in (2, 12, 22):
            cli.subgraph([s])
        cli.close()
        snap = metrics.snapshot()

        def delta(name):
            return snap.get(name, 0.0) - before.get(name, 0.0)

        for stage in ("queue_wait_ms", "batch_ms", "scatter_ms",
                      "e2e_ms", "client_ms"):
            assert delta(f"glt.serving.{stage}.count") >= 3, stage
        assert delta("glt.serving.coalesce_width.count") >= 1
        assert delta("glt.serving.requests") >= 3
        assert snap["glt.serving.e2e_ms.p50"] <= snap[
            "glt.serving.e2e_ms.p99"]
        # Prometheus exposition carries the namespace
        text = serving_server.metrics_text()
        assert "glt_serving_e2e_ms_bucket" in text
        assert "glt_serving_requests_total" in text
    finally:
        metrics.disable()
