"""Server-client deployment tests (cf. test_dist_neighbor_loader.py's
server-client topology, :173-371): real sockets, real producer threads."""
import glob
import json
import multiprocessing
import os
import socket
import struct
import threading

import numpy as np
import pytest

from glt_tpu.distributed.dist_client import RemoteNeighborLoader
from glt_tpu.distributed.dist_server import init_server
from tests.test_dist_loader import N, build_ring_dataset, check_batch


@pytest.fixture(scope="module")
def server():
    ds = build_ring_dataset()
    srv = init_server(ds)
    yield srv
    srv.shutdown()


def test_meta(server):
    from glt_tpu.distributed.dist_client import RemoteServerConnection

    conn = RemoteServerConnection(server.addr)
    meta = conn.request(op="get_dataset_meta")
    assert meta["num_nodes"] == N
    assert meta["server_rank"] == 0 and meta["num_servers"] == 1
    conn.close()


def test_dist_context_roles():
    """Role/rank/fleet bookkeeping (cf. dist_context.py:20-183)."""
    from glt_tpu.distributed import (DistRole, get_context,
                                     init_client_context,
                                     init_worker_group)

    ctx = init_worker_group(world_size=4, rank=2)
    assert get_context() is ctx
    assert ctx.is_worker() and not ctx.is_server()
    assert ctx.num_servers() == 0 and ctx.num_clients() == 0
    assert ctx.worker_name == "_default_worker-2"

    ctx = init_client_context(num_clients=2, client_rank=1, num_servers=2)
    assert ctx.role == DistRole.CLIENT
    assert ctx.num_servers() == 2 and ctx.num_clients() == 2
    assert ctx.global_world_size == 4 and ctx.global_rank == 3

    with pytest.raises(ValueError, match="rank"):
        init_worker_group(world_size=2, rank=2)


def test_get_metrics_exposition(server):
    """The observability hook (ISSUE 6): ``get_metrics`` serves the
    Prometheus text exposition of the unified glt.* namespace, with the
    live-producer gauge refreshed at scrape time."""
    from glt_tpu.distributed.dist_client import RemoteServerConnection
    from glt_tpu.obs import metrics

    metrics.enable()
    try:
        conn = RemoteServerConnection(server.addr)
        loader = RemoteNeighborLoader(server.addr, [2, 2], np.arange(N),
                                      batch_size=6, prefetch=2)
        try:
            for batch in loader:
                check_batch(batch)
            resp = conn.request(op="get_metrics")
            assert resp["enabled"] is True
            text = resp["text"]
            assert text == server.metrics_text() or "glt_server" in text
            assert "# TYPE glt_server_requests_total counter" in text
            assert 'glt_server_requests_total{op="get_metrics"}' in text
            assert "glt_server_messages_sent_total" in text
            assert "# TYPE glt_server_live_producers gauge" in text
            # the producer we created is live and visible in the gauge
            assert "glt_server_live_producers 1.0" in text
            assert "glt_remote_batches_received_total" in text
        finally:
            loader.shutdown()
            conn.close()
    finally:
        metrics.disable()


def test_remote_loader_epochs(server):
    loader = RemoteNeighborLoader(server.addr, [2, 2], np.arange(N),
                                  batch_size=6, prefetch=2)
    try:
        assert len(loader) == 4
        for epoch in range(2):
            seen = []
            for batch in loader:
                check_batch(batch)
                seen.extend(
                    np.asarray(batch.batch)[:batch.batch_size].tolist())
            assert sorted(seen) == list(range(N))
    finally:
        loader.shutdown()


def test_server_mp_producer_pool():
    """Server-side producer fan-out (cf. dist_server.py:83-116): the
    server spawns an mp worker fleet per producer when the client asks for
    num_workers > 0, streaming over one shm ring into the bounded buffer."""
    from glt_tpu.distributed import RemoteSamplingWorkerOptions

    ds = build_ring_dataset()
    srv = init_server(ds, dataset_builder=build_ring_dataset)
    loader = RemoteNeighborLoader(
        srv.addr, [2, 2], np.arange(N), batch_size=6,
        worker_options=RemoteSamplingWorkerOptions(
            num_workers=2, buffer_capacity=4,
            channel_capacity_bytes=1 << 20))
    try:
        for epoch in range(2):
            seen = []
            for batch in loader:
                check_batch(batch)
                seen.extend(
                    np.asarray(batch.batch)[:batch.batch_size].tolist())
            assert sorted(seen) == list(range(N))
    finally:
        loader.shutdown()
        srv.shutdown()


def test_server_mp_producer_needs_builder(server):
    """num_workers > 0 against a server without a picklable builder must
    surface as an error, not a silent fallback."""
    from glt_tpu.distributed import RemoteSamplingWorkerOptions

    with pytest.raises(RuntimeError, match="dataset_builder"):
        RemoteNeighborLoader(
            server.addr, [2], np.arange(N), batch_size=6,
            worker_options=RemoteSamplingWorkerOptions(num_workers=2))


def test_client_prefetch_bounded(server, monkeypatch):
    """A slow trainer holds at most prefetch_size unconsumed messages —
    the client queue must not buffer the whole epoch (VERDICT r2 weak #4;
    the reference bounds this at prefetch_size=4, remote_channel.py:24)."""
    import queue
    import time

    from glt_tpu.distributed import RemoteSamplingWorkerOptions
    from glt_tpu.distributed import dist_client as dc

    # Capture the prefetch queue the loader builds (production code keeps
    # no test hooks).
    made = []
    real_queue = queue.Queue

    def capturing_queue(*a, **kw):
        q = real_queue(*a, **kw)
        made.append(q)
        return q

    monkeypatch.setattr(dc.queue, "Queue", capturing_queue)
    loader = RemoteNeighborLoader(
        server.addr, [2], np.arange(N), batch_size=2,
        worker_options=RemoteSamplingWorkerOptions(prefetch_size=2))
    try:
        it = iter(loader)
        first = next(it)
        check_batch(first)
        assert made, "loader did not build its prefetch queue"
        buf = made[-1]
        # Let the prefetcher run ahead until the bounded queue is full
        # (2s deadline only bounds a broken implementation).
        deadline = time.monotonic() + 2.0
        while not buf.full() and time.monotonic() < deadline:
            time.sleep(0.05)
        # 12 batches total; with depth 2 the client may hold the yielded
        # one + 2 queued + 1 in-flight put — far fewer than the epoch.
        assert buf.qsize() <= 2
        for batch in it:
            check_batch(batch)
    finally:
        loader.shutdown()


def test_abandoned_epoch_restarts(server):
    """A client that abandons an epoch mid-way (early stopping) must be
    able to start the next epoch: start_epoch signals the wedged producer
    thread to stop before joining it."""
    from glt_tpu.distributed import RemoteSamplingWorkerOptions

    loader = RemoteNeighborLoader(
        server.addr, [2], np.arange(N), batch_size=2,
        worker_options=RemoteSamplingWorkerOptions(prefetch_size=1,
                                                   buffer_capacity=1))
    try:
        it = iter(loader)
        check_batch(next(it))  # consume one batch, abandon the rest
        it.close()
        seen = []
        for batch in loader:  # fresh epoch must start promptly
            check_batch(batch)
            seen.extend(np.asarray(batch.batch)[:batch.batch_size].tolist())
        assert sorted(seen) == list(range(N))
    finally:
        loader.shutdown()


def test_two_servers_two_clients():
    """2-servers x 2-clients topology (cf. the reference's server-client
    tests, test_dist_neighbor_loader.py:173-371): each server owns a
    disjoint seed partition of the shared graph; each client consumes from
    its own server; the union of delivered batches covers every seed
    exactly once, and every batch verifies against the id-determined
    fixture."""
    servers = [init_server(build_ring_dataset(), num_servers=2,
                           server_rank=r, num_clients=2)
               for r in range(2)]
    assert servers[1].context.is_server()
    assert servers[1].context.num_servers() == 2
    assert servers[1].context.num_clients() == 2
    assert servers[1].context.worker_name == "_default_server-1"
    halves = [np.arange(0, N // 2), np.arange(N // 2, N)]
    loaders = [
        RemoteNeighborLoader(srv.addr, [2, 2], seeds, batch_size=4)
        for srv, seeds in zip(servers, halves)
    ]
    try:
        seen = [[], []]
        iters = [iter(ld) for ld in loaders]
        # Interleave consumption so both server pipelines are live at once.
        for _ in range(len(loaders[0])):
            for c, it in enumerate(iters):
                batch = next(it)
                check_batch(batch)
                seen[c].extend(
                    np.asarray(batch.batch)[:batch.batch_size].tolist())
        assert sorted(seen[0]) == halves[0].tolist()
        assert sorted(seen[1]) == halves[1].tolist()
        assert sorted(seen[0] + seen[1]) == list(range(N))
    finally:
        for ld in loaders:
            ld.shutdown()
        for srv in servers:
            srv.shutdown()


# ---------------------------------------------------------------------------
# Distributed tracing (ISSUE 7 tentpole): per-process traces, clock-aligned
# merge, server stage histograms, mixed-version compatibility.
# ---------------------------------------------------------------------------

def _traced_server_proc(trace_dir, q, num_workers):
    """Subprocess body: a sampling server with per-process tracing on
    (GLT_OBS_TRACE_DIR), exporting its trace file at shutdown."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["GLT_OBS_TRACE_DIR"] = trace_dir
    import jax

    jax.config.update("jax_platforms", "cpu")
    from glt_tpu.distributed.dist_server import init_server as _init
    from tests.test_dist_loader import build_ring_dataset as _build

    srv = _init(_build(),
                dataset_builder=_build if num_workers else None)
    q.put(srv.addr)
    srv.wait_for_exit(timeout=120)
    srv.shutdown()          # exports trace-server-<pid>.json


def _run_traced_fleet(tmp_path, monkeypatch, num_workers):
    """Client (this process) + server (subprocess) [+ mp workers] with
    tracing on everywhere; returns (trace files, merged trace, client
    epoch trace id)."""
    from glt_tpu import obs
    from glt_tpu.distributed import RemoteSamplingWorkerOptions

    trace_dir = str(tmp_path)
    monkeypatch.setenv("GLT_OBS_TRACE_DIR", trace_dir)
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    # Non-daemonic: the mp-worker variant needs the server process to
    # spawn children of its own; the finally below reaps it regardless.
    proc = ctx.Process(target=_traced_server_proc,
                       args=(trace_dir, q, num_workers), daemon=False)
    proc.start()
    try:
        addr = tuple(q.get(timeout=120))
        loader = RemoteNeighborLoader(
            addr, [2, 2], np.arange(N), batch_size=6,
            worker_options=RemoteSamplingWorkerOptions(
                num_workers=num_workers,
                channel_capacity_bytes=1 << 20))
        seen = []
        for batch in loader:
            check_batch(batch)
            seen.extend(
                np.asarray(batch.batch)[:batch.batch_size].tolist())
        assert sorted(seen) == list(range(N))
        tracer = obs.current()
        assert tracer is not None     # auto-installed by GLT_OBS_TRACE_DIR
        epoch_ev = next(e for e in tracer.events
                        if e["name"] == "remote.epoch")
        epoch_tid = epoch_ev["args"]["trace_id"]
        loader.shutdown(exit_server=True)   # exports the client trace too
        proc.join(timeout=60)
        assert proc.exitcode == 0
    finally:
        obs.install(None)
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout=10)
    files = sorted(glob.glob(os.path.join(trace_dir, "trace-*.json")))
    merged = obs.merge_traces(files)
    return files, merged, epoch_tid


def _sync_error_bound_us(merged) -> float:
    """What the merge's offsets can be wrong by: for each (process, peer)
    pair half the smallest round trip ``(t3 - t0) - (t2 - t1)`` among its
    ``obs.clock_sync`` samples (the NTP bound on the sample
    ``obs/merge.py`` picks), summed over the pairs because offsets
    compose."""
    best = {}
    for ev in merged["traceEvents"]:
        if ev.get("name") != "obs.clock_sync":
            continue
        a = ev["args"]
        delta = ((float(a["t3_us"]) - float(a["t0_us"]))
                 - (float(a["t2_us"]) - float(a["t1_us"])))
        pair = (ev["pid"], int(a["peer_pid"]))
        best[pair] = min(best.get(pair, float("inf")), max(delta, 0.0))
    assert best, "no clock-sync sample in the merged trace"
    return sum(best.values()) / 2.0


def test_distributed_trace_merge_end_to_end(tmp_path, monkeypatch):
    """ISSUE 7 acceptance: a remote-sampling run exports per-process
    traces that `obs merge` stitches into one valid Chrome trace, with
    client request spans parenting server stage spans after clock
    alignment."""
    from glt_tpu import obs

    files, merged, epoch_tid = _run_traced_fleet(tmp_path, monkeypatch,
                                                 num_workers=0)
    roles = {os.path.basename(f).split("-")[1] for f in files}
    assert {"client", "server"} <= roles      # one file per process
    assert obs.validate_chrome_trace(merged) == []
    # Server stage spans nest inside the client fetch spans that caused
    # them, within the alignment's own error bound: a loaded machine
    # stretches every sampled round trip, and the bound with it.
    assert obs.span_tree_check(
        merged, tol_us=5_000.0 + _sync_error_bound_us(merged)) == []
    by_name = {}
    for ev in merged["traceEvents"]:
        by_name.setdefault(ev.get("name"), []).append(ev)
    # One causally-linked tree: client epoch/fetch spans, server request
    # + producer spans all tagged with the SAME trace id.
    assert any(e["args"].get("trace_id") == epoch_tid
               for e in by_name.get("server.fetch", []))
    assert any(e["args"].get("trace_id") == epoch_tid
               for e in by_name.get("producer.sample_batch", []))
    # The clock offset was actually estimated (exact-0 for every file
    # would mean no sync samples were exchanged).
    offsets = merged["glt"]["clock_offsets_us"]
    assert len(offsets) == len(files)
    assert merged["glt"]["unaligned_pids"] == []


@pytest.mark.slow
def test_distributed_trace_merge_with_mp_workers(tmp_path, monkeypatch):
    """Full client -> server -> mp-worker chain: the worker's trace file
    joins the merge through one-way shm clock samples (transitive
    alignment worker -> server -> client)."""
    from glt_tpu import obs

    files, merged, epoch_tid = _run_traced_fleet(tmp_path, monkeypatch,
                                                 num_workers=1)
    roles = {os.path.basename(f).split("-")[1] for f in files}
    assert {"client", "server", "worker0"} <= roles
    assert obs.validate_chrome_trace(merged) == []
    assert merged["glt"]["unaligned_pids"] == []
    worker_spans = [e for e in merged["traceEvents"]
                    if e.get("name") == "worker.sample_batch"]
    assert worker_spans
    assert any(e["args"].get("trace_id") == epoch_tid
               for e in worker_spans)


def test_server_stage_histograms(server):
    """ISSUE 7 acceptance: glt.server.* stage histograms with derived
    p50/p95/p99 in snapshot() and buckets in metrics_text()."""
    from glt_tpu.obs import metrics

    metrics.enable()
    try:
        loader = RemoteNeighborLoader(server.addr, [2, 2], np.arange(N),
                                      batch_size=6)
        try:
            for batch in loader:
                check_batch(batch)
            snap = metrics.snapshot()
            for stage in ("queue_wait", "sample", "serialize", "send"):
                name = f"glt.server.{stage}_ms"
                assert snap[f"{name}.count"] >= len(loader), name
                for p in ("p50", "p95", "p99"):
                    assert f"{name}.{p}" in snap, f"{name}.{p}"
                assert snap[f"{name}.p50"] <= snap[f"{name}.p99"]
            text = server.metrics_text()
            assert "# TYPE glt_server_queue_wait_ms histogram" in text
            assert "glt_server_sample_ms_bucket" in text
            assert "glt_server_send_ms_count" in text
        finally:
            loader.shutdown()
    finally:
        metrics.disable()


def test_old_client_against_traced_server():
    """Mixed-version (ISSUE 7 satellite): a pre-trace client — requests
    WITHOUT the #trace key — against a tracing server must receive
    byte-compatible frames: no trailer, payload parses with the old
    code path verbatim."""
    from glt_tpu import obs
    from glt_tpu.channel.serialization import deserialize
    from glt_tpu.distributed.dist_server import (_KIND_JSON, _KIND_MSG,
                                                 recv_frame, send_frame)

    srv = init_server(build_ring_dataset())
    obs.start_trace(process_name="server")     # server side IS tracing
    try:
        raw = socket.create_connection(srv.addr, timeout=10)
        raw.settimeout(10)
        try:
            def old_request(**req):
                send_frame(raw, _KIND_JSON, json.dumps(req).encode())
                return recv_frame(raw)

            kind, data = old_request(op="create_sampling_producer",
                                     num_neighbors=[2],
                                     input_nodes=list(range(N)),
                                     batch_size=6)
            assert kind == _KIND_JSON
            resp = json.loads(data)
            pid = resp["producer_id"]
            # old peers must not even see the echo key in JSON responses
            kind, data = old_request(op="start_new_epoch_sampling",
                                     producer_id=pid, epoch=1)
            assert "#trace" not in json.loads(data)
            kind, data = old_request(op="fetch_one_sampled_message",
                                     producer_id=pid, epoch=1, ack=-1)
            assert kind == _KIND_MSG
            # exact OLD parsing: u64 seq + serialized message, with no
            # trailer appended (the magic footer must be absent).
            assert not data.endswith(b"GLTT")
            seq = struct.unpack_from("<Q", data, 0)[0]
            assert seq == 0
            msg = deserialize(memoryview(data)[8:])
            assert "node" in msg
            old_request(op="destroy_sampling_producer", producer_id=pid)
        finally:
            raw.close()
    finally:
        obs.install(None)
        srv.shutdown()


def _old_style_server(listener, canned, stop):
    """A pre-PR-7 server: reads only the JSON keys it knows (any extra
    key — #trace included — is ignored), never sends an echo/trailer."""
    from glt_tpu.distributed.dist_server import (_KIND_JSON, _KIND_MSG,
                                                 recv_frame, send_frame)

    while not stop.is_set():
        try:
            conn, _ = listener.accept()
        except OSError:
            return
        with conn:
            seq = 0
            while True:
                kind, data = recv_frame(conn)
                if kind is None:
                    break
                req = json.loads(data)
                op = req["op"]       # old code: known keys only
                if op == "create_sampling_producer":
                    send_frame(conn, _KIND_JSON, json.dumps(
                        {"producer_id": 0,
                         "num_expected": len(canned)}).encode())
                elif op == "fetch_one_sampled_message":
                    send_frame(conn, _KIND_MSG,
                               struct.pack("<Q", seq) + canned[seq])
                    seq += 1
                else:
                    send_frame(conn, _KIND_JSON, b'{"ok": true}')
                    if op == "destroy_sampling_producer":
                        return


def test_new_traced_client_against_old_server():
    """Mixed-version (ISSUE 7 satellite): a tracing client sends #trace;
    an old server ignores unknown JSON keys and answers plain frames —
    the run degrades to untraced operation, not a ProtocolError."""
    from glt_tpu import obs
    from glt_tpu.distributed.dist_server import _Producer

    # Real sampled messages so message_to_batch round-trips.
    ds = build_ring_dataset()
    prod = _Producer(ds, [2, 2], np.arange(12), 6)
    prod.start_epoch(1)
    canned = [prod.fetch_next(-1, 1)[1] for _ in range(2)]
    prod.stop()

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", 0))
    listener.listen(4)
    stop = threading.Event()
    t = threading.Thread(target=_old_style_server,
                         args=(listener, canned, stop), daemon=True)
    t.start()
    tracer = obs.start_trace(process_name="client")
    try:
        loader = RemoteNeighborLoader(listener.getsockname(), [2, 2],
                                      np.arange(12), batch_size=6)
        batches = list(loader)
        assert len(batches) == 2
        for b in batches:
            check_batch(b)
        loader.shutdown()
        # Degraded, not broken: spans exist client-side, but no clock
        # sync ever completed (the old server echoed nothing).
        names = {e["name"] for e in tracer.events}
        assert "remote.fetch" in names
        assert "obs.clock_sync" not in names
    finally:
        obs.install(None)
        stop.set()
        listener.close()
        t.join(timeout=10)


def test_flight_dump_wire_op(server, tmp_path):
    """ISSUE 13: ``flight_dump`` pulls the server's black box over the
    wire — the same ``glt_flight`` object the crash-time dump writes,
    so ``obs merge`` folds it with client-side dumps."""
    from glt_tpu.distributed.dist_client import RemoteServerConnection
    from glt_tpu.obs.flight import is_flight_dump, validate_flight_dump

    conn = RemoteServerConnection(server.addr)
    try:
        snap = conn.flight_dump()
        assert is_flight_dump(snap)
        assert validate_flight_dump(snap) == []
        assert snap["reason"] == "wire_op"
        kinds = [e["kind"] for e in snap["events"]]
        assert "server.flight_dump_served" in kinds
        # Optional server-side artifact beside the wire reply.
        p = tmp_path / "srv_flight.json"
        resp = conn.request(op="flight_dump", path=str(p))
        assert resp["flight"]["path"] == str(p)
        with open(p) as f:
            assert validate_flight_dump(json.load(f)) == []
    finally:
        conn.close()


def test_old_client_flight_dump_against_new_server(server):
    """Mixed-version (ISSUE 13 satellite): a pre-13 client never sends
    the op, but an operator's plain-JSON poke — no #trace, no helper —
    must get the dump back as ordinary JSON: nothing about the black
    box requires a new client."""
    from glt_tpu.distributed.dist_server import (_KIND_JSON, recv_frame,
                                                 send_frame)
    from glt_tpu.obs.flight import is_flight_dump

    raw = socket.create_connection(server.addr, timeout=10)
    raw.settimeout(10)
    try:
        send_frame(raw, _KIND_JSON, json.dumps({"op": "flight_dump"}).encode())
        kind, data = recv_frame(raw)
        assert kind == _KIND_JSON
        resp = json.loads(data)
        assert is_flight_dump(resp["flight"])
        assert "#trace" not in resp
    finally:
        raw.close()


def test_new_client_flight_dump_against_old_server():
    """Mixed-version (ISSUE 13 satellite): a pre-13 server answers the
    unknown op with its structured fatal error and closes — the client
    helper degrades to None ("no black box available"), never a raised
    failure mode on the postmortem path."""
    from glt_tpu.distributed.dist_client import RemoteServerConnection
    from glt_tpu.distributed.dist_server import (_KIND_JSON, recv_frame,
                                                 send_frame)

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)

    def old_server():
        conn, _ = listener.accept()
        with conn:
            kind, data = recv_frame(conn)
            op = json.loads(data)["op"]
            # pre-13 _handle: unknown op -> fatal error, then close.
            send_frame(conn, _KIND_JSON, json.dumps(
                {"error": f"unknown op {op!r}", "code": "fatal"}).encode())

    t = threading.Thread(target=old_server, daemon=True)
    t.start()
    conn = RemoteServerConnection(listener.getsockname())
    try:
        assert conn.flight_dump() is None
        assert conn.broken        # reconnects on next use
    finally:
        conn.close()
        listener.close()
        t.join(timeout=10)


def test_profile_capture_wire_op(server, tmp_path):
    """ISSUE 14: ``profile_capture`` runs a bounded jax.profiler trace
    on the SERVER host and returns the capture dir — a real trace lands
    on disk, and the server's flight ring indexes the incident."""
    from glt_tpu.distributed.dist_client import RemoteServerConnection

    conn = RemoteServerConnection(server.addr)
    cap_dir = str(tmp_path / "srv_capture")
    try:
        resp = conn.profile_capture(dir=cap_dir, millis=10.0)
        assert resp is not None and resp["ok"]
        assert resp["dir"] == cap_dir
        # Real capture artifacts, not just a polite reply.
        files = [os.path.join(root, f)
                 for root, _, fs in os.walk(cap_dir) for f in fs]
        assert any(f.endswith(".xplane.pb") for f in files), files
        # Indexed in the server's black box.
        snap = conn.flight_dump()
        kinds = [e["kind"] for e in snap["events"]]
        assert "server.profile_capture_served" in kinds
        assert "profiler.capture" in kinds
    finally:
        conn.close()


def test_old_client_profile_capture_against_new_server(server, tmp_path):
    """Mixed-version (ISSUE 14 satellite): an operator's plain-JSON
    poke — no helper, no #trace — gets the capture dir back as ordinary
    JSON: nothing about triggered profiling requires a new client."""
    from glt_tpu.distributed.dist_server import (_KIND_JSON, recv_frame,
                                                 send_frame)

    cap_dir = str(tmp_path / "poke_capture")
    raw = socket.create_connection(server.addr, timeout=10)
    raw.settimeout(30)
    try:
        send_frame(raw, _KIND_JSON, json.dumps(
            {"op": "profile_capture", "dir": cap_dir,
             "millis": 10.0}).encode())
        kind, data = recv_frame(raw)
        assert kind == _KIND_JSON
        resp = json.loads(data)
        assert resp["ok"] and resp["dir"] == cap_dir
        assert "#trace" not in resp
        assert os.path.isdir(cap_dir)
    finally:
        raw.close()


def test_new_client_profile_capture_against_old_server():
    """Mixed-version (ISSUE 14 satellite): a pre-14 server answers the
    unknown op with its structured fatal error and closes — the client
    helper degrades to None ("no capture available"), never a raised
    failure mode on the incident path."""
    from glt_tpu.distributed.dist_client import RemoteServerConnection
    from glt_tpu.distributed.dist_server import (_KIND_JSON, recv_frame,
                                                 send_frame)

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)

    def old_server():
        conn, _ = listener.accept()
        with conn:
            kind, data = recv_frame(conn)
            op = json.loads(data)["op"]
            # pre-14 _handle: unknown op -> fatal error, then close.
            send_frame(conn, _KIND_JSON, json.dumps(
                {"error": f"unknown op {op!r}", "code": "fatal"}).encode())

    t = threading.Thread(target=old_server, daemon=True)
    t.start()
    conn = RemoteServerConnection(listener.getsockname())
    try:
        assert conn.profile_capture(millis=10.0) is None
        assert conn.broken        # reconnects on next use
    finally:
        conn.close()
        listener.close()
        t.join(timeout=10)


def test_two_clients_same_server(server):
    l1 = RemoteNeighborLoader(server.addr, [2], np.arange(0, 12),
                              batch_size=6)
    l2 = RemoteNeighborLoader(server.addr, [2], np.arange(12, 24),
                              batch_size=6)
    try:
        s1 = [n for b in l1
              for n in np.asarray(b.batch)[:b.batch_size].tolist()]
        s2 = [n for b in l2
              for n in np.asarray(b.batch)[:b.batch_size].tolist()]
        assert sorted(s1) == list(range(0, 12))
        assert sorted(s2) == list(range(12, 24))
    finally:
        l1.shutdown()
        l2.shutdown()
