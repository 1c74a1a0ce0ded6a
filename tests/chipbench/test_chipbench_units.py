"""The benchmark's yardstick, piece by piece, at ``tiny-*`` size on the
CPU: generator, plain reference, the comparison that decides ``correct``,
the trace reduction, the load generator, the peaks table and the
contract of ``BENCHMARK.json``."""
import json
import os
import re
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))

from chipbench import (checks, data, gen, openloop, peaks,  # noqa: E402
                       reference, tracered)


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def _generate(name, seed, shards):
    import jax

    cfg = _config(name)
    sh = gen.shapes_of(cfg, shards)
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:shards]), ("shard",))
    made = gen.generate(sh, seed, mesh, with_edge_ids=shards > 1)
    return cfg, sh, made


@pytest.mark.parametrize("name,shards", [("tiny-sage", 1),
                                         ("tiny-sage-dist4", 4)])
def test_generator_same_seed_same_graph_stated_counts(name, shards):
    cfg, sh, a = _generate(name, 3, shards)
    _, _, b = _generate(name, 3, shards)
    _, _, c = _generate(name, 4, shards)
    for x, y in zip(a, b):
        if x is not None:
            assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a.indices), np.asarray(c.indices))
    indptr = np.asarray(a.indptr)
    assert indptr.shape == (shards, sh.nodes_per_shard + 1)
    # Every shard holds exactly its share of the file's edge count, and
    # the nodes are the file's: padding rows have no edge and label -1.
    assert (indptr[:, -1] == cfg["data"]["num_edges"] // shards).all()
    assert np.asarray(a.indices).shape == (shards, sh.edges_per_shard)
    labels = np.asarray(a.labels).reshape(-1)
    assert (labels >= 0).sum() == cfg["data"]["num_nodes"]
    deg = np.diff(indptr, axis=1).reshape(-1)
    assert (deg[labels >= 0] >= 1).all() and (deg[labels < 0] == 0).all()
    idx = np.asarray(a.indices)
    assert idx.min() >= 0 and idx.max() < cfg["data"]["num_nodes"]


@pytest.mark.parametrize("name,shards", [("tiny-sage", 1),
                                         ("tiny-sage-dist4", 4)])
def test_reference_recomputes_rows_and_adjacency(name, shards):
    cfg, sh, made = _generate(name, 5, shards)
    ref = reference.RefData(sh, 5, np.asarray(made.indptr))
    indptr, indices = np.asarray(made.indptr), np.asarray(made.indices)
    rows = np.asarray(made.rows).reshape(-1, sh.feature_dim)
    labels = np.asarray(made.labels).reshape(-1)
    rng = np.random.default_rng(0)
    nodes = rng.choice(sh.num_nodes, 200, replace=False)
    assert np.array_equal(ref.features(nodes), rows[nodes])
    assert np.array_equal(ref.labels(nodes), labels[nodes])
    assert (ref.features(np.array([-1])) == 0).all()
    c = sh.nodes_per_shard
    for v in nodes[:50].tolist():
        s, r = divmod(v, c)
        assert np.array_equal(ref.neighbours(v),
                              indices[s, indptr[s, r]: indptr[s, r + 1]])
    # In-degree follows out-degree: the most pointed-at node is a hub.
    indeg = np.bincount(indices.reshape(-1), minlength=sh.num_nodes)
    assert ref.degree([int(indeg.argmax())])[0] > 4 * sh.mean_degree


def _sampled_batch(frontier_cap):
    import jax
    import jax.numpy as jnp

    from glt_tpu.sampler import NeighborSampler, NodeSamplerInput

    cfg = _config("tiny-sage")
    d = data.build_one_chip(cfg, 7, jax.devices()[0])
    sam = cfg["sampling"]
    sampler = NeighborSampler(d.dataset.get_graph(), sam["fanout"],
                              batch_size=sam["batch_size"],
                              frontier_cap=frontier_cap, with_edge=False)
    seeds = d.train_idx[: sam["batch_size"]].astype(np.int32)
    out = sampler.sample_from_nodes(NodeSamplerInput(seeds))
    feat = d.dataset.get_node_feature()
    labels = np.asarray(d.dataset.get_node_label())
    node = np.asarray(out.node)
    batch = {"node": node, "node_mask": np.asarray(out.node_mask),
             "seeds": np.asarray(out.batch),
             "x": np.array(feat.gather(out.node)),
             "y": np.where(node >= 0, labels[np.maximum(node, 0)], -1),
             "edge_index": np.asarray(jnp.stack([out.row, out.col])),
             "edge_mask": np.asarray(out.edge_mask)}
    return d, sam, batch


def test_check_passes_uncapped_and_fails_a_capped_frontier():
    rng = np.random.default_rng(0)
    d, sam, batch = _sampled_batch(None)
    checks.check_batch(d.ref, batch, sam["batch_size"], sam["fanout"],
                       "uncapped", rng)
    d, sam, capped = _sampled_batch(40)       # hop widths [32, 40, 40]
    with pytest.raises(checks.CheckFailure, match="min\\(degree, fanout\\)"):
        checks.check_batch(d.ref, capped, sam["batch_size"], sam["fanout"],
                           "capped", rng)


def test_check_fails_a_corrupted_feature_row_and_a_foreign_edge():
    rng = np.random.default_rng(0)
    d, sam, batch = _sampled_batch(None)
    bad = dict(batch, x=batch["x"].copy())
    bad["x"][5, 3] += np.float32(2.0 ** -20)
    with pytest.raises(checks.CheckFailure, match="features differ"):
        checks.check_batch(d.ref, bad, sam["batch_size"], sam["fanout"],
                           "corrupted", rng)
    # Point every sampled edge of one seed at a node it has no edge to.
    ei = batch["edge_index"].copy()
    live = np.flatnonzero(batch["edge_mask"] & (ei[1] == 0))
    have = set(d.ref.neighbours(int(batch["node"][0])).tolist())
    stranger = next(i for i in range(len(batch["node"]))
                    if batch["node_mask"][i]
                    and int(batch["node"][i]) not in have)
    ei[0, live] = stranger
    with pytest.raises(checks.CheckFailure):
        checks.check_batch(d.ref, dict(batch, edge_index=ei),
                           sam["batch_size"], sam["fanout"], "foreign",
                           np.random.default_rng(0))


def test_reference_forward_tells_bf16_from_a_lower_precision():
    """The logits tolerance passes the program's bf16 matmuls and fails
    inputs rounded to four mantissa bits."""
    import jax
    import jax.numpy as jnp

    cfg = _config("tiny-sage")
    d, sam, batch = _sampled_batch(None)
    model = data.make_model(cfg)
    x, ei, em = (jnp.asarray(batch[k]) for k in
                 ("x", "edge_index", "edge_mask"))
    params = model.init({"params": jax.random.PRNGKey(0)}, x, ei, em)
    n = sam["batch_size"]
    want = reference.sage_forward(
        reference.layer_weights(params, 3), x, ei[0], ei[1], em)[:n]
    got = model.apply(params, x, ei, em, train=False)[:n]
    rtol = cfg["check"]["logits_rtol"]
    assert checks.check_logits(got, want, rtol, "bf16") < rtol / 2

    def crush(a):       # keep four mantissa bits
        m, e = np.frexp(np.asarray(a, np.float32))
        return jnp.asarray(np.ldexp(np.round(m * 32) / 32, e), jnp.float32)

    low = jax.tree.map(crush, params)
    with pytest.raises(checks.CheckFailure, match="logits differ"):
        checks.check_logits(
            model.apply(low, crush(x), ei, em, train=False)[:n], want,
            rtol, "4-bit")
    loss = reference.seed_loss(want, jnp.asarray(batch["y"]), n)
    assert np.isfinite(float(loss))


# -- trace reduction ---------------------------------------------------------

def _trace(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return tracered.normalise(json.load(f))


def test_trace_reduction_on_a_hand_made_trace():
    """Arithmetic that can be checked by eye: see data/hand_trace.json."""
    t = _trace("hand_trace.json")
    dev = t["devices"]["0"]
    ops, modules = dev["ops"], dev["modules"]
    w = t["window"]
    assert w == [0, 1000]
    busy, gaps = tracered.busy_and_gaps(ops, w)
    assert busy == 700 and gaps == [(0, 100), (500, 600), (900, 1000)]
    # the while (100-500) holds fusion.1 (100-300) and all-to-all (300-450)
    selfs = {n: s for n, _, s in tracered.self_times(ops, w)}
    assert selfs == {"while.1": 50, "fusion.1": 200, "all_to_all.2": 150,
                     "fusion.7": 300}
    assert tracered.module_time(modules, "jit_run", w) == (400, 1)
    assert tracered.module_time(modules, "_gather_hot_impl", w) == (300, 1)
    assert tracered.op_time(ops, r"\[402944,256\]", w) == 300
    # all-to-all 300-450 runs alone inside its while: all of it exposed;
    # the asynchronous all-reduce 650-850 hides behind fusion.7.
    assert tracered.collective_times(ops, dev["async"], w) == (350, 150)
    assert tracered.attribute_gaps(gaps, t["host"]) == [
        ("chipbench.dispatch", 200.0), ("no benchmark span", 100.0)]
    assert tracered.top_ops(ops, w)[0] == ("fusion f32[402944,256]", 300)
    assert tracered.split_hlo(
        "%fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(f32[8]{0} %p), "
        "kind=kLoop") == ("fusion.7", "fusion f32[8,128]")
    # kinds of op are told by XLA's opcode, not by JAX's instruction name
    assert tracered.split_hlo(
        "%all_to_all.3 = (f32[4,8]{1,0:T(4,128)}, s32[4]{0}) "
        "all-to-all(f32[4,8]{1,0} %x)") == ("all_to_all.3",
                                            "all-to-all f32[4,8]+")


def test_trace_reduction_on_a_recorded_chip_trace():
    """A cut of a real TPU v5e trace (this PR's ``dist-train`` traced
    run, device 0): the reduction finds its module and collectives."""
    t = _trace("recorded_trace.json")
    dev = t["devices"]["0"]
    ops, w = dev["ops"], t["window"]
    busy, gaps = tracered.busy_and_gaps(ops, w)
    assert 0 < busy <= w[1] - w[0]
    assert abs(busy + sum(hi - lo for lo, hi in gaps) - (w[1] - w[0])) < 1
    selfs = tracered.self_times(ops, w)
    assert abs(sum(s for _, _, s in selfs) - busy) / busy < 1e-6
    total, exposed = tracered.collective_times(ops, dev["async"], w)
    assert 0 < exposed <= total < w[1] - w[0]
    assert tracered.module_time(dev["modules"], "jit__step", w)[1] >= 1
    assert tracered.top_ops(ops, w)[0][0].startswith("fusion ")


# -- load generator, peaks, contract -------------------------------------------

def test_open_loop_times_from_the_due_time_and_records_lateness():
    rng = np.random.default_rng(0)
    arrivals = openloop.poisson_arrivals(200.0, 0.5, rng)
    assert 50 < len(arrivals) < 160 and arrivals.max() < 0.5
    assert (np.diff(arrivals) > 0).all()

    def send(worker, req):
        time.sleep(0.02)
        if req == 3:
            raise TimeoutError("late")

    # One thread, 20 ms a request, arrivals every 5 ms: the queue grows,
    # and latency counted from the due time grows with it.
    due = np.arange(10) * 0.005
    outs = openloop.run(send, list(range(10)), due, threads=1, join_s=5.0)
    assert [o.kind for o in outs].count("TimeoutError") == 1
    ok = [o for o in outs if o.kind == "ok"]
    assert ok[-1].latency_s > 0.15 and ok[0].latency_s < 0.05
    assert ok[-1].late_s > 0.1 and np.isnan(outs[3].latency_s)


def test_peaks_table_and_work_functions():
    assert peaks.peaks_of("TPU v5 lite") == {"hbm_gb_s": 819.0,
                                            "bf16_tflops": 197.0}
    with pytest.raises(LookupError):
        peaks.peaks_of("TPU v9")
    assert peaks.gather_bytes(10, 100) == 8000


def test_benchmark_json_meets_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    b = json.loads(raw)
    assert len(raw) <= 64 * 1024
    assert sorted(b) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    assert len(cells) == len(b["workloads"]) >= 2
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    for c in b["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert name.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["sampling"]["frontier_cap"] is None
    for w in b["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        path = os.path.join(ROOT, "chipbench", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            drv = json.load(f)["driver"]
        assert os.path.exists(os.path.join(ROOT, "chipbench", "drivers",
                                           drv + ".py"))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        # reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
        with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                               m["name"] + ".json")) as f:
            reader = json.load(f)["reducer"]
        assert os.path.exists(os.path.join(ROOT, "chipbench", "reducers",
                                           reader + ".py"))
    for cell in cells:       # every cell: setup_s, another e2e, a per-layer
        assert sum(cell in m.get("workloads", cells)
                   for m in b["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])
