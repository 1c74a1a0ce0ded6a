"""Counts of useful work out of every step: the registry's carrier for
counts produced on the device (``obs.metrics.defer``), and what every
sampler path puts on it (``glt.sample.*``), held to numpy sums over the
batches the same keys sample."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.obs import compilewatch, metrics
from glt_tpu.ops import neighbor_sample

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture
def registry():
    metrics.reset()
    metrics.enable()
    try:
        yield metrics
    finally:
        metrics.disable()
        metrics.reset()


@pytest.fixture(params=[None, 16], ids=["one-chunk", "chunks-of-16"])
def chunk(request, monkeypatch):
    """The hop's read as every tiny shape compiles it (at most one chunk:
    no loop, ``read_rows`` the static width) and with the module's chunk
    cut to 16 rows, so that the same shapes run the loop over their live
    chunks.  The constant is read when a program is traced."""
    if request.param:
        monkeypatch.setattr(neighbor_sample, "CHUNK_ROWS", request.param)
    return request.param


# -- (a) the carrier ---------------------------------------------------------

class FakeArray:
    """What ``defer`` may touch of a device array, every touch recorded."""

    def __init__(self, values, ready=True):
        self._values = np.asarray(values)
        self.shape = self._values.shape
        self.ready = ready
        self.calls = []

    def copy_to_host_async(self):
        self.calls.append("copy_to_host_async")

    def is_ready(self):
        self.calls.append("is_ready")
        return self.ready

    def __array__(self, dtype=None, copy=None):
        self.calls.append("__array__")
        return self._values


class Untouchable:
    def __getattr__(self, name):
        raise AssertionError(f"defer touched .{name} with metrics off")


def _pair():
    return (metrics.counter("test.defer.a"), metrics.counter("test.defer.b"))


def test_disabled_defer_keeps_nothing_and_touches_nothing():
    metrics.reset()
    assert not metrics.enabled()
    metrics.defer(_pair(), Untouchable(), [(_pair()[0], 3)])
    assert len(metrics._pending) == 0
    assert _pair()[0].value == 0


def test_defer_never_waits_and_snapshot_does(registry):
    a, b = _pair()
    late = FakeArray([[1, 2], [3, 4]], ready=False)
    registry.defer((a, b), late)
    assert late.calls == ["copy_to_host_async", "is_ready"]
    assert len(registry._pending) == 1 and a.value == 0
    # a later call folds in what has landed meanwhile, and only that
    late.ready = True
    later = FakeArray([10, 20], ready=False)
    registry.defer((a, b), later)
    assert (a.value, b.value) == (4, 6)           # leading axis summed
    assert "__array__" not in later.calls
    snap = registry.snapshot()                    # waits for the rest
    assert (snap["test.defer.a"], snap["test.defer.b"]) == (14, 26)
    assert len(registry._pending) == 0
    assert "test_defer_a_total 14" in registry.render_prometheus()


def test_defer_sums_every_leading_axis_counts_rows_and_skips_none(registry):
    a, b = _pair()
    rows = metrics.counter("test.defer.rows")
    values = np.arange(24).reshape(2, 3, 4)       # [G, S, C]
    registry.defer((a, None, b, None), FakeArray(values), [(rows, 7)])
    registry.flush_deferred()
    assert a.value == values[..., 0].sum()
    assert b.value == values[..., 2].sum()
    assert rows.value == 7 * 6


def test_an_array_over_several_processes_is_counted_by_our_shards(registry):
    """A mesh over several processes hands over an array that is not
    fully addressable: its addressable shards are counted, each replica
    once.  Held here to a real ``[G, S, C]`` array sharded as the scanned
    dist step returns it, told to say it spans more than this process."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    class Spanning:
        is_fully_addressable = False

        def __init__(self, array):
            self.addressable_shards = array.addressable_shards
            self.copy_to_host_async = array.copy_to_host_async
            self.is_ready = array.is_ready

        def __array__(self, dtype=None, copy=None):
            raise AssertionError("not every shard is on this process")

    a, b = _pair()
    rows = metrics.counter("test.defer.rows")
    values = np.arange(2 * 4 * 2).reshape(2, 4, 2)
    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    for spec in (P(None, "shard"), P()):          # per shard; replicated
        registry.reset()
        arr = jax.device_put(values, NamedSharding(mesh, spec))
        registry.defer((a, b), Spanning(arr), [(rows, 5)])
        registry.flush_deferred()
        assert (a.value, b.value) == (values[..., 0].sum(),
                                      values[..., 1].sum())
        assert rows.value == 5 * 8


def test_pending_list_stays_bounded(registry):
    a, b = _pair()
    entries = [FakeArray([1, 1], ready=False)
               for _ in range(metrics.DEFER_BOUND + 9)]
    for e in entries:
        registry.defer((a, b), e)
        assert len(registry._pending) <= metrics.DEFER_BOUND
    assert a.value == 9                           # the oldest, folded in
    assert all("__array__" in e.calls for e in entries[:9])
    assert not any("__array__" in e.calls for e in entries[9:])
    registry.reset()                              # drops what is pending
    assert len(registry._pending) == 0


def test_a_traced_caller_hands_over_a_tracer_and_nothing_happens(registry):
    a, b = _pair()
    jax.jit(lambda v: registry.defer((a, b), v) or v)(jnp.ones((2,)))
    assert len(registry._pending) == 0


# -- (b) what the samplers put on it ----------------------------------------

def _sample_counters(snap):
    return {k: v for k, v in snap.items()
            if k.startswith("glt.sample.") and "induce_sorted" not in k}


def _rows_read(live, chunk):
    """numpy's word for ``ops.neighbor_sample.read_rows``: the rows of
    the chunks of ``chunk`` frontier rows in which ``live`` (a bool a
    row) is set anywhere; the width where it is at most one chunk."""
    w = live.shape[0]
    if chunk is None or w <= chunk:
        return w
    n = -(-w // chunk)
    padded = np.zeros(n * chunk, bool)
    padded[:w] = live
    return int(padded.reshape(n, chunk).any(axis=1).sum()) * chunk


def _expected(batches, edge_bounds, widths, node_slots, frontier_slots=None,
              chunk=None):
    """The ``glt.sample.*`` counters after ``batches`` (dicts of numpy
    ``edge_mask``, ``num_sampled_nodes``, ``node_mask`` and, where the
    rows a hop's read sees are no prefix of its own frontier,
    ``frontier_live``: a bool a row, a hop)."""
    hops = len(edge_bounds) - 1
    frontier_slots = frontier_slots or widths
    n = len(batches)
    want = {"glt.sample.batches": n,
            "glt.sample.node_slots": n * node_slots,
            "glt.sample.nodes": sum(int(b["node_mask"].sum())
                                    for b in batches)}
    for k in range(1, hops + 1):
        lo, hi = edge_bounds[k - 1], edge_bounds[k]
        fanout = (hi - lo) // widths[k - 1]
        want[f"glt.sample.edges{{hop={k}}}"] = sum(
            int(b["edge_mask"][lo:hi].sum()) for b in batches)
        want[f"glt.sample.frontier_nodes{{hop={k}}}"] = sum(
            int(b["num_sampled_nodes"][k - 1]) for b in batches)
        want[f"glt.sample.frontier_slots{{hop={k}}}"] = \
            n * frontier_slots[k - 1]
        want[f"glt.sample.edge_slots{{hop={k}}}"] = \
            n * frontier_slots[k - 1] * fanout
        want[f"glt.sample.read_rows{{hop={k}}}"] = sum(
            _rows_read(b["frontier_live"][k - 1] if "frontier_live" in b
                       else np.arange(widths[k - 1])
                       < b["num_sampled_nodes"][k - 1], chunk)
            for b in batches)
    return want


def _host(out):
    return {"edge_mask": np.asarray(out.edge_mask),
            "node_mask": np.asarray(out.node_mask),
            "num_sampled_nodes": np.asarray(out.num_sampled_nodes)}


@pytest.fixture(scope="module")
def tiny():
    from chipbench import data

    cfg = _config("tiny-sage")
    return cfg, data.build_one_chip(cfg, 5, jax.devices()[0])


def _node_sampler(cfg, d, **kw):
    from glt_tpu.sampler import NeighborSampler

    sam = cfg["sampling"]
    return NeighborSampler(d.dataset.get_graph(), sam["fanout"],
                           batch_size=sam["batch_size"], with_edge=False,
                           node_capacity=sam["node_capacity"], **kw)


def _node_step(cfg, d, sampler):
    from chipbench import data
    from glt_tpu.models import (init_train_state,
                                make_scanned_node_train_step)

    model, tx = data.make_model(cfg), optax.adam(1e-3)
    feat = d.dataset.get_node_feature()
    state = init_train_state(model, tx, feat.shape[1], jax.random.PRNGKey(1))
    step = make_scanned_node_train_step(
        model, tx, sampler, feat, np.asarray(d.dataset.get_node_label()),
        sampler.batch_size)
    return step, state


def test_scanned_node_step_counts_its_batches(tiny, registry, chunk):
    cfg, d = tiny
    sampler = _node_sampler(cfg, d)
    step, state = _node_step(cfg, d, sampler)
    b, g = sampler.batch_size, 3
    blocks = [np.asarray(d.train_idx[i * g * b:(i + 1) * g * b],
                         np.int32).reshape(g, b) for i in range(2)]
    blocks[1][-1, b // 2:] = -1                   # a short trailing batch
    gr = sampler.graph
    sample = jax.jit(sampler._sample_impl)
    batches = []
    for i, blk in enumerate(blocks):
        key = jax.random.PRNGKey(40 + i)
        state, _, _, flags = step(state, blk, key)
        assert np.asarray(flags).shape == (g,)
        for seeds, k in zip(blk, jax.random.split(key, g)):
            batches.append(_host(sample(gr.indptr, gr.indices,
                                        gr.gather_edge_ids, seeds, k)))
    hb = sampler.hop_bounds
    got = _sample_counters(registry.snapshot())
    assert got == _expected(batches, hb.edge_bounds, sampler._widths,
                            sampler.node_capacity, chunk=chunk)
    assert 0 < got["glt.sample.edges{hop=3}"] \
        < got["glt.sample.edge_slots{hop=3}"]
    # what is still read beside what holds a node: the chunk's granularity
    nodes, read, slots = (got[f"glt.sample.{name}{{hop=3}}"] for name in
                          ("frontier_nodes", "read_rows", "frontier_slots"))
    if chunk:
        assert nodes <= read < min(slots, nodes + chunk * len(batches))
    else:
        assert read == slots


def test_node_loader_counts_its_batches(tiny, registry, chunk):
    from glt_tpu.loader import NeighborLoader

    cfg, d = tiny
    sampler = _node_sampler(cfg, d)
    b = sampler.batch_size
    loader = NeighborLoader(d.dataset, sampler.num_neighbors,
                            np.asarray(d.train_idx[: 4 * b + 5]),
                            batch_size=b, sampler=sampler)
    batches = [{"edge_mask": np.asarray(x.edge_mask),
                "node_mask": np.asarray(x.node_mask)} for x in loader]
    assert len(batches) == 5
    got = _sample_counters(registry.snapshot())
    hb = sampler.hop_bounds
    frontier = {k: v for k, v in got.items()
                if "frontier_nodes" in k or "read_rows" in k}
    # A loader's Batch carries no per-hop node counts: the frontier of
    # hop k holds the rows first seen at hop k - 1, which are the rows
    # the valid edges of hop block k point from.
    for bt in batches:
        bt["num_sampled_nodes"] = np.zeros(len(sampler._widths) + 1, int)
    want = _expected(batches, hb.edge_bounds, sampler._widths,
                     sampler.node_capacity)
    for k in frontier:
        want.pop(k)
        got.pop(k)
    assert got == want
    assert frontier["glt.sample.frontier_nodes{hop=1}"] == 4 * b + 5
    assert all(0 < frontier[f"glt.sample.frontier_nodes{{hop={k}}}"]
               <= want[f"glt.sample.frontier_slots{{hop={k}}}"]
               for k in (2, 3))
    for k in (1, 2, 3):                 # a prefix a batch: under a chunk over
        nodes, read = (frontier[f"glt.sample.{name}{{hop={k}}}"]
                       for name in ("frontier_nodes", "read_rows"))
        if chunk:
            assert nodes <= read < nodes + chunk * len(batches)
            assert read % chunk == 0
        else:
            assert read == want[f"glt.sample.frontier_slots{{hop={k}}}"]


def _link_world():
    from chipbench import data
    from glt_tpu.sampler import NegativeSampling, NeighborSampler

    cfg = _config("tiny-sage-unsup")
    d = data.build_one_chip(cfg, 5, jax.devices()[0])
    sam = cfg["sampling"]
    graph = d.dataset.get_graph()
    neg = NegativeSampling(sam["neg_sampling"], sam["amount"])
    sampler = NeighborSampler(graph, sam["fanout"],
                              batch_size=sam["batch_size"], with_edge=False,
                              node_capacity=sam["node_capacity"])
    topo = graph.topo
    pos = np.arange(4 * sam["batch_size"]) * 29 + 7
    src = np.searchsorted(topo.indptr, pos, side="right") - 1
    return cfg, d, sampler, neg, np.stack([src, topo.indices[pos]])


def test_scanned_link_step_counts_the_seed_unions_chain(registry, chunk):
    from chipbench.drivers.link_scan_train import make_model
    from glt_tpu.models import (init_train_state, link_seed_blocks,
                                make_scanned_link_train_step,
                                run_scanned_epoch)
    from glt_tpu.sampler.base import EdgeSamplerInput

    cfg, d, sampler, neg, edges = _link_world()
    q, g = sampler.batch_size, 2
    model, tx = make_model(cfg), optax.adam(1e-3)
    feat = d.dataset.get_node_feature()
    state = init_train_state(model, tx, feat.shape[1], jax.random.PRNGKey(1))
    step = make_scanned_link_train_step(model, tx, sampler, feat,
                                        neg_sampling=neg, group=g)
    assert [c.name for c in step.flag_counters] == [
        "glt.link.overflowed_batches", "glt.link.neg_padded_slots"]
    blocks = list(link_seed_blocks(edges, q, g, np.random.default_rng(0)))
    batches, flags = [], []
    for i, blk in enumerate(blocks):
        key = jax.random.PRNGKey(70 + i)
        state, _, _, fl = step(state, blk, key)
        flags.append(np.asarray(fl))
        for e, k in zip(blk, jax.random.split(key, g)):
            out = sampler.sample_from_edges(EdgeSamplerInput(
                row=e[0], col=e[1], neg_sampling=neg), key=k)
            batches.append(_host(out))
    union = sampler.seed_union(neg)
    snap = registry.snapshot()
    assert _sample_counters(snap) == _expected(
        batches, union.hop_bounds.edge_bounds, union.widths,
        union.node_capacity, chunk=chunk)
    # (c) the flag columns ride the same carrier, counted once: what the
    # parent summed from the flags it fetched.
    flags = np.concatenate(flags)
    assert flags.shape == (len(batches), 2)
    assert snap["glt.link.overflowed_batches"] == flags[:, 0].sum()
    assert snap["glt.link.neg_padded_slots"] == flags[:, 1].sum()
    # ... and run_scanned_epoch adds nothing of its own to them
    registry.reset()
    _, _, _, ovf = run_scanned_epoch(step, state, edges, q, g,
                                     np.random.default_rng(0),
                                     jax.random.PRNGKey(9))
    snap = registry.snapshot()
    assert snap["glt.link.overflowed_batches"] == ovf
    assert snap["glt.sample.batches"] == len(blocks) * g
    assert snap["glt.train.steps"] == len(blocks)


def test_link_padded_slots_are_counted_once(registry):
    """A graph so dense that strict trials fail: the padded slots the
    step's flags report are what the counter reads."""
    from glt_tpu.data import CSRTopo, Graph
    from glt_tpu.data.feature import Feature
    from glt_tpu.models import (GraphSAGE, init_train_state,
                                link_seed_blocks,
                                make_scanned_link_train_step,
                                run_scanned_epoch)
    from glt_tpu.sampler import NegativeSampling, NeighborSampler

    n = 6
    src, dst = np.nonzero(np.ones((n, n), bool))          # every pair
    graph = Graph(CSRTopo(np.stack([src, dst]), num_nodes=n))
    feat = Feature(np.random.default_rng(0).normal(
        size=(n, 8)).astype(np.float32))
    sampler = NeighborSampler(graph, [2, 2], batch_size=4, with_edge=False)
    neg = NegativeSampling("binary", 1)
    model = GraphSAGE(hidden_features=8, out_features=8, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.sgd(0.1)
    step = make_scanned_link_train_step(model, tx, sampler, feat,
                                        neg_sampling=neg, group=2)
    state = init_train_state(model, tx, 8, jax.random.PRNGKey(0))
    edges = np.stack([src[:16], dst[:16]])
    total = 0
    for i, blk in enumerate(link_seed_blocks(edges, 4, 2,
                                             np.random.default_rng(1))):
        state, _, _, flags = step(state, blk, jax.random.PRNGKey(i))
        total += int(np.asarray(flags)[:, 1].sum())
    assert total > 0
    assert registry.snapshot()["glt.link.neg_padded_slots"] == total
    run_scanned_epoch(step, state, edges, 4, 2, np.random.default_rng(1),
                      jax.random.PRNGKey(5))
    assert registry.snapshot()["glt.link.neg_padded_slots"] == 2 * total


def test_scanned_typed_step_counts_sums_over_types_and_relations(registry,
                                                                  chunk):
    from chipbench import data_hetero
    from glt_tpu.models import (init_hetero_state,
                                make_scanned_hetero_train_step,
                                run_scanned_epoch)
    from glt_tpu.sampler import NodeSamplerInput
    from glt_tpu.sampler.hetero_neighbor_sampler import (
        HeteroNeighborSampler)

    cfg = _config("tiny-rgat")
    sam = cfg["sampling"]
    d = data_hetero.build_hetero_one_chip(cfg, 5, lambda *_: None)
    sampler = HeteroNeighborSampler(
        d.graphs, sam["fanout"], d.seed_type, batch_size=sam["batch_size"],
        frontier_cap=sam["frontier_cap"],
        node_capacity=sam["node_capacity"],
        frontier_capacity=sam["frontier_capacity"])
    model, tx = data_hetero.make_model(cfg), optax.adam(1e-3)
    state = init_hetero_state(model, tx, sampler, d.feats,
                              jax.random.PRNGKey(0))
    step = make_scanned_hetero_train_step(
        model, tx, sampler, d.feats, {d.seed_type: d.labels},
        sam["batch_size"], seed_hops=True)
    assert [c.name for c in step.flag_counters] == [
        "glt.hetero.overflowed_batches"]
    b, g = sam["batch_size"], 2
    blk = np.asarray(d.train_idx[: g * b], np.int32).reshape(g, b)
    key = jax.random.PRNGKey(21)
    state, _, _, flags = step(state, blk, key)
    outs = [sampler.sample_from_nodes(NodeSamplerInput(seeds), key=k)
            for seeds, k in zip(blk, jax.random.split(key, g))]
    snap = registry.snapshot()
    hb, widths = sampler.hop_bounds, sampler.hop_widths
    hops = sampler.num_hops
    want = {"glt.sample.batches": g,
            "glt.sample.node_slots": g * sum(sampler.node_capacity.values()),
            "glt.sample.nodes": sum(int(np.asarray(m).sum())
                                    for o in outs
                                    for m in o.node_mask.values())}
    for k in range(1, hops + 1):
        edges = slots = rows = live = read = 0
        for rel, bounds in hb.edge_bounds.items():
            lo, hi = bounds[k - 1], bounds[k]
            if hi == lo:
                continue
            src_type = rel[2]           # batch keys are reversed relations
            slots += hi - lo
            rows += widths[k - 1][src_type]
            for o in outs:
                edges += int(np.asarray(o.edge_mask[rel])[lo:hi].sum())
                assert int(np.asarray(o.num_sampled_edges[rel])[k - 1]) == \
                    int(np.asarray(o.edge_mask[rel])[lo:hi].sum())
                new = np.asarray(o.num_sampled_nodes[src_type])
                live += int(min(new[k - 1], widths[k - 1][src_type]))
                read += _rows_read(np.arange(widths[k - 1][src_type])
                                   < new[k - 1], chunk)
        want[f"glt.sample.edges{{hop={k}}}"] = edges
        want[f"glt.sample.edge_slots{{hop={k}}}"] = g * slots
        want[f"glt.sample.frontier_slots{{hop={k}}}"] = g * rows
        want[f"glt.sample.frontier_nodes{{hop={k}}}"] = live
        want[f"glt.sample.read_rows{{hop={k}}}"] = read
        assert (live <= read <= g * rows) if chunk else read == g * rows
    assert _sample_counters(snap) == want
    assert snap["glt.hetero.overflowed_batches"] == np.asarray(flags).sum()
    registry.reset()
    _, _, _, ovf = run_scanned_epoch(step, state, d.train_idx[: 2 * g * b],
                                     b, g, np.random.default_rng(0), key)
    assert registry.snapshot()["glt.hetero.overflowed_batches"] == ovf


@pytest.mark.parametrize("scanned", [False, True])
def test_dist_step_counts_every_shards_batch(registry, scanned, chunk):
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from chipbench import data
    from glt_tpu.parallel import (dist_sample_multi_hop, init_dist_state,
                                  make_dist_train_step,
                                  make_scanned_dist_train_step)
    from glt_tpu.sampler.neighbor_sampler import hop_bounds, hop_widths

    cfg = _config("tiny-sage-dist4")
    sam = cfg["sampling"]
    d = data.build_sharded(cfg, 5, jax.devices()[:4])
    s, b, fanout = 4, sam["batch_size"], sam["fanout"]
    model, tx = data.make_model(cfg), optax.adam(1e-3)
    state = init_dist_state(model, tx, d.graph, d.feature,
                            jax.random.PRNGKey(0), fanout, b)
    make = make_scanned_dist_train_step if scanned else make_dist_train_step
    step = make(model, tx, d.graph, d.feature, d.labels, d.mesh, fanout, b)
    rng = np.random.default_rng(3)
    c = d.shapes.nodes_per_shard

    def local(indptr, indices, eids, seeds, key):
        key = jax.random.fold_in(key, lax.axis_index("shard"))
        out = dist_sample_multi_hop(indptr[0], indices[0], eids[0],
                                    seeds[0], key, fanout, c, s, "shard")
        return tuple(a[None] for a in (out.edge_mask, out.node_mask,
                                       out.num_sampled_nodes, out.node))

    sp = P("shard")
    probe = jax.jit(jax.shard_map(local, mesh=d.mesh,
                                  in_specs=(sp,) * 4 + (P(),),
                                  out_specs=(sp,) * 4, check_vma=False))
    widths = hop_widths(b, fanout)

    def served(nsn, node):
        """What each shard's read of hop k sees: one row a requester of
        the request matrix, the ids that requester's frontier (the nodes
        it first saw a hop earlier) holds of this owner leading it."""
        nsn, node = np.asarray(nsn), np.asarray(node)
        first = np.concatenate([np.zeros((s, 1), int),
                                np.cumsum(nsn, axis=1)], axis=1)
        live = [[] for _ in range(s)]
        for k, w in enumerate(widths):
            asked = np.zeros((s, s), int)          # [requester, owner]
            for p_ in range(s):
                ids = node[p_, first[p_, k]: first[p_, k]
                           + min(nsn[p_, k], w)]
                asked[p_] = np.bincount(ids // c, minlength=s)
            for q in range(s):
                live[q].append((np.arange(w)[None, :]
                                < asked[:, q, None]).reshape(-1))
        return live
    # the feature exchange's matrix: each requester's bucket for an owner
    # holds that owner's ids of its node list, a prefix of ``cap`` slots
    cap = hop_bounds(b, fanout).node_bounds[-1]
    gather_chunk = chunk or neighbor_sample.CHUNK_ROWS
    gather = {"glt.gather.served_rows": 0, "glt.gather.read_rows": 0}
    batches, n_steps, g = [], 2, 2
    for it in range(n_steps):
        key = jax.random.PRNGKey(50 + it)
        seeds = np.stack([np.stack([rng.choice(p, b, replace=False)
                                    for p in d.train_idx])
                          for _ in range(g)]).astype(np.int32)   # [G, S, B]
        if scanned:
            out = step(state, seeds, key)
            keys = jax.random.split(key, g)
        else:
            out = step(state, jnp.asarray(seeds[0]), key)
            seeds, keys = seeds[:1], [key]
        state = out[0]
        assert len(out) == 3
        for sd, k in zip(seeds, keys):
            em, nm, nsn, node = probe(d.graph.indptr, d.graph.indices,
                                      d.graph.edge_ids, jnp.asarray(sd), k)
            live = served(nsn, node)
            node = np.asarray(node)
            asked = np.stack([np.bincount(p_[p_ >= 0] // c, minlength=s)
                              for p_ in node])         # [requester, owner]
            gather["glt.gather.served_rows"] += int(asked.sum())
            for q in range(s):
                gather["glt.gather.read_rows"] += _rows_read(
                    (np.arange(cap)[None, :] < asked[:, q, None]).reshape(-1),
                    gather_chunk)
            batches += [{"edge_mask": np.asarray(em[i]),
                         "node_mask": np.asarray(nm[i]),
                         "num_sampled_nodes": np.asarray(nsn[i]),
                         "frontier_live": live[i]}
                        for i in range(s)]
    hb = hop_bounds(b, fanout)
    got = _sample_counters(registry.snapshot())
    # the slots are what each shard's read serves: S requesters' widths
    assert got == _expected(batches, hb.edge_bounds, widths,
                            hb.node_bounds[-1],
                            frontier_slots=[s * w for w in widths],
                            chunk=chunk)
    assert got["glt.sample.batches"] == n_steps * s * (g if scanned else 1)
    if chunk:       # four prefixes a shard's read, and most of it skipped
        assert got["glt.sample.frontier_nodes{hop=3}"] \
            <= got["glt.sample.read_rows{hop=3}"] \
            < got["glt.sample.frontier_slots{hop=3}"] / 2
    # ... and the served feature read's two, counted on the same carrier:
    # every valid node of every shard's list is served once, by its owner
    snap = registry.snapshot()
    assert {k: snap[k] for k in gather} == gather
    assert gather["glt.gather.served_rows"] == sum(
        int(bt["node_mask"].sum()) for bt in batches)
    assert gather["glt.gather.served_rows"] <= gather["glt.gather.read_rows"]
    if chunk:
        assert gather["glt.gather.read_rows"] < len(batches) * s * cap / 2


def test_a_bounded_exchange_counts_live_work_and_no_read_slots(registry):
    """The served matrix of a bounded exchange has a shape of its own that
    nothing here derives: its read slots stay uncounted, so a share over
    them reads nothing, while the live counts and the node buffer's rows
    are counted as ever."""
    from chipbench import data
    from glt_tpu.parallel import init_dist_state, make_dist_train_step

    cfg = _config("tiny-sage-dist4")
    sam = cfg["sampling"]
    d = data.build_sharded(cfg, 5, jax.devices()[:4])
    b, fanout = sam["batch_size"], sam["fanout"]
    model, tx = data.make_model(cfg), optax.adam(1e-3)
    state = init_dist_state(model, tx, d.graph, d.feature,
                            jax.random.PRNGKey(0), fanout, b)
    step = make_dist_train_step(model, tx, d.graph, d.feature, d.labels,
                                d.mesh, fanout, b, exchange_load_factor=2.0)
    rng = np.random.default_rng(3)
    seeds = np.stack([rng.choice(p, b, replace=False)
                      for p in d.train_idx]).astype(np.int32)
    step(state, jnp.asarray(seeds), jax.random.PRNGKey(1))
    got = _sample_counters(registry.snapshot())
    assert got["glt.sample.batches"] == 4
    assert got["glt.sample.nodes"] > 0 and got["glt.sample.node_slots"] > 0
    assert all(got[f"glt.sample.edges{{hop={k}}}"] > 0
               for k in range(1, len(fanout) + 1))
    assert not any(v for k, v in got.items()
                   if k.startswith(("glt.sample.frontier_slots",
                                    "glt.sample.edge_slots")))
    # ... and what its own reads issued (the local split's, the served
    # matrix's), which needs no shape derived here
    assert all(got[f"glt.sample.read_rows{{hop={k}}}"]
               >= got[f"glt.sample.frontier_nodes{{hop={k}}}"] > 0
               for k in range(1, len(fanout) + 1))


# -- (d) one compile, the same bits ------------------------------------------

def test_one_program_whether_metrics_are_off_on_or_off_again(tiny):
    cfg, d = tiny
    compilewatch.install()
    sampler = _node_sampler(cfg, d)
    step, state0 = _node_step(cfg, d, sampler)
    b, g = sampler.batch_size, 2
    blk = np.asarray(d.train_idx[: g * b], np.int32).reshape(g, b)
    key = jax.random.PRNGKey(11)
    metrics.reset()
    results = []
    try:
        _, losses, accs, flags = step(state0, blk, key)       # compiles
        results.append((losses, accs, flags))
        compiles = compilewatch.total_compiles()
        for on in (True, False):
            (metrics.enable if on else metrics.disable)()
            _, losses, accs, flags = step(state0, blk, key)
            results.append((losses, accs, flags))
        assert compilewatch.total_compiles() == compiles
        assert metrics.snapshot()["glt.sample.batches"] == g  # the on call
    finally:
        metrics.disable()
        metrics.reset()
    for other in results[1:]:
        for x, y in zip(results[0], other):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_counts_change_no_bit_of_the_batch(tiny):
    """The sample's own arrays are what a program without the counts
    gives: the live counts are sums of what it already computed."""
    cfg, d = tiny
    sampler = _node_sampler(cfg, d)
    gr = sampler.graph
    seeds = jnp.asarray(d.train_idx[: sampler.batch_size], jnp.int32)
    key = jax.random.PRNGKey(2)

    def without(indptr, indices, eids, seeds, key):
        out = sampler._sample_impl(indptr, indices, eids, seeds, key)
        return out.node, out.row, out.col, out.edge_mask, out.node_mask

    bare = jax.jit(without)(gr.indptr, gr.indices, gr.gather_edge_ids,
                            seeds, key)
    out = jax.jit(sampler._sample_impl)(gr.indptr, gr.indices,
                                        gr.gather_edge_ids, seeds, key)
    for x, y in zip(bare, (out.node, out.row, out.col, out.edge_mask,
                           out.node_mask)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    live = np.asarray(out.live_counts)
    hops = len(sampler.num_neighbors)
    np.testing.assert_array_equal(live[2 * hops: 3 * hops],
                                  np.asarray(out.num_sampled_edges))
    np.testing.assert_array_equal(live[hops: 2 * hops], sampler._widths)
    assert live[-1] == int(np.asarray(out.node_mask).sum())
    assert len(metrics._pending) == 0        # off: nothing was kept


def test_with_metrics_off_no_sample_counter_moves(tiny):
    from glt_tpu.loader import NeighborLoader

    cfg, d = tiny
    metrics.reset()
    sampler = _node_sampler(cfg, d)
    b = sampler.batch_size
    for _ in NeighborLoader(d.dataset, sampler.num_neighbors,
                            np.asarray(d.train_idx[: 2 * b]), batch_size=b,
                            sampler=sampler):
        pass
    assert len(metrics._pending) == 0
    assert not any(_sample_counters(metrics.snapshot()).values())
