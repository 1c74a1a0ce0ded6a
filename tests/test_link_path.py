"""The link path at the seed union's width: the strict negative draw and
its flag, the pair index, capacity and overflow, hop trimming for a loss
that reads pairs, and ``run_scanned_epoch`` over seed edges."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.data import CSRTopo, Feature, Graph
from glt_tpu.models import (GraphSAGE, init_train_state, link_seed_blocks,
                            make_scanned_link_train_step, run_scanned_epoch)
from glt_tpu.models.train import shuffled_positions
from glt_tpu.ops import sample_negative_edges
from glt_tpu.sampler import (NegativeSampling, NeighborSampler,
                             calibrate_node_capacity)
from glt_tpu.sampler.base import EdgeSamplerInput

NEG = NegativeSampling("binary", 1)
N, DIM, Q, FANOUT = 400, 12, 16, [4, 3, 2]


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    row, col = rng.integers(0, N, 4000), rng.integers(0, N, 4000)
    graph = Graph(CSRTopo(np.stack([row, col]), num_nodes=N))
    feat = Feature(rng.normal(size=(N, DIM)).astype(np.float32))
    topo = graph.topo
    src = np.repeat(np.arange(N), np.diff(topo.indptr))
    return graph, feat, np.stack([src, topo.indices]), set(
        zip(src.tolist(), topo.indices.tolist()))


def _sampler(graph, **kw):
    return NeighborSampler(graph, FANOUT, batch_size=Q, with_edge=False,
                           **kw)


def _live_pairs(out):
    node = np.asarray(out.node)
    eli = np.asarray(out.metadata["edge_label_index"])
    return node, eli, np.asarray(out.metadata["edge_label"])


# -- (c) the negative draw ---------------------------------------------------

def test_strict_slots_are_non_edges_and_padded_slots_are_counted():
    """A graph dense enough that five trials do not always find a
    non-edge: ``strict`` tells the two passes apart, ``mask`` does not."""
    rng = np.random.default_rng(1)
    n = 24
    dense = rng.random((n, n)) < 0.85
    row, col = np.nonzero(dense)
    g = Graph(CSRTopo(np.stack([row, col]), num_nodes=n))
    out = sample_negative_edges(g.indptr, g.sorted_indices, 4096,
                                jax.random.PRNGKey(3), n)
    src, dst, mask, strict = map(np.asarray, out)
    assert mask.all() and src.min() >= 0 and dst.min() >= 0
    assert src.max() < n and dst.max() < n
    assert not dense[src[strict], dst[strict]].any()
    # whatever no trial filled is a drawn pair of the last trial: an edge
    padded = ~strict
    assert 0 < padded.sum() < 4096 and dense[src[padded], dst[padded]].all()
    # five independent trials at edge density 0.85
    assert abs(padded.mean() - dense.mean() ** 5) < 0.05
    # without padding the flag is the mask
    out = sample_negative_edges(g.indptr, g.sorted_indices, 4096,
                                jax.random.PRNGKey(3), n, padding=False)
    assert (np.asarray(out.mask) == np.asarray(out.strict)).all()
    assert (np.asarray(out.src)[~np.asarray(out.mask)] == -1).all()


def test_the_negative_draw_is_uniform_over_nodes(world):
    graph = world[0]
    out = sample_negative_edges(graph.indptr, graph.sorted_indices, 40000,
                                jax.random.PRNGKey(7), N)
    for ids in (np.asarray(out.src), np.asarray(out.dst)):
        counts = np.bincount(ids, minlength=N)
        chi2 = ((counts - 100.0) ** 2 / 100.0).sum()
        # 399 degrees of freedom: mean 399, the 99.9th percentile 494
        assert chi2 < 494, chi2


def test_the_sorted_view_is_built_on_the_device_beside_the_placed_arrays(
        world):
    from glt_tpu.data.graph import _sort_columns_within_rows

    topo = world[0].topo
    want = _sort_columns_within_rows(topo.indptr, topo.indices)
    late, early = Graph(topo), Graph(topo, with_sorted_columns=True)
    placed = late.indices
    assert isinstance(late.sorted_indices, jax.Array)
    assert late.indices is placed               # nothing placed again
    assert (np.asarray(late.sorted_indices) == want).all()
    assert (np.asarray(early.sorted_indices) == want).all()
    host = Graph(topo, mode="HOST").sorted_indices
    assert isinstance(host, np.ndarray) and (host == want).all()
    # rows without an edge, at the front, inside and at the end
    t = CSRTopo(np.array([[2, 2, 5, 5, 5], [9, 1, 7, 0, 3]]), num_nodes=10)
    assert np.asarray(Graph(t).sorted_indices).tolist() == [1, 9, 0, 3, 7]


# -- (d) the pairs -----------------------------------------------------------

@pytest.mark.parametrize("real", [Q, 5])
def test_pair_index_maps_back_to_the_input_pairs_in_order(world, real):
    graph, _, edges, edge_set = world
    s = _sampler(graph)
    pick = np.random.default_rng(2).choice(edges.shape[1], real, False)
    src, dst = edges[0, pick], edges[1, pick]
    out = s.sample_from_edges(EdgeSamplerInput(row=src, col=dst,
                                               neg_sampling=NEG))
    node, eli, label = _live_pairs(out)
    assert eli.shape == (2, 2 * Q) and label.shape == (2 * Q,)
    assert label.tolist() == [1] * real + [-1] * (Q - real) + [0] * Q
    assert (eli[:, real:Q] == -1).all() and (eli[:, :real] >= 0).all()
    assert (node[eli[0, :real]] == src).all()
    assert (node[eli[1, :real]] == dst).all()
    # the negatives: seed rows, in range, strict ones no edges
    assert (eli[:, Q:] >= 0).all() and eli.max() < 4 * Q
    neg = list(zip(node[eli[0, Q:]].tolist(), node[eli[1, Q:]].tolist()))
    strict = np.asarray(out.metadata["neg_strict"])
    assert all(0 <= a < N and 0 <= b < N for a, b in neg)
    assert not any(p in edge_set for p, ok in zip(neg, strict) if ok)
    assert int(out.metadata["num_pos"]) == real
    # the union [src, dst, neg_src, neg_dst] leads in first occurrence
    union = np.concatenate([src, dst, [a for a, _ in neg],
                            [b for _, b in neg]])
    _, first = np.unique(union, return_index=True)
    lead = union[np.sort(first)]
    assert (node[: lead.size] == lead).all()


# -- (e) capacity and overflow -----------------------------------------------

def test_sizes_of_the_seed_union(world):
    graph = world[0]
    s = _sampler(graph)
    u = s.seed_union(NEG)
    # 4q slots; 64 + 256 + 768 + 1536 nodes by the fanouts, 400 by the graph
    assert u.batch_size == 4 * Q and u.widths == (64, 256, 768)
    assert u.node_capacity == u.full_node_capacity == N and not u.capped
    assert u.hop_bounds.node_bounds == (64, 320, N, N)
    assert u.hop_bounds.edge_bounds == (0, 256, 1024, 2560)
    assert s.seed_union(None).batch_size == 2 * Q
    assert s.seed_union(NegativeSampling("triplet", 3)).batch_size == 5 * Q
    # the node path keeps the reference's sizing
    assert s.node_capacity == Q + 64 + 192 + 384 and not s.capped
    # a capacity is the union's on the link path: one valid for Q seeds
    # alone lies under the union's frontier floor
    with pytest.raises(ValueError, match="frontier floor"):
        _sampler(graph, node_capacity=300).seed_union(NEG)


def test_a_capacity_under_the_occupancy_flags_and_masks(world):
    graph, _, edges, _ = world
    src, dst = edges[0, :Q * 7:7], edges[1, :Q * 7:7]
    inp = EdgeSamplerInput(row=src, col=dst, neg_sampling=NEG)
    key = jax.random.PRNGKey(5)
    free = _sampler(graph).sample_from_edges(inp, key=key)
    assert "overflow" not in free.metadata      # at the clamp: cannot
    found = int(np.asarray(free.num_sampled_nodes).sum())
    assert found == int(np.asarray(free.node_mask).sum()) <= N
    # With [4, 3, 2] the frontier floor (1088) lies above the graph's 400
    # nodes, so no capacity under the clamp is valid: two hops of two.
    s = NeighborSampler(graph, [2, 2], batch_size=Q, with_edge=False,
                        node_capacity=64 + 128 + 8)
    u = s.seed_union(NEG)
    assert u.capped and u.node_capacity == 200
    out = s.sample_from_edges(inp, key=key)
    wide = NeighborSampler(graph, [2, 2], batch_size=Q,
                           with_edge=False).sample_from_edges(inp, key=key)
    assert int(np.asarray(wide.num_sampled_nodes).sum()) > 200
    assert bool(out.metadata["overflow"])
    em = np.asarray(out.edge_mask)
    row, col = np.asarray(out.row)[em], np.asarray(out.col)[em]
    assert row.max() < 200 and col.max() < 200 and em.sum() < np.asarray(
        wide.edge_mask).sum()
    assert np.asarray(out.node_mask).sum() == 200
    # the pairs still point at seed rows
    assert (np.asarray(out.metadata["edge_label_index"])[:, Q:] >= 0).all()


@pytest.mark.parametrize("case", ["full", "padded_pairs", "overflow"])
def test_the_seed_union_is_laid_out_in_hop_blocks(world, case):
    """The union's batch obeys ``union.hop_bounds`` as a node batch obeys
    its sampler's: the static bounds the link step trims by and the static
    destinations it aggregates by (tests/test_neighbor_sampler.py)."""
    from tests.test_neighbor_sampler import (assert_hop_layout,
                                             assert_static_destinations)
    graph, _, edges, _ = world
    real = 5 if case == "padded_pairs" else Q
    s = (NeighborSampler(graph, [2, 2], batch_size=Q, with_edge=False,
                         node_capacity=200)
         if case == "overflow" else _sampler(graph))
    u = s.seed_union(NEG)
    for it in range(2):
        pick = np.random.default_rng(it).choice(edges.shape[1], real, False)
        out = s.sample_from_edges(EdgeSamplerInput(
            row=edges[0, pick], col=edges[1, pick], neg_sampling=NEG))
        if case == "overflow":
            assert bool(out.metadata["overflow"])
        assert_hop_layout(out, u.hop_bounds)
        starts = assert_static_destinations(out, u.hop_bounds)
        assert starts[0] == 0 and starts == sorted(starts)


@pytest.mark.parametrize("case", ["clamped", "capped_sorted", "capped_map"])
def test_link_sampler_output_equals_the_map_forms(world, case, monkeypatch):
    """The link path's whole output (node batch, pair index, labels) with
    every hop of the seed union's chain sorted against the parent's
    program.  The union's endpoints repeat, so hop 0, the seeds' own
    dedup, has work to do; a capacity under the bound on known nodes
    keeps the id map at every hop."""
    import glt_tpu.sampler.neighbor_sampler as mod
    from glt_tpu.ops.unique import chain_is_sorted
    from tests.test_neighbor_sampler import assert_outputs_equal, map_form

    graph, _, edges, _ = world
    kw = {"clamped": {}, "capped_sorted": {"node_capacity": 200},
          "capped_map": {"frontier_cap": 32, "node_capacity": 150}}[case]
    fanout = FANOUT if case == "clamped" else [2, 2]

    def sample():
        s = NeighborSampler(graph, fanout, batch_size=Q, with_edge=False,
                            **kw)
        # Consecutive CSR positions: the sources repeat, and so do the
        # destinations of the second batch (the first's, reversed).
        return s, [s.sample_from_edges(EdgeSamplerInput(
            row=r, col=c, neg_sampling=NEG), key=jax.random.PRNGKey(it))
            for it, (r, c) in enumerate([
                (edges[0, :Q], edges[1, :Q]),
                (edges[1, :Q], edges[0, :Q])])]
    s, got = sample()
    u = s.seed_union(NEG)
    known_last = min(sum(u.hop_bounds.edge_bounds[:-1][-1:]) + u.batch_size,
                     u.full_node_capacity)
    assert chain_is_sorted(known_last, u.node_capacity) == (
        case != "capped_map")
    for out in got:
        assert int(np.asarray(out.num_sampled_nodes)[0]) < 3 * Q
    with map_form(monkeypatch, mod) as parent:
        _, want = sample()
    assert parent == [known_last]
    for a, b in zip(got, want):
        assert_outputs_equal(a, b)


def test_calibration_over_seed_edges_bounds_the_union(world):
    graph, _, edges, _ = world
    s = NeighborSampler(graph, [2, 2], batch_size=Q, with_edge=False)
    batches = [edges[:, i * Q:(i + 1) * Q] for i in range(6)]
    cap = calibrate_node_capacity(s, batches, neg_sampling=NEG)
    u = s.seed_union(NEG)
    assert sum(u.widths) <= cap <= u.full_node_capacity
    fit = NeighborSampler(graph, [2, 2], batch_size=Q, with_edge=False,
                          node_capacity=cap)
    assert fit.seed_union(NEG).node_capacity == cap


# -- (b) trimming ------------------------------------------------------------

def test_trimmed_and_whole_forward_agree_on_the_seed_rows(world):
    graph, feat, edges, _ = world
    s = _sampler(graph)
    u = s.seed_union(NEG)
    out = s.sample_from_edges(EdgeSamplerInput(
        row=edges[0, :Q], col=edges[1, :Q], neg_sampling=NEG))
    model = GraphSAGE(hidden_features=16, out_features=8, num_layers=3,
                      dropout_rate=0.0)
    state = init_train_state(model, optax.adam(1e-3), DIM,
                             jax.random.PRNGKey(0))
    x = feat.gather(out.node)
    ei = jnp.stack([out.row, out.col])
    whole = model.apply(state.params, x, ei, out.edge_mask)
    trimmed = model.apply(state.params, x, ei, out.edge_mask,
                          hops=u.hop_bounds)
    assert trimmed.shape == (4 * Q, 8) and whole.shape == (N, 8)
    live = int(np.asarray(out.num_sampled_nodes)[0])
    np.testing.assert_allclose(np.asarray(trimmed)[:live],
                               np.asarray(whole)[:live], rtol=1e-5,
                               atol=1e-6)
    assert np.asarray(out.metadata["edge_label_index"]).max() < live
    assert [e[1] for e in model.layer_extents(u.hop_bounds)] == [
        2560, 1024, 256]


# -- (f) the epoch driver ----------------------------------------------------

def test_shuffled_positions_is_a_permutation_drawn_in_blocks():
    for n in (1, 2, 5, 1000, 4097):
        blocks = list(shuffled_positions(n, np.random.default_rng(3), 64))
        assert all(b.shape[0] == 64 for b in blocks[:-1])
        got = np.concatenate(blocks)
        assert sorted(got.tolist()) == list(range(n))
    a = np.concatenate(list(shuffled_positions(
        1000, np.random.default_rng(3), 64)))
    b = np.concatenate(list(shuffled_positions(
        1000, np.random.default_rng(4), 300)))
    assert (a != b).any()
    assert (a != np.arange(1000)).mean() > 0.9


def test_link_seed_blocks_cover_every_edge_once(world):
    edges = world[2][:, :150]
    blocks = list(link_seed_blocks(edges, Q, 4, np.random.default_rng(0)))
    assert all(b.shape == (4, 2, Q) for b in blocks) and len(blocks) == 3
    flat = np.concatenate([b.transpose(1, 0, 2).reshape(2, -1)
                           for b in blocks], axis=1)
    live = flat[0] >= 0
    assert live.sum() == 150 and ((flat[1] >= 0) == live).all()
    assert live[:150].all()                     # padding trails
    assert sorted(map(tuple, flat[:, live].T.tolist())) == sorted(
        map(tuple, edges.T.tolist()))


def test_scanned_epoch_over_seed_edges_resumes_to_the_same_stream(world):
    graph, feat, edges, _ = world
    model = GraphSAGE(hidden_features=16, out_features=8, num_layers=3,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    state0 = init_train_state(model, tx, DIM, jax.random.PRNGKey(0))
    step = make_scanned_link_train_step(model, tx, _sampler(graph), feat,
                                        neg_sampling=NEG)
    seed_edges, key = edges[:, :200], jax.random.PRNGKey(9)
    seen = {}
    state, losses, accs, ovf = run_scanned_epoch(
        step, state0, seed_edges, Q, 2, np.random.default_rng(1), key,
        on_block=lambda st, i: seen.__setitem__(i, st))
    assert losses.shape == accs.shape == (13,) and ovf == 0
    assert np.isfinite(losses).all() and (0 <= accs).all() and (
        accs <= 1).all()
    assert sorted(seen) == list(range(7)) and int(state.step) == 13
    again, tail, _, _ = run_scanned_epoch(
        step, seen[2], seed_edges, Q, 2, np.random.default_rng(1), key,
        start_block=3)
    assert tail.tolist() == losses[6:].tolist()
    for a, b in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(state)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_the_link_step_counts_padded_slots_and_names_its_scopes(world):
    from glt_tpu.obs import metrics

    graph, feat, edges, _ = world
    model = GraphSAGE(hidden_features=16, out_features=8, num_layers=3,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    metrics.reset()
    metrics.enable()
    try:
        step = make_scanned_link_train_step(model, tx, _sampler(graph),
                                            feat, neg_sampling=NEG)
        state = init_train_state(model, tx, DIM, jax.random.PRNGKey(0))
        blk = next(link_seed_blocks(edges[:, :64], Q, 4,
                                    np.random.default_rng(0)))
        text = jax.jit(step).lower(state, blk, jax.random.PRNGKey(1)
                                   ).as_text(debug_info=True)
        _, _, _, flags = step(state, blk, jax.random.PRNGKey(1))
        run_scanned_epoch(step, state, edges[:, :64], Q, 4,
                          np.random.default_rng(0), jax.random.PRNGKey(1))
        snap = metrics.snapshot()
    finally:
        metrics.disable()
        metrics.reset()
    for scope in ("glt.sample.negative", "glt.sample.relabel",
                  "glt.sample.hop3", "glt.sample.induce", "glt.gather.feat",
                  "glt.model.agg", "glt.step.loss", "glt.step.update"):
        assert scope in text, scope
    assert "glt.gather.label" not in text
    assert np.asarray(flags).shape == (4, 2) and not np.asarray(flags).any()
    assert snap["glt.link.seed_union_width"] == 4 * Q
    assert snap["glt.link.node_rows"] == N
    assert snap["glt.link.neg_padded_slots"] == 0
    assert snap["glt.link.overflowed_batches"] == 0
    # the model runs trimmed: layer 3 over the hop-1 block only
    assert snap["glt.model.edge_slots"] == 2560
    layers = sorted(v for k, v in snap.items()
                    if k.startswith("glt.model.layer_edge_slots"))
    assert layers == [256, 1024, 2560]
    # and every layer aggregates its hop blocks without a scatter
    assert layers == sorted(v for k, v in snap.items()
                            if k.startswith("glt.model.layer_block_slots"))
