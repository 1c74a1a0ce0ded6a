"""Multi-hop NeighborSampler tests vs numpy oracles.

Mirrors the reference's sampler tests (test/python/test_neighbor_sampler.py):
tiny CSR graphs with closed-form expectations, checking dedup order,
relabel consistency, direction transpose, and link-path metadata.
"""
import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from glt_tpu.data.topology import CSRTopo
from glt_tpu.data.graph import Graph
from glt_tpu.sampler import (
    EdgeSamplerInput,
    NegativeSampling,
    NeighborSampler,
    NodeSamplerInput,
)


def ring_graph(n=20, hops=2):
    """Ring with forward edges i -> (i+1) % n and i -> (i+2) % n."""
    src = np.repeat(np.arange(n), 2)
    dst = np.concatenate([[(i + 1) % n, (i + 2) % n] for i in range(n)])
    return CSRTopo(np.stack([src, dst]), num_nodes=n)


@pytest.fixture(scope="module")
def graph():
    return Graph(ring_graph(), mode="HOST")


def valid_nodes(out):
    return np.asarray(out.node)[np.asarray(out.node_mask)]


def valid_edges(out):
    m = np.asarray(out.edge_mask)
    return (np.asarray(out.row)[m], np.asarray(out.col)[m],
            np.asarray(out.edge)[m])


class TestSampleFromNodes:
    def test_seeds_first_and_unique(self, graph):
        s = NeighborSampler(graph, [2, 2], batch_size=4, seed=0)
        seeds = np.array([3, 7, 3, 11])  # duplicate seed
        out = s.sample_from_nodes(NodeSamplerInput(seeds))
        nodes = valid_nodes(out)
        # Seeds dedup to first-occurrence order at the front.
        assert list(nodes[:3]) == [3, 7, 11]
        assert len(set(nodes.tolist())) == len(nodes)

    def test_edges_are_real_and_relabeled(self, graph):
        s = NeighborSampler(graph, [2, 2], batch_size=4, seed=1)
        out = s.sample_from_nodes(NodeSamplerInput(np.array([0, 5, 10, 15])))
        nodes = np.asarray(out.node)
        row, col, eid = valid_edges(out)
        topo = graph.topo
        src_g, dst_g = topo.to_coo()
        edge_set = set(zip(src_g.tolist(), dst_g.tolist()))
        # row = neighbor side, col = seed side (direction transpose):
        # the sampled out-edge is (node[col] -> node[row]).
        for r, c, e in zip(row, col, eid):
            assert (nodes[c], nodes[r]) in edge_set
            # edge id consistency with CSR ordering
            assert topo.indices[e] == nodes[r]

    def test_full_low_degree_rows(self, graph):
        # degree 2 everywhere; fanout 3 must return both neighbors, no more.
        s = NeighborSampler(graph, [3], batch_size=2, seed=2)
        out = s.sample_from_nodes(NodeSamplerInput(np.array([4, 9])))
        row, col, _ = valid_edges(out)
        nodes = np.asarray(out.node)
        got = sorted(nodes[r] for r, c in zip(row, col) if nodes[c] == 4)
        assert got == [5, 6]

    def test_num_sampled_counts(self, graph):
        s = NeighborSampler(graph, [2, 2], batch_size=3, seed=3)
        out = s.sample_from_nodes(NodeSamplerInput(np.array([0, 1, 2])))
        nsn = np.asarray(out.num_sampled_nodes)
        assert nsn[0] == 3
        assert nsn.sum() == len(valid_nodes(out))

    def test_reproducible(self, graph):
        s1 = NeighborSampler(graph, [1, 1], batch_size=2, seed=42)
        s2 = NeighborSampler(graph, [1, 1], batch_size=2, seed=42)
        a = s1.sample_from_nodes(NodeSamplerInput(np.array([0, 7])))
        b = s2.sample_from_nodes(NodeSamplerInput(np.array([0, 7])))
        assert np.array_equal(np.asarray(a.node), np.asarray(b.node))
        assert np.array_equal(np.asarray(a.row), np.asarray(b.row))

    def test_padded_batch(self, graph):
        s = NeighborSampler(graph, [2], batch_size=4, seed=0)
        out = s.sample_from_nodes(NodeSamplerInput(np.array([6])))  # 1 < 4
        nodes = valid_nodes(out)
        assert nodes[0] == 6
        assert len(nodes) == 3  # 6 + its two neighbors


class TestSampleFromEdges:
    def test_binary_negative(self, graph):
        s = NeighborSampler(graph, [2], batch_size=4, seed=0)
        inp = EdgeSamplerInput(
            row=np.array([0, 2, 4, 6]), col=np.array([1, 3, 5, 7]),
            neg_sampling=NegativeSampling("binary", 1))
        out = s.sample_from_edges(inp)
        eli = np.asarray(out.metadata["edge_label_index"])
        lab = np.asarray(out.metadata["edge_label"])
        nodes = np.asarray(out.node)
        assert eli.shape == (2, 8)
        # positive pairs resolve to the input edges
        for i, (r, c) in enumerate(zip([0, 2, 4, 6], [1, 3, 5, 7])):
            assert nodes[eli[0, i]] == r
            assert nodes[eli[1, i]] == c
            assert lab[i] == 1
        assert (lab[4:] == 0).all()

    def test_triplet(self, graph):
        s = NeighborSampler(graph, [2], batch_size=3, seed=1)
        inp = EdgeSamplerInput(
            row=np.array([0, 5, 10]), col=np.array([1, 6, 11]),
            neg_sampling=NegativeSampling("triplet", 2))
        out = s.sample_from_edges(inp)
        nodes = np.asarray(out.node)
        srci = np.asarray(out.metadata["src_index"])
        dpi = np.asarray(out.metadata["dst_pos_index"])
        dni = np.asarray(out.metadata["dst_neg_index"])
        assert dni.shape == (3, 2)
        assert [nodes[i] for i in srci] == [0, 5, 10]
        assert [nodes[i] for i in dpi] == [1, 6, 11]
        assert (dni >= 0).all()


class TestSubgraph:
    def test_induced(self, graph):
        s = NeighborSampler(graph, [2], batch_size=3, seed=5)
        out = s.subgraph(NodeSamplerInput(np.array([0, 1, 2])), max_degree=4)
        nodes = np.asarray(out.node)
        m = np.asarray(out.edge_mask)
        row = np.asarray(out.row)[m]
        col = np.asarray(out.col)[m]
        src_g, dst_g = graph.topo.to_coo()
        edge_set = set(zip(src_g.tolist(), dst_g.tolist()))
        node_set = set(nodes[np.asarray(out.node_mask)].tolist())
        for r, c in zip(row, col):
            assert (nodes[r], nodes[c]) in edge_set
            assert nodes[r] in node_set and nodes[c] in node_set
        # every induced edge between sampled nodes must be present
        expected = {(a, b) for a, b in edge_set
                    if a in node_set and b in node_set}
        got = {(nodes[r], nodes[c]) for r, c in zip(row, col)}
        assert got == expected


class TestDedupStrategies:
    def test_dense_matches_sort(self):
        """The dense scatter-map inducer and the argsort-based path are
        drop-in equivalents: identical nodes, edges, masks, and counts for
        the same key on a random graph with duplicate-heavy fanout."""
        from glt_tpu.sampler import NeighborSampler, NodeSamplerInput

        rng = np.random.default_rng(7)
        n, e = 60, 400
        topo = CSRTopo(np.stack([rng.integers(0, n, e),
                                 rng.integers(0, n, e)]), num_nodes=n)
        g = Graph(topo, mode="HOST")
        seeds = rng.integers(0, n, 8)
        key = jax.random.PRNGKey(3)
        outs = {}
        for dedup in ("dense", "sort"):
            s = NeighborSampler(g, [4, 3], batch_size=8, seed=0, dedup=dedup)
            outs[dedup] = s.sample_from_nodes(NodeSamplerInput(seeds),
                                              key=key)
        a, b = outs["dense"], outs["sort"]
        for field in ("node", "row", "col", "edge", "node_mask",
                      "edge_mask", "num_sampled_nodes",
                      "num_sampled_edges"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
                err_msg=field)

    def test_with_edge_false_skips_edge_ids(self):
        """with_edge=False must produce edge=None (no edge-id gather) with
        everything else identical to with_edge=True (the reference's
        Sample vs SampleWithEdge split, random_sampler.cu:267,310)."""
        from glt_tpu.sampler import NeighborSampler, NodeSamplerInput

        g = Graph(ring_graph(), mode="HOST")
        key = jax.random.PRNGKey(5)
        seeds = np.arange(6)
        outs = {}
        for we in (True, False):
            s = NeighborSampler(g, [2, 2], batch_size=6, with_edge=we)
            outs[we] = s.sample_from_nodes(NodeSamplerInput(seeds), key=key)
        assert outs[False].edge is None
        assert outs[True].edge is not None
        for field in ("node", "row", "col", "node_mask", "edge_mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(outs[True], field)),
                np.asarray(getattr(outs[False], field)), err_msg=field)

    def test_last_hop_nodedup_equivalent_edges(self):
        """last_hop_dedup=False must produce the SAME global edge multiset
        (and identical interior hops) as the exact path for the same key —
        only the node list's tail representation changes (leaf block with
        possible duplicates instead of compact uniques)."""
        from glt_tpu.sampler import NeighborSampler, NodeSamplerInput

        rng = np.random.default_rng(11)
        n, e = 80, 600
        topo = CSRTopo(np.stack([rng.integers(0, n, e),
                                 rng.integers(0, n, e)]), num_nodes=n)
        g = Graph(topo, mode="HOST")
        seeds = rng.integers(0, n, 8)
        key = jax.random.PRNGKey(13)
        outs = {}
        for dedup in ("dense", "sort"):
            for lhd in (True, False):
                s = NeighborSampler(g, [4, 3], batch_size=8, seed=0,
                                    dedup=dedup, last_hop_dedup=lhd)
                outs[(dedup, lhd)] = s.sample_from_nodes(
                    NodeSamplerInput(seeds), key=key)

        def global_edges(out):
            nodes = np.asarray(out.node)
            m = np.asarray(out.edge_mask)
            src = nodes[np.asarray(out.col)[m]]
            dst = nodes[np.asarray(out.row)[m]]
            return sorted(zip(src.tolist(), dst.tolist()))

        exact = outs[("dense", True)]
        for k, out in outs.items():
            assert global_edges(out) == global_edges(exact), k
            # row local ids resolve to valid (masked-in) node slots
            nodes = np.asarray(out.node)
            nm = np.asarray(out.node_mask)
            m = np.asarray(out.edge_mask)
            for r in np.asarray(out.row)[m]:
                assert nm[r] and nodes[r] >= 0
        # fast modes agree with each other bit-for-bit
        for field in ("node", "row", "col", "node_mask", "edge_mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(outs[("dense", False)], field)),
                np.asarray(getattr(outs[("sort", False)], field)),
                err_msg=field)
        # seeds stay at the front in fast mode too (first-occurrence order)
        fnodes = np.asarray(outs[("dense", False)].node)
        uniq_seeds = list(dict.fromkeys(seeds.tolist()))
        assert list(fnodes[:len(uniq_seeds)]) == uniq_seeds

    def test_dense_induce_final_matches_dense_induce(self):
        """The commit-free last-hop inducer assigns the same locals,
        node_buf, and count as the committing one."""
        from glt_tpu.ops.unique import (dense_induce, dense_induce_final,
                                        dense_induce_init)

        rng = np.random.default_rng(3)
        n, cap = 50, 40
        st_a = dense_induce_init(n, cap)
        st_b = dense_induce_init(n, cap)
        first = jnp.asarray(rng.integers(-1, n, 16).astype(np.int32))
        st_a, _ = dense_induce(st_a, first)
        st_b, _ = dense_induce(st_b, first)
        cand = jnp.asarray(rng.integers(-1, n, 24).astype(np.int32))
        sa, la = dense_induce(st_a, cand)
        sb, lb = dense_induce_final(st_b, cand)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        np.testing.assert_array_equal(np.asarray(sa.node_buf),
                                      np.asarray(sb.node_buf))
        assert int(sa.count) == int(sb.count)

    def test_batched_matches_single(self):
        """sample_from_nodes_batched(G batches) equals G independent
        single-batch samples with the same per-batch keys."""
        from glt_tpu.sampler import NeighborSampler, NodeSamplerInput

        g = Graph(ring_graph(), mode="HOST")
        s = NeighborSampler(g, [2, 2], batch_size=6, seed=0)
        seeds = np.stack([np.arange(0, 6), np.arange(6, 12),
                          np.arange(12, 18)])
        key = jax.random.PRNGKey(9)
        outs = s.sample_from_nodes_batched(seeds, key=key)
        keys = jax.random.split(key, 3)
        for i in range(3):
            single = s.sample_from_nodes(NodeSamplerInput(seeds[i]),
                                         key=keys[i])
            for field in ("node", "row", "col", "node_mask", "edge_mask"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(outs, field))[i],
                    np.asarray(getattr(single, field)), err_msg=field)


# -- the hop-block layout GraphSAGE trims its layers by ---------------------
#: Sampler variants that share the model, as ``NeighborSampler`` keywords
#: given ``last_hop_dedup`` (batch 8, fanout [3, 3, 2]: 248 rows uncapped).
#: Without a frontier cap the leaf block alone fills an occupancy capacity
#: up to the full one, so that case caps the frontier too.
HOP_VARIANTS = {
    "uncapped": lambda lhd: {},
    "frontier_cap": lambda lhd: {"frontier_cap": 16},
    "occupancy_overflow": lambda lhd: (
        {"node_capacity": 104} if lhd
        else {"frontier_cap": 16, "node_capacity": 76}),
    "padded_seeds": lambda lhd: {},
}
HOP_BATCH, HOP_FANOUT = 8, [3, 3, 2]


def hop_graph(n=2000, seed=0):
    """Degrees 0..12, so some frontier nodes have fewer neighbours than
    the fanout (masked slots) and some none."""
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), rng.integers(0, 13, n))
    return Graph(CSRTopo(np.stack([src, rng.integers(0, n, src.shape[0])]),
                         num_nodes=n), mode="HOST")


def hop_sampler(graph, dedup, lhd, variant):
    return NeighborSampler(graph, HOP_FANOUT, batch_size=HOP_BATCH,
                           dedup=dedup, last_hop_dedup=lhd, with_edge=False,
                           **HOP_VARIANTS[variant](lhd))


def hop_sample(sampler, variant, seed=0):
    """One batch of ``variant`` out of ``sampler``."""
    seeds = np.random.default_rng(seed).choice(
        sampler.graph.num_nodes, HOP_BATCH, replace=False)
    if variant == "padded_seeds":
        seeds[4], seeds[5:] = seeds[0], -1      # a duplicate, three pads
    out = sampler.sample_from_nodes(NodeSamplerInput(seeds))
    if variant == "occupancy_overflow":
        assert bool(out.metadata["overflow"])
    return out


def assert_hop_layout(out, bounds):
    """Every valid edge of hop block ``k`` has ``col < node_bounds[k-1]``
    and ``row < node_bounds[k]``; the blocks tile the edge slots."""
    nb, eb = bounds.node_bounds, bounds.edge_bounds
    row, col = np.asarray(out.row), np.asarray(out.col)
    mask = np.asarray(out.edge_mask)
    assert eb[0] == 0 and eb[-1] == row.shape[0]
    assert nb[-1] == np.asarray(out.node).shape[0]
    assert mask.any()
    for k in range(1, len(eb)):
        blk = slice(eb[k - 1], eb[k])
        m = mask[blk]
        assert (col[blk][m] >= 0).all() and (row[blk][m] >= 0).all()
        assert (col[blk][m] < nb[k - 1]).all(), k
        assert (row[blk][m] < nb[k]).all(), k
        # the block is hop k's: as many valid edges as the sampler counted
        assert m.sum() == int(np.asarray(out.num_sampled_edges)[k - 1])


def assert_static_destinations(out, bounds):
    """``hop_bounds``' second rule, which ``models/conv.py::block_mean``
    stands on: every unmasked edge at slot ``s`` of hop block ``k`` has
    ``col == col[first slot of the block] + s // fanout_k``, the first
    slot holding that start masked or not, or ``-1`` (then nothing in the
    block is unmasked); no destination row belongs to two blocks and a
    block starts at or behind the live rows of those before it.  Returns
    the start of each block."""
    col, mask = np.asarray(out.col), np.asarray(out.edge_mask)
    assert sum(w * f for w, f in bounds.blocks) == col.shape[0]
    starts, seen_upto, offset = [], 0, 0
    for k, (w, f) in enumerate(bounds.blocks, 1):
        assert bounds.edge_bounds[k] - bounds.edge_bounds[k - 1] == w * f
        blk = slice(offset, offset + w * f)
        c, m = col[blk], mask[blk]
        starts.append(int(c[0]))
        assert c[0] == -1 or c[0] >= seen_upto, (k, c[0], seen_upto)
        if m.any():
            want = c[0] + np.arange(w * f) // f
            assert c[0] >= 0 and (c[m] == want[m]).all(), k
            seen_upto = int(c[m].max()) + 1
        offset += w * f
    return starts


def test_hop_bounds_of_the_products_shape():
    from glt_tpu.sampler import hop_bounds

    b = hop_bounds(1024, [15, 10, 5], None, 402944)
    assert b.node_bounds == (1024, 16384, 169984, 402944)
    assert b.edge_bounds == (0, 15360, 168960, 936960)
    assert b.blocks == ((1024, 15), (15360, 10), (153600, 5))
    assert hop_bounds(1024, [15, 10, 5], 8192).blocks == (
        (1024, 15), (8192, 10), (8192, 5))
    assert hop_bounds(1024, [15, 10, 5]).node_bounds[-1] == 937984
    capped = hop_bounds(1024, [15, 10, 5], 8192)
    assert capped.node_bounds == (1024, 16384, 98304, 139264)
    assert hash(b) != hash(capped)          # a static argument of a trace


@pytest.mark.parametrize("variant", sorted(HOP_VARIANTS))
@pytest.mark.parametrize("lhd", [True, False])
@pytest.mark.parametrize("dedup", ["dense", "sort"])
def test_hop_blocks_keep_their_static_bounds(dedup, lhd, variant):
    """What per-layer trimming stands on (models/sage.py): a sampler
    change that breaks the layout fails here, not in a loss."""
    s = hop_sampler(hop_graph(), dedup, lhd, variant)
    assert s.hop_bounds.node_bounds[-1] == s.node_capacity
    for seed in range(3):
        assert_hop_layout(hop_sample(s, variant, seed), s.hop_bounds)


@pytest.mark.parametrize("variant", sorted(HOP_VARIANTS) + ["empty_frontier"])
@pytest.mark.parametrize("lhd", [True, False])
@pytest.mark.parametrize("dedup", ["dense", "sort"])
def test_hop_blocks_have_static_destinations(dedup, lhd, variant):
    """What the block aggregation stands on (models/conv.py::block_mean):
    where an edge slot aggregates to is its block's start plus its static
    position, in every sampler variant the model can meet."""
    graph = hop_graph()
    if variant == "empty_frontier":
        # Seeds without a neighbour: hop 1 samples nothing and hops 2-3
        # have no frontier at all.
        s = hop_sampler(graph, dedup, lhd, "uncapped")
        lone = np.flatnonzero(np.diff(np.asarray(graph.indptr)) == 0)
        out = s.sample_from_nodes(NodeSamplerInput(lone[:HOP_BATCH]))
        assert not np.asarray(out.edge_mask).any()
        assert assert_static_destinations(out, s.hop_bounds) == [0, -1, -1]
        return
    s = hop_sampler(graph, dedup, lhd, variant)
    for seed in range(3):
        starts = assert_static_destinations(hop_sample(s, variant, seed),
                                            s.hop_bounds)
        assert starts[0] == 0 and starts == sorted(starts)


# -- every hop's inducer: the sorted chain against the parent's program ----
@contextlib.contextmanager
def map_form(monkeypatch, module):
    """Inside the block the samplers of ``module`` trace the parent's
    program: every chain holds the id map, so ``dense_induce`` runs at
    every hop and ``dense_induce_final`` at the last.  Yields the list of
    the chains' bounds, one entry a chain, so a test can tell a fresh
    trace from a cached program."""
    from glt_tpu.ops.unique import dense_induce_init
    uses = []

    def parent(num_nodes, capacity, known_last):
        uses.append(known_last)
        return dense_induce_init(num_nodes, capacity)
    with monkeypatch.context() as patch:
        patch.setattr(module, "induce_init", parent)
        yield uses


def assert_outputs_equal(got, want):
    """Every array of two sampler outputs, bit for bit."""
    a, ta = jax.tree.flatten(got)
    b, tb = jax.tree.flatten(want)
    assert ta == tb
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def sorted_slots():
    """``hop -> glt.sample.induce_sorted_slots{hop}``, read from a registry
    that records for the length of the test."""
    from glt_tpu.obs import metrics
    metrics.reset()
    metrics.enable()
    yield lambda hop: metrics.snapshot().get(
        "glt.sample.induce_sorted_slots{hop=%d}" % hop)
    metrics.disable()
    metrics.reset()


INDUCE_VARIANTS = dict(
    {name: make(True) for name, make in HOP_VARIANTS.items()},
    # The bound on known nodes (80) passes the capacity: nodes past the
    # buffer's end live in the id map alone, so the map form stays.
    known_past_capacity={"frontier_cap": 16, "node_capacity": 76})


@pytest.mark.parametrize("variant", sorted(INDUCE_VARIANTS))
@pytest.mark.parametrize("with_edge", [False, True])
def test_sampler_output_equals_the_map_forms(variant, with_edge,
                                             monkeypatch, sorted_slots):
    """The whole ``SamplerOutput`` with every hop sorted against the
    parent's program, same graph, seeds and key: uncapped, under a
    frontier cap, an occupancy capacity that overflows, padded seeds;
    and the engagement gauge of every hop, 0 the seeds."""
    import glt_tpu.sampler.neighbor_sampler as mod

    def build():
        return NeighborSampler(hop_graph(), HOP_FANOUT, batch_size=HOP_BATCH,
                               with_edge=with_edge,
                               **INDUCE_VARIANTS[variant])
    new = build()
    got = [hop_sample(new, variant, seed) for seed in range(3)]
    w = new._widths
    widths = [w[0], w[0] * 3, w[1] * 3, w[2] * 2]   # seeds, then each hop
    knowns = [0] + list(np.cumsum(widths[:-1]))
    sorts = variant != "known_past_capacity"
    assert sorts == (knowns[-1] <= new.node_capacity)
    assert [sorted_slots(hop) for hop in range(4)] == [
        known + m if sorts else 0 for known, m in zip(knowns, widths)]
    with map_form(monkeypatch, mod) as parent:
        old = build()
        want = [hop_sample(old, variant, seed) for seed in range(3)]
    assert parent == [knowns[-1]]
    assert [sorted_slots(hop) for hop in range(4)] == [0] * 4
    for a, b in zip(got, want):
        assert_outputs_equal(a, b)
