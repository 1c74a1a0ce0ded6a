import jax
import jax.numpy as jnp
import numpy as np

from glt_tpu.data import CSRTopo, Graph
from glt_tpu.ops import edge_in_csr, node_subgraph, sample_negative_edges


def _random_graph(seed=0, n=40, e=300):
    rng = np.random.default_rng(seed)
    row, col = rng.integers(0, n, e), rng.integers(0, n, e)
    topo = CSRTopo(np.stack([row, col]), num_nodes=n)
    return topo, set(zip(row.tolist(), col.tolist())), n


def test_edge_in_csr_matches_oracle():
    topo, edges, n = _random_graph()
    g = Graph(topo, with_sorted_columns=True)
    rng = np.random.default_rng(1)
    qs = rng.integers(0, n, 500)
    qd = rng.integers(0, n, 500)
    got = np.asarray(edge_in_csr(
        g.indptr, g.sorted_indices, jnp.asarray(qs, jnp.int32), jnp.asarray(qd, jnp.int32)))
    want = np.array([(s, d) in edges for s, d in zip(qs, qd)])
    np.testing.assert_array_equal(got, want)


def test_edge_in_csr_padding_is_false():
    topo, _, _ = _random_graph()
    g = Graph(topo, with_sorted_columns=True)
    got = np.asarray(edge_in_csr(
        g.indptr, g.sorted_indices,
        jnp.array([-1, 0], jnp.int32), jnp.array([0, -1], jnp.int32)))
    assert not got.any()


def test_strict_negative_sampling_avoids_edges():
    topo, edges, n = _random_graph(seed=2, n=30, e=200)
    g = Graph(topo, with_sorted_columns=True)
    out = sample_negative_edges(
        g.indptr, g.sorted_indices, num=256, key=jax.random.key(5),
        num_nodes=n, trials=8, padding=False,
    )
    src, dst, mask, _ = map(np.asarray, out)
    assert mask.sum() > 200  # density ~0.22 per trial; 8 trials ⇒ nearly all filled
    for s, d, m in zip(src, dst, mask):
        if m:
            assert (int(s), int(d)) not in edges


def test_negative_sampling_with_padding_always_fills():
    topo, _, n = _random_graph(seed=3)
    g = Graph(topo, with_sorted_columns=True)
    out = sample_negative_edges(
        g.indptr, g.sorted_indices, num=64, key=jax.random.key(0),
        num_nodes=n, trials=3, padding=True,
    )
    src, dst, mask, _ = map(np.asarray, out)
    assert mask.all()
    assert ((src >= 0) & (src < n)).all() and ((dst >= 0) & (dst < n)).all()


def test_weighted_draw_respects_support_and_bias():
    from glt_tpu.ops.negative_sample import weight_to_cdf, weighted_draw

    w = np.zeros(20, np.float32)
    w[[3, 7]] = [1.0, 3.0]
    cdf = weight_to_cdf(w)
    draws = np.asarray(weighted_draw(jax.random.key(0), cdf, (4000,)))
    assert set(np.unique(draws)) == {3, 7}
    frac7 = (draws == 7).mean()
    assert 0.70 < frac7 < 0.80  # expected 0.75


def test_weighted_negative_edges_stay_in_support():
    topo, edges, n = _random_graph(seed=4, n=30, e=60)
    from glt_tpu.ops.negative_sample import weight_to_cdf

    g = Graph(topo, with_sorted_columns=True)
    w = np.zeros(n, np.float32)
    support = [2, 9, 17, 25]
    w[support] = 1.0
    cdf = weight_to_cdf(w)
    out = sample_negative_edges(
        g.indptr, g.sorted_indices, num=128, key=jax.random.key(1),
        num_nodes=n, trials=8, padding=True, src_cdf=cdf, dst_cdf=cdf)
    src, dst, _, _ = map(np.asarray, out)
    assert set(np.unique(src)) <= set(support)
    assert set(np.unique(dst)) <= set(support)


def test_sampler_weighted_binary_negatives():
    """NegativeSampling.weight flows through sample_from_edges: negative
    endpoints land only in the weight's support (cf. sampler/base.py:101
    ``weight``)."""
    from glt_tpu.sampler import (EdgeSamplerInput, NegativeSampling,
                                 NeighborSampler)

    topo, edges, n = _random_graph(seed=5, n=30, e=90)
    g = Graph(topo, mode="DEVICE", with_sorted_columns=True)
    w = np.zeros(n, np.float32)
    support = {4, 11, 23}
    w[list(support)] = 1.0
    sampler = NeighborSampler(g, [2], batch_size=8, seed=0)
    rows = np.asarray(topo.indptr)
    esrc = np.repeat(np.arange(n), np.diff(rows))[:8].astype(np.int64)
    edst = np.asarray(topo.indices)[:8].astype(np.int64)
    out = sampler.sample_from_edges(EdgeSamplerInput(
        row=esrc, col=edst,
        neg_sampling=NegativeSampling("binary", 2, weight=w)))
    eli = np.asarray(out.metadata["edge_label_index"])
    lab = np.asarray(out.metadata["edge_label"])
    nodes = np.asarray(out.node)
    neg = lab == 0
    gsrc, gdst = nodes[eli[0][neg]], nodes[eli[1][neg]]
    assert set(gsrc.tolist()) <= support
    assert set(gdst.tolist()) <= support


def test_hetero_strict_binary_negatives():
    """Hetero binary negatives reject existing edges via the seed type's
    sorted-column CSR (the CUDA strict mode's hetero analog)."""
    from glt_tpu.sampler import NegativeSampling
    from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler
    from glt_tpu.sampler.base import EdgeSamplerInput

    # Bipartite u->v over 6x6 where (i, j) is an edge iff (i + j) even:
    # exactly half of all pairs are edges, so strict rejection has real
    # work and non-edges are abundant.
    nu = nv = 6
    pairs = [(i, j) for i in range(nu) for j in range(nv)
             if (i + j) % 2 == 0]
    src = np.array([p[0] for p in pairs])
    dst = np.array([p[1] for p in pairs])
    et = ("u", "to", "v")
    rev = ("v", "rev_to", "u")
    graphs = {
        et: Graph(CSRTopo(np.stack([src, dst]), num_nodes=nu),
                  mode="DEVICE"),
        rev: Graph(CSRTopo(np.stack([dst, src]), num_nodes=nv),
                   mode="DEVICE"),
    }
    sampler = HeteroNeighborSampler(graphs, {et: [2], rev: [2]},
                                    input_type="u", batch_size=4, seed=0)
    out = sampler.sample_from_edges(EdgeSamplerInput(
        row=src[:4].astype(np.int64), col=dst[:4].astype(np.int64),
        input_type=et, neg_sampling=NegativeSampling("binary", 4)))
    eli = np.asarray(out.metadata["edge_label_index"])
    lab = np.asarray(out.metadata["edge_label"])
    u_nodes = np.asarray(out.node["u"])
    v_nodes = np.asarray(out.node["v"])
    neg = lab == 0
    edge_set = set(pairs)
    gsrc, gdst = u_nodes[eli[0][neg]], v_nodes[eli[1][neg]]
    hits = sum((int(s), int(d)) in edge_set for s, d in zip(gsrc, gdst))
    # 16 negatives, 5 strict trials at 50% density: expected stray
    # positives ~0.5; uniform non-strict would average 8.
    assert hits <= 2


def test_node_subgraph_matches_oracle():
    topo, edges, n = _random_graph(seed=4, n=25, e=150)
    g = Graph(topo)
    nodes = np.array([3, 7, 11, 19, 2, -1, -1])
    out = node_subgraph(
        g.indptr, g.indices, jnp.asarray(nodes, jnp.int32),
        max_degree=int(topo.degrees.max()), edge_ids=g.edge_ids,
    )
    rows, cols, eids, mask = map(np.asarray, out)
    nodeset = [int(v) for v in nodes if v >= 0]
    want = set()
    for i, u in enumerate(nodeset):
        for j, v in enumerate(nodeset):
            count = sum(1 for (a, b) in zip(*topo.to_coo()) if a == u and b == v)
            for _ in range(count):
                want.add((i, j))
    got = set(zip(rows[mask].tolist(), cols[mask].tolist()))
    assert got == want
    # Edge ids reference real global edges consistent with the local pair.
    r2, c2 = topo.to_coo()
    for r, c, e, m in zip(rows, cols, eids, mask):
        if m:
            assert r2[np.where(topo.edge_ids == e)[0][0]] == nodeset[r]
            assert c2[np.where(topo.edge_ids == e)[0][0]] == nodeset[c]
