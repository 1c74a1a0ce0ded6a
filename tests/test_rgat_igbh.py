"""The IGBH R-GAT path at small sizes: upstream's ``RGNN('rgat')`` against
a plain reference, the bipartite ``GATConv``, the hetero sampler's exact
clamp and occupancy capacities, every layer run over what reaches the
seeds, and the scanned step's overflow channel and gauges."""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from glt_tpu.data.graph import Graph
from glt_tpu.data.topology import CSRTopo
from glt_tpu.models import (init_hetero_state,
                            make_scanned_hetero_train_step,
                            run_scanned_epoch)
from glt_tpu.models.conv import GATConv
from glt_tpu.models.rgat import RGAT, RGNN
from glt_tpu.models.train import seed_cross_entropy
from glt_tpu.sampler import NodeSamplerInput
from glt_tpu.sampler.hetero_neighbor_sampler import (
    HeteroNeighborSampler, calibrate_hetero_node_capacity,
    hetero_hop_bounds, hetero_hop_widths, measure_hetero_occupancy)
from glt_tpu.testing import rgnn_reference as ref
from glt_tpu.typing import reverse_edge_type

COUNTS = {"paper": 60, "author": 80, "institute": 6, "fos": 12}
FORWARD = [("paper", "cites", "paper", 3), ("paper", "written_by", "author", 2),
           ("author", "affiliated_to", "institute", 1),
           ("paper", "topic", "fos", 2)]
CLASSES, DIM = 11, 16


def igbh_graphs(seed=0, max_deg=None):
    """IGBH's four node types and seven relations (the three typed
    forward relations with their exact transposes), degrees 0..``deg``."""
    rng = np.random.default_rng(seed)
    graphs = {}
    for s_t, rel, d_t, deg in FORWARD:
        deg = max_deg or deg
        src = np.repeat(np.arange(COUNTS[s_t]), deg)
        keep = rng.random(src.shape[0]) < 0.8
        src = src[keep]
        dst = rng.integers(0, COUNTS[d_t], src.shape[0])
        graphs[(s_t, rel, d_t)] = Graph(
            CSRTopo(np.stack([src, dst]), num_nodes=COUNTS[s_t]),
            mode="HOST")
        if s_t != d_t:
            graphs[(d_t, "rev_" + rel, s_t)] = Graph(
                CSRTopo(np.stack([dst, src]), num_nodes=COUNTS[d_t]),
                mode="HOST")
    return graphs


def batch_of(sampler, feats, seeds, key=None):
    out = sampler.sample_from_nodes(NodeSamplerInput(np.asarray(seeds)),
                                    key=key)
    x = {t: jnp.where((n >= 0)[:, None],
                      jnp.asarray(feats[t])[jnp.maximum(n, 0)], 0)
         for t, n in out.node.items()}
    ei = {et: jnp.stack([out.row[et], out.col[et]]) for et in out.row}
    return out, x, ei


@pytest.fixture(scope="module")
def setup():
    graphs = igbh_graphs()
    rng = np.random.default_rng(1)
    feats = {t: rng.uniform(-1, 1, (n, DIM)).astype(np.float32)
             for t, n in COUNTS.items()}
    labels = rng.integers(0, CLASSES, COUNTS["paper"]).astype(np.int32)
    sampler = HeteroNeighborSampler(graphs, [3, 2, 2], "paper",
                                    batch_size=4, seed=0)
    ets = [reverse_edge_type(et) for et in sampler.edge_types]
    model = RGNN(ets, hidden_features=8, out_features=CLASSES,
                 target_type="paper", num_layers=3, heads=2,
                 dropout_rate=0.0)
    out, x, ei = batch_of(sampler, feats, [0, 7, 21, 40])
    params = model.init({"params": jax.random.PRNGKey(3)}, x, ei,
                        out.edge_mask)
    return dict(graphs=graphs, feats=feats, labels=labels, sampler=sampler,
                ets=ets, model=model, out=out, x=x, ei=ei, params=params)


def test_upstream_rgnn_matches_the_plain_reference(setup):
    """Forward, loss and every gradient of ``RGNN`` against
    ``glt_tpu.testing.rgnn_reference`` at the same random weights."""
    s = setup
    model, out, x, ei = s["model"], s["out"], s["x"], s["ei"]
    y = jnp.asarray(s["labels"])[jnp.maximum(out.node["paper"], 0)]
    edges = {et: (ei[et][0], ei[et][1], out.edge_mask[et]) for et in ei}

    def ours(p):
        logits = model.apply(p, x, ei, out.edge_mask, train=False)
        return seed_cross_entropy(logits, y, 4, out.node_mask["paper"])[0], \
            logits

    def plain(p):
        logits = ref.rgnn_forward(ref.layer_weights(p, s["ets"], 3), x,
                                  edges, "paper")
        return ref.seed_loss(logits, y, 4), logits

    (l1, z1), g1 = jax.jit(jax.value_and_grad(ours, has_aux=True))(
        s["params"])
    (l2, z2), g2 = jax.jit(jax.value_and_grad(plain, has_aux=True))(
        s["params"])
    assert z1.shape == (out.node["paper"].shape[0], CLASSES)
    np.testing.assert_allclose(z1[:4], z2[:4], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    flat1, flat2 = (jax.tree_util.tree_leaves_with_path(g) for g in (g1, g2))
    assert any(float(jnp.abs(v).max()) > 0 for _, v in flat1)
    for (path, a), (_, b) in zip(flat1, flat2):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6,
                                   err_msg=str(path))
    # Upstream's shape: no input projection, no head, a class-wide last
    # layer of one head, concatenated heads before it.
    tree = s["params"]["params"]
    assert sorted(tree) == ["layer0", "layer1", "layer2"]
    conv = tree["layer0"]["paper__cites__paper_conv"]
    assert conv["lin"]["kernel"].shape == (DIM, 8)
    assert conv["att_src"].shape == (2, 4)
    assert tree["layer2"]["paper__cites__paper_conv"]["att_src"].shape \
        == (1, CLASSES)


def test_bipartite_gatconv_is_the_concatenated_formulation():
    """``GATConv((x_src, x_dst))`` against the one-graph layer run on
    ``concat([x_dst, x_src])`` with shifted source ids (what
    ``HeteroConv`` built before), same weights."""
    rng = np.random.default_rng(0)
    n_src, n_dst, e = 9, 5, 30
    x_src = jnp.asarray(rng.normal(size=(n_src, 6)), jnp.float32)
    x_dst = jnp.asarray(rng.normal(size=(n_dst, 6)), jnp.float32)
    src = rng.integers(0, n_src, e)
    dst = rng.integers(0, n_dst, e)
    mask = jnp.asarray(rng.random(e) < 0.8)
    src = jnp.asarray(np.where(mask, src, -1), jnp.int32)
    dst = jnp.asarray(np.where(mask, dst, -1), jnp.int32)
    conv = GATConv(4, heads=3)
    params = conv.init(jax.random.PRNGKey(0), (x_src, x_dst),
                       jnp.stack([src, dst]), mask)
    got = conv.apply(params, (x_src, x_dst), jnp.stack([src, dst]), mask)
    joint = jnp.concatenate([x_dst, x_src])
    shifted = jnp.stack([jnp.where(src >= 0, src + n_dst, -1), dst])
    want = conv.apply(params, joint, shifted, mask)[:n_dst]
    assert got.shape == (n_dst, 12)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_rgat_defaults_keep_their_parameters_and_meaning(setup):
    """What examples/ and tests/test_hetero.py build: input Dense,
    residual, averaged heads, Dense head."""
    s = setup
    model = RGAT(edge_types=s["ets"], hidden_features=8,
                 out_features=CLASSES, target_type="paper", num_layers=2,
                 heads=2, conv="gat", dropout_rate=0.0)
    p = model.init({"params": jax.random.PRNGKey(0)}, s["x"], s["ei"],
                   s["out"].edge_mask)["params"]
    assert {"in_paper", "in_author", "layer0", "layer1", "head"} <= set(p)
    assert p["layer0"]["paper__cites__paper_conv"]["att_src"].shape == (2, 8)
    assert p["head"]["kernel"].shape == (8, CLASSES)


def _edges(out, nodes=None):
    """Live edges per relation as global-id pairs, in slot order."""
    nodes = nodes or out.node
    got = {}
    for et in out.row:
        m = np.asarray(out.edge_mask[et])
        src = np.asarray(nodes[et[0]])[np.asarray(out.row[et])[m]]
        dst = np.asarray(nodes[et[2]])[np.asarray(out.col[et])[m]]
        got[et] = np.stack([src, dst])
    return got


def test_the_exact_clamp_changes_shapes_and_no_edge():
    """Fanouts over every degree make sampling deterministic: the
    clamped sampler and the worst-case-width one must agree edge for
    edge and node for node."""
    graphs = igbh_graphs(max_deg=3)
    samp = HeteroNeighborSampler(graphs, [3, 3, 3], "paper", batch_size=16)
    worst_w, worst_c = hetero_hop_widths(
        samp.edge_types, samp.num_neighbors, {"paper": 16}, 3)
    assert samp.node_capacity["institute"] == COUNTS["institute"] \
        < worst_c["institute"]
    assert samp.node_capacity["fos"] == COUNTS["fos"] < worst_c["fos"]
    assert all(samp.hop_widths[h][t] <= COUNTS[t]
               for h in range(1, 4) for t in COUNTS)
    arrays = {et: (g.indptr, g.indices, g.edge_ids)
              for et, g in graphs.items()}
    seeds = {"paper": jnp.arange(16, dtype=jnp.int32) * 3}
    key = jax.random.PRNGKey(0)
    got = jax.jit(lambda *a: samp._sample_impl(
        samp._widths, samp._capacity, *a))(arrays, seeds, key)
    want = jax.jit(lambda *a: samp._sample_impl(
        worst_w, worst_c, *a))(arrays, seeds, key)
    assert got.metadata is None
    for t in COUNTS:
        a, b = np.asarray(got.node[t]), np.asarray(want.node[t])
        n = int(np.asarray(want.node_mask[t]).sum())
        assert a.shape[0] < b.shape[0] or t in ("paper", "author")
        assert (a[:n] == b[:n]).all() and (b[n:] == -1).all()
        assert int(np.asarray(got.node_mask[t]).sum()) == n
    e_got, e_want = _edges(got), _edges(want)
    assert sum(v.shape[1] for v in e_want.values()) > 100
    for et in e_want:
        assert got.row[et].shape[0] <= want.row[et].shape[0]
        np.testing.assert_array_equal(e_got[et], e_want[et], str(et))


def test_capacities_at_occupancy_change_nothing_below_it_flag_and_mask():
    graphs = igbh_graphs(max_deg=3)
    full = HeteroNeighborSampler(graphs, [3, 3, 3], "paper", batch_size=8)
    batches = [np.arange(8) * 2 + i for i in range(6)]
    counts = measure_hetero_occupancy(full, batches)
    assert set(counts) == set(COUNTS) and counts["paper"].shape == (6, 4)
    assert (counts["paper"][:, 0] == 8).all()
    caps, fronts = calibrate_hetero_node_capacity(
        full, counts=counts, pct=100, margin=1.0, multiple=1)
    # pct is held jointly: every threshold sits one common number of its
    # own standard deviations over its mean, the largest any calibration
    # batch needed anywhere.  So no calibration batch overflows, and the
    # threshold that set the number is at its own largest count.
    cols = [(caps[t], c.sum(1)) for t, c in counts.items()] + [
        (fronts[t][k - 1], c[:, k]) for t, c in counts.items()
        for k in (1, 2)]
    assert all(cap >= col.max() for cap, col in cols)
    assert any(cap <= col.max() + 1 for cap, col in cols if col.std() > 0)
    lower, _ = calibrate_hetero_node_capacity(
        full, counts=counts, pct=50, margin=1.0, multiple=1)
    assert all(lower[t] <= caps[t] for t in caps) and lower != caps
    rounded, _ = calibrate_hetero_node_capacity(full, batches, multiple=8)
    assert all(rounded[t] % 8 == 0 or rounded[t] == full.node_capacity[t]
               for t in rounded)

    fit = HeteroNeighborSampler(graphs, [3, 3, 3], "paper", batch_size=8,
                                node_capacity=caps, frontier_capacity=fronts)
    assert fit.node_capacity == caps
    assert all(fit.hop_widths[k][t] == min(fronts[t][k - 1],
                                           full.hop_widths[k][t])
               for t in COUNTS for k in (1, 2))
    assert sum(b[-1] for b in fit.hop_bounds.edge_bounds.values()) \
        < sum(b[-1] for b in full.hop_bounds.edge_bounds.values())
    key = jax.random.PRNGKey(5)
    for seeds in batches:
        a = fit.sample_from_nodes(NodeSamplerInput(seeds), key=key)
        b = full.sample_from_nodes(NodeSamplerInput(seeds), key=key)
        assert not bool(a.metadata["overflow"])
        ea, eb = _edges(a), _edges(b)
        for et in eb:
            np.testing.assert_array_equal(ea[et], eb[et], str(et))
    # A frontier one node too narrow: flagged, and nothing but the nodes
    # past it (leaves now) loses its edges.
    narrow = {t: [max(int(c[:, k].max()) - 1, 1) for k in (1, 2)]
              for t, c in counts.items()}
    thin = HeteroNeighborSampler(graphs, [3, 3, 3], "paper", batch_size=8,
                                 frontier_capacity=narrow)
    flagged = 0
    for seeds in batches:
        a = thin.sample_from_nodes(NodeSamplerInput(seeds), key=key)
        b = full.sample_from_nodes(NodeSamplerInput(seeds), key=key)
        flagged += int(a.metadata["overflow"])
        ea, eb = _edges(a), _edges(b)
        for et in eb:
            assert set(map(tuple, ea[et].T.tolist())) \
                <= set(map(tuple, eb[et].T.tolist()))
    assert flagged > 0

    # Below occupancy: flagged, the surviving edges are edges of the
    # uncapped sample between nodes the buffers hold, nothing else.
    tight = {t: max(c // 2, 8 if t == "paper" else 1)
             for t, c in caps.items()}
    cut = HeteroNeighborSampler(graphs, [3, 3, 3], "paper", batch_size=8,
                                node_capacity=tight)
    a = cut.sample_from_nodes(NodeSamplerInput(batches[0]), key=key)
    b = full.sample_from_nodes(NodeSamplerInput(batches[0]), key=key)
    assert bool(a.metadata["overflow"])
    ea, eb = _edges(a), _edges(b)
    for t in COUNTS:
        node = np.asarray(a.node[t])
        assert node.shape[0] == tight[t]
        live = node[np.asarray(a.node_mask[t])]
        assert (live >= 0).all() and np.unique(live).size == live.size
        assert (node[~np.asarray(a.node_mask[t])] == -1).all()
    assert sum(v.shape[1] for v in ea.values()) \
        < sum(v.shape[1] for v in eb.values())
    for et in eb:
        m = np.asarray(a.edge_mask[et])
        row, col = np.asarray(a.row[et])[m], np.asarray(a.col[et])[m]
        assert (row >= 0).all() and (row < tight[et[0]]).all()
        assert (col >= 0).all() and (col < tight[et[2]]).all()
        have = set(map(tuple, eb[et].T.tolist()))
        assert set(map(tuple, ea[et].T.tolist())) <= have


@pytest.mark.parametrize("capped", [False, True, "frontier"])
def test_sampler_output_obeys_its_hop_bounds(capped):
    graphs = igbh_graphs(seed=2)
    caps = {"paper": 40, "author": 30, "institute": 6, "fos": 10} \
        if capped else None
    # As the cell samples: a width per type and hop, none for a type
    # nothing reaches at hop 1, one narrow enough to leave leaves.
    fronts = {"paper": [12, 20], "author": [6, 8], "institute": [0, 3],
              "fos": [6, 4]} if capped == "frontier" else None
    samp = HeteroNeighborSampler(graphs, [3, 2, 2], "paper", batch_size=6,
                                 node_capacity=caps,
                                 frontier_capacity=fronts)
    hb = samp.hop_bounds
    assert hb == hetero_hop_bounds(samp.edge_types, samp.num_neighbors,
                                   samp._widths, samp._capacity,
                                   samp._num_nodes_by_type)
    assert hb.node_bounds["paper"][0] == 6
    for seeds in (np.arange(6), np.arange(6) * 7 + 1):
        out = samp.sample_from_nodes(NodeSamplerInput(seeds))
        for et, eb in hb.edge_bounds.items():
            assert eb[-1] == out.row[et].shape[0]
            m = np.asarray(out.edge_mask[et])
            row, col = np.asarray(out.row[et]), np.asarray(out.col[et])
            for k in range(1, len(eb)):
                blk = slice(eb[k - 1], eb[k])
                assert (row[blk][m[blk]] < hb.node_bounds[et[0]][k]).all()
                assert (col[blk][m[blk]] < hb.node_bounds[et[2]][k - 1]).all()


TRIM_LAYOUTS = {
    # (node_capacity, frontier_capacity, must a batch overflow)
    "uncapped": (None, None, False),
    "overflowing": ({"paper": 12, "author": 6, "institute": 2, "fos": 3},
                    None, True),
    # As the cell runs: both, and no frontier for institutes at hop 1.
    "as_the_cell": ({"paper": 52, "author": 44, "institute": 6, "fos": 12},
                    {"paper": [12, 18], "author": [8, 18],
                     "institute": [0, 4], "fos": [6, 6]}, False),
    # A type with rows at hop 1 and no frontier there: the relations out
    # of its hop-3 frontier are live, have no slot in the inner layers'
    # blocks, and still give their bias to the rows of hop 1.
    "no_frontier": (None, {"author": [0, 18]}, True),
}


@pytest.mark.parametrize("num_layers", [2, 3, 4])
@pytest.mark.parametrize("layout", list(TRIM_LAYOUTS))
def test_last_layer_over_the_seeds_hops_is_the_whole_model(
        setup, layout, num_layers):
    """``RGNN(hops=)`` runs EVERY layer over what reaches the seeds (the
    name is PR 26's, when it was the last layer alone): the seeds'
    logits, the loss and every gradient are the whole model's in float32,
    for fewer, as many and more layers than hops."""
    s = setup
    caps, fronts, overflows = TRIM_LAYOUTS[layout]
    sampler = HeteroNeighborSampler(s["graphs"], [3, 2, 2], "paper",
                                    batch_size=4, seed=0,
                                    node_capacity=caps,
                                    frontier_capacity=fronts)
    hops = sampler.hop_bounds
    model = RGNN(s["ets"], hidden_features=8, out_features=CLASSES,
                 target_type="paper", num_layers=num_layers, heads=2,
                 dropout_rate=0.0)
    out, x, ei = batch_of(sampler, s["feats"], [0, 7, 21, 40])
    assert bool((out.metadata or {}).get("overflow", False)) == overflows
    params = model.init({"params": jax.random.PRNGKey(3)}, x, ei,
                        out.edge_mask)
    y = jnp.asarray(s["labels"])[jnp.maximum(out.node["paper"], 0)]

    def loss(p, **kw):
        logits = model.apply(p, x, ei, out.edge_mask, train=False, **kw)
        return seed_cross_entropy(logits, y, 4, out.node_mask["paper"])[0], \
            logits

    (l1, z1), g1 = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    (l2, z2), g2 = jax.jit(jax.value_and_grad(
        lambda p: loss(p, hops=hops), has_aux=True))(params)
    assert z2.shape == (4, CLASSES) and z1.shape[0] > 4
    np.testing.assert_allclose(z2, z1[:4], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(l2, l1, rtol=1e-6)
    flat1, flat2 = (jax.tree_util.tree_leaves_with_path(g) for g in (g1, g2))
    assert jax.tree_util.tree_structure(g1) == jax.tree_util.tree_structure(g2)
    assert any(float(jnp.abs(v).max()) > 0 for _, v in flat1)
    for (path, a), (_, b) in zip(flat1, flat2):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-7,
                                   err_msg=str(path))
    # Every layer is cut, not the last alone: layer l emits what layer
    # l + 1 reads, the first layers under a clamped depth run whole, and
    # with fewer layers than hops nothing touches the outer blocks.
    ext = model.layer_extents(hops)
    rows = sum(b[-1] for b in hops.node_bounds.values())
    slots = sum(b[-1] for b in hops.edge_bounds.values())
    assert [e[2] for e in ext[:-1]] == [e[0] for e in ext[1:]]
    assert ext[-1][2] == 4 and ext[-1][1] < ext[-2][1]
    assert all(a[1] >= b[1] and a[2] >= b[2] for a, b in zip(ext, ext[1:]))
    assert (ext[0][1] == slots) == (num_layers >= 3)
    assert ext[0][2] < rows or num_layers == 4 or caps is not None
    assert ext[0] == (rows, slots, rows) or num_layers < 4


def test_trimming_engages_in_the_scanned_step_and_says_so(setup):
    """``seed_hops=True`` records the extents of every layer when the step
    is built; without it that build sets nothing.  A batch that ``hops``
    does not describe raises."""
    from glt_tpu.obs import metrics

    s = setup
    sampler, model, tx = s["sampler"], s["model"], optax.adam(1e-3)
    hops = sampler.hop_bounds
    ext = model.layer_extents(hops)
    assert ext == [tuple(sum(part.values()) for part in layer)
                   for layer in model.typed_extents(hops)]

    def build(**kw):
        metrics.reset()
        metrics.enable()
        try:
            make_scanned_hetero_train_step(
                model, tx, sampler, s["feats"], {"paper": s["labels"]}, 4,
                **kw)
            return metrics.snapshot()
        finally:
            metrics.disable()
            metrics.reset()

    snap = build(seed_hops=True)
    assert snap["glt.model.edge_slots"] == sum(
        b[-1] for b in hops.edge_bounds.values()) == ext[0][1]
    assert snap["glt.model.node_rows"] == sum(
        b[-1] for b in hops.node_bounds.values()) == ext[0][0]
    got = [(snap["glt.model.layer_edge_slots{layer=%d}" % l],
            snap["glt.model.layer_node_rows{layer=%d}" % l])
           for l in (1, 2, 3)]
    assert got == [(e[1], e[2]) for e in ext]
    assert got[1][0] < got[0][0] and got[1][1] < got[0][1]
    assert got[2] == (sum(b[1] for et, b in hops.edge_bounds.items()
                          if et[2] == "paper"), 4)
    snap = build(seed_hops=False)
    assert snap["glt.model.edge_slots"] == 0
    assert all(v == 0 for k, v in snap.items()
               if k.startswith("glt.model.layer_"))

    x, ei, em = s["x"], s["ei"], s["out"].edge_mask
    short = dict(x, author=x["author"][:-1])
    with pytest.raises(ValueError, match="not laid out"):
        model.apply(s["params"], short, ei, em, hops=hops)
    et = ("paper", "cites", "paper")
    with pytest.raises(ValueError, match="not laid out"):
        model.apply(s["params"], x, {**ei, et: ei[et][:, :-1]},
                    {**em, et: em[et][:-1]}, hops=hops)
    with pytest.raises(ValueError, match="not laid out"):
        model.apply(s["params"], x, ei, em, hops=hops._replace(
            node_bounds={t: b for t, b in hops.node_bounds.items()
                         if t != "fos"}))


def test_scanned_hetero_step_reports_overflow_and_counts_it(setup):
    """The scanned step on 16-bit rows with per-type capacities: finite
    losses, a flag per batch, the flags summed by ``run_scanned_epoch``
    and counted in ``glt.hetero.overflowed_batches``; the gauges hold the
    sampler's static sizes."""
    from glt_tpu.obs import metrics

    s = setup
    feats = {t: jnp.asarray(v, jnp.bfloat16) for t, v in s["feats"].items()}
    tight = {"paper": 12, "author": 6, "institute": 2, "fos": 3}
    sampler = HeteroNeighborSampler(s["graphs"], [3, 2, 2], "paper",
                                    batch_size=4, node_capacity=tight)
    model = RGNN(s["ets"], hidden_features=8, out_features=CLASSES,
                 target_type="paper", heads=2, dropout_rate=0.2,
                 dtype=jnp.bfloat16)
    tx = optax.adam(1e-3)
    metrics.enable()
    try:
        before = metrics.snapshot()
        state = init_hetero_state(model, tx, sampler, feats,
                                  jax.random.PRNGKey(0))
        step = make_scanned_hetero_train_step(
            model, tx, sampler, feats, {"paper": s["labels"]}, 4,
            seed_hops=True)
        state, losses, accs, ovfs = step(
            state, np.arange(12).reshape(3, 4), jax.random.PRNGKey(1))
        assert losses.shape == accs.shape == ovfs.shape == (3,)
        assert np.isfinite(np.asarray(losses)).all()
        assert np.asarray(ovfs).sum() > 0
        state, ls, _, n_ovf = run_scanned_epoch(
            step, state, np.arange(24), 4, 3, np.random.default_rng(0),
            jax.random.PRNGKey(2))
        assert ls.shape == (6,) and 0 < n_ovf <= 6
        after = metrics.snapshot()
    finally:
        metrics.disable()
    # The step's own wrapper counts its flags (obs.metrics.defer), once,
    # whoever drives it: the direct call above and the epoch's calls.
    assert after["glt.hetero.overflowed_batches"] \
        - before.get("glt.hetero.overflowed_batches", 0) \
        == int(np.asarray(ovfs).sum()) + n_ovf
    for t, n in tight.items():
        assert after["glt.hetero.node_rows{type=%s}" % t] == n
    et = "paper__cites__paper"
    assert after["glt.hetero.edge_slots{edge_type=%s}" % et] \
        == sampler.hop_bounds.edge_bounds[("paper", "cites", "paper")][-1]
