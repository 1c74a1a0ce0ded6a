"""Distributed train-step test + graft entry dry run on the 8-device mesh."""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh

from tests.test_neighbor_sampler import sorted_slots  # noqa: F401 (fixture)

from glt_tpu.data.topology import CSRTopo
from glt_tpu.models import GraphSAGE
from glt_tpu.parallel import (
    init_dist_state,
    make_dist_train_step,
    shard_feature,
    shard_graph,
)

N_DEV = 8


def test_dist_train_loss_drops():
    devs = jax.devices()[:N_DEV]
    mesh = Mesh(np.array(devs), ("shard",))
    n, classes = 64, 4
    rng = np.random.default_rng(0)
    # clustered graph: edges stay within class -> learnable from structure
    labels = (np.arange(n) % classes).astype(np.int32)
    src, dst = [], []
    for c in range(classes):
        members = np.where(labels == c)[0]
        for i in members:
            for j in rng.choice(members, 3, replace=False):
                src.append(i)
                dst.append(j)
    topo = CSRTopo(np.stack([np.array(src), np.array(dst)]), num_nodes=n)
    feat = np.eye(classes, dtype=np.float32)[labels]
    feat = np.concatenate([feat, rng.normal(0, .1, (n, 4)).astype(np.float32)], 1)

    g = shard_graph(topo, N_DEV)
    f = shard_feature(feat, N_DEV)
    lab = jnp.asarray(labels.reshape(N_DEV, g.nodes_per_shard))

    model = GraphSAGE(hidden_features=16, out_features=classes,
                      num_layers=2, dropout_rate=0.0)
    tx = optax.adam(1e-2)
    bs, fanouts = 4, [3, 3]

    # Exact dedup and the leaf-block fast mode share the objective (loss
    # over seed rows in the compact interior prefix): both must train.
    for lhd in (True, False):
        state = init_dist_state(model, tx, g, f, jax.random.PRNGKey(0),
                                fanouts, bs)
        step = make_dist_train_step(model, tx, g, f, lab, mesh, fanouts,
                                    bs, last_hop_dedup=lhd)
        losses = []
        for it in range(30):
            seeds = np.stack([
                np.random.default_rng(it * N_DEV + s).choice(
                    np.arange(s * 8, (s + 1) * 8), bs, replace=False)
                for s in range(N_DEV)]).astype(np.int32)
            state, loss, acc = step(state, jnp.asarray(seeds),
                                    jax.random.PRNGKey(it))
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.6, (lhd, losses[0], losses[-1])


def test_graft_entry_single_chip():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__
    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(out)).all()


def test_graft_entry_multichip():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__
    __graft_entry__.dryrun_multichip(N_DEV)


def test_hetero_dist_train_loss_drops():
    """8-device hetero fused step (cf. reference examples/igbh distributed):
    bipartite user->item graph where a user's items encode its class; the
    R-GAT must learn user labels from aggregated item features."""
    from glt_tpu.data.topology import CSRTopo
    from glt_tpu.models.rgat import RGAT
    from glt_tpu.parallel import (
        DistHeteroNeighborSampler,
        init_hetero_dist_state,
        make_hetero_dist_train_step,
        shard_hetero_graph,
    )

    devs = jax.devices()[:N_DEV]
    mesh = Mesh(np.array(devs), ("shard",))
    U, I, classes = 64, 32, 4
    rng = np.random.default_rng(0)
    labels = (np.arange(U) % classes).astype(np.int32)
    # user u clicks 3 items j with j % classes == u % classes
    u_src = np.repeat(np.arange(U), 3)
    i_dst = np.concatenate([
        [(u % classes) + classes * ((u // classes + k) % (I // classes))
         for k in range(3)] for u in range(U)])
    ET_UI = ("user", "clicks", "item")
    ET_IU = ("item", "rev_clicks", "user")
    topos = {
        ET_UI: CSRTopo(np.stack([u_src, i_dst]), num_nodes=U),
        ET_IU: CSRTopo(np.stack([i_dst, u_src]), num_nodes=I),
    }
    sharded = shard_hetero_graph(topos, N_DEV)

    from glt_tpu.parallel import shard_feature
    item_feat = np.eye(classes, dtype=np.float32)[np.arange(I) % classes]
    user_feat = rng.normal(0, .1, (U, classes)).astype(np.float32)
    feats = {"user": shard_feature(user_feat, N_DEV),
             "item": shard_feature(item_feat, N_DEV)}
    lab = jnp.asarray(labels.reshape(N_DEV, -1))

    bs = 4
    samp = DistHeteroNeighborSampler(sharded, mesh, [3, 3], "user",
                                     batch_size=bs, frontier_cap=32,
                                     seed=0)
    model = RGAT(edge_types=[ET_IU, ET_UI], hidden_features=16,
                 out_features=classes, target_type="user", num_layers=2,
                 conv="gat", dropout_rate=0.0)
    tx = optax.adam(1e-2)
    state = init_hetero_dist_state(model, tx, samp, feats,
                                   jax.random.PRNGKey(0))
    step = make_hetero_dist_train_step(model, tx, samp, feats, lab, mesh,
                                       batch_size=bs)
    losses = []
    for it in range(30):
        seeds = np.stack([
            np.random.default_rng(it * N_DEV + s).choice(
                np.arange(s * 8, (s + 1) * 8), bs, replace=False)
            for s in range(N_DEV)]).astype(np.int32)
        state, loss, acc = step(state, jnp.asarray(seeds),
                                jax.random.PRNGKey(100 + it))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


def _bipartite_fixture():
    """Shared bipartite user/item fixture (see hetero test above)."""
    rng = np.random.default_rng(0)
    U, I, classes = 64, 32, 4
    labels = (np.arange(U) % classes).astype(np.int32)
    u_src = np.repeat(np.arange(U), 3)
    i_dst = np.concatenate([
        [(u % classes) + classes * ((u // classes + k) % (I // classes))
         for k in range(3)] for u in range(U)])
    ET_UI = ("user", "clicks", "item")
    ET_IU = ("item", "rev_clicks", "user")
    topos = {
        ET_UI: CSRTopo(np.stack([u_src, i_dst]), num_nodes=U),
        ET_IU: CSRTopo(np.stack([i_dst, u_src]), num_nodes=I),
    }
    item_feat = np.eye(classes, dtype=np.float32)[np.arange(I) % classes]
    item_feat = np.concatenate(
        [item_feat, rng.normal(0, .1, (I, 12)).astype(np.float32)], 1)
    user_feat = rng.normal(0, .1, (U, 16)).astype(np.float32)
    return (U, I, classes, labels, topos, user_feat, item_feat,
            ET_UI, ET_IU)


def test_hetero_tiered_train_matches_full():
    """Hetero tiered gather parity (VERDICT r4 #4): the staged-cold train
    step produces EXACTLY the loss of the full-HBM step on the same
    sampled batch, params, and key."""
    from glt_tpu.models.rgat import RGAT
    from glt_tpu.parallel import (
        DistHeteroNeighborSampler,
        HeteroTieredTrainPipeline,
        init_hetero_dist_state,
        make_hetero_tiered_train_step,
        shard_feature,
        shard_feature_tiered,
        shard_hetero_graph,
    )

    (U, I, classes, labels, topos, user_feat, item_feat,
     ET_UI, ET_IU) = _bipartite_fixture()
    devs = jax.devices()[:N_DEV]
    mesh = Mesh(np.array(devs), ("shard",))
    sharded = shard_hetero_graph(topos, N_DEV)
    lab = jnp.asarray(labels.reshape(N_DEV, -1))
    bs = 4
    samp = DistHeteroNeighborSampler(sharded, mesh, [3, 3], "user",
                                     batch_size=bs, frontier_cap=32,
                                     seed=0)
    model = RGAT(edge_types=[ET_IU, ET_UI], hidden_features=16,
                 out_features=classes, target_type="user", num_layers=2,
                 conv="gat", dropout_rate=0.0)
    tx = optax.adam(1e-2)

    feats_full = {"user": shard_feature(user_feat, N_DEV),
                  "item": shard_feature(item_feat, N_DEV)}
    feats_tier = {"user": shard_feature(user_feat, N_DEV),
                  "item": shard_feature_tiered(item_feat, N_DEV,
                                               hot_ratio=0.25)}
    state = init_hetero_dist_state(model, tx, samp, feats_tier,
                                   jax.random.PRNGKey(0))

    train_full = make_hetero_tiered_train_step(
        model, tx, samp, feats_full, lab, mesh, batch_size=bs)
    train_tier = make_hetero_tiered_train_step(
        model, tx, samp, feats_tier, lab, mesh, batch_size=bs)
    pipe = HeteroTieredTrainPipeline(samp, train_tier, feats_tier, mesh)

    seeds = np.stack([
        np.random.default_rng(s).choice(np.arange(s * 8, (s + 1) * 8), bs,
                                        replace=False)
        for s in range(N_DEV)]).astype(np.int32)
    out = samp.sample_from_nodes(jnp.asarray(seeds))
    staged = pipe._stage_cold_async(out).result()
    k = jax.random.PRNGKey(3)
    _, loss_t, acc_t = train_tier(state, out, staged, k)
    # Parity check: BOTH paths must consume the identical key so tiered
    # and full training are bit-comparable.
    _, loss_f, acc_f = train_full(state, out, {}, k)  # gltlint: disable=prng-key-reuse
    np.testing.assert_allclose(float(loss_t), float(loss_f), rtol=1e-6)
    np.testing.assert_allclose(float(acc_t), float(acc_f), rtol=1e-6)
    assert pipe.flush_dropped() == 0
    pipe.close()


def test_hetero_tiered_pipeline_loss_drops():
    """End-to-end hetero two-stage pipeline: sample -> per-type host cold
    staging (row-chunk parallel) -> train; loss must drop, no drops."""
    from glt_tpu.models.rgat import RGAT
    from glt_tpu.parallel import (
        DistHeteroNeighborSampler,
        HeteroTieredTrainPipeline,
        init_hetero_dist_state,
        make_hetero_tiered_train_step,
        shard_feature,
        shard_feature_tiered,
        shard_hetero_graph,
    )

    (U, I, classes, labels, topos, user_feat, item_feat,
     ET_UI, ET_IU) = _bipartite_fixture()
    devs = jax.devices()[:N_DEV]
    mesh = Mesh(np.array(devs), ("shard",))
    sharded = shard_hetero_graph(topos, N_DEV)
    lab = jnp.asarray(labels.reshape(N_DEV, -1))
    bs = 4
    # Bounded exchange + tiered features together — the full hetero
    # parity configuration (VERDICT r4 #4).
    samp = DistHeteroNeighborSampler(sharded, mesh, [3, 3], "user",
                                     batch_size=bs, frontier_cap=32,
                                     seed=0, exchange_load_factor=8.0)
    model = RGAT(edge_types=[ET_IU, ET_UI], hidden_features=16,
                 out_features=classes, target_type="user", num_layers=2,
                 conv="gat", dropout_rate=0.0)
    tx = optax.adam(1e-2)
    feats = {"user": shard_feature(user_feat, N_DEV),
             "item": shard_feature_tiered(item_feat, N_DEV,
                                          hot_ratio=0.25)}
    state = init_hetero_dist_state(model, tx, samp, feats,
                                   jax.random.PRNGKey(0))
    train = make_hetero_tiered_train_step(model, tx, samp, feats, lab,
                                          mesh, batch_size=bs)
    pipe = HeteroTieredTrainPipeline(samp, train, feats, mesh,
                                     stage_threads=2)
    losses = []
    for epoch in range(10):
        batches = [np.stack([
            np.random.default_rng(epoch * 31 + it * N_DEV + s).choice(
                np.arange(s * 8, (s + 1) * 8), bs, replace=False)
            for s in range(N_DEV)]).astype(np.int32) for it in range(4)]
        state, ls, _ = pipe.run_epoch(state, batches,
                                      jax.random.PRNGKey(epoch))
        losses += [float(x) for x in ls]
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])
    assert pipe.flush_dropped() == 0
    pipe.close()


# -- per-layer trimming by the hop-block layout, distributed ----------------
def _random_sharded(n=512, dim=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), rng.integers(0, 9, n))
    topo = CSRTopo(np.stack([src, rng.integers(0, n, src.shape[0])]),
                   num_nodes=n)
    feat = rng.normal(size=(n, dim)).astype(np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    g = shard_graph(topo, N_DEV)
    return (g, shard_feature(feat, N_DEV),
            jnp.asarray(labels.reshape(N_DEV, g.nodes_per_shard)))


def _dist_seeds(g, bs, it, pad_shard=None):
    seeds = np.stack([
        s * g.nodes_per_shard + np.random.default_rng(it * N_DEV + s).choice(
            g.nodes_per_shard, bs, replace=False)
        for s in range(N_DEV)]).astype(np.int32)
    if pad_shard is not None:
        seeds[pad_shard, bs // 2:] = -1
    return seeds


@pytest.mark.parametrize("variant", [
    {}, {"frontier_cap": 8}, {"exchange_load_factor": 1.0},
    {"collective": "ring"}])
@pytest.mark.parametrize("lhd", [True, False])
def test_dist_hop_blocks_keep_their_static_bounds(lhd, variant):
    """The layout ``GraphSAGE`` trims by, held by the dist sampler on every
    shard (see tests/test_neighbor_sampler.py for the one-chip sampler)."""
    from glt_tpu.parallel import DistNeighborSampler
    from tests.test_neighbor_sampler import assert_hop_layout

    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("shard",))
    g, _, _ = _random_sharded()
    bs = 4
    s = DistNeighborSampler(g, mesh, num_neighbors=[3, 3, 2], batch_size=bs,
                            last_hop_dedup=lhd, **variant)
    assert s.hop_bounds.node_bounds[-1] == s.node_capacity
    for it in range(2):
        out = s.sample_from_nodes(jnp.asarray(_dist_seeds(g, bs, it,
                                                          pad_shard=it)))
        for shard in range(N_DEV):
            assert_hop_layout(jax.tree.map(lambda a: a[shard], out),
                              s.hop_bounds)


@pytest.mark.parametrize("variant", [
    {}, {"frontier_cap": 8}, {"exchange_load_factor": 1.0}])
@pytest.mark.parametrize("lhd", [True, False])
def test_dist_hop_blocks_have_static_destinations(lhd, variant):
    """The rule ``GraphSAGE`` aggregates by without a scatter
    (models/conv.py::block_mean), held by the dist sampler on every shard,
    one of them with a half-padded seed batch."""
    from glt_tpu.parallel import DistNeighborSampler
    from tests.test_neighbor_sampler import assert_static_destinations

    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("shard",))
    g, _, _ = _random_sharded()
    bs = 4
    s = DistNeighborSampler(g, mesh, num_neighbors=[3, 3, 2], batch_size=bs,
                            last_hop_dedup=lhd, **variant)
    assert s.hop_bounds.blocks[0] == (bs, 3)
    out = s.sample_from_nodes(jnp.asarray(_dist_seeds(g, bs, 0,
                                                      pad_shard=1)))
    for shard in range(N_DEV):
        starts = assert_static_destinations(
            jax.tree.map(lambda a: a[shard], out), s.hop_bounds)
        assert starts[0] == 0 and starts == sorted(starts)


@pytest.mark.parametrize("variant", [
    {}, {"frontier_cap": 8}, {"collective": "ring"}])
def test_dist_sampler_output_equals_the_map_forms(variant, monkeypatch,
                                                   sorted_slots):
    """Every shard's ``SamplerOutput`` on a four-shard mesh with every hop
    sorted inside ``shard_map`` (the buffer is the worst case, so the
    chain always is) against the parent's program; and the engagement
    gauge of every hop, 0 the seeds."""
    import glt_tpu.parallel.dist_sampler as mod
    from glt_tpu.parallel import DistNeighborSampler
    from tests.test_neighbor_sampler import assert_outputs_equal, map_form

    n_dev, bs = 4, 4
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("shard",))
    rng = np.random.default_rng(1)
    src = np.repeat(np.arange(512), rng.integers(0, 9, 512))
    g = shard_graph(CSRTopo(np.stack([src, rng.integers(0, 512, src.size)]),
                            num_nodes=512), n_dev)

    def sample():
        s = DistNeighborSampler(g, mesh, num_neighbors=[3, 3, 2],
                                batch_size=bs, **variant)
        return [s.sample_from_nodes(jnp.asarray(
            _dist_seeds(g, bs, it, pad_shard=it)[:n_dev]))
            for it in range(2)]
    got = sample()
    # seeds, then each hop's candidates; one chain, its bound the seeds
    # plus the candidates of hops 1-2
    w1, w2 = (8, 8) if variant.get("frontier_cap") else (12, 36)
    widths = [bs, bs * 3, w1 * 3, w2 * 2]
    assert [sorted_slots(k) for k in range(4)] == [
        sum(widths[:k + 1]) for k in range(4)]
    with map_form(monkeypatch, mod) as parent:
        want = sample()
    assert parent == [sum(widths[:3])]
    for a, b in zip(got, want):
        assert_outputs_equal(a, b)


@pytest.mark.parametrize("scanned", [False, True])
def test_dist_train_step_trims_and_equals_whole_steps(scanned):
    """N steps of ``make_dist_train_step`` / ``make_scanned_dist_train_step``
    (trimmed by the layout of their own arguments) against the same factory
    driving the whole model: losses and parameters, dropout off; and the
    gauges of the layout."""
    from glt_tpu import obs
    from glt_tpu.parallel import make_scanned_dist_train_step
    from tests.test_models import Whole

    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("shard",))
    g, f, lab = _random_sharded()
    model = GraphSAGE(hidden_features=16, out_features=4, num_layers=3,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    bs, fanouts, steps = 4, [3, 2, 2], 3
    make = make_scanned_dist_train_step if scanned else make_dist_train_step

    obs.metrics.reset()
    obs.metrics.enable()
    try:
        trimmed = make(model, tx, g, f, lab, mesh, fanouts, bs)
        snap = obs.metrics.snapshot()
    finally:
        obs.metrics.disable()
        obs.metrics.reset()
    assert snap["glt.model.edge_slots"] == 12 + 24 + 48
    assert snap["glt.model.node_rows"] == 4 + 12 + 24 + 48
    assert [snap["glt.model.layer_edge_slots{layer=%d}" % l]
            for l in (1, 2, 3)] == [84, 36, 12]
    assert [snap["glt.model.layer_block_slots{layer=%d}" % l]
            for l in (1, 2, 3)] == [84, 36, 12]
    assert [snap["glt.model.layer_node_rows{layer=%d}" % l]
            for l in (1, 2, 3)] == [40, 16, 4]
    whole = make(Whole(model), tx, g, f, lab, mesh, fanouts, bs)

    # Shard 1's batch is half padding in every step.
    seeds = np.stack([_dist_seeds(g, bs, it, pad_shard=1)
                      for it in range(steps)])
    results = []
    for step in (trimmed, whole):
        state = init_dist_state(model, tx, g, f, jax.random.PRNGKey(0),
                                fanouts, bs)
        if scanned:
            state, losses, _ = step(state, jnp.asarray(seeds),
                                    jax.random.PRNGKey(7))
        else:
            losses = []
            for it in range(steps):
                state, loss, _ = step(state, jnp.asarray(seeds[it]),
                                      jax.random.PRNGKey(it))
                losses.append(loss)
        assert int(state.step) == steps
        results.append((np.asarray(jnp.stack(list(losses))), state.params))
    (loss_t, p_t), (loss_w, p_w) = results
    np.testing.assert_allclose(loss_t, loss_w, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_t),
                    jax.tree_util.tree_leaves(p_w)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
