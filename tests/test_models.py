"""Model + train-step tests: masked message passing and E2E learning.

The E2E test is the framework's minimum end-to-end slice (SURVEY §7 stage
5): NeighborLoader feeding a jitted GraphSAGE train step, loss must drop on
a learnable synthetic task.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from flax import linen as nn

from glt_tpu.data import CSRTopo, Dataset
from glt_tpu.loader import NeighborLoader
from glt_tpu.models import (
    GAT,
    GraphSAGE,
    create_train_state,
    make_eval_step,
    make_train_step,
    scatter_mean,
)


def test_scatter_mean_ignores_padding():
    msgs = jnp.array([[1.0], [3.0], [100.0]])
    dst = jnp.array([0, 0, -1])
    mask = jnp.array([True, True, False])
    out = scatter_mean(msgs, dst, 2, mask)
    np.testing.assert_allclose(np.asarray(out), [[2.0], [0.0]])


def test_sage_forward_shapes_and_padding_invariance():
    model = GraphSAGE(hidden_features=8, out_features=3, num_layers=2)
    x = jnp.ones((10, 4))
    ei = jnp.array([[1, 2, -1], [0, 0, -1]])
    mask = jnp.array([True, True, False])
    params = model.init(jax.random.PRNGKey(0), x, ei, mask)
    out = model.apply(params, x, ei, mask)
    assert out.shape == (10, 3)
    # adding more padded edges must not change the output
    ei2 = jnp.concatenate([ei, jnp.full((2, 5), -1)], axis=1)
    mask2 = jnp.concatenate([mask, jnp.zeros(5, bool)])
    out2 = model.apply(params, x, ei2, mask2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-5)


def test_gat_forward():
    model = GAT(hidden_features=4, out_features=2, num_layers=2, heads=2)
    x = jnp.ones((6, 3))
    ei = jnp.array([[1, 2, 3, -1], [0, 0, 1, -1]])
    mask = ei[0] >= 0
    params = model.init(jax.random.PRNGKey(0), x, ei, mask)
    out = model.apply(params, x, ei, mask)
    assert out.shape == (6, 2)
    assert np.isfinite(np.asarray(out)).all()


def _cluster_dataset(n=48, dim=8, classes=3, rng_seed=0):
    """Nodes in `classes` clusters; edges within cluster; feature = noisy
    one-hot of cluster -> neighbors agree with own class, easy to learn."""
    rng = np.random.default_rng(rng_seed)
    labels = np.arange(n) % classes
    src, dst = [], []
    for c in range(classes):
        members = np.where(labels == c)[0]
        for i in members:
            nb = rng.choice(members, size=3, replace=False)
            for j in nb:
                src.append(i)
                dst.append(j)
    feat = np.eye(classes, dtype=np.float32)[labels]
    feat = np.concatenate(
        [feat, rng.normal(0, 0.1, (n, dim - classes)).astype(np.float32)], 1)
    return (Dataset()
            .init_graph(np.stack([np.array(src), np.array(dst)]),
                        graph_mode="HOST", num_nodes=n)
            .init_node_features(feat)
            .init_node_labels(labels)), labels


def test_e2e_training_loss_drops():
    ds, labels = _cluster_dataset()
    loader = NeighborLoader(ds, [4, 4], np.arange(48), batch_size=16,
                            shuffle=True, seed=0)
    model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    first = next(iter(loader))
    state = create_train_state(model, jax.random.PRNGKey(0), first, tx)
    step = make_train_step(model, tx, batch_size=16)

    losses = []
    for epoch in range(5):
        for batch in loader:
            state, loss, acc = step(state, batch)
            losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    # final accuracy should be high on this trivial task
    ev = make_eval_step(model, batch_size=16)
    accs = [float(ev(state.params, b)[1]) for b in loader]
    assert np.mean(accs) > 0.9


def test_fused_scan_group_matches_unfused_serial_bits():
    """The fused scan-group program (G batches per compile) must be
    BIT-identical to the unfused serial stream (the same step built at
    G=1, driven one batch at a time): per-batch losses, accuracies, and
    final params compare with == on the raw bits.  This is the static
    guarantee that lets the scanned route be the ONLY epoch driver
    (the overlapped path was deleted; see glt_tpu/models/train.py)."""
    from glt_tpu.models import TrainState, make_scanned_node_train_step
    from glt_tpu.sampler import NeighborSampler

    ds, labels = _cluster_dataset()
    model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    bs, G = 16, 3
    sampler = NeighborSampler(ds.get_graph(), [4, 4], batch_size=bs,
                              with_edge=False)
    feat = ds.get_node_feature()
    x0 = jnp.zeros((sampler.node_capacity, feat.shape[1]), jnp.float32)
    ei0 = jnp.full((2, sampler.edge_capacity), -1, jnp.int32)
    m0 = jnp.zeros((sampler.edge_capacity,), bool)
    params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)

    def fresh_state():
        return TrainState(params=params, opt_state=tx.init(params),
                          step=jnp.zeros((), jnp.int32))

    block = np.arange(G * bs).reshape(G, bs).astype(np.int32)
    base = jax.random.PRNGKey(42)

    fused = make_scanned_node_train_step(model, tx, sampler, feat,
                                         labels, bs)
    f_state, f_losses, f_accs, _ = fused(fresh_state(), block, base)

    # Unfused serial stream: one host dispatch per batch, same program,
    # same (epoch key, scan position) schedule — batch i rides in scan
    # slot i with every other slot fully padded (padded batches are
    # exact no-ops: test_scanned_node_step_padded_batch_is_noop).
    state = fresh_state()
    s_losses, s_accs = [], []
    for i in range(G):
        lone = np.full((G, bs), -1, np.int32)
        lone[i] = block[i]
        state, ls, acs, _ = fused(state, lone, base)
        s_losses.append(float(ls[i]))
        s_accs.append(float(acs[i]))

    assert [float(x) for x in f_losses] == s_losses
    assert [float(x) for x in f_accs] == s_accs
    for a, b in zip(jax.tree_util.tree_leaves(f_state.params),
                    jax.tree_util.tree_leaves(state.params)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_scanned_link_step_matches_serial():
    """G link batches scanned in one program == the serial per-batch
    loop with the same keys (sampling, negatives, loss, updates)."""
    from glt_tpu.models import make_scanned_link_train_step
    from glt_tpu.sampler import NegativeSampling, NeighborSampler
    from glt_tpu.sampler.base import EdgeSamplerInput

    ds, labels = _cluster_dataset()
    model = GraphSAGE(hidden_features=8, out_features=8, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    q, G = 8, 3
    neg = NegativeSampling("binary", 1)
    sampler = NeighborSampler(ds.get_graph(), [3, 3], batch_size=q,
                              with_edge=False)
    feat = ds.get_node_feature()

    def loss_fn(z, meta):
        eli = meta["edge_label_index"]
        label = meta["edge_label"]
        valid = (eli[0] >= 0) & (eli[1] >= 0) & (label >= 0)
        s = z[jnp.clip(eli[0], 0, z.shape[0] - 1)]
        d = z[jnp.clip(eli[1], 0, z.shape[0] - 1)]
        ce = optax.sigmoid_binary_cross_entropy(
            (s * d).sum(-1), (label > 0).astype(jnp.float32))
        return jnp.where(valid, ce, 0).sum() / jnp.maximum(valid.sum(), 1)

    from glt_tpu.models import TrainState, init_train_state
    state0 = init_train_state(model, tx, feat.shape[1],
                              jax.random.PRNGKey(0))
    params0 = state0.params

    rng = np.random.default_rng(0)
    src = rng.integers(0, 48, (G, q)).astype(np.int64)
    dst = rng.integers(0, 48, (G, q)).astype(np.int64)
    base = jax.random.PRNGKey(11)

    step = make_scanned_link_train_step(model, tx, sampler, feat, loss_fn,
                                        neg, group=G)
    state1, scanned_losses, _, flags = step(
        state0, np.stack([src, dst], axis=1), base)
    assert isinstance(state1, TrainState) and int(state1.step) == G
    assert flags.shape == (G, 2) and not np.asarray(flags)[:, 0].any()
    scanned_losses = [float(x) for x in np.asarray(scanned_losses)]

    # Serial reference with the same per-batch keys.
    keys = jax.random.split(base, G)
    params, opt = params0, tx.init(params0)
    serial_losses = []
    for i in range(G):
        out = sampler.sample_from_edges(
            EdgeSamplerInput(row=src[i], col=dst[i], neg_sampling=neg),
            key=keys[i])
        x = feat.gather(out.node)
        ei = jnp.stack([out.row, out.col])

        def lf(p, x=x, ei=ei, out=out):
            z = model.apply(p, x, ei, out.edge_mask)
            return loss_fn(z, out.metadata)

        loss, grads = jax.value_and_grad(lf)(params)
        updates, opt = tx.update(grads, opt, params)
        params = optax.apply_updates(params, updates)
        serial_losses.append(float(loss))

    assert scanned_losses == pytest.approx(serial_losses, rel=1e-5), (
        scanned_losses, serial_losses)


def test_bf16_mixed_precision_parity():
    """bf16 matmuls (f32 params/aggregation/loss) track the f32 loss
    curve and reach the same accuracy on the cluster task (VERDICT r4
    #3: flag-gated mixed precision with asserted parity)."""
    ds, labels = _cluster_dataset()
    loader = NeighborLoader(ds, [4, 4], np.arange(48), batch_size=16,
                            shuffle=True, seed=0)
    tx = optax.adam(1e-2)
    first = next(iter(loader))

    curves = {}
    for name, dtype in [("f32", None), ("bf16", jnp.bfloat16)]:
        model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2,
                          dropout_rate=0.0, dtype=dtype)
        state = create_train_state(model, jax.random.PRNGKey(0), first, tx)
        # Params are f32 regardless of compute dtype.
        assert all(p.dtype == jnp.float32
                   for p in jax.tree_util.tree_leaves(state.params))
        step = make_train_step(model, tx, batch_size=16)
        losses = []
        for epoch in range(5):
            for batch in loader:
                state, loss, acc = step(state, batch)
                losses.append(float(loss))
        curves[name] = (np.asarray(losses), state)

    f32_l, bf16_l = curves["f32"][0], curves["bf16"][0]
    # Same trajectory within bf16 rounding noise: early steps nearly
    # identical, both converge.
    np.testing.assert_allclose(bf16_l[:5], f32_l[:5], rtol=0.05, atol=0.05)
    assert bf16_l[-1] < bf16_l[0] * 0.5
    model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2,
                      dropout_rate=0.0, dtype=jnp.bfloat16)
    ev = make_eval_step(model, batch_size=16)
    accs = [float(ev(curves["bf16"][1].params, b)[1]) for b in loader]
    assert np.mean(accs) > 0.9


def test_scanned_node_step_matches_serial():
    """G supervised seed batches scanned in one program == the serial
    per-batch loop with the same keys (sampling, gather, loss, update)."""
    from glt_tpu.loader.transform import to_batch
    from glt_tpu.models import (
        TrainState,
        make_scanned_node_train_step,
        make_train_step,
        node_seed_blocks,
    )
    from glt_tpu.sampler import NeighborSampler
    from glt_tpu.sampler.base import NodeSamplerInput

    ds, labels = _cluster_dataset()
    model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    bs, G = 16, 3
    sampler = NeighborSampler(ds.get_graph(), [4, 4], batch_size=bs,
                              with_edge=False)
    feat = ds.get_node_feature()
    x0 = jnp.zeros((sampler.node_capacity, feat.shape[1]), jnp.float32)
    ei0 = jnp.full((2, sampler.edge_capacity), -1, jnp.int32)
    m0 = jnp.zeros((sampler.edge_capacity,), bool)
    params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)

    def fresh_state():
        return TrainState(params=params, opt_state=tx.init(params),
                          step=jnp.zeros((), jnp.int32))

    rng = np.random.default_rng(3)
    blocks = list(node_seed_blocks(np.arange(48), bs, G, rng))
    assert blocks[0].shape == (G, bs)
    base = jax.random.PRNGKey(9)

    sstep = make_scanned_node_train_step(model, tx, sampler, feat, labels,
                                         bs)
    st, losses, accs, ovfs = sstep(fresh_state(), blocks[0], base)
    assert int(np.asarray(ovfs).sum()) == 0  # uncapped: never flags
    g_losses = [float(x) for x in np.asarray(losses)]

    # Serial reference with the scan's key schedule.
    tstep = make_train_step(model, tx, batch_size=bs)
    state = fresh_state()
    keys = jax.random.split(base, G)
    s_losses = []
    for i in range(G):
        out = sampler.sample_from_nodes(
            NodeSamplerInput(blocks[0][i].astype(np.int64)), key=keys[i])
        x = feat.gather(out.node)
        safe = jnp.clip(out.node, 0, len(labels) - 1)
        y = jnp.where(out.node >= 0,
                      jnp.take(jnp.asarray(labels), safe), -1)
        state, loss, acc = tstep(state, to_batch(out, x=x, y=y,
                                                 batch_size=bs))
        s_losses.append(float(loss))
    assert g_losses == pytest.approx(s_losses, rel=1e-6), (g_losses,
                                                           s_losses)


def test_scanned_node_step_padded_batch_is_noop():
    """A fully -1-padded trailing batch in a scan block must not move
    params or the step counter (adam momentum would otherwise drift on
    zero grads)."""
    from glt_tpu.models import (
        TrainState,
        make_scanned_node_train_step,
        make_train_step,
        node_seed_blocks,
    )
    from glt_tpu.loader.transform import to_batch
    from glt_tpu.sampler import NeighborSampler
    from glt_tpu.sampler.base import NodeSamplerInput

    ds, labels = _cluster_dataset()
    model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    bs, G = 16, 2
    sampler = NeighborSampler(ds.get_graph(), [4, 4], batch_size=bs,
                              with_edge=False)
    feat = ds.get_node_feature()
    x0 = jnp.zeros((sampler.node_capacity, feat.shape[1]), jnp.float32)
    ei0 = jnp.full((2, sampler.edge_capacity), -1, jnp.int32)
    m0 = jnp.zeros((sampler.edge_capacity,), bool)
    params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)

    def fresh_state():
        return TrainState(params=params, opt_state=tx.init(params),
                          step=jnp.zeros((), jnp.int32))

    # 16 seeds, block [2, 16]: batch 1 is ENTIRELY padding.
    rng = np.random.default_rng(0)
    blocks = list(node_seed_blocks(np.arange(16), bs, G, rng))
    assert (blocks[0][1] == -1).all()
    base = jax.random.PRNGKey(5)
    sstep = make_scanned_node_train_step(model, tx, sampler, feat, labels,
                                         bs)
    st, losses, accs, _ = sstep(fresh_state(), blocks[0], base)
    assert int(st.step) == 1  # only the real batch stepped

    # Equivalence with a serial run over the REAL batch only.
    tstep = make_train_step(model, tx, batch_size=bs)
    state = fresh_state()
    keys = jax.random.split(base, G)
    out = sampler.sample_from_nodes(
        NodeSamplerInput(blocks[0][0].astype(np.int64)), key=keys[0])
    x = feat.gather(out.node)
    safe = jnp.clip(out.node, 0, len(labels) - 1)
    y = jnp.where(out.node >= 0, jnp.take(jnp.asarray(labels), safe), -1)
    state, loss, acc = tstep(state, to_batch(out, x=x, y=y, batch_size=bs))
    np.testing.assert_allclose(float(losses[0]), float(loss), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(st.params),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_gat_grads_finite_with_large_scores():
    """Regression (r5, config-4 scale 10 on TPU): once attention scores
    exceed ~88, masked spill lanes computed exp(score - 0) = inf, and the
    where backward turned 0-cotangent x inf into NaN grads.  Scaled-up
    attention params must yield finite grads."""
    from glt_tpu.models.conv import GATConv

    model = GATConv(out_features=4, heads=2)
    x = jnp.ones((6, 3)) * 10.0
    ei = jnp.array([[1, 2, 3, -1, -1], [0, 0, 1, -1, -1]])
    mask = ei[0] >= 0
    params = model.init(jax.random.PRNGKey(0), x, ei, mask)
    # Inflate attention parameters so raw scores overflow exp by far.
    params = jax.tree_util.tree_map(lambda p: p * 100.0, params)

    def loss(p):
        return model.apply(p, x, ei, mask).sum()

    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_hgt_grads_finite_with_large_scores():
    """Same spill-lane exp-overflow regression for HGT's joint softmax."""
    from glt_tpu.models.hgt import HGT

    ET = ("a", "r", "b")
    model = HGT(edge_types=[ET], hidden_features=8, out_features=3,
                target_type="b", num_layers=1, heads=2, dropout_rate=0.0)
    x = {"a": jnp.ones((5, 4)) * 10.0, "b": jnp.ones((4, 4)) * 10.0}
    ei = {ET: jnp.array([[0, 1, 2, -1], [0, 1, 1, -1]])}
    mask = {ET: ei[ET][0] >= 0}
    params = model.init(jax.random.PRNGKey(0), x, ei, mask)
    params = jax.tree_util.tree_map(lambda p: p * 50.0, params)

    def loss(p):
        return model.apply(p, x, ei, mask).sum()

    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_run_scanned_epoch_driver():
    """The shared epoch driver truncates padded batches, reports
    overflow counts, and matches a manual block loop exactly."""
    from glt_tpu.models import (
        TrainState,
        make_scanned_node_train_step,
        node_seed_blocks,
        run_scanned_epoch,
    )
    from glt_tpu.sampler import NeighborSampler

    ds, labels = _cluster_dataset()
    model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    bs, G = 16, 2
    sampler = NeighborSampler(ds.get_graph(), [4, 4], batch_size=bs,
                              with_edge=False)
    feat = ds.get_node_feature()
    x0 = jnp.zeros((sampler.node_capacity, feat.shape[1]), jnp.float32)
    ei0 = jnp.full((2, sampler.edge_capacity), -1, jnp.int32)
    m0 = jnp.zeros((sampler.edge_capacity,), bool)
    params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)

    def fresh():
        return TrainState(params=params, opt_state=tx.init(params),
                          step=jnp.zeros((), jnp.int32))

    sstep = make_scanned_node_train_step(model, tx, sampler, feat, labels,
                                         bs)
    # 40 seeds, bs 16, G 2 -> 3 real batches over 2 blocks (one padded).
    train_idx = np.arange(40)
    base = jax.random.PRNGKey(3)
    st, losses, accs, ovf = run_scanned_epoch(
        sstep, fresh(), train_idx, bs, G, np.random.default_rng(7), base)
    assert losses.shape == (3,) and accs.shape == (3,)
    assert ovf == 0  # uncapped sampler never overflows
    assert int(st.step) == 3  # padded batch did not step

    # Manual loop with the same shuffle/key schedule.
    st2 = fresh()
    m_losses = []
    for i, blk in enumerate(node_seed_blocks(
            train_idx, bs, G, np.random.default_rng(7))):
        st2, ls, acs, _ = sstep(st2, blk, jax.random.fold_in(base, i))
        m_losses += [float(x) for x in np.asarray(ls)]
    np.testing.assert_allclose(losses, np.asarray(m_losses[:3]),
                               rtol=1e-6)


# -- per-layer trimming by the sampler's hop-block layout -------------------
@functools.lru_cache(maxsize=None)
def _hop_graph():
    from tests.test_neighbor_sampler import hop_graph
    return hop_graph()


@functools.lru_cache(maxsize=None)
def _hop_batch(dedup, lhd, variant):
    """One sampled batch of a sampler variant with random features and
    labels: ``(sampler, out, x, y)``, made once for the three depths."""
    from tests.test_neighbor_sampler import hop_sample, hop_sampler

    s = hop_sampler(_hop_graph(), dedup, lhd, variant)
    out = hop_sample(s, variant)
    rng = np.random.default_rng(1)
    node = np.asarray(out.node)
    x = np.where((node >= 0)[:, None],
                 rng.normal(size=(node.shape[0], 12)), 0)
    y = np.where(node >= 0, rng.integers(0, 5, node.shape[0]), -1)
    return s, out, jnp.asarray(x, jnp.float32), jnp.asarray(y)


@pytest.mark.parametrize("layers", [2, 3, 4])
@pytest.mark.parametrize("variant", ["uncapped", "frontier_cap",
                                     "occupancy_overflow", "padded_seeds"])
@pytest.mark.parametrize("lhd", [True, False])
@pytest.mark.parametrize("dedup", ["dense", "sort"])
def test_trimmed_sage_is_the_whole_sage_on_the_seeds(dedup, lhd, variant,
                                                     layers):
    """GraphSAGE(hops=sampler.hop_bounds) against the whole model: seed
    logits, loss and every parameter gradient, dropout off."""
    from glt_tpu.models import seed_cross_entropy

    s, out, x, y = _hop_batch(dedup, lhd, variant)
    bs = s.batch_size
    ei = jnp.stack([out.row, out.col])
    model = GraphSAGE(hidden_features=16, out_features=5,
                      num_layers=layers, dropout_rate=0.0)
    params = model.init(jax.random.PRNGKey(0), x, ei, out.edge_mask)
    num_seeds = out.num_sampled_nodes[0]

    def loss_fn(p, hops):
        logits = model.apply(p, x, ei, out.edge_mask, train=True, hops=hops)
        loss, _ = seed_cross_entropy(logits, y, bs, out.node_mask, num_seeds)
        return loss, logits

    (l_whole, lg_whole), g_whole = jax.value_and_grad(
        loss_fn, has_aux=True)(params, None)
    (l_trim, lg_trim), g_trim = jax.value_and_grad(
        loss_fn, has_aux=True)(params, s.hop_bounds)
    assert lg_whole.shape == (s.node_capacity, 5)
    assert lg_trim.shape == (bs, 5)
    n = int(num_seeds)
    assert n == (4 if variant == "padded_seeds" else bs)
    np.testing.assert_allclose(lg_trim[:n], lg_whole[:n], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(l_trim, l_whole, rtol=1e-5)
    flat_w = jax.tree_util.tree_leaves_with_path(g_whole)
    flat_t = jax.tree_util.tree_leaves_with_path(g_trim)
    assert [k for k, _ in flat_w] == [k for k, _ in flat_t]
    for (path, a), (_, b) in zip(flat_t, flat_w):
        assert np.abs(np.asarray(b)).max() > 0, path
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                   err_msg=str(path))


# -- the block aggregation against scatter_mean, directly --------------------
#: ``name -> (hop blocks (w, f), num_dst, start of each block, live frontier
#: slots of each block)``.  A start of -1 is an empty frontier (the sampler
#: writes -1 into every slot of such a block).
BLOCK_CASES = {
    "three_blocks": (((4, 3), (9, 2), (14, 2)), 27, (0, 4, 13), (4, 9, 14)),
    "partly_live": (((8, 3), (24, 2), (48, 2)), 60, (0, 5, 17), (5, 12, 30)),
    "all_masked_block": (((4, 3), (12, 2), (24, 2)), 30, (0, 3, -1),
                         (3, 7, 0)),
    "start_at_the_last_row": (((4, 3), (12, 2)), 5, (0, 4), (4, 1)),
    "one_block": (((8, 5),), 8, (0,), (8,)),
}


def _block_batch(case, width=7, seed=0):
    """``x, src, dst, mask`` laid out as the sampler lays a batch out: a
    frontier slot past its block's live ones has ``dst`` -1 and only
    masked slots, a live one some masked slots (degree under the fanout,
    or an overflow-masked neighbour) holding any ``src``."""
    blocks, num_dst, starts, lives = BLOCK_CASES[case]
    rng = np.random.default_rng(seed)
    num_src = num_dst + 40
    src, dst, mask = [], [], []
    for (w, f), start, live in zip(blocks, starts, lives):
        slot = np.arange(w * f) // f
        m = (slot < live) & (rng.random(w * f) < 0.7)
        src.append(np.where(rng.random(w * f) < 0.9,
                            rng.integers(0, num_src, w * f), -1))
        dst.append(np.where(slot < live, start + slot, -1))
        mask.append(m & (src[-1] >= 0))
    x = rng.normal(size=(num_src, width)).astype(np.float32)
    return (blocks, num_dst, jnp.asarray(x),
            jnp.asarray(np.concatenate(src), jnp.int32),
            jnp.asarray(np.concatenate(dst), jnp.int32),
            jnp.asarray(np.concatenate(mask)))


@pytest.mark.parametrize("case", sorted(BLOCK_CASES))
def test_block_mean_is_scatter_mean_over_hop_blocks(case):
    """Forward and the gradient w.r.t. ``x`` (what layers 2-3 pay), under
    ``jit`` with the starts traced: the same terms, reassociated."""
    from glt_tpu.models.conv import block_mean

    blocks, num_dst, x, src, dst, mask = _block_batch(case)
    assert bool(mask.any())
    cot = jnp.asarray(np.random.default_rng(1).normal(
        size=(num_dst, x.shape[1])), jnp.float32)

    def scatter(x, src, dst, mask):
        msgs = jnp.take(x, jnp.clip(src, 0, x.shape[0] - 1), axis=0)
        return scatter_mean(msgs, dst, num_dst, mask)

    def block(x, src, dst, mask):
        return block_mean(x, src, dst, mask, blocks, num_dst)

    want, got = (jax.jit(fn)(x, src, dst, mask) for fn in (scatter, block))
    assert got.shape == (num_dst, x.shape[1])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    (v_w, g_w), (v_g, g_g) = (
        jax.jit(jax.value_and_grad(
            lambda x, fn=fn: jnp.vdot(fn(x, src, dst, mask), cot)))(x)
        for fn in (scatter, block))
    np.testing.assert_allclose(v_g, v_w, rtol=1e-5)
    assert np.abs(np.asarray(g_w)).max() > 0
    np.testing.assert_allclose(g_g, g_w, rtol=1e-6, atol=1e-6)


def test_block_mean_refuses_slots_that_are_not_its_blocks():
    from glt_tpu.models.conv import block_mean

    _, num_dst, x, src, dst, mask = _block_batch("one_block")
    with pytest.raises(ValueError, match="not the hop blocks"):
        block_mean(x, src, dst, mask, ((8, 4),), num_dst)


@pytest.mark.parametrize("layers", [2, 3])
def test_trimmed_forward_aggregates_without_a_scatter(layers):
    """With a layout every layer's gauge says block form, and the lowered
    forward holds no scatter at all, as the same call without one does."""
    from glt_tpu import obs
    from glt_tpu.models.step import hop_trimming

    s, out, x, _ = _hop_batch("dense", True, "uncapped")
    ei = jnp.stack([out.row, out.col])
    model = GraphSAGE(hidden_features=16, out_features=5,
                      num_layers=layers, dropout_rate=0.0)
    params = model.init(jax.random.PRNGKey(0), x, ei, out.edge_mask)
    obs.metrics.reset()
    obs.metrics.enable()
    try:
        trim = hop_trimming(model, s.hop_bounds)
        snap = obs.metrics.snapshot()
    finally:
        obs.metrics.disable()
        obs.metrics.reset()
    for l in range(1, layers + 1):
        assert (snap["glt.model.layer_block_slots{layer=%d}" % l]
                == snap["glt.model.layer_edge_slots{layer=%d}" % l] > 0)

    def lowered(**kw):
        return jax.jit(lambda p, x, ei, em: model.apply(
            p, x, ei, em, **kw)).lower(params, x, ei, out.edge_mask
                                       ).as_text()
    assert '"stablehlo.scatter"' in lowered()
    assert "scatter" not in lowered(**trim)
    assert '"stablehlo.gather"' in lowered(**trim)


def test_sage_hops_must_be_the_batchs_own_layout():
    from glt_tpu.sampler import hop_bounds

    model = GraphSAGE(hidden_features=8, out_features=3, num_layers=2)
    x, ei = jnp.ones((10, 4)), jnp.array([[1, 2, -1], [0, 0, -1]])
    params = model.init(jax.random.PRNGKey(0), x, ei, ei[0] >= 0)
    with pytest.raises(ValueError, match="not laid out"):
        model.apply(params, x, ei, ei[0] >= 0, hops=hop_bounds(2, [2, 2]))


class _SeedSAGEConv(nn.Module):
    """``SAGEConv`` as it stood before ``num_dst`` (commit f209a76)."""
    out_features: int
    use_bias: bool = True
    dtype: object = None

    @nn.compact
    def __call__(self, x, edge_index, edge_mask):
        from glt_tpu.models.conv import _mm_dtype

        num_nodes = x.shape[0]
        src, dst = edge_index[0], edge_index[1]
        with jax.named_scope("glt.model.msg"):
            msgs = jnp.take(x, jnp.clip(src, 0, num_nodes - 1), axis=0)
        agg = scatter_mean(msgs, dst, num_nodes, edge_mask)
        dt = _mm_dtype(self.dtype)
        with jax.named_scope("glt.model.dense"):
            out = (nn.Dense(self.out_features, use_bias=self.use_bias,
                            dtype=dt, name="lin_self")(x)
                   + nn.Dense(self.out_features, use_bias=False,
                              dtype=dt, name="lin_nbr")(agg))
            return out if dt is None else out.astype(jnp.float32)


class _SeedGraphSAGE(nn.Module):
    """``GraphSAGE`` as it stood before ``hops`` (commit f209a76)."""
    hidden_features: int
    out_features: int
    num_layers: int = 3
    dropout_rate: float = 0.5
    dtype: object = None

    @nn.compact
    def __call__(self, x, edge_index, edge_mask, *, train: bool = False):
        for i in range(self.num_layers):
            last = i == self.num_layers - 1
            dim = self.out_features if last else self.hidden_features
            x = _SeedSAGEConv(dim, dtype=self.dtype,
                              name=f"conv{i}")(x, edge_index, edge_mask)
            if not last:
                with jax.named_scope("glt.model.dense"):
                    x = nn.relu(x)
                    x = nn.Dropout(self.dropout_rate,
                                   deterministic=not train)(x)
        return x


@pytest.mark.parametrize("which", ["conv", "sage-train", "sage-eval-bf16"])
def test_sage_called_as_before_compiles_to_the_seeds_program(which):
    """``SAGEConv(num_dst=None)`` and ``GraphSAGE(hops=None)`` against
    copies of the seed's modules: forward and backward differ in metadata
    only (the comparison of tests/test_obs_scopes.py)."""
    from glt_tpu.models import SAGEConv
    from tests.test_obs_scopes import _without_debug_info

    s, out, x, y = _hop_batch("dense", True, "uncapped")
    ei = jnp.stack([out.row, out.col])
    if which == "conv":
        new, old, kw = SAGEConv(7), _SeedSAGEConv(7), {}
    else:
        cfg = dict(hidden_features=16, out_features=5, num_layers=3)
        if which == "sage-eval-bf16":
            cfg["dtype"], kw = jnp.bfloat16, {}
        else:
            kw = {"train": True, "rngs": {"dropout": jax.random.PRNGKey(1)}}
        new, old = GraphSAGE(**cfg), _SeedGraphSAGE(**cfg)
    params = new.init(jax.random.PRNGKey(0), x, ei, out.edge_mask)

    def text(module):
        def f(p, x):
            return jax.value_and_grad(lambda p: jnp.sum(
                module.apply(p, x, ei, out.edge_mask, **kw) ** 2))(p)
        return _without_debug_info(
            jax.jit(f).lower(params, x).compile().as_text())

    assert text(new) == text(old)


class Whole:
    """A model without ``layer_extents``: the step factories run it whole."""
    def __init__(self, model):
        self.apply = model.apply


def test_scanned_node_step_trims_and_equals_whole_steps():
    """N batches through ``make_scanned_node_train_step`` (trimmed by its
    sampler's layout) against the same factory driving the whole model:
    losses and parameters, dropout off; and the gauges of the layout."""
    from glt_tpu import obs
    from glt_tpu.models import TrainState, make_scanned_node_train_step
    from glt_tpu.sampler import NeighborSampler

    ds, labels = _cluster_dataset()
    model = GraphSAGE(hidden_features=16, out_features=3, num_layers=3,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    bs, G = 8, 5
    sampler = NeighborSampler(ds.get_graph(), [3, 2, 2], batch_size=bs,
                              with_edge=False)
    feat = ds.get_node_feature()
    params = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((sampler.node_capacity, feat.shape[1]), jnp.float32),
        jnp.full((2, sampler.edge_capacity), -1, jnp.int32),
        jnp.zeros((sampler.edge_capacity,), bool))

    def fresh_state():
        return TrainState(params=params, opt_state=tx.init(params),
                          step=jnp.zeros((), jnp.int32))

    # 37 seeds in 5 batches of 8: the last is partly padded.
    block = np.full((G, bs), -1, np.int32)
    block.reshape(-1)[:37] = np.random.default_rng(0).permutation(48)[:37]
    key = jax.random.PRNGKey(3)

    obs.metrics.reset()
    obs.metrics.enable()
    try:
        trimmed = make_scanned_node_train_step(model, tx, sampler, feat,
                                               labels, bs)
        snap = obs.metrics.snapshot()
    finally:
        obs.metrics.disable()
    assert sampler.hop_bounds.edge_bounds == (0, 24, 72, 168)
    assert sampler.hop_bounds.node_bounds == (8, 32, 80, 176)
    assert snap["glt.model.edge_slots"] == 168
    assert snap["glt.model.node_rows"] == 176
    assert [snap["glt.model.layer_edge_slots{layer=%d}" % l]
            for l in (1, 2, 3)] == [168, 72, 24]
    assert [snap["glt.model.layer_block_slots{layer=%d}" % l]
            for l in (1, 2, 3)] == [168, 72, 24]
    assert [snap["glt.model.layer_node_rows{layer=%d}" % l]
            for l in (1, 2, 3)] == [80, 32, 8]

    obs.metrics.reset()
    obs.metrics.enable()
    try:
        whole = make_scanned_node_train_step(Whole(model), tx, sampler,
                                             feat, labels, bs)
        assert obs.metrics.snapshot()["glt.model.edge_slots"] == 0
        assert not any(v for k, v in obs.metrics.snapshot().items()
                       if k.startswith("glt.model.layer_block_slots"))
    finally:
        obs.metrics.disable()
        obs.metrics.reset()

    st_t, loss_t, acc_t, _ = trimmed(fresh_state(), block, key)
    st_w, loss_w, acc_w, _ = whole(fresh_state(), block, key)
    assert int(st_t.step) == int(st_w.step) == G
    np.testing.assert_allclose(loss_t, loss_w, rtol=1e-5)
    np.testing.assert_array_equal(acc_t, acc_w)
    for a, b in zip(jax.tree_util.tree_leaves(st_t.params),
                    jax.tree_util.tree_leaves(st_w.params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # The trimmed program carries the trimmed shapes, the whole one not.
    hlo_t = jax.jit(trimmed).lower(fresh_state(), block, key).as_text()
    hlo_w = jax.jit(whole).lower(fresh_state(), block, key).as_text()
    assert "tensor<72x16xf32>" in hlo_t and "tensor<24x16xf32>" in hlo_t
    assert "tensor<72x16xf32>" not in hlo_w
