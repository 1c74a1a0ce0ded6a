"""Hetero sampler/loader/model tests (cf. test_hetero_neighbor_sampler.py).

Fixture: bipartite user–item graph where item j is connected to users
(j, j+1 mod U) — every sampled edge is verifiable from ids alone.
"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from glt_tpu.data import Dataset
from glt_tpu.loader import HeteroBatch
from glt_tpu.loader.hetero_neighbor_loader import HeteroNeighborLoader
from glt_tpu.models.rgat import RGAT
from glt_tpu.sampler import NodeSamplerInput
from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler
from tests.test_neighbor_sampler import sorted_slots  # noqa: F401 (fixture)

U, I = 12, 8
ET_UI = ("user", "clicks", "item")
ET_IU = ("item", "rev_clicks", "user")


def hetero_dataset():
    # user u clicks items u % I and (u+1) % I; reverse edges mirror.
    u_src = np.repeat(np.arange(U), 2)
    i_dst = np.concatenate([[u % I, (u + 1) % I] for u in range(U)])
    ei = {ET_UI: np.stack([u_src, i_dst]),
          ET_IU: np.stack([i_dst, u_src])}
    feats = {"user": np.arange(U, dtype=np.float32)[:, None] * [1.0, 0.0],
             "item": np.arange(I, dtype=np.float32)[:, None] * [0.0, 1.0]}
    labels = {"user": (np.arange(U) % 2).astype(np.int32)}
    return (Dataset()
            .init_graph(ei, graph_mode="HOST",
                        num_nodes={"user": U, "item": I})
            .init_node_features(feats)
            .init_node_labels(labels))


def edge_ok(et, s, d):
    if et == ET_UI:
        return d in (s % I, (s + 1) % I)
    return s in (d % I, (d + 1) % I)


class TestHeteroSampler:
    def test_two_hop_bipartite(self):
        ds = hetero_dataset()
        samp = HeteroNeighborSampler(ds.graph, [2, 2], "user", batch_size=3)
        out = samp.sample_from_nodes(
            NodeSamplerInput(np.array([0, 4, 7]), "user"))
        users = np.asarray(out.node["user"])
        items = np.asarray(out.node["item"])
        umask = np.asarray(out.node_mask["user"])
        imask = np.asarray(out.node_mask["item"])
        # seeds first among users
        assert users[:3].tolist() == [0, 4, 7]
        assert len(set(users[umask].tolist())) == umask.sum()
        assert len(set(items[imask].tolist())) == imask.sum()

        # output keys are reversed types ('rev_' convention): the reverse
        # of user--clicks-->item is exactly ET_IU and vice versa.
        rev_ui = ET_IU
        row = np.asarray(out.row[rev_ui])
        col = np.asarray(out.col[rev_ui])
        m = np.asarray(out.edge_mask[rev_ui])
        assert m.sum() > 0
        for r, c in zip(row[m], col[m]):
            # col = seed side (user), row = neighbor side (item)
            assert edge_ok(ET_UI, users[c], items[r])

        rev_iu = ET_UI
        row = np.asarray(out.row[rev_iu])
        col = np.asarray(out.col[rev_iu])
        m = np.asarray(out.edge_mask[rev_iu])
        assert m.sum() > 0  # hop 2: items expand back to users
        for r, c in zip(row[m], col[m]):
            assert edge_ok(ET_IU, items[c], users[r])

    def test_per_edge_type_fanout_dict(self):
        ds = hetero_dataset()
        samp = HeteroNeighborSampler(
            ds.graph, {ET_UI: [2], ET_IU: [0]}, "user", batch_size=2)
        out = samp.sample_from_nodes(
            NodeSamplerInput(np.array([1, 2]), "user"))
        assert np.asarray(out.edge_mask[ET_UI]).sum() == 0


class TestHeteroLoader:
    def test_collate_features_labels(self):
        ds = hetero_dataset()
        loader = HeteroNeighborLoader(ds, [2, 2],
                                      ("user", np.arange(U)), batch_size=4)
        n = 0
        for batch in loader:
            n += 1
            users = np.asarray(batch.node["user"])
            umask = np.asarray(batch.node_mask["user"])
            xu = np.asarray(batch.x["user"])
            np.testing.assert_allclose(xu[umask][:, 0], users[umask])
            yu = np.asarray(batch.y["user"])
            np.testing.assert_array_equal(yu[umask], users[umask] % 2)
            xi = np.asarray(batch.x["item"])
            imask = np.asarray(batch.node_mask["item"])
            items = np.asarray(batch.node["item"])
            np.testing.assert_allclose(xi[imask][:, 1], items[imask])
        assert n == 3


class TestRGAT:
    def test_learns_user_parity(self):
        ds = hetero_dataset()
        loader = HeteroNeighborLoader(ds, [2, 2],
                                      ("user", np.arange(U)), batch_size=4,
                                      shuffle=True, seed=0)
        batch_ets = [ET_IU, ET_UI]  # batch keys = reversed input types
        model = RGAT(edge_types=batch_ets, hidden_features=16,
                     out_features=2, target_type="user", num_layers=2,
                     conv="sage", dropout_rate=0.0)
        first = next(iter(loader))
        params = model.init({"params": jax.random.PRNGKey(0)}, first.x,
                            first.edge_index, first.edge_mask)
        tx = optax.adam(5e-2)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, batch):
            def loss_fn(p):
                logits = model.apply(p, batch.x, batch.edge_index,
                                     batch.edge_mask)
                y = batch.y["user"][:4]
                valid = y >= 0
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits[:4], jnp.where(valid, y, 0))
                return jnp.where(valid, ce, 0).sum() / jnp.maximum(
                    valid.sum(), 1)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for _ in range(10):
            for batch in loader:
                params, opt_state, loss = step(params, opt_state, batch)
                losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


class TestHGT:
    def test_learns_user_parity(self):
        """HGT on the same id-determined task the RGAT test uses: the
        joint cross-edge-type attention softmax + gated residuals must
        train to separate even/odd users."""
        from glt_tpu.models import HGT

        ds = hetero_dataset()
        loader = HeteroNeighborLoader(ds, [2, 2],
                                      ("user", np.arange(U)), batch_size=4,
                                      shuffle=True, seed=0)
        batch_ets = [ET_IU, ET_UI]
        model = HGT(edge_types=batch_ets, hidden_features=16,
                    out_features=2, target_type="user", num_layers=2,
                    heads=2, dropout_rate=0.0)
        first = next(iter(loader))
        params = model.init({"params": jax.random.PRNGKey(0)}, first.x,
                            first.edge_index, first.edge_mask)
        tx = optax.adam(5e-2)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, batch):
            def loss_fn(p):
                logits = model.apply(p, batch.x, batch.edge_index,
                                     batch.edge_mask)
                y = batch.y["user"][:4]
                valid = y >= 0
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits[:4], jnp.where(valid, y, 0))
                return jnp.where(valid, ce, 0).sum() / jnp.maximum(
                    valid.sum(), 1)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for _ in range(30):
            for batch in loader:
                params, opt_state, loss = step(params, opt_state, batch)
                losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])

    def test_attention_normalized_across_edge_types(self):
        """The per-destination attention weights must sum to 1 over ALL
        incoming edge types jointly (the defining HGT property vs
        per-type softmax)."""
        from glt_tpu.models.hgt import HGTConv

        rng = np.random.default_rng(0)
        x = {"a": jnp.asarray(rng.standard_normal((3, 8)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
             "t": jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)}
        ets = [("a", "r1", "t"), ("b", "r2", "t")]
        ei = {("a", "r1", "t"): jnp.array([[0, 1, 2], [0, 0, 1]]),
              ("b", "r2", "t"): jnp.array([[0, 3, -1], [0, 1, -1]])}
        em = {("a", "r1", "t"): jnp.array([True, True, True]),
              ("b", "r2", "t"): jnp.array([True, True, False])}
        conv = HGTConv(ets, out_features=8, heads=2)
        params = conv.init(jax.random.PRNGKey(0), x, ei, em)
        out, state = conv.apply(params, x, ei, em,
                                mutable=["intermediates"])
        # shape + residual sanity: untouched types pass through
        assert out["t"].shape == (2, 8)
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(x["a"]))
        # The defining HGT property: per destination node, attention mass
        # sums to 1 across BOTH incoming edge types jointly (a per-type
        # softmax would give 2.0 for t0, which receives edges of both
        # types: a->t0 x2 via r1 and b->t0 via r2).
        att = np.asarray(
            state["intermediates"]["att_weight_sum_t"][0])  # [2, heads]
        np.testing.assert_allclose(att, np.ones_like(att), atol=1e-5)
        # gradient flows through both edge types' attention params
        g = jax.grad(lambda p: conv.apply(p, x, ei, em)["t"].sum())(params)
        flat = jax.tree.leaves(
            jax.tree.map(lambda v: float(jnp.abs(v).sum()), g))
        assert sum(flat) > 0


class TestHeteroLink:
    def test_binary_negatives(self):
        ds = hetero_dataset()
        samp = HeteroNeighborSampler(ds.graph, [2], "user", batch_size=4)
        from glt_tpu.sampler import EdgeSamplerInput, NegativeSampling
        src = np.array([0, 3, 6, 9])
        dst = src % I
        inp = EdgeSamplerInput(row=src, col=dst, input_type=ET_UI,
                               neg_sampling=NegativeSampling("binary", 1))
        out = samp.sample_from_edges(inp)
        eli = np.asarray(out.metadata["edge_label_index"])
        lab = np.asarray(out.metadata["edge_label"])
        users = np.asarray(out.node["user"])
        items = np.asarray(out.node["item"])
        assert eli.shape == (2, 8)
        for i in range(4):
            assert users[eli[0, i]] == src[i]
            assert items[eli[1, i]] == dst[i]
            assert lab[i] == 1
        assert (lab[4:] == 0).all()
        # negatives resolve to valid local item indices
        assert (eli[1, 4:] >= 0).all()

    def test_triplet(self):
        ds = hetero_dataset()
        samp = HeteroNeighborSampler(ds.graph, [2], "user", batch_size=3)
        from glt_tpu.sampler import EdgeSamplerInput, NegativeSampling
        src = np.array([1, 4, 7])
        dst = src % I
        inp = EdgeSamplerInput(row=src, col=dst, input_type=ET_UI,
                               neg_sampling=NegativeSampling("triplet", 2))
        out = samp.sample_from_edges(inp)
        users = np.asarray(out.node["user"])
        items = np.asarray(out.node["item"])
        assert [users[i] for i in np.asarray(out.metadata["src_index"])] \
            == src.tolist()
        assert [items[i] for i in np.asarray(out.metadata["dst_pos_index"])] \
            == dst.tolist()
        dni = np.asarray(out.metadata["dst_neg_index"])
        assert dni.shape == (3, 2)
        assert (dni >= 0).all()

    def test_loader(self):
        from glt_tpu.loader.hetero_link_loader import HeteroLinkNeighborLoader
        from glt_tpu.sampler import NegativeSampling
        ds = hetero_dataset()
        src = np.arange(U)
        dst = src % I
        loader = HeteroLinkNeighborLoader(
            ds, [2], (ET_UI, np.stack([src, dst])), batch_size=4,
            neg_sampling=NegativeSampling("binary", 1))
        n = 0
        for batch in loader:
            n += 1
            eli = np.asarray(batch.metadata["edge_label_index"])
            assert eli.shape == (2, 8)
            xu = np.asarray(batch.x["user"])
            users = np.asarray(batch.node["user"])
            umask = np.asarray(batch.node_mask["user"])
            np.testing.assert_allclose(xu[umask][:, 0], users[umask])
        assert n == 3


class TestFrontierCap:
    def test_capped_widths(self):
        from glt_tpu.sampler.hetero_neighbor_sampler import hetero_hop_widths
        widths, cap = hetero_hop_widths(
            [ET_UI, ET_IU], {ET_UI: [4, 4], ET_IU: [4, 4]},
            {"user": 8}, 2, frontier_cap=16)
        assert all(w <= 16 for hop in widths for w in hop.values())
        assert cap["user"] <= 8 + 16 + 16 and cap["item"] <= 16 + 16

    def test_capped_sampling_still_valid(self):
        """Edges emitted under a tight cap must still verify against the
        graph, and nbr locals must stay inside the (smaller) node buffer."""
        ds = hetero_dataset()
        samp = HeteroNeighborSampler(ds.graph, [2, 2], "user",
                                     batch_size=3, frontier_cap=4)
        out = samp.sample_from_nodes(NodeSamplerInput(np.array([0, 5, 9])))
        for et in (ET_UI, ET_IU):
            rev_src = np.asarray(out.node[et[2]])   # reversed key: src=nbr
            rev_dst = np.asarray(out.node[et[0]])
            from glt_tpu.typing import reverse_edge_type
            rk = reverse_edge_type(et)
            m = np.asarray(out.edge_mask[rk])
            row = np.asarray(out.row[rk])
            col = np.asarray(out.col[rk])
            assert (row[m] < rev_src.shape[0]).all()
            assert (row[m] >= 0).all()
            for r, c in zip(row[m], col[m]):
                assert edge_ok(et, rev_dst[c], rev_src[r]), (et, rev_dst[c],
                                                             rev_src[r])


class TestHeteroDedupStrategies:
    def test_dense_matches_sort(self):
        """Per-type dense scatter-map inducer equals the argsort path on
        identical keys (hetero analog of the homo equivalence test)."""
        ds = hetero_dataset()
        key = jax.random.PRNGKey(11)
        seeds = np.arange(6)

        def sample(force_sort):
            s = HeteroNeighborSampler(ds.graph, {ET_UI: [2, 2],
                                                 ET_IU: [2, 2]},
                                      input_type="user", batch_size=6,
                                      seed=0)
            if force_sort:
                s._num_nodes_by_type = {}  # before first trace
            return s.sample_from_nodes(NodeSamplerInput(seeds), key=key)

        a, b = sample(False), sample(True)
        for field in ("node", "row", "col", "node_mask", "edge_mask",
                      "num_sampled_nodes", "num_sampled_edges"):
            da, db = getattr(a, field), getattr(b, field)
            if da is None or db is None:
                assert da is db, field
                continue
            assert set(da.keys()) == set(db.keys()), field
            for k in da:
                np.testing.assert_array_equal(
                    np.asarray(da[k]), np.asarray(db[k]),
                    err_msg=f"{field}[{k}]")

    def test_last_hop_nodedup_equivalent_edges(self):
        """Hetero leaf-block mode: identical global edge multiset per
        edge type vs the exact path on the same key; masked-in leaf
        slots resolve to valid global ids."""
        ds = hetero_dataset()
        key = jax.random.PRNGKey(19)
        seeds = np.array([0, 4, 7, 9])
        outs = {}
        for lhd in (True, False):
            s = HeteroNeighborSampler(
                ds.graph, {ET_UI: [2, 2], ET_IU: [2, 2]},
                input_type="user", batch_size=4, seed=0,
                last_hop_dedup=lhd)
            outs[lhd] = s.sample_from_nodes(
                NodeSamplerInput(seeds, "user"), key=key)

        def global_edges(out, ret):
            # ret is the reversed (output) edge type; src side = col,
            # dst side = row, resolved through the per-type node lists.
            src_t, _, dst_t = ret
            m = np.asarray(out.edge_mask[ret])
            r = np.asarray(out.row[ret])[m]
            c = np.asarray(out.col[ret])[m]
            # output convention: row indexes the *reversed* source type
            src = np.asarray(out.node[dst_t])[c]
            dst = np.asarray(out.node[src_t])[r]
            return sorted(zip(src.tolist(), dst.tolist()))

        from glt_tpu.typing import reverse_edge_type
        for et in (ET_UI, ET_IU):
            ret = reverse_edge_type(et)
            assert global_edges(outs[False], ret) == \
                global_edges(outs[True], ret), ret
            # every masked-in edge is a real graph edge
            src_t, _, dst_t = ret
            m = np.asarray(outs[False].edge_mask[ret])
            r = np.asarray(outs[False].row[ret])[m]
            c = np.asarray(outs[False].col[ret])[m]
            for rr, cc in zip(r, c):
                s_g = int(np.asarray(outs[False].node[dst_t])[cc])
                d_g = int(np.asarray(outs[False].node[src_t])[rr])
                assert edge_ok(et, s_g, d_g), (et, s_g, d_g)
        # node_mask marks only valid ids
        for t in ("user", "item"):
            nm = np.asarray(outs[False].node_mask[t])
            ids = np.asarray(outs[False].node[t])
            assert (ids[nm] >= 0).all()
            assert (ids[~nm] == -1).all()
        # seeds stay at the front of the seed type
        assert np.asarray(outs[False].node["user"])[:4].tolist() == \
            seeds.tolist()

    def test_nodedup_with_frontier_cap_stays_valid(self):
        """Regression: with frontier_cap capping an interior hop, the
        capacity budgets capped widths while the inducer inserts raw
        candidates — the leaf block must NOT engage (it would clobber
        live interior slots).  Every masked-in edge must be a real graph
        edge."""
        # 4-ary tree: i -> 4i+1..4i+4 over one self-typed edge type.
        n = 200
        src = np.repeat(np.arange(n), 4)
        dst = np.minimum(4 * np.repeat(np.arange(n), 4)
                         + np.tile(np.arange(1, 5), n), n - 1)
        et = ("n", "e", "n")
        ds = (Dataset()
              .init_graph({et: np.stack([src, dst])}, graph_mode="HOST",
                          num_nodes={"n": n})
              .init_node_features(
                  {"n": np.arange(n, dtype=np.float32)[:, None]}))
        s = HeteroNeighborSampler(ds.graph, {et: [4, 1]}, input_type="n",
                                  batch_size=4, frontier_cap=8,
                                  last_hop_dedup=False, seed=0)
        out = s.sample_from_nodes(
            NodeSamplerInput(np.array([0, 1, 2, 3]), "n"),
            key=jax.random.PRNGKey(7))
        ret = ("n", "e", "n")  # self-typed: reverse keeps the relation
        node = np.asarray(out.node["n"])
        m = np.asarray(out.edge_mask[ret])
        r = np.asarray(out.row[ret])[m]
        c = np.asarray(out.col[ret])[m]
        real = set(zip(src.tolist(), dst.tolist()))
        bad = [(int(node[cc]), int(node[rr])) for rr, cc in zip(r, c)
               if (int(node[cc]), int(node[rr])) not in real]
        assert not bad, f"non-edges emitted: {bad[:5]}"


def test_scanned_hetero_step_matches_eager():
    """G hetero batches scanned in one program == the eager per-batch
    loader loop with the same sampling keys (r5: config-4 is dispatch-
    bound, the scan amortises it)."""
    import optax

    from glt_tpu.models import (
        init_hetero_state,
        make_scanned_hetero_train_step,
    )
    from glt_tpu.models.rgat import RGAT
    from glt_tpu.models.train import TrainState, seed_cross_entropy
    from glt_tpu.sampler.base import NodeSamplerInput
    from glt_tpu.data.graph import Graph
    from glt_tpu.data.topology import CSRTopo
    from glt_tpu.sampler.hetero_neighbor_sampler import (
        HeteroNeighborSampler,
    )

    rng = np.random.default_rng(0)
    U, I, classes = 48, 24, 4
    labels_u = (np.arange(U) % classes).astype(np.int32)
    u_src = np.repeat(np.arange(U), 3)
    i_dst = rng.integers(0, I, U * 3)
    ET_UI = ("user", "clicks", "item")
    ET_IU = ("item", "rev_clicks", "user")
    graphs = {
        ET_UI: Graph(CSRTopo(np.stack([u_src, i_dst]), num_nodes=U),
                     mode="HOST"),
        ET_IU: Graph(CSRTopo(np.stack([i_dst, u_src]), num_nodes=I),
                     mode="HOST"),
    }
    feats = {"user": rng.normal(0, .1, (U, 8)).astype(np.float32),
             "item": np.eye(classes, dtype=np.float32)[
                 np.arange(I) % classes]}
    labels = {"user": labels_u}
    bs, G = 8, 3
    sampler = HeteroNeighborSampler(graphs, [3, 3], "user", batch_size=bs,
                                    seed=0)
    model = RGAT(edge_types=[ET_IU, ET_UI], hidden_features=16,
                 out_features=classes, target_type="user", num_layers=2,
                 conv="gat", dropout_rate=0.0)
    tx = optax.adam(1e-2)

    state0 = init_hetero_state(model, tx, sampler, feats,
                               jax.random.PRNGKey(0))
    sstep = make_scanned_hetero_train_step(model, tx, sampler, feats,
                                           labels, bs)
    blocks = np.stack([np.arange(g * bs, (g + 1) * bs) % U
                       for g in range(G)]).astype(np.int32)
    base = jax.random.PRNGKey(7)
    st, losses, accs, ovfs = sstep(state0, blocks, base)
    g_losses = [float(x) for x in np.asarray(losses)]
    assert np.asarray(ovfs).tolist() == [0] * G     # no node_capacity

    # Eager reference with the scan's key schedule and the same math.
    keys = jax.random.split(base, G)
    labels_dev = jnp.asarray(labels_u)
    rows = {t: jnp.asarray(v) for t, v in feats.items()}
    state = state0
    e_losses = []
    for i in range(G):
        out = sampler.sample_from_nodes(
            NodeSamplerInput(blocks[i].astype(np.int64), "user"),
            key=keys[i])
        x = {}
        for t, node in out.node.items():
            valid = node >= 0
            gid = jnp.where(valid, node, 0)
            x[t] = jnp.where(valid[:, None],
                             jnp.take(rows[t], gid, axis=0, mode="clip"),
                             0)
        node_u = out.node["user"]
        y = jnp.where(node_u >= 0,
                      jnp.take(labels_dev,
                               jnp.clip(node_u, 0, U - 1)), -1)
        ei = {et: jnp.stack([out.row[et], out.col[et]]) for et in out.row}

        def loss_fn(p):
            logits = model.apply(p, x, ei, out.edge_mask, train=True,
                                 rngs={"dropout": jax.random.fold_in(
                                     jax.random.PRNGKey(0), state.step)})
            return seed_cross_entropy(logits, y, bs,
                                      out.node_mask["user"],
                                      out.num_sampled_nodes["user"][0])

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        updates, opt_state = tx.update(grads, state.opt_state,
                                       state.params)
        import optax as _ox

        state = TrainState(_ox.apply_updates(state.params, updates),
                           opt_state, state.step + 1)
        e_losses.append(float(loss))
    assert g_losses == pytest.approx(e_losses, rel=1e-5), (g_losses,
                                                           e_losses)


@pytest.mark.parametrize("layout", ["uncapped", "overflowing", "as_the_cell",
                                    "no_frontier"])
def test_typed_sampler_output_equals_the_map_forms(layout, monkeypatch,
                                                    sorted_slots):
    """The typed sampler's whole output with every hop of a type's chain
    sorted against the parent's program, same relations, seeds and key: a
    type whose capacity can hold every node known before the last hop
    sorts at every hop, one whose buffer may already have overflowed
    keeps the id map at every hop; the gauge sums the sorted chains'
    keys hop by hop."""
    import glt_tpu.sampler.hetero_neighbor_sampler as mod
    from tests.test_neighbor_sampler import assert_outputs_equal, map_form
    from tests.test_rgat_igbh import TRIM_LAYOUTS, igbh_graphs

    caps, fronts, overflows = TRIM_LAYOUTS[layout]
    graphs, chains = igbh_graphs(seed=2), []
    real = mod.induce_init

    def init(num_nodes, capacity, known_last):
        state = real(num_nodes, capacity, known_last)
        chains.append((state.seen is None, capacity, known_last))
        return state
    monkeypatch.setattr(mod, "induce_init", init)

    def sample():
        samp = HeteroNeighborSampler(graphs, [3, 2, 2], "paper",
                                     batch_size=4, seed=0,
                                     node_capacity=caps,
                                     frontier_capacity=fronts)
        return [samp.sample_from_nodes(NodeSamplerInput(np.asarray(seeds)),
                                       key=jax.random.PRNGKey(k))
                for k, seeds in enumerate(([0, 7, 21, 40], [3, 3, 59, -1]))]
    got = sample()
    gauge = [sorted_slots(k) for k in range(4)]
    # Tight capacities leave types under their bound on known nodes
    # (in sorted order of the types: the last is paper).
    assert [c[0] for c in chains] == {
        "overflowing": [False] * 4, "as_the_cell": [True] * 3 + [False],
    }.get(layout, [True] * 4)
    assert all(is_sorted == (known <= cap)
               for is_sorted, cap, known in chains)
    # The seeds are paper's: hop 0 reads their 4 slots where paper's chain
    # is sorted; a hop's keys are over the sorted chains alone.
    assert gauge[0] == (4 if chains[-1][0] else 0)
    assert all(gauge) == all(c[0] for c in chains)
    assert any(gauge) == any(c[0] for c in chains)
    with map_form(monkeypatch, mod) as parent:
        want = sample()
    assert parent == [c[2] for c in chains]
    assert any(bool((o.metadata or {}).get("overflow", False))
               for o in got) == overflows
    for a, b in zip(got, want):
        assert_outputs_equal(a, b)
