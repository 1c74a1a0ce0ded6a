"""Learned embedding tables and upstream's bipartite recommendation model
(``glt_tpu/models/bipartite.py``) held to the plain reference
(``glt_tpu/testing/bipartite_reference.py``) at a tiny size on seeded
random weights: logits, loss and every gradient; one scanned call of the
typed link step against the same number of serial reference steps of
dense Adam; and a model without tables compiling the program it always
did."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

from glt_tpu.data import CSRTopo, Graph
from glt_tpu.models import (BipartiteSAGE, GraphSAGE, TrainState,
                            make_scanned_hetero_link_train_step,
                            make_scanned_node_train_step)
from glt_tpu.models import train as train_mod
from glt_tpu.models.bipartite import ITEM_ITEM, ITEM_USER, init_state
from glt_tpu.sampler import NegativeSampling, NeighborSampler
from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler
from glt_tpu.testing import bipartite_reference as ref

NU, NI, Q, FANOUT, HIDDEN = 60, 90, 8, [3, 2], 16
UI = ("user", "to", "item")
NEG = NegativeSampling("binary", 1)
LR = 1e-3


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(7)
    pairs = np.unique(np.stack([rng.integers(0, NU, 500),
                                rng.integers(0, NI, 500)]), axis=1)
    a, b = rng.integers(0, NI, 250), rng.integers(0, NI, 250)
    ii = np.unique(np.concatenate([np.stack([a, b]), np.stack([b, a])], 1),
                   axis=1)
    graphs = {UI: Graph(CSRTopo(pairs, num_nodes=NU)),
              ("item", "rev_to", "user"): Graph(CSRTopo(pairs[::-1],
                                                        num_nodes=NI)),
              ITEM_ITEM: Graph(CSRTopo(ii, num_nodes=NI))}
    sampler = HeteroNeighborSampler(graphs, FANOUT, "user", batch_size=Q)
    return pairs, sampler


def _model(dtype=None):
    return BipartiteSAGE(NU, NI, HIDDEN, HIDDEN, dtype=dtype)


def _batch(sampler, edges, key):
    """One typed link batch, sampled as the step samples it, in the
    reference's form."""
    impl, _, _ = sampler.edges_program(UI, "binary", 1)
    g = {et: (gr.indptr, gr.indices, gr.gather_edge_ids)
         for et, gr in sampler.graphs.items()}
    s, d = jnp.asarray(edges[0], jnp.int32), jnp.asarray(edges[1], jnp.int32)
    out = jax.jit(impl)(g, sampler.graphs[UI].sorted_indices, s, d,
                        jnp.zeros((1,), jnp.float32), key)
    label = jnp.concatenate([jnp.where(s >= 0, 1, -1),
                             jnp.zeros((Q,), jnp.int32)])
    return {"ids": {t: out.node[t] for t in ("user", "item")},
            "edge_index": {et: jnp.stack([out.row[et], out.col[et]])
                           for et in out.row},
            "edge_mask": dict(out.edge_mask),
            "pairs": out.metadata["edge_label_index"], "label": label}


def _close(got, want, rtol, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-30)
    err = np.abs(got - want).max() / scale
    assert err <= rtol, f"{what}: {err:.3g} of the largest value"


def test_model_agrees_with_the_reference_logits_loss_and_gradients(world):
    """Float32 matmuls: the same sums in another order of addition, so
    1e-5 of the largest value (CPU float32 reads 1e-7..1e-6)."""
    pairs, sampler = world
    model = _model()
    params = init_state(model, optax.adam(LR), jax.random.PRNGKey(1)).params
    b = _batch(sampler, pairs[:, 3:3 + Q], jax.random.PRNGKey(4))
    # the pairs are real, live, and the decoder sees both kinds of label
    assert (np.asarray(b["pairs"]) >= 0).all()

    def system(p):
        logits = model.apply(p, (b["ids"], b["pairs"]), b["edge_index"],
                             b["edge_mask"])
        meta = {"edge_label_index": b["pairs"], "edge_label": b["label"]}
        return train_mod.logit_bce_loss(logits, meta)[0], logits

    (got_loss, got_logits), got_grads = jax.value_and_grad(
        system, has_aux=True)(params)
    want_loss, want_grads = ref.grads(params, b)
    p = params["params"]
    want_logits = ref.logits_of_rows(
        p, ref.lookup(ref.table_rows(p, "user"), b["ids"]["user"]),
        ref.lookup(ref.table_rows(p, "item"), b["ids"]["item"]), b)
    _close(got_logits, want_logits, 1e-5, "logits")
    _close(got_loss, want_loss, 1e-5, "loss")
    flat_got = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) == 2 + 5 * 3 + 4 * 2
    for (path, g), w in zip(flat_got, flat_want):
        _close(g, w, 1e-5, jax.tree_util.keystr(path))
    # both tables get a gradient, dense, zero on the rows nobody read
    for t in ("user", "item"):
        tg = np.asarray(ref.table_rows(got_grads["params"], t))[
            :{"user": NU, "item": NI}[t]]
        read = np.zeros(tg.shape[0], bool)
        ids = np.asarray(b["ids"][t])
        read[ids[ids >= 0]] = True
        assert np.abs(tg[read]).sum() > 0 and not tg[~read].any()


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


@pytest.mark.parametrize("group", [2, 3])
def test_one_scanned_call_is_serial_reference_steps_of_dense_adam(world,
                                                                 group):
    """The state after one call of ``group`` batches against ``group``
    reference steps from the same state, every row of both tables and
    both moments: rows read by one batch and not the next move by the
    decayed moments alone.  A lazy Adam leaves them where they were."""
    pairs, sampler = world
    model, tx = _model(), optax.adam(LR)
    step = make_scanned_hetero_link_train_step(model, tx, sampler, UI, NEG)
    state = init_state(model, tx, jax.random.PRNGKey(2))
    warm = np.stack([pairs[:, :group * Q].reshape(2, group, Q)[:, g]
                     for g in range(group)])
    state, *_ = step(state, warm, jax.random.PRNGKey(5))  # m != 0 rows
    before = _host(state)
    blk = np.stack([pairs[:, 40 + g * Q: 40 + (g + 1) * Q]
                    for g in range(group)])
    key = jax.random.PRNGKey(9)
    after, losses, _, flags = step(state, blk, key)
    assert flags.shape == (group, 2)

    params = jax.tree_util.tree_map(jnp.asarray, before.params)
    mu, nu = before.opt_state[0].mu, before.opt_state[0].nu
    count = int(before.opt_state[0].count)
    seen = {t: np.zeros(n, bool) for t, n in (("user", NU), ("item", NI))}
    for g, k in enumerate(jax.random.split(key, group)):
        b = _batch(sampler, blk[g], k)
        for t in seen:
            ids = np.asarray(b["ids"][t])
            seen[t][ids[ids >= 0]] = True
        value, grads = ref.grads(params, b)
        _close(losses[g], value, 1e-5, f"loss of batch {g}")
        new = jax.tree_util.tree_map(
            lambda p, m, v, gr: ref.adam(p, m, v, count + g, gr, LR),
            params, mu, nu, grads)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda x: x[i], new, is_leaf=lambda x: isinstance(x, tuple))
        params, mu, nu = pick(0), pick(1), pick(2)
    assert int(after.opt_state[0].count) == count + group
    assert int(after.step) == int(before.step) + group
    for name, got, want in (("params", after.params, params),
                            ("mu", after.opt_state[0].mu, mu),
                            ("nu", after.opt_state[0].nu, nu)):
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(got)[0],
                jax.tree_util.tree_leaves(want)):
            _close(g, w, 2e-5, f"{name} {jax.tree_util.keystr(path)}")
    # every row with a moment moved, read by this call or not: dense
    unread = 0
    for t, n in (("user", NU), ("item", NI)):
        old = ref.table_rows(before.params["params"], t)[:n]
        new_t = np.asarray(ref.table_rows(after.params["params"], t))[:n]
        moved = (new_t != old).any(axis=1)
        had_m = (ref.table_rows(before.opt_state[0].mu["params"], t)[:n]
                 != 0).any(axis=1)
        assert moved[had_m].all()
        unread += int((had_m & ~seen[t]).sum())
    assert unread > 0


def _graphsage_step():
    rng = np.random.default_rng(3)
    g = Graph(CSRTopo(rng.integers(0, 40, (2, 200)), num_nodes=40))
    sampler = NeighborSampler(g, [3, 2], batch_size=4, with_edge=False)
    model = GraphSAGE(hidden_features=8, out_features=3, num_layers=2)
    tx = optax.adam(1e-3)
    feat = rng.normal(size=(40, 5)).astype(np.float32)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 5)), jnp.full((2, 1), -1, jnp.int32),
                        jnp.zeros((1,), bool))
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    step = make_scanned_node_train_step(
        model, tx, sampler, feat, rng.integers(0, 3, 40), 4)
    blk = jnp.asarray(np.arange(8).reshape(2, 4), jnp.int32)
    return jax.jit(step).lower(state, blk, jax.random.PRNGKey(1)).as_text()


def _unsplit_gated_update(tx):
    """``gated_update`` as it was before tables existed."""
    def run(state, grads, any_valid):
        def apply(s):
            with jax.named_scope("glt.step.update"):
                updates, opt_state = tx.update(grads, s.opt_state,
                                               s.params)
                params = optax.apply_updates(s.params, updates)
            return TrainState(params, opt_state, s.step + 1)

        return lax.cond(any_valid, apply, lambda s: s, state)

    return run


def test_a_model_without_tables_compiles_the_program_it_always_did(
        monkeypatch):
    split = _graphsage_step()
    monkeypatch.setattr(train_mod, "gated_update", _unsplit_gated_update)
    assert _graphsage_step() == split


def test_the_split_update_is_adam_of_the_whole_tree():
    """Tables and other leaves interleaved in one tree: the split update
    equals the whole-tree update bit for bit, count included."""
    from glt_tpu.models.step import gated_update

    rng = np.random.default_rng(0)
    params = {"params": {"a": {"kernel": rng.normal(size=(3, 2))},
                         "emb": {"table": rng.normal(size=(5, 2))},
                         "z": {"bias": rng.normal(size=(2,))}}}
    params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                    params)
    grads = jax.tree_util.tree_map(lambda x: x * 0.5 + 0.1, params)
    tx = optax.adam(1e-2)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    got = jax.jit(gated_update(tx))(state, grads, jnp.bool_(True))
    want = jax.jit(_unsplit_gated_update(tx))(state, grads, jnp.bool_(True))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    text = jax.jit(gated_update(tx)).lower(
        state, grads, jnp.bool_(True)).as_text(debug_info=True)
    assert "glt.embed.update" in text and "glt.step.update" in text


def test_a_block_without_a_seed_edge_moves_nothing(world):
    """The step's gate: a batch of padding is a no-op, Adam's decay and
    the step count included, for the tables as for the towers."""
    pairs, sampler = world
    model, tx = _model(), optax.adam(LR)
    step = make_scanned_hetero_link_train_step(model, tx, sampler, UI, NEG)
    state = init_state(model, tx, jax.random.PRNGKey(2))
    warm = pairs[:, :Q][None]
    state, *_ = step(state, warm, jax.random.PRNGKey(5))
    before = _host(state)
    after, *_ = step(state, np.full((2, 2, Q), -1), jax.random.PRNGKey(6))
    for a, b in zip(jax.tree_util.tree_leaves(_host(after)),
                    jax.tree_util.tree_leaves(before)):
        np.testing.assert_array_equal(a, b)
