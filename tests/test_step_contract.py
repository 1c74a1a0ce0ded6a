"""The contract of the one train-step body (``glt_tpu/models/step.py``),
held against every ``make_*step`` factory that wraps it.

Graphs are small enough that every degree is under the fanout, so a
sampled batch holds each seed's whole two-hop neighbourhood whatever the
key: the model run on the WHOLE graph is then a reference for the seed
logits that shares no sampler, gather or step code with the factories.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from glt_tpu.data.feature import Feature
from glt_tpu.data.graph import Graph
from glt_tpu.data.topology import CSRTopo
from glt_tpu.loader.transform import to_batch
from glt_tpu.models import (GraphSAGE, TrainState, make_eval_step,
                            make_scanned_hetero_train_step,
                            make_scanned_link_train_step,
                            make_scanned_node_train_step,
                            make_scanned_subgraph_train_step,
                            make_train_step, seed_cross_entropy)
from glt_tpu.models.rgat import RGNN
from glt_tpu.models.step import hop_trimming
from glt_tpu.parallel import (DistHeteroNeighborSampler,
                              DistNeighborSampler,
                              HeteroTieredTrainPipeline,
                              TieredTrainPipeline, make_dist_train_step,
                              make_hetero_dist_train_step,
                              make_scanned_dist_train_step, shard_feature,
                              shard_graph, shard_hetero_graph)
from glt_tpu.parallel.dist_feature import (exchange_gather,
                                           exchange_gather_hot,
                                           exchange_gather_xy,
                                           shard_feature_tiered)
from glt_tpu.parallel.dist_train import (make_hetero_tiered_train_step,
                                         make_tiered_train_step)
from glt_tpu.sampler import NeighborSampler, NodeSamplerInput
from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler
from glt_tpu.typing import reverse_edge_type

N_DEV, BS, FANOUT, CLASSES, DIM = 4, 4, [3, 3], 3, 8
ET_UI = ("user", "clicks", "item")
ET_IU = ("item", "rev_clicks", "user")
TX = optax.adam(1e-2)


def _mesh():
    return Mesh(np.array(jax.devices()[:N_DEV]), ("shard",))


def _csr(pairs, n):
    src, dst = np.array(pairs).T
    return CSRTopo(np.stack([src, dst]), num_nodes=n)


def _state(params):
    return TrainState(params, TX.init(params), jnp.zeros((), jnp.int32))


def _bits_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        (np.asarray(x) == np.asarray(y)).all() for x, y in zip(la, lb))


def _plain_loss(logits, labels, shard_seeds):
    """Mean CE and accuracy over the unique real seeds of each shard's
    batch (0 where there is none), meaned over the shards: numpy."""
    z = np.asarray(logits, np.float64)
    m = z.max(-1, keepdims=True)
    logp = z - m - np.log(np.exp(z - m).sum(-1, keepdims=True))
    losses, accs = [], []
    for seeds in np.asarray(shard_seeds):
        real = np.unique(seeds[seeds >= 0])
        if real.size == 0:
            losses.append(0.0), accs.append(0.0)
            continue
        losses.append(-logp[real, labels[real]].mean())
        accs.append((z[real].argmax(-1) == labels[real]).mean())
    return np.mean(losses), np.mean(accs)


class Homo:
    """32 nodes, 1..3 distinct out-neighbours each, every node labelled
    (so a hop-1 row in the seed block would be read as a seed by a loss
    over the whole block)."""

    def __init__(self):
        rng = np.random.default_rng(0)
        n = self.n = 32
        pairs = [(i, j) for i in range(n)
                 for j in rng.choice(n, rng.integers(1, 4), replace=False)]
        self.topo = _csr(pairs, n)
        self.feat = rng.normal(size=(n, DIM)).astype(np.float32)
        self.labels = rng.integers(0, CLASSES, n).astype(np.int32)
        self.model = GraphSAGE(hidden_features=8, out_features=CLASSES,
                               num_layers=2, dropout_rate=0.0)
        src, dst = np.array(pairs).T
        # messages flow neighbour -> node: row = neighbour, col = node
        self.whole = (jnp.asarray(self.feat),
                      jnp.asarray(np.stack([dst, src]), jnp.int32),
                      jnp.ones((len(pairs),), bool))
        params = self.model.init({"params": jax.random.PRNGKey(0)},
                                 *self.whole)
        self.state0 = _state(params)
        self.graph = Graph(self.topo)
        self.sampler = NeighborSampler(self.graph, FANOUT, batch_size=BS,
                                       with_edge=False)

    def reference(self, params, shard_seeds):
        return _plain_loss(self.model.apply(params, *self.whole),
                           self.labels, shard_seeds)

    def batch(self, seeds, key):
        out = self.sampler.sample_from_nodes(
            NodeSamplerInput(np.asarray(seeds)), key=key)
        ok = out.node >= 0
        gid = jnp.maximum(out.node, 0)
        x = jnp.where(ok[:, None], jnp.asarray(self.feat)[gid], 0)
        y = jnp.where(ok, jnp.asarray(self.labels)[gid], -1)
        return to_batch(out, x=x, y=y, batch_size=BS)

    @functools.cached_property
    def sharded(self):
        g = shard_graph(self.topo, N_DEV)
        lab = jnp.asarray(self.labels.reshape(N_DEV, g.nodes_per_shard))
        return g, lab, _mesh()


class Typed:
    """16 users, 16 items; ``clicks`` of degree 2..3 and its exact
    transpose, in-degree <= 3."""

    def __init__(self):
        rng = np.random.default_rng(1)
        n = self.n = 16
        ui = [(u, j) for u in range(n)
              for j in {u, (u + 1) % n} | ({(u + 5) % n} if u % 2 == 0
                                            else set())]
        self.topos = {ET_UI: _csr(ui, n),
                      ET_IU: _csr([(j, u) for u, j in ui], n)}
        self.feats = {t: rng.normal(size=(n, DIM)).astype(np.float32)
                      for t in ("user", "item")}
        self.labels = rng.integers(0, CLASSES, n).astype(np.int32)
        self.graphs = {et: Graph(t, mode="HOST")
                       for et, t in self.topos.items()}
        self.sampler = HeteroNeighborSampler(self.graphs, FANOUT, "user",
                                             batch_size=BS, seed=0)
        ets = [reverse_edge_type(et) for et in self.sampler.edge_types]
        self.model = RGNN(ets, hidden_features=8, out_features=CLASSES,
                          target_type="user", num_layers=2, heads=2,
                          dropout_rate=0.0)
        ei, mask = {}, {}
        for et, topo in self.topos.items():
            deg = np.diff(topo.indptr)
            src = np.repeat(np.arange(n), deg)
            ei[reverse_edge_type(et)] = jnp.asarray(
                np.stack([topo.indices, src]), jnp.int32)
            mask[reverse_edge_type(et)] = jnp.ones((src.shape[0],), bool)
        self.whole = ({t: jnp.asarray(f) for t, f in self.feats.items()},
                      ei, mask)
        params = self.model.init({"params": jax.random.PRNGKey(0)},
                                 *self.whole)
        self.state0 = _state(params)

    def reference(self, params, shard_seeds):
        return _plain_loss(self.model.apply(params, *self.whole),
                           self.labels, shard_seeds)

    @functools.cached_property
    def sharded(self):
        mesh = _mesh()
        samp = DistHeteroNeighborSampler(
            shard_hetero_graph(self.topos, N_DEV), mesh, FANOUT, "user",
            batch_size=BS, seed=0)
        return samp, jnp.asarray(self.labels.reshape(N_DEV, -1)), mesh


@pytest.fixture(scope="module")
def homo():
    return Homo()


@pytest.fixture(scope="module")
def typed():
    return Typed()


# -- one harness a factory: run(state, seeds [S, B], key) -> (state, loss,
# acc), the graph it trains on, and what to lower for the scope check. ---
@dataclasses.dataclass
class Harness:
    data: object            # Homo or Typed
    shards: int
    run: object
    lower: object           # () -> the step, lowered
    frozen: object = None   # the parent's arithmetic, where it is kept
    updates: bool = True


def _eager(h):
    step = make_train_step(h.model, TX, BS)

    def run(state, seeds, key):
        return step(state, h.batch(seeds[0], key))

    def frozen(state, seeds, key):
        """The parent's ``make_train_step``: the loss over every row of
        the seed block, the update ungated."""
        batch = h.batch(seeds[0], key)

        @jax.jit
        def parent(state, batch):
            rng = jax.random.fold_in(jax.random.PRNGKey(0), state.step)

            def loss_fn(p):
                logits = h.model.apply(p, batch.x, batch.edge_index,
                                       batch.edge_mask, train=True,
                                       rngs={"dropout": rng})
                return seed_cross_entropy(logits, batch.y, BS,
                                          batch.node_mask)

            (loss, acc), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            updates, opt = TX.update(grads, state.opt_state, state.params)
            return TrainState(optax.apply_updates(state.params, updates),
                              opt, state.step + 1), loss, acc

        return parent(state, batch)

    return Harness(h, 1, run, lambda: step.lower(
        h.state0, h.batch(np.arange(BS), jax.random.PRNGKey(0))), frozen)


def _eval(h):
    step = make_eval_step(h.model, BS)

    def run(state, seeds, key):
        return (state,) + tuple(step(state.params, h.batch(seeds[0], key)))

    return Harness(h, 1, run, lambda: step.lower(
        h.state0.params, h.batch(np.arange(BS), jax.random.PRNGKey(0))),
        updates=False)


def _scanned(h):
    step = make_scanned_node_train_step(
        h.model, TX, h.sampler, Feature(h.feat), h.labels, BS)

    def run(state, seeds, key):
        state, losses, accs, _ = step(state, seeds, key)    # G = 1
        return state, losses[0], accs[0]

    return Harness(h, 1, run, lambda: jax.jit(step).lower(
        h.state0, jnp.zeros((1, BS), jnp.int32), jax.random.PRNGKey(0)))


def _scanned_hetero(t, seed_hops=True):
    step = make_scanned_hetero_train_step(
        t.model, TX, t.sampler, t.feats, {"user": t.labels}, BS,
        seed_hops=seed_hops)

    def run(state, seeds, key):
        state, losses, accs, _ = step(state, seeds, key)
        return state, losses[0], accs[0]

    return Harness(t, 1, run, lambda: jax.jit(step).lower(
        t.state0, jnp.zeros((1, BS), jnp.int32), jax.random.PRNGKey(0)))


def _dist(h):
    g, lab, mesh = h.sharded
    step = make_dist_train_step(h.model, TX, g, shard_feature(h.feat, N_DEV),
                                lab, mesh, FANOUT, BS)
    return Harness(h, N_DEV, lambda s, seeds, k: step(s, jnp.asarray(seeds),
                                                      k),
                   lambda: jax.jit(step).lower(
                       h.state0, jnp.zeros((N_DEV, BS), jnp.int32),
                       jax.random.PRNGKey(0)))


def _scanned_dist(h):
    g, lab, mesh = h.sharded
    step = make_scanned_dist_train_step(
        h.model, TX, g, shard_feature(h.feat, N_DEV), lab, mesh, FANOUT, BS)

    def run(state, seeds, key):
        state, losses, accs = step(state, np.asarray(seeds)[None], key)
        return state, losses[0], accs[0]

    return Harness(h, N_DEV, run, lambda: jax.jit(step).lower(
        h.state0, jnp.zeros((1, N_DEV, BS), jnp.int32),
        jax.random.PRNGKey(0)))


def _frozen_sharded(model, mesh, local_xy, tgt=None):
    """The parent's tiered / hetero-dist / hetero-tiered arithmetic:
    gradients of the loss over every row of the seed block inside a
    ``shard_map``, three ``pmean``s, an ungated update outside it."""
    def local_body(arrays, batch, params, key):
        arrays, batch = jax.tree.map(lambda a: a[0], (arrays, batch))
        key = jax.random.fold_in(key, lax.axis_index("shard"))
        out, x, y, kdrop = local_xy(arrays, batch, key)
        if tgt is None:
            ei, node_mask = jnp.stack([out.row, out.col]), out.node_mask
        else:
            ei = {et: jnp.stack([out.row[et], out.col[et]])
                  for et in out.row}
            node_mask = out.node_mask[tgt]

        def loss_fn(p):
            logits = model.apply(p, x, ei, out.edge_mask, train=True,
                                 rngs={"dropout": kdrop})
            return seed_cross_entropy(logits, y, BS, node_mask)

        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        return (lax.pmean(loss, "shard"), lax.pmean(acc, "shard"),
                lax.pmean(grads, "shard"))

    shard_fn = jax.shard_map(
        local_body, mesh=mesh, in_specs=(P("shard"), P("shard"), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False)

    @jax.jit
    def parent(arrays, state, batch, key):
        loss, acc, grads = shard_fn(arrays, batch, state.params, key)
        updates, opt = TX.update(grads, state.opt_state, state.params)
        return TrainState(optax.apply_updates(state.params, updates), opt,
                          state.step + 1), loss, acc

    return parent


def _tiered(h):
    g, lab, mesh = h.sharded
    f = shard_feature_tiered(h.feat, N_DEV, hot_ratio=0.25)
    sampler = DistNeighborSampler(g, mesh, num_neighbors=FANOUT,
                                  batch_size=BS)
    train = make_tiered_train_step(h.model, TX, g, f, lab, mesh, BS)
    pipe = TieredTrainPipeline(sampler, train, f, mesh)

    def staged_batch(seeds, key):
        out = sampler.sample_from_nodes(jnp.asarray(seeds),
                                        key=jax.random.fold_in(key, 1))
        return out, tuple(pipe._stage_cold_async(out).result())

    def run(state, seeds, key):
        return train(state, *staged_batch(seeds, key), key)

    def local_xy(arrays, batch, key):
        (hot, labels_l), (out, (srows, sslots)) = arrays, batch
        x, y = exchange_gather_xy(
            out.node, hot, labels_l, f.nodes_per_shard, f.num_shards,
            "shard", hot_per_shard=f.hot_per_shard, staged_rows=srows,
            staged_slots=sslots)
        return out, x, jnp.where(out.node >= 0, y, -1), key

    parent = _frozen_sharded(h.model, mesh, local_xy)
    return Harness(
        h, N_DEV, run,
        lambda: jax.jit(train).lower(
            h.state0, *staged_batch(np.zeros((N_DEV, BS), np.int32),
                                    jax.random.PRNGKey(0)),
            jax.random.PRNGKey(0)),
        lambda state, seeds, key: parent((f.hot, lab), state,
                                         staged_batch(seeds, key), key))


def _hetero_dist(t):
    samp, lab, mesh = t.sharded
    feats = {k: shard_feature(v, N_DEV) for k, v in t.feats.items()}
    step = make_hetero_dist_train_step(t.model, TX, samp, feats, lab, mesh,
                                       batch_size=BS)
    arrays = {et: (g.indptr, g.indices, g.edge_ids)
              for et, g in samp.sharded.items()}
    rows = {k: f.rows for k, f in feats.items()}
    c = feats["user"].nodes_per_shard

    def local_xy(arrays_l, seeds, key):
        graph_l, rows_l, labels_l = arrays_l
        kdrop, ksample = jax.random.split(key)
        out = samp.local_sample(graph_l, seeds, ksample)
        x = {"item": exchange_gather(out.node["item"], rows_l["item"], c,
                                     N_DEV, "shard")}
        x["user"], y = exchange_gather_xy(out.node["user"], rows_l["user"],
                                          labels_l, c, N_DEV, "shard")
        return out, x, jnp.where(out.node["user"] >= 0, y, -1), kdrop

    parent = _frozen_sharded(t.model, mesh, local_xy, tgt="user")
    return Harness(
        t, N_DEV, lambda s, seeds, k: step(s, jnp.asarray(seeds), k),
        lambda: jax.jit(step).lower(
            t.state0, jnp.zeros((N_DEV, BS), jnp.int32),
            jax.random.PRNGKey(0)),
        lambda state, seeds, key: parent((arrays, rows, lab), state,
                                         jnp.asarray(seeds), key))


def _hetero_tiered(t):
    samp, lab, mesh = t.sharded
    feats = {"user": shard_feature(t.feats["user"], N_DEV),
             "item": shard_feature_tiered(t.feats["item"], N_DEV,
                                          hot_ratio=0.25)}
    train = make_hetero_tiered_train_step(t.model, TX, samp, feats, lab,
                                          mesh, batch_size=BS)
    pipe = HeteroTieredTrainPipeline(samp, train, feats, mesh)
    fu, fi = feats["user"], feats["item"]

    def staged_batch(seeds, key):
        out = samp.sample_from_nodes(jnp.asarray(seeds),
                                     key=jax.random.fold_in(key, 1))
        return out, pipe._stage_cold_async(out).result()

    def run(state, seeds, key):
        return train(state, *staged_batch(seeds, key), key)

    def local_xy(arrays, batch, key):
        (hot_l, labels_l), (out, staged) = arrays, batch
        x = {"item": exchange_gather_hot(
            out.node["item"], hot_l["item"], fi.nodes_per_shard,
            fi.hot_per_shard, N_DEV, "shard", staged_rows=staged["item"][0],
            staged_slots=staged["item"][1])}
        x["user"], y = exchange_gather_xy(
            out.node["user"], hot_l["user"], labels_l, fu.nodes_per_shard,
            N_DEV, "shard", hot_per_shard=fu.nodes_per_shard)
        return out, x, jnp.where(out.node["user"] >= 0, y, -1), key

    parent = _frozen_sharded(t.model, mesh, local_xy, tgt="user")
    return Harness(
        t, N_DEV, run,
        lambda: jax.jit(train).lower(
            t.state0, *staged_batch(np.zeros((N_DEV, BS), np.int32),
                                    jax.random.PRNGKey(0)),
            jax.random.PRNGKey(0)),
        lambda state, seeds, key: parent(
            ({"user": fu.rows, "item": fi.hot}, lab), state,
            staged_batch(seeds, key), key))


HOMO = {"eager": _eager, "eval": _eval, "scanned": _scanned, "dist": _dist,
        "scanned-dist": _scanned_dist, "tiered": _tiered}
TYPED = {"scanned-hetero": _scanned_hetero, "hetero-dist": _hetero_dist,
         "hetero-tiered": _hetero_tiered}
SUPERVISED = list(HOMO) + list(TYPED)
_built = {}


@pytest.fixture
def harness(request, homo, typed):
    name = request.param
    if name not in _built:
        _built[name] = (HOMO[name](homo) if name in HOMO
                        else TYPED[name](typed))
    return _built[name]


def _own_seeds(h, pattern):
    """``[S, B]`` seeds, shard ``s`` drawing from its own ids by
    ``pattern`` (indices into them; -1 stays padding)."""
    per = h.data.n // h.shards
    pattern = np.asarray(pattern)
    return np.stack([np.where(pattern >= 0, s * per + pattern, -1)
                     for s in range(h.shards)]).astype(np.int32)


@pytest.mark.parametrize("harness", [n for n in SUPERVISED if n != "eval"],
                         indirect=True)
def test_a_fully_padded_batch_moves_nothing(harness):
    h = harness
    state0 = h.data.state0
    seeds = _own_seeds(h, [-1] * BS)
    state, loss, acc = h.run(state0, seeds, jax.random.PRNGKey(1))
    assert float(loss) == 0.0 and float(acc) == 0.0
    assert int(state.step) == 0
    assert _bits_equal(state.params, state0.params)
    assert _bits_equal(state.opt_state, state0.opt_state)
    # and a real batch moves all three
    state, loss, _ = h.run(state0, _own_seeds(h, [0, 1, 2, 3]),
                           jax.random.PRNGKey(1))
    assert int(state.step) == 1 and float(loss) > 0
    assert not _bits_equal(state.params, state0.params)


@pytest.mark.parametrize("harness", SUPERVISED, indirect=True)
def test_the_loss_reads_the_real_seeds_only(harness):
    """A repeated seed and a padding slot leave two unique seeds; rows 2
    and 3 of the node list then hold labelled hop-1 nodes."""
    h = harness
    params = h.data.state0.params
    for pattern in ([2, 0, 2, -1], [1, -1, -1, -1], [3, 2, 1, 0]):
        seeds = _own_seeds(h, pattern)
        _, loss, acc = h.run(h.data.state0, seeds, jax.random.PRNGKey(2))
        want_loss, want_acc = h.data.reference(params, seeds)
        np.testing.assert_allclose(float(loss), want_loss, rtol=2e-5)
        np.testing.assert_allclose(float(acc), want_acc, rtol=1e-6)
    # the rule matters here: the loss over the whole seed block differs
    seeds = _own_seeds(h, [2, 0, 2, -1])
    if h.shards == 1 and isinstance(h.data, Homo):
        b = h.data.batch(seeds[0], jax.random.PRNGKey(2))
        logits = h.data.model.apply(params, b.x, b.edge_index, b.edge_mask)
        block, _ = seed_cross_entropy(logits, b.y, BS, b.node_mask)
        assert abs(float(block) - h.data.reference(params, seeds)[0]) > 1e-3


@pytest.mark.parametrize(
    "harness", ["eager", "tiered", "hetero-dist", "hetero-tiered"],
    indirect=True)
def test_a_full_batch_of_unique_seeds_equals_the_parents_step(harness):
    """The steps that took the real-seed loss, the gate and the update
    scope with this body compute what they computed before, bit for bit,
    wherever the two rules agree."""
    h = harness
    seeds, key = _own_seeds(h, [3, 0, 2, 1]), jax.random.PRNGKey(3)
    state, loss, acc = h.run(h.data.state0, seeds, key)
    # gltlint: disable-next=prng-key-reuse
    want, want_loss, want_acc = h.frozen(h.data.state0, seeds, key)
    assert float(loss) == float(want_loss) and float(acc) == float(want_acc)
    assert int(state.step) == int(want.step) == 1
    assert _bits_equal(state.params, want.params)
    assert _bits_equal(state.opt_state, want.opt_state)


@pytest.mark.parametrize("harness", SUPERVISED, indirect=True)
def test_every_step_carries_the_step_scopes(harness):
    text = harness.lower().as_text(debug_info=True)
    assert "glt.step.loss" in text
    assert ("glt.step.update" in text) == harness.updates


def _link_step(h):
    def loss_fn(z, meta):
        eli = meta["edge_label_index"]
        ok = (eli[0] >= 0) & (eli[1] >= 0)
        s = z[jnp.clip(eli[0], 0, z.shape[0] - 1)]
        d = z[jnp.clip(eli[1], 0, z.shape[0] - 1)]
        return jnp.where(ok, ((s - d) ** 2).sum(-1), 0).sum() / jnp.maximum(
            ok.sum(), 1)

    link = make_scanned_link_train_step(h.model, TX, h.sampler,
                                        Feature(h.feat), loss_fn, group=2)

    def step(params, opt_state, edges, key):
        state, losses, _, _ = link(
            TrainState(params, opt_state, jnp.zeros((), jnp.int32)), edges,
            key)
        return state.params, state.opt_state, losses

    src = np.array([[0, 1, 2, 3], [-1, -1, -1, -1]])
    dst = (src + 1) * (src >= 0) - (src < 0)
    return step, (np.stack([src, dst], axis=1),)


def _subgraph_step(h):
    def loss_fn(z, out, y):
        idx = out.metadata["seed_index"]
        ok = idx >= 0
        zs = z[jnp.clip(idx, 0, z.shape[0] - 1)]
        return jnp.where(ok, ((zs.sum(-1) - y) ** 2), 0).sum() / jnp.maximum(
            ok.sum(), 1)

    step = make_scanned_subgraph_train_step(h.model, TX, h.sampler,
                                            Feature(h.feat), loss_fn,
                                            max_degree=4)
    seeds = np.array([[0, 1, 2, 3], [-1, -1, -1, -1]])
    return step, (seeds, np.ones((2, BS), np.float32))


@pytest.mark.parametrize("build", [_link_step, _subgraph_step],
                         ids=["link", "subgraph"])
def test_link_and_subgraph_steps_share_the_update(homo, build):
    """Their losses are the caller's; the update, its scope and the gate
    are the body's: a block whose second batch is all padding ends where
    the first batch alone leaves it."""
    step, blocks = build(homo)
    p0, o0 = homo.state0.params, homo.state0.opt_state
    key = jax.random.PRNGKey(4)
    text = jax.jit(step).lower(p0, o0, *blocks, key).as_text(
        debug_info=True)
    assert "glt.step.update" in text
    p2, o2, losses = step(p0, o0, *blocks, key)
    assert float(losses[0]) > 0 and float(losses[1]) == 0.0
    assert not _bits_equal(p2, p0)
    # Adam's count says how many updates ran: one, not two
    counts = [int(np.asarray(leaf)) for leaf in jax.tree_util.tree_leaves(o2)
              if np.asarray(leaf).shape == () and
              np.issubdtype(np.asarray(leaf).dtype, np.integer)]
    assert counts == [1]


@pytest.mark.parametrize("seed_hops", [True, False])
def test_a_layout_trims_and_no_layout_builds_the_whole_program(typed,
                                                               seed_hops):
    """``seed_hops=False`` is ``hops=None`` inside: no gauge is set, the
    model runs whole, and the seeds' loss is the trimmed step's."""
    from glt_tpu.obs import metrics

    t = typed
    hops = t.sampler.hop_bounds
    assert hop_trimming(t.model, None) == {}
    assert hop_trimming(GraphSAGE(8, CLASSES), None) == {}
    metrics.reset()
    metrics.enable()
    try:
        h = _scanned_hetero(t, seed_hops=seed_hops)
        snap = metrics.snapshot()
    finally:
        metrics.disable()
        metrics.reset()
    slots = sum(b[-1] for b in hops.edge_bounds.values())
    assert snap["glt.model.edge_slots"] == (slots if seed_hops else 0)
    # by key: a reset registry keeps the gauges of earlier, deeper models
    layers = [snap.get("glt.model.layer_edge_slots{layer=%d}" % l, 0)
              for l in (1, 2)]
    assert all(v > 0 for v in layers) == seed_hops
    if seed_hops:
        assert min(layers) < slots
    seeds = _own_seeds(h, [5, 9, -1, 5])
    _, loss, acc = h.run(t.state0, seeds, jax.random.PRNGKey(5))
    want_loss, want_acc = t.reference(t.state0.params, seeds)
    np.testing.assert_allclose(float(loss), want_loss, rtol=2e-5)
    np.testing.assert_allclose(float(acc), want_acc, rtol=1e-6)
