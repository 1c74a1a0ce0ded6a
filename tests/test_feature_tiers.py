"""The two-tier feature store as the loader path runs it: the tiered
gather against the fully resident one bit for bit, the constructor from
tiers, the order-only hotness sort, the static cold width (no compile
after warm-up, a batch past it served exactly), the loader's early plan,
the spans and counters, and the eager step under the sampler's layout
against the whole model."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu import obs
from glt_tpu.data import (CSRTopo, Dataset, Feature, Graph,
                          calibrate_cold_width, cold_rows_of,
                          in_degree_order, sort_by_in_degree)
from glt_tpu.loader import NeighborLoader
from glt_tpu.models import (GraphSAGE, init_train_state, make_eval_step,
                            make_train_step)
from glt_tpu.obs import compilewatch
from glt_tpu.obs import metrics as registry

N, D = 1200, 12


@pytest.fixture(scope="module")
def table():
    """Rows in hotness order with their ``id2index``, and id batches that
    hold hot, cold, repeated and padding ids."""
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(N, D)).astype(np.float32)
    id2index = rng.permutation(N).astype(np.int32)
    batches = [np.concatenate([rng.integers(0, N, 400), [-1] * 30,
                               [7, 7, 7, N - 1, 0]]).astype(np.int32)
               for _ in range(3)]
    batches.append(np.full((435,), -1, np.int32))           # all padding
    want = [np.asarray(Feature(rows, 1.0, id2index=id2index).gather(b))
            for b in batches]
    return rows, id2index, batches, want


@pytest.mark.parametrize("ratio", [0.0, 0.2, 0.5, 0.8, 1.0])
def test_tiered_gather_equals_the_resident_gather_bit_for_bit(table, ratio):
    rows, id2index, batches, want = table
    feat = Feature(rows, ratio, id2index=id2index)
    assert feat.hot_count == int(N * ratio)
    for ids, w in zip(batches, want):
        np.testing.assert_array_equal(np.asarray(feat.gather(ids)), w)
        # device ids in, as the loader hands them over
        np.testing.assert_array_equal(
            np.asarray(feat.gather(jnp.asarray(ids))), w)
    np.testing.assert_array_equal(feat.cpu_get(batches[0]), want[0])


@pytest.mark.parametrize("width", [8, 64, 160, 435, 4096])
def test_a_static_cold_width_serves_every_batch_exactly(table, width):
    """Narrower than a batch's cold rows (further rounds), about as wide,
    and wider than the batch itself."""
    rows, id2index, batches, want = table
    feat = Feature(rows, 0.5, id2index=id2index)
    feat.set_cold_width(width)
    assert feat.cold_width == width
    for ids, w in zip(batches, want):
        np.testing.assert_array_equal(np.asarray(feat.gather(ids)), w)
    feat.set_cold_width(None)
    np.testing.assert_array_equal(np.asarray(feat.gather(batches[0])),
                                  want[0])
    feat.close()


def test_constructor_from_tiers_equals_the_one_from_a_whole_array(table):
    rows, id2index, batches, want = table
    hot = 500
    feat = Feature.from_tiers(jnp.asarray(rows[:hot]), rows[hot:],
                              jnp.asarray(id2index))
    assert feat.shape == (N, D) and feat.hot_count == hot
    assert feat.split_ratio == pytest.approx(hot / N)
    assert feat._cold is rows[hot:].base or np.shares_memory(feat._cold,
                                                             rows)
    assert feat._host_full is None          # no second copy of the table
    for ids, w in zip(batches, want):
        np.testing.assert_array_equal(np.asarray(feat.gather(ids)), w)
    np.testing.assert_array_equal(feat.cpu_get(batches[1]), want[1])
    plain = Feature.from_tiers(rows[:hot], rows[hot:])       # identity order
    np.testing.assert_array_equal(
        np.asarray(plain.gather(np.array([0, hot, N - 1, -1]))),
        np.concatenate([rows[[0, hot, N - 1]], np.zeros((1, D), np.float32)]))
    with pytest.raises(ValueError, match="one width"):
        Feature.from_tiers(rows[:hot], rows[hot:, :3])
    with pytest.raises(ValueError, match="id2index"):
        Feature.from_tiers(rows[:hot], rows[hot:], id2index[:10])


def _topology(n=300, e=4000, seed=1):
    rng = np.random.default_rng(seed)
    # Skewed destinations with many ties among the low in-degrees.
    dst = (rng.pareto(1.5, e) * 6).astype(np.int64) % n
    return CSRTopo(np.stack([rng.integers(0, n, e), dst]), num_nodes=n)


def test_the_order_alone_equals_sort_by_in_degree_on_host_and_device():
    topo = _topology()
    n = topo.num_nodes
    rows = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    reordered, id2index = sort_by_in_degree(rows, 0.5, topo)
    order, mine = in_degree_order(topo.indices, n)
    np.testing.assert_array_equal(mine, id2index)
    np.testing.assert_array_equal(rows[order], reordered)
    # ties by ascending node id: what the stable sort gives
    deg = topo.in_degrees()
    assert ((np.diff(deg[order]) < 0)
            | ((np.diff(deg[order]) == 0) & (np.diff(order) > 0))).all()
    assert (np.diff(deg[order]) == 0).sum() > 20
    d_order, d_id2index = in_degree_order(
        jnp.asarray(topo.indices, jnp.int32), n)
    assert isinstance(d_id2index, jax.Array)
    np.testing.assert_array_equal(np.asarray(d_order), order)
    np.testing.assert_array_equal(np.asarray(d_id2index), id2index)
    # the shuffle of the hot prefix keeps the pair consistent
    shuffled, s_id2index = sort_by_in_degree(rows, 0.3, topo,
                                             shuffle_ratio=0.2)
    np.testing.assert_array_equal(shuffled[s_id2index], rows)


def test_init_node_features_keeps_its_signature_and_its_results():
    topo = _topology()
    n = topo.num_nodes
    rows = np.random.default_rng(2).normal(size=(n, 5)).astype(np.float32)
    ds = Dataset(graph=Graph(topo, mode="HOST"))
    ds.init_node_features(rows, split_ratio=0.4)
    feat = ds.get_node_feature()
    _, id2index = in_degree_order(topo.indices, n)
    np.testing.assert_array_equal(np.asarray(feat.id2index), id2index)
    assert feat.hot_count == int(n * 0.4)
    ids = np.arange(-1, n)
    np.testing.assert_array_equal(
        np.asarray(feat.gather(ids)),
        np.concatenate([np.zeros((1, 5), np.float32), rows]))


def test_nothing_compiles_after_warm_up_whatever_the_cold_count(table):
    """Batches whose cold counts straddle a power of two (and the static
    width) run the programs ``warm_gather`` has run."""
    rows, id2index, _, _ = table
    feat = Feature(rows, 0.5, id2index=id2index)
    resident = Feature(rows, 1.0, id2index=id2index)
    feat.set_cold_width(128)
    b = 400
    cold_ids = np.flatnonzero(id2index >= feat.hot_count)
    hot_ids = np.flatnonzero(id2index < feat.hot_count)
    want = {}
    for n_cold in (0, 63, 64, 65, 127, 128, 129, 300):
        ids = np.concatenate([cold_ids[:n_cold], hot_ids[: b - n_cold - 10],
                              [-1] * 10]).astype(np.int32)
        want[n_cold] = (ids, np.asarray(resident.gather(ids)))
    compilewatch.install()
    feat.warm_gather(b)
    jax.block_until_ready(jnp.asarray(want[0][0]))
    before = compilewatch.total_compiles()
    for n_cold, (ids, w) in want.items():
        np.testing.assert_array_equal(
            np.asarray(feat.gather(jnp.asarray(ids))), w)
    assert compilewatch.total_compiles() == before
    feat.close()


def test_a_batch_past_the_cold_width_is_served_in_rounds_and_counted(table):
    rows, id2index, batches, want = table
    feat = Feature(rows, 0.25, id2index=id2index)
    ids = batches[0]
    n_cold = int(cold_rows_of(feat, [ids])[0])
    assert n_cold > 2 * 96
    feat.set_cold_width(96)
    rounds = -(-n_cold // 96)
    registry.enable()
    try:
        before = registry.snapshot()
        np.testing.assert_array_equal(np.asarray(feat.gather(ids)), want[0])
        after = registry.snapshot()
    finally:
        registry.disable()
    delta = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("glt.feature.")}
    valid = int((ids >= 0).sum())
    assert delta["glt.feature.cold_rows"] == n_cold
    assert delta["glt.feature.hot_rows"] == valid - n_cold
    assert delta["glt.feature.cold_rows_sent"] == rounds * 96
    assert feat.bytes_from_hbm == (valid - n_cold) * D * 4
    assert after["glt.feature.hot_count"] in (0.0, feat.hot_count)


def test_calibrated_cold_width_is_a_percentile_with_a_margin(table):
    rows, id2index, batches, _ = table
    feat = Feature(rows, 0.5, id2index=id2index)
    counts = cold_rows_of(feat, batches[:3])
    assert counts.shape == (3,) and (counts > 100).all()
    width = calibrate_cold_width(feat, batches[:3], multiple=16)
    assert width % 16 == 0
    assert counts.max() <= width <= 1.06 * counts.max() + 16
    assert calibrate_cold_width(None, None, counts=[10, 12], pct=100,
                                margin=1.0, multiple=1024) == 1024


def _tiered_and_resident(ratio=0.4, cap=None, seed=3):
    """Two datasets over one graph: rows tiered by in-degree, and whole
    in device memory."""
    rng = np.random.default_rng(seed)
    n, e = 2000, 20000
    dst = np.where(rng.random(e) < 0.2,
                   (rng.pareto(1.5, e) * 9).astype(np.int64) % n,
                   rng.integers(0, n, e))      # a few hubs, many ties
    topo = CSRTopo(np.stack([rng.integers(0, n, e), dst]), num_nodes=n)
    rows = rng.normal(size=(n, 6)).astype(np.float32)
    labels = rng.integers(0, 4, n)
    tiered = (Dataset(graph=Graph(topo)).init_node_features(
        rows, split_ratio=ratio).init_node_labels(labels))
    resident = (Dataset(graph=Graph(topo)).init_node_features(rows)
                .init_node_labels(labels))
    return tiered, resident, n


def test_loader_plans_ahead_and_hands_out_the_resident_loaders_batches():
    tiered, resident, n = _tiered_and_resident()
    tiered.get_node_feature().set_cold_width(64)
    args = dict(batch_size=32, shuffle=True, seed=5, node_capacity=400)
    a = NeighborLoader(tiered, [4, 3], np.arange(200), **args)
    b = NeighborLoader(resident, [4, 3], np.arange(200), **args)
    assert tiered.get_node_feature().plans_gathers
    assert not resident.get_node_feature().plans_gathers
    seen = 0
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x.node), np.asarray(y.node))
        np.testing.assert_array_equal(np.asarray(x.x), np.asarray(y.x))
        np.testing.assert_array_equal(np.asarray(x.y), np.asarray(y.y))
        seen += 1
    assert seen == 7 and a.overflow_batches == b.overflow_batches
    # with the cold cache the host resolves the ids: no plan is dispatched
    tiered.get_node_feature().enable_cold_cache(32)
    assert not tiered.get_node_feature().plans_gathers
    first = next(iter(NeighborLoader(tiered, [4, 3], np.arange(200), **args)))
    np.testing.assert_array_equal(
        np.asarray(first.x), np.asarray(resident.get_node_feature().gather(
            first.node)))


def test_the_gathers_spans_nest_inside_the_loaders_collate(tmp_path):
    tiered, _, _ = _tiered_and_resident()
    loader = NeighborLoader(tiered, [4, 3], np.arange(64), batch_size=32)
    next(iter(loader))                          # compile outside the trace
    obs.start_trace()
    for _ in loader:
        pass
    path = str(tmp_path / "trace.json")
    obs.stop_trace(path)
    events = json.load(open(path))["traceEvents"]
    collates = [e for e in events if e["name"] == "loader.collate"]
    assert len(collates) == 2
    for name in ("feature.ids_wait", "feature.cold_fetch",
                 "feature.cold_put"):
        mine = [e for e in events if e["name"] == name]
        assert len(mine) == 2, name
        for e in mine:
            assert any(c["ts"] <= e["ts"] and e["ts"] + e["dur"]
                       <= c["ts"] + c["dur"] + 0.5 for c in collates), name


@pytest.mark.parametrize("node_capacity", [None, 420])
def test_eager_step_with_the_layout_equals_the_step_without(node_capacity):
    """Loss, accuracy and every gradient (plain SGD at rate 1: the
    parameters move by exactly the gradients), in float32, over the
    loader's own batches: capped ones and, with a capacity, the replayed
    full-capacity ones under their own layout."""
    tiered, _, _ = _tiered_and_resident()
    loader = NeighborLoader(tiered, [3, 2, 2], np.arange(300), batch_size=32,
                            shuffle=True, seed=1,
                            node_capacity=node_capacity)
    sampler = loader.sampler
    sib = sampler.full_capacity_sibling()
    layouts = tuple(dict.fromkeys((sampler.hop_bounds, sib.hop_bounds)))
    model = GraphSAGE(hidden_features=16, out_features=4, num_layers=3,
                      dropout_rate=0.0)
    tx = optax.sgd(1.0)
    state = init_train_state(model, tx, 6, jax.random.PRNGKey(0))
    whole = make_train_step(model, tx, 32)
    trimmed = make_train_step(model, tx, 32, hops=layouts)
    ev_whole = make_eval_step(model, 32)
    ev_trimmed = make_eval_step(model, 32, hops=layouts)
    widths = set()
    with jax.default_matmul_precision("highest"):
        for batch in loader:
            widths.add(batch.node.shape[0])
            s0, l0, a0 = whole(state, batch)
            s1, l1, a1 = trimmed(state, batch)
            np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
            assert float(a0) == float(a1)
            for g0, g1 in zip(jax.tree.leaves(s0.params),
                              jax.tree.leaves(s1.params)):
                np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                                           rtol=2e-5, atol=2e-6)
            e0, e1 = ev_whole(state.params, batch), ev_trimmed(state.params,
                                                               batch)
            np.testing.assert_allclose(float(e1[0]), float(e0[0]), rtol=1e-6)
    want = {sampler.node_capacity} if node_capacity is None \
        else {sampler.node_capacity, sib.node_capacity}
    assert widths == want, "no batch overflowed: lower the capacity"
    with pytest.raises(ValueError, match="no layout"):
        make_train_step(model, tx, 32, hops=layouts[:1])(
            state, _other_width(batch))


def _other_width(batch):
    import dataclasses

    return dataclasses.replace(
        batch, node=jnp.concatenate([batch.node, batch.node[:1]]))


def test_an_epochs_trailing_batch_runs_the_compiled_step():
    """70 seeds in batches of 32: the third batch has 6, which is static
    data of the ``Batch`` and must not compile the step again."""
    tiered, _, _ = _tiered_and_resident()
    # (a static cold width, or the gather's own bucket would change)
    tiered.get_node_feature().set_cold_width(256)
    loader = NeighborLoader(tiered, [4, 3], np.arange(70), batch_size=32)
    model = GraphSAGE(hidden_features=8, out_features=4, num_layers=2)
    tx = optax.adam(1e-3)
    state = init_train_state(model, tx, 6, jax.random.PRNGKey(0))
    step = make_train_step(model, tx, 32, hops=loader.sampler.hop_bounds)
    compilewatch.install()
    sizes, before = [], None
    for batch in loader:
        state, loss, _ = step(state, batch)
        jax.block_until_ready(loss)
        sizes.append(batch.batch_size)
        if len(sizes) == 2:
            before = compilewatch.total_compiles()
    assert sizes == [32, 32, 6]
    assert compilewatch.total_compiles() == before
