"""Dedup-aware / cached feature-gather pipeline tests.

Covers the three layers of the bandwidth-oriented rebuild:
  * the tiled block-DMA Pallas kernel (interpret mode) and its XLA plan;
  * :func:`~glt_tpu.ops.dedup_gather.dedup_gather_rows` bit-identity;
  * the cross-batch HBM cache (:mod:`glt_tpu.data.feature_cache`):
    counters, eviction invariants, and bit-identity through the fused /
    scanned train steps and the tiered ``Feature`` path.

The slow-marked microbench smoke test at the bottom is the CI seam for
the kernel: it drives the full dedup+cache gather against the naive
gather on a tiny graph and asserts row-for-row equality plus moving
cache counters, so the A/B plumbing can't silently break.
"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from glt_tpu.data import Dataset, Feature
from glt_tpu.data.feature_cache import (
    cache_gather,
    cache_init,
    cache_lookup,
    cache_stats,
)
from glt_tpu.ops.dedup_gather import dedup_counts, dedup_gather_rows
from glt_tpu.ops.gather_pallas import (
    candidate_gather_params,
    default_gather_params,
    gather_rows_pallas,
)


def _naive(table, ids, id2index=None):
    ids = np.asarray(ids)
    valid = ids >= 0
    idx = np.where(valid, ids, 0)
    if id2index is not None:
        idx = np.asarray(id2index)[idx]
    rows = np.asarray(table)[np.clip(idx, 0, np.asarray(table).shape[0] - 1)]
    return np.where(valid[:, None], rows, 0)


class TestTiledPallasKernel:
    @pytest.mark.parametrize("b,n", [(256, 300), (513, 1000), (1024, 64),
                                     (10, 8)])
    def test_interpret_matches_take(self, b, n):
        rng = np.random.default_rng(b)
        table = jnp.asarray(rng.normal(size=(n, 128)).astype(np.float32))
        idx = jnp.asarray(rng.integers(-2, n, b).astype(np.int32))
        out = np.asarray(gather_rows_pallas(table, idx, interpret=True))
        np.testing.assert_allclose(
            out, np.asarray(table)[np.clip(np.asarray(idx), 0, n - 1)])

    def test_clustered_runs_coalesce(self):
        """Sorted hot-prefix ids (the hotness-reordered batch shape) must
        come back exact — the run-coalescing path of the plan."""
        rng = np.random.default_rng(0)
        table = jnp.asarray(rng.normal(size=(41, 128)).astype(np.float32))
        idx = jnp.asarray(np.sort(rng.integers(0, 40, 512)).astype(np.int32))
        out = np.asarray(gather_rows_pallas(table, idx, interpret=True))
        np.testing.assert_allclose(out, np.asarray(table)[np.asarray(idx)])

    def test_shape_constraints(self):
        table = jnp.zeros((16, 100), jnp.float32)  # d % 128 != 0, != 64
        with pytest.raises(ValueError, match="multiple of 128"):
            gather_rows_pallas(table, jnp.zeros((8,), jnp.int32),
                               interpret=True)
        with pytest.raises(ValueError, match=">= 8"):
            gather_rows_pallas(jnp.zeros((4, 128), jnp.float32),
                               jnp.zeros((8,), jnp.int32), interpret=True)
        # Explicit tile past the table raises (the autotuner prunes
        # these candidates instead of silently shrinking them).
        with pytest.raises(ValueError, match=">= 32"):
            gather_rows_pallas(jnp.zeros((16, 128), jnp.float32),
                               jnp.zeros((8,), jnp.int32), interpret=True,
                               tile_rows=32, ring_depth=4)

    @pytest.mark.parametrize("tile,ring", candidate_gather_params(128))
    @pytest.mark.parametrize("b,n", [(256, 300),     # aligned batch
                                     (1000, 777),    # ragged tail rows
                                     (37, 64)])      # sub-chunk batch
    def test_sweep_candidates_exact(self, tile, ring, b, n):
        """Every (tile_rows, ring_depth) point the autotuner can select
        must be bit-exact on ragged tails and random id patterns —
        autotune may pick ANY of these, so all of them are contract."""
        if n < tile:
            pytest.skip("table shorter than tile (autotune prunes)")
        rng = np.random.default_rng(tile * 1000 + ring * 100 + b)
        table = jnp.asarray(rng.normal(size=(n, 128)).astype(np.float32))
        idx = jnp.asarray(rng.integers(-2, n, b).astype(np.int32))
        out = np.asarray(gather_rows_pallas(table, idx, interpret=True,
                                            tile_rows=tile,
                                            ring_depth=ring))
        assert (out == np.asarray(table)[
            np.clip(np.asarray(idx), 0, n - 1)]).all()

    @pytest.mark.parametrize("tile,ring", [(8, 4), (32, 8)])
    def test_all_duplicate_ids(self, tile, ring):
        """An all-duplicate batch (one hub id repeated) collapses to a
        single DMA per chunk — the degenerate coalescing case."""
        rng = np.random.default_rng(5)
        table = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
        idx = jnp.full((513,), 7, jnp.int32)
        out = np.asarray(gather_rows_pallas(table, idx, interpret=True,
                                            tile_rows=tile,
                                            ring_depth=ring))
        assert (out == np.asarray(table)[7]).all()

    @pytest.mark.parametrize("d", [64, 256])
    def test_width_specialized_variants(self, d):
        """d=256 runs natively; d=64 runs through the paired-row view
        ([N/2, 128] tiles + epilogue half-select) — both bit-exact."""
        rng = np.random.default_rng(d)
        n, b = 200, 143
        table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        idx = jnp.asarray(rng.integers(-1, n, b).astype(np.int32))
        out = np.asarray(gather_rows_pallas(table, idx, interpret=True,
                                            tile_rows=8, ring_depth=4))
        assert (out == np.asarray(table)[
            np.clip(np.asarray(idx), 0, n - 1)]).all()

    def test_d64_needs_even_rows(self):
        with pytest.raises(ValueError, match="even"):
            gather_rows_pallas(jnp.zeros((33, 64), jnp.float32),
                               jnp.zeros((8,), jnp.int32), interpret=True)

    def test_width_specialized_defaults(self):
        """Defaults hold DMA byte depth roughly constant across widths
        (~16KB) and respect dtype sublane minimums."""
        t64, _ = default_gather_params(64)
        t128, _ = default_gather_params(128)
        t256, _ = default_gather_params(256)
        assert t64 >= t128 >= t256 >= 8
        tb16, _ = default_gather_params(128, jnp.bfloat16)
        assert tb16 >= 16          # bf16 sublane minimum
        assert all(t >= 16 for t, _ in
                   candidate_gather_params(128, jnp.bfloat16))


class TestAutotuneTable:
    def test_keyed_by_exact_shape(self):
        """The decision table keys include the exact batch size: an
        occupancy-capped gather shape gets its OWN entry instead of
        inheriting the full-cap winner.  Off-TPU both pin 'xla' with an
        empty sweep."""
        from glt_tpu.ops import gather_pallas as gp

        gp.reset_autotune()
        try:
            table = jnp.zeros((64, 128), jnp.float32)
            full = jnp.zeros((512,), jnp.int32)
            capped = jnp.zeros((256,), jnp.int32)
            assert gp.autotune_gather_rows(table, full) == "xla"
            assert gp.autotune_gather_rows(table, capped) == "xla"
            tab = gp.autotune_table()
            assert "d128_b512_float32" in tab
            assert "d128_b256_float32" in tab
            assert tab["d128_b512_float32"]["winner"] == "xla"
        finally:
            gp.reset_autotune()

    def test_gather_rows_follows_winner_params(self, monkeypatch):
        """gather_rows(force='auto') must dispatch the memoized
        (tile_rows, ring_depth) point for its exact shape."""
        from glt_tpu.ops import gather_pallas as gp

        calls = {}

        def fake_pallas(table, idx, tile_rows=None, ring_depth=None):
            calls["params"] = (tile_rows, ring_depth)
            return jnp.take(table, jnp.clip(idx, 0, table.shape[0] - 1),
                            axis=0)

        monkeypatch.setattr(gp, "gather_rows_pallas", fake_pallas)
        gp.reset_autotune()
        try:
            table = jnp.zeros((64, 128), jnp.float32)
            idx = jnp.zeros((256,), jnp.int32)
            gp._AUTO[gp._auto_key(table, idx)] = (16, 4)
            gp.gather_rows(table, idx, force="auto")
            assert calls["params"] == (16, 4)
            # A DIFFERENT batch size has no entry -> XLA fallback, the
            # fake kernel must not be touched.
            calls.clear()
            gp.gather_rows(table, jnp.zeros((128,), jnp.int32),
                           force="auto")
            assert calls == {}
        finally:
            gp.reset_autotune()


class TestDedupGather:
    def test_bit_identical_to_naive(self):
        rng = np.random.default_rng(3)
        table = jnp.asarray(rng.normal(size=(30, 5)).astype(np.float32))
        ids = jnp.asarray(rng.integers(-3, 30, 64).astype(np.int32))
        got = np.asarray(jax.jit(dedup_gather_rows)(table, ids))
        assert (got == _naive(table, ids)).all()   # bit-identical, not close

    def test_with_id2index(self):
        rng = np.random.default_rng(4)
        table = jnp.asarray(rng.normal(size=(20, 3)).astype(np.float32))
        perm = jnp.asarray(rng.permutation(20).astype(np.int32))
        ids = jnp.asarray(rng.integers(-1, 20, 33).astype(np.int32))
        got = np.asarray(dedup_gather_rows(table, ids, id2index=perm))
        assert (got == _naive(table, ids, perm)).all()

    def test_counts(self):
        v, u = dedup_counts(jnp.array([5, 5, 5, -1, 2, 2, -1]))
        assert int(v) == 5 and int(u) == 2


class TestFeatureCache:
    def _fetch(self, backing):
        def fetch(ids):
            v = ids >= 0
            return jnp.where(
                v[:, None], jnp.take(backing, jnp.where(v, ids, 0),
                                     axis=0, mode="clip"), 0)
        return fetch

    def test_counters_and_rows(self):
        rng = np.random.default_rng(0)
        backing = jnp.asarray(rng.normal(size=(50, 4)).astype(np.float32))
        fetch = self._fetch(backing)
        run = jax.jit(lambda s, i: cache_gather(s, i, fetch))
        st = cache_init(50, 8, 4)
        ids1 = jnp.array([3, 7, 9, -1], jnp.int32)
        st, rows = run(st, ids1)
        assert (np.asarray(rows) == np.asarray(fetch(ids1))).all()
        s = cache_stats(st)
        assert (s["hits"], s["misses"], s["resident"]) == (0, 3, 3)
        st, rows = run(st, jnp.array([7, 9, 20, -1], jnp.int32))
        s = cache_stats(st)
        assert (s["hits"], s["misses"]) == (2, 4)

    def test_eviction_invariants(self):
        """After arbitrary churn: every resident id's cached row matches
        the backing store, id2slot agrees with slot_ids both ways, and
        non-resident ids map to -1."""
        rng = np.random.default_rng(1)
        backing = jnp.asarray(rng.normal(size=(40, 3)).astype(np.float32))
        fetch = self._fetch(backing)
        run = jax.jit(lambda s, i: cache_gather(s, i, fetch))
        st = cache_init(40, 6, 3)
        for _ in range(12):
            ids = np.unique(rng.integers(0, 40, 5)).astype(np.int32)
            ids = np.pad(ids, (0, 8 - ids.shape[0]), constant_values=-1)
            st, rows = run(st, jnp.asarray(ids))
            assert (np.asarray(rows)
                    == np.asarray(fetch(jnp.asarray(ids)))).all()
        slot_ids = np.asarray(st.slot_ids[:-1])
        table = np.asarray(st.table[:-1])
        id2slot = np.asarray(st.id2slot[:-2])
        for sl, i in enumerate(slot_ids):
            if i >= 0:
                np.testing.assert_array_equal(table[sl],
                                              np.asarray(backing)[i])
                assert id2slot[i] == sl
        resident = set(slot_ids[slot_ids >= 0].tolist())
        for i in range(40):
            if i not in resident:
                assert id2slot[i] == -1
        s = cache_stats(st)
        assert s["resident"] == 6 and s["lookups"] == s["hits"] + s["misses"]

    def test_overflowing_insert_keeps_rows_exact(self):
        backing = jnp.asarray(np.arange(60, dtype=np.float32).reshape(20, 3))
        fetch = self._fetch(backing)
        st = cache_init(20, 4, 3)
        ids = jnp.asarray(np.arange(10), jnp.int32)
        st, rows = jax.jit(lambda s, i: cache_gather(s, i, fetch))(st, ids)
        assert (np.asarray(rows) == np.asarray(fetch(ids))).all()
        assert cache_stats(st)["resident"] == 4

    def test_lookup_is_readonly(self):
        st = cache_init(10, 2, 3)
        rows, hit = cache_lookup(st, jnp.array([1, -1], jnp.int32))
        assert not bool(hit.any()) and (np.asarray(rows) == 0).all()


def _tiny_dataset(n=48, dim=8, classes=3, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    labels = np.arange(n) % classes
    src, dst = [], []
    for c in range(classes):
        members = np.where(labels == c)[0]
        for i in members:
            for j in rng.choice(members, size=3, replace=False):
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


class TestTrainStepIntegration:
    def test_scanned_step_dedup_and_cache_match_baseline(self):
        """One scanned program per variant, same seeds/keys: the dedup
        and dedup+cache gathers must reproduce the baseline losses
        EXACTLY (their x is bit-identical)."""
        from glt_tpu.models import (
            GraphSAGE,
            TrainState,
            make_scanned_node_train_step,
        )
        from glt_tpu.sampler import NeighborSampler

        ds, labels = _tiny_dataset()
        model = GraphSAGE(hidden_features=8, out_features=3, num_layers=2,
                          dropout_rate=0.0)
        tx = optax.adam(1e-2)
        bs, G = 8, 2
        sampler = NeighborSampler(ds.get_graph(), [3, 3], batch_size=bs,
                                  with_edge=False)
        feat = ds.get_node_feature()
        x0 = jnp.zeros((sampler.node_capacity, feat.shape[1]), jnp.float32)
        ei0 = jnp.full((2, sampler.edge_capacity), -1, jnp.int32)
        m0 = jnp.zeros((sampler.edge_capacity,), bool)
        params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)

        def fresh():
            return TrainState(params=params, opt_state=tx.init(params),
                              step=jnp.zeros((), jnp.int32))

        blocks = [np.arange(i * bs * G, (i + 1) * bs * G)
                  .reshape(G, bs).astype(np.int32) for i in range(2)]
        key = jax.random.PRNGKey(7)

        def run(**kw):
            step = make_scanned_node_train_step(model, tx, sampler, feat,
                                                labels, bs, **kw)
            st = fresh()
            losses = []
            for i, blk in enumerate(blocks):
                st, ls, _, _ = step(st, jnp.asarray(blk),
                                    jax.random.fold_in(key, i))
                losses += [float(l) for l in ls]
            return losses, step

        base, _ = run()
        dedup, _ = run(dedup=True)
        assert dedup == base
        cache = cache_init(feat.size, 32, feat.shape[1], jnp.float32)
        cached, step = run(feature_cache=cache)
        assert cached == base
        stats = cache_stats(step.feature_cache())
        assert stats["lookups"] > 0 and stats["misses"] > 0

    def test_cache_dtype_mismatch_rejected(self):
        from glt_tpu.models import GraphSAGE, make_scanned_node_train_step
        from glt_tpu.sampler import NeighborSampler

        ds, labels = _tiny_dataset()
        sampler = NeighborSampler(ds.get_graph(), [3], batch_size=4,
                                  with_edge=False)
        feat = ds.get_node_feature()
        bad = cache_init(feat.size, 8, feat.shape[1], jnp.bfloat16)
        with pytest.raises(ValueError, match="dtype"):
            make_scanned_node_train_step(
                GraphSAGE(hidden_features=4, out_features=3, num_layers=1),
                optax.sgd(1e-2), sampler, feat, labels, 4,
                feature_cache=bad)


class TestTieredColdCache:
    def test_cached_tiered_matches_uncached(self):
        rng = np.random.default_rng(5)
        arr = rng.normal(size=(64, 6)).astype(np.float32)
        plain = Feature(arr, split_ratio=0.25)
        cached = Feature(arr, split_ratio=0.25)
        cached.enable_cold_cache(capacity=8)
        for seed in range(4):
            ids = np.random.default_rng(seed).integers(-2, 64, 24)
            a = np.asarray(plain.gather(ids))
            b = np.asarray(cached.gather(ids))
            np.testing.assert_array_equal(a, b)
        s = cached.cache_stats()
        assert s["lookups"] > 0 and s["hits"] > 0   # cross-batch reuse

    def test_cache_without_cold_tier_warns_and_noops(self):
        # All-hot features have nothing to cache: warn + no-op (the old
        # ValueError punished harness code that sets one ratio for a
        # sweep); gathers stay exact.  tests/test_feature.py covers the
        # companion capacity-clamp path.
        f = Feature(np.ones((4, 2), np.float32), split_ratio=1.0)
        with pytest.warns(RuntimeWarning, match="no-op at split_ratio"):
            f.enable_cold_cache(4)
        assert f._cache is None
        np.testing.assert_array_equal(
            np.asarray(f.gather(np.array([0, 3]))), np.ones((2, 2)))


@pytest.mark.slow
def test_microbench_dedup_cache_smoke():
    """CI seam for the kernel/dedup/cache plumbing: on a tiny power-law
    graph, the dedup+cache gather must equal the naive gather row-for-row
    over an epoch of sampled batches, cache counters must move, and the
    dedup ratio must be sane.  Timing is collected but NOT asserted
    (CPU-under-CI jitter) — the point is that the full A/B harness runs.
    """
    import time

    from glt_tpu.models.train import make_cached_gather_xy, make_gather_xy
    from glt_tpu.sampler import NeighborSampler
    from glt_tpu.sampler.base import NodeSamplerInput

    rng = np.random.default_rng(0)
    n, dim = 512, 16
    # Power-law-ish degrees: hubs repeat across sampled neighborhoods.
    deg = np.clip(rng.zipf(1.5, n), 1, 64)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, 64, src.shape[0])  # hubs = low ids
    ds = (Dataset()
          .init_graph(np.stack([src, dst]), graph_mode="HOST", num_nodes=n)
          .init_node_features(rng.normal(size=(n, dim)).astype(np.float32))
          .init_node_labels((np.arange(n) % 5).astype(np.int32)))
    feat = ds.get_node_feature()
    labels = jnp.asarray(np.asarray(ds.get_node_label()))
    # last_hop_dedup=False leaves duplicated hub leaves in the node list
    # — the workload dedup-gather exists for.
    sampler = NeighborSampler(ds.get_graph(), [4, 4], batch_size=32,
                              with_edge=False, last_hop_dedup=False)

    naive = jax.jit(make_gather_xy(feat.id2index))
    dedup = jax.jit(make_gather_xy(feat.id2index, dedup=True))
    cached_xy = jax.jit(make_cached_gather_xy(feat.id2index))
    cache = cache_init(feat.size, 128, dim, jnp.float32)

    outs = [sampler.sample_from_nodes(
        NodeSamplerInput(rng.integers(0, n, 32).astype(np.int32)),
        key=jax.random.PRNGKey(i)) for i in range(6)]

    dup_tot, uniq_tot = 0, 0
    t_naive = t_dedup = 0.0
    for out in outs:
        t0 = time.perf_counter()
        x0, y0 = naive(feat.hot_rows, labels, out)
        x0.block_until_ready()
        t_naive += time.perf_counter() - t0
        t0 = time.perf_counter()
        x1, y1 = dedup(feat.hot_rows, labels, out)
        x1.block_until_ready()
        t_dedup += time.perf_counter() - t0
        cache, x2, y2 = cached_xy(cache, feat.hot_rows, labels, out)
        # Row-for-row equality across all three paths.
        assert (np.asarray(x1) == np.asarray(x0)).all()
        assert (np.asarray(x2) == np.asarray(x0)).all()
        assert (np.asarray(y1) == np.asarray(y0)).all()
        assert (np.asarray(y2) == np.asarray(y0)).all()
        v, u = dedup_counts(out.node)
        dup_tot += int(v)
        uniq_tot += int(u)

    assert uniq_tot < dup_tot          # the workload really duplicates
    stats = cache_stats(cache)
    assert stats["misses"] > 0
    assert stats["hits"] > 0           # cross-batch reuse through the cache
    assert stats["lookups"] == stats["hits"] + stats["misses"]
