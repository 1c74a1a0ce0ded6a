"""Routing-layer equivalence suite (sort-free routing + fused collectives).

Everything here is a bit-identity check on the virtual 8-device CPU mesh
(conftest forces ``XLA_FLAGS=--xla_force_host_platform_device_count=8``):

* one-pass cumulative-mask bucketing == stable-sort bucketing, field for
  field, including capacity-bounded overflow;
* fused collectives (packed neighbor+edge-id response, fused
  feature+label payload) == the split launches;
* a routing plan built once via ``build_routing`` and reused across
  exchanges == per-exchange rebucketing;

for the homo, hetero, and capped (``remote_cap``) paths.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from glt_tpu.data.topology import CSRTopo
from glt_tpu.parallel import (
    DistNeighborSampler,
    build_routing,
    exchange_gather,
    exchange_gather_xy,
    shard_feature,
    shard_graph,
)
from glt_tpu.parallel.dist_sampler import (
    _bucket_by_owner_onepass,
    _bucket_by_owner_sort,
    _route_choice,
    _use_fused,
)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:N_DEV])
    return Mesh(devs, ("shard",))


def ring_topo(n):
    src = np.repeat(np.arange(n), 2)
    dst = np.concatenate([[(i + 1) % n, (i + 2) % n] for i in range(n)])
    return CSRTopo(np.stack([src, dst]), num_nodes=n)


def _assert_trees_equal(a, b):
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestBucketEquivalence:
    """One-pass per-owner rank == stable-sort rank, all Routing fields."""

    @pytest.mark.parametrize("b,num_shards,cap", [
        (16, 1, 16), (16, 4, 16), (64, 8, 64),
        (64, 8, 3),            # capacity-bounded: overflow + drops
        (32, 5, 1),            # non-power-of-two owners, tiny cap
    ])
    def test_random_ids(self, b, num_shards, cap):
        rng = np.random.default_rng(b * 31 + num_shards)
        ids = rng.integers(0, num_shards * 10, b).astype(np.int32)
        ids[rng.random(b) < 0.2] = -1   # padding mixed in
        owner = np.where(ids >= 0, ids // 10, -1).astype(np.int32)
        s = jax.jit(lambda i, o: _bucket_by_owner_sort(
            i, o, num_shards, cap))(ids, owner)
        p = jax.jit(lambda i, o: _bucket_by_owner_onepass(
            i, o, num_shards, cap))(ids, owner)
        _assert_trees_equal(s, p)

    def test_adversarial_single_owner(self):
        """Every id owned by one shard: max rank pressure + overflow."""
        b, num_shards, cap = 32, 8, 4
        ids = np.arange(30, 30 + b).astype(np.int32) % 10 + 30
        owner = np.full((b,), 3, np.int32)
        s = _bucket_by_owner_sort(jnp.asarray(ids), jnp.asarray(owner),
                                  num_shards, cap)
        p = _bucket_by_owner_onepass(jnp.asarray(ids), jnp.asarray(owner),
                                     num_shards, cap)
        _assert_trees_equal(s, p)
        assert int(s.dropped) == b - cap


class TestRoutePathsBitIdentical:
    """Full sampler programs, sort vs one-pass routing (homo + hetero +
    capped): the A/B seam must be invisible in the outputs."""

    def _seeds(self, n):
        seeds = np.zeros((N_DEV, 4), np.int32)
        for s in range(N_DEV):
            seeds[s] = [(s * 8 + 17 + k * 9) % n for k in range(4)]
        return seeds

    @pytest.mark.parametrize("alpha", [None, 2.0])
    def test_homo(self, mesh, alpha):
        n = 64
        sg = shard_graph(ring_topo(n), N_DEV)
        seeds = jnp.asarray(self._seeds(n))
        key = jax.random.PRNGKey(5)
        outs = {}
        for route in ("sort", "onepass"):
            samp = DistNeighborSampler(sg, mesh, num_neighbors=[2, 2],
                                       batch_size=4, seed=0, route=route,
                                       exchange_load_factor=alpha)
            outs[route] = samp.sample_from_nodes(seeds, key=key)
        _assert_trees_equal(outs["sort"], outs["onepass"])

    def test_hetero(self, mesh):
        from glt_tpu.parallel.dist_hetero_sampler import (
            DistHeteroNeighborSampler, shard_hetero_graph)

        U, I = 32, 16
        ET_UI = ("user", "clicks", "item")
        ET_IU = ("item", "rev_clicks", "user")
        u_src = np.repeat(np.arange(U), 2)
        i_dst = np.concatenate([[u % I, (u + 1) % I] for u in range(U)])
        topos = {
            ET_UI: CSRTopo(np.stack([u_src, i_dst]), num_nodes=U),
            ET_IU: CSRTopo(np.stack([i_dst, u_src]), num_nodes=I),
        }
        sharded = shard_hetero_graph(topos, N_DEV)
        seeds = jnp.asarray(np.stack([[s * 4, s * 4 + 3]
                                      for s in range(N_DEV)])
                            .astype(np.int32))
        key = jax.random.PRNGKey(9)
        outs = {}
        for route in ("sort", "onepass"):
            samp = DistHeteroNeighborSampler(sharded, mesh, [2, 2], "user",
                                             batch_size=2, route=route)
            outs[route] = samp.sample_from_nodes(seeds, key=key)
        _assert_trees_equal(outs["sort"], outs["onepass"])


class TestFusedEqualsSplit:
    """Packed collectives == split collectives, bit for bit."""

    def _seeds(self, n):
        seeds = np.zeros((N_DEV, 4), np.int32)
        for s in range(N_DEV):
            seeds[s] = [(s * 8 + 5 + k * 11) % n for k in range(4)]
        return seeds

    @pytest.mark.parametrize("alpha", [None, 2.0])
    def test_homo(self, mesh, alpha):
        n = 64
        sg = shard_graph(ring_topo(n), N_DEV)
        seeds = jnp.asarray(self._seeds(n))
        key = jax.random.PRNGKey(2)
        outs = {}
        for fused in (True, False):
            samp = DistNeighborSampler(sg, mesh, num_neighbors=[2, 2],
                                       batch_size=4, seed=0, fused=fused,
                                       exchange_load_factor=alpha)
            outs[fused] = samp.sample_from_nodes(seeds, key=key)
        _assert_trees_equal(outs[True], outs[False])

    def test_homo_ring(self, mesh):
        n = 64
        sg = shard_graph(ring_topo(n), N_DEV)
        seeds = jnp.asarray(self._seeds(n))
        key = jax.random.PRNGKey(3)
        outs = {}
        for fused in (True, False):
            samp = DistNeighborSampler(sg, mesh, num_neighbors=[2],
                                       batch_size=4, seed=0, fused=fused,
                                       collective="ring")
            outs[fused] = samp.sample_from_nodes(seeds, key=key)
        _assert_trees_equal(outs[True], outs[False])

    def test_hetero(self, mesh):
        from glt_tpu.parallel.dist_hetero_sampler import (
            DistHeteroNeighborSampler, shard_hetero_graph)

        U, I = 32, 16
        ET_UI = ("user", "clicks", "item")
        ET_IU = ("item", "rev_clicks", "user")
        u_src = np.repeat(np.arange(U), 2)
        i_dst = np.concatenate([[u % I, (u + 1) % I] for u in range(U)])
        topos = {
            ET_UI: CSRTopo(np.stack([u_src, i_dst]), num_nodes=U),
            ET_IU: CSRTopo(np.stack([i_dst, u_src]), num_nodes=I),
        }
        sharded = shard_hetero_graph(topos, N_DEV)
        seeds = jnp.asarray(np.stack([[s * 4, s * 4 + 3]
                                      for s in range(N_DEV)])
                            .astype(np.int32))
        key = jax.random.PRNGKey(4)
        outs = {}
        for fused in (True, False):
            samp = DistHeteroNeighborSampler(
                sharded, mesh, [2, 2], "user", batch_size=2, fused=fused,
                exchange_load_factor=2.0)
            outs[fused] = samp.sample_from_nodes(seeds, key=key)
        _assert_trees_equal(outs[True], outs[False])

    def test_subgraph(self, mesh):
        n = 64
        sg = shard_graph(ring_topo(n), N_DEV)
        seeds = jnp.asarray(np.stack([
            [(s * 8 + k * 17) % n for k in range(3)]
            for s in range(N_DEV)]).astype(np.int32))
        key = jax.random.PRNGKey(6)
        outs = {}
        for fused in (True, False):
            samp = DistNeighborSampler(sg, mesh, num_neighbors=[2],
                                       batch_size=3, seed=11, fused=fused)
            outs[fused] = samp.subgraph(seeds, max_degree=4, key=key)
        _assert_trees_equal(outs[True], outs[False])


class TestSharedRouting:
    """build_routing plan reuse and the fused feature+label exchange."""

    def _fixture(self):
        n, d = 64, 4
        rng = np.random.default_rng(7)
        feat = rng.normal(0, 1, (n, d)).astype(np.float32)
        sf = shard_feature(feat, N_DEV)
        # Labels with extreme int32 values: the fused payload bitcasts
        # them through float32, which must round-trip ANY bit pattern.
        labels = rng.integers(-2**31 + 1, 2**31 - 1, n, dtype=np.int64)
        labels[:8] = [0, 1, -1, 7, 2**30, -2**30, 2**31 - 1, -2**31 + 1]
        lab = jnp.asarray(labels.astype(np.int32)
                          .reshape(N_DEV, sf.nodes_per_shard))
        ids = np.zeros((N_DEV, 7), np.int32)
        for s in range(N_DEV):
            ids[s] = [(s * 11 + k * 13) % n for k in range(6)] + [s * 8]
        ids[0, 5] = -1                  # padding
        ids[1, 4] = ids[1, 3]           # duplicate (dedup path)
        return sf, lab, jnp.asarray(ids)

    def test_prebuilt_routing_reused(self, mesh):
        sf, _, ids = self._fixture()
        gspec = P("shard")

        def body(rows_blk, ids_blk):
            ids_l, rows_l = ids_blk[0], rows_blk[0]
            r = build_routing(ids_l, sf.nodes_per_shard, N_DEV)
            a = exchange_gather(ids_l, rows_l, sf.nodes_per_shard, N_DEV,
                                "shard", routing=r)
            b = exchange_gather(ids_l, rows_l, sf.nodes_per_shard, N_DEV,
                                "shard")
            return a[None], b[None]

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(gspec, gspec),
                                   out_specs=(gspec, gspec),
                                   check_vma=False))
        a, b = fn(sf.rows, ids)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("shared_plan", [True, False])
    @pytest.mark.parametrize("dedup", [False, True])
    def test_exchange_gather_xy_matches_separate(self, mesh, shared_plan,
                                                 dedup):
        """With a plan built outside or not (a passed plan is ignored
        under ``dedup``), dedup on or off."""
        sf, lab, ids = self._fixture()
        gspec = P("shard")

        def body(rows_blk, lab_blk, ids_blk):
            ids_l, rows_l, lab_l = ids_blk[0], rows_blk[0], lab_blk[0]
            plan = (build_routing(ids_l, sf.nodes_per_shard, N_DEV)
                    if shared_plan else None)
            x, y = exchange_gather_xy(ids_l, rows_l, lab_l,
                                      sf.nodes_per_shard, N_DEV, "shard",
                                      dedup=dedup, routing=plan)
            xs = exchange_gather(ids_l, rows_l, sf.nodes_per_shard, N_DEV,
                                 "shard")
            ys = exchange_gather(ids_l, lab_l[:, None].astype(jnp.int32),
                                 sf.nodes_per_shard, N_DEV, "shard")[:, 0]
            return x[None], y[None], xs[None], ys[None]

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(gspec, gspec, gspec),
            out_specs=(gspec,) * 4, check_vma=False))
        x, y, xs, ys = fn(sf.rows, lab, ids)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(xs))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ys))


class TestSeamResolution:
    """Env overrides and the auto heuristic (no mesh needed)."""

    def test_route_choice(self, monkeypatch):
        monkeypatch.delenv("GLT_ROUTE_FORCE", raising=False)
        assert _route_choice(13, 4, 13, "sort") == "sort"
        assert _route_choice(13, 4, 13, "onepass") == "onepass"
        assert _route_choice(13, 4, 13, "auto") == "onepass"   # small S
        assert _route_choice(13, 64, 13, "auto") == "sort"     # big S
        monkeypatch.setenv("GLT_ROUTE_FORCE", "sort")
        assert _route_choice(13, 4, 13, "onepass") == "sort"
        monkeypatch.setenv("GLT_ROUTE_FORCE", "onepass")
        assert _route_choice(13, 64, 13, "sort") == "onepass"

    def test_fused_choice(self, monkeypatch):
        monkeypatch.delenv("GLT_COLLECTIVE_FORCE", raising=False)
        assert _use_fused(None) is True
        assert _use_fused(False) is False
        monkeypatch.setenv("GLT_COLLECTIVE_FORCE", "split")
        assert _use_fused(None) is False
        assert _use_fused(True) is False
        monkeypatch.setenv("GLT_COLLECTIVE_FORCE", "fused")
        assert _use_fused(False) is True
