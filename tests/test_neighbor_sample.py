import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.data import CSRTopo
from glt_tpu.ops import lookup_degrees, sample_neighbors


def _chain_graph():
    # 0 -> {1,2,3,4,5}; 1 -> {2,3}; 2 -> {}; 3 -> {0}
    row = np.array([0, 0, 0, 0, 0, 1, 1, 3])
    col = np.array([1, 2, 3, 4, 5, 2, 3, 0])
    return CSRTopo(np.stack([row, col]), num_nodes=6)


def test_full_row_when_degree_leq_fanout():
    t = _chain_graph()
    out = sample_neighbors(
        jnp.asarray(t.indptr), jnp.asarray(t.indices),
        jnp.array([1, 2, 3], jnp.int32), fanout=4, key=jax.random.key(0),
        edge_ids=jnp.asarray(t.edge_ids),
    )
    nbrs = np.asarray(out.nbrs)
    mask = np.asarray(out.mask)
    # deg <= fanout: the full (untruncated) neighbor list in CSR order.
    assert nbrs[0, :2].tolist() == [2, 3] and not mask[0, 2:].any()
    assert not mask[1].any() and (nbrs[1] == -1).all()
    assert nbrs[2, 0] == 0 and not mask[2, 1:].any()
    # Edge ids point at the right global edges.
    eids = np.asarray(out.eids)
    assert eids[0, :2].tolist() == [5, 6]
    assert eids[2, 0] == 7


@pytest.mark.parametrize("with_replacement", [False, True])
def test_sampled_neighbors_are_real_edges(with_replacement):
    rng = np.random.default_rng(3)
    n, e = 64, 1024
    row, col = rng.integers(0, n, e), rng.integers(0, n, e)
    t = CSRTopo(np.stack([row, col]), num_nodes=n)
    adj = {i: set() for i in range(n)}
    for r, c in zip(row, col):
        adj[r].add(c)
    seeds = jnp.asarray(rng.integers(0, n, 32), jnp.int32)
    out = sample_neighbors(
        jnp.asarray(t.indptr), jnp.asarray(t.indices), seeds, fanout=5,
        key=jax.random.key(7), with_replacement=with_replacement,
    )
    nbrs, mask = np.asarray(out.nbrs), np.asarray(out.mask)
    for i, s in enumerate(np.asarray(seeds)):
        deg = len(np.where(row == s)[0])
        expected_valid = min(deg, 5) if not with_replacement else (5 if deg else 0)
        assert mask[i].sum() == expected_valid
        for k in range(5):
            if mask[i, k]:
                assert nbrs[i, k] in adj[int(s)]
            else:
                assert nbrs[i, k] == -1


def test_without_replacement_has_no_duplicate_positions():
    # A node with degree 100, fanout 10: sampled edge ids must be distinct.
    row = np.zeros(100, dtype=np.int64)
    col = np.arange(100, dtype=np.int64)
    t = CSRTopo(np.stack([row, col]), num_nodes=101)
    seeds = jnp.zeros((16,), jnp.int32)
    out = sample_neighbors(
        jnp.asarray(t.indptr), jnp.asarray(t.indices), seeds, fanout=10,
        key=jax.random.key(11),
    )
    eids = np.asarray(out.eids)
    for i in range(16):
        assert len(set(eids[i].tolist())) == 10, eids[i]


def test_floyd_uniformity():
    # Every neighbor of a deg-8 node should be picked roughly equally when
    # sampling 4 of 8 across many keys.
    row = np.zeros(8, dtype=np.int64)
    col = np.arange(8, dtype=np.int64)
    t = CSRTopo(np.stack([row, col]), num_nodes=9)
    counts = np.zeros(8)
    trials = 600
    sample = jax.jit(lambda k: sample_neighbors(
        jnp.asarray(t.indptr), jnp.asarray(t.indices),
        jnp.zeros((1,), jnp.int32), fanout=4, key=k).nbrs)
    for s in range(trials):
        nbrs = np.asarray(sample(jax.random.key(s)))[0]
        counts[nbrs] += 1
    freq = counts / trials
    # Expected inclusion probability = 4/8 = 0.5.
    assert np.all(np.abs(freq - 0.5) < 0.1), freq


def test_padding_seeds():
    t = _chain_graph()
    out = sample_neighbors(
        jnp.asarray(t.indptr), jnp.asarray(t.indices),
        jnp.array([0, -1], jnp.int32), fanout=3, key=jax.random.key(0),
    )
    assert not np.asarray(out.mask)[1].any()
    assert (np.asarray(out.nbrs)[1] == -1).all()


def test_lookup_degrees():
    t = _chain_graph()
    deg = lookup_degrees(jnp.asarray(t.indptr), jnp.array([0, 1, 2, -1], jnp.int32))
    assert np.asarray(deg).tolist() == [5, 2, 0, 0]


# -- the chunk rule: only the chunks of the frontier that hold a node --------

from glt_tpu.ops import neighbor_sample as ns  # noqa: E402
from glt_tpu.typing import PADDING_ID  # noqa: E402

_W, _C, _FANOUT = 40, 8, 3


def _frontier(pattern, n, seed):
    """A ``[_W]`` frontier (43 rows where the width is to be no multiple
    of the chunk) of ids below ``n`` and ``-1``."""
    rng = np.random.default_rng(seed)
    w = _W + 3 if pattern == "ragged" else _W
    ids = rng.integers(0, n, w).astype(np.int32)
    if pattern == "prefix":
        ids[int(rng.integers(3, w - 9)):] = -1
    elif pattern == "four-prefixes":            # the dist served matrix
        ids = ids.reshape(4, w // 4)
        for row in ids:
            row[int(rng.integers(0, w // 4 + 1)):] = -1
        ids = ids.reshape(-1)
    elif pattern == "empty":
        ids[:] = -1
    elif pattern == "scattered":
        ids[rng.random(w) < 0.85] = -1
    elif pattern == "ragged":                   # only the short tail chunk
        ids[: w - 2] = -1                       # and the first hold an id
        ids[1] = 5
    else:
        assert pattern == "full"
    return ids


@functools.lru_cache(maxsize=None)
def _chunk_graph():
    rng = np.random.default_rng(11)
    n, e = 96, 900
    t = CSRTopo(np.stack([rng.integers(0, n, e), rng.integers(0, n, e)]),
                num_nodes=n)
    return (n, jnp.asarray(t.indptr), jnp.asarray(t.indices),
            jnp.asarray(rng.permutation(e).astype(np.int32)))


_programs = {}


def _run(mode, form, frontiers, keys):
    """``_read(*form)`` over the frontiers: one after another, as one
    jitted ``lax.scan``, or a shard each of the 4-shard CPU mesh the dist
    tests use (every shard its own trip count).  A program is traced once
    a form, width and ``CHUNK_ROWS``: the pattern is data."""
    read = functools.partial(_read, *form)
    if mode == "eager":
        outs = [read(f, k) for f, k in zip(frontiers, keys)]
        return [np.stack([np.asarray(o[i]) for o in outs])
                for i in range(len(outs[0]))]
    traced_as = (mode, form, frontiers.shape, ns.CHUNK_ROWS)
    if traced_as not in _programs:
        if mode == "scan":
            fn = lambda fs, ks: jax.lax.scan(           # noqa: E731
                lambda c, x: (c, read(*x)), 0, (fs, ks))[1]
        else:
            from jax.sharding import Mesh, PartitionSpec as P

            fn = jax.shard_map(
                lambda fs, ks: tuple(a[None] for a in read(fs[0], ks[0])),
                mesh=Mesh(np.array(jax.devices()[:4]), ("shard",)),
                in_specs=(P("shard"), P("shard")), out_specs=P("shard"),
                check_vma=False)
        _programs[traced_as] = jax.jit(fn)
    return [np.asarray(a) for a in _programs[traced_as](frontiers, keys)]


def _read(edges, with_replacement, key_by, frontier, key):
    _, indptr, indices, edge_ids = _chunk_graph()
    out = sample_neighbors(
        indptr, indices, frontier, _FANOUT, key,
        edge_ids=edge_ids if edges == "edge_ids" else None,
        with_replacement=with_replacement, with_edge=edges != "no-edge",
        force="xla", key_by=key_by)
    assert (out.eids is None) == (edges == "no-edge")
    return tuple(a for a in out if a is not None) + (
        ns.read_rows(frontier),)


@pytest.mark.parametrize("mode", ["eager", "scan", "shard_map"])
@pytest.mark.parametrize("key_by", ["slot", "id"])
@pytest.mark.parametrize("with_replacement", [False, True])
@pytest.mark.parametrize("edges", ["positions", "edge_ids", "no-edge"])
@pytest.mark.parametrize("pattern", ["prefix", "four-prefixes", "empty",
                                     "full", "scattered", "ragged"])
def test_the_chunked_read_is_the_whole_read_bit_for_bit(
        monkeypatch, pattern, edges, with_replacement, key_by, mode):
    n = _chunk_graph()[0]
    shards = {"eager": 1, "scan": 3, "shard_map": 4}[mode]
    # the pattern on every shard or step, each with its own live rows
    frontiers = jnp.asarray(np.stack(
        [_frontier(pattern, n, 7 * i) for i in range(shards)]))
    keys = jax.random.split(jax.random.key(5), shards)
    form = (edges, with_replacement, key_by)
    monkeypatch.setattr(ns, "CHUNK_ROWS", 10 ** 9)      # the whole read
    whole = _run(mode, form, frontiers, keys)
    monkeypatch.setattr(ns, "CHUNK_ROWS", _C)
    chunked = _run(mode, form, frontiers, keys)
    for a, b in zip(whole[:-1], chunked[:-1]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    width = frontiers.shape[1]
    assert (whole[-1] == width).all()
    live = np.zeros((shards, -(-width // _C) * _C), bool)
    live[:, :width] = np.asarray(frontiers) >= 0
    np.testing.assert_array_equal(
        chunked[-1], live.reshape(shards, -1, _C).any(axis=2).sum(axis=1) * _C)
    if pattern == "empty":
        assert (chunked[0] == PADDING_ID).all() and not chunked[-2].any()


def _parent_read(indptr, indices, seeds, fanout, key, edge_ids, with_edge,
                 with_replacement, key_by):
    """The XLA arm as PR 34 had it, letter for letter."""
    seeds = seeds.astype(jnp.int32)
    start, deg = ns._row_offsets_and_degrees(indptr, seeds)
    pos, mask = ns.draw_positions(deg, fanout, key, with_replacement, seeds,
                                  key_by=key_by)
    flat = start[:, None] + jnp.where(mask, pos, 0)
    nbrs = jnp.where(mask, indices[flat], PADDING_ID).astype(jnp.int32)
    if not with_edge:
        eids = None
    elif edge_ids is None:
        eids = jnp.where(mask, flat, PADDING_ID).astype(jnp.int32)
    else:
        eids = jnp.where(mask, edge_ids[flat], PADDING_ID).astype(jnp.int32)
    return ns.NeighborOutput(nbrs=nbrs, eids=eids, mask=mask)


@pytest.mark.parametrize("key_by", ["slot", "id"])
@pytest.mark.parametrize("edges", ["positions", "edge_ids", "no-edge"])
@pytest.mark.parametrize("width", [1, 1024, ns.CHUNK_ROWS])
def test_a_read_of_at_most_one_chunk_lowers_as_the_parents(width, edges,
                                                           key_by):
    """Whether the loop exists is a fact of the static shape: hop 1
    everywhere, most typed reads and serving's small buckets compile to
    the single fusion they always were."""
    _, indptr, indices, edge_ids = _chunk_graph()
    args = (indptr, indices, jnp.zeros((width,), jnp.int32),
            jax.random.key(0), edge_ids)

    def lowered(read):
        def hop(indptr, indices, seeds, key, edge_ids):
            return read(indptr, indices, seeds, 5, key,
                        edge_ids if edges == "edge_ids" else None,
                        edges != "no-edge", False, key_by)
        return jax.jit(hop).lower(*args).as_text()

    def shipped(*a):
        return sample_neighbors(*a[:5], edge_ids=a[5], with_edge=a[6],
                                with_replacement=a[7], force="xla",
                                key_by=a[8])

    text = lowered(shipped)
    assert text == lowered(_parent_read)
    # ... and one row more brings the two loops over the live chunks
    args = args[:2] + (jnp.zeros((ns.CHUNK_ROWS + 1,), jnp.int32),) + args[3:]
    wider = lowered(shipped)
    assert wider.count("stablehlo.while") == \
        text.count("stablehlo.while") + 2
