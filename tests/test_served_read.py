"""The served read of the feature exchanges (``parallel/dist_feature.py::
_request_rows``) visits only the chunks of the request matrix that hold a
request, and returns what the whole take returns, bit for bit.

Every case runs the same programs twice on a four-device CPU mesh: with
``ops.neighbor_sample.CHUNK_ROWS`` cut small, so the loop over live
chunks runs, and with it above every width, the whole take.  The
constant is read when a program is traced."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from glt_tpu.ops import neighbor_sample
from glt_tpu.parallel import (HostColdStore, exchange_gather,
                              exchange_gather_hot, exchange_gather_xy,
                              route_cold_requests, shard_feature)
from glt_tpu.parallel.dist_feature import (_request_rows,
                                           compact_cold_requests,
                                           shard_feature_tiered)

S, C, D, B = 4, 40, 8, 37        # shards, rows a shard, width, ids a shard
WHOLE = 10 ** 9                  # a chunk above every width: no loop
#: 16 leaves the 148-slot matrix no multiple of the chunk, 37 divides it,
#: 148 is the width (one chunk) and 1000 more than it
CHUNKS = (16, 37, 148, 1000)


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    feat = rng.normal(0, 1, (S * C, D)).astype(np.float32)
    labels = rng.integers(-2**31, 2**31 - 1, S * C, dtype=np.int64)
    labels[:4] = [0, -1, 2**31 - 1, -2**31]       # any bits round-trip
    tiered = shard_feature_tiered(feat, S, 0.5)
    return {"mesh": Mesh(np.array(jax.devices()[:S]), ("shard",)),
            "rows": shard_feature(feat, S).rows,
            "labels": jnp.asarray(labels.astype(np.int32).reshape(S, C)),
            "tiered": tiered, "store": HostColdStore(tiered),
            "programs": {}}


def _layout(name):
    """``[S, B]`` global ids a shard asks for.  The flat exchange lands
    each requester's ids of an owner as a prefix of its bucket: ``prefixes``
    gives every owner four of them, ``dead`` none, ``live`` fills every
    bucket of shard 0 (each requester asks 37 of its 40 rows)."""
    rng = np.random.default_rng(1)
    ids = np.full((S, B), -1, np.int32)
    for s in range(S):
        if name == "prefixes":
            ids[s, :25] = rng.choice(S * C, 25, replace=False)
        elif name == "live":
            ids[s] = rng.choice(C, B, replace=False)
        else:
            assert name == "dead"
    return jnp.asarray(ids)


def _every_exchange(world):
    """One program running every exchange on the same ids over the flat
    topology: rows alone and rows with labels (dedup off / on each), and
    the tiered read with compact host staging."""
    c, h = C, world["tiered"].hot_per_shard

    def body(rows, labels, hot, ids, staged, slots):
        rows, labels, hot, ids, staged, slots = (
            a[0] for a in (rows, labels, hot, ids, staged, slots))
        out = [exchange_gather(ids, rows, c, S, "shard", dedup=dedup)
               for dedup in (False, True)]
        for dedup in (False, True):
            out += exchange_gather_xy(ids, rows, labels, c, S, "shard",
                                      dedup=dedup)
        out.append(exchange_gather_hot(ids, hot, c, h, S, "shard",
                                       staged_rows=staged,
                                       staged_slots=slots))
        return tuple(a[None] for a in out)

    sp = P("shard")
    return jax.jit(jax.shard_map(body, mesh=world["mesh"],
                                 in_specs=(sp,) * 6, out_specs=sp,
                                 check_vma=False))


def _every_hier_exchange(world):
    """The rows and the rows-with-labels exchanges (dedup off / on) over
    the hierarchical topology of a 2 x 2 mesh: the served matrix is each
    host's deduplicated request list, no set of prefixes."""
    axes = ("host", "chip")
    mesh = Mesh(np.array(jax.devices()[:S]).reshape(2, 2), axes)
    kw = dict(route="hier", mesh_shape=(2, 2))

    def body(rows, labels, ids):
        rows, labels, ids = (a[0] for a in (rows, labels, ids))
        out = [exchange_gather(ids, rows, C, S, axes, dedup=dedup, **kw)
               for dedup in (False, True)]
        for dedup in (False, True):
            out += exchange_gather_xy(ids, rows, labels, C, S, axes,
                                      dedup=dedup, **kw)
        return tuple(a[None] for a in out)

    sp = P(axes)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(sp,) * 3,
                                 out_specs=sp, check_vma=False))


def _staging(world, ids):
    """The compact cold staging of ``ids``: request slots and rows."""
    t = world["tiered"]

    def route(nodes):
        req = route_cold_requests(nodes[0], C, t.hot_per_shard, S, "shard")
        slots, cold, _ = compact_cold_requests(req, S * B)
        return slots[None], cold[None]

    sp = P("shard")
    slots, cold = jax.jit(jax.shard_map(
        route, mesh=world["mesh"], in_specs=(sp,), out_specs=(sp, sp),
        check_vma=False))(ids)
    cold = np.asarray(cold)
    rows = np.stack([world["store"].serve(s, cold[s]) for s in range(S)])
    return jnp.asarray(rows), slots


def _run(world, ids, chunk, monkeypatch):
    """Every exchange's outputs, its program traced (once a chunk: the
    layouts share their shapes) with ``CHUNK_ROWS`` at ``chunk``."""
    monkeypatch.setattr(neighbor_sample, "CHUNK_ROWS", chunk)
    staged, slots = _staging(world, ids)
    flat, hier = world["programs"].setdefault(
        chunk, (_every_exchange(world), _every_hier_exchange(world)))
    out = flat(world["rows"], world["labels"], world["tiered"].hot, ids,
               staged, slots)
    out += hier(world["rows"], world["labels"], ids)
    return [np.asarray(a) for a in out]


@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("layout", ["prefixes", "dead", "live"])
def test_every_exchange_returns_the_whole_takes_bits(world, layout, chunk,
                                                     monkeypatch):
    ids = _layout(layout)
    got = _run(world, ids, chunk, monkeypatch)
    want = _run(world, ids, WHOLE, monkeypatch)
    assert len(got) == (2 + 4 + 1) + (2 + 4)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    # and the whole take is the rows and labels asked for, on either
    # topology: flat x, x, (x, y) twice, hot x; hier x, x, (x, y) twice
    ids = np.asarray(ids)
    rows = np.asarray(world["rows"]).reshape(S * C, D)
    labels = np.asarray(world["labels"]).reshape(-1)
    x = np.where((ids >= 0)[..., None], rows[np.maximum(ids, 0)], 0)
    y = np.where(ids >= 0, labels[np.maximum(ids, 0)], 0)
    is_y = {3, 5, 10, 12}
    for i, a in enumerate(want):
        np.testing.assert_array_equal(a, y if i in is_y else x)


def _served_numpy(local, oks, tables):
    """What the served read returns, and the slots holding a request."""
    live = np.logical_or.reduce(oks)
    blocks = [np.where(ok.reshape(ok.shape + (1,) * (t.ndim - 1)),
                       t[np.where(ok, local, 0)], 0)
              for t, ok in zip(tables, oks)]
    return blocks, live


@pytest.mark.parametrize("width", [148, 160, 12])
@pytest.mark.parametrize("share", [0.0, 0.3, 1.0])
def test_a_scattered_request_list_reads_its_live_chunks(width, share,
                                                        monkeypatch):
    """Requests anywhere in the matrix (a hierarchical plan's host-deduped
    list is no set of prefixes), two tables with masks of their own (the
    tiered rows stop at the hot prefix, the labels do not): blocks the
    whole take's and numpy's, and the counts of slots that hold a request
    and of slots visited, at widths no multiple of the chunk (148), a
    multiple (160) and at most one chunk (12)."""
    chunk = 16
    rng = np.random.default_rng(width)
    rows = rng.normal(0, 1, (50, D)).astype(np.float32)
    labels = rng.integers(-2**31, 2**31 - 1, 50).astype(np.int32)
    local = rng.integers(-60, 60, width).astype(np.int32)
    oky = (rng.random(width) < share) & (local >= 0) & (local < 50)
    okx = oky & (local < 30)

    def served(rows, labels, local, okx, oky):
        return _request_rows(local, [(rows, okx, "glt.gather.feat"),
                                     (labels, oky, "glt.gather.label")])

    got = {}
    for c in (chunk, WHOLE):
        monkeypatch.setattr(neighbor_sample, "CHUNK_ROWS", c)
        # a function of its own a chunk: jit's trace cache is keyed by it
        got[c] = jax.jit(lambda *a: served(*a))(rows, labels, local, okx,
                                                 oky)
    (bx, by), counts = got[chunk]
    for a, b in zip((bx, by), got[WHOLE][0]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    want, live = _served_numpy(local, [okx, oky], [rows, labels])
    np.testing.assert_array_equal(np.asarray(bx), want[0])
    np.testing.assert_array_equal(np.asarray(by), want[1])
    if width <= chunk:
        visited = width
    else:
        n = -(-width // chunk)
        padded = np.zeros(n * chunk, bool)
        padded[:width] = live
        visited = int(padded.reshape(n, chunk).any(axis=1).sum()) * chunk
    assert np.asarray(counts).tolist() == [int(live.sum()), visited]
    assert np.asarray(got[WHOLE][1]).tolist() == [int(live.sum()), width]
