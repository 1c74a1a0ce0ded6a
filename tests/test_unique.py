import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.ops import relabel_by_reference, unique_first_occurrence


def _oracle_unique(ids):
    """First-occurrence-order unique via numpy."""
    seen, out = set(), []
    for v in ids:
        if v >= 0 and v not in seen:
            seen.add(v)
            out.append(v)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_unique_first_occurrence_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 40, 128)
    ids[rng.random(128) < 0.2] = -1  # padding holes
    u, inv, cnt = jax.jit(unique_first_occurrence)(jnp.asarray(ids))
    u, inv, cnt = np.asarray(u), np.asarray(inv), int(cnt)

    want = _oracle_unique(ids.tolist())
    assert cnt == len(want)
    assert u[:cnt].tolist() == want
    assert (u[cnt:] == -1).all()
    # Inverse maps every valid input position back to its id.
    for p, v in enumerate(ids.tolist()):
        if v < 0:
            assert inv[p] == -1
        else:
            assert u[inv[p]] == v


def test_unique_seeds_stay_in_front():
    # The loader invariant: seeds placed first come out first, in order.
    seeds = jnp.array([9, 4, 7], jnp.int32)
    nbrs = jnp.array([4, 11, 9, -1, 2, 7, 11], jnp.int32)
    u, inv, cnt = unique_first_occurrence(jnp.concatenate([seeds, nbrs]))
    assert np.asarray(u[:3]).tolist() == [9, 4, 7]
    assert np.asarray(u[3:int(cnt)]).tolist() == [11, 2]


def test_unique_all_padding():
    u, inv, cnt = unique_first_occurrence(jnp.full((8,), -1, jnp.int32))
    assert int(cnt) == 0
    assert (np.asarray(u) == -1).all()
    assert (np.asarray(inv) == -1).all()


def test_unique_all_duplicates():
    """Single repeated id (the hub-node extreme the dedup gather relies
    on): one unique, every valid position maps to slot 0."""
    ids = jnp.array([7, 7, 7, 7, 7, 7], jnp.int32)
    u, inv, cnt = unique_first_occurrence(ids)
    assert int(cnt) == 1
    assert np.asarray(u).tolist() == [7, -1, -1, -1, -1, -1]
    assert np.asarray(inv).tolist() == [0] * 6


def test_unique_all_duplicates_with_padding():
    ids = jnp.array([-1, 5, 5, -1, 5], jnp.int32)
    u, inv, cnt = unique_first_occurrence(ids)
    assert int(cnt) == 1
    assert np.asarray(u)[:1].tolist() == [5]
    assert np.asarray(inv).tolist() == [-1, 0, 0, -1, 0]


def test_unique_seeds_front_under_interleaved_padding():
    """The loader invariant the dedup gather must preserve: seeds placed
    first come out first IN ORDER even when padding holes interleave the
    seed block and the neighbor tail repeats them."""
    ids = jnp.array([9, -1, 4, -1, 7, 4, 11, -1, 9, 2], jnp.int32)
    u, inv, cnt = unique_first_occurrence(ids)
    assert np.asarray(u)[: int(cnt)].tolist() == [9, 4, 7, 11, 2]
    # inverse of the padded seed slots is -1, of the dup tail the seed slot
    assert int(inv[1]) == -1 and int(inv[5]) == 1 and int(inv[8]) == 0


def test_unique_count_equals_capacity():
    """All-distinct input: count == array capacity, no -1 slots, inverse
    is the identity permutation over first occurrences."""
    rng = np.random.default_rng(0)
    vals = rng.permutation(64).astype(np.int32)
    u, inv, cnt = unique_first_occurrence(jnp.asarray(vals))
    assert int(cnt) == 64
    assert np.asarray(u).tolist() == vals.tolist()
    assert np.asarray(inv).tolist() == list(range(64))


def test_relabel_by_reference():
    ref = jnp.array([9, 4, 7, 11, 2, -1, -1], jnp.int32)
    q = jnp.array([7, 2, 9, -1, 11, 4], jnp.int32)
    local = np.asarray(relabel_by_reference(ref, q))
    assert local.tolist() == [2, 4, 0, -1, 3, 1]


def test_relabel_missing_id_returns_minus_one():
    ref = jnp.array([5, 3, -1], jnp.int32)
    q = jnp.array([3, 8, 5], jnp.int32)
    assert np.asarray(relabel_by_reference(ref, q)).tolist() == [1, -1, 0]


# -- the last hop's inducer: sorted form against the map form -------------

def _induce_case(name):
    """``(num_nodes, capacity, prior, cand)`` of one named input."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n, prior = 500, rng.choice(500, 40, replace=False)
    if name == "heavy_repeats":
        cap, cand = 400, (rng.random(256) ** 3 * n).astype(np.int64)
        cand[rng.random(256) < 0.1] = -1
    elif name == "prior_partly_full":
        prior = np.concatenate([prior[:17], np.full(23, -1)])
        cap, cand = 300, rng.integers(-1, n, 200)
    elif name == "all_padding":
        cap, cand = 100, np.full(64, -1)
    elif name == "all_one_id":
        cap, cand = 100, np.full(64, 499)
    elif name == "all_known":
        cap, cand = 100, rng.choice(prior, 96)
    elif name == "capacity_exceeded":
        # 40 known + ~150 new into 64 slots: ids past the capacity are
        # still numbered, none of them written.
        cap, cand = 64, rng.integers(0, n, 192)
    elif name == "capacity_met_exactly":
        cap, cand = 72, np.concatenate([np.arange(32) + 460, prior[:9]])
    elif name == "width_not_power_of_two":
        cap, cand = 250, rng.integers(-1, n, 197)
    else:
        raise KeyError(name)
    return n, cap, prior.astype(np.int32), np.asarray(cand, np.int32)


_INDUCE_CASES = ["heavy_repeats", "prior_partly_full", "all_padding",
                 "all_one_id", "all_known", "capacity_exceeded",
                 "capacity_met_exactly", "width_not_power_of_two"]


def _under(wrap, fn, state, cands):
    """``fn(state, cand)`` for every row of ``cands`` [4, m] from the same
    ``state``: jitted calls, the body of one ``lax.scan``, or the four
    shards of one ``shard_map``."""
    if wrap == "jit":
        outs = [jax.jit(fn)(state, c) for c in cands]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    if wrap == "scan":
        return jax.jit(lambda s, cs: jax.lax.scan(
            lambda carry, c: (carry, fn(s, c)), 0, cs)[1])(state, cands)
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    return jax.jit(jax.shard_map(
        lambda s, cs: jax.tree.map(lambda x: x[None], fn(s, cs[0])),
        mesh=mesh, in_specs=(P(), P("shard")), out_specs=P("shard")))(
            state, cands)


@pytest.mark.parametrize("wrap", ["jit", "scan", "shard_map"])
@pytest.mark.parametrize("case", _INDUCE_CASES)
def test_sorted_induce_final_equals_map_form(case, wrap):
    """The last hop of a sorted chain (``induce`` on a state without an id
    map) against ``dense_induce_final``, field for field: the same local
    id for every candidate, the same node buffer, the same count (the
    buffer's last slot is the map form's write dump and holds no node)."""
    from glt_tpu.ops.unique import (chain_is_sorted, dense_induce,
                                    dense_induce_final, dense_induce_init,
                                    induce, sorted_slots)
    n, cap, prior, cand = _induce_case(case)
    known, m = prior.shape[0], cand.shape[0]
    assert chain_is_sorted(known, cap)
    state, _ = dense_induce(dense_induce_init(n, cap), jnp.asarray(prior))
    mapless = state._replace(seen=None)
    assert sorted_slots(mapless, known, m) == known + m
    assert sorted_slots(state, known, m) == 0
    # Four candidate rows from the one prior state: the case itself, two
    # rotations of it and its reverse (other first occurrences).
    cands = jnp.asarray(np.stack([cand, np.roll(cand, 7),
                                  np.roll(cand, -31), cand[::-1]]))
    (want_s, want_l) = _under(wrap, dense_induce_final, state, cands)
    (got_s, got_l) = _under(
        wrap, lambda s, c: induce(s, c, known, True), mapless, cands)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    np.testing.assert_array_equal(np.asarray(got_s.node_buf)[:, :cap],
                                  np.asarray(want_s.node_buf)[:, :cap])
    np.testing.assert_array_equal(np.asarray(got_s.count),
                                  np.asarray(want_s.count))
    if case == "capacity_exceeded":
        assert (np.asarray(got_s.count) > cap).all()
        assert (np.asarray(got_l) >= cap).any()


# -- every hop's inducer: the sorted chain against the map chain -----------

def _chain_case(name):
    """``(num_nodes, capacity, hops)`` of one named chain: ``hops[0]`` the
    seeds, then the candidates of each hop."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n = 300

    def draw(m, pad=0.1, lo=0):
        c = rng.integers(lo, n, m)
        c[rng.random(m) < pad] = -1
        return c
    seeds = np.concatenate([rng.choice(n, 10, replace=False), [-1, -1]])
    seeds[7] = seeds[2]                       # a repeat among the seeds
    if name == "two_hops":
        cap, hops = 200, [seeds, draw(36), draw(72)]
    elif name == "three_hops":
        cap, hops = 260, [seeds, draw(36), draw(72), draw(144)]
    elif name == "hops_repeat_what_is_known":
        # A narrow id range: most candidates are seeds, earlier hops'
        # nodes or each other.
        n = 40
        seeds = np.concatenate([rng.choice(n, 10, replace=False), [-1, -1]])
        seeds[5] = seeds[0]
        cap, hops = 200, [seeds, draw(36), draw(72), draw(144)]
    elif name == "empty_frontier":
        cap, hops = 200, [seeds, np.full(36, -1), draw(72)]
    elif name == "no_seed_at_all":
        cap, hops = 130, [np.full(12, -1), np.full(36, -1), draw(72, 0.5)]
    elif name == "all_seeds_one_id":
        cap, hops = 200, [np.full(12, 7), draw(36), draw(72)]
    elif name == "last_hop_past_capacity":
        # 12 + 36 + 72 = 120 known at most before the last hop, the
        # capacity: the last hop's new nodes run past it.
        cap, hops = 120, [seeds, draw(36, 0), draw(72, 0), draw(144, 0)]
    elif name == "capacity_met_by_the_bound":
        cap, hops = 48, [seeds, draw(36), draw(72)]
    else:
        raise KeyError(name)
    return n, cap, [np.asarray(h, np.int32) for h in hops]


_CHAIN_CASES = ["two_hops", "three_hops", "hops_repeat_what_is_known",
                "empty_frontier", "no_seed_at_all", "all_seeds_one_id",
                "last_hop_past_capacity", "capacity_met_by_the_bound"]


def _knowns(hops):
    """Static bound on known nodes before each call of a chain."""
    return [0] + list(np.cumsum([h.shape[0] for h in hops[:-1]]))


def _chain(n, cap):
    """``hops -> [(local, node_buf[:cap], count) of every hop]`` through
    ``induce_init`` / ``induce``, the chain as a sampler runs it: sorted
    where ``cap`` covers the bound on known nodes, else on the map."""
    from glt_tpu.ops.unique import induce, induce_init

    def run(hops):
        knowns = _knowns(hops)
        state = induce_init(n, cap, int(knowns[-1]))
        out = []
        for k, (cand, known) in enumerate(zip(hops, knowns)):
            state, local = induce(state, cand, int(known),
                                  k + 1 == len(hops))
            out.append((local, state.node_buf[:cap], state.count))
        return out
    return run


def _map_chain(n, cap):
    """The same through the id map: ``dense_induce`` at every hop, and
    ``dense_induce_final`` where ``final`` says the last hop takes it."""
    from glt_tpu.ops.unique import (dense_induce, dense_induce_final,
                                    dense_induce_init)

    def run(hops, final=False):
        state, out = dense_induce_init(n, cap), []
        for k, cand in enumerate(hops):
            last = final and k + 1 == len(hops)
            state, local = (dense_induce_final if last
                            else dense_induce)(state, cand)
            out.append((local, state.node_buf[:cap], state.count))
        return out
    return run


def _chain_under(wrap, run, hop_rows):
    """``run(hops)`` for each of the rows of ``hop_rows`` (a list over hops
    of ``[R, m_k]``): called eagerly, jitted, or as the body of one
    ``lax.scan`` over the rows."""
    hop_rows = [jnp.asarray(h) for h in hop_rows]
    if wrap == "scan":
        return jax.jit(lambda hs: jax.lax.scan(
            lambda carry, h: (carry, run(h)), 0, hs)[1])(hop_rows)
    fn = jax.jit(run) if wrap == "jit" else run
    outs = [fn([h[r] for h in hop_rows])
            for r in range(hop_rows[0].shape[0])]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)


def _assert_chains_equal(got, want):
    assert len(got) == len(want)
    for k, (g, w) in enumerate(zip(got, want)):
        for name, a, b in zip(("local", "node_buf", "count"), g, w):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"hop {k}: {name}")


@pytest.mark.parametrize("wrap", ["eager", "jit", "scan"])
@pytest.mark.parametrize("case", _CHAIN_CASES)
def test_sorted_chain_equals_map_chain(case, wrap):
    """Every hop of a chain without an id map (seeds that repeat and pad,
    then hops whose candidates repeat earlier nodes, each other and -1)
    against the ``dense_induce`` chain, bit for bit: ``local``,
    ``node_buf`` and ``count`` after the seeds and after every hop."""
    from glt_tpu.ops.unique import chain_is_sorted, induce_init
    n, cap, hops = _chain_case(case)
    known_last = int(_knowns(hops)[-1])
    assert chain_is_sorted(known_last, cap)
    assert induce_init(n, cap, known_last).seen is None
    # Three rows: the chain, every hop rotated, every hop reversed.
    rows = [np.stack([h, np.roll(h, 5), h[::-1]]) for h in hops]
    got = _chain_under(wrap, _chain(n, cap), rows)
    want = _chain_under(wrap, _map_chain(n, cap), rows)
    _assert_chains_equal(got, want)
    counts = np.asarray(got[-1][2])
    if case == "last_hop_past_capacity":
        assert (counts > cap).all() and (np.asarray(got[-1][0]) >= cap).any()
        assert (np.asarray(got[-2][2]) <= cap).all()
    if case == "empty_frontier":
        assert (np.asarray(got[1][2]) == np.asarray(got[0][2])).all()
        assert (np.asarray(got[1][0]) == -1).all()
    if case == "no_seed_at_all":
        assert (np.asarray(got[1][2]) == 0).all() and (counts > 0).all()
    if case == "all_seeds_one_id":
        assert (np.asarray(got[0][2]) == 1).all()
        assert (np.asarray(got[0][0]) == 0).all()


@pytest.mark.parametrize("case", _CHAIN_CASES)
def test_sorted_chains_last_hop_equals_dense_induce_final(case):
    """The map chain's own last hop (``dense_induce_final``, one map op
    fewer) is the other reference the sorted chain is held to."""
    n, cap, hops = _chain_case(case)
    got = jax.jit(_chain(n, cap))(hops)
    want = jax.jit(lambda h: _map_chain(n, cap)(h, final=True))(hops)
    _assert_chains_equal(got, want)


@pytest.mark.parametrize("case,cap", [("two_hops", 40), ("three_hops", 100),
                                      ("last_hop_past_capacity", 119),
                                      ("hops_repeat_what_is_known", 30)])
def test_chain_under_its_bound_keeps_the_map_at_every_hop(case, cap):
    """Where the capacity lies under the bound on nodes known before the
    last hop, nodes past the buffer's end live in the id map alone: the
    state holds the map, every hop runs the map form (the engagement
    gauge's value is 0 at each) and the numbering is today's."""
    from glt_tpu.ops.unique import (chain_is_sorted, induce, induce_init,
                                    sorted_slots)
    n, _, hops = _chain_case(case)
    knowns = _knowns(hops)
    assert not chain_is_sorted(int(knowns[-1]), cap)
    state = induce_init(n, cap, int(knowns[-1]))
    assert state.seen is not None and state.seen.shape == (n + 2,)
    assert all(sorted_slots(state, int(k), h.shape[0]) == 0
               for k, h in zip(knowns, hops))
    got = jax.jit(_chain(n, cap))(hops)
    want = jax.jit(lambda h: _map_chain(n, cap)(h, final=True))(hops)
    _assert_chains_equal(got, want)
    # ... and the sorted form refuses a bound it cannot honour
    with pytest.raises(ValueError, match="past capacity"):
        induce(state._replace(seen=None), jnp.asarray(hops[-1]),
               int(knowns[-1]), True)


def test_induce_final_keeps_the_map_where_the_buffer_may_have_overflowed():
    """Where the static bound on known nodes passes the capacity, nodes
    past the buffer's end live in the id map alone: the map form runs,
    the gauge's value is 0, and a node that overflowed earlier keeps its
    number."""
    from glt_tpu.ops.unique import (dense_induce_final, induce, induce_init,
                                    sorted_slots)
    prior = jnp.arange(10, 22, dtype=jnp.int32)          # 12 into 8 slots
    cand = jnp.asarray([21, 3, 10, 21, 4], jnp.int32)
    state = induce_init(30, 8, 12)
    assert sorted_slots(state, 0, 12) == sorted_slots(state, 12, 5) == 0
    state, _ = induce(state, prior, 0, False)
    got_s, got_l = induce(state, cand, 12, True)
    want_s, want_l = dense_induce_final(state, cand)
    assert np.asarray(got_l).tolist() == [11, 12, 0, 11, 13]
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    assert int(got_s.count) == int(want_s.count) == 14


def test_sorted_induce_final_makes_no_random_pass_and_leaves_the_map():
    """The sorted form as lowered: four sorts, no gather, no scatter, and
    no id map: a sorted chain's state holds none."""
    from glt_tpu.ops.unique import (dense_induce_final, dense_induce_init,
                                    induce, induce_init)
    state = induce_init(1000, 300, 40)
    assert state.seen is None
    cand = jnp.zeros((200,), jnp.int32)

    def lowered(fn, state):
        return jax.jit(fn).lower(state, cand).as_text()
    text = lowered(lambda s, c: induce(s, c, 40, True), state)
    assert text.count("stablehlo.sort") == 4
    assert "gather" not in text and "scatter" not in text
    assert "tensor<1002xi32>" not in text
    old = lowered(dense_induce_final, dense_induce_init(1000, 300))
    assert "stablehlo.scatter" in old and "stablehlo.gather" in old
    assert "tensor<1002xi32>" in old


@pytest.mark.parametrize("case", ["two_hops", "three_hops"])
def test_sorted_chain_as_lowered_holds_no_id_map_and_no_scatter(case):
    """A whole sorted chain's program: four sorts a call, no array of
    ``num_nodes + 2`` entries, no scatter and no gather; the map chain's
    holds all three."""
    n, cap, hops = _chain_case(case)
    text = jax.jit(_chain(n, cap)).lower(hops).as_text()
    assert text.count("stablehlo.sort") == 4 * len(hops)
    assert f"tensor<{n + 2}xi32>" not in text
    assert "scatter" not in text and "gather" not in text
    old = jax.jit(_map_chain(n, cap)).lower(hops).as_text()
    assert f"tensor<{n + 2}xi32>" in old and "stablehlo.scatter" in old


@pytest.mark.parametrize("n", [1, 7, 1024, 1025, 5000])
def test_run_fill_copies_each_heads_value_down_its_run(n):
    from glt_tpu.ops.unique import _run_fill
    rng = np.random.default_rng(n)
    head = rng.random(n) < 0.01
    head[0] = True
    value = rng.integers(0, 1 << 30, n).astype(np.int32)
    want = value[np.maximum.accumulate(np.where(head, np.arange(n), 0))]
    got = jax.jit(_run_fill)(jnp.asarray(head), jnp.asarray(value))
    np.testing.assert_array_equal(np.asarray(got), want)
