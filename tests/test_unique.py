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
    """``induce_final`` in its sorted form against ``dense_induce_final``,
    field for field: the same local id for every candidate, the same
    node buffer, the same count (the buffer's last slot is the map form's
    write dump and holds no node)."""
    from glt_tpu.ops.unique import (dense_induce, dense_induce_final,
                                    dense_induce_init, induce_final,
                                    sorted_final_slots)
    n, cap, prior, cand = _induce_case(case)
    known, m = prior.shape[0], cand.shape[0]
    assert sorted_final_slots(known, cap, m) == known + m
    state, _ = dense_induce(dense_induce_init(n, cap), jnp.asarray(prior))
    # Four candidate rows from the one prior state: the case itself, two
    # rotations of it and its reverse (other first occurrences).
    cands = jnp.asarray(np.stack([cand, np.roll(cand, 7),
                                  np.roll(cand, -31), cand[::-1]]))
    (want_s, want_l) = _under(wrap, dense_induce_final, state, cands)
    (got_s, got_l) = _under(
        wrap, lambda s, c: induce_final(s, c, known), state, cands)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    np.testing.assert_array_equal(np.asarray(got_s.node_buf)[:, :cap],
                                  np.asarray(want_s.node_buf)[:, :cap])
    np.testing.assert_array_equal(np.asarray(got_s.count),
                                  np.asarray(want_s.count))
    if case == "capacity_exceeded":
        assert (np.asarray(got_s.count) > cap).all()
        assert (np.asarray(got_l) >= cap).any()


def test_induce_final_keeps_the_map_where_the_buffer_may_have_overflowed():
    """Where the static bound on known nodes passes the capacity, nodes
    past the buffer's end live in the id map alone: the map form runs,
    the gauge's value is 0, and a node that overflowed earlier keeps its
    number."""
    from glt_tpu.ops.unique import (dense_induce, dense_induce_final,
                                    dense_induce_init, induce_final,
                                    sorted_final_slots)
    prior = jnp.arange(10, 22, dtype=jnp.int32)          # 12 into 8 slots
    cand = jnp.asarray([21, 3, 10, 21, 4], jnp.int32)
    assert sorted_final_slots(12, 8, 5) == 0
    state, _ = dense_induce(dense_induce_init(30, 8), prior)
    got_s, got_l = induce_final(state, cand, 12)
    want_s, want_l = dense_induce_final(state, cand)
    assert np.asarray(got_l).tolist() == [11, 12, 0, 11, 13]
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    assert int(got_s.count) == int(want_s.count) == 14


def test_sorted_induce_final_makes_no_random_pass_and_leaves_the_map():
    """The sorted form as lowered: four sorts, no gather, no scatter, and
    the id map handed through unread (its one use is the result)."""
    from glt_tpu.ops.unique import (dense_induce_final, dense_induce_init,
                                    induce_final)
    state = dense_induce_init(1000, 300)
    cand = jnp.zeros((200,), jnp.int32)

    def lowered(fn):
        return jax.jit(fn).lower(state, cand).as_text()
    text = lowered(lambda s, c: induce_final(s, c, 40))
    assert text.count("stablehlo.sort") == 4
    assert "gather" not in text and "scatter" not in text
    uses = [line for line in text.splitlines() if "tensor<1002xi32>" in line]
    assert all("func.func" in line or "return" in line for line in uses), uses
    old = lowered(dense_induce_final)
    assert "stablehlo.scatter" in old and "stablehlo.gather" in old


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
