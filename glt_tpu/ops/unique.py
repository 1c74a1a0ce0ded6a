"""Static-shape, first-occurrence-order unique + relabel.

This is the TPU-native replacement for the reference's GPU hash-table
inducer (``csrc/cuda/hash_table.cu``, ``csrc/cuda/inducer.cu``): the CUDA
design deduplicates node ids with an ``atomicCAS`` open-addressing table and
emits unique keys in first-occurrence order.  Hash tables are a poor fit for
the TPU's vector units, so we obtain identical semantics with sorts and
segmented scans — O(M log M), fully static shapes, jit/vmap/shard_map safe.

Key invariant preserved from the reference: unique ids come out in **first
occurrence order**, so when seeds are placed at the front of the input, the
output node list starts with the seeds — loaders rely on
``batch.node[:batch_size] == seeds`` exactly as GLT does
(csrc/cuda/inducer.cu:75-95, python/loader/node_loader.py:85).

Negative ids are padding (PADDING_ID) and are ignored; they map to inverse
index -1 and never appear among the unique ids.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import metrics as _metrics
from ..obs.scopes import scoped

_INT32_MAX = jnp.iinfo(jnp.int32).max


class UniqueResult(NamedTuple):
    uniques: jnp.ndarray  # [M] unique ids in first-occurrence order, -1 padded
    inverse: jnp.ndarray  # [M] position of each input id in `uniques` (-1 for padding)
    count: jnp.ndarray    # [] int32 number of valid uniques


@scoped("glt.sample.induce")
def unique_first_occurrence(ids: jnp.ndarray) -> UniqueResult:
    """Deduplicate ``ids`` preserving first-occurrence order.

    Args:
      ids: ``[M]`` int array; negative entries are padding.

    Returns:
      ``UniqueResult(uniques, inverse, count)`` with static shapes ``[M]``.
    """
    ids = ids.astype(jnp.int32)
    m = ids.shape[0]
    valid = ids >= 0
    # Padding sorts to the back.
    keys = jnp.where(valid, ids, _INT32_MAX)

    # Stable sort so the head of each equal-id run carries the smallest
    # original position == the first occurrence.
    perm = jnp.argsort(keys, stable=True)
    sorted_keys = keys[perm]

    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), sorted_keys[:-1]])
    heads = (sorted_keys != prev) & (sorted_keys != _INT32_MAX)
    # Run index of every sorted element (garbage for padding; masked later).
    run_of_sorted = jnp.cumsum(heads.astype(jnp.int32)) - 1
    count = jnp.sum(heads.astype(jnp.int32))

    # Per-run first-occurrence position and id, scattered at head slots.
    # Scatter target M+1 with an overflow slot for non-heads / padding.
    scatter_idx = jnp.where(heads, run_of_sorted, m)
    first_pos = (
        jnp.full((m + 1,), _INT32_MAX, jnp.int32)
        .at[scatter_idx]
        .min(perm.astype(jnp.int32))[:m]
    )
    run_ids = (
        jnp.full((m + 1,), -1, jnp.int32).at[scatter_idx].max(sorted_keys)[:m]
    )
    run_ids = jnp.where(run_ids == _INT32_MAX, -1, run_ids)

    # Order runs by first occurrence; padding runs (first_pos == INT32_MAX)
    # sort to the back.
    order = jnp.argsort(first_pos, stable=True)
    uniques = run_ids[order]

    # rank[r] = final position of run r.
    rank = jnp.zeros((m,), jnp.int32).at[order].set(jnp.arange(m, dtype=jnp.int32))
    inv_sorted = rank[jnp.clip(run_of_sorted, 0, m - 1)]
    inverse = jnp.zeros((m,), jnp.int32).at[perm].set(inv_sorted)
    inverse = jnp.where(valid, inverse, -1)
    return UniqueResult(uniques, inverse, count)


class DenseInduceState(NamedTuple):
    """Carry of the incremental inducer, one chain of hops (seeds first).

    ``seen`` is ``None`` in a sorted chain (:func:`induce_init`): every
    hop works on ``node_buf`` alone and the program holds no
    ``[num_nodes + 2]`` array.  In a map chain it is the dense
    (scatter-based) inducer's ``[num_nodes + 2]`` int32 map: 0 = unseen,
    else the
    committed encoding ``_LOCAL_BASE - local_id`` (decode with
    ``_LOCAL_BASE - seen[id]``; between the two scatters of a
    :func:`dense_induce` call it may transiently hold provisional
    markers — see the band comment there).  Slot ``N`` absorbs padding
    *reads*; slot ``N + 1`` absorbs dump *writes*.  ``node_buf`` is the
    cumulative ``[capacity + 1]`` unique-node list (-1 padded; last slot
    is the write dump), ``count`` the number of valid uniques.
    """
    seen: jnp.ndarray
    node_buf: jnp.ndarray
    count: jnp.ndarray


def dense_map_fits(num_nodes: int, budget_bytes: int = 1 << 30) -> bool:
    """Whether a dense id->local map for ``num_nodes`` fits the budget
    (the 'auto' dedup heuristic shared by every sampler)."""
    return num_nodes * 4 <= budget_bytes


@scoped("glt.sample.induce")
def dense_induce_init(num_nodes: int, capacity: int) -> DenseInduceState:
    """Fresh per-batch state of a map chain (the analog of
    ``Inducer::Reset``, csrc/cpu/inducer.cc; allocating zeros is a
    ~4B/node memset).  Samplers come through :func:`induce_init`."""
    return DenseInduceState(
        seen=jnp.zeros((num_nodes + 2,), jnp.int32),
        node_buf=jnp.full((capacity + 1,), -1, jnp.int32),
        count=jnp.zeros((), jnp.int32),
    )


# Encoded `seen` values (see dense_induce): 0 = unseen; provisional
# in-batch representative markers live in (0, _PROV_BASE]; committed
# local ids live in [_LOCAL_BASE - count, _LOCAL_BASE].  The committed
# band sits strictly above the provisional band, so one scatter-MAX both
# detects first occurrences and preserves existing assignments.  Hard
# bounds: per-call candidate width m < _PROV_BASE (validated below) and
# cumulative count < _LOCAL_BASE - _PROV_BASE (~1.04e9; unreachable —
# count is bounded by node_buf's capacity, itself an int32 array size).
_PROV_BASE = 1 << 25
_LOCAL_BASE = 1 << 30


@scoped("glt.sample.induce")
def dense_induce(state: DenseInduceState, cand: jnp.ndarray
                 ) -> tuple:
    """Insert ``cand`` (negative = padding) into the cumulative unique
    list; return ``(state, local)`` where ``local[i]`` is the compact
    index of ``cand[i]`` (-1 for padding).

    This is the hash-table inducer's contract
    (``CUDAInducer::InduceNext``, csrc/cuda/inducer.cu:95) implemented
    with dense scatters into an O(N) id->local map: the form a chain
    keeps only where a capacity lies under its bound on known nodes
    (:func:`chain_is_sorted`), because there the map alone remembers a
    node past the buffer's end; every other chain runs every hop as sorts
    and scans (:func:`induce`), and this is the reference its tests hold
    that form to.  A random element op is what it pays for: on a TPU v5e
    a gather or scatter of 768,000 int32 elements takes 3.7-8.0 ms
    (4.9-10.4 ns an element; the scatter-max into the map grows with the
    map, 4.9 ms at 2.45 M nodes and 8.0 ms at 27.8 M) where a
    two-operand ``lax.sort`` of 937,984 takes 1.0 ms (my chip run, PR
    29, scripts/induce_micro.py).  So the hop costs exactly FOUR such
    passes per candidate — scatter-max of an encoded marker, read-back,
    commit scatter, resolve read — via a single map whose value encoding
    makes existing assignments beat in-batch provisional markers under
    max.  New nodes receive consecutive local ids in first-occurrence
    order, so per-hop frontier slices of ``node_buf`` are exactly the
    newly discovered nodes, and seeds placed first keep
    ``node_buf[:batch] == seeds``.
    """
    seen, node_buf, count = state
    n2 = seen.shape[0]
    n = n2 - 2
    m = cand.shape[0]
    if m >= _PROV_BASE:
        raise ValueError(f"candidate width {m} exceeds the {_PROV_BASE} "
                         f"encoding band")
    cand = cand.astype(jnp.int32)
    valid = cand >= 0
    safe = jnp.where(valid, cand, n)                     # padding reads slot n
    pos = jnp.arange(m, dtype=jnp.int32)

    # Op 1 (scatter-max): provisional marker _PROV_BASE - pos.  Unseen
    # slots (0) lose to any marker; among markers the smallest pos wins;
    # committed ids (>= _LOCAL_BASE - cap) beat every marker.
    seen = seen.at[jnp.where(valid, safe, n + 1)].max(
        jnp.where(valid, _PROV_BASE - pos, 0))
    # Op 2 (gather): who won each id?
    won = seen[safe]
    is_first = valid & (won == _PROV_BASE - pos)  # my marker won => new id
    local_new = count + jnp.cumsum(is_first.astype(jnp.int32)) - 1
    # Op 3 (scatter): commit final encodings for the new ids (ids are
    # unique among is_first slots; dump slot n+1 absorbs the rest).
    seen = seen.at[jnp.where(is_first, safe, n + 1)].set(
        jnp.where(is_first, _LOCAL_BASE - local_new, 0))
    # Op 4 (gather): resolve every candidate through the committed map.
    local = jnp.where(valid, _LOCAL_BASE - seen[safe], -1)
    dump = node_buf.shape[0] - 1
    # Defensive clamp: callers that size node_buf below the worst case
    # (capped hetero buffers) overflow into the dump slot; the node keeps
    # its >=capacity local id in `seen`, so its edges are maskable.
    slot = jnp.minimum(jnp.where(is_first, local_new, dump), dump)
    node_buf = node_buf.at[slot].set(jnp.where(is_first, cand, -1))
    count = count + jnp.sum(is_first.astype(jnp.int32))
    return DenseInduceState(seen, node_buf, count), local


@scoped("glt.sample.induce")
def dense_induce_final(state: DenseInduceState, cand: jnp.ndarray
                       ) -> tuple:
    """Last-hop :func:`dense_induce` on the id map: same contract, one
    fewer map op: the last hop of a chain that keeps the map
    (:func:`induce`), and the reference the sorted form's tests hold its
    last hop to.

    After the final hop no later hop reads the ``seen`` map, so the
    commit scatter (op 3 of :func:`dense_induce`) is dead work; losers of
    the provisional scatter-max resolve through an ``[m]``-sized gather
    of the winner's freshly assigned id instead of re-reading the map.
    Still four random passes with the node-buffer scatter: 20.1-22.5 ms
    at 768,000 candidates (my chip run, PR 29, scripts/induce_micro.py).
    The returned ``state.seen`` is stale (still holds provisional
    markers) and MUST NOT be fed to another induce call;
    ``node_buf``/``count`` are exact.
    """
    seen, node_buf, count = state
    n2 = seen.shape[0]
    n = n2 - 2
    m = cand.shape[0]
    if m >= _PROV_BASE:
        raise ValueError(f"candidate width {m} exceeds the {_PROV_BASE} "
                         f"encoding band")
    cand = cand.astype(jnp.int32)
    valid = cand >= 0
    safe = jnp.where(valid, cand, n)
    pos = jnp.arange(m, dtype=jnp.int32)

    # Op 1 (scatter-max) + op 2 (gather): identical to dense_induce.
    seen = seen.at[jnp.where(valid, safe, n + 1)].max(
        jnp.where(valid, _PROV_BASE - pos, 0))
    won = seen[safe]
    is_first = valid & (won == _PROV_BASE - pos)
    local_new = count + jnp.cumsum(is_first.astype(jnp.int32)) - 1
    # Resolve WITHOUT the commit scatter: committed winners (previous
    # hops) decode in-register; marker winners (this call) are by
    # construction is_first slots, so an [m]-gather of local_new at the
    # winner position replaces the map read-back.
    winner_pos = jnp.clip(_PROV_BASE - won, 0, m - 1)
    local = jnp.where(won > _PROV_BASE, _LOCAL_BASE - won,
                      local_new[winner_pos])
    local = jnp.where(valid, local, -1)
    dump = node_buf.shape[0] - 1
    slot = jnp.minimum(jnp.where(is_first, local_new, dump), dump)
    node_buf = node_buf.at[slot].set(jnp.where(is_first, cand, -1))
    count = count + jnp.sum(is_first.astype(jnp.int32))
    return DenseInduceState(seen, node_buf, count), local


# Row width of the blocked segmented fill: eight 128-lane tiles.
_FILL_COLS = 1024


def _fill_doubling(head: jnp.ndarray, value: jnp.ndarray, axis: int):
    """Inclusive "latest head wins" scan along ``axis`` as log2(size)
    shift-and-select steps; returns ``(any head so far, its value)``."""
    size, d = head.shape[axis], 1
    while d < size:
        shift = [(0, 0)] * head.ndim
        shift[axis] = (d, 0)
        keep = [slice(None)] * head.ndim
        keep[axis] = slice(0, size - d)
        value = jnp.where(head, value, jnp.pad(value[tuple(keep)], shift))
        head = head | jnp.pad(head[tuple(keep)], shift)
        d *= 2
    return head, value


def _run_fill(head: jnp.ndarray, value: jnp.ndarray) -> jnp.ndarray:
    """``value`` of the nearest ``head`` at or before each position (a
    segmented copy scan; positions before the first head are undefined).

    Two levels of shift-and-select steps, inside ``_FILL_COLS``-wide rows
    and once over the rows' last columns: 0.27 ms at 937,984 slots where
    ``lax.associative_scan``, which recurses over stride-2 slices of a
    1-D array, takes 2.0 ms (my chip run, PR 29, scripts/induce_micro.py).
    """
    n = head.shape[0]
    rows = -(-n // _FILL_COLS)
    grid = lambda a: jnp.pad(a, (0, rows * _FILL_COLS - n)).reshape(
        rows, _FILL_COLS)
    head, value = _fill_doubling(grid(head), grid(value), 1)
    # What each row hands on: its last head's value, or what it was handed.
    _, carry = _fill_doubling(head[:, -1], value[:, -1], 0)
    carry = jnp.concatenate([carry[:1], carry[:-1]])
    return jnp.where(head, value, carry[:, None]).reshape(-1)[:n]


def chain_is_sorted(known_last: int, capacity: int) -> bool:
    """Whether a chain of inducer calls (one sampler program; one node
    type of the typed sampler) runs as sorts and scans at every hop.

    ``known_last`` is the static bound on the nodes known before the
    chain's last hop: seeds plus every candidate of the earlier hops.
    The bound grows with the hops, so where it fits the node buffer every
    known node of every hop is sure to sit in ``node_buf`` and no hop
    needs the id map.  Where it does not, a node past the buffer's end is
    remembered by the map alone, and the whole chain keeps the map."""
    return known_last <= capacity


@scoped("glt.sample.induce")
def induce_init(num_nodes: int, capacity: int, known_last: int
                ) -> DenseInduceState:
    """Fresh per-batch state of a chain: no id map (``seen=None``, nothing
    of ``O(num_nodes)`` in the program) where :func:`chain_is_sorted`,
    else :func:`dense_induce_init`'s."""
    if not chain_is_sorted(known_last, capacity):
        return dense_induce_init(num_nodes, capacity)
    return DenseInduceState(
        seen=None,
        node_buf=jnp.full((capacity + 1,), -1, jnp.int32),
        count=jnp.zeros((), jnp.int32),
    )


def sorted_slots(state: DenseInduceState, known: int, m: int) -> int:
    """Keys the sorted inducer handles for a call of static shape,
    ``known + m``, or 0 where the chain of ``state`` keeps the id map (the
    ``glt.sample.induce_sorted_slots`` gauge)."""
    return known + m if state.seen is None else 0


def record_sorted_slots(hop: int, slots: int) -> None:
    """Engagement of the sorted inducer, a trace-time fact the samplers
    record when their program is built: the static :func:`sorted_slots`
    of hop ``hop`` (0 the seeds' own dedup; summed over node types in the
    typed sampler), 0 where the chain kept the id map."""
    _metrics.gauge("glt.sample.induce_sorted_slots", "keys the sorted "
                   "inducer handles in the last sampler built",
                   {"hop": str(hop)}).set(slots)


def induce(state: DenseInduceState, cand: jnp.ndarray, known: int,
           last: bool) -> tuple:
    """One hop of a chain, :func:`dense_induce`'s contract bit for bit:
    ``local``, ``node_buf``, ``count``, ids numbered past the capacity
    and dropped from the store, ``-1`` padding.  The form is the
    chain's, chosen once at trace time from static shapes
    (:func:`induce_init`), never per hop.

    ``known`` is the caller's static bound on ``state.count``: 0 for the
    seeds' own dedup, else seeds plus every candidate of the earlier
    hops.  A sorted chain runs the hop as sorts and scans over
    ``node_buf[:known] ++ cand`` (:func:`_sorted_induce`) and has no id
    map to touch.  On the chip (scripts/induce_micro.py): the last hop
    takes 4.2-4.6 ms where the map form takes 20.1-22.5 at the GraphSAGE
    cells' 169,984 + 768,000 keys, 0.57-0.58 against 0.90-1.42 at the
    typed cell's 39,840-67,712 (my chip run, PR 29); the hops before it
    are in PERF.md section 6 (PR 33).  The two forms agree bit for bit at
    every one of those widths.  A map chain runs :func:`dense_induce`,
    and :func:`dense_induce_final` at its ``last`` hop, after which the
    returned ``state.seen`` MUST NOT be fed to another call.
    """
    if state.seen is None:
        return _sorted_induce(state, cand, known)
    return (dense_induce_final if last else dense_induce)(state, cand)


@scoped("glt.sample.induce")
def _sorted_induce(state: DenseInduceState, cand: jnp.ndarray,
                   known: int) -> tuple:
    """:func:`dense_induce` without the id map or a random pass: four
    sorts, a segmented scan and one contiguous store (see
    :func:`induce`).  New nodes land at ``count`` in first-occurrence
    order, so the next frontier is still the slice of ``node_buf`` there.
    """
    seen, node_buf, count = state
    cap = node_buf.shape[0] - 1
    if known > cap:
        raise ValueError(f"the sorted inducer reads its known nodes from "
                         f"the node buffer: bound {known} past capacity "
                         f"{cap}")
    m = cand.shape[0]
    n = known + m
    cand = cand.astype(jnp.int32)
    ids = jnp.concatenate([node_buf[:known], cand])
    keys = jnp.where(ids >= 0, ids, _INT32_MAX)          # padding last
    pos = jnp.arange(n, dtype=jnp.int32)

    # No sort here needs to be stable: every key that matters is distinct
    # (XLA's stable sort carries one more operand to break ties by).
    sort = functools.partial(jax.lax.sort, is_stable=False)
    # Sort 1, by (id, position): equal ids form a run whose head is the
    # earliest position — a buffer slot (its local id) where the node is
    # known, else the candidate that saw it first.
    sk, sp = sort((keys, pos), num_keys=2)
    head = (sk != jnp.concatenate([jnp.full((1,), -1, jnp.int32), sk[:-1]])
            ) & (sk != _INT32_MAX)
    new_head = head & (sp >= known)
    num_new = jnp.sum(new_head.astype(jnp.int32))

    # Sort 2, new heads by position (all else behind them, in any order):
    # the front is the new stretch of node_buf in first-occurrence order
    # and the index in it the rank.  Sort 3 carries the rank back to sort
    # 1's order.
    _, new_ids, back = sort(
        (jnp.where(new_head, sp, _INT32_MAX), sk, pos), num_keys=1)
    _, rank = sort((back, pos), num_keys=1)

    # One segmented fill hands each head's local id to its run; sort 4
    # returns to candidate order.
    run_local = _run_fill(head, jnp.where(new_head, count + rank, sp))
    _, local = sort((sp, run_local), num_keys=1)
    local = jnp.where(cand >= 0, local[known:], -1)

    # Slots from `count` on hold -1, so the store is one [m] window at
    # `count` (padded: dynamic_update_slice clamps its start); ids that
    # land past the capacity are numbered above and dropped here.
    window = jnp.where(jnp.arange(m, dtype=jnp.int32) < num_new,
                       new_ids[:m], -1)
    stored = jax.lax.dynamic_update_slice(
        jnp.concatenate([node_buf[:cap], jnp.full((m,), -1, jnp.int32)]),
        window, (count,))
    node_buf = jnp.concatenate([stored[:cap], node_buf[cap:]])
    return DenseInduceState(seen, node_buf, count + num_new), local


@scoped("glt.sample.induce")
def relabel_by_reference(reference_ids: jnp.ndarray, query_ids: jnp.ndarray) -> jnp.ndarray:
    """Map each ``query_id`` to its position in ``reference_ids``.

    ``reference_ids`` must be a -1-padded first-occurrence-unique list (as
    produced by :func:`unique_first_occurrence`); every valid query id must
    appear in it.  Returns -1 for padding queries.  This replaces the
    reference's persistent per-batch hash-table lookups
    (include/hash_table.cuh:43-55) with a sort-free searchsorted pass.
    """
    m = reference_ids.shape[0]
    ref_keys = jnp.where(reference_ids >= 0, reference_ids, _INT32_MAX)
    order = jnp.argsort(ref_keys)
    sorted_ref = ref_keys[order]
    q = jnp.where(query_ids >= 0, query_ids, _INT32_MAX - 1)
    pos = jnp.searchsorted(sorted_ref, q)
    pos = jnp.clip(pos, 0, m - 1)
    hit = sorted_ref[pos] == q
    local = jnp.where(hit, order[pos], -1)
    return jnp.where(query_ids >= 0, local, -1).astype(jnp.int32)
