"""The comparison that decides ``correct``.

``check_batch`` and ``check_reply`` started as copies of
``chip_smoke.py::_check_batch`` and ``_check_reply`` (PR 21): static
shapes, seeds first, ``-1`` / zero padding exactly off the node mask,
features and labels bit for bit, every edge a graph edge, no padding in
a reply.  What they compare against is ``reference.RefData`` (recomputed
from the seed), not a host copy of the tables, and they add the check
the source's sampling semantics need: every node first seen before the
last hop has exactly ``min(degree, fanout)`` sampled out-edges, a
sub-multiset of its adjacency list.  A capped frontier (``frontier_cap``,
"nodes past the cap stay leaves") leaves such nodes with none and fails.
"""
from __future__ import annotations

from collections import Counter

import numpy as np


class CheckFailure(RuntimeError):
    """A check on what the program produced did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailure(msg)


def hop_depth(num_slots: int, seed_slots, row, col, hops: int):
    """Depth of every node slot in the sampled graph, by breadth-first
    search from the seeds along ``col -> row`` (seed side -> neighbour
    side); ``hops + 1`` where not reached within ``hops``."""
    depth = np.full((num_slots,), hops + 1, np.int64)
    depth[seed_slots] = 0
    frontier = np.zeros((num_slots,), bool)
    frontier[seed_slots] = True
    for h in range(hops):
        reached = np.zeros((num_slots,), bool)
        reached[row[frontier[col]]] = True
        frontier = reached & (depth > h + 1)
        depth[frontier] = h + 1
    return depth


def check_sampling(ref, node, row, col, seed_slots, fanouts, what: str,
                   rng, exact_hop: bool = True, sources: int = 256):
    """Sampling semantics on live edges only (``row``/``col`` are local
    slots of ``node``, padding already dropped)."""
    n = node.shape[0]
    hops = len(fanouts)
    depth = hop_depth(n, seed_slots, row, col, hops)
    check(bool((depth[row] <= hops).all() and (depth[col] < hops).all()),
          f"{what}: an edge lies beyond {hops} hops of the seeds")
    out_count = np.bincount(col, minlength=n)
    deg = ref.degree(node)
    inner = np.flatnonzero(depth < hops)
    if exact_hop:
        want = np.minimum(deg[inner], np.asarray(fanouts)[depth[inner]])
        bad = inner[out_count[inner] != want]
        check(bad.size == 0,
              f"{what}: {bad.size} nodes first seen before the last hop do "
              f"not have min(degree, fanout) sampled edges, e.g. node "
              f"{node[bad[:1]]} at hop {depth[bad[:1]]} has "
              f"{out_count[bad[:1]]} of degree {deg[bad[:1]]} (a capped "
              f"frontier or node capacity does this)")
    else:
        # A served reply shares one sample among co-riding requests: a
        # node is expanded at the hop of the request that reached it
        # first, so any hop's fanout is a right answer, none is not.
        allowed = np.stack([np.minimum(deg[inner], f) for f in fanouts])
        ok = (allowed == out_count[inner][None, :]).any(axis=0)
        check(bool(ok.all()),
              f"{what}: {int((~ok).sum())} nodes within {hops - 1} hops "
              f"have no fanout's worth of sampled edges")
    # Sub-multiset of the adjacency list, on a seeded sample of sources.
    order = np.argsort(col, kind="stable")
    starts = np.searchsorted(col[order], np.arange(n + 1))
    pick = rng.choice(inner, size=min(sources, inner.size), replace=False)
    for slot in pick.tolist():
        got = Counter(node[row[order[starts[slot]:starts[slot + 1]]]]
                      .tolist())
        have = Counter(ref.neighbours(int(node[slot])).tolist())
        check(not (got - have),
              f"{what}: node {int(node[slot])} has sampled neighbours "
              f"{dict(got - have)} that its adjacency list does not hold")


def check_batch(ref, batch, batch_size: int, fanouts, what: str, rng,
                with_xy: bool = True) -> None:
    """A padded loader / sampler batch against the reference."""
    node = np.asarray(batch["node"])
    mask = np.asarray(batch["node_mask"])
    check(bool(((node >= 0) == mask).all()),
          f"{what}: node ids are not -1 exactly off the node mask")
    seeds = np.asarray(batch["seeds"])
    live_seed = seeds >= 0
    check(bool((node[:batch_size][live_seed] == seeds[live_seed]).all()),
          f"{what}: seeds do not lead the node list")
    live = node[mask]
    check(np.unique(live).size == live.size,
          f"{what}: the node list repeats an id")
    if with_xy:
        x = np.asarray(batch["x"])
        check(x.shape == (node.shape[0], ref.sh.feature_dim),
              f"{what}: x {x.shape} vs node {node.shape}")
        check(bool((x == ref.features(node)).all()),
              f"{what}: gathered features differ from the generator's "
              f"rows (padding rows must be zero)")
        check(bool((np.asarray(batch["y"]) == ref.labels(node)).all()),
              f"{what}: gathered labels differ from the generator's")
    ei = np.asarray(batch["edge_index"])
    em = np.asarray(batch["edge_mask"])
    row, col = ei[0][em], ei[1][em]
    check(bool((row >= 0).all() and (row < node.shape[0]).all()
               and (col >= 0).all() and (col < node.shape[0]).all()
               and mask[row].all() and mask[col].all()),
          f"{what}: a live edge points at a padding slot")
    check_sampling(ref, node, row, col, np.flatnonzero(live_seed), fanouts,
                   what, rng)


def check_reply(ref, reply, seeds, fanouts, rng) -> None:
    """A served ego-subgraph: request-local, compact, no padding."""
    node = np.asarray(reply.node)
    n, k = node.shape[0], len(seeds)
    what = f"reply to {k} seeds"
    check(reply.batch_size == k
          and np.asarray(reply.batch).tolist() == list(seeds),
          f"{what}: seed block {np.asarray(reply.batch)[:8]}")
    check(node[:k].tolist() == list(seeds), f"{what}: seeds do not lead")
    check(bool((node >= 0).all()) and np.unique(node).size == n,
          f"{what}: node list holds padding or repeats")
    check(bool(np.asarray(reply.node_mask).all()
               and np.asarray(reply.edge_mask).all()),
          f"{what}: a compact reply carries a masked slot")
    x = np.asarray(reply.x)
    check(x.shape == (n, ref.sh.feature_dim), f"{what}: x shape {x.shape}")
    check(bool((x == ref.features(node)).all()),
          f"{what}: features differ from the generator's rows")
    check(bool((np.asarray(reply.y) == ref.labels(node)).all()),
          f"{what}: labels differ")
    ei = np.asarray(reply.edge_index)
    check(ei.shape[0] == 2 and bool((ei >= 0).all() and (ei < n).all()),
          f"{what}: edge index out of range")
    check_sampling(ref, node, ei[0], ei[1], np.arange(k), fanouts, what,
                   rng, exact_hop=False)


def check_logits(got, want, rtol: float, what: str) -> float:
    """Seed logits of the program against the reference's: the
    root-mean-square of the difference over the reference's
    root-mean-square.  Returns it."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{what}: {got.shape} vs {want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite logits")
    err = float(np.sqrt(((got - want) ** 2).mean())
                / max(np.sqrt((want ** 2).mean()), 1e-30))
    check(err <= rtol,
          f"{what}: logits differ from the reference by {err:.3g} of "
          f"their RMS, over the tolerance {rtol:.3g}")
    return err
