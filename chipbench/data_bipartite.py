"""Taobao's user-item graph of ``bipartite-sage-taobao``, made on the
device from ``--seed``, and the program's containers built from it.

Three relations, as upstream's example holds them after
``T.ToUndirected`` and its co-interaction step:

* ``('user', 'to', 'item')``: the training behaviours.  Out-degrees of
  the users follow ``gen_hetero``'s Lomax law (the relation's
  ``min_degree``, ``max_degree``; ``gen_hetero._relation_program``
  makes the row pointers, summed to the file's count exactly).  Each
  edge's item is drawn in proportion to an integer popularity weight
  per item, itself a Lomax draw of the same exponent with the mean
  ``num_edges / items`` (``item_max_degree`` caps it): position ``p``
  takes the item whose slot of the weights' running sum holds
  ``mix32(p ^ key) % total``.  The draw is integer only, so the
  reference recomputes any edge from its position and the weights.
* ``('item', 'rev_to', 'user')``: exactly its transpose (a stable sort
  of the edges by item, as ``gen_hetero`` makes a transpose).
* ``('item', 'to', 'item')``: ``gen_hetero``'s symmetric relation:
  the file's pairs, each stored in both directions.

Everything is fetched to the host once and handed to the public
``Graph`` constructor, relation by relation, as ``data_hetero`` does.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from chipbench import draws
from chipbench import gen_hetero

UI = ("user", "to", "item")
IU = ("item", "rev_to", "user")
II = ("item", "to", "item")
_WEIGHT, _PICK = 21, 22         # streams beside gen_hetero's (11-15)


def relations(config: dict):
    """``(user->item, item->item)`` as ``gen_hetero.Relation`` s.  Built
    here, not by ``gen_hetero.relations_of``: its rank scramble bounds
    the destination type at 2^21 nodes, and the items are 4.16 M (the
    user->item relation draws its items by weight instead; the
    item->item draw's ``rank * stride`` stays under 2^32)."""
    d = config["data"]
    counts = d["node_types"]
    alpha = float(config["assumed"]["degree_alpha"])
    out = []
    for i, r in enumerate(d["relations"]):
        s, _, t = r["type"]
        rel = gen_hetero.Relation(
            index=i, etype=tuple(r["type"]), transpose=None,
            num_src=int(counts[s]), num_dst=int(counts[t]),
            drawn_edges=int(r["num_edges"]), self_loops=False,
            symmetric=bool(r.get("symmetric", False)),
            min_degree=int(r["min_degree"]), max_degree=int(r["max_degree"]),
            alpha=alpha)
        if rel.num_edges >= 2 ** 31 or rel.num_dst * rel.stride >= 2 ** 32:
            raise ValueError(f"{rel.etype}: counters are 32 bit")
        out.append(rel)
    ui, ii = out
    if ui.etype != UI or ii.etype != II or not ii.symmetric:
        raise ValueError("relations: user->item, then item->item symmetric")
    return ui, ii


def item_weights(config: dict, seed: int):
    """Integer popularity weights of the items (a device array): Lomax,
    at least 1, mean ``num_edges / items``, capped at
    ``item_max_degree``.  Their running sum is under 2^31."""
    import jax
    import jax.numpy as jnp

    ui = config["data"]["relations"][0]
    n = int(config["data"]["node_types"]["item"])
    alpha = float(config["assumed"]["degree_alpha"])
    cap = int(ui["item_max_degree"])
    scale = gen_hetero.lomax_scale(int(ui["num_edges"]) / n, alpha, 1, cap)

    @jax.jit
    def make(key):
        node = jnp.arange(n, dtype=jnp.uint32)
        u = draws.unit_open(draws.mix32(node ^ key), jnp)
        raw = 1 + jnp.floor(jnp.float32(scale) * (
            jnp.exp(-jnp.log1p(-u) / jnp.float32(alpha)) - 1.0))
        return jnp.minimum(raw, float(cap)).astype(jnp.int32)

    return make(np.uint32(draws.stream_key(seed, _WEIGHT)))


def pick_key(seed: int) -> np.uint32:
    return np.uint32(draws.stream_key(seed, _PICK))


def picked_items(pos, cum, key, xp):
    """The item of user->item edge position ``pos`` (uint32): where
    ``mix32(pos ^ key) % total`` falls in the weights' running sum
    ``cum`` (inclusive, int32).  The same under numpy and jax.numpy."""
    u = draws.mix32(pos ^ key) % cum[-1].astype(xp.uint32)
    return xp.searchsorted(cum, u.astype(cum.dtype), side="right")


def _user_item_program(num_items: int, num_edges: int):
    """``(indptr, cum, key) -> (indices, rev_indptr, rev_indices, order)``:
    the items of every edge and the transpose."""
    import jax.numpy as jnp

    def body(indptr, cum, key):
        marks = jnp.zeros((num_edges,), jnp.int32).at[indptr[1:-1]].add(
            1, mode="drop")
        src = jnp.cumsum(marks)
        pos = jnp.arange(num_edges, dtype=jnp.uint32)
        dst = picked_items(pos, cum, key, jnp).astype(jnp.int32)
        order = jnp.argsort(dst, stable=True).astype(jnp.int32)
        rev_indptr = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(jnp.bincount(dst, length=num_items))
             .astype(jnp.int32)])
        return dst, rev_indptr, jnp.take(src, order), order

    return body


def generate(config: dict, seed: int):
    """``{etype: gen_hetero.MadeRelation}`` (host arrays) and the items'
    weights' running sum (host, int32)."""
    import jax
    import jax.numpy as jnp

    ui, ii = relations(config)
    made = {}
    # User->item: gen_hetero's row pointers (its own draw of the items,
    # by rank, is dropped), then the items by weight and the transpose.
    mean = ui.drawn_edges / ui.num_src
    scale = gen_hetero.lomax_scale(mean, ui.alpha, ui.min_degree,
                                   ui.max_degree)
    indptr, _, short = jax.jit(gen_hetero._relation_program(ui, scale))(
        gen_hetero.relation_keys(ui, seed))
    if int(short):
        raise ValueError(f"{ui.etype}: degrees do not sum to the count")
    cum = jnp.cumsum(item_weights(config, seed))
    out = jax.jit(_user_item_program(ui.num_dst, ui.forward_edges))(
        indptr, cum, pick_key(seed))
    made[UI] = gen_hetero.MadeRelation(np.asarray(indptr),
                                       *(np.asarray(a) for a in out))
    made[II] = gen_hetero.generate_relation(ii, seed)
    return made, np.asarray(cum)


class Taobao(NamedTuple):
    graphs: dict            # edge type -> glt_tpu.data.Graph
    ref: object             # reference_bipartite.RefBipartite


def build(config: dict, seed: int, log=lambda msg: None) -> Taobao:
    """Generate on the default device, fetch, and place the three
    relations (user->item with its column-sorted view for the strict
    negatives)."""
    import jax

    from chipbench import reference_bipartite
    from glt_tpu.data import CSRTopo, Graph

    t0 = time.perf_counter()
    made, cum = generate(config, seed)
    t1 = time.perf_counter()
    m_ui, m_ii = made[UI], made[II]
    graphs = {}
    for etype, indptr, indices in ((UI, m_ui.indptr, m_ui.indices),
                                   (IU, m_ui.rev_indptr, m_ui.rev_indices),
                                   (II, m_ii.indptr, m_ii.indices)):
        graphs[etype] = Graph(CSRTopo.from_csr_arrays(
            indptr, indices,
            edge_ids=np.arange(indices.shape[0], dtype=np.int32)))
        jax.block_until_ready(graphs[etype].indices)        # placed lazily
    jax.block_until_ready(graphs[UI].sorted_indices)
    log(f"3 relations made in {t1 - t0:.2f} s, placed with the sorted "
        f"view in {time.perf_counter() - t1:.2f} s")
    ref = reference_bipartite.RefBipartite(relations(config), seed, made,
                                           cum)
    return Taobao(graphs, ref)
