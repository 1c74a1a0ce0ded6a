"""Seeded heterogeneous graph, feature and label generator, made on the
device: ``gen.py``'s method for several node types and typed relations.

A configuration lists node types with their counts and *forward*
relations ``(src, rel, dst)`` with their edge counts; a relation that
names a ``transpose`` also gives ``(dst, transpose, src)``, which holds
exactly the forward relation's edges (made by a stable sort of the
forward edges by destination, so ``order[i]`` is the forward edge
position of transposed slot ``i``).  A ``symmetric`` relation (source
and destination type the same) is stored as both directions in one CSR,
as upstream's ``dataset.py`` concatenates ``cites`` with its transpose
after ``add_self_loops``: row ``v`` is its forward row followed by its
transposed row, ``2 * (num_edges + self loops)`` edges in all.

Every draw is counter based (``draws.py``) and integer only, so
``reference_hetero.py`` recomputes any adjacency list or row from
positions alone:

* Out-degrees of a forward relation follow ``gen.py``'s Lomax law
  (``min_degree + floor(L * tail)``, capped, then adjusted to sum to the
  file's edge count exactly).  With ``self_loops`` every source row
  starts with the node itself (upstream's ``dataset.py`` adds one self
  loop a paper) on top of the drawn edges.
* Destinations are drawn in proportion to a per-type weight: the
  destination's popularity rank ``r`` has density ``~ r^(-1/2)`` (a
  two-level draw: level ``l`` with probability ``2^-(l+1)`` picks the
  ranks ``[N >> 2(l+1), N >> 2l)``, uniform inside), and rank ``r`` is
  node ``r * stride mod N`` so that popular nodes are spread over the id
  space.  The in-degree law is the transposed relation's out-degree law.
* Feature ``[v, j]`` of a type is ``k / 128`` for an 8-bit ``k`` drawn
  from ``(seed, type, v * d + j)``: exact in bfloat16, so the device and
  the host agree bit for bit in either dtype.  Labels are uniform.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from chipbench import draws

# Stream ids: beside draws.DEGREE.. (1-4); a relation's and a type's
# index are folded in by ``stream()``.
_DEGREE, _LEVEL, _RANK, _FEATURE, _LABEL = 11, 12, 13, 14, 15
_STRIDES = (1021, 1019, 1013, 1009, 997)


def stream(kind: int, index: int) -> int:
    return kind + 32 * (index + 1)


class Relation(NamedTuple):
    """One forward relation of a configuration file."""
    index: int
    etype: tuple            # (src type, name, dst type)
    transpose: Optional[str]
    num_src: int
    num_dst: int
    drawn_edges: int        # the file's edge count
    self_loops: bool
    symmetric: bool
    min_degree: int
    max_degree: int
    alpha: float

    @property
    def forward_edges(self) -> int:
        return self.drawn_edges + (self.num_src if self.self_loops else 0)

    @property
    def num_edges(self) -> int:
        """Edges of the stored CSR."""
        return self.forward_edges * (2 if self.symmetric else 1)

    @property
    def rev_etype(self):
        s, _, d = self.etype
        return None if self.transpose is None else (d, self.transpose, s)

    @property
    def levels(self) -> int:
        """Levels of the destination draw: ``N >> 2 * levels >= 1``."""
        return max((int(self.num_dst).bit_length() - 1) // 2, 0)

    @property
    def stride(self) -> int:
        return next(s for s in _STRIDES if self.num_dst % s)


def relations_of(config: dict):
    counts = config["data"]["node_types"]
    out = []
    for i, r in enumerate(config["data"]["relations"]):
        s, _, d = r["type"]
        rel = Relation(
            index=i, etype=tuple(r["type"]), transpose=r.get("transpose"),
            num_src=int(counts[s]), num_dst=int(counts[d]),
            drawn_edges=int(r["num_edges"]),
            self_loops=bool(r.get("self_loops", False)),
            symmetric=bool(r.get("symmetric", False)),
            min_degree=int(r["min_degree"]), max_degree=int(r["max_degree"]),
            alpha=float(config["assumed"]["degree_alpha"]))
        if rel.num_edges >= 2 ** 31 or rel.num_dst >= 2 ** 21:
            raise ValueError(f"{rel.etype}: counters are 32 bit and the "
                             f"rank scramble needs N < 2^21")
        if rel.symmetric and (s != d or rel.transpose is not None):
            raise ValueError(f"{rel.etype}: a symmetric relation joins one "
                             f"type and names no transpose")
        out.append(rel)
    return out


def lomax_scale(mean_degree: float, alpha: float, min_degree: int,
                max_degree: int) -> float:
    """``L`` such that ``min_degree + floor(L * ((1-u)^(-1/a) - 1))``
    capped at ``max_degree`` has the wanted mean (``gen.lomax_scale``
    with a floor other than 1)."""
    u = (np.arange(1 << 16, dtype=np.float64) + 0.5) / (1 << 16)
    tail = np.exp(-np.log1p(-u) / alpha) - 1.0

    def mean(scale):
        return float(np.minimum(max_degree,
                                min_degree + np.floor(scale * tail)).mean())

    lo, hi = 1e-3, float(max_degree)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if mean(mid) < mean_degree else (lo, mid)
    return 0.5 * (lo + hi)


def destinations(rel: Relation, pos, k_level, k_rank, xp):
    """Destination id of every edge position ``pos`` (uint32), integer
    operations only; the same under numpy and jax.numpy."""
    n = xp.uint32(rel.num_dst)
    h = draws.mix32(pos ^ k_level)
    level = xp.zeros(pos.shape, xp.uint32)
    for j in range(1, rel.levels + 1):      # leading zeros, capped
        level = level + (h < xp.uint32(1 << (32 - j))).astype(xp.uint32)
    hi = n >> (xp.uint32(2) * level)
    lo = xp.where(level < xp.uint32(rel.levels),
                  n >> (xp.uint32(2) * level + xp.uint32(2)), xp.uint32(0))
    rank = lo + draws.mix32(pos ^ k_rank) % (hi - lo)
    return (rank * xp.uint32(rel.stride)) % n


def _relation_program(rel: Relation, scale: float):
    """``keys -> (indptr, indices, short[, rev_indptr, rev_indices,
    order[, fwd_indptr]])`` for one forward relation; the seed is an
    argument.  With ``symmetric`` the first two are the merged CSR and
    ``fwd_indptr`` the forward relation's row pointers."""
    import jax.numpy as jnp
    from jax import lax

    n, e = rel.num_src, rel.forward_edges
    loops = int(rel.self_loops)

    def body(keys):
        k_deg, k_level, k_rank = keys[0], keys[1], keys[2]
        node = jnp.arange(n, dtype=jnp.uint32)
        u = draws.unit_open(draws.mix32(node ^ k_deg), jnp)
        raw = rel.min_degree + jnp.floor(jnp.float32(scale) * (
            jnp.exp(-jnp.log1p(-u) / jnp.float32(rel.alpha)) - 1.0))
        deg = jnp.minimum(raw, float(rel.max_degree)).astype(jnp.int32)
        # Make the drawn degrees sum to the file's count (gen.py's way:
        # spread a shortfall over the nodes; shed an excess by lowering
        # every degree to at most ``level`` below itself).
        diff = jnp.int32(rel.drawn_edges) - jnp.sum(deg)
        up = jnp.maximum(diff, 0)
        add = up // n + (node.astype(jnp.int32) < up % n)
        excess = jnp.maximum(-diff, 0)
        room = jnp.maximum(deg - rel.min_degree, 0)

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = (lo + hi) // 2
            enough = jnp.sum(jnp.minimum(room, mid)) >= excess
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

        _, level = lax.fori_loop(0, 32, halve,
                                 (jnp.int32(0), jnp.int32(rel.max_degree)))
        base = jnp.minimum(room, jnp.maximum(level - 1, 0))
        last = room >= level
        sub = base + (last & (jnp.cumsum(last.astype(jnp.int32))
                              <= excess - jnp.sum(base))).astype(jnp.int32)
        deg = deg + add - sub + loops
        short = jnp.int32(e) - jnp.sum(deg)              # 0 when it worked

        indptr = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(deg)])
        marks = jnp.zeros((e,), jnp.int32).at[indptr[1:-1]].add(
            1, mode="drop")
        src = jnp.cumsum(marks)
        pos = jnp.arange(e, dtype=jnp.uint32)
        dst = destinations(rel, pos, k_level, k_rank, jnp).astype(jnp.int32)
        if loops:
            first = pos.astype(jnp.int32) == jnp.take(indptr, src)
            dst = jnp.where(first, src, dst)
        out = (indptr, dst, short)
        if rel.transpose is not None or rel.symmetric:
            order = jnp.argsort(dst, stable=True).astype(jnp.int32)
            rev_indptr = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 jnp.cumsum(jnp.bincount(dst, length=rel.num_dst))
                 .astype(jnp.int32)])
            rev_src = jnp.take(src, order)
            out += (rev_indptr, rev_src, order)
        if rel.symmetric:
            # Row v: its forward row, then its transposed row.
            both = indptr + rev_indptr
            at = pos.astype(jnp.int32)
            fwd_slot = jnp.take(both, src) + at - jnp.take(indptr, src)
            row = jnp.take(dst, order)      # the transposed slots' rows
            rev_slot = (jnp.take(both, row) + jnp.take(deg, row)
                        + at - jnp.take(rev_indptr, row))
            merged = (jnp.zeros((2 * e,), jnp.int32)
                      .at[fwd_slot].set(dst).at[rev_slot].set(rev_src))
            out = (both, merged, short) + out[3:] + (indptr,)
        return out

    return body


class MadeRelation(NamedTuple):
    """Host arrays of one forward relation (and its transpose)."""
    indptr: np.ndarray
    indices: np.ndarray
    rev_indptr: Optional[np.ndarray]
    rev_indices: Optional[np.ndarray]
    order: Optional[np.ndarray]     # forward position of transposed slot
    fwd_indptr: Optional[np.ndarray] = None     # symmetric: forward rows


def relation_keys(rel: Relation, seed: int) -> np.ndarray:
    return np.asarray([draws.stream_key(seed, stream(k, rel.index))
                       for k in (_DEGREE, _LEVEL, _RANK)], np.uint32)


def generate_relation(rel: Relation, seed: int) -> MadeRelation:
    """One relation on the default device, fetched to the host."""
    import jax

    mean = rel.drawn_edges / rel.num_src
    scale = lomax_scale(mean, rel.alpha, rel.min_degree, rel.max_degree)
    out = jax.jit(_relation_program(rel, scale))(relation_keys(rel, seed))
    out = [np.asarray(a) for a in out]
    if int(out[2]):
        raise ValueError(f"{rel.etype}: the degree sequence could not be "
                         f"made to sum to the file's edge count "
                         f"(left over: {int(out[2])})")
    return MadeRelation(out[0], out[1], *(out[3:] or (None, None, None)))


def feature_key(type_index: int, seed: int) -> np.uint32:
    return np.uint32(draws.stream_key(seed, stream(_FEATURE, type_index)))


def label_key(seed: int) -> np.uint32:
    return np.uint32(draws.stream_key(seed, stream(_LABEL, 0)))


def feature_values(nodes, dim: int, key, xp):
    """Float32 rows ``[len(nodes), dim]`` of ``k / 128``."""
    cnt = (nodes.astype(xp.uint32)[:, None] * xp.uint32(dim)
           + xp.arange(dim, dtype=xp.uint32)[None, :])
    k = (draws.mix32(cnt ^ key) >> 24).astype(xp.float32)
    return (k - xp.float32(128.0)) * xp.float32(2.0 ** -7)


def generate_features(num_nodes: int, dim: int, dtype: str, type_index: int,
                      seed: int):
    """``[num_nodes, dim]`` rows of one type, a device array."""
    import jax
    import jax.numpy as jnp

    if num_nodes * dim >= 2 ** 32:
        raise ValueError("counters are 32 bit: the table is too large")

    @jax.jit
    def make(key):
        return feature_values(jnp.arange(num_nodes, dtype=jnp.uint32), dim,
                              key, jnp).astype(jnp.dtype(dtype))

    return make(feature_key(type_index, seed))


def label_values(nodes, num_classes: int, key, xp):
    return (draws.mix32(nodes.astype(xp.uint32) ^ key)
            % xp.uint32(num_classes)).astype(xp.int32)
