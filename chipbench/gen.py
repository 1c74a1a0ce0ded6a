"""Seeded graph, feature and label generator, made on the device.

Data takes the place of weights: one jitted call makes, from ``--seed``
and a configuration file's shapes, the CSR blocks, the feature rows and
the labels of every shard, each shard on its own chip
(``shard_map`` over a 1-D mesh; one chip is the mesh of one).

Every draw is counter based (``draws.py``): the neighbour stored at
global edge position ``p`` is a function of ``(seed, p)`` and of the
row pointers alone, feature ``[v, j]`` of ``(seed, v * d + j)``, the
label of ``(seed, v)``.  ``reference.py`` recomputes any adjacency list
or row from those without holding a table.

* Degrees follow a Lomax (Pareto II) law, ``P(deg > x) = (1 + x / L)^-a``
  truncated at ``max_degree``; ``L`` is solved on the host so the mean is
  the file's.  The method is ``benchmarks/graph_gen.py``'s
  (power-law degrees, prefix-sum ``indptr``, O(E)); the sizes are the
  source's.  Each shard's sequence is then adjusted to sum to
  ``num_edges / num_shards`` exactly, so shapes (and compiled programs)
  are the same for every seed.
* Neighbour ids are drawn in proportion to degree: edge position ``p``
  points at the source node of a uniformly drawn edge position ``q``, so
  in-degree follows the same law as out-degree and hubs repeat inside a
  batch (uniform ids, as ``graph_gen.py`` draws them, understate the
  duplication that dedup and every gather width depend on).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from chipbench import draws


STREAMS = (draws.DEGREE, draws.NEIGHBOUR, draws.FEATURE, draws.LABEL)


class Shapes(NamedTuple):
    """What a configuration file fixes about the data."""
    num_nodes: int
    num_edges: int
    feature_dim: int
    num_classes: int
    num_shards: int
    mean_degree: float
    max_degree: int
    degree_alpha: float

    @property
    def nodes_per_shard(self) -> int:
        return -(-self.num_nodes // self.num_shards)

    @property
    def edges_per_shard(self) -> int:
        return self.num_edges // self.num_shards


def shapes_of(config: dict, num_shards: int) -> Shapes:
    data = config["data"]
    sh = Shapes(
        num_nodes=int(data["num_nodes"]), num_edges=int(data["num_edges"]),
        feature_dim=int(data["feature_dim"]),
        num_classes=int(data["num_classes"]), num_shards=int(num_shards),
        mean_degree=float(data["num_edges"]) / float(data["num_nodes"]),
        max_degree=int(data["max_degree"]),
        degree_alpha=float(config["assumed"]["degree_alpha"]))
    if sh.num_edges % sh.num_shards:
        raise ValueError(
            f"num_edges {sh.num_edges} is not a multiple of the "
            f"{sh.num_shards} shards: every shard holds the same edge "
            f"count so that shapes do not depend on the seed")
    if sh.num_edges >= 2 ** 31 or \
            sh.nodes_per_shard * sh.num_shards * sh.feature_dim >= 2 ** 32:
        raise ValueError("counters are 32 bit: the configuration is too "
                         "large for this generator")
    return sh


def lomax_scale(mean_degree: float, alpha: float, max_degree: int) -> float:
    """``L`` such that ``1 + floor(L * ((1-u)^(-1/a) - 1))`` capped at
    ``max_degree`` has the wanted mean, by bisection over a quantile
    grid.  A count, so the host computes it."""
    u = (np.arange(1 << 16, dtype=np.float64) + 0.5) / (1 << 16)
    tail = np.exp(-np.log1p(-u) / alpha) - 1.0

    def mean(scale):
        return float(np.minimum(max_degree,
                                1.0 + np.floor(scale * tail)).mean())

    lo, hi = 1e-3, float(max_degree)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if mean(mid) < mean_degree else (lo, mid)
    return 0.5 * (lo + hi)


def _shard_body(sh: Shapes, scale: float, with_edge_ids: bool):
    """The per-shard program (runs inside ``shard_map``).  The seed comes
    in as an argument (the four stream keys), so one compiled program
    serves every seed."""
    import jax.numpy as jnp
    from jax import lax

    c, es, d = sh.nodes_per_shard, sh.edges_per_shard, sh.feature_dim

    def body(keys):
        k_deg, k_nbr, k_feat, k_lab = keys[0], keys[1], keys[2], keys[3]
        s = lax.axis_index("shard").astype(jnp.uint32)
        local = jnp.arange(c, dtype=jnp.uint32)
        node = s * jnp.uint32(c) + local
        live = node < jnp.uint32(sh.num_nodes)

        # Degrees: Lomax by inverse CDF, then made to sum to ``es``.
        u = draws.unit_open(draws.mix32(node ^ k_deg), jnp)
        raw = 1.0 + jnp.floor(jnp.float32(scale) * (
            jnp.exp(-jnp.log1p(-u) / jnp.float32(sh.degree_alpha)) - 1.0))
        deg = jnp.where(live, jnp.minimum(raw, float(sh.max_degree)),
                        0).astype(jnp.int32)
        diff = jnp.int32(es) - jnp.sum(deg)
        n_live = jnp.sum(live.astype(jnp.int32))
        # Too few edges: spread the shortfall over the live nodes.  Too
        # many: lower every degree to at most ``level`` below itself
        # (never under 1), the smallest level that sheds the excess, the
        # last level taken from the first nodes only.
        up = jnp.maximum(diff, 0)
        add = up // n_live + (local.astype(jnp.int32) < up % n_live)
        excess = jnp.maximum(-diff, 0)
        room = jnp.maximum(deg - 1, 0)

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = (lo + hi) // 2
            enough = jnp.sum(jnp.minimum(room, mid)) >= excess
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

        _, level = lax.fori_loop(0, 32, halve,
                                 (jnp.int32(0), jnp.int32(sh.max_degree)))
        base = jnp.minimum(room, jnp.maximum(level - 1, 0))
        last = room >= level
        sub = base + (last & (jnp.cumsum(last.astype(jnp.int32))
                              <= excess - jnp.sum(base))).astype(jnp.int32)
        deg = jnp.where(live, deg + add - sub, 0)
        short = jnp.int32(es) - jnp.sum(deg)          # 0 when it worked

        indptr = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(deg)])
        # Source node (local) of every local edge position: a mark where
        # each row starts, then a prefix sum (no gather; rows of padding
        # nodes start past the end and are dropped).
        marks = jnp.zeros((es,), jnp.int32).at[indptr[1:-1]].add(
            1, mode="drop")
        src_local = jnp.cumsum(marks)
        src_all = lax.all_gather(src_local, "shard").reshape(-1)

        pos = s * jnp.uint32(es) + jnp.arange(es, dtype=jnp.uint32)
        q = draws.mix32(pos ^ k_nbr) % jnp.uint32(sh.num_edges)
        q = q.astype(jnp.int32)
        indices = (q // es) * c + jnp.take(src_all, q, axis=0)

        cnt = (node[:, None] * jnp.uint32(d)
               + jnp.arange(d, dtype=jnp.uint32)[None, :])
        rows = draws.unit_signed(draws.mix32(cnt ^ k_feat), jnp)
        labels = jnp.where(
            live, (draws.mix32(node ^ k_lab)
                   % jnp.uint32(sh.num_classes)).astype(jnp.int32), -1)
        out = (indptr[None], indices[None], rows[None], labels[None],
               short[None])
        if with_edge_ids:
            out += (pos.astype(jnp.int32)[None],)
        return out

    return body


class Generated(NamedTuple):
    """Device arrays with a leading shard axis, placed one shard a chip."""
    indptr: object      # [S, c + 1] int32, 0-based within the shard
    indices: object     # [S, E / S] int32 global neighbour ids
    rows: object        # [S, c, d]  float32
    labels: object      # [S, c]     int32, -1 on padding rows
    edge_ids: object    # [S, E / S] int32 global edge positions, or None


def generate(sh: Shapes, seed: int, mesh, with_edge_ids: bool) -> Generated:
    """Make the whole data set on ``mesh`` (axis ``shard``) in one call."""
    import jax
    from jax.sharding import PartitionSpec as P

    scale = lomax_scale(sh.mean_degree, sh.degree_alpha, sh.max_degree)
    n_out = 6 if with_edge_ids else 5
    fn = jax.jit(jax.shard_map(
        _shard_body(sh, scale, with_edge_ids), mesh=mesh,
        in_specs=(P(),), out_specs=(P("shard"),) * n_out, check_vma=False))
    out = fn(np.asarray([draws.stream_key(seed, s) for s in STREAMS],
                        np.uint32))
    short = np.asarray(out[4])
    if short.any():
        raise ValueError(
            f"the degree sequence could not be made to sum to the file's "
            f"edge count (left over per shard: {short.tolist()})")
    return Generated(out[0], out[1], out[2], out[3],
                     out[5] if with_edge_ids else None)


def train_seeds(sh: Shapes, seed: int, count: int) -> np.ndarray:
    """``[S, count // S]`` training seeds, each shard's drawn from its
    own live nodes (host: a few hundred thousand ids)."""
    rng = np.random.default_rng([int(seed), 7])
    per = count // sh.num_shards
    c = sh.nodes_per_shard
    out = np.empty((sh.num_shards, per), np.int64)
    for s in range(sh.num_shards):
        live = min(c, sh.num_nodes - s * c)
        out[s] = s * c + rng.choice(live, size=per, replace=False)
    return out
