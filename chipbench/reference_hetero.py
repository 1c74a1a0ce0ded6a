"""The plain reference of the heterogeneous cells: same semantics, none of
the program's code.

* Data.  ``RefHetero`` holds the forward relations' row pointers and the
  transposes' row pointers and slot orders (fetched once from the
  generator's output) and recomputes adjacency lists, feature rows and
  labels from ``draws.py`` / ``gen_hetero.py``'s integer draws in numpy.
  ``check_transposes`` holds every transposed CSR, and both halves of
  every symmetric relation's rows, to the forward relation edge for edge.
* Sampling.  ``check_hetero_batch``: the guarantees of the
  configuration file, per relation.
* Model.  ``rgnn_seed_logits`` is upstream's ``RGNN('rgat')``
  (examples/igbh/rgnn.py with PyG's bipartite ``GATConv``,
  ``add_self_loops=False``: per relation one ``lin`` on both sides,
  ``leaky_relu(alpha_src[j] + alpha_dst[i], 0.2)``, softmax over each
  destination's incoming edges, heads concatenated, relations summed,
  ``leaky_relu`` between layers) in float32 under
  ``jax.default_matmul_precision("highest")``, relation by relation and
  in row blocks so that it fits beside the tables.  The layers before
  the last run over every row and edge of the batch; the class-wide last
  layer is computed for the seed rows alone, from the edges that end in
  a seed (found by their destination, not by any layout).  Dropout is
  off, as in ``reference.py``.
"""
from __future__ import annotations

from collections import Counter

import numpy as np

from chipbench import draws, gen_hetero
from chipbench.checks import check


class RefHetero:
    """Recomputes what ``gen_hetero`` made, from positions alone."""

    def __init__(self, config: dict, seed: int, made: dict):
        d = config["data"]
        self.counts = {t: int(n) for t, n in d["node_types"].items()}
        self.types = list(self.counts)
        self.dim = int(d["feature_dim"])
        self.num_classes = int(d["num_classes"])
        self.seed = seed
        self.rels = {}          # etype -> (Relation, forward?)
        self.made = {}
        for rel in gen_hetero.relations_of(config):
            m = made[rel.etype]
            self.made[rel.etype] = m
            self.rels[rel.etype] = (rel, True)
            if rel.transpose is not None:
                self.rels[rel.rev_etype] = (rel, False)
        self._k_lab = gen_hetero.label_key(seed)

    # -- adjacency ----------------------------------------------------------
    def _indptr(self, etype):
        rel, fwd = self.rels[etype]
        m = self.made[rel.etype]
        return m.indptr if fwd else m.rev_indptr

    def _forward_indptr(self, rel):
        m = self.made[rel.etype]
        return m.fwd_indptr if rel.symmetric else m.indptr

    def degree(self, etype, nodes) -> np.ndarray:
        ip = self._indptr(etype)
        nodes = np.asarray(nodes, np.int64)
        return (ip[nodes + 1] - ip[nodes]).astype(np.int64)

    def forward_edges(self, rel, pos) -> tuple:
        """``(src, dst)`` of forward edge positions ``pos``."""
        pos = np.asarray(pos, np.int64)
        ip = self._forward_indptr(rel)
        src = np.searchsorted(ip, pos, side="right") - 1
        keys = gen_hetero.relation_keys(rel, self.seed)
        dst = gen_hetero.destinations(rel, pos.astype(np.uint32), keys[1],
                                      keys[2], np).astype(np.int64)
        if rel.self_loops:
            dst = np.where(pos == ip[src], src, dst)
        return src, dst

    def neighbours(self, etype, node: int) -> np.ndarray:
        """The adjacency list of ``node`` under ``etype`` (CSR order)."""
        rel, fwd = self.rels[etype]
        m = self.made[rel.etype]

        def row(ip):
            return np.arange(ip[node], ip[node + 1])

        if rel.symmetric:       # the forward row, then the transposed row
            return np.concatenate([
                self.forward_edges(rel, row(m.fwd_indptr))[1],
                self.forward_edges(rel, m.order[row(m.rev_indptr)])[0]])
        if fwd:
            return self.forward_edges(rel, row(m.indptr))[1]
        return self.forward_edges(rel, m.order[row(m.rev_indptr)])[0]

    def check_transposes(self, csr: dict) -> int:
        """``csr[etype] = (indptr, indices)`` as the sampler holds them:
        every transposed relation holds exactly its forward relation's
        edges, and every row of a symmetric relation its forward row and
        then its transposed row.  Returns the number of transposed edges
        compared."""
        total = 0
        for etype, (rel, fwd) in self.rels.items():
            if fwd and not rel.symmetric:
                continue
            m = self.made[rel.etype]
            e = rel.forward_edges
            src, dst = self.forward_edges(rel, np.arange(e))
            check(m.order.shape == (e,) and bool(
                (np.bincount(m.order, minlength=e) == 1).all()),
                f"{etype}: slots are not a permutation of the forward edges")
            want_ptr = np.concatenate(
                [[0], np.cumsum(np.bincount(dst, minlength=rel.num_dst))])
            ip, idx = (np.asarray(a) for a in csr[etype])
            if rel.symmetric:
                # Split the stored rows: the first out-degree slots of a
                # row are its forward row, the rest its transposed row.
                fwd_ip = self._forward_indptr(rel)
                check(bool((ip == fwd_ip + want_ptr).all()),
                      f"{etype}: row pointers are not out- plus in-degrees")
                rows = np.repeat(np.arange(rel.num_src), np.diff(ip))
                back = (np.arange(idx.shape[0]) - ip[rows]
                        >= np.diff(fwd_ip)[rows])
                stored_fwd, idx, ip = idx[~back], idx[back], want_ptr
            else:
                stored_fwd = np.asarray(csr[rel.etype][1])
            check(bool((stored_fwd == dst).all()),
                  f"{rel.etype}: stored neighbours differ from the draws")
            check(bool((ip == want_ptr).all()),
                  f"{etype}: row pointers are not the forward in-degrees")
            rows = np.repeat(np.arange(rel.num_dst), np.diff(ip))
            check(bool((dst[m.order] == rows).all()
                       and (src[m.order] == idx).all()),
                  f"{etype}: a transposed slot is not its forward edge")
            total += e
        return total

    # -- rows ---------------------------------------------------------------
    def features(self, ntype: str, nodes) -> np.ndarray:
        """Float32 rows; a negative id gives a zero row."""
        nodes = np.asarray(nodes, np.int64)
        key = gen_hetero.feature_key(self.types.index(ntype), self.seed)
        rows = gen_hetero.feature_values(
            np.where(nodes >= 0, nodes, 0), self.dim, key, np)
        return np.where((nodes >= 0)[:, None], rows, np.float32(0))

    def labels(self, nodes) -> np.ndarray:
        nodes = np.asarray(nodes, np.int64)
        lab = gen_hetero.label_values(np.where(nodes >= 0, nodes, 0),
                                      self.num_classes, self._k_lab, np)
        return np.where(nodes >= 0, lab, -1).astype(np.int32)


# -- sampling semantics -------------------------------------------------------

def _reverse(etype):
    s, rel, d = etype
    if s != d:
        rel = rel[4:] if rel.startswith("rev_") else "rev_" + rel
    return (d, rel, s)


def check_hetero_batch(ref: RefHetero, batch: dict, seed_type: str,
                       batch_size: int, fanouts, what: str, rng,
                       sources: int = 64) -> None:
    """A padded hetero batch against the reference.  ``batch``: ``node``,
    ``node_mask``, ``x`` per type; ``seeds``; ``y`` (seed labels);
    ``row``, ``col``, ``edge_mask`` per *batch* edge type (reversed: the
    sampled relation is ``_reverse(key)``, ``col`` its source slot)."""
    node = {t: np.asarray(v) for t, v in batch["node"].items()}
    mask = {t: np.asarray(v) for t, v in batch["node_mask"].items()}
    for t in node:
        check(bool(((node[t] >= 0) == mask[t]).all()),
              f"{what}: {t} ids are not -1 exactly off the node mask")
        live = node[t][mask[t]]
        check(np.unique(live).size == live.size and
              (live.size == 0 or live.max() < ref.counts[t]),
              f"{what}: the {t} list repeats an id or leaves its type")
        x = np.asarray(batch["x"][t].astype(np.float32))
        check(x.shape == (node[t].shape[0], ref.dim),
              f"{what}: x[{t}] {x.shape} vs node {node[t].shape}")
        check(bool((x == ref.features(t, node[t])).all()),
              f"{what}: gathered {t} rows differ from the generator's "
              f"(padding rows must be zero)")
    seeds = np.asarray(batch["seeds"])
    live_seed = seeds >= 0
    check(bool((node[seed_type][:batch_size][live_seed]
                == seeds[live_seed]).all()),
          f"{what}: seeds do not lead the {seed_type} list")
    check(bool((np.asarray(batch["y"])
                == ref.labels(node[seed_type][:batch_size])).all()),
          f"{what}: seed labels differ from the generator's")

    hops = len(fanouts)
    edges = {}
    for key in batch["row"]:
        em = np.asarray(batch["edge_mask"][key])
        row = np.asarray(batch["row"][key])[em]
        col = np.asarray(batch["col"][key])[em]
        et = _reverse(key)                      # the sampled relation
        check(bool((row >= 0).all() and (col >= 0).all()
                   and (row < node[et[2]].shape[0]).all()
                   and (col < node[et[0]].shape[0]).all()
                   and mask[et[2]][row].all() and mask[et[0]][col].all()),
              f"{what}: a live {et} edge points at a padding slot")
        edges[et] = (row, col)
    # Depth of every slot: typed breadth-first search from the seeds.
    depth = {t: np.full(node[t].shape, hops + 1, np.int64) for t in node}
    depth[seed_type][np.flatnonzero(live_seed)] = 0
    for h in range(hops):
        reached = {t: np.zeros(node[t].shape, bool) for t in node}
        for (s_t, _, d_t), (row, col) in edges.items():
            reached[d_t][row[depth[s_t][col] == h]] = True
        for t in node:
            new = reached[t] & (depth[t] > h + 1)
            depth[t][new] = h + 1
    for et, (row, col) in edges.items():
        s_t, _, d_t = et
        check(bool((depth[d_t][row] <= hops).all()
                   and (depth[s_t][col] < hops).all()),
              f"{what}: a {et} edge lies beyond {hops} hops of the seeds")
        inner = np.flatnonzero(depth[s_t] < hops)
        out_count = np.bincount(col, minlength=node[s_t].shape[0])
        deg = ref.degree(et, node[s_t][inner])
        want = np.minimum(deg, np.asarray(fanouts)[depth[s_t][inner]])
        bad = inner[out_count[inner] != want]
        check(bad.size == 0,
              f"{what}: {bad.size} {s_t} nodes first seen before the last "
              f"hop do not have min(degree, fanout) sampled {et} edges, "
              f"e.g. node {node[s_t][bad[:1]]} at hop "
              f"{depth[s_t][bad[:1]]} has {out_count[bad[:1]]}")
        order = np.argsort(col, kind="stable")
        starts = np.searchsorted(col[order],
                                 np.arange(node[s_t].shape[0] + 1))
        has = inner[out_count[inner] > 0]
        for slot in rng.choice(has, size=min(sources, has.size),
                               replace=False).tolist():
            got = Counter(node[d_t][row[order[
                starts[slot]:starts[slot + 1]]]].tolist())
            have = Counter(ref.neighbours(et, int(node[s_t][slot])).tolist())
            check(not (got - have),
                  f"{what}: {s_t} node {int(node[s_t][slot])} has sampled "
                  f"{et} neighbours {dict(got - have)} that its adjacency "
                  f"list does not hold")


# -- the model ----------------------------------------------------------------

def layer_weights(params, edge_types, num_layers: int):
    """``[{edge_type: (W, att_src, att_dst, bias)}, ...]`` out of the
    program's parameter tree (Flax names
    ``layer<i>/<src>__<rel>__<dst>_conv``, models/rgat.py)."""
    tree = params["params"]
    out = []
    for i in range(num_layers):
        layer = {}
        for et in edge_types:
            c = tree[f"layer{i}"].get("__".join(et) + "_conv")
            if c is not None:
                layer[tuple(et)] = (c["lin"]["kernel"], c["att_src"],
                                    c["att_dst"], c["bias"])
        out.append(layer)
    return out


def _project(x, w, block: int = 1 << 16):
    """``x @ w`` in float32 ``highest``, ``block`` rows at a time."""
    import jax
    import jax.numpy as jnp

    w = jnp.asarray(w, jnp.float32)
    parts = [jnp.dot(x[i:i + block].astype(jnp.float32), w,
                     precision=jax.lax.Precision.HIGHEST)
             for i in range(0, x.shape[0], block)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _gat_relation(weights, x_src, x_dst, src, dst):
    """One relation's bipartite GAT over live edges ``src -> dst``."""
    import jax
    import jax.numpy as jnp

    w, att_src, att_dst, bias = weights
    h, f = att_src.shape
    n_dst = x_dst.shape[0]
    z_src = _project(x_src, w).reshape(-1, h, f)
    z_dst = _project(x_dst, w).reshape(-1, h, f)
    e = (z_src * att_src).sum(-1)[src] + (z_dst * att_dst).sum(-1)[dst]
    e = jnp.where(e > 0, e, 0.2 * e)
    top = jax.ops.segment_max(e, dst, num_segments=n_dst)
    p = jnp.exp(e - top[dst])
    alpha = p / jax.ops.segment_sum(p, dst, num_segments=n_dst)[dst]
    out = jax.ops.segment_sum(z_src[src] * alpha[:, :, None], dst,
                              num_segments=n_dst)
    return out.reshape(n_dst, h * f) + bias


def rgnn_seed_logits(weights, x, edges, target_type: str, num_seeds: int):
    """Logits ``[num_seeds, classes]`` of the target type's first rows.
    ``x``: ``{type: [N_t, d]}``; ``edges``: ``{edge_type: (src, dst)}``
    host arrays of LIVE edges, ``src`` indexing the source type's rows."""
    import jax.numpy as jnp

    h = dict(x)
    for i, layer in enumerate(weights):
        last = i + 1 == len(weights)
        out = {}
        for et, wts in layer.items():
            s_t, _, d_t = et
            if et not in edges or s_t not in h or d_t not in h:
                continue
            src, dst = edges[et]
            x_src, x_dst = h[s_t], h[d_t]
            if last:
                if d_t != target_type:
                    continue
                keep = dst < num_seeds
                rows, src = np.unique(src[keep], return_inverse=True)
                dst = dst[keep]
                x_src, x_dst = x_src[jnp.asarray(rows)], x_dst[:num_seeds]
            if src.size == 0:       # no live edge: the bias alone
                o = jnp.zeros((x_dst.shape[0], wts[3].shape[0])) + wts[3]
            else:
                o = _gat_relation(wts, x_src, x_dst, jnp.asarray(src),
                                  jnp.asarray(dst))
            out[d_t] = out[d_t] + o if d_t in out else o
        h = out if last else {t: jnp.where(v > 0, v, 0.01 * v)
                              for t, v in out.items()}
    return h[target_type]
