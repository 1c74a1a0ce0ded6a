"""The plain reference of the link path: same semantics, none of the
program's code.

Beside ``reference.py`` (whose ``RefData`` and GraphSAGE forward it
uses): edge membership out of the generator's adjacency lists, pair
logits ``(z_src * z_dst).sum(-1)``, the masked binary cross-entropy of
upstream's unsupervised objective (``examples/graph_sage_unsup_ppi.py``,
``examples/distributed/dist_sage_unsup/dist_sage_unsup.py``) and its
gradients, and the comparison of a sampled link batch with what was asked
for.  ``numpy`` and float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, no hop layout:
every layer runs over every row and every live edge.  At the cell's own
size the forward is taken a block of edges at a time, so that the
messages of one layer never stand in memory at once.
"""
from __future__ import annotations

import numpy as np

from chipbench import checks
from chipbench import reference


def is_edge(ref, src, dst) -> np.ndarray:
    """``dst[i]`` in the adjacency list of ``src[i]``, pair by pair."""
    return np.array([bool((ref.neighbours(int(s)) == int(d)).any())
                     for s, d in zip(np.asarray(src), np.asarray(dst))],
                    bool)


def sage_embed_blocked(weights, x, src, dst, edge_mask, block: int = 1 << 19):
    """``reference.sage_forward`` with each layer's neighbour sum taken
    ``block`` edge slots at a time (the same sums in another order of
    addition; one block is the unblocked forward)."""
    import jax
    import jax.numpy as jnp

    n, slots = x.shape[0], src.shape[0]
    seg = jnp.where(edge_mask, dst, n)

    @jax.jit
    def add_block(agg, h, s, g, m):
        return agg.at[g].add(jnp.where(m[:, None],
                                       h[jnp.clip(s, 0, n - 1)], 0.0))

    @jax.jit
    def layer(h, agg, cnt, w_self, b, w_nbr, relu):
        with jax.default_matmul_precision("highest"):
            out = h @ w_self + b + (agg[:n] / jnp.maximum(cnt, 1.0)[:, None]
                                    ) @ w_nbr
        return jnp.where(relu, jnp.maximum(out, 0.0), out)

    cnt = jax.ops.segment_sum(edge_mask.astype(jnp.float32), seg,
                              num_segments=n + 1)[:n]
    h = jnp.asarray(x, jnp.float32)
    for i, (w_self, b, w_nbr) in enumerate(weights):
        agg = jnp.zeros((n + 1, h.shape[1]), jnp.float32)
        for lo in range(0, slots, block):
            hi = min(lo + block, slots)
            agg = add_block(agg, h, src[lo:hi], seg[lo:hi], edge_mask[lo:hi])
        h = layer(h, agg, cnt, w_self, b, w_nbr, i + 1 < len(weights))
    return h


def pair_logits(z, pair_index):
    """``(z_src * z_dst).sum(-1)`` of the pairs (rows of ``z``)."""
    import jax.numpy as jnp

    last = z.shape[0] - 1
    return (z[jnp.clip(pair_index[0], 0, last)]
            * z[jnp.clip(pair_index[1], 0, last)]).sum(-1)


def pair_loss(weights, x, src, dst, edge_mask, pair_index, label):
    """Mean ``binary_cross_entropy_with_logits`` over the pairs whose
    label is not padding: ``max(l, 0) - l y + log(1 + exp(-|l|))``."""
    import jax.numpy as jnp

    z = reference.sage_forward(weights, x, src, dst, edge_mask)
    logit = pair_logits(z, pair_index)
    valid = (pair_index[0] >= 0) & (pair_index[1] >= 0) & (label >= 0)
    y = (label > 0).astype(jnp.float32)
    ce = (jnp.maximum(logit, 0.0) - logit * y
          + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    return jnp.where(valid, ce, 0.0).sum() / jnp.maximum(valid.sum(), 1)


def pair_loss_and_grads(weights, x, src, dst, edge_mask, pair_index, label):
    """The loss and its gradient by every ``(W_self, b, W_nbr)``."""
    import jax

    return jax.value_and_grad(pair_loss)(weights, x, src, dst, edge_mask,
                                         pair_index, label)


def check_link_batch(ref, batch, src, dst, batch_size: int, amount: int,
                     fanouts, what: str, rng) -> dict:
    """A padded link batch against what was asked for: the given seed
    edges ``src -> dst`` (``-1`` padded to ``batch_size``) with ``amount``
    binary negatives each.

    ``batch``: ``node``, ``node_mask``, ``x`` (or None), ``edge_index``,
    ``edge_mask``, ``edge_label_index``, ``edge_label``, ``neg_strict``.
    Returns the counts of strict and padded negative slots.
    """
    check = checks.check
    node = np.asarray(batch["node"])
    mask = np.asarray(batch["node_mask"])
    src, dst = np.asarray(src), np.asarray(dst)
    q, n_neg = batch_size, batch_size * amount
    check(bool(((node >= 0) == mask).all()),
          f"{what}: node ids are not -1 exactly off the node mask")
    live = node[mask]
    check(np.unique(live).size == live.size,
          f"{what}: the node list repeats an id")
    check(bool(live.min() >= 0 and live.max() < ref.sh.num_nodes),
          f"{what}: a node id lies outside the graph")

    # (d) the pairs, mapped back through the node list, in order.
    eli = np.asarray(batch["edge_label_index"])
    label = np.asarray(batch["edge_label"])
    check(eli.shape == (2, q + n_neg) and label.shape == (q + n_neg,),
          f"{what}: pair index {eli.shape}, labels {label.shape}")
    real = src >= 0
    check(bool((eli[:, :q][:, ~real] == -1).all()
               and (label[:q] == np.where(real, 1, -1)).all()
               and (label[q:] == 0).all()),
          f"{what}: labels are not 1 on the seed edges, -1 on their "
          f"padding and 0 on the negatives")
    check(bool((eli[:, :q][:, real] >= 0).all() and (eli[:, q:] >= 0).all()
               and eli.max() < 2 * (q + n_neg)),
          f"{what}: a pair points outside the seed rows")
    check(bool((node[eli[0, :q][real]] == src[real]).all()
               and (node[eli[1, :q][real]] == dst[real]).all()),
          f"{what}: the positive pairs are not the given seed edges in "
          f"order")
    neg_src, neg_dst = node[eli[0, q:]], node[eli[1, q:]]
    check(bool((neg_src >= 0).all() and (neg_dst >= 0).all()),
          f"{what}: a negative pair points at a padding row")
    strict = np.asarray(batch["neg_strict"])
    check(strict.shape == (n_neg,), f"{what}: strict flags {strict.shape}")
    hit = is_edge(ref, neg_src[strict], neg_dst[strict])
    check(not hit.any(),
          f"{what}: {int(hit.sum())} negative slots flagged strict are "
          f"edges of the graph, e.g. {neg_src[strict][hit][:1]} -> "
          f"{neg_dst[strict][hit][:1]}")
    pos = is_edge(ref, src[real][:64], dst[real][:64])
    check(bool(pos.all()), f"{what}: a given seed edge is not in the graph")

    # The seed union leads the node list in order of first occurrence.
    union = np.concatenate([src[real], dst[real], neg_src, neg_dst])
    _, first = np.unique(union, return_index=True)
    lead = union[np.sort(first)]
    check(bool((node[: lead.size] == lead).all()),
          f"{what}: the seed union [src, dst, neg_src, neg_dst] does not "
          f"lead the node list in first-occurrence order")

    if batch.get("x") is not None:
        x = np.asarray(batch["x"])
        check(x.shape == (node.shape[0], ref.sh.feature_dim),
              f"{what}: x {x.shape} vs node {node.shape}")
        check(bool((x == ref.features(node)).all()),
              f"{what}: gathered features differ from the generator's "
              f"rows (padding rows must be zero)")
    ei = np.asarray(batch["edge_index"])
    em = np.asarray(batch["edge_mask"])
    row, col = ei[0][em], ei[1][em]
    check(bool((row >= 0).all() and (row < node.shape[0]).all()
               and (col >= 0).all() and (col < node.shape[0]).all()
               and mask[row].all() and mask[col].all()),
          f"{what}: a live edge points at a padding slot")
    checks.check_sampling(ref, node, row, col, np.arange(lead.size),
                          fanouts, what, rng)
    return {"neg_strict": int(strict.sum()),
            "neg_padded": int((~strict).sum()),
            "seed_union_nodes": int(lead.size)}
