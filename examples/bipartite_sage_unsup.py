"""Bipartite user-item GraphSAGE with learned ID embeddings (link
prediction).

TPU rebuild of the reference's ``examples/hetero/bipartite_sage_unsup.py``
(PyG's example of the same name, on Taobao): users and items have no
features, only their ids, looked up in two trainable tables
(``glt_tpu.models.NodeEmbedding``); an item tower over ``item -> item``
and a user tower over ``item -> item`` and ``item -> user`` feed an MLP
decoder of (user, item) pairs, trained with binary cross-entropy against
strict binary negatives and Adam over every parameter, tables included.
``item -> item`` joins two items that at least three users share, as
upstream derives it from the training edges.  The training path is the
fused one, ``run_scanned_epoch`` over
``make_scanned_hetero_link_train_step``; after every epoch the example
prints upstream's line: the loss, then the ROC-AUC of the held-out
validation and test edges against as many non-edges.

The graph here is synthetic (no dataset is fetched): users of a
community mostly pick items of the same community, so the held-out
edges are predictable from the training ones.  The benchmark runs the
same model at Taobao's shapes (chipbench cell
``bipartite-sage-taobao.hetero-link-train-scan``).
"""
import argparse
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from sklearn.metrics import roc_auc_score

from glt_tpu.data import CSRTopo, Graph
from glt_tpu.models import (BipartiteSAGE,
                            make_scanned_hetero_link_train_step,
                            run_scanned_epoch)
from glt_tpu.models.bipartite import init_state
from glt_tpu.sampler import NegativeSampling
from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler

UI = ("user", "to", "item")
IU = ("item", "rev_to", "user")
II = ("item", "to", "item")


def synthetic_user_item(n_users, n_items, communities=20, per_user=12,
                        seed=0):
    """Unique (user, item) pairs ``[2, E]``: 80 % of a user's picks lie in
    its own community's items."""
    rng = np.random.default_rng(seed)
    cu = rng.integers(0, communities, n_users)
    ci = rng.integers(0, communities, n_items)
    by_comm = [np.flatnonzero(ci == c) for c in range(communities)]
    users = np.repeat(np.arange(n_users), per_user)
    own = rng.random(users.shape[0]) < 0.8
    items = rng.integers(0, n_items, users.shape[0])
    for c in range(communities):
        pick = own & (cu[users] == c)
        items[pick] = rng.choice(by_comm[c], pick.sum())
    return np.unique(np.stack([users, items]), axis=1)


def split(pairs, n_users, n_items, rng):
    """RandomLinkSplit(num_val=0.1, num_test=0.1, neg_sampling_ratio=1.0):
    80 / 10 / 10 of the edges, each held-out edge with one non-edge."""
    perm = rng.permutation(pairs.shape[1])
    n_val = n_test = pairs.shape[1] // 10
    train = pairs[:, perm[n_val + n_test:]]
    known = set(map(tuple, pairs.T.tolist()))

    def with_negatives(pos):
        neg = []
        while len(neg) < pos.shape[1]:
            u, i = int(rng.integers(n_users)), int(rng.integers(n_items))
            if (u, i) not in known:
                neg.append((u, i))
        edges = np.concatenate([pos, np.array(neg).T], axis=1)
        return edges, np.r_[np.ones(pos.shape[1]), np.zeros(pos.shape[1])]

    return (train, with_negatives(pairs[:, perm[:n_val]]),
            with_negatives(pairs[:, perm[n_val:n_val + n_test]]))


def co_interactions(train, n_users, n_items, at_least=3):
    """``item -> item`` edges: ``comat = A^T A`` of the training edges,
    diagonal dropped, kept where at least ``at_least`` users share both."""
    a = np.zeros((n_users, n_items), np.float32)
    a[train[0], train[1]] = 1.0
    comat = a.T @ a
    np.fill_diagonal(comat, 0)
    return np.stack(np.nonzero(comat >= at_least))


def make_scorer(model, sampler):
    """``(params, users, items) -> logits`` of given pairs, evaluation
    mode: each batch sampled from its pairs' users and items (no
    negatives), as upstream's evaluation loader does."""
    impl, _, _ = sampler.edges_program(UI, None, 0)
    graphs = {et: (g.indptr, g.indices, g.gather_edge_ids)
              for et, g in sampler.graphs.items()}
    q = sampler.batch_size

    @jax.jit
    def score(params, src, dst, key):
        out = impl(graphs, sampler.graphs[UI].indices, src, dst,
                   jnp.zeros((1,), jnp.float32), key)
        ei = {et: jnp.stack([out.row[et], out.col[et]]) for et in out.row}
        return model.apply(params, (dict(out.node),
                                    out.metadata["edge_label_index"]),
                           ei, out.edge_mask)

    def scores(params, edges):
        got = []
        for lo in range(0, edges.shape[1], q):
            blk = np.full((2, q), -1, np.int32)
            chunk = edges[:, lo:lo + q]
            blk[:, :chunk.shape[1]] = chunk
            got.append(np.asarray(score(params, blk[0], blk[1],
                                        jax.random.PRNGKey(lo)))
                       [:chunk.shape[1]])
        return np.concatenate(got)

    return scores


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--users", type=int, default=3000)
    ap.add_argument("--items", type=int, default=2000)
    ap.add_argument("--batch-size", type=int, default=2048)
    ap.add_argument("--fanout", type=int, nargs="+", default=[8, 4])
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--group", type=int, default=4,
                    help="batches a scanned program")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    pairs = synthetic_user_item(args.users, args.items)
    train, (val, val_y), (test, test_y) = split(pairs, args.users,
                                                args.items, rng)
    items = co_interactions(train, args.users, args.items)
    graphs = {UI: Graph(CSRTopo(train, num_nodes=args.users)),
              IU: Graph(CSRTopo(train[::-1], num_nodes=args.items)),
              II: Graph(CSRTopo(items, num_nodes=args.items))}
    print(f"{args.users} users, {args.items} items: {train.shape[1]} "
          f"training edges, {items.shape[1]} item->item edges, "
          f"{val.shape[1] // 2} / {test.shape[1] // 2} held out")
    sampler = HeteroNeighborSampler(graphs, args.fanout, "user",
                                    batch_size=args.batch_size)
    model = BipartiteSAGE(args.users, args.items, args.hidden, args.hidden,
                          dtype=jnp.bfloat16)
    tx = optax.adam(args.lr)
    state = init_state(model, tx, jax.random.PRNGKey(0))
    step = make_scanned_hetero_link_train_step(
        model, tx, sampler, UI, NegativeSampling("binary", 1.0))
    scores = make_scorer(model, sampler)

    for epoch in range(1, args.epochs + 1):
        t0 = time.perf_counter()
        state, losses, _, _ = run_scanned_epoch(
            step, state, train, args.batch_size, args.group, rng,
            jax.random.PRNGKey(epoch))
        val_auc = roc_auc_score(val_y, scores(state.params, val))
        test_auc = roc_auc_score(test_y, scores(state.params, test))
        print(f"Epoch: {epoch:03d}, Loss: {float(np.mean(losses)):.4f}, "
              f"Val: {val_auc:.4f}, Test: {test_auc:.4f} "
              f"({time.perf_counter() - t0:.1f}s)")


if __name__ == "__main__":
    main()
