"""Unsupervised GraphSAGE on (synthetic) PPI with negative sampling.

TPU rebuild of the reference's examples/graph_sage_unsup_ppi.py:
seed edges with binary strict negative sampling; the loss pushes linked
node embeddings together and negatives apart (binary cross-entropy on the
edge_label_index pairs, ``glt_tpu.models.pair_bce_loss``).  The default
path is the fused one, ``run_scanned_epoch`` over
``make_scanned_link_train_step`` (``--group`` batches a program, every
layer over the hop blocks that reach the seed union only); ``--group 0``
is the per-batch ``LinkNeighborLoader`` loop.  After every epoch it
prints the ROC-AUC of held-out edges against strict negatives.
"""
import argparse
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from examples.datasets import synthetic_ppi
from glt_tpu.loader import LinkNeighborLoader
from glt_tpu.models import (GraphSAGE, init_train_state, make_gather_xy,
                            make_scanned_link_train_step, pair_bce_loss,
                            run_scanned_epoch)
from glt_tpu.sampler import NegativeSampling, NeighborSampler


def roc_auc(scores, labels) -> float:
    """Area under the ROC curve by ranks (Mann-Whitney U, ties at their
    mean rank)."""
    scores, labels = np.asarray(scores, np.float64), np.asarray(labels, bool)
    _, inverse, counts = np.unique(scores, return_inverse=True,
                                   return_counts=True)
    ranks = (np.cumsum(counts) - (counts - 1) / 2.0)[inverse]
    pos, neg = labels.sum(), (~labels).sum()
    return float((ranks[labels].sum() - pos * (pos + 1) / 2) / (pos * neg))


def holdout_auc(model, params, sampler, feat, neg, edges) -> float:
    """ROC-AUC of the held-out ``edges`` against as many strict negatives
    (the source's quality metric): evaluation-mode embeddings of each
    batch's seed union, scored by their dot products.  A negative slot the
    strict trials left to the padding pass may be an edge and is left out.
    """
    from glt_tpu.sampler.base import EdgeSamplerInput

    q, hops = sampler.batch_size, sampler.seed_union(neg).hop_bounds
    rows, id2index = feat.hot_rows, feat.id2index

    @jax.jit
    def pair_scores(params, out):
        x, _ = make_gather_xy(id2index)(rows, None, out)
        z = model.apply(params, x, jnp.stack([out.row, out.col]),
                        out.edge_mask, train=False, hops=hops)
        eli = out.metadata["edge_label_index"]
        last = z.shape[0] - 1
        return (z[jnp.clip(eli[0], 0, last)]
                * z[jnp.clip(eli[1], 0, last)]).sum(-1)

    scores, labels = [], []
    for lo in range(0, edges.shape[1], q):
        out = sampler.sample_from_edges(EdgeSamplerInput(
            row=edges[0, lo: lo + q], col=edges[1, lo: lo + q],
            neg_sampling=neg))
        label = np.asarray(out.metadata["edge_label"])
        keep = np.concatenate([label[:q] == 1,
                               np.asarray(out.metadata["neg_strict"])])
        scores.append(np.asarray(pair_scores(params, out))[keep])
        labels.append(label[keep] == 1)
    return roc_auc(np.concatenate(scores), np.concatenate(labels))


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--fanout", type=int, nargs="+", default=[10, 10])
    ap.add_argument("--hidden", type=int, default=64)
    # G link batches per device program (amortises dispatch — the small
    # batches here are dispatch-bound); 0 = per-batch loader loop.
    ap.add_argument("--group", type=int, default=8)
    # Share of the seed edges held out of training and scored at the end
    # of every epoch (ROC-AUC against strict negatives); 0 = no scoring.
    ap.add_argument("--holdout", type=float, default=0.05)
    # Rows of a batch's node list (the seed union's capacity); default:
    # what the fanouts can reach, and never more than the graph's nodes.
    ap.add_argument("--node-capacity", type=int, default=None)
    # bf16 matmuls (f32 params/aggregation/loss); see glt_tpu/models/conv.py.
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()

    ds, edge_index = synthetic_ppi(scale=args.scale)
    model = GraphSAGE(dtype=jax.numpy.bfloat16 if args.bf16 else None,
                      hidden_features=args.hidden, out_features=args.hidden,
                      num_layers=len(args.fanout), dropout_rate=0.0)
    tx = optax.adam(1e-3)
    neg = NegativeSampling("binary", 1)
    rng = np.random.default_rng(0)
    held = rng.random(edge_index.shape[1]) < args.holdout
    train_edges, test_edges = edge_index[:, ~held], edge_index[:, held]
    feat = ds.get_node_feature()
    sampler = NeighborSampler(ds.get_graph(), args.fanout,
                              batch_size=args.batch_size, with_edge=False,
                              node_capacity=args.node_capacity)

    def report(epoch, params, loss, acc, t0, overflowed=0):
        line = (f"epoch {epoch}: loss={loss:.4f} acc={acc:.4f} "
                f"time={time.perf_counter() - t0:.2f}s")
        if overflowed:
            line += f" overflowed_batches={overflowed}"
        if test_edges.shape[1]:
            auc = holdout_auc(model, params, sampler, feat, neg, test_edges)
            line += f" auc={auc:.4f} ({test_edges.shape[1]} held-out edges)"
        print(line)

    if args.group > 0:
        state = init_train_state(model, tx, feat.shape[1],
                                 jax.random.PRNGKey(0))
        step = make_scanned_link_train_step(model, tx, sampler, feat,
                                            neg_sampling=neg,
                                            group=args.group)
        for epoch in range(args.epochs):
            t0 = time.perf_counter()
            state, losses, accs, ovf = run_scanned_epoch(
                step, state, train_edges, args.batch_size, args.group, rng,
                jax.random.PRNGKey(epoch))
            report(epoch, state.params, float(losses.mean()),
                   float(accs.mean()), t0, ovf)
        return

    loader = LinkNeighborLoader(
        ds, args.fanout, train_edges, batch_size=args.batch_size,
        neg_sampling=neg, shuffle=True)
    first = next(iter(loader))
    params = model.init({"params": jax.random.PRNGKey(0)}, first.x,
                        first.edge_index, first.edge_mask)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        def loss_fn(p):
            z = model.apply(p, batch.x, batch.edge_index, batch.edge_mask)
            return pair_bce_loss(z, batch.metadata)

        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        losses, accs = [], []
        for batch in loader:
            params, opt_state, loss, acc = step(params, opt_state, batch)
            losses.append(loss)
            accs.append(acc)
        report(epoch, params, float(np.mean(jax.device_get(losses))),
               float(np.mean(jax.device_get(accs))), t0)


if __name__ == "__main__":
    main()
