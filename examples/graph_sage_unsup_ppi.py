"""Unsupervised GraphSAGE on (synthetic) PPI with negative sampling.

TPU rebuild of the reference's examples/graph_sage_unsup_ppi.py:
LinkNeighborLoader with binary negative sampling; the loss pushes linked
node embeddings together and negatives apart (binary cross-entropy on the
edge_label_index pairs).
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
from glt_tpu.models import GraphSAGE
from glt_tpu.sampler import NegativeSampling


def unsup_dot_loss(z, meta):
    """Binary CE on seed-edge embedding dot products (the reference's
    unsupervised objective)."""
    eli = meta["edge_label_index"]
    label = meta["edge_label"]
    valid = (eli[0] >= 0) & (eli[1] >= 0) & (label >= 0)
    src = z[jnp.clip(eli[0], 0, z.shape[0] - 1)]
    dst = z[jnp.clip(eli[1], 0, z.shape[0] - 1)]
    logits = (src * dst).sum(-1)
    y = (label > 0).astype(jnp.float32)
    ce = optax.sigmoid_binary_cross_entropy(logits, y)
    return jnp.where(valid, ce, 0).sum() / jnp.maximum(valid.sum(), 1)


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--fanout", type=int, nargs="+", default=[10, 10])
    # G link batches per device program (amortises dispatch — the small
    # batches here are dispatch-bound); 0 = per-batch loader loop.
    ap.add_argument("--group", type=int, default=8)
    # bf16 matmuls (f32 params/aggregation/loss); see glt_tpu/models/conv.py.
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()

    ds, edge_index = synthetic_ppi(scale=args.scale)
    model = GraphSAGE(dtype=jax.numpy.bfloat16 if args.bf16 else None,
                      hidden_features=64, out_features=64, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-3)
    neg = NegativeSampling("binary", 1)

    if args.group > 0:
        from glt_tpu.models import (
            link_seed_blocks,
            make_scanned_link_train_step,
        )
        from glt_tpu.sampler import NeighborSampler

        sampler = NeighborSampler(ds.get_graph(), args.fanout,
                                  batch_size=args.batch_size,
                                  frontier_cap=4096, with_edge=False)
        feat = ds.get_node_feature()
        cap = 4 * sampler.batch_size  # binary seed union width
        import glt_tpu.sampler.neighbor_sampler as ns

        seed_width = 4 * args.batch_size
        ecap_widths = ns.hop_widths(seed_width, args.fanout, 4096)
        x0 = jnp.zeros((ns.max_sampled_nodes(seed_width, args.fanout, 4096),
                        feat.shape[1]), jnp.float32)
        ecap = sum(w * f for w, f in zip(ecap_widths, args.fanout))
        ei0 = jnp.full((2, ecap), -1, jnp.int32)
        m0 = jnp.zeros((ecap,), bool)
        params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)
        opt_state = tx.init(params)
        step = make_scanned_link_train_step(model, tx, sampler, feat,
                                            unsup_dot_loss, neg,
                                            group=args.group)
        rng = np.random.default_rng(0)

        for epoch in range(args.epochs):
            t0 = time.perf_counter()
            losses, nbs, batches = [], [], 0
            for sb, db, nb in link_seed_blocks(edge_index, args.batch_size,
                                               args.group, rng):
                params, opt_state, ls = step(
                    params, opt_state, sb, db,
                    jax.random.fold_in(jax.random.PRNGKey(epoch), batches))
                # Whole [G] blocks: per-block slices + fetches would put
                # a dispatch/round-trip per block on the critical path
                # (see glt_tpu.models.run_scanned_epoch).
                losses.append(ls)
                nbs.append(nb)
                batches += nb
            flat = np.asarray(jax.device_get(jnp.concatenate(losses)))
            valid = np.concatenate(
                [np.arange(nb) + i * args.group
                 for i, nb in enumerate(nbs)])
            mean = float(np.mean(flat[valid]))
            print(f"epoch {epoch}: loss={mean:.4f} "
                  f"time={time.perf_counter() - t0:.2f}s")
        return

    loader = LinkNeighborLoader(
        ds, args.fanout, edge_index, batch_size=args.batch_size,
        neg_sampling=neg, shuffle=True, frontier_cap=4096)
    first = next(iter(loader))
    params = model.init({"params": jax.random.PRNGKey(0)}, first.x,
                        first.edge_index, first.edge_mask)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        def loss_fn(p):
            z = model.apply(p, batch.x, batch.edge_index, batch.edge_mask)
            return unsup_dot_loss(z, batch.metadata)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        losses = []
        for batch in loader:
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(loss)
        jax.block_until_ready(losses[-1])
        print(f"epoch {epoch}: loss={float(np.mean(jax.device_get(losses))):.4f} "
              f"time={time.perf_counter() - t0:.2f}s")


if __name__ == "__main__":
    main()
