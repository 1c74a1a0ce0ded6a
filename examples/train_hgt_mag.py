"""HGT on an OGB-MAG-shaped heterogeneous graph.

TPU rebuild of the reference's ``examples/hetero/train_hgt_mag.py``:
hetero neighbor sampling over MAG's paper/author/institution/field types,
a flax Heterogeneous Graph Transformer (``glt_tpu/models/hgt.py``), paper
venue classification.  One fused XLA program per train step.
"""
import argparse
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from examples.datasets import synthetic_mag
from glt_tpu.loader.hetero_neighbor_loader import HeteroNeighborLoader
from glt_tpu.models import HGT
from glt_tpu.typing import reverse_edge_type


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--fanout", type=int, nargs="+", default=[5, 5])
    ap.add_argument("--last-hop-dedup", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="exact final-hop dedup (default); "
                         "--no-last-hop-dedup opts into the fast leaf block")
    # G-batch scan: one program trains --group consecutive hetero
    # batches (see rgat_igbh.py — per-batch dispatch dominates small
    # hetero batches on TPU).  0 = eager loader loop.
    ap.add_argument("--group", type=int, default=8)
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()

    ds, train_idx, classes = synthetic_mag(scale=args.scale)
    batch_ets = sorted(reverse_edge_type(et) for et in ds.graph)

    model = HGT(edge_types=batch_ets, hidden_features=args.hidden,
                out_features=classes, target_type="paper",
                num_layers=len(args.fanout), heads=args.heads,
                dropout_rate=0.3,
                dtype=jnp.bfloat16 if args.bf16 else None)

    if args.group > 0:
        from glt_tpu.models import (
            init_hetero_state,
            make_scanned_hetero_train_step,
            run_scanned_epoch,
        )
        from glt_tpu.sampler.hetero_neighbor_sampler import (
            HeteroNeighborSampler,
        )

        sampler = HeteroNeighborSampler(
            ds.graph, args.fanout, "paper", batch_size=args.batch_size,
            seed=0, last_hop_dedup=args.last_hop_dedup)
        feats = {t: ds.get_node_feature(t) for t in ds.get_node_types()}
        labels = {"paper": np.asarray(ds.node_labels["paper"])}
        tx = optax.adam(1e-3)
        state = init_hetero_state(model, tx, sampler, feats,
                                  jax.random.PRNGKey(0))
        sstep = make_scanned_hetero_train_step(
            model, tx, sampler, feats, labels, args.batch_size)
        rng = np.random.default_rng(0)
        for epoch in range(args.epochs):
            t0 = time.perf_counter()
            state, losses, accs, _ = run_scanned_epoch(
                sstep, state, train_idx, args.batch_size, args.group,
                rng, jax.random.PRNGKey(100 + epoch))
            dt = time.perf_counter() - t0
            print(f"epoch {epoch}: loss {float(np.mean(losses)):.4f} "
                  f"acc {float(np.mean(accs)):.4f} "
                  f"({dt:.2f}s, {len(losses)} batches)")
        return

    loader = HeteroNeighborLoader(
        ds, args.fanout, ("paper", train_idx),
        batch_size=args.batch_size, shuffle=True, seed=0,
        last_hop_dedup=args.last_hop_dedup)
    first = next(iter(loader))
    tx = optax.adam(1e-3)
    params = model.init({"params": jax.random.PRNGKey(0)}, first.x,
                        first.edge_index, first.edge_mask)
    opt_state = tx.init(params)
    bsz = args.batch_size

    @jax.jit
    def step(params, opt_state, batch, rng):
        def loss_fn(p):
            logits = model.apply(p, batch.x, batch.edge_index,
                                 batch.edge_mask, train=True,
                                 rngs={"dropout": rng})
            y = batch.y["paper"][:bsz]
            valid = y >= 0
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:bsz], jnp.where(valid, y, 0))
            loss = jnp.where(valid, ce, 0).sum() / jnp.maximum(valid.sum(), 1)
            acc = jnp.where(valid, logits[:bsz].argmax(-1) == y,
                            False).sum() / jnp.maximum(valid.sum(), 1)
            return loss, acc
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    rng = jax.random.PRNGKey(1)
    for epoch in range(args.epochs):
        t0 = time.time()
        tot_l = tot_a = nb = 0
        for batch in loader:
            rng, sub = jax.random.split(rng)
            params, opt_state, loss, acc = step(params, opt_state, batch, sub)
            tot_l += float(loss); tot_a += float(acc); nb += 1
        print(f"epoch {epoch}: loss {tot_l/nb:.4f} acc {tot_a/nb:.4f} "
              f"({time.time()-t0:.2f}s, {nb} batches)")


if __name__ == "__main__":
    main()
