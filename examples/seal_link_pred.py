"""SEAL-style link prediction with induced-subgraph sampling.

TPU rebuild of the reference's examples/seal_link_pred.py: for each
candidate link, extract the induced enclosing subgraph around its
endpoints (SubGraphLoader path), label nodes by distance role (DRNL
simplified to endpoint one-hot), and classify the subgraph.
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
from glt_tpu.loader import SubGraphLoader
from glt_tpu.models import GraphSAGE
from glt_tpu.models.conv import scatter_mean


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    # G subgraph batches per device program (amortises per-call dispatch
    # — SEAL batches are tiny); 0 = per-batch loader loop.
    ap.add_argument("--group", type=int, default=8)
    # bf16 matmuls (f32 params/aggregation/loss); see glt_tpu/models/conv.py.
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()

    ds, edge_index = synthetic_ppi(scale=args.scale)
    n = ds.get_graph().num_nodes
    rng = np.random.default_rng(0)

    # candidate links: half real edges (label 1), half random (label 0)
    m = 512
    pos = edge_index[:, rng.permutation(edge_index.shape[1])[:m]]
    neg = rng.integers(0, n, (2, m))
    links = np.concatenate([pos, neg], axis=1)
    labels = np.concatenate([np.ones(m), np.zeros(m)]).astype(np.int32)

    if args.group > 0:
        return run_scanned(args, ds, links, labels, rng)

    loader = SubGraphLoader(ds, [8, 8], links.T.reshape(-1),
                            batch_size=args.batch_size * 2, max_degree=16)

    model = GraphSAGE(dtype=jax.numpy.bfloat16 if args.bf16 else None,
                      hidden_features=32, out_features=32, num_layers=2,
                      dropout_rate=0.0)
    head_tx = optax.adam(1e-3)

    # seeds come in (src, dst) pairs: batch.node[2k], batch.node[2k+1]
    first = next(iter(loader))
    params = model.init({"params": jax.random.PRNGKey(0)}, first.x,
                        first.edge_index, first.edge_mask)
    w = jax.random.normal(jax.random.PRNGKey(1), (32,)) * 0.1
    opt_state = head_tx.init((params, w))

    @jax.jit
    def step(params, w, opt_state, batch, y):
        def loss_fn(pw):
            p, w = pw
            z = model.apply(p, batch.x, batch.edge_index, batch.edge_mask)
            # Seeds are deduped in the node list: locate each (src, dst)
            # endpoint by value, never positionally.
            from glt_tpu.ops.unique import relabel_by_reference

            ref = batch.node[: y.shape[0] * 2]
            si = relabel_by_reference(ref, batch.batch).reshape(
                y.shape[0], 2)
            zs = z[jnp.clip(si, 0, z.shape[0] - 1)]
            logit = ((zs[:, 0] * zs[:, 1]) @ w)
            valid = (si >= 0).all(axis=1)
            ce = optax.sigmoid_binary_cross_entropy(
                logit, y.astype(jnp.float32))
            return jnp.where(valid, ce, 0).sum() / jnp.maximum(
                valid.sum(), 1)

        loss, grads = jax.value_and_grad(loss_fn)((params, w))
        updates, opt_state = head_tx.update(grads, opt_state, (params, w))
        params, w = optax.apply_updates((params, w), updates)
        return params, w, opt_state, loss

    order = rng.permutation(2 * m)
    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        losses = []
        for lo in range(0, 2 * m, args.batch_size):
            sel = order[lo: lo + args.batch_size]
            if sel.shape[0] < args.batch_size:
                continue
            seeds = links.T[sel].reshape(-1)
            from glt_tpu.sampler import NodeSamplerInput
            out = loader.sampler.subgraph(NodeSamplerInput(seeds),
                                          max_degree=16)
            batch = loader._collate_fn(out, seeds.shape[0])
            params, w, opt_state, loss = step(
                params, w, opt_state, batch, jnp.asarray(labels[sel]))
            losses.append(loss)
        jax.block_until_ready(losses[-1])
        print(f"epoch {epoch}: loss={float(np.mean(jax.device_get(losses))):.4f} "
              f"time={time.perf_counter() - t0:.2f}s")


def run_scanned(args, ds, links, labels, rng):
    """G subgraph batches per program: hop expansion + induced extract +
    gather + fwd/bwd + update scanned in one jit (the per-batch loop pays
    a per-call dispatch/transfer floor)."""
    from glt_tpu.models import make_scanned_subgraph_train_step
    from glt_tpu.sampler import NeighborSampler

    bs, G = args.batch_size, args.group
    seed_width = bs * 2
    sampler = NeighborSampler(ds.get_graph(), [8, 8],
                              batch_size=seed_width, with_edge=True)
    feat = ds.get_node_feature()
    model = GraphSAGE(dtype=jax.numpy.bfloat16 if args.bf16 else None,
                      hidden_features=32, out_features=32, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-3)

    def loss_fn(z, out, y):
        # Seeds are deduped in the node list: locate each (src, dst)
        # pair through seed_index, never positionally.
        si = out.metadata["seed_index"].reshape(y.shape[0], 2)
        zs = z[jnp.clip(si, 0, z.shape[0] - 1)]      # [B, 2, d]
        logit = (zs[:, 0] * zs[:, 1]).sum(-1)
        valid = (y >= 0) & (si >= 0).all(axis=1)
        ce = optax.sigmoid_binary_cross_entropy(
            logit, jnp.clip(y, 0, 1).astype(jnp.float32))
        return jnp.where(valid, ce, 0).sum() / jnp.maximum(valid.sum(), 1)

    x0 = jnp.zeros((sampler.node_capacity, feat.shape[1]), jnp.float32)
    ecap = sampler.node_capacity * 16
    params = model.init({"params": jax.random.PRNGKey(0)}, x0,
                        jnp.full((2, ecap), -1, jnp.int32),
                        jnp.zeros((ecap,), bool))
    opt_state = tx.init(params)
    step = make_scanned_subgraph_train_step(model, tx, sampler, feat,
                                            loss_fn, max_degree=16)

    m2 = labels.shape[0]
    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        order = rng.permutation(m2)
        losses, nbs, nb = [], [], 0
        per_block = bs * G
        for lo in range(0, m2, per_block):
            sel = order[lo: lo + per_block]
            sb = np.full((G, seed_width), -1, np.int64)
            yb = np.full((G, bs), -1, np.int64)
            pairs = links.T[sel]                      # [k, 2]
            k = pairs.shape[0]
            sb.reshape(-1)[: k * 2] = pairs.reshape(-1)
            yb.reshape(-1)[:k] = labels[sel]
            params, opt_state, ls = step(
                params, opt_state, sb, yb,
                jax.random.fold_in(jax.random.PRNGKey(epoch), lo))
            # Whole [G] blocks; one concat + one fetch below (see
            # glt_tpu.models.run_scanned_epoch).
            losses.append(ls)
            nbs.append(-(-k // bs))
            nb += -(-k // bs)
        flat = np.asarray(jax.device_get(jnp.concatenate(losses)))
        valid = np.concatenate(
            [np.arange(b) + i * G for i, b in enumerate(nbs)])
        mean = float(np.mean(flat[valid]))
        print(f"epoch {epoch}: loss={mean:.4f} "
              f"time={time.perf_counter() - t0:.2f}s")


if __name__ == "__main__":
    main()
