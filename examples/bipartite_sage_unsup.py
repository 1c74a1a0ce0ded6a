"""Unsupervised bipartite GraphSAGE on a user-item graph.

TPU rebuild of the reference's ``examples/hetero/bipartite_sage_unsup.py``:
hetero link-neighbor sampling over the ``user -> item`` seed edge type with
binary negatives, two-tower hetero SAGE encoders, a dot-product edge
decoder, BCE on ``edge_label`` — each train step one fused XLA program.
"""
import argparse
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from glt_tpu.data import Dataset
from glt_tpu.loader.hetero_link_loader import HeteroLinkNeighborLoader
from glt_tpu.models.rgat import HeteroConv
from glt_tpu.sampler import NegativeSampling
from glt_tpu.typing import reverse_edge_type

ET_UI = ("user", "clicks", "item")
ET_IU = ("item", "rev_clicks", "user")


def synthetic_user_item(n_users=600, n_items=300, deg=6, seed=0):
    """Users click items near ``u % n_items`` — structure recoverable
    from the graph alone (the unsupervised task)."""
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n_users), deg)
    dst = (src % n_items + rng.integers(0, 8, src.shape[0])) % n_items
    ei = {ET_UI: np.stack([src, dst]), ET_IU: np.stack([dst, src])}
    feats = {
        "user": rng.normal(size=(n_users, 16)).astype(np.float32),
        "item": rng.normal(size=(n_items, 16)).astype(np.float32),
    }
    ds = (Dataset()
          .init_graph(ei, graph_mode="DEVICE",
                      num_nodes={"user": n_users, "item": n_items})
          .init_node_features(feats))
    return ds, np.stack([src, dst])


class TwoTowerSAGE(nn.Module):
    """Per-type hetero SAGE encoders + dot-product edge decoder
    (cf. ItemGNNEncoder/UserGNNEncoder/EdgeDecoder in the reference)."""
    edge_types: tuple
    hidden: int = 64
    out: int = 32
    num_layers: int = 2

    @nn.compact
    def __call__(self, x, edge_index, edge_mask, edge_label_index):
        h = {t: nn.Dense(self.hidden, name=f"in_{t}")(v)
             for t, v in x.items()}
        for i in range(self.num_layers):
            out = HeteroConv(self.edge_types, self.hidden, conv="sage",
                             name=f"layer{i}")(h, edge_index, edge_mask)
            h = {t: nn.relu(out[t]) if t in out else h[t] for t in h}
        z = {t: nn.Dense(self.out, name=f"out_{t}")(v)
             for t, v in h.items()}
        zu = z["user"][jnp.clip(edge_label_index[0], 0, None)]
        zi = z["item"][jnp.clip(edge_label_index[1], 0, None)]
        return (zu * zi).sum(-1)      # [Q] logits


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--fanout", type=int, nargs="+", default=[8, 4])
    args = ap.parse_args()

    ds, pos_edges = synthetic_user_item()
    loader = HeteroLinkNeighborLoader(
        ds, args.fanout, (ET_UI, pos_edges),
        neg_sampling=NegativeSampling("binary", 1.0),
        batch_size=args.batch_size, shuffle=True, seed=0)
    batch_ets = sorted(reverse_edge_type(et) for et in ds.graph)
    model = TwoTowerSAGE(edge_types=tuple(batch_ets))

    first = next(iter(loader))
    eli0 = first.metadata["edge_label_index"]
    params = model.init(jax.random.PRNGKey(0), first.x, first.edge_index,
                        first.edge_mask, eli0)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        eli = batch.metadata["edge_label_index"]
        label = batch.metadata["edge_label"]

        def loss_fn(p):
            logits = model.apply(p, batch.x, batch.edge_index,
                                 batch.edge_mask, eli)
            valid = label >= 0
            y = jnp.clip(label, 0, 1).astype(jnp.float32)
            bce = optax.sigmoid_binary_cross_entropy(logits, y)
            loss = jnp.where(valid, bce, 0).sum() / jnp.maximum(
                valid.sum(), 1)
            acc = jnp.where(valid, (logits > 0) == (y > 0.5),
                            False).sum() / jnp.maximum(valid.sum(), 1)
            return loss, acc
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    for epoch in range(args.epochs):
        t0 = time.time()
        tot_l = tot_a = nb = 0
        for batch in loader:
            params, opt_state, loss, acc = step(params, opt_state, batch)
            tot_l += float(loss); tot_a += float(acc); nb += 1
        print(f"epoch {epoch}: bce {tot_l/nb:.4f} link-acc {tot_a/nb:.4f} "
              f"({time.time()-t0:.2f}s)")


if __name__ == "__main__":
    main()
