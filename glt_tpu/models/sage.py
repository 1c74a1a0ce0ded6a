"""GraphSAGE — the flagship model for sampled-batch training.

Matches the architecture of the reference's example trainer
(examples/train_sage_ogbn_products.py: PyG ``SAGEConv`` stack, relu +
dropout between layers).  Consumes padded :class:`Batch` tensors; padding
nodes flow through harmlessly (their features are zero and their outputs are
masked by the loss).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from .conv import SAGEConv


class GraphSAGE(nn.Module):
    hidden_features: int
    out_features: int
    num_layers: int = 3
    dropout_rate: float = 0.5
    # Matmul compute dtype (e.g. jnp.bfloat16): params, aggregation, loss
    # all stay f32; only the MXU matmuls run reduced (see conv.py).
    dtype: Any = None

    @nn.compact
    def __call__(self, x, edge_index, edge_mask, *, train: bool = False):
        for i in range(self.num_layers):
            last = i == self.num_layers - 1
            dim = self.out_features if last else self.hidden_features
            x = SAGEConv(dim, dtype=self.dtype,
                         name=f"conv{i}")(x, edge_index, edge_mask)
            if not last:
                with jax.named_scope("glt.model.dense"):
                    x = nn.relu(x)
                    x = nn.Dropout(self.dropout_rate,
                                   deterministic=not train)(x)
        return x
