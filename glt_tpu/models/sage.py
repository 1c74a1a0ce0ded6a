"""GraphSAGE — the flagship model for sampled-batch training.

Matches the architecture of the reference's example trainer
(examples/train_sage_ogbn_products.py: PyG ``SAGEConv`` stack, relu +
dropout between layers).  Consumes padded :class:`Batch` tensors; padding
nodes flow through harmlessly (their features are zero and their outputs are
masked by the loss).

**Per-layer trimming** (``hops=``).  A sampled batch is laid out in hop
blocks (:func:`~glt_tpu.sampler.neighbor_sampler.hop_bounds`): the edges
of hops ``1..k`` are a static prefix of ``edge_index`` and touch only a
static prefix of the node buffer.  The seed rows of the last layer read
the layer before it on the hop-1 rows, through the hop-1 edges only; that
layer reads its predecessor on the hop-2 rows through the edges of hops
1-2, and so on outwards.  With ``hops`` every layer runs on exactly those
prefixes and the model returns the ``batch_size`` seed rows.  It is what
PyG ships as ``trim_to_layer``.  The result is exact, not an
approximation: no edge or node that reaches a seed is dropped and the
surviving edges keep their order, so seed logits, loss and every
parameter gradient are the whole model's numbers (up to float32
reassociation in the matmuls' row sums); the rows and edges left out have
a zero cotangent in the whole model and are never read.  Only rows that
hold a real seed are exact: a partly padded seed batch leaves hop-1 nodes
in the rows behind its seeds, and the loss must not read them
(``seed_cross_entropy(..., num_seeds=)``).

**Block aggregation.**  The same layout says where every edge slot
aggregates to: slot ``s`` of hop block ``k`` into row ``start_k + s //
fanout_k``, ``start_k`` one dynamic scalar a block.  With ``hops`` every
layer therefore sums its hop blocks as contiguous slabs
(:func:`~glt_tpu.models.conv.block_mean`) and no ``segment_sum`` runs;
``layer_blocks`` says which blocks a layer reads.  Without ``hops`` the
batch is any COO and the layers scatter as before.  Nothing selects
between the two but the presence of the layout.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..sampler.neighbor_sampler import HopBounds
from .conv import SAGEConv


class GraphSAGE(nn.Module):
    hidden_features: int
    out_features: int
    num_layers: int = 3
    dropout_rate: float = 0.5
    # Matmul compute dtype (e.g. jnp.bfloat16): params, aggregation, loss
    # all stay f32; only the MXU matmuls run reduced (see conv.py).
    dtype: Any = None

    def layer_extents(self, hops: HopBounds) -> List[Tuple[int, int, int]]:
        """``(source rows, edge slots, destination rows)`` of each layer
        under ``hops``, first layer first.

        Layer ``l`` of ``L`` (1-based) sits ``d = L - l`` layers under the
        output, so its result is read on the rows of hop ``d`` and it
        aggregates the edges of hops ``1..d+1`` from the rows of hop
        ``d+1``.  ``d`` clamps to the number of hops: more layers than
        hops leave the first layers whole, fewer never touch the outer
        hop blocks.
        """
        nb, eb = hops.node_bounds, hops.edge_bounds
        k = len(eb) - 1
        return [(nb[min(d + 1, k)], eb[min(d + 1, k)], nb[min(d, k)])
                for d in range(self.num_layers - 1, -1, -1)]

    def layer_blocks(self, hops: HopBounds
                     ) -> List[Tuple[Tuple[int, int], ...]]:
        """The hop blocks ``(width, fanout)`` each layer aggregates in
        block form under ``hops`` (:func:`~glt_tpu.models.conv.block_mean`),
        first layer first: the blocks of its ``layer_extents`` edge slots.
        """
        k = len(hops.blocks)
        return [hops.blocks[:min(d + 1, k)]
                for d in range(self.num_layers - 1, -1, -1)]

    @nn.compact
    def __call__(self, x, edge_index, edge_mask, *, train: bool = False,
                 hops: Optional[HopBounds] = None):
        if hops is not None:
            if (x.shape[0] != hops.node_bounds[-1]
                    or edge_index.shape[1] != hops.edge_bounds[-1]):
                raise ValueError(
                    f"batch of {x.shape[0]} rows and {edge_index.shape[1]} "
                    f"edge slots is not laid out by {hops}")
            extents = self.layer_extents(hops)
            blocks = self.layer_blocks(hops)
        for i in range(self.num_layers):
            last = i == self.num_layers - 1
            dim = self.out_features if last else self.hidden_features
            conv = SAGEConv(dim, dtype=self.dtype, name=f"conv{i}")
            if hops is None:
                x = conv(x, edge_index, edge_mask)
            else:
                n_src, n_edge, n_dst = extents[i]
                x = conv(x[:n_src], edge_index[:, :n_edge],
                         edge_mask[:n_edge], num_dst=n_dst,
                         blocks=blocks[i])
            if not last:
                with jax.named_scope("glt.model.dense"):
                    x = nn.relu(x)
                    x = nn.Dropout(self.dropout_rate,
                                   deterministic=not train)(x)
        return x
