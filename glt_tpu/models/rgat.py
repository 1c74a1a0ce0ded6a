"""Relational (hetero) GNNs: HeteroConv combinator + R-GAT / R-SAGE.

The reference trains R-GAT on IGBH via PyG's ``HeteroConv`` dict-of-convs
pattern (examples/igbh); the framework-native equivalent consumes
:class:`~glt_tpu.loader.transform.HeteroBatch` dicts: one conv per edge
type, summed per destination node type.  :class:`RGAT` is this repo's
variant (a per-type input ``Dense``, residual layers, averaged heads, a
``Dense`` head); :class:`RGNN` is the reference's ``RGNN('rgat')``
(examples/igbh/rgnn.py).  Both are stacks of :class:`HeteroConv`.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..typing import as_str
from .conv import GATConv, SAGEConv, _mm_dtype


class HeteroConv(nn.Module):
    """Apply one conv per edge type; sum results per destination type.

    ``edge_types`` use the *batch's* (already reversed) keys: an edge type
    ``(src_t, rel, dst_t)`` aggregates messages from ``x[src_t]`` into
    ``x_dst[dst_t]`` rows (``x_dst`` defaults to ``x``; a caller that
    needs fewer destination rows than source rows passes its own).

    ``conv='gat'`` runs the bipartite :class:`GATConv` on
    ``(x[src_t], x_dst[dst_t])``: each side is projected once, no row is
    copied.  ``concat=True`` concatenates ``heads`` heads of
    ``out_features // heads`` (PyG's ``GATConv(in, out // heads,
    heads)``); ``False`` averages ``heads`` heads of ``out_features``.
    """
    edge_types: Sequence[Tuple[str, str, str]]
    out_features: int
    conv: str = "sage"      # 'sage' | 'gat'
    heads: int = 2
    concat: bool = False
    negative_slope: float = 0.2
    dtype: Any = None       # matmul compute dtype (see conv.py)

    @nn.compact
    def __call__(self, x: Dict[str, jnp.ndarray], edge_index, edge_mask,
                 x_dst: Optional[Dict[str, jnp.ndarray]] = None):
        dt = _mm_dtype(self.dtype)
        x_dst = x if x_dst is None else x_dst
        outs: Dict[str, list] = {}
        for et in self.edge_types:
            src_t, _, dst_t = et
            if et not in edge_index or src_t not in x or dst_t not in x_dst:
                continue
            ei = edge_index[et]
            if ei.shape[-1] == 0 or x_dst[dst_t].shape[0] == 0:
                continue
            mask = edge_mask[et]
            if self.conv == "gat":
                f = (self.out_features // self.heads if self.concat
                     else self.out_features)
                h = GATConv(f, heads=self.heads, concat=self.concat,
                            negative_slope=self.negative_slope,
                            dtype=self.dtype, name=f"{as_str(et)}_conv")(
                    (x[src_t], x_dst[dst_t]), ei, mask)
            else:
                # SAGEConv is a one-graph layer: stack src rows behind dst
                # rows so it can run on one node array.  Src rows are
                # aligned to the dst width only when the types' feature
                # dims genuinely differ.
                n_dst = x_dst[dst_t].shape[0]
                src_rows = x[src_t]
                if src_rows.shape[-1] != x_dst[dst_t].shape[-1]:
                    src_rows = nn.Dense(x_dst[dst_t].shape[-1], dtype=dt,
                                        name=f"{as_str(et)}_align")(
                        src_rows).astype(jnp.float32)
                joint = jnp.concatenate([x_dst[dst_t], src_rows], axis=0)
                ei_shift = jnp.stack([
                    jnp.where(ei[0] >= 0, ei[0] + n_dst, -1),
                    ei[1],
                ])
                h = SAGEConv(self.out_features, dtype=self.dtype,
                             name=f"{as_str(et)}_conv")(
                    joint, ei_shift, mask)[:n_dst]
            outs.setdefault(dst_t, []).append(h)
        with jax.named_scope("glt.model.agg"):
            return {t: sum(hs) for t, hs in outs.items()}


class RGAT(nn.Module):
    """Multi-layer relational GAT over hetero batches (IGBH-style): a
    per-type input ``Dense``, ``h + relu(conv)`` and dropout per layer,
    averaged heads, a ``Dense`` head on the target type's rows."""
    edge_types: Sequence[Tuple[str, str, str]]
    hidden_features: int
    out_features: int
    target_type: str
    num_layers: int = 2
    heads: int = 2
    conv: str = "gat"
    dropout_rate: float = 0.5
    dtype: Any = None       # matmul compute dtype (see conv.py)

    @nn.compact
    def __call__(self, x: Dict[str, jnp.ndarray], edge_index, edge_mask, *,
                 train: bool = False):
        dt = _mm_dtype(self.dtype)
        with jax.named_scope("glt.model.dense"):
            h = {t: nn.Dense(self.hidden_features, dtype=dt,
                             name=f"in_{t}")(v).astype(jnp.float32)
                 for t, v in x.items()}
        for i in range(self.num_layers):
            out = HeteroConv(self.edge_types, self.hidden_features,
                             conv=self.conv, heads=self.heads,
                             dtype=self.dtype,
                             name=f"layer{i}")(h, edge_index, edge_mask)
            with jax.named_scope("glt.model.dense"):
                # Residual per layer (the HGT layers here do the same via
                # a gated skip): target-type identity features reach the
                # head directly instead of having to survive every conv.
                # Untouched types pass through.
                h = {t: h[t] + nn.relu(out[t]) if t in out else h[t]
                     for t in h}
                if train:
                    h = {t: nn.Dropout(self.dropout_rate,
                                       deterministic=False)(v)
                         for t, v in h.items()}
        with jax.named_scope("glt.model.dense"):
            return nn.Dense(self.out_features,
                            name="head")(h[self.target_type])


class RGNN(nn.Module):
    """The reference's ``RGNN('rgat')`` (examples/igbh/rgnn.py):
    ``num_layers`` :class:`HeteroConv` s of ``GATConv(in, hidden // heads,
    heads, add_self_loops=False)``, heads concatenated, relations summed,
    ``leaky_relu`` then dropout between layers (a type with no incoming
    relation drops out, as in PyG), the last layer ``out_features`` wide,
    its ``target_type`` rows returned; no input projection, no residual,
    no head.  One departure: upstream's last layer has ``heads`` heads of
    ``out_features // heads`` (2,983 classes give 2,980 columns); here it
    has one head of ``out_features``.

    ``hops`` (a :class:`~glt_tpu.sampler.hetero_neighbor_sampler.
    HeteroHopBounds`, the sampler's static hop-block layout) runs the
    LAST layer over what reaches the seeds only: the seed rows of the
    target type, the hop-1 edge blocks and the rows first seen by hop 1.
    The seeds' logits are the whole model's (a seed is expanded at hop 1
    and nowhere else), and the result has ``node_bounds[target][0]``
    rows.  The layers before it run whole: trimming them by the same
    layout is ROADMAP Reach A.1.
    """
    edge_types: Sequence[Tuple[str, str, str]]
    hidden_features: int
    out_features: int
    target_type: str
    num_layers: int = 3
    heads: int = 4
    dropout_rate: float = 0.2
    dtype: Any = None       # matmul compute dtype (see conv.py)

    @nn.compact
    def __call__(self, x: Dict[str, jnp.ndarray], edge_index, edge_mask, *,
                 train: bool = False, hops=None):
        h, tgt = x, self.target_type
        for i in range(self.num_layers):
            last = i + 1 == self.num_layers
            ei, em, h_dst = edge_index, edge_mask, h
            if last and hops is not None:
                ei = {et: edge_index[et][:, :b[1]]
                      for et, b in hops.edge_bounds.items()
                      if et[2] == tgt and et in edge_index}
                em = {et: edge_mask[et][:ei[et].shape[1]] for et in ei}
                h_dst = {tgt: h[tgt][:hops.node_bounds[tgt][0]]}
                h = {t: v[:hops.node_bounds[t][1]] for t, v in h.items()}
            h = HeteroConv(
                self.edge_types,
                self.out_features if last else self.hidden_features,
                conv="gat", heads=1 if last else self.heads, concat=True,
                dtype=self.dtype, name=f"layer{i}")(h, ei, em, h_dst)
            if not last:
                with jax.named_scope("glt.model.dense"):
                    h = {t: nn.leaky_relu(v) for t, v in h.items()}
                    if train:
                        h = {t: nn.Dropout(self.dropout_rate,
                                           deterministic=False)(v)
                             for t, v in h.items()}
        return h[tgt]
