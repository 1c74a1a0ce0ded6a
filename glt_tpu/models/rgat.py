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

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..typing import as_str
from .conv import GATConv, SAGEConv, _mm_dtype


class HeteroConv(nn.Module):
    """Apply one conv per edge type; sum results per destination type.

    ``edge_types`` use the *batch's* (already reversed) keys: an edge type
    ``(src_t, rel, dst_t)`` aggregates messages from ``x[src_t]`` into the
    rows of ``dst_t``.  A relation with no edge slot, and a type with no
    row or no incoming relation, drop out.

    ``num_dst`` (``node_type -> rows``) is for a caller that reads a
    prefix of the result only (:class:`RGNN` under ``hops``): the
    destinations of type ``t`` are the first ``num_dst[t]`` rows of
    ``x[t]``, the result has that many, and a type not listed is not
    computed.  The caller has cut ``x`` and the edge slots to what those
    rows need, so emptiness means nothing here: a relation with no slot
    left still gives its bias, a type with no row left stays (with none).

    ``conv='gat'`` runs the bipartite :class:`GATConv` on
    ``(x[src_t], x[dst_t][:n])``: each side is projected once, no row is
    copied; a relation whose two ends are one type projects its rows once
    and takes the destinations' projection as the prefix.
    ``concat=True`` concatenates ``heads`` heads of ``out_features //
    heads`` (PyG's ``GATConv(in, out // heads, heads)``); ``False``
    averages ``heads`` heads of ``out_features``.
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
                 num_dst: Optional[Dict[str, int]] = None):
        dt = _mm_dtype(self.dtype)
        outs: Dict[str, list] = {}
        for et in self.edge_types:
            src_t, _, dst_t = et
            if et not in edge_index or src_t not in x or dst_t not in x:
                continue
            ei, mask, dst_rows = edge_index[et], edge_mask[et], x[dst_t]
            if num_dst is None:
                if ei.shape[-1] == 0 or dst_rows.shape[0] == 0:
                    continue
            elif dst_t in num_dst:
                with jax.named_scope("glt.model.dense"):
                    dst_rows = dst_rows[:num_dst[dst_t]]
            else:
                continue
            n_dst = dst_rows.shape[0]
            if self.conv == "gat":
                f = (self.out_features // self.heads if self.concat
                     else self.out_features)
                conv = GATConv(f, heads=self.heads, concat=self.concat,
                               negative_slope=self.negative_slope,
                               dtype=self.dtype, name=f"{as_str(et)}_conv")
                if src_t == dst_t:
                    h = conv(x[src_t], ei, mask, num_dst=n_dst)
                else:
                    h = conv((x[src_t], dst_rows), ei, mask)
            else:
                # SAGEConv is a one-graph layer: stack src rows behind dst
                # rows so it can run on one node array.  Src rows are
                # aligned to the dst width only when the types' feature
                # dims genuinely differ.
                src_rows = x[src_t]
                if src_rows.shape[-1] != dst_rows.shape[-1]:
                    src_rows = nn.Dense(dst_rows.shape[-1], dtype=dt,
                                        name=f"{as_str(et)}_align")(
                        src_rows).astype(jnp.float32)
                joint = jnp.concatenate([dst_rows, src_rows], axis=0)
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

    **Per-layer trimming** (``hops=``, a :class:`~glt_tpu.sampler.
    hetero_neighbor_sampler.HeteroHopBounds`: the sampler's static
    hop-block layout) runs every layer over what reaches the seeds only,
    the typed :class:`~glt_tpu.models.sage.GraphSAGE` ``hops=``.  Layer
    ``l`` of ``L`` (1-based) sits ``d = L - l`` layers under the output
    (``d`` clamps to the number of hops) and runs on

    * destinations: rows ``[:node_bounds[t][d]]`` of each type ``t`` (the
      last layer: the target type alone);
    * edges: slots ``[:edge_bounds[et][d + 1]]`` of each relation into
      such a type: hop blocks ``1..d+1``;
    * sources: rows ``[:node_bounds[t][d + 1]]`` of each type, which is
      what layer ``l - 1`` emits.

    The seeds' logits (``node_bounds[target][0]`` rows come back), the
    loss and every gradient are the whole model's, up to float32
    reassociation.  Every valid edge of hop block ``k`` of ``(s, rel, d)``
    has ``col < node_bounds[d][k-1]`` and ``row < node_bounds[s][k]``, and
    a node is expanded once, at the hop after it was first seen (or never:
    a node past a frontier's capacity stays a leaf, one past a node
    buffer's has its edges masked).  So a node first seen by hop ``j`` has
    ALL its incoming edges in blocks ``<= j + 1``: its segment softmax
    over the trimmed edges is its softmax over all of them, and it reads
    sources seen by hop ``j + 1``, which the layer below computed from
    blocks ``<= j + 2``, and so on down to the features.  The bounds count
    candidates, not uniques, so a row under a bound may hold a node seen
    later, whose edges were cut: its value is wrong and nothing reads it,
    because block-``k`` edges read only rows truly seen by hop ``k``.  A
    type with no row at depth ``d`` (IGBH's institutes at ``d <= 1``) has
    nothing to compute in that layer; which relations and types are live
    is decided on the whole batch's shapes, as without ``hops``.  Dropout
    draws one mask a layer output, so with ``train=True`` a trimmed layer
    draws a smaller mask: the same distribution, not the same sample.
    A batch not laid out by ``hops`` raises.
    """
    edge_types: Sequence[Tuple[str, str, str]]
    hidden_features: int
    out_features: int
    target_type: str
    num_layers: int = 3
    heads: int = 4
    dropout_rate: float = 0.2
    dtype: Any = None       # matmul compute dtype (see conv.py)

    def typed_extents(self, hops) -> List[Tuple[Dict, Dict, Dict]]:
        """``(source rows by type, edge slots by relation, destination
        rows by type)`` of each layer under ``hops``, first layer first
        (the rule in the class docstring)."""
        nb, eb = hops.node_bounds, hops.edge_bounds
        k = len(nb[self.target_type]) - 1
        out = []
        for d in range(self.num_layers - 1, -1, -1):
            lo, hi = min(d, k), min(d + 1, k)
            dst = {t: b[lo] for t, b in nb.items()
                   if d or t == self.target_type}
            out.append(({t: b[hi] for t, b in nb.items()},
                        {et: eb[et][hi]
                         for et in self.edge_types if et[2] in dst},
                        dst))
        return out

    def layer_extents(self, hops) -> List[Tuple[int, int, int]]:
        """``(source rows, edge slots, destination rows)`` of each layer
        under ``hops``, summed over types and relations (what
        :func:`~glt_tpu.models.step.hop_trimming` records)."""
        return [tuple(sum(part.values()) for part in layer)
                for layer in self.typed_extents(hops)]

    @nn.compact
    def __call__(self, x: Dict[str, jnp.ndarray], edge_index, edge_mask, *,
                 train: bool = False, hops=None):
        h, tgt = x, self.target_type
        if hops is not None:
            rows = {t: v.shape[0] for t, v in x.items()}
            slots = {et: v.shape[1] for et, v in edge_index.items()}
            nb, eb = hops.node_bounds, hops.edge_bounds
            # (A type nothing reaches has one padding row in the batch.)
            if (any(t not in nb or max(nb[t][-1], 1) != n
                    for t, n in rows.items())
                    or any(et not in eb or eb[et][-1] != n
                           for et, n in slots.items())):
                raise ValueError(
                    f"batch of {rows} rows and {slots} edge slots is not "
                    f"laid out by {hops}")
            extents = self.typed_extents(hops)
            # Which relations and types are live is the whole batch's
            # matter: one with no slot or no row there is not in the
            # whole model either; a cut that leaves none says nothing.
            h = {t: v for t, v in x.items() if rows[t]}
            edge_index = {et: v for et, v in edge_index.items() if slots[et]}
        for i in range(self.num_layers):
            last = i + 1 == self.num_layers
            ei, em, n_dst = edge_index, edge_mask, None
            if hops is not None:
                n_src, n_edge, n_dst = extents[i]
                with jax.named_scope("glt.model.msg"):
                    ei = {et: edge_index[et][:, :n]
                          for et, n in n_edge.items() if et in edge_index}
                    em = {et: edge_mask[et][:n_edge[et]] for et in ei}
                with jax.named_scope("glt.model.dense"):
                    h = {t: v[:n_src[t]] for t, v in h.items()}
            h = HeteroConv(
                self.edge_types,
                self.out_features if last else self.hidden_features,
                conv="gat", heads=1 if last else self.heads, concat=True,
                dtype=self.dtype, name=f"layer{i}")(h, ei, em, n_dst)
            if not last:
                with jax.named_scope("glt.model.dense"):
                    h = {t: nn.leaky_relu(v) for t, v in h.items()}
                    if train:
                        h = {t: nn.Dropout(self.dropout_rate,
                                           deterministic=False)(v)
                             for t, v in h.items()}
        return h[tgt]
