"""Graph convolution layers over padded COO batches, flax-native.

The reference library ships no models (GNNs come from PyG; see SURVEY §0),
but its sampled batches exist to feed SAGEConv/GATConv-style layers — so a
complete TPU framework must provide them.  These layers consume
:class:`~glt_tpu.loader.transform.Batch` tensors directly: ``[2, E]`` COO
with -1 padding and an ``edge_mask``, ``edge_index[0]`` = message source
(the sampler already transposed direction, neighbor_sampler.py:159-165).

TPU notes: all matmuls are batched over the padded node dimension so
shapes are static.  The mean over a destination's in-edges has two forms,
chosen at trace time by what the caller knows about ``edge_index``:

* any COO (:func:`scatter_mean`, :func:`scatter_sum`,
  :func:`segment_softmax`): ``jax.ops.segment_sum`` with a spill segment
  for padding edges.  XLA lowers it to a scatter-add that knows nothing
  about where its rows go; on the v5e it was the costliest operation of
  two benchmark cells, 25-45 times over what its bytes cost (PERF.md §6,
  PR 31).
* the sampler's hop blocks (:func:`block_mean`; ``SAGEConv(blocks=)``,
  which :class:`~glt_tpu.models.sage.GraphSAGE` passes whenever it has
  the layout): the destination of every edge slot is its block's start
  plus a static offset, so the sum is contiguous slabs added and no
  scatter runs.

``SAGEConv`` can aggregate into a static prefix of the rows only
(``num_dst``), which is how ``GraphSAGE`` trims each layer to the hops
whose result reaches the seeds.

Mixed precision: every layer takes ``dtype`` (e.g. ``jnp.bfloat16``) — the
COMPUTE dtype of its Dense matmuls only.  Params stay float32, the MXU
accumulates in float32 natively, outputs are cast back to float32, and the
gather/segment aggregation path is untouched.  The reference's torch
examples train in f32 (examples/train_sage_ogbn_products.py); bf16 is
the MXU's native input type.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax

from ..obs.scopes import scoped


def _mm_dtype(dtype):
    """Resolve a layer's matmul compute dtype (None = full f32)."""
    return None if dtype is None else jnp.dtype(dtype)


@scoped("glt.model.agg")
def scatter_sum(msgs: jnp.ndarray, dst: jnp.ndarray, num_nodes: int,
                mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Sum messages into destination slots; -1/masked edges go to a spill row."""
    if mask is None:
        mask = dst >= 0
    seg = jnp.where(mask, dst, num_nodes)
    msgs = jnp.where(mask[:, None], msgs, 0)
    return jax.ops.segment_sum(msgs, seg, num_segments=num_nodes + 1)[:num_nodes]


@scoped("glt.model.agg")
def scatter_mean(msgs: jnp.ndarray, dst: jnp.ndarray, num_nodes: int,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if mask is None:
        mask = dst >= 0
    s = scatter_sum(msgs, dst, num_nodes, mask)
    seg = jnp.where(mask, dst, num_nodes)
    cnt = jax.ops.segment_sum(mask.astype(msgs.dtype), seg,
                              num_segments=num_nodes + 1)[:num_nodes]
    return s / jnp.maximum(cnt, 1)[:, None]


@scoped("glt.model.agg")
def segment_softmax(scores: jnp.ndarray, seg: jnp.ndarray, num_segments: int,
                    mask: jnp.ndarray) -> jnp.ndarray:
    """Numerically-stable softmax over edges grouped by destination.

    The exponent is clamped at 0 BEFORE ``exp``: valid lanes satisfy
    ``score <= smax`` by construction (no-op), but masked lanes route to
    the spill segment whose max is reset to 0 — once attention scores
    grow past ~88, ``exp`` of those discarded lanes overflows to inf and
    the ``where`` backward turns 0-cotangent x inf into NaN grads
    (observed on TPU at config-4 scale 10, batch 136).
    """
    seg_safe = jnp.where(mask, seg, num_segments)
    smax = jax.ops.segment_max(jnp.where(mask, scores, -jnp.inf), seg_safe,
                               num_segments=num_segments + 1)
    smax = jnp.where(jnp.isfinite(smax), smax, 0)
    ex = jnp.where(mask,
                   jnp.exp(jnp.minimum(scores - smax[seg_safe], 0.0)), 0)
    denom = jax.ops.segment_sum(ex, seg_safe, num_segments=num_segments + 1)
    return ex / jnp.maximum(denom[seg_safe], 1e-16)


def block_mean(x: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
               mask: jnp.ndarray, blocks: Sequence[Tuple[int, int]],
               num_dst: int) -> jnp.ndarray:
    """``scatter_mean(x[src], dst, num_dst, mask)`` where the edge slots
    are the sampler's hop blocks: no scatter.

    ``blocks`` is the static ``(width, fanout)`` of each hop block the
    slots are made of, in order (``HopBounds.blocks``); slot ``s`` of a
    block aggregates into row ``start + s // fanout``, ``start`` being
    the ``dst`` of the block's first slot (``-1``: an empty frontier,
    every slot of the block is then masked).  Each block's slots are
    read fanout-major (a transpose of 4 bytes a slot), so that ONE row
    gather writes ``fanout`` contiguous slabs of ``[width, F]`` and the
    sum over a destination's slots is those slabs added: a streaming
    read of the messages where ``segment_sum`` is a scatter-add that
    runs 25-45 times over its bytes (PERF.md §6, PR 31).  The count is
    the mask summed the same way.  The blocks' means are then written at
    their dynamic starts, in order, into ``num_dst`` zero rows padded by
    the widest block (so that no update is clamped; a block without a
    frontier lands in the padding).  A plain contiguous store serves
    because the layout has the starts ascend and every row of a block
    behind its live ones is zero: a later block overwrites only zeros.

    The same terms as the scatter form under the same mask in float32;
    only the order of a destination's at most ``fanout`` additions
    differs.
    """
    offsets = np.cumsum([0] + [w * f for w, f in blocks])
    if src.shape[0] != offsets[-1]:
        raise ValueError(f"{src.shape[0]} edge slots are not the hop "
                         f"blocks {tuple(blocks)}")

    def fanout_major(a):
        return [a[o:o + w * f].reshape(w, f).T
                for o, (w, f) in zip(offsets, blocks)]

    with jax.named_scope("glt.model.agg"):
        src_t = jnp.concatenate([b.ravel() for b in fanout_major(src)])
    with jax.named_scope("glt.model.msg"):
        # Clamped inside the gather: a masked slot's -1 reads row 0 and
        # no fill pass runs over the messages.
        msgs = jnp.take(x, src_t, axis=0, mode="clip")
    with jax.named_scope("glt.model.agg"):
        out = jnp.zeros((num_dst + max(w for w, _ in blocks),
                         msgs.shape[1]), msgs.dtype)
        for o, (w, f), m_t in zip(offsets, blocks, fanout_major(mask)):
            slabs = msgs[o:o + w * f].reshape(f, w, -1)
            total = jnp.where(m_t[:, :, None], slabs, 0).sum(0)
            cnt = m_t.sum(0).astype(msgs.dtype)
            start = jnp.where(dst[o] >= 0, dst[o], num_dst)
            out = lax.dynamic_update_slice(
                out, total / jnp.maximum(cnt, 1)[:, None], (start, 0))
        return out[:num_dst]


class SAGEConv(nn.Module):
    """GraphSAGE convolution (mean aggregator).

    ``h_i = W_self x_i + W_nbr mean_{j->i} x_j``

    ``num_dst`` makes the layer bipartite over a prefix: messages are
    gathered from every row of ``x``, summed into the first ``num_dst``
    rows only, and ``lin_self`` runs on ``x[:num_dst]``; the result has
    ``num_dst`` rows.  The caller guarantees that every unmasked edge
    has ``dst < num_dst`` (the sampler's hop-block layout does, see
    :func:`~glt_tpu.sampler.neighbor_sampler.hop_bounds`), so rows
    ``< num_dst`` are the numbers the whole layer computes for them.
    ``None`` is the whole layer: every row of ``x`` is a destination.

    ``blocks`` says that the edge slots are that layout's hop blocks
    (their static ``(width, fanout)``, ``HopBounds.blocks``): the mean
    is then :func:`block_mean`, a contiguous sum a block.  Without it
    ``edge_index`` is any COO and the mean is :func:`scatter_mean`.

    ``x`` may be a pair ``(x_src, x_dst)``, PyG's bipartite form for a
    relation between two node types: messages are gathered from
    ``x_src`` (``edge_index[0]``), summed into the rows of ``x_dst``
    (``edge_index[1]``), and ``lin_self`` runs on ``x_dst``.
    """
    out_features: int
    use_bias: bool = True
    dtype: Any = None   # matmul compute dtype (e.g. bf16); params/agg f32

    @nn.compact
    def __call__(self, x, edge_index, edge_mask,
                 num_dst: Optional[int] = None,
                 blocks: Optional[Sequence[Tuple[int, int]]] = None):
        x, x_dst = x if isinstance(x, (tuple, list)) else (x, x)
        num_src = x.shape[0]
        if num_dst is None:
            num_dst = x_dst.shape[0]
        src, dst = edge_index[0], edge_index[1]
        if blocks is None:
            with jax.named_scope("glt.model.msg"):
                msgs = jnp.take(x, jnp.clip(src, 0, num_src - 1), axis=0)
            agg = scatter_mean(msgs, dst, num_dst, edge_mask)
        else:
            agg = block_mean(x, src, dst, edge_mask, blocks, num_dst)
        dt = _mm_dtype(self.dtype)
        with jax.named_scope("glt.model.dense"):
            out = (nn.Dense(self.out_features, use_bias=self.use_bias,
                            dtype=dt, name="lin_self")(x_dst[:num_dst])
                   + nn.Dense(self.out_features, use_bias=False,
                              dtype=dt, name="lin_nbr")(agg))
            return out if dt is None else out.astype(jnp.float32)


class GATConv(nn.Module):
    """Graph attention convolution (GATv1, multi-head, concat).

    ``x`` is one array (every row is a source and a destination) or a
    pair ``(x_src, x_dst)`` — PyG's bipartite form, the one a typed
    relation needs: the one ``lin`` projects each side, ``alpha_src``
    comes from the source projection and ``alpha_dst`` from the
    destination's, the softmax runs over each destination's incoming
    edges, and the result has ``x_dst.shape[0]`` rows.  ``edge_index[0]``
    indexes ``x_src``, ``edge_index[1]`` indexes ``x_dst``.

    ``num_dst`` (with one array) makes the first ``num_dst`` rows the
    destinations, as in :class:`SAGEConv`: every row is projected once,
    the destinations' projection is the prefix of it, and the result has
    ``num_dst`` rows.  The caller guarantees that every unmasked edge has
    ``dst < num_dst``.  With no edge slot at all the result is the bias.
    """
    out_features: int
    heads: int = 1
    concat: bool = True
    negative_slope: float = 0.2
    dtype: Any = None   # matmul compute dtype; attention math stays f32

    @nn.compact
    def __call__(self, x, edge_index, edge_mask,
                 num_dst: Optional[int] = None):
        pair = isinstance(x, (tuple, list))
        x_src, x_dst = x if pair else (x, None)
        num_src = x_src.shape[0]
        if pair:
            num_dst = x_dst.shape[0]
        elif num_dst is None:
            num_dst = num_src
        h, f = self.heads, self.out_features
        src, dst = edge_index[0], edge_index[1]
        src_c = jnp.clip(src, 0, num_src - 1)
        dst_c = jnp.clip(dst, 0, num_dst - 1)

        with jax.named_scope("glt.model.dense"):
            lin = nn.Dense(h * f, use_bias=False,
                           dtype=_mm_dtype(self.dtype), name="lin")
            z = lin(x_src).astype(jnp.float32).reshape(num_src, h, f)
            if pair:
                z_dst = lin(x_dst).astype(jnp.float32).reshape(num_dst, h, f)
            else:
                z_dst = z if num_dst == num_src else z[:num_dst]
            att_src = self.param("att_src",
                                 nn.initializers.glorot_uniform(), (h, f))
            att_dst = self.param("att_dst",
                                 nn.initializers.glorot_uniform(), (h, f))
            alpha_src = (z * att_src).sum(-1)       # [N_src, h]
            alpha_dst = (z_dst * att_dst).sum(-1)   # [N_dst, h]

        with jax.named_scope("glt.model.msg"):
            e = alpha_src[src_c] + alpha_dst[dst_c]          # [E, h]
            e = nn.leaky_relu(e, self.negative_slope)
        # Per-head softmax over incoming edges of each destination.
        alpha = jax.vmap(
            lambda s: segment_softmax(s, dst, num_dst, edge_mask),
            in_axes=1, out_axes=1)(e)                    # [E, h]
        with jax.named_scope("glt.model.msg"):
            msgs = z[src_c] * alpha[:, :, None]          # [E, h, f]
        out = scatter_sum(msgs.reshape(-1, h * f), dst, num_dst,
                          edge_mask).reshape(num_dst, h, f)
        with jax.named_scope("glt.model.dense"):
            if self.concat:
                out = out.reshape(num_dst, h * f)
            else:
                out = out.mean(axis=1)
            bias = self.param("bias", nn.initializers.zeros,
                              (out.shape[-1],))
            return out + bias
