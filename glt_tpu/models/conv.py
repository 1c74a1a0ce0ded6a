"""Graph convolution layers over padded COO batches, flax-native.

The reference library ships no models (GNNs come from PyG; see SURVEY §0),
but its sampled batches exist to feed SAGEConv/GATConv-style layers — so a
complete TPU framework must provide them.  These layers consume
:class:`~glt_tpu.loader.transform.Batch` tensors directly: ``[2, E]`` COO
with -1 padding and an ``edge_mask``, ``edge_index[0]`` = message source
(the sampler already transposed direction, neighbor_sampler.py:159-165).

TPU notes: aggregation is ``jax.ops.segment_sum`` with a spill segment for
padding edges (XLA lowers this to sorted-scatter, MXU-friendly); all matmuls
are batched over the padded node dimension so shapes are static.
``SAGEConv`` can aggregate into a static prefix of the rows only
(``num_dst``), which is how :class:`~glt_tpu.models.sage.GraphSAGE` trims
each layer to the hops whose result reaches the seeds.

Mixed precision: every layer takes ``dtype`` (e.g. ``jnp.bfloat16``) — the
COMPUTE dtype of its Dense matmuls only.  Params stay float32, the MXU
accumulates in float32 natively, outputs are cast back to float32, and the
gather/segment aggregation path is untouched.  The reference's torch
examples train in f32 (examples/train_sage_ogbn_products.py); bf16 is
the MXU's native input type.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

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
    """
    out_features: int
    use_bias: bool = True
    dtype: Any = None   # matmul compute dtype (e.g. bf16); params/agg f32

    @nn.compact
    def __call__(self, x, edge_index, edge_mask,
                 num_dst: Optional[int] = None):
        num_src = x.shape[0]
        if num_dst is None:
            num_dst = num_src
        src, dst = edge_index[0], edge_index[1]
        with jax.named_scope("glt.model.msg"):
            msgs = jnp.take(x, jnp.clip(src, 0, num_src - 1), axis=0)
        agg = scatter_mean(msgs, dst, num_dst, edge_mask)
        dt = _mm_dtype(self.dtype)
        with jax.named_scope("glt.model.dense"):
            out = (nn.Dense(self.out_features, use_bias=self.use_bias,
                            dtype=dt, name="lin_self")(x[:num_dst])
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
