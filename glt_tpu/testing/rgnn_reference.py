"""Plain float32 reference of upstream's ``RGNN('rgat')``.

``examples/igbh/rgnn.py`` of the reference library with PyG's bipartite
``GATConv(add_self_loops=False)``, written out relation by relation in
straightforward ``jax.numpy`` (so that ``jax.grad`` differentiates it),
with none of ``glt_tpu.models``' code: the tests hold
:class:`glt_tpu.models.rgat.RGNN` to it on forward, loss and
gradients.  ``chipbench/reference_hetero.py`` keeps its own copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_weights(params, edge_types, num_layers: int):
    """``[{edge_type: (W, att_src, att_dst, bias)}, ...]`` out of the
    Flax tree of :class:`~glt_tpu.models.rgat.RGNN` (names
    ``layer<i>/<src>__<rel>__<dst>_conv``)."""
    tree = params["params"]
    out = []
    for i in range(num_layers):
        layer = {}
        for et in edge_types:
            c = tree[f"layer{i}"].get("__".join(et) + "_conv")
            if c is not None:
                layer[tuple(et)] = (c["lin"]["kernel"], c["att_src"],
                                    c["att_dst"], c["bias"])
        out.append(layer)
    return out


def gat_relation(w, att_src, att_dst, bias, x_src, x_dst, src, dst, mask,
                 negative_slope: float = 0.2):
    """One relation's bipartite GAT: ``[N_dst, heads * f]``."""
    h, f = att_src.shape
    n_dst = x_dst.shape[0]
    z_src = (x_src @ w).reshape(-1, h, f)
    z_dst = (x_dst @ w).reshape(-1, h, f)
    src = jnp.where(mask, src, 0)
    seg = jnp.where(mask, dst, n_dst)               # spill segment
    e = ((z_src * att_src).sum(-1)[src]
         + (z_dst * att_dst).sum(-1)[jnp.where(mask, dst, 0)])
    e = jnp.where(e > 0, e, negative_slope * e)
    e = jnp.where(mask[:, None], e, -jnp.inf)
    top = jax.ops.segment_max(e, seg, num_segments=n_dst + 1)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    p = jnp.where(mask[:, None], jnp.exp(e - top[seg]), 0.0)
    den = jax.ops.segment_sum(p, seg, num_segments=n_dst + 1)
    alpha = p / jnp.maximum(den[seg], 1e-16)
    out = jax.ops.segment_sum(z_src[src] * alpha[:, :, None], seg,
                              num_segments=n_dst + 1)[:n_dst]
    return out.reshape(n_dst, h * f) + bias


def rgnn_forward(weights, x, edges, target_type: str):
    """Logits ``[N_target, classes]``.  ``x``: ``{type: [N_t, d]}``;
    ``edges``: ``{edge_type: (src, dst, mask)}`` with ``src`` indexing
    the source type's rows."""
    with jax.default_matmul_precision("highest"):
        h = {t: v.astype(jnp.float32) for t, v in x.items()}
        for i, layer in enumerate(weights):
            out = {}
            for et, (w, a_s, a_d, b) in layer.items():
                s_t, _, d_t = et
                if et not in edges or s_t not in h or d_t not in h:
                    continue
                o = gat_relation(w, a_s, a_d, b, h[s_t], h[d_t],
                                 *edges[et])
                out[d_t] = out[d_t] + o if d_t in out else o
            if i + 1 < len(weights):
                out = {t: jnp.where(v > 0, v, 0.01 * v)
                       for t, v in out.items()}
            h = out
    return h[target_type]


def seed_loss(logits, y, num_seeds: int):
    """Mean softmax cross-entropy over the seed rows with a label."""
    sl, sy = logits[:num_seeds], y[:num_seeds]
    valid = sy >= 0
    logp = jax.nn.log_softmax(sl.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, sy, 0)[:, None],
                              axis=1)[:, 0]
    return jnp.where(valid, ce, 0.0).sum() / jnp.maximum(valid.sum(), 1)
