"""Plain float32 reference of upstream's bipartite recommendation model.

``examples/hetero/bipartite_sage_unsup.py`` of the reference library
(PyG's example on Taobao): two learned embedding tables, the item tower
over ``item -> item``, the user tower over ``item -> item`` and
``item -> user``, the MLP decoder of a (user, item) pair, binary
cross-entropy with logits, and Adam over every parameter with torch's
dense ``Embedding`` gradients.  Written out in straightforward
``jax.numpy`` under ``default_matmul_precision("highest")`` (so that
``jax.grad`` differentiates it), with none of ``glt_tpu.models``' code:
the tests hold :class:`glt_tpu.models.bipartite.BipartiteSAGE`, its step
and its update to it.  ``chipbench/reference_bipartite.py`` keeps its own
copy.

Departures from upstream's example, all of the layout, none of the
arithmetic: PyG's ``SAGEConv`` puts the bias on the neighbour side
(``lin_l``), this model's on the root side (``lin_self``): the same sum.
The model stores a table of 64-wide rows two to a 128-lane row, so
``table.reshape(-1, 64)`` is upstream's ``[N, 64]`` table
(:func:`table_rows`); it is read with the batch's node ids, zero on
padding.  Matmul inputs are float32 here and bfloat16 in the model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ITEM_ITEM = ("item", "to", "item")
ITEM_USER = ("item", "rev_to", "user")


def table_rows(p, node_type):
    """The ``[N, width]`` table of a type out of ``params['params']``."""
    width = p["item_conv1"]["lin_nbr"]["kernel"].shape[0]
    return p[f"{node_type}_emb"]["table"].reshape(-1, width)


def lookup(table, ids):
    """Rows of ``table`` at ``ids``, zero where the id is -1."""
    valid = ids >= 0
    return jnp.where(valid[:, None], table[jnp.where(valid, ids, 0)], 0.0)


def sage(c, x_src, x_dst, edge_index, mask):
    """``x_dst W_self + b + mean_{j -> i} x_src[j] W_nbr`` over the live
    edges ``edge_index[0] -> edge_index[1]``."""
    n = x_dst.shape[0]
    seg = jnp.where(mask, edge_index[1], n)
    msgs = jnp.where(mask[:, None],
                     x_src[jnp.where(mask, edge_index[0], 0)], 0.0)
    total = jax.ops.segment_sum(msgs, seg, num_segments=n + 1)[:n]
    cnt = jax.ops.segment_sum(mask.astype(jnp.float32), seg,
                              num_segments=n + 1)[:n]
    mean = total / jnp.maximum(cnt, 1.0)[:, None]
    return (x_dst @ c["lin_self"]["kernel"] + c["lin_self"]["bias"]
            + mean @ c["lin_nbr"]["kernel"])


def dense(c, x):
    return x @ c["kernel"] + c["bias"]


def logits_of_rows(p, x_user, x_item, batch):
    """The ``[Q]`` pair logits of a batch whose rows are given: ``p`` is
    the model's parameter tree (``params['params']``)."""
    with jax.default_matmul_precision("highest"):
        ii = (batch["edge_index"][ITEM_ITEM], batch["edge_mask"][ITEM_ITEM])
        iu = (batch["edge_index"][ITEM_USER], batch["edge_mask"][ITEM_USER])
        relu = jax.nn.relu
        h = relu(sage(p["item_conv1"], x_item, x_item, *ii))
        h = relu(sage(p["item_conv2"], h, h, *ii))
        z_i = dense(p["item_lin"], h)
        ix = relu(sage(p["user_conv1"], x_item, x_item, *ii))
        u = relu(sage(p["user_conv2"], x_item, x_user, *iu))
        u = relu(sage(p["user_conv3"], ix, u, *iu))
        z_u = dense(p["user_lin"], u)
        row, col = batch["pairs"]
        z = jnp.concatenate([z_u[jnp.maximum(row, 0)],
                             z_i[jnp.maximum(col, 0)]], axis=-1)
        z = relu(dense(p["dec_lin1"], z))
        return dense(p["dec_lin2"], z)[:, 0]


def bce(logits, batch):
    """Mean ``binary_cross_entropy_with_logits`` over the pairs that are
    not padding (label -1, or an endpoint -1)."""
    row, col = batch["pairs"]
    label = batch["label"]
    valid = (row >= 0) & (col >= 0) & (label >= 0)
    y = (label > 0).astype(jnp.float32)
    ce = (jnp.maximum(logits, 0.0) - logits * y
          + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return jnp.where(valid, ce, 0.0).sum() / jnp.maximum(valid.sum(), 1)


def loss_of_rows(p, x_user, x_item, batch):
    return bce(logits_of_rows(p, x_user, x_item, batch), batch)


def loss(params, batch):
    """The loss from the whole parameter tree (``{'params': ...}``):
    tables looked up by the batch's ids."""
    p = params["params"]
    return loss_of_rows(p, lookup(table_rows(p, "user"),
                                  batch["ids"]["user"]),
                        lookup(table_rows(p, "item"),
                               batch["ids"]["item"]), batch)


def grads(params, batch):
    """``(loss, gradient of every parameter)``; a table's gradient is
    dense, zero on every row the batch did not read."""
    return jax.value_and_grad(loss)(params, batch)


def adam(param, m, v, count, grad, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    """One dense Adam step of one array (torch's and optax's): ``count``
    is the steps taken before this one."""
    t = jnp.asarray(count, jnp.int32) + 1
    m = (1.0 - b1) * grad + b1 * m
    v = (1.0 - b2) * grad * grad + b2 * v
    m_hat = m / (1.0 - b1 ** t)
    v_hat = v / (1.0 - b2 ** t)
    return param + (-lr) * (m_hat / (jnp.sqrt(v_hat) + eps)), m, v
