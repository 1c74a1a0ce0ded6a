"""Learned node embeddings and upstream's bipartite recommendation model.

``examples/hetero/bipartite_sage_unsup.py`` of the reference library (PyG's
example of the same name, on the Taobao user-item graph) gives its nodes
no features: a node's input is its id, looked up in a trainable table
that the optimiser updates whole every step (torch's dense ``Embedding``
under Adam).  :class:`NodeEmbedding` is that table; :class:`BipartiteSAGE`
is the example's ``Model``: both tables, the item tower over
``item -> item``, the user tower over ``item -> item`` and
``item -> user``, and the MLP decoder of a (user, item) pair.

The batch is a typed seed-edge sample (:meth:`HeteroNeighborSampler.
edges_program`): ``x`` holds each type's node ids (-1 on padding), and
the relations are the batch's reversed keys, so ``item -> user``
messages are the sampled ``('user', 'to', 'item')`` edges.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from .conv import SAGEConv, _mm_dtype

#: The name of a table's leaf: what ``step.gated_update`` splits by.
TABLE = "table"
LANES = 128
ITEM_ITEM = ("item", "to", "item")
ITEM_USER = ("item", "rev_to", "user")


def is_table(path) -> bool:
    """Whether a parameter leaf (its ``tree_flatten_with_path`` path) is a
    :class:`NodeEmbedding` table."""
    return bool(path) and getattr(path[-1], "key", None) == TABLE


class NodeEmbedding(nn.Module):
    """A float32 table of one ``features``-wide row per node id,
    initialised N(0, 1) as torch's ``Embedding``.  ``ids`` -> rows, zero
    where the id is padding (-1).  The read runs under
    ``glt.embed.lookup``; its gradient is the scatter of the rows'
    cotangents into a table-shaped zero, under the same scope.

    Stored packed: a row narrower than the TPU's 128 lanes shares a lane
    row with its neighbours, ``[ceil(num_nodes / k), k * features]``
    with ``k = 128 // features`` (row ``i`` at packed row ``i // k``,
    lanes ``(i % k) * features`` on: the row-major order of the
    ``[num_nodes, features]`` table, so ``table.reshape(-1, features)``
    is that table).  A ``[N, 64]`` float32 array is laid out with its 64
    lanes padded to 128 inside a program that gathers its rows, and
    column-major outside it; a scanned step then converts the table and
    both moments at its entry and exit (the Taobao step's temporaries:
    12.2 GB, packed 1.6 GB; AOT for a v5e, PR 36).  Packed, the table
    and its moments keep one layout, unpadded, and the dense update
    runs in place."""
    num_nodes: int
    features: int

    @nn.compact
    def __call__(self, ids):
        k = LANES // self.features if not LANES % self.features else 1
        table = self.param(TABLE, nn.initializers.normal(1.0),
                           (-(-self.num_nodes // k), k * self.features),
                           jnp.float32)
        with jax.named_scope("glt.embed.lookup"):
            valid = ids >= 0
            safe = jnp.where(valid, ids, 0)
            packed = jnp.take(table, safe // k, axis=0, mode="clip")
            lane_row = packed.reshape(ids.shape[0], k, self.features)
            pick = jax.nn.one_hot(safe % k, k, dtype=jnp.float32)
            rows = (lane_row * pick[:, :, None]).sum(axis=1)
            return jnp.where(valid[:, None], rows, 0.0)


class BipartiteSAGE(nn.Module):
    """Upstream's ``Model(num_users, num_items, hidden, out)``.

    With ``SAGE(a, b)_i = W_l mean_{j in N(i)} a_j + b_l + W_r b_i``:

    * item tower: ``h = relu(SAGE1(x_i)); h = relu(SAGE2(h)); z_i =
      Lin(h)`` over ``item -> item``;
    * user tower: ``ix = relu(SAGE1'(x_i))`` over ``item -> item``, ``u =
      relu(SAGE2'((x_i, x_u)))`` and ``u = relu(SAGE3'((ix, u)))`` over
      ``item -> user``, ``z_u = Lin'(u)``;
    * decoder: ``w2 . relu(W1 [z_u[row]; z_i[col]] + b1) + b2``.

    ``__call__(x, edge_index, edge_mask)``: ``x`` is ``(ids, pairs)``,
    ``ids = {'user': [N_u], 'item': [N_i]}`` the batch's node ids and
    ``pairs`` its ``[2, Q]`` pair index (user row, item row; -1 on
    padding): the model reads its inputs out of its tables and scores
    the pairs itself.  Returns ``[Q]`` logits.  No dropout: ``train``
    changes nothing.
    """
    num_users: int
    num_items: int
    hidden: int = 64
    out: int = 64
    dtype: Any = None       # matmul compute dtype (see conv.py)

    @property
    def table_rows(self) -> Dict[str, int]:
        return {"user": self.num_users, "item": self.num_items}

    @nn.compact
    def __call__(self, x, edge_index, edge_mask, train: bool = False):
        del train
        x, pairs = x
        dt = _mm_dtype(self.dtype)
        dense = partial(nn.Dense, dtype=dt)
        conv = partial(SAGEConv, self.hidden, dtype=self.dtype)
        xu = NodeEmbedding(self.num_users, self.hidden, name="user_emb")(
            x["user"])
        xi = NodeEmbedding(self.num_items, self.hidden, name="item_emb")(
            x["item"])
        ii = (edge_index[ITEM_ITEM], edge_mask[ITEM_ITEM])
        iu = (edge_index[ITEM_USER], edge_mask[ITEM_USER])

        def relu(h):
            with jax.named_scope("glt.model.dense"):
                return nn.relu(h)

        h = relu(conv(name="item_conv1")(xi, *ii))
        h = relu(conv(name="item_conv2")(h, *ii))
        ix = relu(conv(name="user_conv1")(xi, *ii))
        u = relu(conv(name="user_conv2")((xi, xu), *iu))
        u = relu(conv(name="user_conv3")((ix, u), *iu))
        with jax.named_scope("glt.model.dense"):
            z_i = dense(self.out, name="item_lin")(h).astype(jnp.float32)
            z_u = dense(self.out, name="user_lin")(u).astype(jnp.float32)
        with jax.named_scope("glt.model.msg"):
            z = jnp.concatenate(
                [jnp.take(z_u, jnp.maximum(pairs[0], 0), axis=0, mode="clip"),
                 jnp.take(z_i, jnp.maximum(pairs[1], 0), axis=0,
                          mode="clip")], axis=-1)
        with jax.named_scope("glt.model.dense"):
            z = nn.relu(dense(self.out, name="dec_lin1")(z))
            return dense(1, name="dec_lin2")(z).astype(jnp.float32)[:, 0]


def init_state(model: BipartiteSAGE, tx, rng):
    """The :class:`~glt_tpu.models.step.TrainState` of ``model``: tables
    and moments made on the device in one program (no batch: one padded
    row a type and relation)."""
    from ..typing import PADDING_ID
    from .step import TrainState

    def make(key):
        none = jnp.full((1,), PADDING_ID, jnp.int32)
        rels = (ITEM_ITEM, ITEM_USER)
        params = model.init(
            {"params": key}, ({"user": none, "item": none},
                              jnp.full((2, 1), PADDING_ID, jnp.int32)),
            {et: jnp.full((2, 1), PADDING_ID, jnp.int32) for et in rels},
            {et: jnp.zeros((1,), bool) for et in rels})
        return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))

    return jax.jit(make)(rng)
