"""The plain reference: same semantics, none of the program's code.

Two halves.

* Data.  ``RefData`` holds only the row pointers (fetched once from the
  generator's output) and recomputes adjacency lists, feature rows and
  labels from ``draws.py`` in ``numpy``.
* Model.  ``sage_forward`` / ``seed_loss`` are GraphSAGE (mean
  aggregator, ``h_i = W_self x_i + b + W_nbr mean_{j->i} x_j``, ReLU
  between layers; upstream ``examples/train_sage_ogbn_products.py`` with
  PyG ``SAGEConv``) in straightforward ``jax.numpy``, float32, under
  ``jax.default_matmul_precision("highest")``.  Departure from the
  source: dropout is off (evaluation mode) — the program's training
  mask comes out of its own RNG plumbing, which a plain reference cannot
  reproduce without the program's code, so the program is held to the
  reference on its evaluation-mode forward at the same parameters and
  the same sampled batch.
"""
from __future__ import annotations

import numpy as np

from chipbench import draws


class RefData:
    """Recomputes what the generator made, from positions alone."""

    def __init__(self, shapes, seed: int, indptr: np.ndarray):
        self.sh = shapes
        self.indptr = np.asarray(indptr).astype(np.int64)   # [S, c + 1]
        self._k_nbr = np.uint32(draws.stream_key(seed, draws.NEIGHBOUR))
        self._k_feat = np.uint32(draws.stream_key(seed, draws.FEATURE))
        self._k_lab = np.uint32(draws.stream_key(seed, draws.LABEL))

    def degree(self, nodes) -> np.ndarray:
        nodes = np.asarray(nodes, np.int64)
        c = self.sh.nodes_per_shard
        s, r = nodes // c, nodes % c
        return self.indptr[s, r + 1] - self.indptr[s, r]

    def neighbours(self, node: int) -> np.ndarray:
        """The adjacency list of ``node`` (global ids, CSR order)."""
        sh = self.sh
        c, es = sh.nodes_per_shard, sh.edges_per_shard
        s, r = divmod(int(node), c)
        lo, hi = self.indptr[s, r], self.indptr[s, r + 1]
        pos = (s * es + np.arange(lo, hi)).astype(np.uint32)
        q = (draws.mix32(pos ^ self._k_nbr)
             % np.uint32(sh.num_edges)).astype(np.int64)
        t, x = q // es, q % es
        src = np.empty_like(q)
        for shard in np.unique(t):
            m = t == shard
            src[m] = np.searchsorted(self.indptr[shard], x[m],
                                     side="right") - 1
        return t * c + src

    def features(self, nodes) -> np.ndarray:
        """Feature rows of ``nodes``; a negative id gives a zero row."""
        nodes = np.asarray(nodes, np.int64)
        d = self.sh.feature_dim
        safe = np.where(nodes >= 0, nodes, 0).astype(np.uint32)
        cnt = safe[:, None] * np.uint32(d) + np.arange(d, dtype=np.uint32)
        rows = draws.unit_signed(draws.mix32(cnt ^ self._k_feat), np)
        return np.where((nodes >= 0)[:, None], rows,
                        np.float32(0)).astype(np.float32)

    def labels(self, nodes) -> np.ndarray:
        """Labels of ``nodes``; a negative id gives -1."""
        nodes = np.asarray(nodes, np.int64)
        safe = np.where(nodes >= 0, nodes, 0).astype(np.uint32)
        lab = (draws.mix32(safe ^ self._k_lab)
               % np.uint32(self.sh.num_classes)).astype(np.int32)
        return np.where(nodes >= 0, lab, -1).astype(np.int32)


# -- the model ---------------------------------------------------------------

def layer_weights(params, num_layers: int):
    """``[(W_self, b, W_nbr), ...]`` out of the program's parameter tree
    (Flax names ``conv<i>/lin_self|lin_nbr``, models/sage.py)."""
    tree = params["params"]
    return [(tree[f"conv{i}"]["lin_self"]["kernel"],
             tree[f"conv{i}"]["lin_self"]["bias"],
             tree[f"conv{i}"]["lin_nbr"]["kernel"])
            for i in range(num_layers)]


def sage_forward(weights, x, src, dst, edge_mask):
    """Logits ``[N, classes]`` of mean-aggregator GraphSAGE."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    with jax.default_matmul_precision("highest"):
        seg = jnp.where(edge_mask, dst, n)
        cnt = jax.ops.segment_sum(edge_mask.astype(jnp.float32), seg,
                                  num_segments=n + 1)[:n]
        h = x.astype(jnp.float32)
        for i, (w_self, b, w_nbr) in enumerate(weights):
            msgs = jnp.where(edge_mask[:, None],
                             h[jnp.clip(src, 0, n - 1)], 0.0)
            agg = jax.ops.segment_sum(msgs, seg, num_segments=n + 1)[:n]
            agg = agg / jnp.maximum(cnt, 1.0)[:, None]
            h = h @ w_self + b + agg @ w_nbr
            if i + 1 < len(weights):
                h = jnp.maximum(h, 0.0)
    return h


def seed_loss(logits, y, num_seeds: int):
    """Mean softmax cross-entropy over the seed rows with a label."""
    import jax
    import jax.numpy as jnp

    sl, sy = logits[:num_seeds], y[:num_seeds]
    valid = sy >= 0
    logp = jax.nn.log_softmax(sl.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, sy, 0)[:, None],
                              axis=1)[:, 0]
    return jnp.where(valid, ce, 0.0).sum() / jnp.maximum(valid.sum(), 1)
