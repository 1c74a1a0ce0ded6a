"""Hotness reordering for the tiered feature store.

Rebuild of the reference's ``sort_by_in_degree`` (python/data/reorder.py:18-40):
feature rows are reordered hottest-first (hotness = in-degree, i.e. how often
a node appears as a sampled neighbor) so that a ``split_ratio`` prefix is the
hot cache.  Returns the ``id2index`` indirection that the feature store
applies on every lookup.

:func:`in_degree_order` is the order alone, for a table whose rows are
made or loaded tier by tier and never lie in one host array
(:meth:`~glt_tpu.data.feature.Feature.from_tiers`): it counts and sorts
where the neighbour ids live, on the host for a numpy array and on the
device for a ``jax.Array``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .topology import CSRTopo


def in_degree_order(indices, num_nodes: int):
    """``(order, id2index)`` of ``num_nodes`` nodes by descending
    in-degree, ties by ascending node id (what a stable sort gives).

    ``indices`` are the stored neighbour ids of the out-edge CSR (ids at
    or past ``num_nodes`` are not counted).  ``order[r]`` is the node
    whose row has rank ``r`` and ``id2index[v]`` the rank of node ``v``,
    both int32; a device array in gives device arrays out, sorted there.
    """
    n = int(num_nodes)
    if isinstance(indices, jax.Array):
        return _device_in_degree_order(indices, n)
    deg = np.bincount(np.asarray(indices), minlength=n)[:n]
    order = np.argsort(-deg, kind="stable").astype(np.int32)
    id2index = np.empty(n, np.int32)
    id2index[order] = np.arange(n, dtype=np.int32)
    return order, id2index


@partial(jax.jit, static_argnums=1)
def _device_in_degree_order(indices, n: int):
    deg = jnp.zeros((n,), jnp.int32).at[indices].add(1, mode="drop")
    node = jnp.arange(n, dtype=jnp.int32)
    order = jax.lax.sort((-deg, node), num_keys=2)[1]
    id2index = jax.lax.sort((order, node), num_keys=1)[1]
    return order, id2index


def sort_by_in_degree(
    feature: np.ndarray,
    split_ratio: float,
    topo: CSRTopo,
    shuffle_ratio: float = 0.0,
    rng: Optional[np.random.Generator] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Reorder ``feature`` rows by descending in-degree.

    Args:
      feature: ``[N, d]`` row-per-node features.
      split_ratio: fraction of rows that will live in the device (hot) tier —
        only used to scope the optional shuffle.
      topo: topology whose in-degrees define hotness.
      shuffle_ratio: optionally shuffle this fraction of the hot prefix to
        de-bias benchmarks, as the reference supports.

    Returns:
      ``(reordered_feature, id2index)`` where ``id2index[global_id]`` is the
      row of that node in the reordered matrix.
    """
    n = feature.shape[0]
    order, id2index = in_degree_order(topo.indices, n)  # hottest first
    if shuffle_ratio > 0:
        rng = rng or np.random.default_rng(0)
        limit = int(n * min(split_ratio + shuffle_ratio, 1.0))
        head = order[:limit].copy()
        rng.shuffle(head)
        order = np.concatenate([head, order[limit:]])
        id2index[order] = np.arange(n, dtype=np.int32)
    return np.ascontiguousarray(feature[order]), id2index
