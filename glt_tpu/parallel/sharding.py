"""Graph + feature sharding across a device mesh.

TPU-native replacement for the reference's partitioned distributed dataset
(distributed/dist_dataset.py, dist_graph.py): there, each machine owns a
graph partition plus a dense partition book and routes per-id requests over
RPC.  Here each **mesh device** owns a contiguous node range; the "partition
book" degenerates to arithmetic (``owner = id // nodes_per_shard``), and the
padded per-shard CSR blocks are plain jax Arrays sharded over the mesh axis,
so routing happens with ``lax.all_to_all`` inside one jitted program (see
:mod:`glt_tpu.parallel.dist_sampler`).

General (non-contiguous) partitions from :mod:`glt_tpu.partition` are
supported by relabeling ids so each partition is contiguous — the partitioner
emits that relabeling; sharding here stays arithmetic.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..data.topology import CSRTopo


class ShardedGraph(NamedTuple):
    """Padded per-shard CSR blocks; leading axis = shard.

    ``indptr``: ``[S, max_nodes_per_shard + 1]`` local row pointers
    (0-based within shard); ``indices``: ``[S, max_edges_per_shard]`` global
    neighbor ids (-1 padded); ``edge_ids``: same shape, global edge ids.
    """
    indptr: jnp.ndarray
    indices: jnp.ndarray
    edge_ids: jnp.ndarray
    nodes_per_shard: int
    num_nodes: int
    num_shards: int

    def owner_of(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Partition-book lookup, arithmetic form (cf. dist_graph.py:88)."""
        return jnp.where(ids >= 0, ids // self.nodes_per_shard, -1)


class ShardedFeature(NamedTuple):
    """Per-shard feature blocks: ``[S, nodes_per_shard, d]``."""
    rows: jnp.ndarray
    nodes_per_shard: int
    num_shards: int


def shard_bounds(topo: CSRTopo, num_shards: int):
    """Per-shard node/edge ranges of the contiguous split.

    Returns ``(c, bounds, max_e)``: nodes per shard, a list of
    ``(lo, hi, e0, e1)`` per shard, and the max per-shard edge count (the
    rectangular padding width).  Cheap — touches only ``indptr``.
    """
    n = topo.num_nodes
    c = -(-n // num_shards)  # ceil
    indptr = topo.indptr
    max_e = 0
    bounds = []
    for s in range(num_shards):
        lo, hi = min(s * c, n), min((s + 1) * c, n)
        e0, e1 = int(indptr[lo]), int(indptr[hi])
        bounds.append((lo, hi, e0, e1))
        max_e = max(max_e, e1 - e0)
    return c, bounds, max_e


def shard_graph_blocks(topo: CSRTopo, num_shards: int,
                       shard_range: Optional[range] = None,
                       pad_edges: Optional[int] = None):
    """Host-side numpy CSR blocks for ``shard_range`` (default: all).

    Returns ``(ip, ix, ei, c)`` with leading axis ``len(shard_range)``.
    ``pad_edges`` overrides the edge padding width (multi-host callers pass
    the globally-agreed max so every process's blocks stack congruently).
    """
    n = topo.num_nodes
    c, bounds, max_e = shard_bounds(topo, num_shards)
    if pad_edges is not None:
        if pad_edges < max_e:
            raise ValueError(f"pad_edges {pad_edges} < local max {max_e}")
        max_e = pad_edges
    if shard_range is None:
        shard_range = range(num_shards)
    indptr = topo.indptr.astype(np.int64)
    indices = topo.indices.astype(np.int32)
    edge_ids = topo.edge_ids.astype(np.int32)

    k = len(shard_range)
    ip = np.zeros((k, c + 1), np.int32)
    ix = np.full((k, max_e), -1, np.int32)
    ei = np.full((k, max_e), -1, np.int32)
    for j, s in enumerate(shard_range):
        lo, hi, e0, e1 = bounds[s]
        local = (indptr[lo: hi + 1] - indptr[lo]).astype(np.int32)
        ip[j, : hi - lo + 1] = local
        ip[j, hi - lo + 1:] = local[-1] if local.size else 0
        ix[j, : e1 - e0] = indices[e0:e1]
        ei[j, : e1 - e0] = edge_ids[e0:e1]
    return ip, ix, ei, c


def shard_graph(topo: CSRTopo, num_shards: int) -> ShardedGraph:
    """Split a CSR topology into contiguous per-shard blocks (host-side).

    Nodes ``[s * c, (s+1) * c)`` go to shard ``s`` where
    ``c = ceil(N / num_shards)``; edge blocks are padded to the max shard
    edge count so the result stacks into rectangular arrays that
    ``jax.device_put`` can shard along axis 0.
    """
    ip, ix, ei, c = shard_graph_blocks(topo, num_shards)
    return ShardedGraph(
        indptr=jnp.asarray(ip), indices=jnp.asarray(ix),
        edge_ids=jnp.asarray(ei), nodes_per_shard=c,
        num_nodes=topo.num_nodes, num_shards=num_shards)


def shard_feature(feature: np.ndarray, num_shards: int,
                  dtype=None) -> ShardedFeature:
    """Split ``[N, d]`` features into ``[S, c, d]`` blocks (zero padded)."""
    feature = np.asarray(feature)
    n, d = feature.shape
    c = -(-n // num_shards)
    rows = np.zeros((num_shards, c, d), feature.dtype)
    for s in range(num_shards):
        lo, hi = min(s * c, n), min((s + 1) * c, n)
        rows[s, : hi - lo] = feature[lo:hi]
    arr = jnp.asarray(rows) if dtype is None else jnp.asarray(rows, dtype)
    return ShardedFeature(rows=arr, nodes_per_shard=c, num_shards=num_shards)


def put_sharded(sharded, mesh: jax.sharding.Mesh, axis: str):
    """Place the leading (shard) axis of every array field on ``axis``.

    ``sharded`` is a :class:`ShardedGraph` / :class:`ShardedFeature`, or
    one bare ``[S, ...]`` array (the label block).  :func:`shard_graph`
    and :func:`shard_feature` build their arrays on the default device;
    a step fed those re-shards the whole graph and feature table from
    that one device on every call, so place them once, here, before
    building the step.
    """
    spec = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(axis))
    if isinstance(sharded, (jnp.ndarray, np.ndarray)):
        return jax.device_put(sharded, spec)

    def place(x):
        if isinstance(x, jnp.ndarray) and x.ndim >= 1:
            return jax.device_put(x, spec)
        return x

    return type(sharded)(*[place(v) if isinstance(v, jnp.ndarray) else v
                           for v in sharded])
