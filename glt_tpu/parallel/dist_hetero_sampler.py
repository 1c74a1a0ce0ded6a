"""Distributed heterogeneous neighbor sampling over a device mesh.

Rebuild of the reference's distributed hetero path
(dist_neighbor_sampler.py:270-288: all edge-type hop tasks issued
concurrently, each routed per-partition and stitched).  Here every edge
type's CSR is sharded by its **source type's** contiguous node ranges, and
the hetero multi-hop body (:class:`HeteroNeighborSampler`) runs per shard
with the one-hop primitive swapped for the all-to-all exchange of
:func:`~glt_tpu.parallel.dist_sampler.exchange_one_hop` — per edge type,
over the same mesh axis.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..data.topology import CSRTopo
from ..ops.neighbor_sample import NeighborOutput
from ..sampler.base import HeteroSamplerOutput, NodeSamplerInput
from ..sampler.hetero_neighbor_sampler import (
    HeteroNeighborSampler,
    hetero_hop_widths,
)
from ..typing import EdgeType, NodeType, PADDING_ID
from .dist_sampler import (
    autotune_routing,
    bounded_remote_cap,
    exchange_one_hop,
    mesh_axis_sizes,
    resolve_mesh_axes,
)
from .sharding import ShardedGraph, shard_graph


def shard_hetero_graph(topos: Dict[EdgeType, CSRTopo], num_shards: int
                       ) -> Dict[EdgeType, ShardedGraph]:
    """Shard every edge type's CSR by its source type's node ranges."""
    return {et: shard_graph(t, num_shards) for et, t in topos.items()}


class DistHeteroNeighborSampler:
    """Multi-hop distributed hetero sampler.

    Args:
      sharded: dict ``EdgeType -> ShardedGraph`` (from
        :func:`shard_hetero_graph`).
      mesh / axis_name: the device mesh to sample over.
      num_neighbors / input_type / batch_size: as
        :class:`HeteroNeighborSampler`.
    """

    def __init__(self, sharded: Dict[EdgeType, ShardedGraph], mesh: Mesh,
                 num_neighbors, input_type: NodeType,
                 batch_size: int = 512, axis_name: Optional[str] = None,
                 frontier_cap: Optional[int] = None,
                 seed: int = 0,
                 last_hop_dedup: bool = True,
                 exchange_load_factor: Optional[float] = None,
                 route: str = "auto",
                 fused: Optional[bool] = None,
                 hier_load_factor: Optional[float] = None):
        self.sharded = sharded
        self.mesh = mesh
        # None resolves to the mesh's own axes (1-D name or 2-D tuple);
        # on a 2-D mesh the per-type hops ride the hierarchical
        # dedup-then-exchange topology when the route seam picks 'hier'.
        axis_name = resolve_mesh_axes(mesh, axis_name)
        self.axis_name = axis_name
        self.mesh_shape = mesh_axis_sizes(mesh, axis_name)
        self.hier_load_factor = hier_load_factor
        self.fused = fused
        # Capacity-bounded exchange, per edge type (homo parity — VERDICT
        # r4 #4; the reference's hetero engine issues worst-case per-hop
        # RPC fan-outs, dist_neighbor_sampler.py:270-288): each hop's
        # per-owner request buckets hold ceil(α * width / S) remote ids of
        # THAT edge type's frontier instead of the full width; shard-local
        # ids bypass the collective.  Per-type dropped counts surface in
        # metadata['exchange_dropped'].
        self.exchange_load_factor = exchange_load_factor
        self._trace_dropped: list = []
        # Reuse the single-device sampler's planning + multi-hop body; the
        # Graph objects aren't touched (one_hop is overridden).
        self._planner = HeteroNeighborSampler.__new__(HeteroNeighborSampler)
        p = self._planner
        p.graphs = {et: None for et in sharded}
        p.edge_types = sorted(sharded.keys())
        if isinstance(num_neighbors, dict):
            p.num_neighbors = {et: list(v) for et, v in num_neighbors.items()}
        else:
            p.num_neighbors = {et: list(num_neighbors)
                               for et in p.edge_types}
        p.num_hops = max(len(v) for v in p.num_neighbors.values())
        p.input_type = input_type
        p.batch_size = int(batch_size)
        p.last_hop_dedup = bool(last_hop_dedup)
        p.capped = False
        self.last_hop_dedup = bool(last_hop_dedup)
        # Global per-type node counts so the planner's dense inducer
        # engages (ids here are global across shards).
        p._num_nodes_by_type = {}
        for et, g in sharded.items():
            p._num_nodes_by_type.setdefault(
                et[0], g.nodes_per_shard * g.num_shards)
        self.input_type = input_type
        self.batch_size = int(batch_size)
        self._base_key = jax.random.PRNGKey(seed)
        self._call_count = 0

        self._widths, self._capacity = hetero_hop_widths(
            p.edge_types, p.num_neighbors, {input_type: self.batch_size},
            p.num_hops, frontier_cap=frontier_cap)

        # Routing A/B seam (homo parity): autotune at the widest per-type
        # frontier on TPU, heuristic elsewhere; GLT_ROUTE_FORCE still
        # wins at trace time.
        self.route = route
        if route == "auto":
            num_shards = next(iter(sharded.values())).num_shards
            widest = max(max(w.values()) for w in self._widths)
            self.route = autotune_routing(widest, num_shards,
                                          mesh_shape=self.mesh_shape)

        gspec = P(axis_name)
        arrays = {et: (g.indptr, g.indices, g.edge_ids)
                  for et, g in sharded.items()}
        specs = jax.tree.map(lambda _: gspec, arrays)
        self._shard_fn = jax.jit(jax.shard_map(
            self._local_body, mesh=mesh,
            in_specs=(specs, gspec, P()),
            out_specs=gspec,
            check_vma=False))

    def _next_key(self) -> jax.Array:
        key = jax.random.fold_in(self._base_key, self._call_count)
        self._call_count += 1
        return key

    def _one_hop(self, et, arrays, frontier, fanout, key):
        indptr, indices, edge_ids = arrays
        g = self.sharded[et]
        remote_cap = (None if self.exchange_load_factor is None
                      else bounded_remote_cap(frontier.shape[0],
                                              self.exchange_load_factor,
                                              g.num_shards))
        nbrs, eids, mask, dropped, _ = exchange_one_hop(
            frontier, indptr, indices, edge_ids, g.nodes_per_shard,
            g.num_shards, fanout, key, self.axis_name,
            remote_cap=remote_cap, route=self.route, fused=self.fused,
            mesh_shape=self.mesh_shape,
            hier_load_factor=self.hier_load_factor)
        if self.exchange_load_factor is not None:
            self._trace_dropped.append(dropped)
        return NeighborOutput(nbrs=nbrs, eids=eids, mask=mask)

    def local_sample(self, arrays, seeds, key):
        """Multi-hop hetero sample from inside an enclosing shard_map.

        Public seam for fused train steps
        (:func:`~glt_tpu.parallel.dist_train.make_hetero_dist_train_step`):
        ``arrays`` is the per-shard ``{etype: (indptr, indices, edge_ids)}``
        view, ``seeds`` the local ``[batch]`` seed ids of ``input_type``,
        ``key`` already folded with the shard's axis index.
        """
        self._trace_dropped = []
        out = self._planner._sample_impl(
            self._widths, self._capacity, arrays,
            {self.input_type: seeds}, key, one_hop=self._one_hop)
        if self._trace_dropped:
            # Summed over hops and edge types during THIS trace; rides the
            # output so callers observe bounded-exchange drops exactly as
            # in the homo path (dist_sample_multi_hop's metadata).
            total = self._trace_dropped[0]
            for d in self._trace_dropped[1:]:
                total = total + d
            out.metadata = {"exchange_dropped": total,
                            **(out.metadata or {})}
            self._trace_dropped = []
        return out

    @property
    def edge_types(self):
        return list(self._planner.edge_types)

    @property
    def num_neighbors(self):
        return {et: list(v) for et, v in self._planner.num_neighbors.items()}

    @property
    def node_capacity(self):
        """Static per-node-type unique-node capacity of one local sample."""
        return dict(self._capacity)

    @property
    def hop_widths(self):
        """Per-hop per-node-type frontier widths (static trace shapes)."""
        return [dict(w) for w in self._widths]

    def _local_body(self, arrays_blk, seeds_blk, key):
        arrays = jax.tree.map(lambda x: x[0], arrays_blk)
        seeds = seeds_blk[0]
        key = jax.random.fold_in(key, lax.axis_index(self.axis_name))
        out = self.local_sample(arrays, seeds, key)
        return jax.tree.map(lambda x: x[None], out)

    def sample_from_nodes(self, seeds_per_shard: jnp.ndarray,
                          key: Optional[jax.Array] = None
                          ) -> HeteroSamplerOutput:
        """``seeds_per_shard``: ``[S, batch_size]`` global seed ids of the
        input type, -1 padded; returns per-shard hetero outputs (leading
        axis = shard)."""
        if key is None:
            key = self._next_key()
        arrays = {et: (g.indptr, g.indices, g.edge_ids)
                  for et, g in self.sharded.items()}
        return self._shard_fn(arrays, seeds_per_shard, key)
