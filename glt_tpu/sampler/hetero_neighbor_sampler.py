"""Heterogeneous multi-hop neighbor sampling, fully jitted.

Rebuild of the reference's hetero path (neighbor_sampler.py:192-253 +
``CUDAHeteroInducer``, csrc/cuda/inducer.cu:208-345): the reference loops
``num_hops`` over edge types, sampling each type's frontier and deduping
per node type with one hash table per type.  Here the same structure is
traced into one XLA program: per-node-type cumulative unique buffers with
static per-hop widths derived from the fanout dict, per-edge-type sampling
kernels, and the same reversed-edge-type output convention
(neighbor_sampler.py:236-243).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.graph import Graph
from ..ops.negative_sample import sample_negative_edges, weighted_draw
from ..ops.neighbor_sample import read_rows, sample_neighbors
from ..ops.unique import (
    dense_map_fits,
    induce,
    induce_init,
    record_sorted_slots,
    sorted_slots,
    unique_first_occurrence,
)
from ..typing import EdgeType, NodeType, PADDING_ID, reverse_edge_type
from ..ops.unique import relabel_by_reference
from .base import (BaseSampler, HeteroSamplerOutput, NodeSamplerInput,
                   live_counters)
from .neighbor_sampler import _pad_ids


_NO_LIMIT = 1 << 62


def hetero_hop_widths(
    edge_types: Sequence[EdgeType],
    num_neighbors: Dict[EdgeType, List[int]],
    seed_widths: Dict[NodeType, int],
    num_hops: int,
    frontier_cap: Optional[int] = None,
    num_nodes: Optional[Dict[NodeType, int]] = None,
    node_capacity: Optional[Dict[NodeType, int]] = None,
    frontier_capacity: Optional[Dict[NodeType, Sequence[int]]] = None,
) -> Tuple[List[Dict[NodeType, int]], Dict[NodeType, int]]:
    """Static frontier width per (hop, node type) + total capacity per type.

    Mirrors the implicit bound of the reference's hetero loop: the hop-``i``
    frontier of type ``t`` is every node of type ``t`` first discovered at
    hop ``i-1`` across all edge types ending in ``t``.  ``seed_widths``
    gives the hop-0 frontier per type (node sampling seeds one type; link
    sampling seeds the edge's endpoint types).

    Widths multiply across edge types per hop, so three bounds keep them
    from being the product of the fanouts:

    * ``num_nodes`` (exact): a de-duplicated frontier of type ``t``, and
      the node buffer of type ``t``, never hold more than ``N_t`` nodes.
      Nothing is cut.
    * ``node_capacity`` (occupancy, see
      :func:`calibrate_hetero_node_capacity`): the buffer of type ``t``
      holds its first ``node_capacity[t]`` uniques, and no frontier of
      the type is wider than that.  A batch that discovers more is
      flagged (``metadata['overflow']``) and the edges of the excess
      nodes are masked.
    * ``frontier_capacity`` (occupancy, same calibration):
      ``frontier_capacity[t][k-1]`` is the width of type ``t``'s hop-``k``
      frontier, ``k = 1..num_hops-1``: the nodes first seen at hop ``k``
      are mostly fewer than the candidates, which are ``fanout`` a slot
      whatever the degree.  A batch with more new nodes than the width
      is flagged the same way; the nodes beyond it stay leaves.
    * ``frontier_cap`` bounds each (hop, type) frontier like the homo
      sampler's knob: newly-discovered nodes beyond the cap don't expand
      further hops (they stay in the node set).  It CUTS the sampling
      semantics, silently.
    """
    ntypes = sorted({et[0] for et in edge_types} | {et[2] for et in edge_types}
                    | set(seed_widths))
    limit = {t: min((num_nodes or {}).get(t, _NO_LIMIT),
                    (node_capacity or {}).get(t, _NO_LIMIT),
                    _NO_LIMIT if frontier_cap is None else frontier_cap)
             for t in ntypes}
    widths: List[Dict[NodeType, int]] = [
        {t: seed_widths.get(t, 0) for t in ntypes}]
    for hop in range(num_hops):
        nxt = {t: 0 for t in ntypes}
        for et in edge_types:
            fanouts = num_neighbors[et]
            if hop < len(fanouts) and fanouts[hop] > 0:
                nxt[et[2]] += widths[hop][et[0]] * fanouts[hop]
        if frontier_capacity is not None and hop + 1 < num_hops:
            nxt = {t: min(w, int(frontier_capacity[t][hop]))
                   if t in frontier_capacity else w for t, w in nxt.items()}
        widths.append({t: min(w, limit[t]) for t, w in nxt.items()})
    capacity = {t: sum(w[t] for w in widths) for t in ntypes}
    for bound in (num_nodes, node_capacity):
        for t, n in (bound or {}).items():
            if t in capacity:
                capacity[t] = min(capacity[t], max(int(n), widths[0][t]))
    return widths, capacity



class HeteroHopBounds(NamedTuple):
    """Static hop-block layout of a hetero batch, hops ``0..num_hops``
    (the typed :class:`~glt_tpu.sampler.neighbor_sampler.HopBounds`).

    ``edge_bounds[et][k]`` (``et`` the batch's reversed key) is the
    number of edge slots of hops ``1..k`` of that relation: hop blocks
    are concatenated in order.  ``node_bounds[t][k]`` bounds the rows of
    type ``t``'s node buffer that can hold a node first seen by hop
    ``k``.  Every valid edge of hop block ``k`` of ``(s, rel, d)`` has
    ``col < node_bounds[d][k-1]`` and ``row < node_bounds[s][k]``: a node
    is expanded once, at the hop after it was first seen, and what it
    reaches is appended behind everything seen before.
    """
    node_bounds: Dict[NodeType, Tuple[int, ...]]
    edge_bounds: Dict[EdgeType, Tuple[int, ...]]


def hetero_hop_bounds(edge_types, num_neighbors, widths, capacity,
                      num_nodes=None) -> HeteroHopBounds:
    """The layout of a sampler with these ``widths`` and ``capacity``.
    Node bounds count RAW candidates (a ``frontier_cap`` narrows the
    frontier, not what the inducer inserts)."""
    num_hops = len(widths) - 1
    node = {t: [min(widths[0][t], capacity[t])] for t in capacity}
    edge = {reverse_edge_type(et): [0] for et in edge_types}
    for hop in range(num_hops):
        raw = {t: 0 for t in capacity}
        for et in edge_types:
            fo = num_neighbors[et]
            slots = widths[hop][et[0]] * fo[hop] \
                if hop < len(fo) and fo[hop] > 0 else 0
            raw[et[2]] += slots
            rev = reverse_edge_type(et)
            edge[rev].append(edge[rev][-1] + slots)
        for t in capacity:
            new = min(raw[t], (num_nodes or {}).get(t, _NO_LIMIT))
            node[t].append(min(node[t][-1] + new, capacity[t]))
    return HeteroHopBounds({t: tuple(b) for t, b in node.items()},
                           {et: tuple(b) for et, b in edge.items()})


def measure_hetero_occupancy(sampler: "HeteroNeighborSampler",
                             seed_batches) -> Dict[NodeType, np.ndarray]:
    """Nodes first seen per hop, ``{type: [batches, num_hops + 1]}`` (one
    host fetch), the typed :func:`~glt_tpu.sampler.neighbor_sampler.
    measure_occupancy`; a row's sum is the batch's unique nodes of the
    type.  ``sampler`` is typically built without capacities."""
    counts = [sampler.sample_from_nodes(
        NodeSamplerInput(seeds)).num_sampled_nodes for seeds in seed_batches]
    counts = jax.device_get(counts)
    return {t: np.stack([c[t] for c in counts]) for t in counts[0]}


def calibrate_hetero_node_capacity(
        sampler: "HeteroNeighborSampler", seed_batches=None,
        pct: float = 99.0, margin: float = 1.05, multiple: int = 256,
        counts: Optional[Dict[NodeType, np.ndarray]] = None
) -> Tuple[Dict[NodeType, int], Dict[NodeType, List[int]]]:
    """Occupancy-sized ``(node_capacity, frontier_capacity)`` for a
    calibrated workload: a threshold per type (its unique nodes) and per
    (type, hop before the last) (the nodes first seen at that hop).

    A batch overflows when ANY threshold is exceeded, so ``pct`` is held
    jointly, not threshold by threshold (a dozen thresholds each at pct
    99 let up to a dozen batches in a hundred through): thresholds are
    tied as ``mean + z * std`` of their own counts, a batch's excursion
    is the largest ``z`` it needs anywhere, and ``z`` is the ``pct``
    percentile of the batches' excursions.  Times ``margin``, rounded up
    to ``multiple`` rows, never over the sampler's own (clamped) sizes.
    Feed both to :class:`HeteroNeighborSampler`."""
    if counts is None:
        counts = measure_hetero_occupancy(sampler, seed_batches)
    full, widths = sampler.node_capacity, sampler.hop_widths
    cols = {(t, 0): (c.sum(axis=1), full[t]) for t, c in counts.items()}
    for t, c in counts.items():
        for k in range(1, sampler.num_hops):
            cols[t, k] = (c[:, k], widths[k][t])
    spread = {key: (float(c.mean()), float(c.std()))
              for key, (c, _) in cols.items()}
    excursion = np.max([(c - spread[key][0]) / spread[key][1]
                        for key, (c, _) in cols.items()
                        if spread[key][1] > 0], axis=0)
    z = float(np.percentile(excursion, pct))

    def size(key):
        mean, std = spread[key]
        want = (mean + z * std) * margin
        return min(int(np.ceil(want / multiple) * multiple), cols[key][1])

    nodes = {t: size((t, 0)) for t in counts}
    frontiers = {t: [size((t, k)) for k in range(1, sampler.num_hops)]
                 for t in counts}
    return nodes, frontiers


def _node_mask(buf: jnp.ndarray, count: jnp.ndarray, fast) -> jnp.ndarray:
    """Validity mask for a per-type node buffer: compact prefix, or
    (interior prefix | leaf-region mask) when the final hop used the
    no-dedup leaf block."""
    idx = jnp.arange(buf.shape[0], dtype=jnp.int32)
    if fast is None:
        return idx < count
    leaf_off, leaf_region, interior = fast
    return (idx < jnp.minimum(interior, leaf_off)) | leaf_region


class HeteroNeighborSampler(BaseSampler):
    """Fixed-fanout hetero sampler over per-edge-type :class:`Graph` s.

    Args:
      graphs: dict ``EdgeType -> Graph`` (out-edge CSR per type).
      num_neighbors: per-hop fanouts — a list (applied to every edge type)
        or a dict keyed by edge type.
      input_type: node type of the seeds.
      batch_size: static seed width.
      frontier_cap / node_capacity / frontier_capacity: see
        :func:`hetero_hop_widths`.
    """

    def __init__(
        self,
        graphs: Dict[EdgeType, Graph],
        num_neighbors,
        input_type: NodeType,
        batch_size: int = 512,
        frontier_cap: Optional[int] = None,
        seed: int = 0,
        last_hop_dedup: bool = True,
        node_capacity: Optional[Dict[NodeType, int]] = None,
        frontier_capacity: Optional[Dict[NodeType, Sequence[int]]] = None,
    ):
        self.graphs = graphs
        self.edge_types = sorted(graphs.keys())
        if isinstance(num_neighbors, dict):
            self.num_neighbors = {et: list(v)
                                  for et, v in num_neighbors.items()}
        else:
            self.num_neighbors = {et: list(num_neighbors)
                                  for et in self.edge_types}
        self.num_hops = max(len(v) for v in self.num_neighbors.values())
        self.input_type = input_type
        self.batch_size = int(batch_size)
        self.last_hop_dedup = bool(last_hop_dedup)
        self._base_key = jax.random.PRNGKey(seed)
        self._call_count = 0

        # Per-type node counts: the exact clamp of every frontier and
        # node buffer, and the size of the dense inducer's id map.  A
        # type's id space must cover BOTH roles: its CSR row count where
        # it is a source AND the max destination id arriving from other
        # edge types (CSRTopo derives num_nodes from one edge type's own
        # ids, so a source-only bound can undercount and silently drop
        # neighbors).  Types with no evidence fall back to the sort-based
        # inducer and go unclamped.
        self._num_nodes_by_type = {}
        for et, g in graphs.items():
            if g is None:
                continue
            src_t, _, dst_t = et
            self._num_nodes_by_type[src_t] = max(
                self._num_nodes_by_type.get(src_t, 0), g.num_nodes)
            idx = np.asarray(g.topo.indices)
            if idx.size:
                self._num_nodes_by_type[dst_t] = max(
                    self._num_nodes_by_type.get(dst_t, 0),
                    int(idx.max()) + 1)
        self.frontier_cap = frontier_cap
        # Occupancy-sized per-type capacities (see
        # calibrate_hetero_node_capacity): a batch that discovers more
        # uniques of a type than its buffer holds, or more new nodes at a
        # hop than the next frontier holds, is flagged through
        # metadata['overflow']; the excess nodes' edges are masked, the
        # nodes past a frontier stay leaves.
        self.capped = (node_capacity is not None
                       or frontier_capacity is not None)
        self._widths, self._capacity = hetero_hop_widths(
            self.edge_types, self.num_neighbors,
            {input_type: self.batch_size}, self.num_hops,
            frontier_cap=frontier_cap, num_nodes=self._num_nodes_by_type,
            node_capacity=node_capacity,
            frontier_capacity=frontier_capacity)
        self.node_types = sorted(self._capacity.keys())
        self.hop_bounds = hetero_hop_bounds(
            self.edge_types, self.num_neighbors, self._widths,
            self._capacity, self._num_nodes_by_type)
        self.live = self.live_counters(self._widths, self._capacity)
        self._sample_jit = jax.jit(
            partial(self._sample_impl, self._widths, self._capacity))
        self._edges_jit = {}

    @property
    def node_capacity(self) -> Dict[NodeType, int]:
        """Static per-node-type unique-node capacity (mirrors the
        distributed sampler's property — shared by state initializers)."""
        return dict(self._capacity)

    @property
    def hop_widths(self) -> List[Dict[NodeType, int]]:
        """Per-hop per-node-type frontier widths (static trace shapes)."""
        return [dict(w) for w in self._widths]

    def live_counters(self, widths, cap):
        """Where a batch's ``live_counts`` are counted
        (:func:`~glt_tpu.sampler.base.live_counters`), summed over the
        hop's neighbour reads, one a relation: ``frontier_slots{hop}``
        counts a source type's frontier once for every relation read
        from it, as ``frontier_nodes{hop}`` counts its live rows and
        ``read_rows{hop}`` the rows each of those reads issued."""
        reads = [[(widths[hop][et[0]], self.num_neighbors[et][hop])
                  for et in self.edge_types
                  if hop < len(self.num_neighbors[et])
                  and self.num_neighbors[et][hop] > 0
                  and widths[hop][et[0]] > 0]
                 for hop in range(self.num_hops)]
        return live_counters([sum(w for w, _ in r) for r in reads],
                             [sum(w * f for w, f in r) for r in reads],
                             sum(max(n, 1) for n in cap.values()))

    def _next_key(self) -> jax.Array:
        key = jax.random.fold_in(self._base_key, self._call_count)
        self._call_count += 1
        return key

    def _sample_impl(self, widths, cap, graph_arrays, seeds_dict, key,
                     one_hop=None):
        """graph_arrays: dict et -> (indptr, indices, edge_ids);
        seeds_dict: dict ntype -> padded seed ids (hop-0 frontiers);
        one_hop: optional override ``(et, arrays, frontier, fanout, key) ->
        NeighborOutput`` — the distributed sampler plugs its all-to-all
        exchange here, keeping this multi-hop body single-source."""
        node_types = sorted(cap.keys())

        # Worst-case uniques per type before each hop (and, last, before
        # the last hop: the interior): seeds + every RAW candidate of the
        # hops before it, and no more than the type has.  With
        # frontier_cap the capacity budgets *capped* widths while the
        # inducer inserts raw candidates, so the interior can outgrow the
        # leaf block — the fast path must stay off for such types (exact
        # mode masks overflow into the buffer tail instead).
        raw_known = {t: [widths[0].get(t, 0)] for t in node_types}
        for h in range(self.num_hops - 1):
            for t in node_types:
                raw_known[t].append(raw_known[t][-1])
            for et in self.edge_types:
                fo = self.num_neighbors[et]
                f = fo[h] if h < len(fo) else 0
                if f > 0:
                    raw_known[et[2]][-1] += widths[h][et[0]] * f
        raw_interior = {t: raw_known[t][-1] for t in node_types}

        # Per-type inducer choice: the dense chain when the type's node
        # count is known and an id map of it is small enough (mirrors
        # NeighborSampler's dedup='auto'); sort otherwise.  A dense chain
        # holds the map only where the type's capacity lies under its
        # bound on known nodes (ops/unique.py::chain_is_sorted).
        dense_state, knowns = {}, {}
        for t in node_types:
            n_t = self._num_nodes_by_type.get(t)
            if n_t is not None and dense_map_fits(n_t):
                knowns[t] = [min(k, n_t) for k in raw_known[t]]
                dense_state[t] = induce_init(n_t, max(cap[t], 1),
                                             knowns[t][-1])
        # Keys of the sorted inducers, hop by hop (0: the seeds), over
        # the types.
        sorted_keys = [0] * (self.num_hops + 1)

        node_buf = {
            t: (dense_state[t].node_buf[: max(cap[t], 1)]
                if t in dense_state
                else jnp.full((max(cap[t], 1),), PADDING_ID, jnp.int32))
            for t in node_types}
        count = {t: jnp.zeros((), jnp.int32) for t in node_types}
        frontier = {t: None for t in node_types}
        frontier_start = {t: jnp.zeros((), jnp.int32)
                          for t in node_types}
        # Live rows of each type's current frontier, and the hop's sums
        # over its neighbour reads (``live_counters``).
        frontier_live = dict(frontier_start)
        frontier_nodes, rows_read, hop_edges = [], [], []

        for t0, seeds in seeds_dict.items():
            if t0 in dense_state:
                sorted_keys[0] += sorted_slots(dense_state[t0], 0,
                                               seeds.shape[0])
                dense_state[t0], _ = induce(dense_state[t0], seeds, 0,
                                            False)
                buflen0 = node_buf[t0].shape[0]
                node_buf[t0] = dense_state[t0].node_buf[:buflen0]
                count[t0] = jnp.minimum(dense_state[t0].count, buflen0)
                frontier[t0] = node_buf[t0][: seeds.shape[0]]
            else:
                u0 = unique_first_occurrence(seeds)
                node_buf[t0] = (node_buf[t0].at[: seeds.shape[0]]
                                .set(u0.uniques))
                count[t0] = u0.count
                frontier[t0] = u0.uniques
            frontier_live[t0] = count[t0]

        rows = {et: [] for et in self.edge_types}
        cols = {et: [] for et in self.edge_types}
        eids = {et: [] for et in self.edge_types}
        emasks = {et: [] for et in self.edge_types}
        edge_counts = {et: [] for et in self.edge_types}
        counts_hist = {t: [count[t]] for t in node_types}
        # t -> (leaf_off, full-leaf-region validity mask, interior count)
        # for types whose final hop used the no-dedup leaf block.
        fast_leaf = {}
        keys = jax.random.split(key, self.num_hops * len(self.edge_types))
        overflow = jnp.zeros((), bool)

        for hop in range(self.num_hops):
            # 1) sample every active edge type from its src frontier
            hop_out = {}   # et -> (nbrs, eids, mask, src_local)
            for ei_idx, et in enumerate(self.edge_types):
                fanouts = self.num_neighbors[et]
                f = fanouts[hop] if hop < len(fanouts) else 0
                w = widths[hop][et[0]]
                if f <= 0 or w <= 0 or frontier[et[0]] is None:
                    continue
                hop_key = keys[hop * len(self.edge_types) + ei_idx]
                with jax.named_scope(f"glt.sample.hop{hop + 1}"):
                    if one_hop is not None:
                        out = one_hop(et, graph_arrays[et],
                                      frontier[et[0]], f, hop_key)
                    else:
                        indptr, indices, edge_ids = graph_arrays[et]
                        out = sample_neighbors(
                            indptr, indices, frontier[et[0]], f, hop_key,
                            edge_ids=edge_ids)
                src_local = (frontier_start[et[0]]
                             + jnp.arange(w, dtype=jnp.int32))
                src_local = jnp.where(frontier[et[0]] >= 0, src_local,
                                      PADDING_ID)
                hop_out[et] = (out, src_local, w, f)
            frontier_nodes.append(sum(
                (frontier_live[et[0]] for et in hop_out),
                jnp.zeros((), jnp.int32)))
            rows_read.append(sum(
                (read_rows(frontier[et[0]]) for et in hop_out),
                jnp.zeros((), jnp.int32)))

            # 2) per dst type: merge all candidates into the unique buffer
            new_frontier = {}
            for t in node_types:
                ets = [et for et in hop_out if et[2] == t]
                if not ets:
                    continue
                cands = jnp.concatenate(
                    [hop_out[et][0].nbrs.ravel() for et in ets])
                buflen = node_buf[t].shape[0]
                total_wf = sum(hop_out[et][2] * hop_out[et][3] for et in ets)
                # Leaf-block fast path (see NeighborSampler.last_hop_dedup):
                # only when the final-hop width wasn't frontier_cap-capped
                # below the raw candidate count (a capped width can't hold
                # every candidate at a static offset) AND the worst-case
                # interior fits below the leaf block (it always does when
                # frontier_cap is None).
                if (hop + 1 == self.num_hops and not self.last_hop_dedup
                        and widths[hop + 1][t] >= total_wf
                        and raw_interior[t] <= buflen - widths[hop + 1][t]):
                    leaf_off = buflen - widths[hop + 1][t]
                    cmask = jnp.concatenate(
                        [hop_out[et][0].mask.ravel() for et in ets])
                    leaf_ids = jnp.where(cmask, cands, PADDING_ID)
                    uniques_src = jax.lax.dynamic_update_slice(
                        node_buf[t], leaf_ids, (leaf_off,))
                    merged_count = count[t] + jnp.sum(cmask.astype(jnp.int32))
                    inverse_tail = jnp.where(
                        cmask,
                        leaf_off + jnp.arange(total_wf, dtype=jnp.int32),
                        PADDING_ID)
                    off = 0
                    leaf_region = jnp.concatenate([
                        jnp.zeros((leaf_off,), bool), cmask,
                        jnp.zeros((buflen - leaf_off - total_wf,), bool)])
                    fast_leaf[t] = (leaf_off, leaf_region, count[t])
                elif t in dense_state:
                    known = knowns[t][hop]
                    sorted_keys[hop + 1] += sorted_slots(
                        dense_state[t], known, total_wf)
                    dense_state[t], locs = induce(
                        dense_state[t], cands, known,
                        hop + 1 == self.num_hops)
                    uniques_src = dense_state[t].node_buf
                    merged_count = dense_state[t].count
                    inverse_tail = locs
                    off = 0
                else:
                    merged = unique_first_occurrence(
                        jnp.concatenate([node_buf[t], cands]))
                    uniques_src = merged.uniques
                    merged_count = merged.count
                    inverse_tail = merged.inverse
                    off = buflen
                # per-etype segments of the candidates' local ids
                for et in ets:
                    out, src_local, w, f = hop_out[et]
                    nbr_local = inverse_tail[off: off + w * f].reshape(w, f)
                    off += w * f
                    # With a frontier_cap the unique buffer can fill before
                    # every candidate lands; edges to dropped nodes must be
                    # masked, or nbr_local would index past the buffer.
                    ok = out.mask & (nbr_local >= 0) & (nbr_local < buflen)
                    nbr_local = jnp.where(ok, nbr_local, PADDING_ID)
                    # reversed edge type, transposed direction
                    rows[et].append(nbr_local.ravel())
                    cols[et].append(
                        jnp.broadcast_to(src_local[:, None], (w, f)).ravel())
                    eids[et].append(out.eids.ravel())
                    emasks[et].append(ok.ravel())
                    edge_counts[et].append(jnp.sum(ok, dtype=jnp.int32))

                old_count = count[t]
                nw = widths[hop + 1][t]
                if nw > 0 and hop + 1 < self.num_hops:
                    # Slice strictly within the buffer: overflowed nodes
                    # (and the dense dump slot) never become frontier.
                    new_frontier[t] = jax.lax.dynamic_slice(
                        jnp.concatenate(
                            [uniques_src[:buflen],
                             jnp.full((nw,), PADDING_ID, jnp.int32)]),
                        (jnp.clip(old_count, 0, buflen),),
                        (nw,))
                node_buf[t] = uniques_src[:buflen]
                # merged_count keeps counting uniques past the buffer
                # (the dense inducer's dump slot absorbs their writes).
                overflow = overflow | (merged_count > buflen)
                if hop + 1 < self.num_hops:
                    overflow = overflow | (
                        merged_count - old_count > widths[hop + 1][t])
                count[t] = jnp.minimum(merged_count, buflen)
                frontier_start[t] = old_count
                frontier_live[t] = jnp.minimum(count[t] - old_count, nw)

            zero = jnp.zeros((), jnp.int32)
            for et in self.edge_types:
                if len(edge_counts[et]) <= hop:     # no read of it this hop
                    edge_counts[et].append(zero)
            hop_edges.append(sum((edge_counts[et][hop]
                                  for et in self.edge_types), zero))
            for t in node_types:
                counts_hist[t].append(count[t])
                # the hop frontier is consumed; only newly discovered
                # nodes expand next hop
                frontier[t] = new_frontier.get(t)
        for hop, slots in enumerate(sorted_keys):
            record_sorted_slots(hop, slots)

        def cat_or_empty(lst, width_hint=1):
            if lst:
                return jnp.concatenate(lst)
            return jnp.full((0,), PADDING_ID, jnp.int32)

        rev = {et: reverse_edge_type(et) for et in self.edge_types}
        out = HeteroSamplerOutput(
            node={t: node_buf[t] for t in node_types},
            row={rev[et]: cat_or_empty(rows[et]) for et in self.edge_types},
            col={rev[et]: cat_or_empty(cols[et]) for et in self.edge_types},
            edge={rev[et]: cat_or_empty(eids[et]) for et in self.edge_types},
            batch=dict(seeds_dict),
            node_mask={t: _node_mask(node_buf[t], count[t],
                                     fast_leaf.get(t)) for t in node_types},
            edge_mask={rev[et]: (cat_or_empty(emasks[et]).astype(bool)
                                 if emasks[et] else
                                 jnp.zeros((0,), bool))
                       for et in self.edge_types},
            num_sampled_nodes={
                t: jnp.stack(
                    [counts_hist[t][0]]
                    + [counts_hist[t][i + 1] - counts_hist[t][i]
                       for i in range(len(counts_hist[t]) - 1)])
                for t in node_types},
            num_sampled_edges={rev[et]: jnp.stack(edge_counts[et])
                               for et in self.edge_types},
            input_type=self.input_type,
            metadata={"overflow": overflow} if self.capped else None,
            live_counts=jnp.stack(
                frontier_nodes + rows_read + hop_edges
                + [sum(count.values(), jnp.zeros((), jnp.int32))]),
        )
        return out

    def sample_from_nodes(self, inputs: NodeSamplerInput,
                          key: Optional[jax.Array] = None
                          ) -> HeteroSamplerOutput:
        seeds = _pad_ids(np.asarray(inputs.node), self.batch_size)
        if key is None:
            key = self._next_key()
        graph_arrays = {
            et: (g.indptr, g.indices, g.edge_ids)
            for et, g in self.graphs.items()}
        return self._sample_jit(graph_arrays,
                                {self.input_type: jnp.asarray(seeds)}, key)

    # -- hetero link path (cf. neighbor_sampler.py:255-381 hetero branch) --
    def sample_from_edges(self, inputs, key: Optional[jax.Array] = None
                          ) -> HeteroSamplerOutput:
        """Seed-edge sampling with optional binary/triplet negatives.

        Binary negatives are drawn **strict** — rejection-tested against
        the seed edge type's CSR via its sorted-column view, the hetero
        analog of the CUDA strict mode (random_negative_sampler.cu:37-54)
        — with the reference's non-strict padding fallback.  An optional
        ``NegativeSampling.weight`` biases negative draws over the
        destination node type.
        """
        et = inputs.input_type
        if et is None:
            raise ValueError("hetero EdgeSamplerInput needs input_type")
        src_t, _, dst_t = et
        neg = inputs.neg_sampling
        q = self.batch_size
        src = _pad_ids(np.asarray(inputs.row), q)
        dst = _pad_ids(np.asarray(inputs.col), q)
        if key is None:
            key = self._next_key()

        mode = None if neg is None else neg.mode
        amount = 0 if neg is None else int(round(neg.amount))
        cdf = None if neg is None else neg.cdf()
        fn = self._get_edges_jit(et, mode, amount, cdf is not None)
        graph_arrays = {
            e: (g.indptr, g.indices, g.edge_ids)
            for e, g in self.graphs.items()}
        seed_g = self.graphs[et]
        sorted_idx = (seed_g.sorted_indices if mode == "binary"
                      else seed_g.indices)
        out = fn(graph_arrays, sorted_idx, jnp.asarray(src),
                 jnp.asarray(dst),
                 jnp.zeros((1,), jnp.float32) if cdf is None else cdf, key)

        if mode == "binary":
            label = inputs.label
            pos_label = (jnp.ones((q,), jnp.int32) if label is None
                         else jnp.asarray(_pad_ids(label, q)) + 1)
            pos_label = jnp.where(jnp.asarray(src) >= 0, pos_label,
                                  PADDING_ID)
            out.metadata["edge_label"] = jnp.concatenate(
                [pos_label, jnp.zeros((q * amount,), jnp.int32)])
        elif mode is None and inputs.label is not None:
            label = jnp.asarray(_pad_ids(inputs.label, q))
            out.metadata["edge_label"] = jnp.where(
                jnp.asarray(src) >= 0, label, PADDING_ID)
        return out

    def _get_edges_jit(self, et, mode, amount, weighted: bool = False):
        k = (et, mode, amount, weighted)
        if k not in self._edges_jit:
            self._edges_jit[k] = jax.jit(
                self.edges_program(et, mode, amount, weighted)[0])
        return self._edges_jit[k]

    def edges_program(self, et, mode, amount: int, weighted: bool = False):
        """``(impl, widths, capacity)`` of the seed-edge path of relation
        ``et``: ``impl(graph_arrays, sorted_idx, src, dst, cdf, key) ->
        HeteroSamplerOutput``, unjitted, so that a traced step can call
        it (``make_scanned_hetero_link_train_step``), and the static
        sizes it samples at.  ``graph_arrays`` is ``et -> (indptr,
        indices, edge_ids)``; ``sorted_idx`` the seed relation's
        column-sorted view (binary) or anything (otherwise).

        The sample runs from the seed union (binary: ``[src, neg_src]``
        of the source type and ``[dst, neg_dst]`` of the destination
        type, ``q (1 + amount)`` slots each) at its own widths, under the
        exact clamp: an occupancy capacity of the node path does not
        transfer (another seed width, another occupancy).  Binary
        negatives are drawn strict under ``glt.sample.negative`` and
        ``metadata['neg_strict']`` says which slots passed a trial; the
        pair index is found under ``glt.sample.relabel``."""
        src_t, _, dst_t = et
        q = self.batch_size
        if mode == "binary":
            sw, dw = q * (1 + amount), q * (1 + amount)
        elif mode == "triplet":
            sw, dw = q, q * (1 + amount)
        else:
            sw, dw = q, q
        seed_widths = ({src_t: sw + dw} if src_t == dst_t
                       else {src_t: sw, dst_t: dw})
        widths, cap = hetero_hop_widths(
            self.edge_types, self.num_neighbors, seed_widths,
            self.num_hops, frontier_cap=self.frontier_cap,
            num_nodes=self._num_nodes_by_type)

        # Node counts are static: an edge type's CSR rows are its
        # source type's nodes.
        n_src = self.graphs[et].num_nodes
        dst_rows = [e for e in self.edge_types if e[0] == dst_t]
        if not dst_rows:
            raise ValueError(
                f"cannot size negatives: no edge type has source type "
                f"{dst_t!r} (needed for its node count)")
        n_dst = self.graphs[dst_rows[0]].num_nodes

        def impl(graph_arrays, sorted_idx, src, dst, cdf, key):
            kneg, ksample = jax.random.split(key)
            dst_cdf = cdf if weighted else None
            if mode == "binary":
                # Strict rejection against the seed edge type's CSR
                # (sorted-column binary search), weighted dst draws
                # when NegativeSampling.weight is set.
                with jax.named_scope("glt.sample.negative"):
                    negs = sample_negative_edges(
                        graph_arrays[et][0], sorted_idx, q * amount, kneg,
                        n_src, num_dst_nodes=n_dst, dst_cdf=dst_cdf)
                srcs = jnp.concatenate([src, negs.src])
                dsts = jnp.concatenate([dst, negs.dst])
            elif mode == "triplet":
                with jax.named_scope("glt.sample.negative"):
                    if weighted:
                        neg_dst = weighted_draw(kneg, cdf, (q * amount,))
                    else:
                        neg_dst = jax.random.randint(
                            kneg, (q * amount,), 0, n_dst, dtype=jnp.int32)
                    neg_dst = jnp.where(jnp.repeat(src >= 0, amount),
                                        neg_dst, PADDING_ID)
                srcs, dsts = src, jnp.concatenate([dst, neg_dst])
            else:
                srcs, dsts = src, dst

            if src_t == dst_t:
                seeds_dict = {src_t: jnp.concatenate([srcs, dsts])}
            else:
                seeds_dict = {src_t: srcs, dst_t: dsts}
            out = self._sample_impl(widths, cap, graph_arrays, seeds_dict,
                                    ksample)
            # Seed ids first-occur within the hop-0 prefix of their
            # type's node list; relabel against that slice only (the
            # no-dedup leaf block may hold duplicate seed copies).
            if src_t == dst_t:
                src_ref = dst_ref = out.node[src_t][: sw + dw]
            else:
                src_ref = out.node[src_t][:sw]
                dst_ref = out.node[dst_t][:dw]
            meta = {}
            with jax.named_scope("glt.sample.relabel"):
                if mode == "binary":
                    meta["edge_label_index"] = jnp.stack([
                        relabel_by_reference(src_ref, srcs),
                        relabel_by_reference(dst_ref, dsts)])
                    meta["neg_strict"] = negs.strict
                elif mode == "triplet":
                    meta["src_index"] = relabel_by_reference(src_ref, src)
                    meta["dst_pos_index"] = relabel_by_reference(
                        dst_ref, dst)
                    meta["dst_neg_index"] = relabel_by_reference(
                        dst_ref, neg_dst).reshape(q, amount)
                else:
                    meta["edge_label_index"] = jnp.stack([
                        relabel_by_reference(src_ref, src),
                        relabel_by_reference(dst_ref, dst)])
            out.metadata = meta
            return out

        return impl, widths, cap
