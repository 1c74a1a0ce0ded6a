"""Multi-hop neighbor sampling engine, fully jitted, static shapes.

Rebuild of the reference's single-machine sampling engine
(``graphlearn_torch/python/sampler/neighbor_sampler.py``).  The reference
loops hops on the host, calling a CUDA kernel + a hash-table inducer per hop
with a forced device sync per hop to size ragged outputs
(random_sampler.cu:288-300).  Here the **entire multi-hop pipeline is one
XLA program**: per-hop frontiers, cumulative first-occurrence dedup, and
relabeled COO edges all have trace-time-constant shapes, so sampling runs
back-to-back with the train step with no host round-trips.

Key design points:

* The cumulative unique node list (the reference's persistent hash-table
  inducer, csrc/cuda/inducer.cu:75-95) is a -1-padded buffer rebuilt per hop
  by :func:`unique_first_occurrence` over ``concat(old_buffer, new_nbrs)``;
  old uniques provably keep their positions (they occur first).
* The hop-``i+1`` frontier — only the *globally new* nodes discovered at hop
  ``i`` — is ``lax.dynamic_slice(buffer, [old_count], [hop_i_width])``:
  a traced start with a static width.  This replaces the inducer's
  "return newly inserted keys" contract exactly.
* Edge direction is transposed on output to PyG's dst<-src convention
  (out-edges sampled, then row=neighbor, col=seed), mirroring
  neighbor_sampler.py:159-165.
* ``frontier_cap`` bounds per-hop frontier width (nodes past the cap stay
  leaves), the static-shape analog of the reference's implicit bound
  ``_max_sampled_nodes`` (neighbor_sampler.py:595-612).
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.graph import Graph
from ..ops.neighbor_sample import read_rows, sample_neighbors
from ..ops.negative_sample import sample_negative_edges, weighted_draw
from ..ops.subgraph import node_subgraph
from ..ops.unique import (
    dense_map_fits,
    induce,
    induce_init,
    record_sorted_slots,
    relabel_by_reference,
    sorted_slots,
    unique_first_occurrence,
)
from ..typing import PADDING_ID
from .base import (
    BaseSampler,
    EdgeSamplerInput,
    NegativeSampling,
    NodeSamplerInput,
    SamplerOutput,
    live_counters,
    live_counts,
)


def _pad_ids(ids: np.ndarray, size: int) -> np.ndarray:
    """Right-pad a host id array with PADDING_ID to a static length."""
    ids = np.asarray(ids).astype(np.int32).ravel()
    if ids.shape[0] > size:
        raise ValueError(f"batch of {ids.shape[0]} exceeds static size {size}")
    out = np.full((size,), PADDING_ID, np.int32)
    out[: ids.shape[0]] = ids
    return out


def hop_widths(batch_size: int, fanouts: Sequence[int],
               frontier_cap: Optional[int] = None) -> List[int]:
    """Static frontier width per hop: B, B*f0, B*f0*f1, ... (capped)."""
    widths = [batch_size]
    for f in fanouts[:-1]:
        w = widths[-1] * f
        if frontier_cap is not None:
            w = min(w, frontier_cap)
        widths.append(w)
    return widths


def max_sampled_nodes(batch_size: int, fanouts: Sequence[int],
                      frontier_cap: Optional[int] = None) -> int:
    """Padded node capacity (cf. ``_max_sampled_nodes``, neighbor_sampler.py:595)."""
    widths = hop_widths(batch_size, fanouts, frontier_cap)
    return widths[0] + sum(w * f for w, f in zip(widths, fanouts))


class HopBounds(NamedTuple):
    """Static hop-block layout of a sampled batch (see :func:`hop_bounds`)."""
    node_bounds: Tuple[int, ...]
    edge_bounds: Tuple[int, ...]
    # Static (frontier width, fanout) of hop blocks 1..len(fanouts): under
    # a ``frontier_cap`` the width is not ``edge_bounds``' to tell.
    blocks: Tuple[Tuple[int, int], ...]


def hop_bounds(batch_size: int, fanouts: Sequence[int],
               frontier_cap: Optional[int] = None,
               node_capacity: Optional[int] = None) -> HopBounds:
    """Cumulative static bounds of the hop blocks, hops ``0..len(fanouts)``.

    ``edge_bounds[k]`` is the number of edge slots of hops ``1..k`` (hop
    blocks are concatenated in order, so hops ``1..k`` are the prefix
    ``[:edge_bounds[k]]``); ``node_bounds[k]`` bounds the rows of the node
    buffer that can hold a node first seen by hop ``k`` (first-occurrence
    order, seeds first), clamped to ``node_capacity``.  Every valid edge
    of hop block ``k`` has ``col < node_bounds[k-1]`` and
    ``row < node_bounds[k]``: a node is expanded once, at the hop after
    it was first seen, and its neighbours are appended behind everything
    seen before.  Holds for both ``dedup`` strategies, the leaf block of
    ``last_hop_dedup=False`` (it ends at the capacity), a ``frontier_cap``
    (an unexpanded node has no in-edges) and an occupancy capacity
    (overflow edges are masked).

    **Static destinations.**  Hop block ``k`` is ``blocks[k-1] = (w, f)``:
    ``w`` frontier slots times ``f`` edge slots each, frontier-major, and
    the frontier is a contiguous run of the node buffer.  So every
    unmasked edge at slot ``s`` of the block has
    ``col == col[first slot of the block] + s // f``.  The first slot
    holds that start whether it is masked or not, or ``-1`` where the
    block has no frontier (every slot of it is then masked); any other
    masked slot may hold anything.  A destination row belongs to one
    block only (a node is expanded once) and the starts ascend: a block
    starts at or behind the last live row of the blocks before it.

    tests/test_neighbor_sampler.py, tests/test_link_path.py and
    tests/test_dist_train.py hold the samplers to both rules, because
    :class:`~glt_tpu.models.sage.GraphSAGE` trims its layers by the first
    and aggregates without a scatter by the second
    (:func:`~glt_tpu.models.conv.block_mean`).
    """
    widths = hop_widths(batch_size, fanouts, frontier_cap)
    edges = [0]
    for w, f in zip(widths, fanouts):
        edges.append(edges[-1] + w * f)
    cap = batch_size + edges[-1]
    if node_capacity is not None:
        cap = min(cap, int(node_capacity))
    return HopBounds(tuple(min(batch_size + e, cap) for e in edges),
                     tuple(edges), tuple(zip(widths, fanouts)))


class SampleSizes(NamedTuple):
    """Static sizes of a sampler at one seed width (the node path's
    ``batch_size``, or the link path's seed union):
    :meth:`NeighborSampler.sizes_at`."""
    batch_size: int
    widths: Tuple[int, ...]
    node_capacity: int
    full_node_capacity: int
    capped: bool
    edge_capacity: int
    hop_bounds: HopBounds


def measure_occupancy(sampler: "NeighborSampler", seed_batches,
                      neg_sampling: Optional[NegativeSampling] = None
                      ) -> np.ndarray:
    """Unique-node counts per seed batch (ONE host fetch for all batches).

    The sampler's padded node buffer is sized to the zero-dedup worst case
    (the reference's ``_max_sampled_nodes``, neighbor_sampler.py:595-612);
    on real graphs per-batch occupancy is far lower.  This measures the
    actual interior-unique count per batch so callers can size the static
    capacity to a percentile instead of the worst case — feature-gather
    cost, the train step's segment ops, and HBM footprint all scale with
    the padded width.

    In leaf-block mode (``last_hop_dedup=False``) the final hop's width is
    static, so only interior hops are counted.

    A ``[2, q]`` batch is a batch of seed edges: it goes through
    ``sample_from_edges`` with ``neg_sampling``, and the count is the
    seed union's (sample it with a sampler that holds no capacity, or
    the counts stop at the capacity).
    """
    import jax as _jax

    counts = []
    for seeds in seed_batches:
        if np.ndim(seeds) == 2:
            out = sampler.sample_from_edges(EdgeSamplerInput(
                row=seeds[0], col=seeds[1], neg_sampling=neg_sampling))
        else:
            out = sampler.sample_from_nodes(NodeSamplerInput(seeds))
        n = out.num_sampled_nodes
        if not sampler.last_hop_dedup:
            n = n[:-1]
        counts.append(jnp.sum(n))
    return np.asarray(_jax.device_get(jnp.stack(counts)))


def calibrate_node_capacity(sampler: "NeighborSampler", seed_batches=None,
                            pct: float = 99.0, margin: float = 1.05,
                            multiple: int = 256,
                            counts: Optional[np.ndarray] = None,
                            neg_sampling: Optional[NegativeSampling] = None
                            ) -> int:
    """Occupancy-sized static node capacity for a calibrated workload.

    Samples ``seed_batches`` through ``sampler`` (typically uncapped),
    takes the ``pct`` percentile of interior-unique counts, applies a
    safety ``margin``, rounds up to ``multiple`` rows (sublane/lane tile
    alignment), and re-adds the static leaf-block width in leaf mode.
    Feed the result to ``NeighborSampler(node_capacity=...)``; batches
    that exceed it are flagged via ``metadata['overflow']`` and their
    excess-node edges are masked (or exactly re-sampled by the loaders'
    full-capacity fallback).

    ``[2, q]`` seed batches, or a ``neg_sampling``, calibrate the link
    path: the counts are of the seed union under ``neg_sampling``, and
    the result is bounded by the union's sizes, not the node path's.
    """
    edges = neg_sampling is not None or (
        seed_batches is not None and np.ndim(seed_batches[0]) == 2)
    if counts is None:
        counts = measure_occupancy(sampler, seed_batches, neg_sampling)
    sizes = (sampler.seed_union(neg_sampling) if edges
             else sampler.sizes_at(sampler.batch_size))
    interior = float(np.percentile(counts, pct)) * margin
    leaf_w = (0 if sampler.last_hop_dedup
              else sizes.widths[-1] * sampler.num_neighbors[-1])
    cap = int(np.ceil(interior / multiple) * multiple) + leaf_w
    cap = max(cap, sum(sizes.widths) + leaf_w)
    return min(cap, sizes.full_node_capacity)


class NeighborSampler(BaseSampler):
    """Fixed-fanout multi-hop sampler over a :class:`~glt_tpu.data.graph.Graph`.

    Args:
      graph: device-resident CSR graph.
      num_neighbors: per-hop fanouts, e.g. ``[15, 10, 5]``.
      batch_size: static seed-batch width (callers pad the last batch).
      frontier_cap: optional cap on per-hop frontier width (memory knob).
      with_edge: emit global edge ids.
      seed: base PRNG seed; each ``sample_from_nodes`` call advances a
        counter so batches are independent yet reproducible (the analog of
        the curand Philox stream setup, random_sampler.cu:71-73).
      dedup: the inducer of every hop, the seeds' own dedup included:
        'dense' (``ops/unique.py::induce``: four sorts, a fill and one
        store a hop and no O(N) state wherever the node buffer covers
        the static bound on nodes known before the last hop; under an
        occupancy capacity below that bound an O(N) id map at every hop,
        four random passes a candidate), 'sort' (argsort-based, no O(N)
        state, two argsorts and seven random passes), or 'auto' (dense
        unless the id map would exceed ~1GB).
      last_hop_dedup: when False, final-hop neighbors skip the inducer
        entirely and land in a contiguous leaf block of the node list
        (duplicates allowed).  The sampled edge multiset, every edge's
        endpoint features, and all shapes are identical (static
        capacities already assume zero dedup); the one semantic change
        is that a final-hop duplicate of an *interior* node becomes a
        fresh leaf — it aggregates from raw features instead of reusing
        the interior node's sampled out-edges (the tree-unrolled
        semantics of the original GraphSAGE algorithm).  The node list
        may repeat leaf ids, so ``num_sampled_nodes[-1]`` counts sampled
        (not unique) leaves.  Leaves the widest frontier no inducer at
        all (four sorts and a fill in exact mode); its effect on the
        chip is not measured.  Default True = exact reference
        semantics (unique node list, csrc/cuda/inducer.cu:95).
    """

    def __init__(
        self,
        graph: Graph,
        num_neighbors: Sequence[int],
        batch_size: int = 512,
        frontier_cap: Optional[int] = None,
        with_edge: bool = True,
        seed: int = 0,
        dedup: str = "auto",
        last_hop_dedup: bool = True,
        node_capacity: Optional[int] = None,
        sample_force: str = "auto",
    ):
        self.graph = graph
        self.num_neighbors = list(num_neighbors)
        self.batch_size = int(batch_size)
        self.frontier_cap = frontier_cap
        self.with_edge = with_edge
        self.last_hop_dedup = bool(last_hop_dedup)
        # Neighbor-read kernel seam, passed through to every
        # sample_neighbors call ('auto'|'pallas'|'xla'|'interpret'; see
        # ops/sample_pallas.py).  'auto' serves whatever autotune_sample
        # memoized for each hop's exact (width, fanout) shape.
        self.sample_force = sample_force
        self._base_key = jax.random.PRNGKey(seed)
        self._call_count = 0

        if dedup not in ("auto", "dense", "sort"):
            raise ValueError(f"dedup must be auto|dense|sort, got {dedup!r}")
        if dedup == "auto":
            dedup = "dense" if dense_map_fits(graph.num_nodes) else "sort"
        self.dedup = dedup

        # The calibrated capacity as given: the node path holds it to the
        # sizes of ``batch_size`` seeds, the link path to the sizes of its
        # seed union (:meth:`seed_union`).
        self._given_capacity = node_capacity
        self._sizes = {}
        mine = self.sizes_at(self.batch_size)
        self._widths = list(mine.widths)
        self.full_node_capacity = mine.full_node_capacity
        self.node_capacity = mine.node_capacity
        self.capped = mine.capped
        self.edge_capacity = mine.edge_capacity
        self.hop_bounds = mine.hop_bounds
        # Where a node batch's ``live_counts`` are counted (a link batch's:
        # ``live_counters(self.seed_union(neg_sampling))``).
        self.live = self.live_counters(mine)

        self._sample_jit = jax.jit(self._sample_impl)
        self._sample_many_jit = {}
        self._sample_edges_jit = {}
        self._subgraph_jit = {}
        self._full_sibling: Optional["NeighborSampler"] = None

    def sizes_at(self, width: int, unique_bound: bool = False
                 ) -> SampleSizes:
        """Static sizes of this sampler run from ``width`` seed slots.

        Without a capacity the node list is sized to the zero-dedup worst
        case — the reference's sizing (``_max_sampled_nodes``,
        neighbor_sampler.py:595-612).  With one
        (:func:`calibrate_node_capacity`, for the width it is used at)
        the buffer holds only the first ``node_capacity`` uniques; later
        discoveries overflow — their edges are masked and the batch is
        flagged via ``metadata['overflow']``.  ``unique_bound`` adds what
        no calibration is needed for: a de-duplicated node list holds at
        most ``num_nodes`` ids, so the capacity and every hop bound stop
        there, and a batch at that bound cannot overflow.
        """
        key = (int(width), bool(unique_bound))
        if key in self._sizes:
            return self._sizes[key]
        fanouts, fcap = self.num_neighbors, self.frontier_cap
        widths = hop_widths(width, fanouts, fcap)
        full = max_sampled_nodes(width, fanouts, fcap)
        leaf_w = 0 if self.last_hop_dedup else widths[-1] * fanouts[-1]
        cap = full
        if self._given_capacity is not None:
            nc = int(self._given_capacity)
            floor_cap = sum(widths) + leaf_w
            if nc < floor_cap:
                raise ValueError(
                    f"node_capacity {nc} below the frontier floor "
                    f"{floor_cap} (sum of hop widths + leaf block) of "
                    f"{width} seed slots")
            cap = min(nc, full)
        limit = full
        if unique_bound and self.last_hop_dedup:
            limit = min(full, max(self.graph.num_nodes, width))
            cap = min(cap, limit)
        sizes = SampleSizes(
            batch_size=int(width), widths=tuple(widths), node_capacity=cap,
            full_node_capacity=limit, capped=cap < limit,
            edge_capacity=sum(w * f for w, f in zip(widths, fanouts)),
            hop_bounds=hop_bounds(width, fanouts, fcap, cap))
        self._sizes[key] = sizes
        return sizes

    def live_counters(self, sizes: SampleSizes):
        """The ``glt.sample.*`` counters of a batch sampled at ``sizes``
        (:func:`~glt_tpu.sampler.base.live_counters`)."""
        return live_counters(
            sizes.widths,
            [w * f for w, f in zip(sizes.widths, self.num_neighbors)],
            sizes.node_capacity)

    def seed_union(self, neg_sampling: Optional[NegativeSampling] = None
                   ) -> SampleSizes:
        """Sizes of the link path's node sample: it runs from the seed
        union ``[src, dst, neg_src, neg_dst]`` (binary, ``2q(1 + amount)``
        slots; triplet ``q(2 + amount)``; none ``2q``), de-duplicated, so
        its ``hop_bounds`` is the layout a model trims by and its
        ``node_capacity`` the rows a link batch has."""
        q = self.batch_size
        mode = None if neg_sampling is None else neg_sampling.mode
        amount = 0 if mode is None else int(round(neg_sampling.amount))
        per_pos = {None: 2, "binary": 2 + 2 * amount,
                   "triplet": 2 + amount}[mode]
        return self.sizes_at(q * per_pos, unique_bound=True)

    def full_capacity_sibling(self) -> "NeighborSampler":
        """Uncapped twin (same graph/fanouts) for exact re-sampling of
        overflow-flagged batches (its program compiles lazily on the
        first overflow; shapes differ, so consumers see a second
        compiled bucket)."""
        if not self.capped:
            return self
        if self._full_sibling is None:
            self._full_sibling = NeighborSampler(
                self.graph, self.num_neighbors, self.batch_size,
                frontier_cap=self.frontier_cap, with_edge=self.with_edge,
                dedup=self.dedup, last_hop_dedup=self.last_hop_dedup,
                sample_force=self.sample_force)
        return self._full_sibling

    # -- key management ----------------------------------------------------
    def _next_key(self) -> jax.Array:
        key = jax.random.fold_in(self._base_key, self._call_count)
        self._call_count += 1
        return key

    # -- core jitted multi-hop program ------------------------------------
    def _sample_impl(self, indptr, indices, edge_ids, seeds, key,
                     sizes: Optional[SampleSizes] = None):
        """One fused multi-hop sample. seeds: [batch_size], -1 padded
        (``sizes``: another seed width's, the link path's seed union).

        Dedup strategy ('dense' default): one chain of
        :func:`~glt_tpu.ops.unique.induce` calls, the seeds first.  Its
        form is a fact of the static shapes: sorts and scans at every
        hop, and no id map in the program, where the node buffer covers
        the bound on nodes known before the last hop; the O(N)
        scatter-map inducer at every hop where an occupancy capacity
        lies under that bound (``ops/unique.py`` has the chip's
        numbers).  'sort' keeps the growing-buffer argsort path.
        """
        fanouts = self.num_neighbors
        if sizes is None:
            sizes = self.sizes_at(self.batch_size)
        widths = list(sizes.widths)
        cap = sizes.node_capacity
        dense = self.dedup == "dense"

        if dense:
            # Static bounds on the nodes known before each hop: seeds plus
            # every candidate of the earlier hops.
            knowns = [min(b, sizes.full_node_capacity) for b in hop_bounds(
                widths[0], fanouts, self.frontier_cap).node_bounds]
            state = induce_init(self.graph.num_nodes, cap, knowns[-2])
            record_sorted_slots(0, sorted_slots(state, 0, widths[0]))
            state, _ = induce(state, seeds, 0, False)
            node_buf = state.node_buf
            count = state.count
            frontier = node_buf[: widths[0]]
        else:
            u0 = unique_first_occurrence(seeds)
            # The unique buffer GROWS hop by hop (static per-hop sizes):
            # hop i sorts only O(nodes discoverable by hop i) keys.
            node_buf = u0.uniques            # [widths[0]], -1 padded
            count = u0.count                 # valid uniques so far
            frontier = u0.uniques            # [widths[0]]
        frontier_start = jnp.zeros((), jnp.int32)

        rows, cols, eids, emasks = [], [], [], []
        counts_per_hop = [count]
        edges_per_hop, rows_read = [], []
        keys = jax.random.split(key, len(fanouts))
        # Static interior capacity: where the no-dedup leaf block starts.
        leaf_off = cap - widths[-1] * fanouts[-1]
        leaf_mask = None
        capped = sizes.capped
        # Largest valid interior local index + 1: under an occupancy-sized
        # cap, nodes assigned locals past this are overflow — their edges
        # are masked and the batch flagged (the uncapped program compiles
        # byte-identically: every `capped` branch below is trace-time
        # static and off).
        interior_cap = cap if self.last_hop_dedup else leaf_off

        for i, f in enumerate(fanouts):
            w = widths[i]
            last = i + 1 == len(fanouts)
            with jax.named_scope(f"glt.sample.hop{i + 1}"):
                out = sample_neighbors(indptr, indices, frontier, f,
                                       keys[i], edge_ids=edge_ids,
                                       with_edge=self.with_edge,
                                       force=self.sample_force)
            rows_read.append(read_rows(frontier))
            # Seed-side local indices (position of frontier nodes in node_buf).
            src_local = frontier_start + jnp.arange(w, dtype=jnp.int32)
            src_local = jnp.where(frontier >= 0, src_local, PADDING_ID)
            emask = out.mask
            if capped:
                # Frontier slots past the cap hold garbage on overflow
                # batches; mask every edge they source.
                src_local = jnp.where(src_local < interior_cap, src_local,
                                      PADDING_ID)
                emask = emask & (src_local >= 0)[:, None]

            # Insert this hop's neighbors into the cumulative unique list;
            # old uniques keep their positions.
            cand = out.nbrs.ravel()                        # [w*f]
            if last and not self.last_hop_dedup:
                # Leaf block: no inducer at the widest frontier.  Local
                # ids are static offsets; the only memory traffic is one
                # CONTIGUOUS store of the candidates themselves.
                leaf_mask = emask.ravel()
                leaf_ids = jnp.where(leaf_mask, cand, PADDING_ID)
                nbr_local = (leaf_off
                             + jnp.arange(w * f, dtype=jnp.int32)
                             ).reshape(w, f)
                if dense:
                    node_buf = jax.lax.dynamic_update_slice(
                        node_buf, leaf_ids, (leaf_off,))
                elif capped:
                    # The growing sort-path buffer has full-width interior
                    # length L >= leaf_off; truncate to leaf_off so the
                    # leaf block lands exactly where nbr_local points
                    # (interior locals >= leaf_off are already masked).
                    node_buf = jnp.concatenate([node_buf[:leaf_off],
                                                leaf_ids])
                else:
                    node_buf = jnp.concatenate([node_buf, leaf_ids])
                new_count = count + jnp.sum(leaf_mask.astype(jnp.int32))
            elif dense:
                record_sorted_slots(
                    i + 1, sorted_slots(state, knowns[i], w * f))
                state, nbr_local = induce(state, cand, knowns[i], last)
                node_buf = state.node_buf
                new_count = state.count
                nbr_local = nbr_local.reshape(w, f)
            else:
                buflen = node_buf.shape[0]
                merged = unique_first_occurrence(
                    jnp.concatenate([node_buf, cand]))
                node_buf = merged.uniques              # [buflen + w*f]
                new_count = merged.count
                nbr_local = merged.inverse[buflen:].reshape(w, f)
            nbr_local = jnp.where(emask, nbr_local, PADDING_ID)
            if capped and not (last and not self.last_hop_dedup):
                # Induced locals past the cap point at dropped nodes
                # (dense_induce dump-slot clamp / sort-path truncation):
                # mask those edges out.
                lim = cap if self.last_hop_dedup else interior_cap
                nbr_local = jnp.where(nbr_local < lim, nbr_local,
                                      PADDING_ID)
                emask = emask & (nbr_local >= 0)

            rows.append(nbr_local.ravel())
            cols.append(jnp.broadcast_to(src_local[:, None], (w, f)).ravel())
            if self.with_edge:
                eids.append(out.eids.ravel())
            emasks.append(emask.ravel())
            edges_per_hop.append(jnp.sum(emask.astype(jnp.int32)))

            if not last:
                nw = widths[i + 1]
                frontier = jax.lax.dynamic_slice(
                    jnp.concatenate(
                        [node_buf,
                         jnp.full((nw,), PADDING_ID, jnp.int32)]),
                    (jnp.clip(count, 0, node_buf.shape[0]),), (nw,))
                frontier_start = count
            count = new_count
            counts_per_hop.append(count)

        # Pad/trim the final buffer to the static capacity.
        if node_buf.shape[0] < cap:
            node_buf = jnp.concatenate(
                [node_buf,
                 jnp.full((cap - node_buf.shape[0],), PADDING_ID,
                          jnp.int32)])
        node_buf = node_buf[:cap]
        count = jnp.minimum(count, cap)
        if leaf_mask is None:
            node_mask = jnp.arange(cap, dtype=jnp.int32) < count
        else:
            # Interior prefix is compact; the leaf block keeps its own
            # validity mask (holes between interior count and leaf_off).
            interior = jnp.minimum(count - edges_per_hop[-1], leaf_off)
            node_mask = (jnp.arange(cap, dtype=jnp.int32) < interior) | (
                jnp.concatenate([jnp.zeros((leaf_off,), bool), leaf_mask]))

        num_sampled_nodes = jnp.stack(
            [counts_per_hop[0]]
            + [counts_per_hop[i + 1] - counts_per_hop[i]
               for i in range(len(fanouts))])
        num_sampled_edges = jnp.stack(edges_per_hop)
        metadata = None
        if capped:
            # `count` keeps counting uniques past the cap (dense_induce's
            # dump slot absorbs their writes), so overflow is exactly
            # "more uniques discovered than the buffer holds".  Loaders
            # check this flag to fall back to the exact full-capacity
            # program; the flagged batch itself is still safe to train on
            # (overflow-node edges are masked above).
            # counts_per_hop holds the UNCLAMPED totals (`count` itself is
            # min'd to cap just above for the node_mask).
            if self.last_hop_dedup:
                overflow = counts_per_hop[-1] > cap
            else:
                overflow = counts_per_hop[len(fanouts) - 1] > leaf_off
            metadata = {"overflow": overflow}
        return SamplerOutput(
            node=node_buf,
            # Direction transpose: row = neighbor side, col = seed side
            # (neighbor_sampler.py:159-165).
            row=jnp.concatenate(rows),
            col=jnp.concatenate(cols),
            edge=jnp.concatenate(eids) if self.with_edge else None,
            batch=seeds,
            node_mask=node_mask,
            edge_mask=jnp.concatenate(emasks),
            num_sampled_nodes=num_sampled_nodes,
            num_sampled_edges=num_sampled_edges,
            metadata=metadata,
            live_counts=live_counts(num_sampled_nodes, num_sampled_edges,
                                    widths, cap, rows_read),
        )

    # -- public API (cf. sampler/neighbor_sampler.py:138) ------------------
    def sample_from_nodes(self, inputs: NodeSamplerInput,
                          key: Optional[jax.Array] = None) -> SamplerOutput:
        ids = inputs.node
        if (isinstance(ids, jax.Array)
                and ids.shape == (self.batch_size,)):
            # Pre-staged device seeds (already padded): skip the host
            # round-trip — prefetching loaders ship seed batches to HBM
            # ahead of time (the reference's pin_memory + .to(device)).
            seeds = ids.astype(jnp.int32)
        else:
            seeds = jnp.asarray(_pad_ids(np.asarray(ids), self.batch_size))
        if key is None:
            key = self._next_key()
        g = self.graph
        return self._sample_jit(g.indptr, g.indices, g.gather_edge_ids,
                                seeds, key)

    def sample_from_nodes_batched(self, seeds: jnp.ndarray,
                                  key: Optional[jax.Array] = None
                                  ) -> SamplerOutput:
        """Sample ``G`` seed batches in ONE device program.

        ``seeds``: ``[G, batch_size]`` (-1 padded) device or host array.
        Returns a stacked :class:`SamplerOutput` pytree (leading axis G).

        This is the TPU analog of the reference's per-worker in-flight
        concurrency (``worker_concurrency`` <= 32 async batches,
        dist_options.py / event_loop.py): a ``lax.scan`` chains G
        independent batches inside one XLA program, amortising host
        dispatch (one call instead of G).  Measured device time per batch
        is ~parity with the single-batch path at batch 1024 (device work
        dominates); the win appears when dispatch is the constraint —
        many small batches, or busy host threads.  The scan keeps
        scatters unbatched: a vmap formulation batches the dense-inducer
        scatters and is ~60x slower.
        """
        seeds = jnp.asarray(seeds, jnp.int32)
        if seeds.ndim != 2 or seeds.shape[1] != self.batch_size:
            raise ValueError(
                f"expected [G, {self.batch_size}] seeds, got {seeds.shape}")
        g = int(seeds.shape[0])
        if key is None:
            key = self._next_key()
        if g not in self._sample_many_jit:
            def many(indptr, indices, edge_ids, seeds_g, key):
                keys = jax.random.split(key, g)

                def body(carry, inp):
                    sd, k = inp
                    return carry, self._sample_impl(indptr, indices,
                                                    edge_ids, sd, k)

                _, outs = jax.lax.scan(body, jnp.zeros((), jnp.int32),
                                       (seeds_g, keys))
                return outs

            # One program per group count, cached in _sample_many_jit —
            # the closure over `g` is the compile-cache key, not a leak.
            self._sample_many_jit[g] = jax.jit(many)  # gltlint: disable=recompile-hazard
        gr = self.graph
        return self._sample_many_jit[g](gr.indptr, gr.indices,
                                        gr.gather_edge_ids, seeds, key)

    def sample_one_hop(self, srcs: jnp.ndarray, fanout: int,
                       key: Optional[jax.Array] = None):
        """Single-hop primitive, used by the distributed sampler
        (cf. neighbor_sampler.py:118 ``sample_one_hop``)."""
        if key is None:
            key = self._next_key()
        g = self.graph
        return sample_neighbors(g.indptr, g.indices, srcs, fanout, key,
                                edge_ids=g.gather_edge_ids,
                                with_edge=self.with_edge,
                                force=self.sample_force)

    # -- link path (cf. neighbor_sampler.py:255 sample_from_edges) ---------
    def sample_from_edges(self, inputs: EdgeSamplerInput,
                          key: Optional[jax.Array] = None) -> SamplerOutput:
        neg = inputs.neg_sampling
        q = self.batch_size  # static positive-edge width
        src = _pad_ids(inputs.row, q)
        dst = _pad_ids(inputs.col, q)
        num_pos = int(len(inputs))
        if key is None:
            key = self._next_key()

        mode = None if neg is None else neg.mode
        amount = 0 if neg is None else int(round(neg.amount))
        cdf = None if neg is None else neg.cdf()
        fn = self._get_edges_jit(mode, amount, cdf is not None)
        g = self.graph
        label = (None if inputs.label is None
                 else jnp.asarray(_pad_ids(inputs.label, q)))
        sorted_indices = (g.sorted_indices if mode is not None else g.indices)
        out = fn(g.indptr, g.indices, g.gather_edge_ids, sorted_indices,
                 jnp.asarray(src), jnp.asarray(dst),
                 jnp.zeros((1,), jnp.float32) if cdf is None else cdf, key)
        # Labels are host-side metadata; attach eagerly.
        if mode == "binary":
            meta = out.metadata or {}
            pos_label = (jnp.ones((q,), jnp.int32) if label is None
                         else label + 1)
            pos_label = jnp.where(jnp.asarray(src) >= 0, pos_label, PADDING_ID)
            neg_label = jnp.zeros((q * amount,), jnp.int32)
            meta["edge_label"] = jnp.concatenate([pos_label, neg_label])
            out.metadata = meta
        elif mode is None and label is not None:
            # Pass the caller's labels through unchanged (reference homo
            # None branch: edge_label untouched, no +1 increment).
            meta = out.metadata or {}
            meta["edge_label"] = jnp.where(jnp.asarray(src) >= 0, label,
                                           PADDING_ID)
            out.metadata = meta
        out.metadata = out.metadata or {}
        out.metadata["num_pos"] = jnp.asarray(num_pos, jnp.int32)
        return out

    def _get_edges_jit(self, mode: Optional[str], amount: int,
                       weighted: bool = False):
        k = (mode, amount, weighted)
        if k not in self._sample_edges_jit:
            self._sample_edges_jit[k] = jax.jit(
                partial(self._sample_edges_impl, mode, amount, weighted))
        return self._sample_edges_jit[k]

    def _sample_edges_impl(self, mode, amount, weighted, indptr, indices,
                           edge_ids, sorted_indices, src, dst, cdf, key):
        q = self.batch_size
        kneg, ksample = jax.random.split(key)
        num_nodes = self.graph.num_nodes
        node_cdf = cdf if weighted else None

        if mode == "binary":
            # Strict rejection (trials + non-strict padding); weighted
            # draws bias both endpoints through NegativeSampling.weight.
            with jax.named_scope("glt.sample.negative"):
                negs = sample_negative_edges(
                    indptr, sorted_indices, q * amount, kneg, num_nodes,
                    src_cdf=node_cdf, dst_cdf=node_cdf)
            seed_ids = jnp.concatenate([src, dst, negs.src, negs.dst])
        elif mode == "triplet":
            # amount negative destinations per positive source
            # (cf. neighbor_sampler.py:332-381 triplet reconstruction).
            with jax.named_scope("glt.sample.negative"):
                if weighted:
                    neg_dst = weighted_draw(kneg, cdf, (q * amount,))
                else:
                    neg_dst = jax.random.randint(kneg, (q * amount,), 0,
                                                 num_nodes, dtype=jnp.int32)
                neg_dst = jnp.where(jnp.repeat(src >= 0, amount), neg_dst,
                                    PADDING_ID)
            seed_ids = jnp.concatenate([src, dst, neg_dst])
        else:
            seed_ids = jnp.concatenate([src, dst])

        # Dedup seeds, then run the node path with the union as the batch,
        # at the union's own sizes: a capacity calibrated for seed edges,
        # and never more rows than the graph has nodes.
        seed_width = seed_ids.shape[0]
        out = self._sample_impl(
            indptr, indices, edge_ids, seed_ids, ksample,
            self.sizes_at(seed_width, unique_bound=True))

        meta = dict(out.metadata or {})
        # Seed ids all first-occur within the hop-0 prefix of the node
        # list, so relabel against that slice only — with
        # last_hop_dedup=False the tail leaf block may hold duplicate
        # copies of a seed, and a leaf copy has no deep embedding.
        ref = out.node[:seed_width]
        with jax.named_scope("glt.sample.relabel"):
            if mode == "binary":
                all_src = jnp.concatenate([src, negs.src])
                all_dst = jnp.concatenate([dst, negs.dst])
                meta["edge_label_index"] = jnp.stack([
                    relabel_by_reference(ref, all_src),
                    relabel_by_reference(ref, all_dst),
                ])
                # Which negative slots passed a strict trial (the rest are
                # the non-strict padding pass and may be edges).
                meta["neg_strict"] = negs.strict
            elif mode == "triplet":
                meta["src_index"] = relabel_by_reference(ref, src)
                meta["dst_pos_index"] = relabel_by_reference(ref, dst)
                meta["dst_neg_index"] = relabel_by_reference(
                    ref, neg_dst).reshape(q, amount)
            else:
                # No negative sampling still emits edge_label_index so the
                # LinkLoader can locate seed edges in the batch
                # (neighbor_sampler.py:366-372, the None-or-binary branch).
                meta["edge_label_index"] = jnp.stack([
                    relabel_by_reference(ref, src),
                    relabel_by_reference(ref, dst),
                ])
        out.metadata = meta
        return out

    # -- hotness estimation (cf. neighbor_sampler.py:435-562 sample_prob,
    #    CalNbrProb kernel random_sampler.cu:168-209) ----------------------
    def sample_prob(self, seed_ids: np.ndarray, node_count: int) -> jnp.ndarray:
        """Per-node probability of being touched by sampling from ``seeds``.

        One full-graph sparse propagation per hop: an edge ``u -> v``
        contributes ``p_u * min(fanout / deg_u, 1)`` to ``p_v`` (exactly the
        per-edge weight the CUDA ``CalNbrProb`` kernel applies); hop results
        are union-bounded into a cumulative visit probability.  Used by the
        frequency partitioner's hotness scores.
        """
        g = self.graph
        indptr, indices = g.indptr, g.indices
        num_nodes = int(indptr.shape[0]) - 1
        edge_src = jnp.searchsorted(
            indptr, jnp.arange(indices.shape[0], dtype=indptr.dtype),
            side="right").astype(jnp.int32) - 1
        deg = (indptr[1:] - indptr[:-1]).astype(jnp.float32)

        prob = jnp.zeros((num_nodes,), jnp.float32)
        prob = prob.at[jnp.asarray(seed_ids, jnp.int32)].set(1.0)
        total = prob
        for f in self.num_neighbors:
            w = jnp.minimum(f / jnp.maximum(deg, 1.0), 1.0)
            contrib = prob[edge_src] * w[edge_src]
            nxt = jax.ops.segment_sum(contrib, indices,
                                      num_segments=num_nodes)
            prob = jnp.minimum(nxt, 1.0)
            total = jnp.minimum(total + prob, 1.0)
        if node_count > num_nodes:
            total = jnp.concatenate(
                [total, jnp.zeros((node_count - num_nodes,), jnp.float32)])
        return total

    # -- induced subgraph (cf. neighbor_sampler.py:409-433) ---------------
    def subgraph(self, inputs: NodeSamplerInput, max_degree: int = 64,
                 key: Optional[jax.Array] = None) -> SamplerOutput:
        """Hop expansion + induced-subgraph extraction (SubGraphOp path).

        Unlike ``sample_from_nodes`` (whose ``row`` is the transposed
        message-source side), the induced subgraph keeps **graph-direction
        COO**: ``row`` = CSR source, ``col`` = destination, matching the
        reference SubGraph op (csrc/cuda/subgraph_op.cu) and PyG's
        ``subgraph()``. Subgraph models (SEAL/DGCNN) treat the extract as
        a standalone graph, so the raw direction is preserved.
        """
        if not self.last_hop_dedup:
            raise ValueError(
                "subgraph() requires last_hop_dedup=True: the induced "
                "extract relabels against a unique node set")
        ids = inputs.node
        if isinstance(ids, jax.Array) and ids.shape == (self.batch_size,):
            seeds = ids.astype(jnp.int32)
        else:
            seeds = jnp.asarray(_pad_ids(np.asarray(ids), self.batch_size))
        if key is None:
            key = self._next_key()
        # ONE program: hop expansion + induced extraction.  The eager
        # composition (sample jit, then op-by-op node_subgraph) paid ~20
        # per-op dispatches per batch — pure host overhead.
        k = int(max_degree)
        if k not in self._subgraph_jit:
            def fused(indptr, indices, hop_eids, sub_eids, seeds, key,
                      _k=k):
                base = self._sample_impl(indptr, indices, hop_eids, seeds,
                                         key)
                sub = node_subgraph(indptr, indices, base.node, _k,
                                    edge_ids=sub_eids)
                return base, sub

            # One program per max_degree, cached in _subgraph_jit — the
            # baked `_k=k` default is the compile-cache key, not a leak.
            self._subgraph_jit[k] = jax.jit(fused)  # gltlint: disable=recompile-hazard
        g = self.graph
        # gather_edge_ids for the hop loop (None when ids are positional
        # — skips identity gathers); real edge ids for the extract.
        base, sub = self._subgraph_jit[k](g.indptr, g.indices,
                                          g.gather_edge_ids, g.edge_ids,
                                          seeds, key)
        return SamplerOutput(
            node=base.node,
            row=sub.rows,
            col=sub.cols,
            edge=sub.eids,
            batch=base.batch,
            node_mask=base.node_mask,
            edge_mask=sub.mask,
            num_sampled_nodes=base.num_sampled_nodes,
            metadata={"mapping": jnp.arange(self.batch_size, dtype=jnp.int32),
                      **(base.metadata or {})},
        )
