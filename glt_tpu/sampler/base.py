"""Sampler input/output containers and the abstract sampler interface.

Rebuild of the reference's ``graphlearn_torch/python/sampler/base.py`` —
``NodeSamplerInput`` (base.py:44), ``EdgeSamplerInput`` (:149),
``NegativeSampling`` (:84-145), ``SamplerOutput`` (:207),
``HeteroSamplerOutput`` (:243), ``SamplingConfig`` (:334), ``BaseSampler``
(:348) — re-expressed as JAX pytrees with **static shapes**: every array is
padded to a trace-time-constant size with PADDING_ID sentinels, and ragged
truths (how many nodes/edges were really sampled) travel as device scalars,
never forcing a host sync.
"""
from __future__ import annotations

import dataclasses
from abc import ABC, abstractmethod
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics
from ..typing import EdgeType, NodeType


@dataclasses.dataclass
class NodeSamplerInput:
    """Seed nodes for node-based sampling (cf. sampler/base.py:44).

    ``node`` is a host numpy array of global node ids; ``input_type`` names
    the seed node type for heterogeneous graphs.
    """
    node: np.ndarray
    input_type: Optional[NodeType] = None

    def __len__(self) -> int:
        return int(self.node.shape[0])

    def __getitem__(self, index) -> "NodeSamplerInput":
        return NodeSamplerInput(self.node[index], self.input_type)

    def share_memory(self) -> "NodeSamplerInput":
        return self


class NegativeSampling:
    """Negative sampling spec (cf. sampler/base.py:84-145).

    mode 'binary': per positive edge, ``amount`` negative edges are drawn and
    labeled 0 (positives get 1).  mode 'triplet': per positive edge,
    ``amount`` negative *destination* nodes are drawn for each source.

    ``weight`` is an optional node-level vector biasing the negative node
    draws (need not sum to one; the reference's ``NegativeSampling.weight``,
    sampler/base.py:101-106).  Uniform when absent.  On hetero graphs the
    weight indexes the *destination* node type.
    """
    MODES = ("binary", "triplet")

    def __init__(self, mode: str = "binary", amount: float = 1,
                 weight=None):
        mode = mode.lower()
        if mode not in self.MODES:
            raise ValueError(f"mode must be one of {self.MODES}, got {mode!r}")
        self.mode = mode
        self.amount = amount
        self.weight = None if weight is None else np.asarray(weight,
                                                             np.float32)
        if self.weight is not None:
            if not np.isfinite(self.weight).all():
                raise ValueError("negative-sampling weight must be finite")
            if (self.weight < 0).any():
                raise ValueError("negative-sampling weight must be >= 0")
            if float(self.weight.sum()) <= 0.0:
                # An all-zero weight would make the CDF 0/0 = NaN and every
                # draw silently collapse to one node.
                raise ValueError("negative-sampling weight must have a "
                                 "positive sum")
        self._cdf = None

    def is_binary(self) -> bool:
        return self.mode == "binary"

    def is_triplet(self) -> bool:
        return self.mode == "triplet"

    def sample_count(self, num_pos: int) -> int:
        return int(round(num_pos * self.amount))

    def cdf(self):
        """Normalized cumulative weight (device array), or None."""
        if self.weight is None:
            return None
        if self._cdf is None:
            from ..ops.negative_sample import weight_to_cdf

            self._cdf = weight_to_cdf(self.weight)
        return self._cdf


@dataclasses.dataclass
class EdgeSamplerInput:
    """Seed edges for link-based sampling (cf. sampler/base.py:149)."""
    row: np.ndarray
    col: np.ndarray
    label: Optional[np.ndarray] = None
    input_type: Optional[EdgeType] = None
    neg_sampling: Optional[NegativeSampling] = None

    def __len__(self) -> int:
        return int(self.row.shape[0])

    def __getitem__(self, index) -> "EdgeSamplerInput":
        return EdgeSamplerInput(
            self.row[index],
            self.col[index],
            None if self.label is None else self.label[index],
            self.input_type,
            self.neg_sampling,
        )


class LiveCounters(NamedTuple):
    """What :func:`~glt_tpu.obs.metrics.defer` needs beside a batch's
    ``live_counts``: the counters its columns go to, and the static slot
    counts of one batch (:func:`live_counters`)."""
    counters: Tuple[Any, ...]
    per_row: Tuple[Tuple[Any, int], ...]


def live_counters(frontier_slots: Sequence[int], edge_slots: Sequence[int],
                  node_slots: int) -> LiveCounters:
    """The ``glt.sample.*`` counters of a sampler with these static sizes
    (docs/observability.md).  Live, in the order of a batch's
    ``live_counts``: ``frontier_nodes{hop=k}`` (frontier rows of hop ``k``
    that hold a node), ``read_rows{hop=k}`` (frontier rows whose random
    reads hop ``k`` issued: its live chunks' rows, the static width where
    the read is one chunk; :func:`~glt_tpu.ops.neighbor_sample.read_rows`),
    ``edges{hop=k}`` (sampled edges of hop ``k``), ``nodes`` (valid rows
    of the node buffer).  Static, once a batch:
    ``frontier_slots{hop=k}`` and ``edge_slots{hop=k}`` (the rows and the
    edge slots hop ``k``'s neighbour read PROCESSES, whoever asked for
    them; ``None`` where the caller cannot say: that counter is not
    counted), ``node_slots`` (rows of the node buffer) and ``batches``."""
    hops = [{"hop": str(k + 1)} for k in range(len(frontier_slots))]

    def per_hop(name, help):
        return [_metrics.counter("glt.sample." + name, help, h)
                for h in hops]

    live = (per_hop("frontier_nodes", "frontier rows of the hop that held "
                    "a node, over sampled batches")
            + per_hop("read_rows", "frontier rows whose neighbour reads "
                      "the hop issued, over sampled batches")
            + per_hop("edges", "edges the hop sampled, over sampled batches")
            + [_metrics.counter("glt.sample.nodes", "valid rows of the "
                                "node buffer, over sampled batches")])
    static = (list(zip(per_hop("frontier_slots", "frontier rows the hop's "
                               "neighbour read processed, live or not"),
                       frontier_slots))
              + list(zip(per_hop("edge_slots", "edge slots the hop's "
                                 "neighbour read processed, live or not"),
                         edge_slots))
              + [(_metrics.counter("glt.sample.node_slots", "rows of the "
                                   "node buffer, live or not"), node_slots),
                 (_metrics.counter("glt.sample.batches", "sampled batches "
                                   "whose counts were deferred"), 1)])
    return LiveCounters(tuple(live), tuple((c, int(n)) for c, n in static
                                           if n is not None))


def live_counts(num_sampled_nodes, num_sampled_edges,
                frontier_widths: Sequence[int], node_capacity: int,
                read_rows: Sequence[jnp.ndarray]):
    """A batch's ``live_counts`` from what a homogeneous sampler already
    counts: the frontier of hop ``k`` is the nodes first seen at hop
    ``k - 1`` as far as its static width holds them, and the node buffer
    holds every node seen as far as its capacity does.  ``read_rows`` is
    each hop's own count of the rows it read."""
    frontier = jnp.minimum(num_sampled_nodes[:-1],
                           jnp.asarray(frontier_widths, jnp.int32))
    nodes = jnp.minimum(jnp.sum(num_sampled_nodes), node_capacity)
    return jnp.concatenate([frontier, jnp.stack(read_rows),
                            num_sampled_edges,
                            nodes[None]]).astype(jnp.int32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SamplerOutput:
    """Sampled ego-subgraph in local (relabeled) COO form.

    Mirrors sampler/base.py:207, with the static-shape additions ``node_mask``
    / ``edge_mask`` / ``num_nodes`` / ``num_edges``:

    * ``node``: ``[max_nodes]`` global ids of batch-local nodes, in
      first-occurrence order (seeds first), -1 padded.
    * ``row`` / ``col``: ``[max_edges]`` local indices into ``node``; the
      edge direction is already transposed to PyG's dst<-src convention
      (row = neighbor, col = seed side), as in neighbor_sampler.py:159-165.
    * ``edge``: ``[max_edges]`` global edge ids, -1 padded.
    * ``batch``: ``[batch_size]`` the seed ids this batch was sampled for.
    * ``num_sampled_nodes`` / ``num_sampled_edges``: per-hop valid counts
      (device int32 vectors, lengths num_hops+1 / num_hops).
    * ``live_counts``: ``[3 * num_hops + 1]`` int32, the useful work of
      the batch in the order of :func:`live_counters`: frontier slots
      that held a node at each hop, frontier rows each hop read, sampled
      edges of each hop, valid rows of ``node``.
    * ``metadata``: dict of extra arrays (edge_label_index, labels, ...).

    Leaf-block layout caveat: with ``last_hop_dedup=False`` (see
    :class:`~glt_tpu.sampler.neighbor_sampler.NeighborSampler`) the
    final-hop nodes are stored in a *leaf block* at a static offset
    ``max_nodes - last_width * last_fanout``, not appended to the compact
    interior prefix.  Valid rows must then be selected with ``node_mask``
    — PyG-style ``cumsum(num_sampled_nodes)`` trimming over ``node`` would
    mis-slice.  Seed rows always stay in the compact hop-0 prefix.
    """
    node: jnp.ndarray
    row: jnp.ndarray
    col: jnp.ndarray
    edge: jnp.ndarray
    batch: Optional[jnp.ndarray] = None
    node_mask: Optional[jnp.ndarray] = None
    edge_mask: Optional[jnp.ndarray] = None
    num_sampled_nodes: Optional[jnp.ndarray] = None
    num_sampled_edges: Optional[jnp.ndarray] = None
    input_type: Optional[Any] = None
    metadata: Optional[Dict[str, Any]] = None
    live_counts: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        children = (self.node, self.row, self.col, self.edge, self.batch,
                    self.node_mask, self.edge_mask, self.num_sampled_nodes,
                    self.num_sampled_edges, self.metadata, self.live_counts)
        return children, (self.input_type,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (node, row, col, edge, batch, node_mask, edge_mask, nsn, nse,
         metadata, live) = children
        return cls(node, row, col, edge, batch, node_mask, edge_mask, nsn,
                   nse, aux[0], metadata, live)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HeteroSamplerOutput:
    """Heterogeneous sampling result (cf. sampler/base.py:243).

    Dicts keyed by node type / edge type; values have the same static-shape
    semantics as :class:`SamplerOutput`.  Edge types in ``row``/``col``/
    ``edge`` are the *reversed* types (dst<-src), as the reference emits
    (neighbor_sampler.py:236-243).
    """
    node: Dict[NodeType, jnp.ndarray]
    row: Dict[EdgeType, jnp.ndarray]
    col: Dict[EdgeType, jnp.ndarray]
    edge: Dict[EdgeType, jnp.ndarray]
    batch: Optional[Dict[NodeType, jnp.ndarray]] = None
    node_mask: Optional[Dict[NodeType, jnp.ndarray]] = None
    edge_mask: Optional[Dict[EdgeType, jnp.ndarray]] = None
    num_sampled_nodes: Optional[Dict[NodeType, jnp.ndarray]] = None
    num_sampled_edges: Optional[Dict[EdgeType, jnp.ndarray]] = None
    input_type: Optional[Any] = None
    metadata: Optional[Dict[str, Any]] = None
    # As SamplerOutput's, summed over node types and relations per hop.
    live_counts: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        children = (self.node, self.row, self.col, self.edge, self.batch,
                    self.node_mask, self.edge_mask, self.num_sampled_nodes,
                    self.num_sampled_edges, self.metadata, self.live_counts)
        return children, (self.input_type,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (node, row, col, edge, batch, node_mask, edge_mask, nsn, nse,
         metadata, live) = children
        return cls(node, row, col, edge, batch, node_mask, edge_mask, nsn,
                   nse, aux[0], metadata, live)


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Static sampling plan (cf. sampler/base.py:334 ``SamplingConfig``).

    Everything here is trace-time constant: it determines compiled shapes.
    ``max_nodes``/``max_edges`` cap the padded batch-subgraph size; ``None``
    means the exact worst-case bound batch * prod(fanouts) (mirroring
    ``_max_sampled_nodes``, neighbor_sampler.py:595-612), which is safe but
    can be lowered substantially for power-law graphs to save HBM.
    """
    num_neighbors: Any = None          # List[int] or Dict[EdgeType, List[int]]
    batch_size: int = 512
    with_edge: bool = True
    with_neg: bool = False
    with_weight: bool = False
    collect_features: bool = True
    max_nodes: Optional[int] = None
    max_edges: Optional[int] = None
    seed: int = 0


class BaseSampler(ABC):
    """Abstract sampler interface (cf. sampler/base.py:348)."""

    @abstractmethod
    def sample_from_nodes(self, inputs: NodeSamplerInput, **kwargs):
        raise NotImplementedError

    @abstractmethod
    def sample_from_edges(self, inputs: EdgeSamplerInput, **kwargs):
        raise NotImplementedError
