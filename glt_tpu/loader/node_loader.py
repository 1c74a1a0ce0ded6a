"""NodeLoader / NeighborLoader — seed iteration + batch assembly.

Rebuild of ``loader/node_loader.py`` + ``loader/neighbor_loader.py``: the
reference wraps a torch ``DataLoader`` over seed ids and joins features +
labels in ``_collate_fn`` (node_loader.py:54-113).  Here the host loop is a
plain numpy batcher; sampling is one fused XLA program per batch and feature
gather is either in-graph (HBM-resident features) or a host stage (tiered).

Pipelining replaces the reference's producer processes: jax dispatch is
async, so the loader dispatches batch ``i+1``'s sampling before the caller
has consumed batch ``i`` (``prefetch`` depth), hiding sample latency behind
train-step compute the way GLT's shm-channel producers did.
"""
from __future__ import annotations

from collections import deque
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..data.dataset import Dataset
from ..obs import metrics as _metrics
from ..obs.trace import span as _span
from ..sampler.base import NodeSamplerInput
from ..sampler.neighbor_sampler import NeighborSampler
from ..typing import PADDING_ID
from .transform import Batch, to_batch

# Host-boundary instrumentation (docs/observability.md): the dispatch
# span measures async enqueue cost only — device completion is observed
# by the consumer's own sync, never forced here (a per-batch fence would
# serialize the prefetch pipeline this loader exists to keep full).
_M_BATCHES = _metrics.counter(
    "glt.loader.batches", "batches delivered by Node/NeighborLoader")
_M_OVERFLOW = _metrics.counter(
    "glt.loader.overflow_batches",
    "occupancy-capped batches re-sampled at full capacity")
_M_SAMPLE_MS = _metrics.histogram(
    "glt.loader.sample_dispatch_ms", "sampler dispatch wall per batch")
_M_COLLATE_MS = _metrics.histogram(
    "glt.loader.collate_ms", "feature/label collate dispatch per batch")


class NodeLoader:
    """Iterate seed-node batches through a sampler into :class:`Batch` es.

    Args:
      data: the :class:`~glt_tpu.data.dataset.Dataset`.
      node_sampler: any sampler exposing ``sample_from_nodes``.
      input_nodes: ``[num_seeds]`` global seed ids (host).
      batch_size: static batch width; the trailing partial batch is padded
        (never dropped) unless ``drop_last``.
      shuffle: reshuffle seeds each epoch.
      prefetch: how many sampled batches to keep in flight.
    """

    def __init__(
        self,
        data: Dataset,
        node_sampler,
        input_nodes: np.ndarray,
        batch_size: int = 512,
        shuffle: bool = False,
        drop_last: bool = False,
        prefetch: int = 2,
        seed: int = 0,
        overflow_fallback: bool = True,
    ):
        self.data = data
        self.sampler = node_sampler
        self.input_nodes = np.asarray(input_nodes).astype(np.int64)
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.prefetch = max(1, int(prefetch))
        self._rng = np.random.default_rng(seed)
        self._labels_dev = None
        self._epoch = 0
        # Occupancy-capped samplers flag rare batches whose unique-node
        # count exceeds the static buffer; strict mode (default) re-runs
        # those through the exact full-capacity program.  Costs one
        # device->host scalar fetch per batch — free once the batch is
        # consumed anyway; set False to defer (flag rides in
        # batch.metadata, overflow edges are already masked).
        self.overflow_fallback = bool(overflow_fallback)
        self.overflow_batches = 0
        self._autotune_row_gather()
        self._autotune_sample()

    def _autotune_row_gather(self) -> None:
        """Warmup sweep of the row-gather kernel (XLA vs the tiled-DMA
        Pallas (tile_rows, ring_depth) grid) for this loader's gather
        shape, memoized per (row width, batch, dtype) —
        ``gather_rows(force='auto')`` then serves every ``_collate_fn``
        with the measured winner.  The probe is built at THIS sampler's
        ``node_capacity``, so an occupancy-capped loader sweeps its own
        (smaller) shape instead of inheriting a full-cap winner whose
        tile/padding choice may lose there.  No-op off TPU, for widths
        outside the kernel's gate (config 1's 100) and for tiered/absent
        features (their gathers are host-side stages)."""
        feat = self.data.get_node_feature() if self.data is not None else None
        cap = getattr(self.sampler, "node_capacity", None)
        if (feat is None or cap is None
                or getattr(feat, "hot_count", 0) != getattr(feat, "size", -1)):
            return
        from ..ops.gather_pallas import autotune_gather_rows

        # Spread probe ids across the table: a constant index would hit
        # one cached row and flatter whichever path wins on latency.
        probe = jnp.arange(int(cap), dtype=jnp.int32) % max(feat.size, 1)
        autotune_gather_rows(feat.hot_rows, probe)

    def _autotune_sample(self) -> None:
        """Warmup sweep of the neighbor-sampling kernel (XLA vs the
        degree-binned Pallas (tile_rows, ring_depth, bin_edges) grid),
        one sweep per hop at that hop's **exact** frontier (width,
        fanout) — ``sample_neighbors(force='auto')`` inside the
        sampler's jitted programs then serves each hop with its measured
        winner.  Same exact-shape discipline as ``_autotune_row_gather``
        (a capped hop width is its own key, never the full-cap
        winner's).  No-op off TPU — ``autotune_sample`` pins 'xla'
        there, so CPU runs resolve the seam honestly — and for samplers
        without the hop-width protocol.  On a TPU the sweep is ruled
        out while ``sample_pallas.TPU_REFUSAL`` stands; the table
        records the reason per hop."""
        sampler = self.sampler
        graph = getattr(sampler, "graph", None)
        widths = getattr(sampler, "_widths", None)
        fanouts = getattr(sampler, "num_neighbors", None)
        if graph is None or widths is None or not fanouts:
            return
        if jax.default_backend() != "tpu":
            return
        from ..ops.sample_pallas import autotune_sample

        nn = max(int(graph.num_nodes), 1)
        for w, f in zip(widths, fanouts):
            # Probe seeds spread across the graph so per-bin occupancy
            # reflects the real degree distribution, not one hot row.
            probe = jnp.arange(int(w), dtype=jnp.int32) % nn
            autotune_sample(graph.indptr, graph.indices, probe, int(f),
                            edge_ids=graph.gather_edge_ids,
                            with_edge=getattr(sampler, "with_edge", True))

    def __len__(self) -> int:
        n = self.input_nodes.shape[0]
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    # -- state-capture protocol (glt_tpu.ckpt) -----------------------------
    def state_dict(self) -> dict:
        """Epoch cursor + shuffle-rng state, for durable checkpoints.

        Restoring this into a freshly constructed loader (same seeds,
        same config) makes its NEXT epoch's shuffle order identical to
        what the captured loader would have drawn — the loader half of
        the bit-identical-resume contract.  Covers every subclass
        (Neighbor/Link/LinkNeighbor ride the same ``_rng``/``_epoch``).
        """
        from ..ckpt.state import capture_rng

        return {
            "epoch": int(self._epoch),
            "rng": capture_rng(self._rng),
            "overflow_batches": int(self.overflow_batches),
        }

    def load_state_dict(self, state: dict) -> None:
        from ..ckpt.state import load_rng

        load_rng(self._rng, state["rng"])
        self._epoch = int(state["epoch"])
        self.overflow_batches = int(state.get("overflow_batches", 0))

    def _epoch_seed_batches(self) -> Iterator[np.ndarray]:
        ids = self.input_nodes
        if self.shuffle:
            ids = ids[self._rng.permutation(ids.shape[0])]
        n = ids.shape[0]
        end = (n // self.batch_size) * self.batch_size if self.drop_last else n
        for lo in range(0, end, self.batch_size):
            yield ids[lo: lo + self.batch_size]

    def __iter__(self) -> Iterator[Batch]:
        self._epoch += 1
        pending = deque()
        batches = self._epoch_seed_batches()
        feat = self.data.get_node_feature() if self.data is not None else None
        stage = getattr(feat, "stage_ahead", None)
        # A tiered feature resolves a batch's ids on the device
        # (Feature.plan_gather): dispatched here behind the sample, the
        # plan has reached the host by the time the batch is collated.
        plan_of = (feat.plan_gather
                   if getattr(feat, "plans_gathers", False) else None)
        live = getattr(self.sampler, "live", None)  # any sampler may come
        try:
            while True:
                while len(pending) < self.prefetch:
                    seeds = next(batches, None)
                    if seeds is None:
                        break
                    if stage is not None:
                        # Disk-tier hint (glt_tpu.store): seeds are
                        # host-side at dispatch, so this costs no device
                        # sync; the DRAM stager pulls their rows off
                        # disk while the batch sits in the prefetch
                        # queue.  No-op for DRAM-resident features.
                        stage(np.asarray(seeds))
                    with _span("loader.sample_dispatch"), \
                            _M_SAMPLE_MS.time():
                        out = self.sampler.sample_from_nodes(
                            NodeSamplerInput(seeds))
                    # Deferred-flag pattern (cf. run_scanned_epoch):
                    # start the flag's D2H copy at dispatch so the
                    # strict check at pop time resolves a transfer that
                    # overlapped the prefetch window instead of paying a
                    # blocking round trip per batch.
                    self._prime_overflow_flag(out)
                    # The sample's live counts ride the same pattern, on
                    # the registry's carrier: copied behind the program,
                    # counted once landed, never waited for here.
                    if live is not None:
                        _metrics.defer(live.counters, out.live_counts,
                                       live.per_row)
                    pending.append((out, seeds.shape[0], None if plan_of
                                    is None else plan_of(out.node)))
                if not pending:
                    return
                sampled, nseeds, plan = pending.popleft()
                out = self._maybe_refetch_overflow(sampled)
                if out is not sampled:
                    plan = None         # replayed: another node list
                with _span("loader.collate"), _M_COLLATE_MS.time():
                    batch = self._collate_fn(out, nseeds, plan)
                _M_BATCHES.inc()
                yield batch
        finally:
            pending.clear()

    def _overflow_checked(self) -> bool:
        """Whether the strict overflow fallback is active for this loader."""
        return (self.overflow_fallback
                and bool(getattr(self.sampler, "capped", False)))

    def _prime_overflow_flag(self, out) -> None:
        """Async-fetch the overflow scalar of a freshly primed batch.

        ``copy_to_host_async`` enqueues the device->host copy behind the
        sample program; by the time the batch reaches the head of the
        prefetch queue the scalar has usually landed, so the pop-time
        check costs ~nothing when overflow never occurs (the blocking
        per-batch ``device_get`` round trip was ADVICE r5's finding).
        """
        if not self._overflow_checked() or not out.metadata:
            return
        flag = out.metadata.get("overflow")
        if flag is not None:
            flag.copy_to_host_async()

    def _maybe_refetch_overflow(self, out):
        """Strict overflow fallback: re-sample a flagged batch through the
        sampler's full-capacity twin.

        Only the SEEDS are verbatim (``out.batch``): the full-capacity
        sibling draws with its own fresh RNG counter, so the refetched
        batch is a NEW neighbor draw at full capacity — not the uncapped
        replay of the flagged draw.  Fine for training (any exact draw
        is as good as another); don't expect deterministic reproduction
        of the flagged batch during eval/debugging.
        """
        if not self._overflow_checked() or not out.metadata:
            return out
        import jax

        # The one place next() can block on the device: the flag is an
        # output of the sample program.
        with _span("loader.overflow_wait"):
            flagged = bool(np.asarray(
                jax.device_get(out.metadata["overflow"])))
        if not flagged:
            return out
        self.overflow_batches += 1
        _M_OVERFLOW.inc()
        with _span("loader.overflow_replay"):
            return self.sampler.full_capacity_sibling().sample_from_nodes(
                NodeSamplerInput(out.batch))

    # -- collate (cf. node_loader.py:85 ``_collate_fn``) -------------------
    def _collate_fn(self, out, num_seeds: int, plan=None) -> Batch:
        x = None
        feat = self.data.get_node_feature()
        if feat is not None:
            x = (feat.gather(out.node) if plan is None
                 else feat.gather(out.node, plan=plan))
        y = None
        labels = self.data.get_node_label()
        if labels is not None:
            if self._labels_dev is None:
                self._labels_dev = jnp.asarray(np.asarray(labels))
            safe = jnp.clip(out.node, 0, self._labels_dev.shape[0] - 1)
            y = jnp.where(out.node >= 0, jnp.take(self._labels_dev, safe,
                                                  axis=0), PADDING_ID)
        return to_batch(out, x=x, y=y, batch_size=num_seeds)


class NeighborLoader(NodeLoader):
    """Neighbor-sampling loader (cf. loader/neighbor_loader.py:27-105).

    Builds its own :class:`NeighborSampler` from ``num_neighbors`` when one
    isn't supplied.
    """

    def __init__(
        self,
        data: Dataset,
        num_neighbors: Sequence[int],
        input_nodes: np.ndarray,
        batch_size: int = 512,
        shuffle: bool = False,
        drop_last: bool = False,
        frontier_cap: Optional[int] = None,
        with_edge: bool = True,
        prefetch: int = 2,
        seed: int = 0,
        sampler: Optional[NeighborSampler] = None,
        as_pyg_v1: bool = False,
        last_hop_dedup: bool = True,
        node_capacity: Optional[int] = None,
        overflow_fallback: bool = True,
        sample_force: str = "auto",
    ):
        if sampler is None:
            sampler = NeighborSampler(
                data.get_graph(), num_neighbors, batch_size=batch_size,
                frontier_cap=frontier_cap, with_edge=with_edge, seed=seed,
                last_hop_dedup=last_hop_dedup, node_capacity=node_capacity,
                sample_force=sample_force)
        super().__init__(data, sampler, input_nodes, batch_size=batch_size,
                         shuffle=shuffle, drop_last=drop_last,
                         prefetch=prefetch, seed=seed,
                         overflow_fallback=overflow_fallback)
        self.num_neighbors = list(num_neighbors)
        self.frontier_cap = frontier_cap
        self.as_pyg_v1 = as_pyg_v1

    def __iter__(self):
        if not self.as_pyg_v1:
            yield from super().__iter__()
            return
        # Layered (batch_size, n_id, adjs) protocol
        # (cf. neighbor_loader.py as_pyg_v1 path).
        from .transform import as_pyg_v1_adjs

        for batch in super().__iter__():
            # widths derive from the loader's static batch width, not the
            # (possibly smaller) trailing batch's seed count
            yield as_pyg_v1_adjs(batch, self.batch_size,
                                 self.num_neighbors, self.frontier_cap)
