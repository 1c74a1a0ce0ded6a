"""Tiered feature store: HBM-resident hot rows + host-DRAM cold rows.

Rebuild of the reference's two-tier feature system (python/data/feature.py +
csrc/cuda/unified_tensor.cu): there, a ``split_ratio`` fraction of rows is
sharded across an NVLink clique's GPUs and the remainder is pinned host
memory read through UVA, with a warp-per-row gather kernel choosing the
source by binary-scanning shard offsets (unified_tensor.cu:35-81).

TPU redesign — no UVA, no IPC handles:

* the **hot tier** is a plain ``jax.Array`` in device HBM (sharding it
  across a mesh is the :mod:`glt_tpu.parallel` layer's job, the analog of
  the reference's ``DeviceGroup`` replication, feature.py:31-45);
* the **cold tier** stays in host numpy and is gathered eagerly on the
  host — and ONLY at the batch positions that actually resolve cold: the
  host moves ``n_cold`` rows, not ``B`` rows, and the hot/cold merge is a
  padded device scatter instead of a double full-batch materialization;
* an optional **cross-batch HBM cache** (:mod:`.feature_cache`) fronts the
  cold tier: recently fetched cold rows stay device-resident, so repeat
  lookups (hub nodes under power-law sampling) skip the host entirely —
  the TPU seat of the reference's ``UnifiedTensor`` hotness cache.  Enable
  with :meth:`Feature.enable_cold_cache`; hit/miss counters ride on device
  and surface through :meth:`Feature.cache_stats`.
* the ``id2index`` indirection (feature.py:141-154) is identical: lookups
  translate global ids through the hotness reordering of
  :func:`~glt_tpu.data.reorder.sort_by_in_degree`.

``gather`` is jit-safe when the store is fully device-resident
(``split_ratio == 1.0``); tiered stores gather eagerly with a static output
shape ``[B, d]``.  Padding ids (< 0) return zero rows either way.  With
``dedup=True`` device gathers route through
:func:`~glt_tpu.ops.dedup_gather.dedup_gather_rows` — bit-identical
output, each unique row fetched from HBM once.

Ids must fit int32 (GLT004): int64 id arrays are accepted but their
VALUES are range-checked before the cast — silent truncation raises
``OverflowError`` instead of corrupting the gather.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.scopes import scoped
from .feature_cache import cache_init, cache_insert, cache_lookup

_I32_MAX = np.iinfo(np.int32).max
_I32_MIN = np.iinfo(np.int32).min


def require_int32_ids(ids) -> None:
    """GLT004 guard: refuse id VALUES that overflow int32.

    The whole engine runs int32 ids on device (x64 is disabled); a host
    int64 id array is fine as long as every value fits — otherwise the
    downcast silently truncates and the gather reads the wrong rows.
    Host-side check only (device arrays are already int32-typed; checking
    their values would force a sync).
    """
    if isinstance(ids, jax.core.Tracer) or isinstance(ids, jax.Array):
        return
    a = np.asarray(ids)
    if a.dtype.kind in "iu" and a.dtype.itemsize > 4 and a.size:
        mx, mn = int(a.max()), int(a.min())
        if mx > _I32_MAX or mn < _I32_MIN:
            raise OverflowError(
                f"node ids [{mn}, {mx}] overflow int32; the id space must "
                f"fit int32 (relabel/partition first — GLT004)")


def _pow2_pad(k: int) -> int:
    """Bucket a dynamic count to the next power of two (bounds the jit
    retrace count of the padded merge scatter to log2(B))."""
    return 1 if k <= 1 else 1 << (k - 1).bit_length()


class Feature:
    """Row-gatherable feature matrix with hot/cold tiering.

    Args:
      feature_array: ``[N, d]`` host array (already hotness-reordered if
        ``id2index`` is given).
      split_ratio: fraction of rows resident in device HBM (the rest stays
        on host).  1.0 = fully device-resident, 0.0 = fully host.
      id2index: optional ``[N]`` indirection from global id to row.
      dtype: optional cast applied to gathered rows (e.g. ``jnp.bfloat16``).
      dedup: route device gathers through the dedup-aware path (each
        unique row fetched once; output bit-identical to the naive
        gather).
    """

    def __init__(
        self,
        feature_array: np.ndarray,
        split_ratio: float = 1.0,
        id2index: Optional[np.ndarray] = None,
        dtype=None,
        dedup: bool = False,
    ):
        feature_array = np.asarray(feature_array)
        if feature_array.ndim == 1:
            feature_array = feature_array[:, None]
        self._n, self._dim = feature_array.shape
        self.split_ratio = float(split_ratio)
        self._hot_count = int(self._n * self.split_ratio)
        self.dtype = dtype or jnp.asarray(feature_array[:1]).dtype
        self.dedup = bool(dedup)

        self._quant = None               # compressed stores only (from_store)
        self._hot = jnp.asarray(feature_array[: self._hot_count], self.dtype)
        # Host tier; kept as a contiguous numpy view for fast np.take.
        self._cold = np.ascontiguousarray(feature_array[self._hot_count:])
        self._cold_count = self._cold.shape[0]
        self._cold_np_dtype = self._cold.dtype
        self._id2index = (
            None if id2index is None else jnp.asarray(id2index, jnp.int32))
        self._id2index_np = (
            None if id2index is None else np.asarray(id2index, np.int32))
        self._host_full = feature_array  # for cpu_get / save paths
        self._store = None               # optional disk tier (glt_tpu.store)
        self._stager = None
        self.bytes_from_hbm = 0          # hot-tier bytes served (tiered path)
        self._gather_jit = None
        self._cache = None               # optional cold-tier HBM cache
        self._cache_lookup_jit = None
        self._merge_cached_jit = None
        self._merge_jit = None

    @classmethod
    def from_store(cls, store, dram_budget_bytes: int,
                   split_ratio: float = 0.0,
                   id2index: Optional[np.ndarray] = None,
                   dtype=None, dedup: bool = False,
                   stage_threads: int = 1,
                   prefetch_scores: Optional[np.ndarray] = None
                   ) -> "Feature":
        """Third-tier constructor: features live on disk, never fully in
        DRAM (docs/storage.md).

        The ``split_ratio`` prefix loads to HBM once (straight from the
        store); every other row is served by a
        :class:`~glt_tpu.store.stager.DramStager` under the given
        (enforced) DRAM budget — cold gathers are bit-identical to the
        all-DRAM :class:`Feature`, only their residency differs.
        ``prefetch_scores`` (e.g. :func:`~glt_tpu.partition.
        frequency_partitioner.residency_scores` over the partition
        book's access statistics) warms the stager's DRAM set.

        A COMPRESSED store (``store.codec`` bf16/int8) keeps compressed
        bytes in every tier — the HBM hot prefix, the stager's DRAM
        buffer (whose row budget therefore stretches 2x/4x) and the
        device transfer — and dequantizes on-chip in the gather
        epilogue; ``self.dtype`` is then the LOGICAL dtype gathers
        return (f32), not the wire dtype.
        """
        from ..store.stager import DramStager

        self = cls.__new__(cls)
        self._n, self._dim = store.num_rows, store.dim
        self.split_ratio = float(split_ratio)
        self._hot_count = int(self._n * self.split_ratio)
        hot_np = store.read_rows(np.arange(self._hot_count, dtype=np.int64))
        spec = store.quant_spec() if hasattr(store, "quant_spec") else None
        self._quant = spec if (spec is not None and spec.is_compressed) \
            else None
        self.dedup = bool(dedup)
        if self._quant is not None:
            self.dtype = dtype or jnp.asarray(
                np.zeros(1, np.dtype(self._quant.logical_dtype))).dtype
            # storage-dtype hot tier (explicit dtype: rows, not ids)
            self._hot = jnp.asarray(hot_np, hot_np.dtype)
        else:
            self.dtype = dtype or jnp.asarray(np.zeros(1, store.dtype)).dtype
            self._hot = jnp.asarray(hot_np, self.dtype)
        self._cold = None                # no DRAM copy of the cold tier
        self._cold_count = self._n - self._hot_count
        self._cold_np_dtype = store.dtype
        self._id2index = (
            None if id2index is None else jnp.asarray(id2index, jnp.int32))
        self._id2index_np = (
            None if id2index is None else np.asarray(id2index, np.int32))
        self._host_full = None           # cpu_get reads the store directly
        self._store = store
        self._stager = DramStager(store, dram_budget_bytes,
                                  stage_threads=stage_threads)
        if prefetch_scores is not None and self._cold_count:
            scores = np.zeros(self._n, np.float64)
            scores[:] = np.asarray(prefetch_scores, np.float64)
            scores[: self._hot_count] = 0.0   # hot prefix never staged
            self._stager.warm(scores)
        self.bytes_from_hbm = 0
        self._gather_jit = None
        self._cache = None
        self._cache_lookup_jit = None
        self._merge_cached_jit = None
        self._merge_jit = None
        return self

    def _fetch_cold(self, local_ids: np.ndarray) -> np.ndarray:
        """Cold rows by LOCAL id (0 = first cold row) — the tier seam:
        DRAM-resident numpy for plain features, DRAM-stage-or-disk for
        store-backed ones (bit-identical rows either way)."""
        if self._stager is not None:
            return self._stager.gather(
                np.asarray(local_ids, np.int64) + self._hot_count)
        return self._cold[local_ids]

    def stage_ahead(self, ids) -> None:
        """Hint upcoming global ``ids`` to the DRAM stager (async; no-op
        for DRAM-resident features).  The loader calls this at sample
        *dispatch* so staging overlaps the prefetch window."""
        if self._stager is None:
            return
        ids = np.asarray(ids).reshape(-1)
        ids = ids[ids >= 0].astype(np.int64)
        if self._id2index_np is not None:
            ids = self._id2index_np[ids].astype(np.int64)
        self._stager.stage_ahead(ids[ids >= self._hot_count])

    def store_stats(self) -> Optional[dict]:
        """Tier byte counters for store-backed features (``glt.store.*``
        seed): stager counters + this feature's hot-tier bytes."""
        if self._stager is None:
            return None
        stats = self._stager.stats()
        stats["bytes_from_hbm"] = self.bytes_from_hbm
        return stats

    def close(self) -> None:
        """Release the staging threads of a store-backed feature."""
        if self._stager is not None:
            self._stager.close()

    @scoped("glt.gather.feat")
    def _gather_hot_impl(self, hot, id2index, ids):
        from ..ops.dedup_gather import dedup_gather_rows
        from ..ops.gather_pallas import gather_rows
        from ..store import quant

        ids = ids.astype(jnp.int32)
        if self.dedup:
            # unique -> gather uniques -> scatter back (bit-identical).
            rows = dedup_gather_rows(hot, ids, id2index=id2index)
            if self._quant is not None:
                # Padding rows must be re-zeroed AFTER dequant:
                # dequantize(0) is the column zero point, not 0.
                rows = jnp.where((ids >= 0)[:, None],
                                 quant.dequantize(rows, self._quant), 0)
            return rows
        valid = ids >= 0
        idx = jnp.where(valid, ids, 0)
        if id2index is not None:
            idx = id2index[idx]
        rows = gather_rows(hot, idx, dequant=self._quant)
        return jnp.where(valid[:, None], rows, 0)

    # -- shape info --------------------------------------------------------
    @property
    def shape(self):
        return (self._n, self._dim)

    @property
    def size(self) -> int:
        return self._n

    @property
    def hot_count(self) -> int:
        return self._hot_count

    @property
    def id2index(self):
        return self._id2index

    @property
    def hot_rows(self) -> jnp.ndarray:
        """The HBM-resident hot tier ``[hot_count, d]`` as a jax.Array."""
        return self._hot

    # -- cold-tier cache ---------------------------------------------------
    def enable_cold_cache(self, capacity: int) -> None:
        """Attach a device-resident cache in front of the host cold tier.

        ``capacity`` rows of the cold tier stay resident in HBM (FIFO
        replacement); tiered ``gather`` calls then host-fetch only the
        cache MISSES.  Costs one device->host fetch of the ``[B]`` hit
        mask per gather (the host must know which rows to stage — the
        same sync the loader's overflow check already pays).

        A fully device-resident store (``split_ratio == 1.0``) has
        nothing to cache: the call warns and no-ops instead of failing.
        ``capacity`` above the cold-row count would only pad a cache no
        gather can ever fill past the cold tier itself, so it clamps
        (with a warning) to the cold-row count.
        """
        if self._cold_count == 0:
            warnings.warn(
                "enable_cold_cache is a no-op at split_ratio == 1.0: "
                "every row is already HBM-resident, there is no cold "
                "tier to cache", RuntimeWarning, stacklevel=2)
            return
        capacity = int(capacity)
        if capacity > self._cold_count:
            warnings.warn(
                f"cold-cache capacity {capacity} exceeds the "
                f"{self._cold_count}-row cold tier; clamping (a larger "
                f"cache can never hold more than every cold row)",
                RuntimeWarning, stacklevel=2)
            capacity = self._cold_count
        self._cache = cache_init(self._cold_count, capacity,
                                 self._dim, self.dtype)
        self._cache_lookup_jit = jax.jit(cache_lookup)

    def cache_stats(self) -> Optional[dict]:
        """Cold-cache hit/miss counters (host sync), or None."""
        if self._cache is None:
            return None
        from .feature_cache import cache_stats as _stats

        return _stats(self._cache)

    # -- gather ------------------------------------------------------------
    def gather(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Gather rows for global ``ids`` (-1 padded).

        Fully device-resident stores (``split_ratio == 1.0``) are jit-safe.
        Tiered stores run the hot gather on device and the cold gather on
        host — touching each tier only at its own batch positions — and
        merge with a padded device scatter; callable only eagerly (the
        loader stages it before the jitted train step).  Padding rows are
        zeros.
        """
        if self._cold_count == 0:
            if isinstance(ids, jax.core.Tracer):
                # Already inside an enclosing jit: trace inline.
                return self._gather_hot_impl(self._hot, self._id2index,
                                             jnp.asarray(ids, jnp.int32))
            require_int32_ids(ids)
            # Eager call sites (loader collate): ONE fused dispatch
            # instead of one per op.
            if self._gather_jit is None:
                self._gather_jit = jax.jit(self._gather_hot_impl)
            return self._gather_jit(self._hot, self._id2index,
                                    jnp.asarray(ids, jnp.int32))

        if isinstance(ids, jax.core.Tracer):
            raise ValueError(
                "tiered Feature.gather (split_ratio < 1) is a host-side "
                "stage and cannot run under jit; gather before the jitted "
                "step or use split_ratio=1.0")
        require_int32_ids(ids)
        ids_np = np.asarray(ids).astype(np.int64)
        valid = ids_np >= 0
        idx = np.where(valid, ids_np, 0)
        if self._id2index_np is not None:
            idx = self._id2index_np[idx].astype(np.int64)
        is_hot = idx < self._hot_count
        hot_mask = valid & is_hot
        cold_mask = valid & ~is_hot
        if self._cache is not None:
            return self._gather_tiered_cached(idx, hot_mask, cold_mask)
        cold_pos = np.nonzero(cold_mask)[0]
        # Host moves ONLY the cold rows (was: full-batch np.take of both
        # tiers + masked merge).  Hot bytes count at the WIRE width — a
        # compressed hot tier serves compressed bytes.
        self.bytes_from_hbm += int(hot_mask.sum()) * self._dim \
            * jnp.dtype(self._hot.dtype).itemsize
        cold_np = self._fetch_cold(idx[cold_pos] - self._hot_count)
        cap = _pow2_pad(cold_pos.shape[0])
        b = ids_np.shape[0]
        pos_pad = np.full((cap,), b, np.int32)      # b = out-of-range: drop
        pos_pad[: cold_pos.shape[0]] = cold_pos
        rows_pad = np.zeros((cap, self._dim), self._cold_np_dtype)
        rows_pad[: cold_pos.shape[0]] = cold_np
        # Compressed rows cross the host->device wire at storage width
        # and widen inside the jitted merge; raw rows cast to the target
        # dtype host-side as before.
        rows_dev = (jnp.asarray(rows_pad) if self._quant is not None
                    else jnp.asarray(rows_pad, self.dtype))
        return self._merge_tiered(
            jnp.asarray(np.where(hot_mask, idx, 0), jnp.int32),
            jnp.asarray(hot_mask), jnp.asarray(pos_pad), rows_dev)

    def _merge_tiered(self, idx, hot_mask, cold_pos, cold_rows):
        """Device merge: hot gather at hot slots + cold-row scatter."""
        if self._merge_jit is None:
            from ..store import quant

            spec = self._quant

            @jax.jit
            def merge(hot, idx, hot_mask, cold_pos, cold_rows):
                if spec is not None:
                    cold_rows = quant.dequantize(cold_rows, spec)
                if hot.shape[0]:
                    rows = jnp.take(hot, idx, axis=0, mode="clip")
                    if spec is not None:
                        rows = quant.dequantize(rows, spec)
                    out = jnp.where(hot_mask[:, None], rows, 0)
                else:
                    # Fully host-resident (split_ratio == 0, e.g. a
                    # shared-memory attach in a sampling worker).
                    out = jnp.zeros((idx.shape[0], cold_rows.shape[1]),
                                    cold_rows.dtype)
                return out.at[cold_pos].set(cold_rows, mode="drop")

            self._merge_jit = merge
        return self._merge_jit(self._hot, idx, hot_mask, cold_pos,
                               cold_rows)

    def _gather_tiered_cached(self, idx, hot_mask, cold_mask):
        """Tiered gather with the HBM cold cache in front of the host.

        One device->host sync (the hit mask); the host stages only cache
        misses, and the merge program inserts them into the cache for the
        next batch (the previous cache buffers are donated in place).
        """
        b = idx.shape[0]
        cold_ids = np.where(cold_mask, idx - self._hot_count, -1).astype(
            np.int32)
        cold_ids_dev = jnp.asarray(cold_ids)
        rows_c, hit = self._cache_lookup_jit(self._cache, cold_ids_dev)
        hit_np = np.asarray(hit)                      # the one sync
        miss_mask = cold_mask & ~hit_np
        miss_pos = np.nonzero(miss_mask)[0]
        self.bytes_from_hbm += int(hot_mask.sum()) * self._dim \
            * jnp.dtype(self._hot.dtype).itemsize
        miss_np = self._fetch_cold(idx[miss_pos] - self._hot_count)
        cap = _pow2_pad(miss_pos.shape[0])
        pos_pad = np.full((cap,), b, np.int32)
        pos_pad[: miss_pos.shape[0]] = miss_pos
        rows_pad = np.zeros((cap, self._dim), self._cold_np_dtype)
        rows_pad[: miss_pos.shape[0]] = miss_np

        if self._merge_cached_jit is None:
            from ..store import quant

            spec = self._quant

            @jax.jit
            def merge_cached(cache, hot, idx, hot_mask, rows_c, hit,
                             cold_ids, miss_mask, cold_pos, cold_rows):
                # The cold cache stores POST-dequant logical rows, so
                # only the freshly staged misses widen here.
                if spec is not None:
                    cold_rows = quant.dequantize(cold_rows, spec)
                if hot.shape[0]:
                    rows = jnp.take(hot, idx, axis=0, mode="clip")
                    if spec is not None:
                        rows = quant.dequantize(rows, spec)
                    out = jnp.where(hot_mask[:, None], rows, 0)
                else:
                    out = jnp.zeros((idx.shape[0], rows_c.shape[1]),
                                    rows_c.dtype)
                out = jnp.where(hit[:, None], rows_c.astype(out.dtype), out)
                out = out.at[cold_pos].set(cold_rows.astype(out.dtype),
                                           mode="drop")
                # Insert the staged miss rows; out at miss positions holds
                # exactly the host-fetched cold rows.
                cache = cache_insert(
                    cache, jnp.where(miss_mask, cold_ids, -1), out,
                    miss_mask)
                cache = cache._replace(
                    hits=cache.hits + jnp.sum(hit.astype(jnp.int32)),
                    misses=cache.misses
                    + jnp.sum(miss_mask.astype(jnp.int32)))
                return cache, out

            self._merge_cached_jit = merge_cached

        rows_dev = (jnp.asarray(rows_pad) if self._quant is not None
                    else jnp.asarray(rows_pad, self.dtype))
        self._cache, out = self._merge_cached_jit(
            self._cache, self._hot,
            jnp.asarray(np.where(hot_mask, idx, 0), jnp.int32),
            jnp.asarray(hot_mask), rows_c, hit, cold_ids_dev,
            jnp.asarray(miss_mask), jnp.asarray(pos_pad), rows_dev)
        return out

    def __getitem__(self, ids) -> jnp.ndarray:
        return self.gather(jnp.atleast_1d(jnp.asarray(ids)))

    def cpu_get(self, ids: np.ndarray) -> np.ndarray:
        """Pure host-side lookup (cf. feature.py:156 ``cpu_get``).

        Store-backed features (:meth:`from_store`) read the rows straight
        off the disk store — no full DRAM materialization exists to index
        — bypassing the stager so inspection reads never churn the
        residency set.
        """
        require_int32_ids(ids)
        ids = np.atleast_1d(np.asarray(ids))
        valid = ids >= 0
        idx = np.where(valid, ids, 0)
        if self._id2index is not None:
            idx = np.asarray(self._id2index)[idx]
        if self._host_full is None:
            rows = self._store.read_rows(np.asarray(idx, np.int64))
            if self._quant is not None:
                from ..store import quant

                # Host decode mirrors the device formula; padding rows
                # re-zero below (decode(0) != 0 for int8).
                rows = quant.decode(rows, self._quant)
        else:
            rows = self._host_full[idx]
        rows = np.where(valid[:, None], rows, 0)
        return rows

    def __len__(self) -> int:
        return self._n

    def __repr__(self) -> str:
        return (f"Feature(shape={self.shape}, split_ratio={self.split_ratio},"
                f" hot={self._hot_count})")
