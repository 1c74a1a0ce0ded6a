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
  host — and ONLY at the batch positions that actually resolve cold.
  The device resolves the ids (``id2index``, which tier, the cold rows'
  positions: the *plan*, one small program whose result the host
  fetches), the host moves ``n_cold`` rows into one reused host buffer
  and sends it, and a second program gathers the hot rows and places
  the cold ones (:meth:`Feature.gather` has the details, and the static
  cold width that keeps it to those two programs);
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

import contextlib
import os
import threading
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics
from ..obs.scopes import scoped
from ..obs.trace import span as _span
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


# Tiered-gather instrumentation (docs/observability.md): rows by the tier
# that served them, and the rows that crossed the host link with their
# padding.  Host counts only: both come back with the plan's fetch.
_M_HOT_ROWS = _metrics.counter(
    "glt.feature.hot_rows", "rows a tiered gather served from HBM")
_M_COLD_ROWS = _metrics.counter(
    "glt.feature.cold_rows", "rows a tiered gather served from the host")
_M_COLD_SENT = _metrics.counter(
    "glt.feature.cold_rows_sent",
    "row slots sent host to device for them, padding included")
_M_HOT_COUNT = _metrics.gauge(
    "glt.feature.hot_count",
    "rows of the hot tier of the last tiered Feature built")

# The cold fetch's threads (four read 100 k rows in 3.7 ms where one takes
# 9.2 and eight 2.9; scripts/tier_micro.py), and the rows below which one
# np.take beats handing runs to the pool.
_COLD_THREADS = 4
_POOL_MIN_ROWS = 8192


class ColdPlan(NamedTuple):
    """The device's half of a tiered gather's bookkeeping
    (:meth:`Feature.plan_gather`): device arrays, the last two on their
    way to the host."""
    hot_idx: jax.Array      # [B] row in the hot tier, -1 where not hot
    cold_pos: jax.Array     # [W] batch positions of cold rows, >= B: none
    cold_local: jax.Array   # [W] their rows in the cold tier
    counts: jax.Array       # [2] hot rows, cold rows of the whole batch


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
        self._init_gather_state()

    def _init_gather_state(self):
        """What every constructor leaves the gather paths with."""
        self.bytes_from_hbm = 0          # hot-tier bytes served (tiered path)
        self._gather_jit = None
        self._cache = None               # optional cold-tier HBM cache
        self._cache_lookup_jit = None
        self._merge_cached_jit = None
        self._merge_jit = None
        self._plan_jits = {}             # by static cold width
        self._patch_jit = None
        self._cold_width = None          # set_cold_width
        self._stage_buf = None           # its host buffer
        self._stage_lock = threading.Lock()
        self._pool = None                # the cold fetch's threads, lazily
        if self._cold_count:
            _M_HOT_COUNT.set(self._hot_count)

    @classmethod
    def from_tiers(cls, hot_rows, cold_rows: np.ndarray, id2index=None,
                   dtype=None, dedup: bool = False) -> "Feature":
        """Constructor from tiers that already exist: ``hot_rows``
        ``[hot, d]`` on the device (or a host array, placed once) and
        ``cold_rows`` ``[N - hot, d]`` in host memory, both in the order
        ``id2index`` gives (row ``id2index[v]`` of the two stacked is node
        ``v``'s; a device or host ``[N]`` array, or None for the identity:
        :func:`~glt_tpu.data.reorder.in_degree_order` makes one without
        the table).  For a table that is made, loaded or converted tier
        by tier and is larger than either memory alone: the two arrays
        are adopted as they are, no second copy of any row is held, and
        ``split_ratio`` reads ``hot / N``.  Gathers are those of the
        constructor from one array, bit for bit."""
        self = cls.__new__(cls)
        cold_rows = np.asarray(cold_rows)
        if cold_rows.ndim != 2 or np.ndim(hot_rows) != 2 \
                or hot_rows.shape[1] != cold_rows.shape[1]:
            raise ValueError(
                f"hot rows {np.shape(hot_rows)} and cold rows "
                f"{cold_rows.shape} must be two row blocks of one width")
        self._hot_count, self._dim = map(int, hot_rows.shape)
        self._cold_count = int(cold_rows.shape[0])
        self._n = self._hot_count + self._cold_count
        if id2index is not None and id2index.shape != (self._n,):
            raise ValueError(f"id2index {id2index.shape} for {self._n} rows")
        self.split_ratio = self._hot_count / max(self._n, 1)
        self.dtype = dtype or jnp.asarray(cold_rows[:1]).dtype
        self.dedup = bool(dedup)
        self._quant = None
        self._hot = jnp.asarray(hot_rows, self.dtype)
        self._cold = cold_rows if cold_rows.flags.c_contiguous \
            else np.ascontiguousarray(cold_rows)
        self._cold_np_dtype = self._cold.dtype
        self._id2index = (
            None if id2index is None else jnp.asarray(id2index, jnp.int32))
        self._id2index_np = None         # fetched on a host lookup, if ever
        self._host_full = None           # cpu_get reads the tiers
        self._store = None
        self._stager = None
        self._init_gather_state()
        return self

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
        self._init_gather_state()
        return self

    def _fetch_cold(self, local_ids: np.ndarray) -> np.ndarray:
        """Cold rows by LOCAL id (0 = first cold row) — the tier seam:
        DRAM-resident numpy for plain features, DRAM-stage-or-disk for
        store-backed ones (bit-identical rows either way)."""
        if self._stager is not None:
            return self._stager.gather(
                np.asarray(local_ids, np.int64) + self._hot_count)
        return self._cold[local_ids]

    def _fetch_cold_into(self, local_ids: np.ndarray, out: np.ndarray
                         ) -> None:
        """:meth:`_fetch_cold` written straight into ``out`` (the buffer
        that crosses the host link): one pass over the rows, split into
        contiguous runs over a few threads where there are enough of them
        (``np.take`` releases the GIL; ``mode="clip"`` because
        ``"raise"`` buffers ``out``, a second copy; the ids are in range
        by construction)."""
        n = local_ids.shape[0]
        if self._stager is not None:
            out[:] = self._fetch_cold(local_ids)
            return
        threads = min(_COLD_THREADS, len(os.sched_getaffinity(0)))
        if threads <= 1 or n < _POOL_MIN_ROWS:
            np.take(self._cold, local_ids, axis=0, out=out, mode="clip")
            return
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                threads, thread_name_prefix="glt-cold-fetch")
        step = -(-n // threads)
        for fut in [self._pool.submit(
                np.take, self._cold, local_ids[lo: lo + step], 0,
                out[lo: lo + step], "clip") for lo in range(0, n, step)]:
            fut.result()

    def _host_id2index(self) -> Optional[np.ndarray]:
        """``id2index`` as a host array, for the host-side lookups (a
        feature built from tiers holds it on the device alone until one
        asks)."""
        if self._id2index_np is None and self._id2index is not None:
            self._id2index_np = np.asarray(self._id2index)
        return self._id2index_np

    def stage_ahead(self, ids) -> None:
        """Hint upcoming global ``ids`` to the DRAM stager (async; no-op
        for DRAM-resident features).  The loader calls this at sample
        *dispatch* so staging overlaps the prefetch window."""
        if self._stager is None:
            return
        ids = np.asarray(ids).reshape(-1)
        ids = ids[ids >= 0].astype(np.int64)
        if self._id2index is not None:
            ids = self._host_id2index()[ids].astype(np.int64)
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
        """Release the staging threads of a store-backed feature and the
        cold fetch's."""
        if self._stager is not None:
            self._stager.close()
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None

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
    def gather(self, ids: jnp.ndarray,
               plan: Optional[ColdPlan] = None) -> jnp.ndarray:
        """Gather rows for global ``ids`` (-1 padded).

        Fully device-resident stores (``split_ratio == 1.0``) are jit-safe.
        Tiered stores run the hot gather on device and the cold gather on
        host — touching each tier only at its own batch positions —
        callable only eagerly (the loader stages it before the jitted
        train step).  Every row is the stored row bit for bit whatever
        tier holds it, padding rows are zeros, no row is dropped.

        The tiered path, stage by stage (spans ``glt.feature.*``, device
        scopes ``glt.gather.*``): the *plan* (:meth:`plan_gather`, taken
        from ``plan`` when the caller dispatched it earlier) resolves the
        ids on the device; the host waits for its two small outputs
        (``ids_wait``), copies the cold rows out of host memory into one
        buffer (``cold_fetch``), sends it (``cold_put``), and one program
        gathers the hot rows (``glt.gather.feat``) and places the cold
        ones (``glt.gather.merge``).  :meth:`set_cold_width` makes the
        buffer's width static.
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
        if self._cache is not None:
            return self._gather_tiered_cached(np.asarray(ids))
        if plan is None:
            plan = self.plan_gather(ids)
        b = plan.hot_idx.shape[0]
        with _span("feature.ids_wait"):
            # The one place a tiered gather waits for the device: the
            # plan is an output of a program behind the sampler's.
            n_hot, n_cold = map(int, np.asarray(plan.counts))
            local = np.asarray(plan.cold_local)
        width = local.shape[0]
        cold_pos = plan.cold_pos
        if self._cold_width is None:
            # No static width: a power-of-two bucket of the count, one
            # merge program a bucket (at most log2(B) of them).
            width = min(_pow2_pad(n_cold), b)
            cold_pos = cold_pos[:width]
        self.bytes_from_hbm += n_hot * self._dim \
            * jnp.dtype(self._hot.dtype).itemsize
        _M_HOT_ROWS.inc(n_hot)
        _M_COLD_ROWS.inc(n_cold)
        out, done = None, 0
        while True:
            take = min(n_cold - done, width)
            rows = self._stage_cold(local[:take], width)
            if out is None:
                out = self._merge_tiered(plan.hot_idx, cold_pos, rows)
            else:
                out = self._patch_tiered(out, cold_pos, rows)
            done += take
            if done >= n_cold:
                return out
            # A batch past the static width: the rows behind the first
            # ``width`` in further rounds of the same width, through
            # programs that are compiled already.
            more = self.plan_gather(ids, offset=done)
            cold_pos, local = more.cold_pos, np.asarray(more.cold_local)

    def set_cold_width(self, width: Optional[int]) -> None:
        """Fix the number of cold row slots a tiered gather sends a round
        (None: back to power-of-two buckets of each batch's count).

        With a width, every batch of one length runs the same two
        programs whatever its cold count, so nothing compiles once each
        has run (:meth:`warm_gather`), and the transfer carries
        ``width - n_cold`` rows of padding where a bucket carries up to
        ``n_cold``.  A batch with more cold rows than ``width`` is served
        in further rounds, exactly.  :func:`calibrate_cold_width` finds
        one."""
        if width is not None and int(width) < 1:
            raise ValueError(f"cold width {width} must be positive")
        self._cold_width = None if width is None else int(width)
        self._stage_buf = None
        if width is not None:
            self._stage_buf = np.empty((self._cold_width, self._dim),
                                       self._cold_np_dtype)
            self._stage_buf.fill(0)      # touch every page now

    @property
    def cold_width(self) -> Optional[int]:
        return self._cold_width

    @property
    def plans_gathers(self) -> bool:
        """Whether :meth:`gather` takes a plan dispatched ahead of it (a
        tiered store without the cold cache, whose lookups the host
        resolves)."""
        return self._cold_count > 0 and self._cache is None

    def plan_gather(self, ids, offset: int = 0) -> ColdPlan:
        """Dispatch the device's half of a tiered gather's bookkeeping
        for ``ids`` and start its fetch: which row of which tier every id
        resolves to, the batch positions of the cold ones and their rows
        in the cold tier (those of rank ``offset`` on, as many as the
        cold width), and the two counts.  Nothing waits: a loader calls
        this when it dispatches the sample, and hands the plan to
        :meth:`gather` when the batch is collated."""
        ids = jnp.asarray(ids, jnp.int32)
        width = min(self._cold_width or ids.shape[0], ids.shape[0])
        fn = self._plan_jits.get(width)
        if fn is None:
            def _plan(id2index, ids, offset):
                return self._plan_impl(id2index, ids, offset, width=width)

            fn = self._plan_jits[width] = jax.jit(_plan)
        plan = ColdPlan(*fn(self._id2index, ids, jnp.int32(offset)))
        plan.counts.copy_to_host_async()
        plan.cold_local.copy_to_host_async()
        return plan

    def _plan_impl(self, id2index, ids, offset, *, width: int):
        b = ids.shape[0]
        valid = ids >= 0
        with jax.named_scope("glt.gather.feat"):
            idx = jnp.where(valid, ids, 0)
            if id2index is not None:
                idx = jnp.take(id2index, idx, axis=0, mode="clip")
        with jax.named_scope("glt.gather.merge"):
            hot = valid & (idx < self._hot_count)
            cold = valid & ~hot
            keep = cold & (jnp.cumsum(cold.astype(jnp.int32)) > offset)
            # The kept positions first, in order: a sort, where a
            # scatter by rank costs five times as much on a TPU.
            at = jnp.arange(b, dtype=jnp.int32)
            _, pos, loc = jax.lax.sort(
                ((~keep).astype(jnp.int32), at, idx - self._hot_count),
                num_keys=2)
            slot = at[:width]
            # Padding slots point past the batch, each at its own row,
            # so the placement's indices stay sorted and unique.
            cold_pos = jnp.where(slot < jnp.sum(keep, dtype=jnp.int32),
                                 pos[:width], b + slot)
            cold_local = loc[:width]
            counts = jnp.stack([jnp.sum(hot, dtype=jnp.int32),
                                jnp.sum(cold, dtype=jnp.int32)])
        return jnp.where(hot, idx, -1), cold_pos, cold_local, counts

    def _stage_cold(self, local: np.ndarray, width: int):
        """``local``'s cold rows as a ``[width, d]`` device array: a host
        buffer written once (the slots behind the rows are never read:
        their position is out of range) and sent.  At the static width
        the buffer is the feature's own, allocated and touched once (a
        fresh 50 MB ``np.empty`` costs 60 ms of page faults a batch on
        the chip's host; scripts/tier_micro.py), so the transfer is
        waited for before the next round may write it."""
        mine = self._stage_buf is not None \
            and self._stage_buf.shape[0] == width
        with self._stage_lock if mine else contextlib.nullcontext():
            with _span("feature.cold_fetch"):
                buf = self._stage_buf if mine else np.empty(
                    (width, self._dim), self._cold_np_dtype)
                self._fetch_cold_into(local, buf[: local.shape[0]])
            with _span("feature.cold_put"):
                _M_COLD_SENT.inc(width)
                rows = jax.device_put(buf)
                if mine:
                    rows.block_until_ready()
                return rows

    def _merge_impl(self, hot, hot_idx, cold_pos, cold_rows):
        """Device merge: hot gather at hot slots + cold-row scatter."""
        from ..store import quant

        spec, dtype = self._quant, self.dtype
        with jax.named_scope("glt.gather.feat"):
            if hot.shape[0]:
                rows = jnp.take(hot, hot_idx, axis=0, mode="clip")
                if spec is not None:
                    rows = quant.dequantize(rows, spec)
                out = jnp.where((hot_idx >= 0)[:, None],
                                rows.astype(dtype), 0)
            else:
                # Fully host-resident (split_ratio == 0, e.g. a
                # shared-memory attach in a sampling worker).
                out = jnp.zeros((hot_idx.shape[0], cold_rows.shape[1]),
                                dtype)
        return self._patch_impl(out, cold_pos, cold_rows)

    @scoped("glt.gather.merge")
    def _patch_impl(self, out, cold_pos, cold_rows):
        """The rows the host sent, placed among ``out``'s (compressed
        rows cross the host link at storage width and widen here)."""
        if self._quant is not None:
            from ..store import quant

            cold_rows = quant.dequantize(cold_rows, self._quant)
        return out.at[cold_pos].set(
            cold_rows.astype(out.dtype), mode="drop",
            indices_are_sorted=True, unique_indices=True)

    def _merge_tiered(self, hot_idx, cold_pos, cold_rows):
        if self._merge_jit is None:
            self._merge_jit = jax.jit(self._merge_impl)
        return self._merge_jit(self._hot, hot_idx, cold_pos, cold_rows)

    def _patch_tiered(self, out, cold_pos, cold_rows):
        """A further round's cold rows placed into ``out`` (donated)."""
        if self._patch_jit is None:
            self._patch_jit = jax.jit(self._patch_impl, donate_argnums=0)
        return self._patch_jit(out, cold_pos, cold_rows)

    def warm_gather(self, num_ids: int) -> None:
        """Compile (or read from the cache) and run every program a
        tiered gather of ``num_ids`` ids can need at the static cold
        width, the further round's among them, so that a measured window
        or a served request never meets a compilation."""
        if self._cold_count == 0 or self._cold_width is None:
            return
        ids = jnp.full((int(num_ids),), -1, jnp.int32)
        plan = self.plan_gather(ids, offset=1)
        rows = self._stage_cold(np.zeros((0,), np.int32),
                                plan.cold_pos.shape[0])
        out = self._merge_tiered(plan.hot_idx, plan.cold_pos, rows)
        jax.block_until_ready(self._patch_tiered(out, plan.cold_pos, rows))

    def _gather_tiered_cached(self, ids_np):
        """Tiered gather with the HBM cold cache in front of the host.

        The ids are resolved on the host here; one device->host sync
        (the hit mask); the host stages only cache misses, and the merge
        program inserts them into the cache for the next batch (the
        previous cache buffers are donated in place).
        """
        ids_np = ids_np.astype(np.int64)
        valid = ids_np >= 0
        idx = np.where(valid, ids_np, 0)
        if self._id2index is not None:
            idx = self._host_id2index()[idx].astype(np.int64)
        is_hot = idx < self._hot_count
        hot_mask = valid & is_hot
        cold_mask = valid & ~is_hot
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
            idx = self._host_id2index()[idx]
        if self._host_full is not None:
            rows = self._host_full[idx]
        elif self._store is None:
            # Built from tiers: each row out of the tier that holds it.
            idx = np.asarray(idx, np.int64)
            hot = idx < self._hot_count
            rows = np.empty((idx.shape[0], self._dim), self._cold_np_dtype)
            rows[hot] = np.asarray(self._hot[idx[hot]])
            rows[~hot] = self._cold[idx[~hot] - self._hot_count]
        else:
            rows = self._store.read_rows(np.asarray(idx, np.int64))
            if self._quant is not None:
                from ..store import quant

                # Host decode mirrors the device formula; padding rows
                # re-zero below (decode(0) != 0 for int8).
                rows = quant.decode(rows, self._quant)
        rows = np.where(valid[:, None], rows, 0)
        return rows

    def __len__(self) -> int:
        return self._n

    def __repr__(self) -> str:
        return (f"Feature(shape={self.shape}, split_ratio={self.split_ratio},"
                f" hot={self._hot_count})")


def calibrate_cold_width(feature: Feature, node_batches, pct: float = 99.0,
                         margin: float = 1.05, multiple: int = 1024,
                         counts: Optional[np.ndarray] = None) -> int:
    """A static cold width for :meth:`Feature.set_cold_width`, sized as
    :func:`~glt_tpu.sampler.calibrate_node_capacity` sizes a node buffer:
    the ``pct`` percentile of the cold rows per batch over
    ``node_batches`` (node lists as the sampler returns them, ``-1``
    padded; or their ``counts``, :func:`cold_rows_of`), times ``margin``,
    rounded up to ``multiple`` rows.  A batch past it costs a further
    round, never a row."""
    if counts is None:
        counts = cold_rows_of(feature, node_batches)
    width = float(np.percentile(np.asarray(counts), pct)) * margin
    return max(int(np.ceil(width / multiple) * multiple), multiple)


def cold_rows_of(feature: Feature, node_batches) -> np.ndarray:
    """Cold rows per node list (one host fetch for all of them)."""
    counts = [feature.plan_gather(ids).counts[1] for ids in node_batches]
    return np.asarray(jax.device_get(jnp.stack(counts)))
