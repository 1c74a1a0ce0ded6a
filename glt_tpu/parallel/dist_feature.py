"""Distributed feature lookup: all-to-all row exchange inside shard_map.

TPU-native replacement for ``distributed/dist_feature.py:122-269``: the
reference masks ids through the feature partition book, gathers local rows
from the UnifiedTensor, issues per-remote-partition async RPCs
(``RpcFeatureLookupCallee``) and scatter-stitches responses into the output
buffer.  Here the whole lookup is one collective round-trip: bucket ids by
owner shard (a :func:`~glt_tpu.parallel.dist_sampler.build_routing` plan,
reusable across exchanges), ``all_to_all`` the id buckets, every shard
gathers its rows from HBM, ``all_to_all`` the row blocks back, unscatter.
:func:`exchange_gather_xy` fuses the feature AND label lookup of a
frontier into ONE such round-trip (labels bitcast into a float32 payload
column — bit-exact).  Payload rides ICI and overlaps with neighboring
compute under XLA's scheduler.

**Host tiering** (:class:`TieredShardedFeature`): when the feature matrix
exceeds mesh HBM (papers100M ≈ 200GB), each shard keeps only a hotness-
ordered prefix of its rows in HBM; the remainder stays in host DRAM.  The
reference reads its host tier through UVA from inside the gather kernel
(unified_tensor.cu:202-311); a TPU kernel cannot read host memory, so the
cold path is a **host-side pipeline stage**: the sampler's node list (known
after the sample stage) drives a numpy gather whose result is
``device_put`` while the previous batch trains — the
:class:`~glt_tpu.parallel.dist_train.TieredTrainPipeline` double-buffers
the two jitted stages so step time approaches
``max(device compute, host gather)``, the same overlap UVA bought the GPU.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scoped
from ..ops.fused_frontier import fused_frontier as _fused_frontier
from ..ops.unique import unique_first_occurrence
from .dist_sampler import (HierarchicalRouting, Routing, _topology_choice,
                           _use_fused, build_hier_routing, build_routing,
                           hier_requests, hier_response)


def _dedup_scatter_back(urows: jnp.ndarray, inv: jnp.ndarray) -> jnp.ndarray:
    """Expand unique-id rows back to every original position (-1 = pad)."""
    out = jnp.take(urows, jnp.clip(inv, 0, inv.shape[0] - 1), axis=0)
    return jnp.where((inv >= 0)[:, None], out, 0)


def _dedup_scatter_back_1d(uvals: jnp.ndarray, inv: jnp.ndarray
                           ) -> jnp.ndarray:
    """1-D analog of :func:`_dedup_scatter_back` (label columns)."""
    out = jnp.take(uvals, jnp.clip(inv, 0, inv.shape[0] - 1))
    return jnp.where(inv >= 0, out, 0)


@scoped("glt.gather.feat")
def _request_rows(rows: jnp.ndarray, local: jnp.ndarray, ok: jnp.ndarray,
                  fused_frontier: str) -> jnp.ndarray:
    """Serving-side row fetch of every exchange: rows for the id
    requests landed on this shard (zeros where ``ok`` is False).

    ``fused_frontier`` != 'off' serves the request block through the
    one-dispatch dedup+gather kernel — the request list repeats hub rows
    across requesting shards, and the fused path reads each distinct row
    from HBM once, out of VMEM thereafter.  Bit-identical to the naive
    take (valid ``local`` needs no clip; invalid positions are -1-masked
    into the kernel's padding path, which zeroes them exactly like the
    ``where``).
    """
    if fused_frontier != "off":
        return _fused_frontier(rows, jnp.where(ok, local, -1),
                               force=fused_frontier).features
    got = jnp.take(rows, jnp.where(ok, local, 0), axis=0, mode="clip")
    return jnp.where(ok[:, None], got, 0)


@scoped("glt.route.exchange")
def _exchange_ids(routing: Routing, num_shards: int, cap: int,
                  axis_name: str) -> jnp.ndarray:
    """The id request all-to-all of every exchange: row q of the result
    holds the ids shard q wants from us."""
    return lax.all_to_all(
        routing.buckets.reshape(num_shards, cap), axis_name, 0, 0,
        tiled=False).reshape(num_shards * cap)


def _resolve_plan(ids, nodes_per_shard, num_shards, axis_name, routing,
                  route, mesh_shape, hier_load_factor):
    """Shared plan prologue of every feature exchange: resolve the
    routing plan — flat :class:`Routing` or 2-D-mesh
    :class:`HierarchicalRouting`, building one when the caller didn't
    pass a shared plan — and run the id-request leg(s).

    Returns ``(routing, flat_plan, requests)``: ``requests`` is the id
    vector this shard must serve (``[S*b]`` flat, ``[H*hier_cap]``
    hier, where the hier DCN leg carries only the per-host-deduped
    ids), and ``flat_plan`` drives the shared unscatter epilogue (the
    hier response retraces its legs back into flat bucket order).
    """
    b = ids.shape[0]
    if routing is None:
        if _topology_choice(route, axis_name, mesh_shape) == "hier":
            routing = build_hier_routing(
                ids, nodes_per_shard, mesh_shape[0], mesh_shape[1],
                axis_name[0], axis_name[1],
                hier_load_factor=hier_load_factor, route=route)
        else:
            routing = build_routing(ids, nodes_per_shard, num_shards,
                                    route=route)
    if isinstance(routing, HierarchicalRouting):
        return routing, routing.base, hier_requests(routing)
    return routing, routing, _exchange_ids(routing, num_shards, b,
                                           axis_name)


def _return_payload(routing, payload, num_shards, b, axis_name):
    """Response leg of every feature exchange: per-request-slot payload
    back to the requesters, landing in flat bucket order
    ``[num_shards * b, w]`` (the hier path retraces DCN then ICI in
    reverse; dropped/padding slots come back as zero rows, exactly what
    the flat path's masked serve produces)."""
    w = payload.shape[-1]
    if isinstance(routing, HierarchicalRouting):
        return hier_response(routing, payload, 0)
    with jax.named_scope("glt.route.exchange"):
        return lax.all_to_all(
            payload.reshape(num_shards, b, w), axis_name, 0, 0,
            tiled=False).reshape(num_shards * b, w)


def exchange_gather(
    ids: jnp.ndarray,
    rows: jnp.ndarray,
    nodes_per_shard: int,
    num_shards: int,
    axis_name: str,
    dedup: bool = False,
    routing=None,
    route: str = "auto",
    fused_frontier: str = "off",
    mesh_shape: Optional[tuple] = None,
    hier_load_factor: Optional[float] = None,
) -> jnp.ndarray:
    """Gather feature rows for global ``ids`` across shards.

    Call inside ``shard_map``. Args:
      ids: ``[B]`` global node ids on this shard (-1 padded -> zero rows).
      rows: ``[nodes_per_shard, d]`` this shard's feature block.
      dedup: route UNIQUE ids through the exchange and scatter rows back
        to every original position — duplicated ids (un-deduped leaf
        hops, hub nodes) cross the ICI once instead of once per
        occurrence.  Output is bit-identical to ``dedup=False``.
      routing: pre-built plan for ``ids`` from
        :func:`~glt_tpu.parallel.dist_sampler.build_routing` (or
        :func:`~glt_tpu.parallel.dist_sampler.build_hier_routing` on a
        2-D mesh) — reuse ONE plan across the neighbor/feature/label
        exchanges of a frontier instead of re-bucketing per exchange.
        Ignored under ``dedup`` (the plan there is over the unique id
        list).
      fused_frontier: serving-side kernel seam (see
        :func:`_request_rows`); bit-identical either way.
      mesh_shape: static ``(num_hosts, chips_per_host)`` when
        ``axis_name`` is the 2-D mesh axis tuple — enables the
        hierarchical dedup-then-exchange topology (``route='hier'``).
      hier_load_factor: DCN buffer bound for the hier topology (see
        :func:`~glt_tpu.parallel.dist_sampler.hier_request_cap`).

    Returns: ``[B, d]`` rows in input order.
    """
    if dedup:
        uniq, inv, _ = unique_first_occurrence(ids)
        urows = exchange_gather(uniq, rows, nodes_per_shard, num_shards,
                                axis_name, route=route,
                                fused_frontier=fused_frontier,
                                mesh_shape=mesh_shape,
                                hier_load_factor=hier_load_factor)
        return _dedup_scatter_back(urows, inv)
    b = ids.shape[0]
    routing, flat_plan, requests = _resolve_plan(
        ids, nodes_per_shard, num_shards, axis_name, routing, route,
        mesh_shape, hier_load_factor)

    my_rank = lax.axis_index(axis_name)
    local = requests - my_rank * nodes_per_shard
    ok = (local >= 0) & (local < nodes_per_shard) & (requests >= 0)
    got = _request_rows(rows, local, ok, fused_frontier)

    resp = _return_payload(routing, got, num_shards, b, axis_name)
    with jax.named_scope("glt.route.payload"):
        out = resp[jnp.clip(flat_plan.slot, 0, num_shards * b - 1)]
        return jnp.where(flat_plan.valid[:, None], out, 0)


class TieredShardedFeature(NamedTuple):
    """Per-shard features split between HBM and host DRAM.

    ``hot``: ``[S, hot_per_shard, d]`` device array (shard axis placed on
    the mesh by ``put_sharded``); ``cold``: ``[S, c - hot_per_shard, d]``
    host numpy.  Row ``r`` of shard ``s`` holds global (relabeled) id
    ``s * c + r`` — use hotness-ordered
    :func:`~glt_tpu.partition.contiguous.contiguous_relabel` so the prefix
    really is the hot set (the ``cat_feature_cache``/``sort_by_in_degree``
    role, reference data/reorder.py:18, partition/base.py:606).
    """
    hot: jnp.ndarray
    cold: np.ndarray
    nodes_per_shard: int
    hot_per_shard: int
    num_shards: int

    @property
    def dim(self) -> int:
        return self.hot.shape[-1]


def shard_feature_tiered(feature: np.ndarray, num_shards: int,
                         hot_ratio: float, dtype=None
                         ) -> TieredShardedFeature:
    """Split ``[N, d]`` rows into per-shard HBM prefix + host remainder."""
    feature = np.asarray(feature)
    n, d = feature.shape
    c = -(-n // num_shards)
    # At least one hot row per shard: downstream exchange_gather_hot and
    # make_tiered_train_step derive shapes/dtype from the hot array, and a
    # [S, 0, d] hot tier would make jnp.take fail inside shard_map.
    h = min(c, max(1, int(round(c * float(hot_ratio)))))
    hot = np.zeros((num_shards, h, d), feature.dtype)
    cold = np.zeros((num_shards, c - h, d), feature.dtype)
    for s in range(num_shards):
        lo, hi = min(s * c, n), min((s + 1) * c, n)
        blk = feature[lo:hi]
        hot[s, : min(h, hi - lo)] = blk[:h]
        if hi - lo > h:
            cold[s, : hi - lo - h] = blk[h:]
    arr = jnp.asarray(hot) if dtype is None else jnp.asarray(hot, dtype)
    return TieredShardedFeature(hot=arr, cold=cold, nodes_per_shard=c,
                                hot_per_shard=h, num_shards=num_shards)


def shard_feature_tiered_from_store(store, num_shards: int,
                                    hot_ratio: float, dtype=None
                                    ) -> TieredShardedFeature:
    """Third-tier constructor (glt_tpu.store, docs/storage.md): hot
    prefixes load straight off a shard-major
    :class:`~glt_tpu.store.disk.DiskFeatureStore`; the cold remainder
    STAYS on disk.

    The store holds the full ``[num_shards * nodes_per_shard, d]``
    matrix in the :class:`TieredShardedFeature` id layout (shard ``s``
    row ``r`` at global row ``s * c + r``), so the same file backs both
    the hot loads here and a
    :class:`~glt_tpu.store.stager.DiskColdStore` — which you MUST pass
    as the pipeline's ``cold_store`` (the returned ``cold`` field is a
    zero-row placeholder; :class:`~glt_tpu.parallel.dist_train.
    TieredTrainPipeline` refuses to default it to a
    :class:`HostColdStore`).
    """
    if store.num_rows % num_shards:
        raise ValueError(
            f"store rows {store.num_rows} not divisible by {num_shards} "
            f"shards — pad the matrix to the shard grid before writing")
    c = store.num_rows // num_shards
    h = min(c, max(1, int(round(c * float(hot_ratio)))))
    hot = np.empty((num_shards, h, store.dim), store.dtype)
    for s in range(num_shards):
        hot[s] = store.read_rows(
            np.arange(s * c, s * c + h, dtype=np.int64))
    arr = jnp.asarray(hot) if dtype is None else jnp.asarray(hot, dtype)
    cold = np.zeros((num_shards, 0, store.dim), store.dtype)
    return TieredShardedFeature(hot=arr, cold=cold, nodes_per_shard=c,
                                hot_per_shard=h, num_shards=num_shards)


def exchange_gather_hot(
    ids: jnp.ndarray,
    hot_rows: jnp.ndarray,
    nodes_per_shard: int,
    hot_per_shard: int,
    num_shards: int,
    axis_name: str,
    staged_resp: Optional[jnp.ndarray] = None,
    staged_rows: Optional[jnp.ndarray] = None,
    staged_slots: Optional[jnp.ndarray] = None,
    dedup: bool = False,
    routing=None,
    route: str = "auto",
    mesh_shape: Optional[tuple] = None,
    hier_load_factor: Optional[float] = None,
) -> jnp.ndarray:
    """Tiered gather; call inside ``shard_map``.

    Same collective round-trip as :func:`exchange_gather`, but the serving
    shard answers hot requests (``local < hot_per_shard``) from HBM and
    cold requests from host-staged rows (produced by
    :func:`route_cold_requests` + :meth:`HostColdStore.serve`).  Because
    every shard serves only rows it owns, each pod host stages only its
    own shards' cold rows — the multi-host seam the reference's
    UnifiedTensor UVA reads provided on a single node
    (unified_tensor.cu:202-311).

    Two staged forms:
      * **compact** (preferred): ``staged_rows`` ``[cold_cap, d]`` +
        ``staged_slots`` ``[cold_cap]`` request-slot indices (-1 pad),
        scattered into the response — host->device bytes scale with the
        actual cold traffic, not the worst-case request matrix
        (:func:`compact_cold_requests`);
      * **dense** (legacy): ``staged_resp`` ``[num_shards * b, d]``, one
        row per request slot.

    Without either, cold rows come back as zeros (fill them via the
    legacy :func:`merge_cold` overlay).

    ``dedup`` routes unique ids only (see :func:`exchange_gather`); the
    staged cold rows must then come from a :func:`route_cold_requests`
    call made with the SAME ``dedup`` flag — and, on a 2-D mesh, the
    same topology (``route``/``mesh_shape``) — or slot indices won't
    line up with the (possibly host-deduped) request layout.
    """
    if dedup:
        uniq, inv, _ = unique_first_occurrence(ids)
        urows = exchange_gather_hot(
            uniq, hot_rows, nodes_per_shard, hot_per_shard, num_shards,
            axis_name, staged_resp=staged_resp, staged_rows=staged_rows,
            staged_slots=staged_slots, route=route,
            mesh_shape=mesh_shape, hier_load_factor=hier_load_factor)
        return _dedup_scatter_back(urows, inv)
    b = ids.shape[0]
    routing, flat_plan, requests = _resolve_plan(
        ids, nodes_per_shard, num_shards, axis_name, routing, route,
        mesh_shape, hier_load_factor)

    my_rank = lax.axis_index(axis_name)
    local = requests - my_rank * nodes_per_shard
    ok = (local >= 0) & (local < hot_per_shard) & (requests >= 0)
    got = jnp.take(hot_rows, jnp.where(ok, local, 0), axis=0, mode="clip")
    if staged_rows is not None:
        # Compact scatter: cold slots are disjoint from hot slots; -1
        # pad slots are dropped as out-of-bounds (no copy, no trash row).
        got = jnp.where(ok[:, None], got, 0)
        idx = jnp.where(staged_slots >= 0, staged_slots, got.shape[0])
        got = got.at[idx].set(staged_rows.astype(got.dtype), mode="drop")
    elif staged_resp is None:
        got = jnp.where(ok[:, None], got, 0)
    else:
        # Hot slots from HBM, cold slots from the staged host rows
        # (disjoint by construction; padding slots are zero either way).
        got = jnp.where(ok[:, None], got, staged_resp.astype(got.dtype))

    resp = _return_payload(routing, got, num_shards, b, axis_name)
    out = resp[jnp.clip(flat_plan.slot, 0, num_shards * b - 1)]
    return jnp.where(flat_plan.valid[:, None], out, 0)


def exchange_gather_xy(
    ids: jnp.ndarray,
    rows: jnp.ndarray,
    labels_col: jnp.ndarray,
    nodes_per_shard: int,
    num_shards: int,
    axis_name: str,
    hot_per_shard: Optional[int] = None,
    staged_rows: Optional[jnp.ndarray] = None,
    staged_slots: Optional[jnp.ndarray] = None,
    dedup: bool = False,
    routing=None,
    route: str = "auto",
    fused: Optional[bool] = None,
    fused_frontier: str = "off",
    mesh_shape: Optional[tuple] = None,
    hier_load_factor: Optional[float] = None,
):
    """Feature AND label gather for one frontier in a single exchange.

    Call inside ``shard_map``.  The pre-fusion train step ran this as two
    (or, tiered, three) independent exchanges over the SAME ids — each
    rebuilding the identical routing plan and launching its own id +
    payload collectives.  Here one :func:`build_routing` plan, one id
    all-to-all, and one fused payload all-to-all carry both: the serving
    shard's int32 label column is **bitcast** to a float32 payload column
    and concatenated onto the feature rows (pure data movement end to
    end, so the round trip is bit-exact for ANY label value), then split
    and bitcast back on the requester.  Halves the collective launches of
    the gather stage and removes two redundant routing prologues.

    Args:
      ids: ``[B]`` global node ids (-1 padded -> zero rows/labels).
      rows: ``[nodes_per_shard, d]`` (full) or hot-prefix feature block.
      labels_col: ``[nodes_per_shard]`` this shard's label column.
      hot_per_shard: tiered serving bound — requests past it take staged
        cold rows (see :func:`exchange_gather_hot`); None = full HBM.
      staged_rows / staged_slots: compact cold staging, as
        :func:`exchange_gather_hot`.
      dedup: unique ids ride the exchange once; scatter-back is
        bit-identical (see :func:`exchange_gather`).
      fused: collective-fusion seam; the split fallback still shares the
        routing plan and id collective, paying one extra payload launch.
        Value-fusion also requires a float32 feature block (the bitcast
        target); other dtypes silently take the shared-routing split.
      fused_frontier: serving-side kernel seam for the feature-row fetch
        (see :func:`_request_rows`); bit-identical either way.
      mesh_shape / hier_load_factor: 2-D mesh hierarchical-topology
        knobs (see :func:`exchange_gather`).  The fused x+y payload
        rides the hier legs as one block, so the feature+label lookup
        stays a single round trip on both topologies.

    Returns:
      ``(x [B, d], y [B] int32)`` in input order (zeros at invalid
      slots, exactly like the separate exchanges).
    """
    if dedup:
        uniq, inv, _ = unique_first_occurrence(ids)
        ux, uy = exchange_gather_xy(
            uniq, rows, labels_col, nodes_per_shard, num_shards,
            axis_name, hot_per_shard=hot_per_shard,
            staged_rows=staged_rows, staged_slots=staged_slots,
            route=route, fused=fused, fused_frontier=fused_frontier,
            mesh_shape=mesh_shape, hier_load_factor=hier_load_factor)
        return _dedup_scatter_back(ux, inv), _dedup_scatter_back_1d(uy, inv)

    b = ids.shape[0]
    d = rows.shape[-1]
    routing, flat_plan, requests = _resolve_plan(
        ids, nodes_per_shard, num_shards, axis_name, routing, route,
        mesh_shape, hier_load_factor)

    my_rank = lax.axis_index(axis_name)
    local = requests - my_rank * nodes_per_shard
    h = nodes_per_shard if hot_per_shard is None else int(hot_per_shard)
    okx = (local >= 0) & (local < h) & (requests >= 0)
    oky = (local >= 0) & (local < nodes_per_shard) & (requests >= 0)
    gotx = _request_rows(rows, local, okx, fused_frontier)
    if staged_rows is not None:
        idx = jnp.where(staged_slots >= 0, staged_slots, gotx.shape[0])
        gotx = gotx.at[idx].set(staged_rows.astype(gotx.dtype),
                                mode="drop")
    with jax.named_scope("glt.gather.label"):
        goty = jnp.take(labels_col.astype(jnp.int32),
                        jnp.where(oky, local, 0), mode="clip")
        goty = jnp.where(oky, goty, 0)

    if _use_fused(fused) and rows.dtype == jnp.float32:
        with jax.named_scope("glt.route.payload"):
            ybits = lax.bitcast_convert_type(goty, jnp.float32)[:, None]
            packed = jnp.concatenate([gotx, ybits], axis=-1)
        resp = _return_payload(routing, packed, num_shards, b, axis_name)
        with jax.named_scope("glt.route.payload"):
            respx = resp[:, :d]
            respy = lax.bitcast_convert_type(resp[:, d], jnp.int32)
    else:
        respx = _return_payload(routing, gotx, num_shards, b, axis_name)
        respy = _return_payload(routing, goty[:, None], num_shards, b,
                                axis_name)[:, 0]

    with jax.named_scope("glt.route.payload"):
        slot = jnp.clip(flat_plan.slot, 0, num_shards * b - 1)
        x = jnp.where(flat_plan.valid[:, None], respx[slot], 0)
        y = jnp.where(flat_plan.valid, respy[slot], 0)
    return x, y


def compact_cold_requests(cold_req: jnp.ndarray, cold_cap: int):
    """Compress a responder-side cold-request vector to ``cold_cap`` slots.

    ``cold_req``: ``[R]`` local cold row ids from
    :func:`route_cold_requests` (-1 = not cold).  Returns ``(slots, ids,
    dropped)``: request-slot indices and local cold ids (``[cold_cap]``,
    -1 padded) plus the count of cold requests past the cap (served as
    zero rows — monitor and raise ``cold_cap`` if ever nonzero).  The
    host then gathers ``ids`` only: staged host->device bytes drop from
    the dense ``R = num_shards * node_cap`` rows to ``cold_cap`` (the
    capacity-bounding trick of the sampler exchange applied to the
    feature tier).
    """
    is_cold = cold_req >= 0
    order = jnp.argsort(~is_cold, stable=True)   # cold slots first
    slots = order[:cold_cap].astype(jnp.int32)
    ids = cold_req[slots]
    slots = jnp.where(ids >= 0, slots, -1)
    dropped = jnp.maximum(
        jnp.sum(is_cold.astype(jnp.int32)) - cold_cap, 0)
    return slots, ids, dropped


def route_cold_requests(
    ids: jnp.ndarray,
    nodes_per_shard: int,
    hot_per_shard: int,
    num_shards: int,
    axis_name: str,
    dedup: bool = False,
    routing=None,
    route: str = "auto",
    mesh_shape: Optional[tuple] = None,
    hier_load_factor: Optional[float] = None,
) -> jnp.ndarray:
    """Responder-side cold request slots; call inside ``shard_map``.

    Runs the SAME deterministic bucketing + id exchange as
    :func:`exchange_gather_hot` and returns, for this shard, the local
    cold row index (``0..c-h``) of every incoming request slot, or -1
    for hot/foreign/padding slots: ``[num_shards * b]`` on the flat
    topology, ``[num_hosts * hier_cap]`` on the hierarchical one (the
    request layout follows the topology).  The host then gathers
    exactly these rows from its local cold store — no host ever touches
    another host's rows.  Pass the same ``dedup`` flag — and, on a 2-D
    mesh, the same ``route``/``mesh_shape``/``hier_load_factor`` — as
    the paired :func:`exchange_gather_hot` call so both resolve the
    identical request layout.
    """
    if dedup:
        ids = unique_first_occurrence(ids).uniques
        routing = None   # the shared plan is over the un-deduped list
    routing, _, requests = _resolve_plan(
        ids, nodes_per_shard, num_shards, axis_name, routing, route,
        mesh_shape, hier_load_factor)
    my_rank = lax.axis_index(axis_name)
    local = requests - my_rank * nodes_per_shard
    is_cold = (requests >= 0) & (local >= hot_per_shard) & (
        local < nodes_per_shard)
    return jnp.where(is_cold, local - hot_per_shard, -1)


class HostColdStore:
    """Cold rows for the shards one host owns (all shards by default).

    On a multi-host pod each process builds
    ``HostColdStore(f, shard_ids=<its local shards>)`` and serves only
    those; the single-process emulation holds every shard.  The staged
    response for shard ``s`` depends only on shard ``s``'s store, so
    per-host ``device_put`` placement is naturally correct.
    """

    def __init__(self, f: TieredShardedFeature, shard_ids=None):
        self.shard_ids = (tuple(range(f.num_shards)) if shard_ids is None
                          else tuple(shard_ids))
        self._blocks = {s: np.asarray(f.cold[s]) for s in self.shard_ids}
        self.dim = f.cold.shape[-1]
        self.dtype = f.cold.dtype

    def serve(self, shard: int, cold_req: np.ndarray) -> np.ndarray:
        """Rows for one shard's request slots.

        Args:
          cold_req: ``[R]`` local cold row ids from
            :func:`route_cold_requests` (-1 = not a cold row of ours).
        Returns ``[R, d]`` with zeros at -1 slots.
        """
        cold_req = np.asarray(cold_req)
        out = np.zeros((cold_req.shape[0], self.dim), self.dtype)
        self.serve_into(out, shard, cold_req)
        return out

    def serve_into(self, out: np.ndarray, shard: int, cold_req: np.ndarray,
                   pool=None, row_chunk: int = 16384) -> list:
        """Gather one shard's cold rows into ``out`` (``[R, d]``), row-chunk
        parallel.

        With ``pool`` (a ThreadPoolExecutor) the gather splits into
        ``row_chunk``-row work items and returns their futures (caller
        awaits); numpy fancy indexing releases the GIL during the copy,
        so chunks scale across host cores — the thread-level rebuild of
        the warp-parallel UVA gather (unified_tensor.cu:48-81).  Without
        a pool the gather runs inline and returns ``[]``.
        """
        if shard not in self._blocks:
            raise KeyError(
                f"shard {shard} is not local to this host "
                f"(local: {self.shard_ids})")
        blk = self._blocks[shard]
        cold_req = np.asarray(cold_req)
        sel = np.where(cold_req >= 0)[0]
        if blk.shape[0] == 0 or sel.size == 0:
            return []

        def work(lo, hi):
            idx = sel[lo:hi]
            out[idx] = blk[cold_req[idx]]

        if pool is None:
            work(0, sel.size)
            return []
        return [pool.submit(work, lo, min(lo + row_chunk, sel.size))
                for lo in range(0, sel.size, row_chunk)]


def cold_mask(ids: jnp.ndarray, nodes_per_shard: int,
              hot_per_shard: int) -> jnp.ndarray:
    """True where ``ids`` resolve to the host tier (jit-safe)."""
    return (ids >= 0) & (ids % nodes_per_shard >= hot_per_shard)


def merge_cold(hot_x: jnp.ndarray, staged_cold: jnp.ndarray,
               ids: jnp.ndarray, nodes_per_shard: int,
               hot_per_shard: int) -> jnp.ndarray:
    """Overlay staged cold rows onto the hot-tier gather result."""
    m = cold_mask(ids, nodes_per_shard, hot_per_shard)
    return jnp.where(m[:, None], staged_cold.astype(hot_x.dtype), hot_x)


def cold_gather_host(f: TieredShardedFeature,
                     nodes: np.ndarray) -> np.ndarray:
    """Host-side gather of the cold rows for per-shard node lists.

    Args:
      nodes: ``[S, cap]`` global (relabeled) ids, -1 padded — the sample
        stage's ``out.node``.

    Returns ``[S, cap, d]`` host array with zeros at hot/padding slots.
    On a multi-host pod each host only holds its own shards' cold rows;
    this single-process build holds all of them (the emulation mirrors the
    reference's single-host multi-GPU tests, SURVEY §4).
    """
    nodes = np.asarray(nodes)
    s_axis, cap = nodes.shape
    c, h = f.nodes_per_shard, f.hot_per_shard
    d = f.cold.shape[-1]
    out = np.zeros((s_axis, cap, d), f.cold.dtype)
    if f.cold.shape[1] == 0:
        return out
    flat = nodes.reshape(-1)
    is_cold = (flat >= 0) & (flat % c >= h)
    # Gather only the cold slots (typically a minority of the batch):
    # the host stage bounds pipelined step time, so no wasted rows.
    cold_flat = flat[is_cold]
    out.reshape(-1, d)[is_cold] = f.cold[cold_flat // c, cold_flat % c - h]
    return out
