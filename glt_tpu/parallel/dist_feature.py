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
frontier into ONE such round-trip (one plan, one id collective, one
served read).  Payload rides ICI and overlaps with neighboring compute
under XLA's scheduler.

**The served read** (:func:`_request_rows`, one for all three
exchanges).  The request matrix a shard serves is ``S`` requesters'
buckets, each a prefix of live ids then padding: in the dist cell about
a tenth of its 3.75 M slots hold a node.  The read of the feature rows
and the label column visits only the chunks of ``CHUNK_ROWS`` slots that
hold a request of ours, the chunk rule of
:mod:`~glt_tpu.ops.neighbor_sample` with a trip count known at run time;
the response is the whole take's bit for bit, and how much it read is
counted (``glt.gather.served_rows`` / ``glt.gather.read_rows``).

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

import functools
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scoped
from ..ops import neighbor_sample as _ns
from ..ops.fused_frontier import fused_frontier as _fused_frontier
from ..ops.unique import unique_first_occurrence
from .dist_sampler import (HierarchicalRouting, Routing, _topology_choice,
                           build_hier_routing, build_routing, hier_requests,
                           hier_response)


def _dedup_scatter_back(urows: jnp.ndarray, inv: jnp.ndarray) -> jnp.ndarray:
    """Expand unique-id rows back to every original position (-1 = pad)."""
    out = jnp.take(urows, jnp.clip(inv, 0, inv.shape[0] - 1), axis=0)
    return jnp.where((inv >= 0)[:, None], out, 0)


def _dedup_scatter_back_1d(uvals: jnp.ndarray, inv: jnp.ndarray
                           ) -> jnp.ndarray:
    """1-D analog of :func:`_dedup_scatter_back` (label columns)."""
    out = jnp.take(uvals, jnp.clip(inv, 0, inv.shape[0] - 1))
    return jnp.where(inv >= 0, out, 0)


def _take_served(table, local, ok, scope):
    """``table``'s rows at ``local``, zeros where ``ok`` is False."""
    with jax.named_scope(scope):
        got = jnp.take(table, jnp.where(ok, local, 0), axis=0, mode="clip")
        return jnp.where(ok.reshape(ok.shape + (1,) * (got.ndim - 1)),
                         got, 0)


def _request_rows(local: jnp.ndarray, reads, fused_frontier: str = "off"):
    """Serving-side read of every exchange: the rows the id requests
    landed on this shard ask for.  ``reads`` is ``((table, ok, scope),
    ...)``, the feature rows first: each table's rows at ``local`` come
    back ``[R, ...]`` with zeros where its ``ok`` is False, read under its
    own scope.  Returns ``(blocks, counts)``; ``counts`` is ``int32[2]``:
    the request slots that hold a row of ours, and the slots the read
    visited (the counters ``glt.gather.served_rows`` / ``.read_rows``).

    The request matrix is ``S`` prefixes, one a requester, and most of it
    is padding (about 10 % live in the dist cell).  Wider than one chunk,
    the read runs :data:`~glt_tpu.ops.neighbor_sample.CHUNK_ROWS` slots
    at a time over only the chunks in which some slot holds a request of
    ours, a trip count known at run time (the chunk rule of
    :mod:`~glt_tpu.ops.neighbor_sample`): a skipped slot stays the zero it
    would have been made, a read chunk reads what the whole take read, so
    the blocks are the whole take's bit for bit.  Every table rides the
    one loop; its reads and zero fill keep the table's scope, the loop's
    stores into the blocks carry none.  At most one chunk wide it is the
    one take it always was.

    ``fused_frontier`` != 'off' serves the feature rows through the
    one-dispatch dedup+gather kernel — the request list repeats hub rows
    across requesting shards, and the fused path reads each distinct row
    from HBM once, out of VMEM thereafter; it visits every slot.
    Bit-identical to the take (invalid positions are -1-masked into the
    kernel's padding path, which zeroes them exactly like the ``where``).
    """
    width, chunk = local.shape[0], _ns.CHUNK_ROWS
    with jax.named_scope("glt.gather.feat"):
        live = functools.reduce(jnp.logical_or, [ok for _, ok, _ in reads])
        ids = jnp.where(live, local, -1)
        first = ()
        if fused_frontier != "off":
            rows, ok, _ = reads[0]
            first = (_fused_frontier(rows, jnp.where(ok, local, -1),
                                     force=fused_frontier).features,)
            reads = reads[1:]
            visited = jnp.full((), width, jnp.int32)
        else:
            visited = _ns.read_rows(ids)
        counts = jnp.stack([jnp.sum(live.astype(jnp.int32)), visited])
    if width <= chunk or not reads:
        return first + tuple(_take_served(t, local, ok, scope)
                             for t, ok, scope in reads), counts

    def read(off):
        at = lax.dynamic_slice_in_dim(local, off, chunk)
        return tuple(
            _take_served(t, at, lax.dynamic_slice_in_dim(ok, off, chunk),
                         scope)
            for t, ok, scope in reads)

    with jax.named_scope("glt.gather.feat"):
        order, n = _ns._live_chunks(ids)
    fill = []
    for t, _, scope in reads:
        with jax.named_scope(scope):
            fill.append(jnp.zeros((width,) + t.shape[1:], t.dtype))
    return first + _ns._read_live_chunks(order, n, width, 1, read,
                                         tuple(fill)), counts


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
    return _exchange(ids, rows, None, nodes_per_shard, num_shards,
                     axis_name, dedup=dedup, routing=routing, route=route,
                     fused_frontier=fused_frontier, mesh_shape=mesh_shape,
                     hier_load_factor=hier_load_factor)[0]


def _exchange(ids, rows, labels_col, nodes_per_shard, num_shards, axis_name,
              hot_per_shard=None, staged_rows=None, staged_slots=None,
              staged_resp=None, dedup=False, routing=None, route="auto",
              fused_frontier="off", mesh_shape=None, hier_load_factor=None):
    """The one round trip behind :func:`exchange_gather`,
    :func:`exchange_gather_hot` and :func:`exchange_gather_xy` (their
    arguments; ``labels_col`` None: rows only).  Returns ``(x, y, counts)``:
    ``y`` None without labels, ``counts`` the serving shard's
    :func:`_request_rows` counts (of the unique ids' exchange under
    ``dedup``)."""
    if dedup:
        uniq, inv, _ = unique_first_occurrence(ids)
        ux, uy, counts = _exchange(
            uniq, rows, labels_col, nodes_per_shard, num_shards, axis_name,
            hot_per_shard=hot_per_shard, staged_rows=staged_rows,
            staged_slots=staged_slots, staged_resp=staged_resp, route=route,
            fused_frontier=fused_frontier, mesh_shape=mesh_shape,
            hier_load_factor=hier_load_factor)
        return (_dedup_scatter_back(ux, inv),
                None if uy is None else _dedup_scatter_back_1d(uy, inv),
                counts)

    b = ids.shape[0]
    routing, flat_plan, requests = _resolve_plan(
        ids, nodes_per_shard, num_shards, axis_name, routing, route,
        mesh_shape, hier_load_factor)

    my_rank = lax.axis_index(axis_name)
    local = requests - my_rank * nodes_per_shard
    h = nodes_per_shard if hot_per_shard is None else int(hot_per_shard)
    okx = (local >= 0) & (local < h) & (requests >= 0)
    reads = [(rows, okx, "glt.gather.feat")]
    if labels_col is not None:
        oky = (local >= 0) & (local < nodes_per_shard) & (requests >= 0)
        with jax.named_scope("glt.gather.label"):
            reads.append((labels_col.astype(jnp.int32), oky,
                          "glt.gather.label"))
    (gotx, *goty), counts = _request_rows(local, reads, fused_frontier)
    if staged_rows is not None:
        # Compact scatter: cold slots are disjoint from hot slots; -1
        # pad slots are dropped as out-of-bounds (no copy, no trash row).
        idx = jnp.where(staged_slots >= 0, staged_slots, gotx.shape[0])
        gotx = gotx.at[idx].set(staged_rows.astype(gotx.dtype),
                                mode="drop")
    elif staged_resp is not None:
        # Hot slots from HBM, cold slots from the staged host rows
        # (disjoint by construction; padding slots are zero either way).
        gotx = jnp.where(okx[:, None], gotx, staged_resp.astype(gotx.dtype))

    # The labels ride a payload collective of their own, as int32: no
    # bitcast, and no copy of the rows to pack them into.
    respx = _return_payload(routing, gotx, num_shards, b, axis_name)
    respy = None if not goty else _return_payload(
        routing, goty[0][:, None], num_shards, b, axis_name)[:, 0]

    with jax.named_scope("glt.route.payload"):
        slot = jnp.clip(flat_plan.slot, 0, num_shards * b - 1)
        x = jnp.where(flat_plan.valid[:, None], respx[slot], 0)
        y = None if respy is None else jnp.where(flat_plan.valid,
                                                 respy[slot], 0)
    return x, y, counts


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
    return _exchange(ids, hot_rows, None, nodes_per_shard, num_shards,
                     axis_name, hot_per_shard=hot_per_shard,
                     staged_rows=staged_rows, staged_slots=staged_slots,
                     staged_resp=staged_resp, dedup=dedup, routing=routing,
                     route=route, mesh_shape=mesh_shape,
                     hier_load_factor=hier_load_factor)[0]


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
    fused_frontier: str = "off",
    mesh_shape: Optional[tuple] = None,
    hier_load_factor: Optional[float] = None,
):
    """Feature AND label gather for one frontier in a single exchange.

    Call inside ``shard_map``.  The pre-fusion train step ran this as two
    (or, tiered, three) independent exchanges over the SAME ids — each
    rebuilding the identical routing plan and launching its own id +
    payload collectives.  Here one :func:`build_routing` plan, one id
    all-to-all, and the served read's one loop carry both; the response
    leaves as two payload collectives on either topology, the rows' and
    the int32 labels'.  Removes two redundant routing prologues.

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
      fused_frontier: serving-side kernel seam for the feature-row fetch
        (see :func:`_request_rows`); bit-identical either way.
      mesh_shape / hier_load_factor: 2-D mesh hierarchical-topology
        knobs (see :func:`exchange_gather`).

    Returns:
      ``(x [B, d], y [B] int32)`` in input order (zeros at invalid
      slots, exactly like the separate exchanges).
    """
    x, y, _ = _exchange(
        ids, rows, labels_col, nodes_per_shard, num_shards, axis_name,
        hot_per_shard=hot_per_shard, staged_rows=staged_rows,
        staged_slots=staged_slots, dedup=dedup, routing=routing, route=route,
        fused_frontier=fused_frontier, mesh_shape=mesh_shape,
        hier_load_factor=hier_load_factor)
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
