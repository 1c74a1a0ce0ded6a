"""Distributed neighbor sampling: all-to-all id exchange inside shard_map.

TPU-native replacement for the reference's distributed sampling engine
(distributed/dist_neighbor_sampler.py:542-598): there, each hop partitions
seed ids by the partition book, samples locally, RPC-fans-out remote ids to
owner workers, awaits, and stitches results back into seed order with a CUDA
kernel (stitch_sample_results.cu).  Here the same dataflow is **three
collectives inside one jitted shard_map program**:

  1. bucket seeds by owner shard (sort-based, static capacity);
  2. ``lax.all_to_all`` the request buckets;
  3. every shard samples its requests from its local CSR block;
  4. ``lax.all_to_all`` the neighbor/edge blocks back;
  5. unscatter into original seed order (the stitch, now a pure gather).

No RPC, no event loop, no serialization: the exchange rides ICI, and the
multi-hop loop + dedup runs per shard exactly like the single-device
sampler.  Each device doubles as a trainer (the reference's
worker-mode collocated layout, dist_loader.py:142-186).
"""
from __future__ import annotations

import os
import time
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import metrics as _metrics
from ..obs.scopes import scoped
from ..obs.trace import span as _span
from ..ops.neighbor_sample import (_row_offsets_and_degrees, read_rows,
                                   sample_neighbors)
from ..ops.unique import (
    dense_map_fits,
    induce,
    induce_init,
    record_sorted_slots,
    relabel_by_reference,
    sorted_slots,
    unique_first_occurrence,
)
from ..sampler.base import (NegativeSampling, SamplerOutput, live_counters,
                            live_counts)
from ..sampler.neighbor_sampler import (hop_bounds, hop_widths,
                                        max_sampled_nodes)
from ..typing import PADDING_ID

# Host-boundary instrumentation; the shard_map program itself is traced
# code and stays span-free (gltlint GLT010).
_M_DIST_BATCHES = _metrics.counter(
    "glt.dist.sample_batches", "distributed sample programs dispatched")
_M_DIST_SAMPLE_MS = _metrics.histogram(
    "glt.dist.sample_dispatch_ms",
    "dist sampler shard_map dispatch wall per batch")
_M_ROUTE_AUTOTUNE = _metrics.counter(
    "glt.dist.route_autotune_runs", "routing A/B warmups",
)


def bounded_remote_cap(width: int, load_factor: float,
                       num_shards: int) -> int:
    """Per-owner request-bucket capacity for the bounded exchange:
    ``ceil(load_factor * width / num_shards)``, clamped to ``[1, width]``."""
    return min(width,
               max(1, -(-int(round(load_factor * width)) // num_shards)))


def resolve_mesh_axes(mesh: Mesh, axis_name=None):
    """Resolve a sampler/step ``axis_name`` argument against its mesh:
    ``None`` derives the mesh's own axes (the axis name for a 1-D mesh,
    the full name tuple for a 2-D ``(host, chip)`` mesh); an explicit
    value passes through untouched (backward compat)."""
    if axis_name is not None:
        return axis_name
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else names


def mesh_axis_sizes(mesh: Mesh, axis_name):
    """``(num_hosts, chips_per_host)`` for a 2-D axis tuple, else None
    (1-D meshes have no topology choice to parameterize)."""
    if isinstance(axis_name, str):
        return None
    return tuple(int(mesh.shape[a]) for a in axis_name)


class Routing(NamedTuple):
    """Owner-bucketed routing plan for one frontier (see
    :func:`build_routing`): everything an exchange needs to scatter ids
    into per-owner request buckets and unscatter the responses.  Build it
    ONCE per hop frontier and thread it through every exchange over that
    frontier (neighbors, features, labels) — the plan depends only on
    ``(ids, nodes_per_shard, num_shards, cap)``, not on the payload.
    """
    buckets: jnp.ndarray   # [S * cap] ids grouped by owner, -1 padded
    slot: jnp.ndarray      # [B] bucket slot each input id landed in
    valid: jnp.ndarray     # [B] input validity (overflowed ids excluded)
    dropped: jnp.ndarray   # [] int32: ids beyond an owner's cap


# Backward-compat alias (pre-routing-layer name).
_Routing = Routing

# Decision table for route='auto': (b, num_shards, cap) -> 'onepass' |
# 'sort', filled by autotune_routing at warmup.  Without an entry the
# heuristic prefers the one-pass cumulative-mask path up to
# _ONEPASS_MAX_SHARDS (its [B, S] rank matrix is O(B*S) elementwise work
# vs the sort's O(B log B) — a clear win at small shard counts, a wash
# and then a loss as S grows past the sort's log factor).
_ROUTE_AUTO: dict = {}
_ONEPASS_MAX_SHARDS = 16


def _route_choice(b: int, num_shards: int, cap: int, route: str) -> str:
    """Resolve the bucketing implementation at trace time.

    Priority: ``GLT_ROUTE_FORCE`` env var > explicit ``route`` argument >
    autotuned decision table > shard-count heuristic — the same seam
    shape as ``gather_rows(force=)``/``GLT_GATHER_FORCE``.
    """
    env = os.environ.get("GLT_ROUTE_FORCE")
    if env in ("sort", "onepass"):
        return env
    if route in ("sort", "onepass"):
        return route
    hit = _ROUTE_AUTO.get((int(b), int(num_shards), int(cap)))
    if hit is not None:
        return hit
    return "onepass" if num_shards <= _ONEPASS_MAX_SHARDS else "sort"


def _use_fused(fused: Optional[bool]) -> bool:
    """Resolve the collective-fusion seam at trace time (default: fused).

    ``GLT_COLLECTIVE_FORCE`` ('fused'|'split') overrides the argument —
    the A/B escape hatch for the packed-payload collectives.
    """
    env = os.environ.get("GLT_COLLECTIVE_FORCE")
    if env in ("fused", "split"):
        return env == "fused"
    return True if fused is None else bool(fused)


def _bucket_by_owner_sort(ids: jnp.ndarray, owner: jnp.ndarray,
                          num_shards: int, cap: int) -> Routing:
    """Sort-based bucketing (the fallback path; see `_bucket_by_owner`).

    Stable argsort by owner, then segment starts straight off the sorted
    owner keys — O(S log B) searchsorted instead of a dense [B, S+1]
    one-hot count, which at hop-2 frontier widths (50k+) dominated the
    exchange prologue.
    """
    b = ids.shape[0]
    valid = ids >= 0
    owner_key = jnp.where(valid, owner, num_shards)  # padding sorts last
    order = jnp.argsort(owner_key, stable=True)
    sorted_ids = ids[order]
    sorted_owner = owner_key[order]

    starts = jnp.searchsorted(
        sorted_owner, jnp.arange(num_shards + 1, dtype=sorted_owner.dtype)
    ).astype(jnp.int32)
    rank = jnp.arange(b, dtype=jnp.int32) - starts[sorted_owner]
    fits = rank < cap
    sorted_slot = jnp.where((sorted_owner < num_shards) & fits,
                            sorted_owner * cap + jnp.minimum(rank, cap - 1),
                            num_shards * cap)

    buckets = jnp.full((num_shards * cap + 1,), PADDING_ID, jnp.int32)
    buckets = buckets.at[sorted_slot].set(sorted_ids)[:-1]

    slot = jnp.zeros((b,), jnp.int32).at[order].set(sorted_slot)
    slot_valid = jnp.zeros((b,), bool).at[order].set(
        fits & (sorted_owner < num_shards))
    dropped = jnp.sum(((sorted_owner < num_shards) & ~fits)
                      .astype(jnp.int32))
    return Routing(buckets=buckets, slot=jnp.minimum(slot, num_shards * cap - 1),
                   valid=valid & slot_valid, dropped=dropped)


def _bucket_by_owner_onepass(ids: jnp.ndarray, owner: jnp.ndarray,
                             num_shards: int, cap: int) -> Routing:
    """Sort-free bucketing: one-pass per-owner rank via cumulative masks.

    The stable sort's only job is the rank-within-owner; a [B, S] one-hot
    cumsum computes the identical rank directly (input order within each
    owner is preserved by construction), so every field is bit-identical
    to :func:`_bucket_by_owner_sort` — O(B*S) elementwise work, no sort.
    """
    b = ids.shape[0]
    valid = ids >= 0
    owner_key = jnp.where(valid, owner, num_shards).astype(jnp.int32)
    onehot = owner_key[:, None] == jnp.arange(num_shards,
                                              dtype=jnp.int32)[None, :]
    rank_m = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    rank = jnp.sum(jnp.where(onehot, rank_m, 0), axis=1)
    in_range = owner_key < num_shards
    fits = rank < cap
    slot = jnp.where(in_range & fits,
                     owner_key * cap + jnp.minimum(rank, cap - 1),
                     num_shards * cap)
    buckets = jnp.full((num_shards * cap + 1,), PADDING_ID, jnp.int32)
    buckets = buckets.at[slot].set(ids)[:-1]
    dropped = jnp.sum((in_range & ~fits).astype(jnp.int32))
    return Routing(buckets=buckets,
                   slot=jnp.minimum(slot, num_shards * cap - 1),
                   valid=valid & in_range & fits, dropped=dropped)


@scoped("glt.route.bucket")
def _bucket_by_owner(ids: jnp.ndarray, owner: jnp.ndarray, num_shards: int,
                     cap: int, route: str = "auto") -> Routing:
    """Group ids into per-owner rows of a static ``[S, cap]`` buffer.

    The scatter order is stable (input order within each owner), so every
    valid id gets slot ``owner * cap + rank-within-owner``.  With ``cap =
    len(ids)`` overflow is impossible (the reference-exact default);
    smaller capacity-bounded buffers (see :func:`exchange_one_hop`'s
    ``remote_cap``) route ids past an owner's cap to the trash slot, mark
    them invalid, and count them in ``dropped`` so callers can observe
    the loss.

    ``route`` selects the rank computation ('onepass' cumulative masks vs
    'sort' stable argsort — bit-identical outputs; see
    :func:`_route_choice` for the 'auto' resolution order).
    """
    if _route_choice(ids.shape[0], num_shards, cap, route) == "onepass":
        return _bucket_by_owner_onepass(ids, owner, num_shards, cap)
    return _bucket_by_owner_sort(ids, owner, num_shards, cap)


@scoped("glt.route.bucket")
def build_routing(ids: jnp.ndarray, nodes_per_shard: int, num_shards: int,
                  cap: Optional[int] = None,
                  route: str = "auto") -> Routing:
    """Build the owner-bucketed routing plan for a frontier of global ids.

    Call inside ``shard_map``, ONCE per hop frontier, and thread the
    result through every exchange over that frontier
    (:func:`exchange_one_hop`,
    :func:`~glt_tpu.parallel.dist_feature.exchange_gather`,
    :func:`~glt_tpu.parallel.dist_feature.exchange_gather_hot`,
    :func:`~glt_tpu.parallel.dist_feature.route_cold_requests`) — the
    plan depends only on the ids and the contiguous partition geometry,
    so rebuilding it per exchange (as the pre-routing-layer train step
    did, 3x per batch) is pure waste.

    Args:
      ids: ``[B]`` global node ids, -1 padded.
      cap: per-owner bucket capacity; ``None`` -> ``B`` (overflow-free).
      route: 'auto' | 'onepass' | 'sort' (see :func:`_route_choice`).
    """
    owner = jnp.where(ids >= 0, ids // nodes_per_shard, -1)
    return _bucket_by_owner(ids, owner, num_shards,
                            ids.shape[0] if cap is None else int(cap),
                            route=route)


def autotune_routing(b: int, num_shards: int, cap: Optional[int] = None,
                     iters: int = 3, seed: int = 0,
                     mesh_shape: Optional[tuple] = None) -> str:
    """Measure sort vs one-pass bucketing for this (B, S, cap) and
    memoize the winner for ``route='auto'``.

    Call EAGERLY at warmup (sampler construction) — never from inside a
    trace.  Each timing ends in a host value fetch.  Off-TPU backends
    pin the shard-count heuristic without timing.

    With ``mesh_shape=(H, C)`` (a 2-D mesh) the sweep also covers the
    flat-vs-hier topology choice (memoized in the ``_TOPO_AUTO`` table
    consumed by :func:`_topology_choice`): hier's extra cost is the
    per-dest-host dedup (the legs are bandwidth, not compute), so on TPU
    we time the vmapped ``unique_first_occurrence`` over the ``[H,
    C*cap]`` slab against the flat bucketing it augments and keep hier
    unless the dedup alone dwarfs the plan build; off-TPU the shape
    heuristic (hier iff both axes > 1) is pinned without timing.  1-D
    meshes never consult the table — :func:`_topology_choice` pins
    'flat' before reaching it.
    """
    cap = b if cap is None else int(cap)
    if mesh_shape is not None:
        _autotune_topology(b, mesh_shape, cap, iters=iters, seed=seed)
    key = (int(b), int(num_shards), cap)
    if key in _ROUTE_AUTO:
        return _ROUTE_AUTO[key]
    choice = "onepass" if num_shards <= _ONEPASS_MAX_SHARDS else "sort"
    if jax.default_backend() == "tpu":
        # Both arms are plain XLA programs: one that fails to compile or
        # run is an error and propagates, it does not lose the sweep.
        rng = np.random.default_rng(seed)
        ids = jnp.asarray(rng.integers(
            0, num_shards * max(b, 1), size=b).astype(np.int32))
        owner = jnp.asarray(rng.integers(
            0, num_shards, size=b).astype(np.int32))

        def timed(fn):
            f = jax.jit(partial(fn, num_shards=num_shards, cap=cap))
            int(f(ids, owner).dropped)   # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                out = f(ids, owner)
            int(out.dropped)             # host fetch: the sync
            return time.perf_counter() - t0

        t_sort = timed(_bucket_by_owner_sort)
        t_one = timed(_bucket_by_owner_onepass)
        choice = "onepass" if t_one < t_sort else "sort"
    _ROUTE_AUTO[key] = choice
    _M_ROUTE_AUTOTUNE.inc()
    _metrics.gauge("glt.dist.route_onepass_selected",
                   "1 if the last routing autotune picked one-pass",
                   ).set(1.0 if choice == "onepass" else 0.0)
    return choice


def _autotune_topology(b: int, mesh_shape, cap: int,
                       iters: int = 3, seed: int = 0) -> str:
    """Fill the flat-vs-hier decision table for one (H, C) grid."""
    h, c = int(mesh_shape[0]), int(mesh_shape[1])
    tkey = (h, c)
    if tkey in _TOPO_AUTO:
        return _TOPO_AUTO[tkey]
    choice = "hier" if (h > 1 and c > 1) else "flat"
    if choice == "hier" and jax.default_backend() == "tpu":
        rng = np.random.default_rng(seed)
        num_shards = h * c
        ids = jnp.asarray(rng.integers(
            0, num_shards * max(b, 1), size=b).astype(np.int32))
        owner = jnp.asarray(rng.integers(
            0, num_shards, size=b).astype(np.int32))
        slab = jnp.asarray(rng.integers(
            -1, max(b, 2), size=(h, c * cap)).astype(np.int32))

        def timed(f, *args):
            g = jax.jit(f)
            jax.block_until_ready(g(*args))    # compile + warm
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = g(*args)
            jax.block_until_ready(out)
            return time.perf_counter() - t0

        t_flat = timed(partial(_bucket_by_owner_sort,
                               num_shards=num_shards, cap=cap),
                       ids, owner)
        t_dedup = timed(jax.vmap(unique_first_occurrence), slab)
        # The dedup is pure overhead vs flat; the DCN bytes it saves
        # are shape-static (exchange_byte_model) and DCN is orders
        # of magnitude slower than ICI, so keep hier unless the
        # dedup dominates the whole plan build.
        choice = "hier" if t_dedup < 8.0 * max(t_flat, 1e-9) \
            else "flat"
    _TOPO_AUTO[tkey] = choice
    _M_ROUTE_AUTOTUNE.inc()
    _metrics.gauge("glt.dist.route_hier_selected",
                   "1 if the last topology autotune picked hierarchical",
                   ).set(1.0 if choice == "hier" else 0.0)
    return choice


@scoped("glt.route.payload")
def _bucket_payload(routing: Routing, payload: jnp.ndarray,
                    num_shards: int, cap: int) -> jnp.ndarray:
    """Scatter a payload array into the same bucket slots as its ids."""
    buckets = jnp.full((num_shards * cap + 1,), PADDING_ID, jnp.int32)
    slot = jnp.where(routing.valid, routing.slot, num_shards * cap)
    return buckets.at[slot].set(payload)[:-1]


# -- hierarchical (two-level ICI/DCN) routing ------------------------------
#
# On a 2-D (host, chip) mesh (multihost.global_mesh_2d) the flat plan
# wastes the slow fabric: a frontier id that every chip of one host wants
# crosses DCN once PER CHIP.  The hierarchical plan dedups within the
# host first:
#
#   per-chip owner bucketing            [S*cap] viewed [H, C, cap]
#     -> intra-host all_to_all (ICI, chip axis, split/concat dim 1)
#   per-dest-host slab                  [H, C*cap] on the owner-chip column
#     -> vmapped unique_first_occurrence per dest-host row
#   host-unique ids + inverse           uniq [H, hier_cap], inv [H, C*cap]
#     -> cross-host all_to_all (DCN, host axis) of ONLY uniq
#   owner serves each unique id once    [H*hier_cap] -> payload
#     -> DCN back, expand via inv (take_along_axis; inv never crossed DCN)
#     -> ICI back (chip axis), landing in the flat bucket order
#   flat unscatter                      resp[base.slot] masked by base.valid
#
# The response retraces the request legs in reverse, so the final scatter
# is the unmodified flat epilogue.  Bit-identity with the flat path holds
# because on 2-D meshes draws are keyed per (key, id) — layout-invariant
# — so serving a deduped id once and broadcasting the answer equals
# serving every duplicate slot (ops/neighbor_sample.draw_positions).

#: Decision table for the 2-D topology choice: (H, C, b, cap) -> 'flat' |
#: 'hier', filled by autotune_routing when given a mesh_shape.
_TOPO_AUTO: dict = {}


class HierGeom(NamedTuple):
    """Static geometry of a hierarchical plan (never crosses a jit
    boundary — built and consumed inside one shard_map body)."""
    num_hosts: int
    chips_per_host: int
    host_axis: str
    chip_axis: str
    cap: int        # per-owner bucket capacity of the flat base plan
    hier_cap: int   # per-dest-host unique-request capacity (DCN leg width)


class HierarchicalRouting(NamedTuple):
    """Two-level routing plan for one frontier on a 2-D mesh (see
    :func:`build_hier_routing`).  Wraps the flat :class:`Routing` (whose
    ``slot``/``valid`` still drive the final unscatter) plus the per-host
    dedup state the DCN legs ride on.  Like :class:`Routing`: build ONCE
    per hop frontier, thread through every exchange over that frontier.
    """
    base: Routing
    uniq: jnp.ndarray          # [H, hier_cap] host-unique ids, -1 padded
    inv: jnp.ndarray           # [H, C*cap] index into uniq row, -1 = pad/drop
    hier_dropped: jnp.ndarray  # [] int32: unique ids beyond hier_cap
    geom: HierGeom


def hier_request_cap(cap: int, chips_per_host: int, nodes_per_shard: int,
                     hier_load_factor: Optional[float] = None) -> int:
    """DCN-leg width per dest host: how many host-unique ids one device
    forwards to each remote host.

    The lossless bound is ``min(C*cap, nodes_per_shard)`` — a dest-host
    slab has ``C*cap`` slots, and its uniques are all owned by ONE shard
    so there can never be more than ``nodes_per_shard`` of them.  An
    explicit ``hier_load_factor`` (α) bounds the buffer at
    ``ceil(α * C * cap)`` like ``exchange_load_factor`` does for the flat
    buckets: overflow is dropped (masked padding, counted), and the DCN
    bytes shrink by ~1/α.
    """
    lossless = min(int(chips_per_host) * int(cap),
                   max(1, int(nodes_per_shard)))
    if hier_load_factor is None:
        return lossless
    bounded = max(1, int(np.ceil(float(hier_load_factor)
                                 * chips_per_host * cap)))
    return min(lossless, bounded)


def _topology_choice(route: str, axis_name,
                     mesh_shape: Optional[tuple] = None) -> str:
    """Resolve the routing topology ('flat' | 'hier') at trace time.

    Priority: ``GLT_ROUTE_FORCE`` env ('flat'/'hier') > explicit
    ``route`` argument > 1-D meshes pin 'flat' > autotuned decision table
    > default ('hier' on a mesh with both axes > 1, else 'flat').  The
    same env var keeps carrying the bucketing values ('sort'/'onepass');
    the two sub-seams are orthogonal and each ignores the other's tokens.
    """
    env = os.environ.get("GLT_ROUTE_FORCE")
    forced = env if env in ("flat", "hier") else (
        route if route in ("flat", "hier") else None)
    if isinstance(axis_name, str) or len(tuple(axis_name)) < 2:
        return "flat"          # 1-D meshes pin flat, even when forced
    if forced is not None:
        return forced
    if mesh_shape is None:
        return "flat"
    h, c = int(mesh_shape[0]), int(mesh_shape[1])
    if h < 2 or c < 2:
        return "flat"          # degenerate grid: nothing to dedup over
    hit = _TOPO_AUTO.get((h, c))
    return hit if hit is not None else "hier"


def build_hier_routing(
    ids: jnp.ndarray,
    nodes_per_shard: int,
    num_hosts: int,
    chips_per_host: int,
    host_axis: str,
    chip_axis: str,
    cap: Optional[int] = None,
    hier_load_factor: Optional[float] = None,
    route: str = "auto",
    base: Optional[Routing] = None,
) -> HierarchicalRouting:
    """Build the two-level routing plan for a frontier; call inside
    ``shard_map`` over the 2-D mesh, ONCE per hop frontier.

    Runs the ICI request leg and the per-dest-host dedup eagerly (they
    are part of the plan — every exchange over this frontier reuses the
    same ``uniq``/``inv``); the DCN legs run per exchange.  ``inv`` stays
    device-local: only the host-unique ids ever cross DCN.

    Args:
      ids: ``[B]`` global node ids, -1 padded.
      cap: per-owner bucket capacity; ``None`` -> ``B`` (overflow-free).
      hier_load_factor: DCN buffer bound (see :func:`hier_request_cap`).
      base: pre-built flat :class:`Routing` over ``ids`` with this
        ``cap``, if the caller already has one.
    """
    b = ids.shape[0]
    cap = b if cap is None else int(cap)
    h, c = int(num_hosts), int(chips_per_host)
    num_shards = h * c
    if base is None:
        owner = jnp.where(ids >= 0, ids // nodes_per_shard, -1)
        base = _bucket_by_owner(ids, owner, num_shards, cap=cap,
                                route=route)
    # ICI leg: land every local chip's bucket for owner (oh, my_chip) on
    # this device — slab[oh, q*cap + j] = chip q's j-th request for that
    # owner.
    with jax.named_scope("glt.route.exchange"):
        slab = lax.all_to_all(base.buckets.reshape(h, c, cap), chip_axis,
                              1, 1, tiled=False).reshape(h, c * cap)
    hc = hier_request_cap(cap, c, nodes_per_shard, hier_load_factor)
    with jax.named_scope("glt.route.bucket"):
        u = jax.vmap(unique_first_occurrence)(slab)
        uniq = u.uniques[:, :hc]
        inv = jnp.where((u.inverse >= 0) & (u.inverse < hc), u.inverse, -1)
        hier_dropped = jnp.sum(
            jnp.maximum(u.count - hc, 0)).astype(jnp.int32)
    return HierarchicalRouting(
        base=base, uniq=uniq, inv=inv, hier_dropped=hier_dropped,
        geom=HierGeom(num_hosts=h, chips_per_host=c, host_axis=host_axis,
                      chip_axis=chip_axis, cap=cap, hier_cap=hc))


def hier_requests(hr: HierarchicalRouting) -> jnp.ndarray:
    """DCN request leg: ``[H * hier_cap]`` host-unique ids addressed to
    this device (row ``qh`` came from host ``qh``'s same-chip peer)."""
    g = hr.geom
    with jax.named_scope("glt.route.exchange"):
        return lax.all_to_all(hr.uniq, g.host_axis, 0, 0,
                              tiled=False).reshape(g.num_hosts * g.hier_cap)


def hier_response(hr: HierarchicalRouting, payload: jnp.ndarray,
                  fill) -> jnp.ndarray:
    """Retrace the request legs in reverse: per-unique-request payload
    ``[H * hier_cap, W]`` -> ``[S * cap, W]`` in flat bucket order.

    DCN back (host axis), expand each dest-host row through ``inv``
    (duplicates get copies of the one served answer; dropped/padding
    slots get ``fill``), then ICI back (chip axis) to the requesting
    chip.  The result unscatters with the unmodified flat epilogue
    ``payload[base.slot]`` under ``base.valid``.
    """
    g = hr.geom
    w = payload.shape[-1]
    with jax.named_scope("glt.route.exchange"):
        resp = lax.all_to_all(payload.reshape(g.num_hosts, g.hier_cap, w),
                              g.host_axis, 0, 0, tiled=False)
    with jax.named_scope("glt.route.payload"):
        safe = jnp.clip(hr.inv, 0, g.hier_cap - 1)
        full = jnp.take_along_axis(resp, safe[..., None], axis=1)
        full = jnp.where((hr.inv >= 0)[..., None], full, fill)
    with jax.named_scope("glt.route.exchange"):
        back = lax.all_to_all(
            full.reshape(g.num_hosts, g.chips_per_host, g.cap, w),
            g.chip_axis, 1, 1, tiled=False)
    return back.reshape(g.num_hosts * g.chips_per_host * g.cap, w)


def exchange_byte_model(topology: str, num_hosts: int, chips_per_host: int,
                        cap: int, payload_elems: int,
                        hier_cap: Optional[int] = None,
                        elem_bytes: int = 4):
    """Per-device ``(ici_bytes, dcn_bytes)`` for one request+response
    round trip, from static plan shapes (what the
    ``glt.dist.collective_bytes{axis=}`` counters accumulate).

    Flat on ``[H, C]``: each device sends ``cap`` ids (+ ``payload_elems``
    response elems per slot) to all ``S-1`` peers — ``C-1`` of them over
    ICI, ``(H-1)*C`` over DCN.  Hier: the ICI legs move the full
    ``[H, C, cap]`` bucket block minus the self column; only
    ``(H-1) * hier_cap`` slots cross DCN.
    """
    h, c = int(num_hosts), int(chips_per_host)
    per_slot = (1 + int(payload_elems)) * int(elem_bytes)
    if topology == "flat":
        ici = (c - 1) * cap * per_slot
        dcn = (h - 1) * c * cap * per_slot
    elif topology == "hier":
        hc = c * cap if hier_cap is None else int(hier_cap)
        ici = (c - 1) * h * cap * per_slot
        dcn = (h - 1) * hc * per_slot
    else:
        raise ValueError(f"topology must be 'flat' or 'hier', "
                         f"got {topology!r}")
    return int(ici), int(dcn)


def build_sorted_edge_view(indptr: jnp.ndarray, indices: jnp.ndarray):
    """Per-shard (row, dst) pairs lex-sorted for binary search; call inside
    ``shard_map`` (or on a single shard's block).

    The distributed analog of the column-sorted auxiliary view the Graph
    class keeps for `edge_in_csr` (random_negative_sampler.cu:37-54) —
    here the whole local edge block is sorted by (local row, global dst)
    so membership is one lexicographic ``lower_bound``.  Two int32 keys
    instead of one packed int64 key: x64 stays off.
    """
    max_e = indices.shape[0]
    c = indptr.shape[0] - 1
    pos = jnp.arange(max_e, dtype=jnp.int32)
    row = jnp.searchsorted(indptr.astype(jnp.int32), pos,
                           side="right").astype(jnp.int32) - 1
    n_edges = indptr[c].astype(jnp.int32)
    valid = pos < n_edges
    big = jnp.int32(2**31 - 1)
    row = jnp.where(valid, row, big)
    dst = jnp.where(valid, indices, big)
    order = jnp.lexsort((dst, row))
    return row[order], dst[order]


def _pair_exists(rows_s: jnp.ndarray, dsts_s: jnp.ndarray,
                 r: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """Branchless lexicographic lower_bound over the sorted edge view."""
    e = rows_s.shape[0]
    last = e - 1
    lo = jnp.zeros_like(r)
    hi = jnp.full_like(r, e)
    for _ in range(32):
        cond = lo < hi
        mid = lo + (hi - lo) // 2
        mc = jnp.clip(mid, 0, last)
        mr, md = rows_s[mc], dsts_s[mc]
        less = (mr < r) | ((mr == r) & (md < d))
        lo = jnp.where(cond & less, mid + 1, lo)
        hi = jnp.where(cond & ~less, mid, hi)
    lc = jnp.clip(lo, 0, last)
    return (lo < e) & (rows_s[lc] == r) & (dsts_s[lc] == d)


def dist_edge_exists(
    rows_s: jnp.ndarray,
    dsts_s: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    nodes_per_shard: int,
    num_shards: int,
    axis_name: str,
    route: str = "auto",
    fused: Optional[bool] = None,
) -> jnp.ndarray:
    """Global membership test for (src, dst) pairs; call inside shard_map.

    Routes each candidate pair to the shard owning ``src`` (one fused
    id+payload all-to-all), runs the local sorted-view lookup there, and
    routes the verdicts back — the collective rebuild of the reference's
    strict negative check, which it *skips* in distributed mode
    (dist_neighbor_sampler.py:327-453 uses non-strict draws).  Returns
    ``[B]`` bool (False for padding slots).
    """
    b = src.shape[0]
    my_rank = lax.axis_index(axis_name)
    owner = jnp.where(src >= 0, src // nodes_per_shard, -1)
    routing = _bucket_by_owner(src, owner, num_shards, cap=b, route=route)
    dst_buckets = _bucket_payload(routing, dst, num_shards, b)

    if _use_fused(fused):
        # src ids and dst payload ride ONE collective as a packed [.., 2]
        # block — all_to_all moves axis-0 blocks, so the trailing pack
        # axis is inert and the unpacked halves are bit-identical to the
        # split path's two launches.
        pair = jnp.stack([routing.buckets, dst_buckets], axis=-1)
        req = lax.all_to_all(pair.reshape(num_shards, b, 2), axis_name,
                             0, 0, tiled=False).reshape(num_shards * b, 2)
        req_s, req_d = req[:, 0], req[:, 1]
    else:
        req_s = lax.all_to_all(routing.buckets.reshape(num_shards, b),
                               axis_name, 0, 0, tiled=False).reshape(-1)
        req_d = lax.all_to_all(dst_buckets.reshape(num_shards, b),
                               axis_name, 0, 0, tiled=False).reshape(-1)

    local = req_s - my_rank * nodes_per_shard
    ok = (req_s >= 0) & (local >= 0) & (local < nodes_per_shard)
    exists = _pair_exists(rows_s, dsts_s,
                          jnp.where(ok, local, 0).astype(jnp.int32),
                          jnp.where(ok, req_d, 0).astype(jnp.int32))
    exists = (exists & ok).astype(jnp.int32)

    resp = lax.all_to_all(exists.reshape(num_shards, b), axis_name, 0, 0,
                          tiled=False).reshape(-1)
    return jnp.where(routing.valid, resp[routing.slot] > 0, False)


def exchange_one_hop(
    seeds: jnp.ndarray,
    indptr: jnp.ndarray,
    indices: jnp.ndarray,
    edge_ids: jnp.ndarray,
    nodes_per_shard: int,
    num_shards: int,
    fanout: int,
    key: jax.Array,
    axis_name: str,
    remote_cap: Optional[int] = None,
    route: str = "auto",
    fused: Optional[bool] = None,
    routing=None,
    mesh_shape: Optional[tuple] = None,
    hier_load_factor: Optional[float] = None,
    hop: int = 1,
):
    """One distributed sampling hop; call inside ``shard_map``.

    Args:
      seeds: ``[B]`` global seed ids on this shard (-1 padded).
      indptr/indices/edge_ids: this shard's local CSR block
        (:class:`~glt_tpu.parallel.sharding.ShardedGraph` fields with the
        leading shard axis already consumed by shard_map).
      key: per-shard PRNG key (fold in the axis index for decorrelation).
      axis_name: the mesh axis (str) or axis tuple — a 2-D
        ``("host", "chip")`` mesh passes the tuple; the flat topology
        then addresses the combined axis (host-major, identical to the
        1-D flat order) and the hier topology splits the legs per axis.
      remote_cap: capacity-bounded exchange (VERDICT r3 #3).  ``None``
        reproduces the reference-exact worst-case buffers (every shard
        reserves the full frontier width ``B`` for every destination, so
        each hop moves ``S*B`` ids — the exact-size-message analog of
        dist_neighbor_sampler.py:542-598 padded to worst case).  With a
        cap, **locally-owned seeds never enter the collective at all**
        (they are sampled straight from the local CSR block — on
        contiguous partitions hop 0 of a shard-local seed batch is
        exchange-free) and only remote ids ride per-owner buckets of
        width ``remote_cap``, shrinking exchange bytes by ``S*B /
        (S*remote_cap)``.  Ids past an owner's cap are dropped (masked
        padding, never garbage) and counted.
      route / fused: routing-path and collective-fusion seams (see
        :func:`_route_choice` / :func:`_use_fused`); ``route`` also
        carries the topology tokens 'flat'/'hier' (see
        :func:`_topology_choice`).
      routing: pre-built :class:`Routing` (flat) or
        :class:`HierarchicalRouting` for ``seeds`` — only honored when
        ``remote_cap`` is None (the capped path buckets the
        remote-masked subset, a different plan).  A hierarchical plan
        forces the hier transport regardless of ``route``.
      mesh_shape: ``(num_hosts, chips_per_host)`` of the 2-D mesh —
        required for the hier topology when ``routing`` is not prebuilt.
      hier_load_factor: DCN-leg buffer bound (see
        :func:`hier_request_cap`); None = lossless.
      hop: which hop of the caller's loop this is (from 1); names the
        device scope ``glt.sample.hop<k>`` of the local neighbour read
        and nothing else.

    Returns:
      ``(nbrs, eids, mask, dropped, rows_read)``; first three ``[B,
      fanout]`` in seed order, ``dropped`` a scalar int32 (always 0 when
      ``remote_cap`` is None and the hier DCN buffer is lossless),
      ``rows_read`` a scalar int32: the rows whose random reads THIS
      shard's neighbour reads issued, for whoever asked
      (:func:`~glt_tpu.ops.neighbor_sample.read_rows` of the served
      request matrix, and of the local split's frontier where there is
      one).
    """
    b = seeds.shape[0]
    my_rank = lax.axis_index(axis_name)
    owner = jnp.where(seeds >= 0, seeds // nodes_per_shard, -1)
    # `hier` reads ONLY the incoming argument and the static topology
    # seam — never the rebuilt plan below — so the branch predicate is
    # provably uniform across shards (GLT020's taint chain stops at the
    # parameter).  The plan gets its own name for the same reason.
    hier = isinstance(routing, HierarchicalRouting) or (
        routing is None
        and _topology_choice(route, axis_name, mesh_shape) == "hier")
    plan = routing
    # 2-D meshes key draws per (key, id) so the flat and hier transports
    # are bit-identical (dedup serves each id once); 1-D meshes keep the
    # historical per-slot stream.
    key_by = "slot" if isinstance(axis_name, str) else "id"

    rows_read = jnp.zeros((), jnp.int32)
    if remote_cap is None:
        cap = b
        local_nbrs = local_eids = None
        if hier and not isinstance(plan, HierarchicalRouting):
            plan = build_hier_routing(
                seeds, nodes_per_shard, mesh_shape[0], mesh_shape[1],
                axis_name[0], axis_name[1], cap=b,
                hier_load_factor=hier_load_factor, route=route,
                base=plan)
        elif plan is None:
            plan = _bucket_by_owner(seeds, owner, num_shards, cap=b,
                                    route=route)
    else:
        cap = int(remote_cap)
        # Local split: owner == my shard -> direct sample, no collective.
        is_local = owner == my_rank
        with jax.named_scope(f"glt.sample.hop{hop}"):
            local_ids = jnp.where(
                is_local, seeds - my_rank * nodes_per_shard, -1)
            lout = sample_neighbors(indptr, indices, local_ids, fanout,
                                    key, edge_ids=edge_ids, key_by=key_by)
            rows_read = read_rows(local_ids)
        local_nbrs, local_eids = lout.nbrs, lout.eids
        remote_ids = jnp.where(is_local, PADDING_ID, seeds)
        if hier:
            plan = build_hier_routing(
                remote_ids, nodes_per_shard, mesh_shape[0], mesh_shape[1],
                axis_name[0], axis_name[1], cap=cap,
                hier_load_factor=hier_load_factor, route=route)
        else:
            plan = _bucket_by_owner(remote_ids, owner, num_shards,
                                    cap=cap, route=route)

    flat_plan = plan.base if hier else plan

    # Request exchange: the ids this shard must serve.  Flat: row q =
    # ids wanted by shard q from us.  Hier: row qh = host qh's unique
    # wants from us (DCN leg; the ICI leg already ran in the plan build).
    if hier:
        requests = hier_requests(plan)
    else:
        with jax.named_scope("glt.route.exchange"):
            requests = lax.all_to_all(
                plan.buckets.reshape(num_shards, cap), axis_name, 0, 0,
                tiled=False).reshape(num_shards * cap)

    # Sample requested ids from the local CSR block (global -> local row).
    with jax.named_scope(f"glt.sample.hop{hop}"):
        local = jnp.where(requests >= 0,
                          requests - my_rank * nodes_per_shard, -1)
        local = jnp.where((local >= 0) & (local < nodes_per_shard),
                          local, -1)
        out = sample_neighbors(indptr, indices, local, fanout,
                               jax.random.fold_in(key, 1),
                               edge_ids=edge_ids, key_by=key_by)
        rows_read = rows_read + read_rows(local)

    # Response exchange + unscatter (the stitch, stitch_sample_results.cu:57).
    fuse = _use_fused(fused)
    if hier or fuse:
        with jax.named_scope("glt.route.payload"):
            packed = jnp.concatenate([out.nbrs, out.eids], axis=-1)
    if hier:
        # The hier transport always packs neighbors + edge ids into one
        # payload (its legs are shared infrastructure); `fused` only
        # selects the flat path's collective shape.
        resp = hier_response(plan, packed, fill=PADDING_ID)
        resp_nbrs, resp_eids = resp[:, :fanout], resp[:, fanout:]
    elif fuse:
        # Neighbors and edge ids ride ONE [S, cap, 2*fanout] collective
        # (half the per-hop launches); the halves split back bit-exact.
        with jax.named_scope("glt.route.exchange"):
            resp = lax.all_to_all(
                packed.reshape(num_shards, cap, 2 * fanout), axis_name,
                0, 0, tiled=False).reshape(num_shards * cap, 2 * fanout)
        resp_nbrs, resp_eids = resp[:, :fanout], resp[:, fanout:]
    else:
        with jax.named_scope("glt.route.exchange"):
            resp_nbrs = lax.all_to_all(
                out.nbrs.reshape(num_shards, cap, fanout), axis_name, 0,
                0, tiled=False).reshape(num_shards * cap, fanout)
            resp_eids = lax.all_to_all(
                out.eids.reshape(num_shards, cap, fanout), axis_name, 0,
                0, tiled=False).reshape(num_shards * cap, fanout)

    with jax.named_scope("glt.route.payload"):
        nbrs = jnp.where(flat_plan.valid[:, None],
                         resp_nbrs[flat_plan.slot], PADDING_ID)
        eids = jnp.where(flat_plan.valid[:, None],
                         resp_eids[flat_plan.slot], PADDING_ID)
        if local_nbrs is not None:
            sel = is_local[:, None]
            nbrs = jnp.where(sel, local_nbrs, nbrs)
            eids = jnp.where(sel, local_eids, eids)
    dropped = (flat_plan.dropped + plan.hier_dropped if hier
               else plan.dropped)
    return nbrs, eids, nbrs >= 0, dropped, rows_read


def exchange_one_hop_ring(
    seeds: jnp.ndarray,
    indptr: jnp.ndarray,
    indices: jnp.ndarray,
    edge_ids: jnp.ndarray,
    nodes_per_shard: int,
    num_shards: int,
    fanout: int,
    key: jax.Array,
    axis_name: str,
    remote_cap: Optional[int] = None,
    route: str = "auto",
    fused: Optional[bool] = None,
    routing: Optional[Routing] = None,
    mesh_shape: Optional[tuple] = None,
    hier_load_factor: Optional[float] = None,
    hop: int = 1,
):
    """Ring-pipelined variant of :func:`exchange_one_hop`.

    Instead of one all-to-all burst, request buckets rotate around the ring
    with ``lax.ppermute`` (the ring-attention software-pipeline pattern):
    at step ``k`` each shard samples the requests of the shard ``k`` hops
    upstream while the next buckets are in flight.  Same result, different
    collective shape — preferable when the mesh axis spans DCN links or
    when overlapping sampling compute with transfers matters more than
    burst bandwidth.  ``remote_cap`` bounds the travelling matrix exactly
    as in :func:`exchange_one_hop` (local seeds never enter the ring).
    With ``fused`` the neighbor/edge-id answer buffers travel as one
    packed block, cutting the per-step ppermute launches from 3 to 2.
    The ring is a flat topology by construction — ``mesh_shape`` /
    ``hier_load_factor`` are accepted for signature parity with
    :func:`exchange_one_hop` and ignored (on a 2-D mesh the ring rotates
    the combined axis; draws keep the 2-D per-id keying so it stays
    comparable with the all-to-all paths).  ``hop`` names the device
    scope of the local neighbour read, and the fifth result counts the
    rows this shard's reads issued, as in :func:`exchange_one_hop`.
    """
    del mesh_shape, hier_load_factor  # flat-only transport
    b = seeds.shape[0]
    my = lax.axis_index(axis_name)
    owner = jnp.where(seeds >= 0, seeds // nodes_per_shard, -1)
    key_by = "slot" if isinstance(axis_name, str) else "id"
    rows_read = []

    def local_sample(ids, k):
        with jax.named_scope(f"glt.sample.hop{hop}"):
            local = jnp.where(ids >= 0, ids - my * nodes_per_shard, -1)
            local = jnp.where((local >= 0) & (local < nodes_per_shard),
                              local, -1)
            rows_read.append(read_rows(local))
            return sample_neighbors(indptr, indices, local, fanout,
                                    jax.random.fold_in(key, k),
                                    edge_ids=edge_ids, key_by=key_by)

    if remote_cap is None:
        cap = b
        if routing is None:
            routing = _bucket_by_owner(seeds, owner, num_shards, cap=cap,
                                       route=route)
        local_nbrs = local_eids = is_local = None
    else:
        cap = int(remote_cap)
        is_local = owner == my
        lout = local_sample(jnp.where(is_local, seeds, PADDING_ID),
                            num_shards)
        local_nbrs, local_eids = lout.nbrs, lout.eids
        routing = _bucket_by_owner(
            jnp.where(is_local, PADDING_ID, seeds), owner, num_shards,
            cap=cap, route=route)

    right = [(i, (i + 1) % num_shards) for i in range(num_shards)]
    fuse = _use_fused(fused)

    def rotate(block):
        with jax.named_scope("glt.route.exchange"):
            return lax.ppermute(block, axis_name, right)

    # The request matrix and its answer buffers travel the ring together:
    # after k rotations shard i holds the matrix that originated at shard
    # i-k and serves ITS row i (the requests shard i-k addressed to i).
    # After a final rotation (num_shards total) every matrix is home with
    # all rows answered — one serve + one hop per step, fully pipelined.
    reqs = routing.buckets.reshape(num_shards, cap)
    if fuse:
        ans = jnp.full((num_shards, cap, 2 * fanout), PADDING_ID,
                       jnp.int32)

        def serve(reqs, ans, k):
            o = local_sample(jnp.take(reqs, my, axis=0), k)
            with jax.named_scope("glt.route.payload"):
                return ans.at[my].set(
                    jnp.concatenate([o.nbrs, o.eids], axis=-1))

        ans = serve(reqs, ans, 0)
        for k in range(1, num_shards):
            reqs = rotate(reqs)
            ans = rotate(ans)
            ans = serve(reqs, ans, k)
        if num_shards > 1:
            ans = rotate(ans)
        ans = ans.reshape(num_shards * cap, 2 * fanout)
        resp_nbrs, resp_eids = ans[:, :fanout], ans[:, fanout:]
    else:
        ans_n = jnp.full((num_shards, cap, fanout), PADDING_ID, jnp.int32)
        ans_e = jnp.full((num_shards, cap, fanout), PADDING_ID, jnp.int32)

        def serve(reqs, ans_n, ans_e, k):
            incoming = jnp.take(reqs, my, axis=0)
            o = local_sample(incoming, k)
            with jax.named_scope("glt.route.payload"):
                return ans_n.at[my].set(o.nbrs), ans_e.at[my].set(o.eids)

        ans_n, ans_e = serve(reqs, ans_n, ans_e, 0)
        for k in range(1, num_shards):
            reqs = rotate(reqs)
            ans_n = rotate(ans_n)
            ans_e = rotate(ans_e)
            ans_n, ans_e = serve(reqs, ans_n, ans_e, k)
        if num_shards > 1:
            ans_n = rotate(ans_n)
            ans_e = rotate(ans_e)

        resp_nbrs = ans_n.reshape(num_shards * cap, fanout)
        resp_eids = ans_e.reshape(num_shards * cap, fanout)
    with jax.named_scope("glt.route.payload"):
        nbrs = jnp.where(routing.valid[:, None], resp_nbrs[routing.slot],
                         PADDING_ID)
        eids = jnp.where(routing.valid[:, None], resp_eids[routing.slot],
                         PADDING_ID)
        if local_nbrs is not None:
            sel = is_local[:, None]
            nbrs = jnp.where(sel, local_nbrs, nbrs)
            eids = jnp.where(sel, local_eids, eids)
    return nbrs, eids, nbrs >= 0, routing.dropped, sum(rows_read)


def dist_live_counters(batch_size: int, num_neighbors: Sequence[int],
                       num_shards: int, frontier_cap: Optional[int] = None,
                       exact: bool = True):
    """Where one shard's counts of a dist step are counted: the
    ``live_counts`` of :func:`dist_sample_multi_hop`, then the two of its
    served feature read.  ``read_rows{hop}`` is what the shard's own reads
    issued, whatever the exchange (its fifth result).  The frontier and
    edge slots are what the shard's reads PROCESS, not the width it asks
    with: under the exact flat exchange (``exact``: no
    ``exchange_load_factor``, no hierarchical plan) every shard serves
    ``S`` requesters' whole frontiers, ``S x width`` rows a hop.  Any
    other exchange serves a matrix of its own shape that nothing here
    re-derives: its slot counters stay where they are, so a share over
    them reads nothing until the change that runs that exchange counts
    them from its own shapes.  ``glt.gather.served_rows`` (request slots
    of the feature exchange that held a node of this shard) and
    ``glt.gather.read_rows`` (the slots its read visited;
    :func:`~glt_tpu.parallel.dist_feature._request_rows`) are counted
    from the matrix served, whatever its shape."""
    fanouts = list(num_neighbors)
    rows = [num_shards * w if exact else None
            for w in hop_widths(batch_size, fanouts, frontier_cap)]
    live = live_counters(
        rows, [r * f if exact else None for r, f in zip(rows, fanouts)],
        max_sampled_nodes(batch_size, fanouts, frontier_cap))
    return live._replace(counters=live.counters + (
        _metrics.counter("glt.gather.served_rows", "request slots of the "
                         "feature exchange that held a node of the serving "
                         "shard, over dist steps' shards"),
        _metrics.counter("glt.gather.read_rows", "request slots the "
                         "serving shard's feature read visited, over dist "
                         "steps' shards")))


def dist_sample_multi_hop(
    indptr: jnp.ndarray,
    indices: jnp.ndarray,
    edge_ids: jnp.ndarray,
    seeds: jnp.ndarray,
    key: jax.Array,
    num_neighbors: Sequence[int],
    nodes_per_shard: int,
    num_shards: int,
    axis_name: str,
    frontier_cap: Optional[int] = None,
    collective: str = "all_to_all",
    dedup: str = "auto",
    last_hop_dedup: bool = True,
    exchange_load_factor: Optional[float] = None,
    route: str = "auto",
    fused: Optional[bool] = None,
    mesh_shape: Optional[tuple] = None,
    hier_load_factor: Optional[float] = None,
) -> SamplerOutput:
    """Per-shard multi-hop sampling body; call inside ``shard_map``.

    Identical structure to the single-device
    ``NeighborSampler._sample_impl`` — frontier, cumulative
    first-occurrence dedup, relabeled COO — with
    :func:`exchange_one_hop` (or its ring variant, ``collective='ring'``)
    as the one-hop primitive.  ``dedup`` selects the inducer like the
    single-device sampler: 'dense' runs every hop, the seeds' own dedup
    included, as sorts and scans over the node buffer
    (``ops/unique.py::induce``; the buffer here is the worst case, so it
    covers the bound on known nodes and no shard holds an O(N_global) id
    map), 'sort' the growing argsort buffer; 'auto' takes dense wherever
    the single-device sampler's id map would stay under ~1GB.

    ``exchange_load_factor`` (α) opts into capacity-bounded exchanges:
    each hop's per-owner request buckets hold ``ceil(α * width /
    num_shards)`` remote ids instead of the full frontier width, cutting
    per-hop exchange bytes ~``num_shards/α``x; locally-owned frontier ids
    bypass the collective entirely.  Overflowed (dropped) request counts
    are surfaced in ``metadata['exchange_dropped']`` — with contiguous
    partitions and shard-local seeds α≈2 makes drops rare; monitor the
    counter and raise α (or use None = exact) if it is ever nonzero.

    ``route`` / ``fused`` select the bucketing implementation and the
    packed response collective (see :func:`_route_choice` /
    :func:`_use_fused`); on the exact (uncapped) path each hop's routing
    plan is built ONCE via :func:`build_routing` (or
    :func:`build_hier_routing` when the topology resolves hierarchical
    on a 2-D mesh — ``mesh_shape``/``hier_load_factor`` parameterize the
    two-level plan) and threaded into the exchange.
    """
    exchange = (exchange_one_hop if collective == "all_to_all"
                else exchange_one_hop_ring)
    topo = ("flat" if collective != "all_to_all"
            else _topology_choice(route, axis_name, mesh_shape))
    fanouts = list(num_neighbors)
    widths = hop_widths(seeds.shape[0], fanouts, frontier_cap)
    cap = max_sampled_nodes(seeds.shape[0], fanouts, frontier_cap)
    num_global = nodes_per_shard * num_shards
    if dedup == "auto":
        dedup = "dense" if dense_map_fits(num_global) else "sort"
    dense = dedup == "dense"

    if dense:
        # Seeds plus every candidate of the earlier hops, before each hop.
        knowns = hop_bounds(widths[0], fanouts, frontier_cap).node_bounds
        state = induce_init(num_global, cap, knowns[-2])
        record_sorted_slots(0, sorted_slots(state, 0, widths[0]))
        state, _ = induce(state, seeds, 0, False)
        node_buf = state.node_buf
        count = state.count
        frontier = node_buf[: widths[0]]
    else:
        u0 = unique_first_occurrence(seeds)
        # Growing unique buffer (see NeighborSampler._sample_impl): hop i
        # only sorts what can exist by hop i.
        node_buf = u0.uniques
        count = u0.count
        frontier = u0.uniques
    frontier_start = jnp.zeros((), jnp.int32)

    rows, cols, eids_out, emasks = [], [], [], []
    counts_per_hop = [count]
    edges_per_hop, rows_read = [], []
    keys = jax.random.split(key, len(fanouts))
    leaf_off = cap - widths[-1] * fanouts[-1]
    leaf_mask = None

    dropped_total = jnp.zeros((), jnp.int32)
    for i, f in enumerate(fanouts):
        w = widths[i]
        last = i + 1 == len(fanouts)
        remote_cap = (None if exchange_load_factor is None
                      else bounded_remote_cap(w, exchange_load_factor,
                                              num_shards))
        # One routing plan per hop frontier (exact path); the capped
        # path buckets only the remote-masked subset inside the
        # exchange, a different plan per construction.
        if remote_cap is not None:
            hop_routing = None
        elif topo == "hier":
            hop_routing = build_hier_routing(
                frontier, nodes_per_shard, mesh_shape[0], mesh_shape[1],
                axis_name[0], axis_name[1],
                hier_load_factor=hier_load_factor, route=route)
        else:
            hop_routing = build_routing(frontier, nodes_per_shard,
                                        num_shards, route=route)
        nbrs, eids, mask, dropped, read = exchange(
            frontier, indptr, indices, edge_ids, nodes_per_shard,
            num_shards, f, keys[i], axis_name, remote_cap=remote_cap,
            route=route, fused=fused, routing=hop_routing,
            mesh_shape=mesh_shape, hier_load_factor=hier_load_factor,
            hop=i + 1)
        dropped_total = dropped_total + dropped
        rows_read.append(read)

        src_local = frontier_start + jnp.arange(w, dtype=jnp.int32)
        src_local = jnp.where(frontier >= 0, src_local, PADDING_ID)

        if last and not last_hop_dedup:
            # Leaf block (see NeighborSampler.last_hop_dedup): zero map
            # ops at the widest frontier, one contiguous store.
            leaf_mask = mask.ravel()
            leaf_ids = jnp.where(leaf_mask, nbrs.ravel(), PADDING_ID)
            nbr_local = (leaf_off + jnp.arange(w * f, dtype=jnp.int32)
                         ).reshape(w, f)
            if dense:
                node_buf = lax.dynamic_update_slice(node_buf, leaf_ids,
                                                    (leaf_off,))
            else:
                node_buf = jnp.concatenate([node_buf, leaf_ids])
            new_count = count + jnp.sum(leaf_mask.astype(jnp.int32))
        elif dense:
            record_sorted_slots(i + 1, sorted_slots(state, knowns[i], w * f))
            state, nbr_local = induce(state, nbrs.ravel(), knowns[i], last)
            node_buf = state.node_buf
            new_count = state.count
            nbr_local = nbr_local.reshape(w, f)
        else:
            buflen = node_buf.shape[0]
            merged = unique_first_occurrence(
                jnp.concatenate([node_buf, nbrs.ravel()]))
            node_buf = merged.uniques
            new_count = merged.count
            nbr_local = merged.inverse[buflen:].reshape(w, f)
        nbr_local = jnp.where(mask, nbr_local, PADDING_ID)

        rows.append(nbr_local.ravel())
        cols.append(jnp.broadcast_to(src_local[:, None], (w, f)).ravel())
        eids_out.append(eids.ravel())
        emasks.append(mask.ravel())
        edges_per_hop.append(jnp.sum(mask.astype(jnp.int32)))

        if i + 1 < len(fanouts):
            nw = widths[i + 1]
            frontier = lax.dynamic_slice(
                jnp.concatenate(
                    [node_buf, jnp.full((nw,), PADDING_ID, jnp.int32)]),
                (jnp.clip(count, 0, node_buf.shape[0]),), (nw,))
            frontier_start = count
        count = new_count
        counts_per_hop.append(count)

    if node_buf.shape[0] < cap:
        node_buf = jnp.concatenate(
            [node_buf,
             jnp.full((cap - node_buf.shape[0],), PADDING_ID, jnp.int32)])
    node_buf = node_buf[:cap]
    count = jnp.minimum(count, cap)
    if leaf_mask is None:
        node_mask = jnp.arange(cap, dtype=jnp.int32) < count
    else:
        interior = jnp.minimum(count - edges_per_hop[-1], leaf_off)
        node_mask = (jnp.arange(cap, dtype=jnp.int32) < interior) | (
            jnp.concatenate([jnp.zeros((leaf_off,), bool), leaf_mask]))

    num_sampled_nodes = jnp.stack(
        [counts_per_hop[0]]
        + [counts_per_hop[i + 1] - counts_per_hop[i]
           for i in range(len(fanouts))])
    num_sampled_edges = jnp.stack(edges_per_hop)
    return SamplerOutput(
        node=node_buf,
        row=jnp.concatenate(rows),
        col=jnp.concatenate(cols),
        edge=jnp.concatenate(eids_out),
        batch=seeds,
        node_mask=node_mask,
        edge_mask=jnp.concatenate(emasks),
        num_sampled_nodes=num_sampled_nodes,
        num_sampled_edges=num_sampled_edges,
        metadata=(None
                  if exchange_load_factor is None
                  and hier_load_factor is None
                  else {"exchange_dropped": dropped_total}),
        live_counts=live_counts(num_sampled_nodes, num_sampled_edges,
                                widths, cap, rows_read),
    )


def dist_node_subgraph(
    indptr: jnp.ndarray,
    indices: jnp.ndarray,
    edge_ids: jnp.ndarray,
    nodes: jnp.ndarray,
    max_degree: int,
    nodes_per_shard: int,
    num_shards: int,
    axis_name: str,
    route: str = "auto",
    fused: Optional[bool] = None,
):
    """Distributed induced-subgraph extraction; call inside ``shard_map``.

    TPU rebuild of the reference's distributed subgraph path
    (dist_neighbor_sampler.py:456-516): there, node-set rows are fetched
    from owner workers over RPC and the CUDA SubGraphOp filters them.  Here
    each node's CSR row (capped at ``max_degree``) comes back through one
    all-to-all round trip, and membership filtering is the same sorted
    lookup the single-device op uses (ops/subgraph.py).

    Args:
      nodes: ``[B]`` unique global node ids (-1 padded).

    Returns ``(rows, cols, eids, mask)`` of shape ``[B * max_degree]`` —
    local indices into ``nodes``, matching
    :class:`~glt_tpu.ops.subgraph.SubGraphOutput`.
    """
    b = nodes.shape[0]
    routing = build_routing(nodes, nodes_per_shard, num_shards,
                            route=route)

    requests = lax.all_to_all(
        routing.buckets.reshape(num_shards, b), axis_name, 0, 0,
        tiled=False).reshape(num_shards * b)

    my_rank = lax.axis_index(axis_name)
    local = jnp.where(requests >= 0,
                      requests - my_rank * nodes_per_shard, -1)
    local = jnp.where((local >= 0) & (local < nodes_per_shard), local, -1)
    start, deg = _row_offsets_and_degrees(indptr, local.astype(jnp.int32))
    start = start.astype(jnp.int32)
    offs = jnp.arange(max_degree, dtype=jnp.int32)[None, :]
    in_row = (offs < deg[:, None]) & (local >= 0)[:, None]
    flat = start[:, None] + jnp.where(in_row, offs, 0)
    nbrs = jnp.where(in_row, indices[flat], PADDING_ID).astype(jnp.int32)
    eids = jnp.where(in_row, edge_ids[flat], PADDING_ID).astype(jnp.int32)

    if _use_fused(fused):
        resp = lax.all_to_all(
            jnp.concatenate([nbrs, eids], axis=-1)
            .reshape(num_shards, b, 2 * max_degree), axis_name, 0, 0,
            tiled=False).reshape(num_shards * b, 2 * max_degree)
        resp_nbrs, resp_eids = resp[:, :max_degree], resp[:, max_degree:]
    else:
        resp_nbrs = lax.all_to_all(
            nbrs.reshape(num_shards, b, max_degree), axis_name, 0, 0,
            tiled=False).reshape(num_shards * b, max_degree)
        resp_eids = lax.all_to_all(
            eids.reshape(num_shards, b, max_degree), axis_name, 0, 0,
            tiled=False).reshape(num_shards * b, max_degree)
    nbrs = jnp.where(routing.valid[:, None], resp_nbrs[routing.slot],
                     PADDING_ID)
    eids = jnp.where(routing.valid[:, None], resp_eids[routing.slot],
                     PADDING_ID)

    # Membership + relabel (ops/subgraph.py:56-63 semantics).
    local_dst = relabel_by_reference(nodes, nbrs.ravel()).reshape(
        b, max_degree)
    keep = (nbrs >= 0) & (local_dst >= 0)
    local_src = jnp.broadcast_to(
        jnp.arange(b, dtype=jnp.int32)[:, None], (b, max_degree))
    rows = jnp.where(keep, local_src, PADDING_ID).ravel()
    cols = jnp.where(keep, local_dst, PADDING_ID).ravel()
    eids = jnp.where(keep, eids, PADDING_ID).ravel()
    return rows, cols, eids, keep.ravel()


class DistNeighborSampler:
    """Multi-hop distributed sampler over a :class:`ShardedGraph`.

    The multi-hop structure (frontier, cumulative first-occurrence dedup,
    relabeled COO) is identical to the single-device
    :class:`~glt_tpu.sampler.neighbor_sampler.NeighborSampler`; only the
    one-hop primitive is the all-to-all exchange.  ``sample`` returns a
    per-shard :class:`SamplerOutput` (leading axis = shard) — each shard's
    batch is its own ego-subgraph, ready for data-parallel training.
    """

    def __init__(self, sharded_graph, mesh: Mesh,
                 axis_name: Optional[str] = None,
                 num_neighbors: Sequence[int] = (15, 10, 5),
                 batch_size: int = 512,
                 frontier_cap: Optional[int] = None,
                 collective: str = "all_to_all",
                 valid_per_shard: Optional[np.ndarray] = None,
                 seed: int = 0,
                 last_hop_dedup: bool = True,
                 exchange_load_factor: Optional[float] = None,
                 route: str = "auto",
                 fused: Optional[bool] = None,
                 hier_load_factor: Optional[float] = None):
        self.collective = collective
        self.valid_per_shard = valid_per_shard
        self.last_hop_dedup = bool(last_hop_dedup)
        self.exchange_load_factor = exchange_load_factor
        self.fused = fused
        self.hier_load_factor = hier_load_factor
        self._edges_fns = {}
        self._subgraph_fns = {}
        self.g = sharded_graph
        self.mesh = mesh
        self.axis_name = resolve_mesh_axes(mesh, axis_name)
        axis_name = self.axis_name
        self.mesh_shape = mesh_axis_sizes(mesh, self.axis_name)
        self.num_neighbors = list(num_neighbors)
        self.batch_size = int(batch_size)
        self.frontier_cap = frontier_cap
        self._base_key = jax.random.PRNGKey(seed)
        self._call_count = 0
        self._widths = hop_widths(self.batch_size, self.num_neighbors,
                                  frontier_cap)
        # Routing A/B seam: 'auto' autotunes sort vs one-pass at the
        # dominant (widest-frontier) shape on TPU; elsewhere the
        # shard-count heuristic picks (env GLT_ROUTE_FORCE still wins at
        # trace time — see _route_choice).  On a 2-D mesh the same sweep
        # also fills the flat-vs-hier topology table; the topology token
        # itself resolves at trace time (_topology_choice) so the
        # resolved bucketing choice stored here never erases it.
        self.route = route
        if route == "auto":
            self.route = autotune_routing(max(self._widths),
                                          self.g.num_shards,
                                          mesh_shape=self.mesh_shape)
        self.node_capacity = max_sampled_nodes(self.batch_size,
                                               self.num_neighbors,
                                               frontier_cap)
        self.hop_bounds = hop_bounds(self.batch_size, self.num_neighbors,
                                     frontier_cap)

        g = self.g
        gspec = P(axis_name)
        self._shard_fn = jax.jit(
            jax.shard_map(
                self._sample_local,
                mesh=mesh,
                in_specs=(gspec, gspec, gspec, gspec, P()),
                out_specs=gspec,
                check_vma=False,
            ))

    def _next_key(self) -> jax.Array:
        key = jax.random.fold_in(self._base_key, self._call_count)
        self._call_count += 1
        return key

    def _sample_local(self, indptr_blk, indices_blk, eids_blk, seeds_blk,
                      key):
        """Per-shard body (shapes carry a leading singleton shard axis)."""
        key = jax.random.fold_in(key, lax.axis_index(self.axis_name))
        out = dist_sample_multi_hop(
            indptr_blk[0], indices_blk[0], eids_blk[0], seeds_blk[0], key,
            self.num_neighbors, self.g.nodes_per_shard, self.g.num_shards,
            self.axis_name, self.frontier_cap, self.collective,
            last_hop_dedup=self.last_hop_dedup,
            exchange_load_factor=self.exchange_load_factor,
            route=self.route, fused=self.fused,
            mesh_shape=self.mesh_shape,
            hier_load_factor=self.hier_load_factor)
        # Re-add the shard axis for shard_map's out_specs.
        return jax.tree.map(lambda x: x[None], out)

    def sample_from_nodes(self, seeds_per_shard: jnp.ndarray,
                          key: Optional[jax.Array] = None) -> SamplerOutput:
        """``seeds_per_shard``: ``[S, batch_size]`` global ids, -1 padded."""
        if key is None:
            key = self._next_key()
        g = self.g
        # Host dispatch boundary of the whole shard_map program (routing
        # + collectives + local sampling run device-side inside it) —
        # span measures enqueue only, the consumer's sync sees the rest.
        with _span("dist.sample_dispatch", route=self.route), \
                _M_DIST_SAMPLE_MS.time():
            out = self._shard_fn(g.indptr, g.indices, g.edge_ids,
                                 seeds_per_shard, key)
        _M_DIST_BATCHES.inc()
        return out

    # -- distributed link path (cf. dist_neighbor_sampler.py:327-453) ------
    def _valid_per_shard(self) -> jnp.ndarray:
        """Valid-node count per shard, for uniform negative draws."""
        if self.valid_per_shard is not None:
            return jnp.asarray(self.valid_per_shard, jnp.int32)
        g = self.g
        counts = np.clip(g.num_nodes - np.arange(g.num_shards)
                         * g.nodes_per_shard, 0, g.nodes_per_shard)
        return jnp.asarray(counts, jnp.int32)

    def _sorted_edge_view(self):
        """Per-shard lex-sorted (row, dst) view for strict negative
        checks; built once, cached (device arrays, sharded)."""
        if getattr(self, "_sorted_view", None) is None:
            gspec = P(self.axis_name)
            fn = jax.jit(jax.shard_map(
                lambda ip, ix: tuple(
                    a[None] for a in build_sorted_edge_view(ip[0], ix[0])),
                mesh=self.mesh, in_specs=(gspec, gspec),
                out_specs=(gspec, gspec), check_vma=False))
            self._sorted_view = fn(self.g.indptr, self.g.indices)
        return self._sorted_view

    def sample_from_edges(self, src: jnp.ndarray, dst: jnp.ndarray,
                          neg_sampling: Optional[NegativeSampling] = None,
                          key: Optional[jax.Array] = None,
                          strict: bool = False,
                          trials: int = 4) -> SamplerOutput:
        """Distributed seed-edge sampling; negatives non-strict by default.

        ``src`` / ``dst``: ``[S, B]`` global endpoint ids per shard (-1
        padded).  The reference's distributed engine is always non-strict
        (dist_neighbor_sampler.py:327-453: "we use non-strict negative
        sampling in distributed mode"); here ``strict=True`` goes beyond
        it: candidate pairs are routed to the shard owning the source and
        checked against its CSR block (:func:`dist_edge_exists`) over
        ``trials`` rejection rounds, with the reference's non-strict
        padding pass for slots that never clear
        (random_negative_sampler.cu:153-160).  Returns a per-shard
        :class:`SamplerOutput` whose metadata carries ``edge_label_index``
        + ``edge_label`` (binary/None) or the triplet indices.
        """
        if key is None:
            key = self._next_key()
        mode = None if neg_sampling is None else neg_sampling.mode
        amount = (0 if neg_sampling is None
                  else int(round(neg_sampling.amount)))
        strict = bool(strict) and mode is not None
        fn = self._get_edges_fn(mode, amount, int(src.shape[1]), strict,
                                trials)
        g = self.g
        if strict:
            rows_s, dsts_s = self._sorted_edge_view()
            return fn(g.indptr, g.indices, g.edge_ids, rows_s, dsts_s,
                      src, dst, key)
        return fn(g.indptr, g.indices, g.edge_ids, src, dst, key)

    def _get_edges_fn(self, mode, amount, q, strict=False, trials=4):
        k = (mode, amount, q, strict, trials)
        if k not in self._edges_fns:
            gspec = P(self.axis_name)

            if strict:
                def local(indptr, indices, eids, rows_s, dsts_s, src, dst,
                          key):
                    out = self._edges_body(
                        mode, amount, q, indptr[0], indices[0], eids[0],
                        src[0], dst[0], key,
                        strict_view=(rows_s[0], dsts_s[0]), trials=trials)
                    return jax.tree.map(lambda x: x[None], out)

                specs = (gspec,) * 7 + (P(),)
            else:
                def local(indptr, indices, eids, src, dst, key):
                    out = self._edges_body(mode, amount, q, indptr[0],
                                           indices[0], eids[0], src[0],
                                           dst[0], key)
                    return jax.tree.map(lambda x: x[None], out)

                specs = (gspec,) * 5 + (P(),)

            self._edges_fns[k] = jax.jit(jax.shard_map(
                local, mesh=self.mesh, in_specs=specs,
                out_specs=gspec, check_vma=False))
        return self._edges_fns[k]

    def _edges_body(self, mode, amount, q, indptr, indices, eids, src, dst,
                    key, strict_view=None, trials=4):
        key = jax.random.fold_in(key, lax.axis_index(self.axis_name))
        kneg, ksample = jax.random.split(key)
        counts = self._valid_per_shard()
        c = self.g.nodes_per_shard
        s_count = self.g.num_shards

        def uniform_ids(k, n):
            """Uniform over valid (relabeled) ids: pick a shard, then a
            row modulo that shard's valid count."""
            ks, ku = jax.random.split(k)
            sh = jax.random.randint(ks, (n,), 0, s_count, dtype=jnp.int32)
            # Draw over the full int31 range before the modulo so the bias
            # toward low rows is O(count / 2^31) instead of O(count / c).
            u = jax.random.randint(ku, (n,), 0, jnp.int32(2**31 - 1),
                                   dtype=jnp.int32)
            return sh * c + u % jnp.maximum(counts[sh], 1)

        def strict_pairs(k, n, valid, fixed_src=None):
            """``trials`` routed rejection rounds + non-strict padding."""
            rows_s, dsts_s = strict_view
            best_s = jnp.full((n,), PADDING_ID, jnp.int32)
            best_d = jnp.full((n,), PADDING_ID, jnp.int32)
            found = jnp.zeros((n,), bool)
            last_s = last_d = None
            for t in range(trials):
                ks_, kd_ = jax.random.split(jax.random.fold_in(k, t))
                s = (fixed_src if fixed_src is not None
                     else uniform_ids(ks_, n))
                d = uniform_ids(kd_, n)
                ex = dist_edge_exists(
                    rows_s, dsts_s, jnp.where(valid, s, PADDING_ID), d,
                    c, s_count, self.axis_name, route=self.route,
                    fused=self.fused)
                take = valid & ~found & ~ex
                best_s = jnp.where(take, s, best_s)
                best_d = jnp.where(take, d, best_d)
                found = found | take
                last_s, last_d = s, d
            # Padding pass: never-cleared slots keep their last draw
            # (possibly positive) so the output is always full width.
            pad = valid & ~found
            best_s = jnp.where(pad, last_s, best_s)
            best_d = jnp.where(pad, last_d, best_d)
            return best_s, best_d

        if mode == "binary":
            rep = jnp.repeat(src >= 0, amount)
            if strict_view is not None:
                neg_src, neg_dst = strict_pairs(kneg, q * amount, rep)
            else:
                ks, kd = jax.random.split(kneg)
                neg_src = uniform_ids(ks, q * amount)
                neg_dst = uniform_ids(kd, q * amount)
            neg_src = jnp.where(rep, neg_src, PADDING_ID)
            neg_dst = jnp.where(rep, neg_dst, PADDING_ID)
            seeds = jnp.concatenate([src, dst, neg_src, neg_dst])
        elif mode == "triplet":
            rep = jnp.repeat(src >= 0, amount)
            if strict_view is not None:
                src_rep = jnp.repeat(src, amount)
                _, neg_dst = strict_pairs(kneg, q * amount, rep,
                                          fixed_src=src_rep)
            else:
                neg_dst = uniform_ids(kneg, q * amount)
            neg_dst = jnp.where(rep, neg_dst, PADDING_ID)
            seeds = jnp.concatenate([src, dst, neg_dst])
        else:
            seeds = jnp.concatenate([src, dst])

        out = dist_sample_multi_hop(
            indptr, indices, eids, seeds, ksample, self.num_neighbors,
            c, s_count, self.axis_name, self.frontier_cap, self.collective,
            last_hop_dedup=self.last_hop_dedup,
            exchange_load_factor=self.exchange_load_factor,
            route=self.route, fused=self.fused)

        # Seed ids first-occur in the hop-0 prefix; relabel against that
        # slice only (the no-dedup leaf block may repeat seed ids).
        ref = out.node[: seeds.shape[0]]
        meta = dict(out.metadata or {})
        if mode == "binary":
            all_src = jnp.concatenate([src, neg_src])
            all_dst = jnp.concatenate([dst, neg_dst])
            meta["edge_label_index"] = jnp.stack([
                relabel_by_reference(ref, all_src),
                relabel_by_reference(ref, all_dst)])
            pos_label = jnp.where(src >= 0, 1, PADDING_ID)
            meta["edge_label"] = jnp.concatenate(
                [pos_label, jnp.zeros((q * amount,), jnp.int32)])
        elif mode == "triplet":
            meta["src_index"] = relabel_by_reference(ref, src)
            meta["dst_pos_index"] = relabel_by_reference(ref, dst)
            meta["dst_neg_index"] = relabel_by_reference(
                ref, neg_dst).reshape(q, amount)
        else:
            meta["edge_label_index"] = jnp.stack([
                relabel_by_reference(ref, src),
                relabel_by_reference(ref, dst)])
        out.metadata = meta
        return out

    # -- distributed subgraph (cf. dist_neighbor_sampler.py:456-516) -------
    def subgraph(self, seeds_per_shard: jnp.ndarray, max_degree: int = 64,
                 key: Optional[jax.Array] = None) -> SamplerOutput:
        """Hop expansion + distributed induced-subgraph extraction.

        Each shard's node set is collected by the multi-hop exchange, then
        every member's (capped) adjacency row is fetched from its owner
        shard and filtered to the set — all inside one jitted program.
        """
        if key is None:
            key = self._next_key()
        fn = self._get_subgraph_fn(int(max_degree))
        g = self.g
        return fn(g.indptr, g.indices, g.edge_ids, seeds_per_shard, key)

    def _get_subgraph_fn(self, max_degree):
        if max_degree not in self._subgraph_fns:
            gspec = P(self.axis_name)

            def local(indptr, indices, eids, seeds, key):
                key = jax.random.fold_in(key, lax.axis_index(self.axis_name))
                # Always exact dedup here: the induced extract relabels
                # against a unique node set (cf. NeighborSampler.subgraph).
                base = dist_sample_multi_hop(
                    indptr[0], indices[0], eids[0], seeds[0], key,
                    self.num_neighbors, self.g.nodes_per_shard,
                    self.g.num_shards, self.axis_name, self.frontier_cap,
                    self.collective, last_hop_dedup=True,
                    route=self.route, fused=self.fused)
                rows, cols, se, mask = dist_node_subgraph(
                    indptr[0], indices[0], eids[0], base.node, max_degree,
                    self.g.nodes_per_shard, self.g.num_shards,
                    self.axis_name, route=self.route, fused=self.fused)
                out = SamplerOutput(
                    node=base.node, row=rows, col=cols, edge=se,
                    batch=seeds[0], node_mask=base.node_mask,
                    edge_mask=mask,
                    num_sampled_nodes=base.num_sampled_nodes,
                    metadata={"mapping": jnp.arange(self.batch_size,
                                                    dtype=jnp.int32)})
                return jax.tree.map(lambda x: x[None], out)

            self._subgraph_fns[max_degree] = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(gspec, gspec, gspec, gspec, P()),
                out_specs=gspec, check_vma=False))
        return self._subgraph_fns[max_degree]
