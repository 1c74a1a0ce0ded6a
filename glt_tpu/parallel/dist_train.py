"""Fully-fused distributed training step: sample + gather + SGD in one jit.

The reference's distributed training loop spans four process fleets —
sampling workers, shm channels, RPC feature servers, and DDP trainers
(SURVEY §3.2).  On TPU the entire iteration is **one XLA program over the
mesh**: per-shard all-to-all neighbor sampling
(:func:`~glt_tpu.parallel.dist_sampler.dist_sample_multi_hop`), all-to-all
feature/label gather (:func:`~glt_tpu.parallel.dist_feature.exchange_gather`),
model forward/backward, and a gradient ``pmean`` (the NCCL-allreduce analog,
examples/distributed/dist_train_sage_supervised.py:52-58).  Each mesh device
plays both roles of the reference's collocated mode (dist_loader.py:142-186):
graph-shard owner and data-parallel trainer.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.step import (TrainState, gated_update, graph_inputs,
                           loss_and_grads, seed_loss)
from ..models.train import node_seed_blocks
from ..sampler.neighbor_sampler import hop_bounds
from ..typing import PADDING_ID
from .dist_feature import (
    TieredShardedFeature,
    HostColdStore,
    _exchange,
    exchange_gather,
    route_cold_requests,
)
from ..obs import metrics as _metrics
from .dist_sampler import (DistNeighborSampler, _topology_choice,
                           dist_live_counters, dist_sample_multi_hop,
                           exchange_byte_model,
                           hier_request_cap, mesh_axis_sizes,
                           resolve_mesh_axes)
from .sharding import ShardedFeature, ShardedGraph


def dist_step_byte_model(nodes_per_shard, num_shards, num_neighbors,
                         batch_size, frontier_cap, feature_dim, axis_name,
                         mesh_shape, route="auto", hier_load_factor=None,
                         elem_bytes=4):
    """Static per-device collective bytes for ONE dist train step.

    Sums :func:`~glt_tpu.parallel.dist_sampler.exchange_byte_model` over
    the step's exchanges — one per sampling hop (id request + fanout
    neighbor/edge-id payload) plus the fused feature+label gather over
    the node capacity — and splits the total by fabric.  Returns
    ``{"ici": bytes, "dcn": bytes, "topology": 'flat'|'hier'}``.  On a
    1-D mesh everything is attributed to ICI (there is no host axis to
    split on); the numbers are what the
    ``glt.dist.collective_bytes{axis=}`` counters accumulate per step.
    """
    from ..sampler.neighbor_sampler import hop_widths, max_sampled_nodes

    topo = _topology_choice(route, axis_name, mesh_shape)
    if isinstance(axis_name, str) or mesh_shape is None:
        h, c = 1, int(num_shards)
    else:
        h, c = int(mesh_shape[0]), int(mesh_shape[1])
    widths = hop_widths(batch_size, list(num_neighbors), frontier_cap)
    node_cap = max_sampled_nodes(batch_size, list(num_neighbors),
                                 frontier_cap)
    ici = dcn = 0
    for w, fo in zip(widths, num_neighbors):
        hc = hier_request_cap(w, c, nodes_per_shard, hier_load_factor)
        i, d = exchange_byte_model(topo, h, c, w, 2 * fo, hier_cap=hc,
                                   elem_bytes=elem_bytes)
        ici += i
        dcn += d
    hc = hier_request_cap(node_cap, c, nodes_per_shard, hier_load_factor)
    i, d = exchange_byte_model(topo, h, c, node_cap, feature_dim + 1,
                               hier_cap=hc, elem_bytes=elem_bytes)
    return {"ici": ici + i, "dcn": dcn + d, "topology": topo}


def _byte_counters(byte_model):
    """The per-axis collective byte counters a step increments per call."""
    c_ici = _metrics.counter(
        "glt.dist.collective_bytes",
        "static per-device collective bytes moved by dist train steps, "
        "split by fabric (from the routing plan's shapes)",
        labels={"axis": "ici"})
    c_dcn = _metrics.counter(
        "glt.dist.collective_bytes",
        "static per-device collective bytes moved by dist train steps, "
        "split by fabric (from the routing plan's shapes)",
        labels={"axis": "dcn"})

    def record(steps=1):
        c_ici.inc(float(byte_model["ici"] * steps))
        c_dcn.inc(float(byte_model["dcn"] * steps))
    return record


def _sharded_step(tx, mesh, axis_name, local_grads, any_valid,
                  per_shard: int = 0):
    """The wrapper of every step that takes its gradients inside a
    ``shard_map`` and updates outside it: ``step(arrays, state, batch,
    key) -> (state, loss, acc)``, one jit.

    ``local_grads(arrays, batch, params, key) -> (loss, acc, grads)`` sees
    one shard's blocks (leading axis stripped) and ``key`` folded with
    the shard's index, and returns mesh means; ``any_valid(batch)`` gates
    the replicated update (:func:`~glt_tpu.models.step.gated_update`).
    With ``per_shard`` it returns that many arrays more, each shard's
    own (its counts), and the step returns them stacked ``[S, ...]``
    behind ``acc``.
    The sharded ``arrays`` ride as jit ARGUMENTS, not closure captures:
    multi-host global arrays span non-addressable devices and may not be
    closed over.
    """
    update = gated_update(tx)

    def local_body(arrays, batch, params, key):
        arrays, batch = jax.tree.map(lambda a: a[0], (arrays, batch))
        key = jax.random.fold_in(key, lax.axis_index(axis_name))
        loss, acc, grads, *mine = local_grads(arrays, batch, params, key)
        return (loss, acc, grads) + tuple(m[None] for m in mine)

    shard_fn = jax.shard_map(
        local_body, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(), P()),
        out_specs=(P(), P(), P()) + (P(axis_name),) * per_shard,
        check_vma=False)

    @jax.jit
    def _step(arrays, state: TrainState, batch, key: jax.Array):
        loss, acc, grads, *mine = shard_fn(arrays, batch, state.params,
                                           key)
        return (update(state, grads, any_valid(batch)), loss, acc, *mine)

    return _step


def _any_seed(seeds):
    return jnp.sum((seeds >= 0).astype(jnp.int32)) > 0


def _exchange_xy(axis_name, mesh_shape, label_space, route,
                 hier_load_factor, dedup: bool = False,
                 fused_frontier: str = "off"):
    """The exchange gathers of the distributed steps, closed over the
    routing options: ``gather_x(node, rows, space, staged) -> (x,
    counts)`` and ``gather_xy(node, rows, labels, space, staged) -> (x, y,
    counts)``, ``counts`` the serving shard's ``[served, read]`` request
    slots of the feature rows' exchange
    (:func:`~glt_tpu.parallel.dist_feature._request_rows`).

    ``space = (nodes_per_shard, hot_per_shard, num_shards)`` of the
    table; ``staged = (rows, slots)`` is the compact host staging of its
    cold rows, ``None`` for a table whole in HBM.  Features and labels
    ride ONE routing plan, id collective and served read
    (:func:`~glt_tpu.parallel.dist_feature.exchange_gather_xy`) when the
    table's id space is the labels' ``label_space = (per_shard,
    num_shards)`` (always true for shard_graph/shard_feature over the
    same node set).  ``dedup`` routes unique ids through every exchange
    and scatters back, ``fused_frontier`` selects the serving-side fused
    dedup+gather kernel on the feature rows of a table whole in HBM; both
    bit-identical.  ``y`` is -1 off the node list.
    """
    kw = dict(route=route, mesh_shape=mesh_shape,
              hier_load_factor=hier_load_factor)

    def gather_x(node, rows, space, staged):
        c, h, s = space
        if staged is None:
            x, _, counts = _exchange(node, rows, None, c, s, axis_name,
                                     dedup=dedup,
                                     fused_frontier=fused_frontier, **kw)
        else:
            x, _, counts = _exchange(node, rows, None, c, s, axis_name,
                                     hot_per_shard=h, staged_rows=staged[0],
                                     staged_slots=staged[1], dedup=dedup,
                                     **kw)
        return x, counts

    def gather_xy(node, rows, labels, space, staged):
        c, h, s = space
        if (c, s) == label_space:
            srows, sslots = staged or (None, None)
            x, y, counts = _exchange(
                node, rows, labels, c, s, axis_name, hot_per_shard=h,
                staged_rows=srows, staged_slots=sslots, dedup=dedup,
                fused_frontier=fused_frontier, **kw)
        else:
            x, counts = gather_x(node, rows, space, staged)
            y = exchange_gather(node, labels[:, None].astype(jnp.int32),
                                *label_space, axis_name, dedup=dedup,
                                **kw)[:, 0]
        return x, jnp.where(node >= 0, y, PADDING_ID), counts

    return gather_x, gather_xy


def _feature_space(f):
    """``(rows, (nodes_per_shard, hot_per_shard, num_shards))`` of a
    :class:`ShardedFeature` or :class:`TieredShardedFeature`."""
    if isinstance(f, TieredShardedFeature):
        return f.hot, (f.nodes_per_shard, f.hot_per_shard, f.num_shards)
    return f.rows, (f.nodes_per_shard, f.nodes_per_shard, f.num_shards)


def _any_seed_of(out):
    batch = out.batch
    return _any_seed(batch[out.input_type] if isinstance(batch, dict)
                     else batch)


def _dist_local_grads(model, g, f, mesh, num_neighbors, batch_size,
                      axis_name, frontier_cap, last_hop_dedup,
                      exchange_load_factor, dedup_gather, route, fused,
                      fused_frontier, hier_load_factor):
    """What :func:`make_dist_train_step` and its scanned twin share:
    ``(axis_name, byte_model, live, local_grads)`` with
    ``local_grads(arrays, seeds, params, key) -> (loss, acc, grads,
    live_counts)`` one shard's sample, gather, forward and backward,
    meaned over the mesh, and the sample's ``live_counts``, which the
    step defers into ``live``
    (:func:`~glt_tpu.parallel.dist_sampler.dist_live_counters`).  ``key``
    draws the sample AND the dropout mask."""
    axis_name = resolve_mesh_axes(mesh, axis_name)
    mesh_shape = mesh_axis_sizes(mesh, axis_name)
    _, gather_xy = _exchange_xy(
        axis_name, mesh_shape, (g.nodes_per_shard, g.num_shards), route,
        hier_load_factor, dedup=dedup_gather,
        fused_frontier=fused_frontier)
    space = _feature_space(f)[1]
    byte_model = dist_step_byte_model(
        g.nodes_per_shard, g.num_shards, num_neighbors, batch_size,
        frontier_cap, f.rows.shape[-1], axis_name, mesh_shape,
        route=route, hier_load_factor=hier_load_factor)
    grads_of = loss_and_grads(
        model, seed_loss(batch_size),
        hop_bounds(batch_size, num_neighbors, frontier_cap),
        mean_over=axis_name)
    live = dist_live_counters(
        batch_size, num_neighbors, g.num_shards, frontier_cap,
        exact=exchange_load_factor is None and _topology_choice(
            route, axis_name, mesh_shape) == "flat")

    def local_grads(arrays, seeds, params, key):
        indptr, indices, edge_ids, rows, labels_blk = arrays
        out = dist_sample_multi_hop(
            indptr, indices, edge_ids, seeds, key, num_neighbors,
            g.nodes_per_shard, g.num_shards, axis_name, frontier_cap,
            last_hop_dedup=last_hop_dedup,
            exchange_load_factor=exchange_load_factor,
            route=route, fused=fused, mesh_shape=mesh_shape,
            hier_load_factor=hier_load_factor)
        x, y, served = gather_xy(out.node, rows, labels_blk, space, None)
        edge_index, edge_mask, aux = graph_inputs(out)
        return grads_of(params, x, edge_index, edge_mask, y, aux, key) + (
            jnp.concatenate([out.live_counts, served]),)

    return axis_name, byte_model, live, local_grads


def make_dist_train_step(
    model,
    tx,
    g: ShardedGraph,
    f: ShardedFeature,
    labels: jnp.ndarray,          # [S, nodes_per_shard] int labels
    mesh: Mesh,
    num_neighbors: Sequence[int],
    batch_size: int,
    axis_name: Optional[str] = None,
    frontier_cap: Optional[int] = None,
    last_hop_dedup: bool = True,
    exchange_load_factor: Optional[float] = None,
    dedup_gather: bool = False,
    route: str = "auto",
    fused: Optional[bool] = None,
    fused_frontier: str = "off",
    hier_load_factor: Optional[float] = None,
):
    """Build ``step(state, seeds [S, B], key) -> (state, loss, acc)``.

    ``seeds`` carries one seed batch per shard (the per-rank disjoint seed
    split of dist_train_sage_supervised.py:76); params/opt state are
    replicated; gradients are ``pmean``-ed across the mesh.  The model is
    trimmed to ``hop_bounds(batch_size, num_neighbors, frontier_cap)``
    where it can be.

    ``last_hop_dedup=False`` selects the leaf-block final hop (see
    NeighborSampler) — seed rows stay in the compact interior prefix, so
    the objective is unchanged.  ``exchange_load_factor``, ``route``,
    ``fused`` and ``hier_load_factor`` are
    :func:`~glt_tpu.parallel.dist_sampler.dist_sample_multi_hop`'s
    (``route`` and ``hier_load_factor`` the gather's too,
    :func:`_exchange_xy`); ``dedup_gather`` routes unique
    node ids through the feature/label exchange — pair it with
    ``last_hop_dedup=False``, whose leaf blocks repeat hub nodes;
    ``fused_frontier`` != 'off' serves each shard's landed feature
    requests through the one-dispatch dedup+gather kernel
    (:func:`~glt_tpu.parallel.dist_feature._request_rows`).  All leave
    the batch bit-identical.

    ``axis_name=None`` resolves to the mesh's own axes — the 1-D
    ``global_mesh`` name or the 2-D ``global_mesh_2d`` tuple, over which
    sampling and gather ride the hierarchical dedup-then-exchange
    topology when ``route`` resolves 'hier' (bit-identical to 'flat').
    The returned step carries its static ``step.collective_bytes``
    ICI/DCN byte model and feeds the ``glt.dist.collective_bytes{axis=}``
    counters per call.
    """
    axis_name, byte_model, live, local_grads = _dist_local_grads(
        model, g, f, mesh, num_neighbors, batch_size, axis_name,
        frontier_cap, last_hop_dedup, exchange_load_factor, dedup_gather,
        route, fused, fused_frontier, hier_load_factor)
    record_bytes = _byte_counters(byte_model)
    _step = _sharded_step(tx, mesh, axis_name, local_grads, _any_seed,
                          per_shard=1)

    def step(state: TrainState, seeds: jnp.ndarray, key: jax.Array):
        record_bytes()
        state, loss, acc, counts = _step(
            (g.indptr, g.indices, g.edge_ids, f.rows, labels), state,
            seeds, key)
        _metrics.defer(live.counters, counts, live.per_row)   # [S, C]
        return state, loss, acc

    step.collective_bytes = byte_model
    return step


def make_scanned_dist_train_step(
    model,
    tx,
    g: ShardedGraph,
    f: ShardedFeature,
    labels: jnp.ndarray,          # [S, nodes_per_shard] int labels
    mesh: Mesh,
    num_neighbors: Sequence[int],
    batch_size: int,
    axis_name: Optional[str] = None,
    frontier_cap: Optional[int] = None,
    last_hop_dedup: bool = True,
    exchange_load_factor: Optional[float] = None,
    dedup_gather: bool = False,
    route: str = "auto",
    fused: Optional[bool] = None,
    fused_frontier: str = "off",
    hier_load_factor: Optional[float] = None,
):
    """:func:`make_dist_train_step` under ``lax.scan`` INSIDE one
    ``shard_map`` program (same options): ``G`` consecutive distributed
    batches, update included, so intermediate ids and the updated
    replicated state never round-trip through host dispatch between
    batches.

    Returns ``step(state, seeds_blk [G, S, B], key) -> (state,
    losses [G], accs [G])``.  Per-slot keys follow the homo scan
    convention (``jax.random.split(key, G)``, then the per-shard
    ``fold_in(axis_index)`` of the serial step), and a fully padded
    slot (every shard's seeds all ``-1``) is an exact no-op: the gate
    reads a global count, so every shard takes the same branch.  On a 2-D
    mesh the scan body traces the hierarchical exchange ONCE — the
    topology choice is static, so scanning over ``dist_seed_blocks``
    recompiles nothing.
    """
    axis_name, byte_model, live, local_grads = _dist_local_grads(
        model, g, f, mesh, num_neighbors, batch_size, axis_name,
        frontier_cap, last_hop_dedup, exchange_load_factor, dedup_gather,
        route, fused, fused_frontier, hier_load_factor)
    record_bytes = _byte_counters(byte_model)
    update = gated_update(tx)
    gspec = P(axis_name)

    def local_body(indptr, indices, edge_ids, rows, labels_blk,
                   seeds_blk, state: TrainState, keys):
        arrays = (indptr[0], indices[0], edge_ids[0], rows[0],
                  labels_blk[0])
        seeds_blk = seeds_blk[:, 0]          # [G, B] local slice
        me = lax.axis_index(axis_name)

        def body(carry, inp):
            st, = carry
            seeds, k = inp
            loss, acc, grads, counts = local_grads(
                arrays, seeds, st.params, jax.random.fold_in(k, me))
            nvalid = lax.psum(jnp.sum((seeds >= 0).astype(jnp.int32)),
                              axis_name)
            return (update(st, grads, nvalid > 0),), (loss, acc, counts)

        (state,), (losses, accs, counts) = lax.scan(body, (state,),
                                                    (seeds_blk, keys))
        return state, losses, accs, counts[:, None]

    shard_fn = jax.shard_map(
        local_body, mesh=mesh,
        in_specs=(gspec, gspec, gspec, gspec, gspec, P(None, axis_name),
                  P(), P()),
        out_specs=(P(), P(), P(), P(None, axis_name)),
        check_vma=False)

    # Global arrays as jit arguments (multi-host: no closure capture).
    @jax.jit
    def _step(indptr, indices, edge_ids, rows, labels_blk,
              state: TrainState, seeds_blk: jnp.ndarray, key: jax.Array):
        keys = jax.random.split(key, seeds_blk.shape[0])
        return shard_fn(indptr, indices, edge_ids, rows, labels_blk,
                        seeds_blk, state, keys)

    def step(state: TrainState, seeds_blk: jnp.ndarray, key: jax.Array):
        seeds_blk = jnp.asarray(seeds_blk, jnp.int32)
        record_bytes(int(seeds_blk.shape[0]))
        state, losses, accs, counts = _step(
            g.indptr, g.indices, g.edge_ids, f.rows, labels, state,
            seeds_blk, key)
        _metrics.defer(live.counters, counts, live.per_row)   # [G, S, C]
        return state, losses, accs

    step.collective_bytes = byte_model
    return step


def dist_seed_blocks(train_idx, num_shards: int, batch_size: int,
                     group: int, rng):
    """Shuffled ``[G, S, B]`` seed blocks, -1 padded — the epoch feed
    for :func:`make_scanned_dist_train_step` (each scan slot carries one
    disjoint per-shard seed batch; trailing slots may be fully padded
    no-ops)."""
    for blk in node_seed_blocks(train_idx, num_shards * batch_size, group,
                                rng):
        yield blk.reshape(group, num_shards, batch_size)


def run_scanned_dist_epoch(step, state, train_idx, num_shards: int,
                           batch_size: int, group: int, rng,
                           base_key, start_block: int = 0,
                           on_block=None):
    """One fused epoch through :func:`make_scanned_dist_train_step`.

    The dist twin of ``models.train.run_scanned_epoch``: shuffles
    ``train_idx`` into ``[G, S, B]`` blocks, drives one program dispatch
    per block, and reduces losses/accs with ONE device concat + ONE host
    fetch.  Returns ``(state, losses [n_real], accs [n_real])`` as host
    numpy; ``n_real`` counts real (non-padded) scan slots.  Block ``i``
    always runs under ``fold_in(base_key, i)`` — pure in its absolute
    position — so ``start_block``/``on_block`` give the same
    bit-identical resume seam as the homo driver.
    """
    blocks = list(dist_seed_blocks(train_idx, num_shards, batch_size,
                                   group, rng))
    n_real = -(-len(train_idx) // (batch_size * num_shards))
    n_real = max(0, n_real - int(start_block) * group)
    losses, accs = [], []
    for i, blk in enumerate(blocks):
        if i < start_block:
            continue
        state, ls, acs = step(state, blk, jax.random.fold_in(base_key, i))
        losses.append(ls)
        accs.append(acs)
        if on_block is not None:
            # The hook may checkpoint: the sync is the point (post-block
            # exact state), not an accidental per-batch round trip.
            # gltlint: disable-next=dispatch-in-epoch-loop
            jax.block_until_ready(state)
            on_block(state, i)
    losses = (np.asarray(jax.device_get(jnp.concatenate(losses)))[:n_real]
              if losses else np.zeros((0,), np.float32))
    accs = (np.asarray(jax.device_get(jnp.concatenate(accs)))[:n_real]
            if accs else np.zeros((0,), np.float32))
    return state, losses, accs


def make_tiered_train_step(
    model,
    tx,
    g: ShardedGraph,
    f: TieredShardedFeature,
    labels: jnp.ndarray,          # [S, nodes_per_shard] int labels
    mesh: Mesh,
    batch_size: int,
    axis_name: Optional[str] = None,
    dedup_gather: bool = False,
    route: str = "auto",
    hier_load_factor: Optional[float] = None,
):
    """Build the train half of the tiered two-stage pipeline.

    Returns ``train(state, out, staged, key) -> (state, loss, acc)``
    where ``out`` is the sample stage's per-shard :class:`SamplerOutput`
    and ``staged = (rows, slots)`` is the COMPACT responder-side cold
    staging: shard ``s``'s ``rows[s] [cold_cap, d]`` hold host-gathered
    cold rows for its incoming request slots ``slots[s]``
    (:func:`route_cold_requests` -> :func:`compact_cold_requests` ->
    :meth:`HostColdStore.serve`), so each pod host stages only rows its
    own shards own and host->device bytes scale with actual cold traffic.
    Hot rows ride the in-jit all-to-all; cold rows are scattered into the
    response leg — the per-row HBM/host split the reference's
    UnifiedTensor makes inside its gather kernel (unified_tensor.cu:48-81).

    ``dedup_gather`` must match the :class:`TieredTrainPipeline`'s flag:
    the staged cold rows are keyed to the (possibly deduped) request
    layout.  The model runs whole (the sample stage's layout is not
    handed over); the dropout key is the per-shard fold of ``key``.
    """
    axis_name = resolve_mesh_axes(mesh, axis_name)
    _, gather_xy = _exchange_xy(
        axis_name, mesh_axis_sizes(mesh, axis_name),
        (g.nodes_per_shard, g.num_shards), route, hier_load_factor,
        dedup=dedup_gather)
    hot, space = _feature_space(f)
    grads_of = loss_and_grads(model, seed_loss(batch_size),
                              mean_over=axis_name)

    def local_grads(arrays, batch, params, key):
        hot_rows, labels_blk = arrays
        out, staged = batch
        x, y, _ = gather_xy(out.node, hot_rows, labels_blk, space, staged)
        edge_index, edge_mask, aux = graph_inputs(out)
        return grads_of(params, x, edge_index, edge_mask, y, aux, key)

    _step = _sharded_step(tx, mesh, axis_name, local_grads,
                          lambda batch: _any_seed_of(batch[0]))

    def train(state: TrainState, out, staged, key: jax.Array):
        return _step((hot, labels), state, (out, tuple(staged)), key)

    return train


class _ColdStagePipeline:
    """Shared core of the two-stage (sample → host cold gather → train)
    pipelines: staging/gather thread pools, the locked drop-counter
    reduction, the double-buffered epoch loop, and shutdown.  Subclasses
    implement ``_stage_cold_async(out) -> Future[staged]``.

    The cold gather for batch ``k`` runs on a staging thread while the main
    thread trains batch ``k-1`` — steady-state step time ≈
    ``max(device compute, host cold gather)`` rather than their sum, the
    UVA-overlap property of the reference's UnifiedTensor
    (unified_tensor.cu:202-311) recovered at the pipeline level.  A thread
    (not jax async dispatch) carries the overlap so it holds on every
    backend, including the synchronous CPU emulation the tests run on.
    """

    @staticmethod
    def _device_put_copies() -> bool:
        """Whether ``device_put`` of a numpy array COPIES on this backend.

        Host staging buffers may only be reused across batches when the
        device array made from them does not alias the host memory;
        zero-copy backends must fall back to fresh per-batch buffers.
        Probed once: put, mutate the source, compare.  The probe array
        must be LARGE: CPU zero-copy aliasing only engages for
        sufficiently-aligned buffers, and large numpy allocations are
        page-aligned exactly like the real staging buffers — a small
        probe can land on an unaligned pointer and falsely report copy
        semantics.
        """
        src = np.full((1 << 18,), 1.0, np.float32)   # 1 MB, page-aligned
        arr = jax.device_put(src)
        src[:] = 2.0
        return bool((np.asarray(arr) == 1.0).all())

    def _staged_buffer(self, bufs: list, flip: int, inflight: list,
                       shape, dtype) -> np.ndarray:
        """Next staging buffer: reused (after syncing the consumer that
        read it two batches ago) when device_put copies, else fresh."""
        if not self._reuse_staged:
            return np.empty(shape, dtype)
        prev = inflight[flip]
        if prev is not None:
            # The batch that used this buffer fed its rows to the device
            # two iterations ago; wait for that transfer before the
            # overwrite (depth-2 ring + this sync = no aliasing window).
            jax.block_until_ready(prev)
        return bufs[flip]

    def _init_pools(self, stage_threads: Optional[int],
                    name: str) -> None:
        import concurrent.futures
        import os
        import threading

        self._reuse_staged = self._device_put_copies()
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"{name}-stage")
        # Gather workers: the host cold gather splits into (shard,
        # row-chunk) work items fanned across this pool (VERDICT r4 #5 —
        # the serial per-process stage dominated papers100M-shape steady
        # state).  numpy fancy indexing releases the GIL, so chunks scale
        # with host cores; a pod host sizes this to its core count.
        self.stage_threads = (max(1, os.cpu_count() or 1)
                              if stage_threads is None
                              else max(1, int(stage_threads)))
        self._gather_pool = (concurrent.futures.ThreadPoolExecutor(
            max_workers=self.stage_threads,
            thread_name_prefix=f"{name}-gather")
            if self.stage_threads > 1 else None)
        self._pending_dropped = []   # unreduced per-batch device counts
        self.dropped_total = 0       # host sum over all staged batches
        self._drop_lock = threading.Lock()  # staging thread vs caller

    def _record_dropped(self, dropped) -> None:
        # Accumulate lazily (device values; reduced on flush) so the
        # documented contract — "raise cold_cap if drops are ever
        # nonzero" — is checkable over a whole epoch without a per-batch
        # host sync.
        with self._drop_lock:
            self._pending_dropped.append(dropped)

    def _maybe_flush_on_stage_thread(self) -> None:
        # Periodic reduction rides the staging thread (it already blocks
        # on the route stage), never the main thread's critical path
        # (advisor r4 finding).
        if len(self._pending_dropped) >= 64:
            self.flush_dropped()

    def flush_dropped(self) -> int:
        """Reduce pending per-batch drop counters into ``dropped_total``."""
        with self._drop_lock:
            pending, self._pending_dropped = self._pending_dropped, []
        total = 0
        for d in pending:
            for leaf in jax.tree_util.tree_leaves(d):
                shards = getattr(leaf, "addressable_shards", None)
                if shards is not None:
                    total += int(sum(np.asarray(sh.data).sum()
                                     for sh in shards))
                else:
                    total += int(np.asarray(leaf).sum())
        with self._drop_lock:
            self.dropped_total += total
        return self.dropped_total

    def run_epoch(self, state: TrainState, seed_batches, key: jax.Array,
                  start_batch: int = 0, on_batch=None, supervisor=None):
        """Drive one epoch; ``seed_batches``: iterable of ``[S, B]`` seeds.

        Returns ``(state, losses, accs)`` (device scalars, unsynced).
        Check ``flush_dropped()`` after the epoch: nonzero means some
        cold requests overflowed the staging capacity and trained on
        zero rows.

        Preemption-safety seam (glt_tpu.ckpt): batch ``i`` always trains
        under keys folded from its absolute position, so resuming with
        ``start_batch=k`` (skipping the first ``k`` batches of a
        deterministic ``split_seeds`` schedule — thread the SAME
        epoch-rng state you checkpointed) replays the identical
        remaining stream.  ``on_batch(state, i)`` fires after each
        trained batch, synced — the checkpoint-cadence hook.
        ``supervisor`` (a :class:`~glt_tpu.distributed.supervisor.
        Supervisor`) is polled at the same boundary; a dead peer raises
        its structured :class:`~glt_tpu.distributed.supervisor.
        PeerDeadError` out of this loop for the caller's
        checkpoint-and-exit.
        """
        from . import multihost

        losses, accs = [], []
        pending = None  # (idx, out, cold future)
        n = 0

        def trained(i, state):
            if on_batch is None and supervisor is None:
                return
            jax.block_until_ready(state)
            if on_batch is not None:
                on_batch(state, i)
            if supervisor is not None:
                supervisor.raise_if_dead()

        for i, seeds in enumerate(seed_batches):
            if i < start_batch:
                continue
            kb = jax.random.fold_in(key, i)
            if not isinstance(seeds, jax.Array):
                # Per-host feed: every process holds the full [S, B] host
                # batch (deterministic split) and contributes its rows.
                # Host-side seeds, not a device fetch — this eager tiered
                # pipeline stages per batch BY DESIGN (the host cold
                # gather is the overlapped stage).
                # gltlint: disable-next=dispatch-in-epoch-loop
                seeds = multihost.feed_seeds(np.asarray(seeds), self.mesh,
                                             self.axis_name)
            out = self.sampler.sample_from_nodes(
                seeds, key=jax.random.fold_in(kb, 1))
            fut = self._stage_cold_async(out)
            if pending is not None:
                state, loss, acc = self.train_step(
                    state, pending[1], pending[2].result(),
                    jax.random.fold_in(kb, 2))
                losses.append(loss)
                accs.append(acc)
                trained(pending[0], state)
            pending = (i, out, fut)
            n = i + 1
        if pending is not None:
            state, loss, acc = self.train_step(
                state, pending[1], pending[2].result(),
                jax.random.fold_in(jax.random.fold_in(key, n), 2))
            losses.append(loss)
            accs.append(acc)
            trained(pending[0], state)
        # Epoch-boundary seam for tier-aware cold stores (glt_tpu.store):
        # a DiskColdStore snapshots + publishes its per-epoch glt.store.*
        # gauges here (bytes_from_dram/disk, hit rate, stage depth).
        pub = getattr(getattr(self, "cold_store", None),
                      "publish_epoch_stats", None)
        if pub is not None:
            pub()
        return state, losses, accs

    def close(self) -> None:
        self._pool.shutdown(wait=False)
        if self._gather_pool is not None:
            self._gather_pool.shutdown(wait=False)
        closer = getattr(getattr(self, "cold_store", None), "close", None)
        if closer is not None:
            closer()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class TieredTrainPipeline(_ColdStagePipeline):
    """Homogeneous two-stage pipeline (see :class:`_ColdStagePipeline`):
    jitted sample → host cold gather → jitted train, double-buffered."""

    def __init__(self, sampler: DistNeighborSampler,
                 train_step, f: TieredShardedFeature, mesh: Mesh,
                 axis_name: Optional[str] = None,
                 cold_store: Optional[HostColdStore] = None,
                 cold_cap: Optional[int] = None,
                 stage_threads: Optional[int] = None,
                 dedup_gather: bool = False,
                 route: str = "auto",
                 hier_load_factor: Optional[float] = None):
        from . import multihost
        from .dist_feature import compact_cold_requests

        self.sampler = sampler
        self.train_step = train_step
        self.f = f
        self.mesh = mesh
        axis_name = resolve_mesh_axes(mesh, axis_name)
        mesh_shape = mesh_axis_sizes(mesh, axis_name)
        self.axis_name = axis_name
        # Compact staging capacity: cold rows staged per responder shard
        # per batch.  Worst case is S * node_cap (every request cold and
        # aimed at one shard); the typical per-responder load is ~the
        # node capacity itself, so alpha=2 over it keeps drops rare.
        # Overflowed requests are served as zeros and counted in
        # ``last_dropped`` — raise cold_cap if it is ever nonzero.
        self.cold_cap = (2 * sampler.node_capacity if cold_cap is None
                         else int(cold_cap))
        # This process's contiguous shard block (all shards when
        # single-process); the cold store serves exactly these.
        self._local = multihost.local_shard_range(mesh, axis_name)
        if (cold_store is None and f.cold.shape[1] == 0
                and f.nodes_per_shard > f.hot_per_shard):
            # shard_feature_tiered_from_store leaves ``cold`` as a
            # zero-row placeholder: the cold tier lives on disk.  A
            # defaulted HostColdStore over it would serve silent zero
            # rows for every cold request — refuse instead.
            raise ValueError(
                "TieredShardedFeature has an empty host cold tier but "
                f"{f.nodes_per_shard - f.hot_per_shard} cold rows per "
                "shard — pass the DiskColdStore backing it as "
                "cold_store= (see docs/storage.md)")
        self.cold_store = cold_store or HostColdStore(
            f, shard_ids=self._local)
        self._init_pools(stage_threads, "glt-cold")
        self.last_dropped = None     # [S] device counts, latest batch
        # Observed per-shard cold-row peak — size cold_cap to this (+
        # margin) on a re-run to shrink the host->device feed.
        self.max_cold_rows = 0
        self._staged_bufs = [
            np.empty((len(self._local), self.cold_cap,
                      self.cold_store.dim), self.cold_store.dtype)
            for _ in range(2)]
        self._staged_flip = 0
        self._staged_inflight = [None, None]
        gspec = P(axis_name)

        def route_body(nodes):
            # dedup_gather must match the train step's flag: the staged
            # slots index the (possibly deduped) request layout.
            req = route_cold_requests(
                nodes[0], f.nodes_per_shard, f.hot_per_shard,
                f.num_shards, axis_name, dedup=dedup_gather, route=route,
                mesh_shape=mesh_shape, hier_load_factor=hier_load_factor)
            slots, ids, dropped = compact_cold_requests(req, self.cold_cap)
            return slots[None], ids[None], dropped[None]

        self._route = jax.jit(jax.shard_map(
            route_body, mesh=mesh, in_specs=(gspec,),
            out_specs=(gspec, gspec, gspec), check_vma=False))

    def _stage_cold_async(self, out):
        """Submit the cold staging for ``out.node``; returns a future.

        Route + compact (in-jit all_to_all) -> per-shard host gather of
        ONLY the compacted cold ids -> per-host feed of the
        ``[S, cold_cap, d]`` staged rows + their slot indices.  Each
        process serves only its local shards (all of them in the
        single-process emulation) and feeds only its slab of the global
        staged arrays — remote slabs are produced by their own hosts.
        """
        from . import multihost

        slots, ids, dropped = self._route(out.node)
        self.last_dropped = dropped
        self._record_dropped(dropped)

        def work():
            # Fetch only this host's addressable id rows (waits on the
            # route stage only).
            shards = sorted(ids.addressable_shards,
                            key=lambda sh: sh.index[0].start or 0)
            req = np.concatenate([np.asarray(sh.data) for sh in shards])
            # Staging buffer, never zeroed: rows at -1 slots are garbage
            # but the compact scatter drops them (exchange_gather_hot
            # mode="drop").  Reused across batches (page-resident) only
            # when device_put provably copies — see _staged_buffer; at
            # papers100M shape the per-batch 100+ MB zeroed alloc was a
            # measurable slice of the stage (VERDICT r4 #5).
            flip = self._staged_flip
            self._staged_flip ^= 1
            staged = self._staged_buffer(
                self._staged_bufs, flip, self._staged_inflight,
                (len(self._local), self.cold_cap, self.cold_store.dim),
                self.cold_store.dtype)
            self.max_cold_rows = max(self.max_cold_rows,
                                     int((req >= 0).sum(axis=1).max()))
            # Fan the gather across (shard, row-chunk) work items.
            futs = []
            for j, s in enumerate(self._local):
                futs += self.cold_store.serve_into(
                    staged[j], s, req[j], pool=self._gather_pool)
            for fu in futs:
                fu.result()
            self._maybe_flush_on_stage_thread()
            rows = multihost.assemble_global(staged, self.mesh,
                                             self.axis_name)
            self._staged_inflight[flip] = rows
            return rows, slots
        return self._pool.submit(work)


def init_dist_state(model, tx, g: ShardedGraph, f,
                    rng: jax.Array, num_neighbors: Sequence[int],
                    batch_size: int,
                    frontier_cap: Optional[int] = None) -> TrainState:
    """Initialize replicated params/opt-state with correctly-shaped dummies.

    ``f`` may be a :class:`ShardedFeature` or
    :class:`~glt_tpu.parallel.dist_feature.TieredShardedFeature`.
    """
    from ..sampler.neighbor_sampler import hop_widths, max_sampled_nodes

    cap = max_sampled_nodes(batch_size, list(num_neighbors), frontier_cap)
    widths = hop_widths(batch_size, list(num_neighbors), frontier_cap)
    ecap = sum(w * fo for w, fo in zip(widths, num_neighbors))

    rows = f.hot if isinstance(f, TieredShardedFeature) else f.rows
    x = jnp.zeros((cap, rows.shape[-1]), rows.dtype)
    ei = jnp.full((2, ecap), PADDING_ID, jnp.int32)
    mask = jnp.zeros((ecap,), bool)
    params = model.init({"params": rng}, x, ei, mask)
    return TrainState(params=params, opt_state=tx.init(params),
                      step=jnp.zeros((), jnp.int32))


def _typed_gather(sampler, feats, labels, axis_name, mesh, route,
                  hier_load_factor):
    """``(rows, gather)`` of the typed distributed steps: every type's
    device rows, and ``gather(out, rows_l, labels_l, staged) -> (x, y)``
    for one shard — each type's rows through its own exchange (``staged``:
    ``{type: (rows, slots)}`` of the tiered types), the seed type's with
    the labels (:func:`_exchange_xy`)."""
    tgt = sampler.input_type
    num_shards = next(iter(sampler.sharded.values())).num_shards
    gather_x, gather_xy = _exchange_xy(
        axis_name, mesh_axis_sizes(mesh, axis_name),
        (int(labels.shape[1]), num_shards), route, hier_load_factor)
    spaces = {t: _feature_space(f) for t, f in feats.items()}

    def gather(out, rows_l, labels_l, staged):
        x = {t: gather_x(out.node[t], rows_l[t], spaces[t][1],
                         staged.get(t))[0]
             for t in rows_l if t != tgt}
        x[tgt], y, _ = gather_xy(out.node[tgt], rows_l[tgt], labels_l,
                                 spaces[tgt][1], staged.get(tgt))
        return x, y

    return {t: sp[0] for t, sp in spaces.items()}, gather


def make_hetero_dist_train_step(
    model,
    tx,
    sampler,                      # DistHeteroNeighborSampler
    feats,                        # Dict[NodeType, ShardedFeature]
    labels: jnp.ndarray,          # [S, c_target] target-type labels
    mesh: Mesh,
    batch_size: int,
    axis_name: Optional[str] = None,
    route: str = "auto",
    hier_load_factor: Optional[float] = None,
):
    """Hetero analog of :func:`make_dist_train_step` (cf. the reference's
    igbh distributed run, examples/igbh/dist_train_rgat.py): hetero
    multi-hop exchange sampling, per-node-type all-to-all feature gather,
    R-GAT forward/backward, gradient pmean — one XLA program.

    ``model.edge_types`` must use the sampler's *reversed* output keys
    (``reverse_edge_type`` of the dataset's edge types), and
    ``model.target_type`` == ``sampler.input_type``.  The model runs
    whole; the per-shard key is split into a dropout and a sampling key.
    """
    axis_name = resolve_mesh_axes(mesh, axis_name)
    arrays = {et: (g.indptr, g.indices, g.edge_ids)
              for et, g in sampler.sharded.items()}
    rows, gather = _typed_gather(sampler, feats, labels, axis_name, mesh,
                                 route, hier_load_factor)
    grads_of = loss_and_grads(model, seed_loss(batch_size),
                              mean_over=axis_name)

    def local_grads(arrays_l, seeds, params, key):
        graph_l, rows_l, labels_l = arrays_l
        kdrop, ksample = jax.random.split(key)
        out = sampler.local_sample(graph_l, seeds, ksample)
        x, y = gather(out, rows_l, labels_l, {})
        edge_index, edge_mask, aux = graph_inputs(out)
        return grads_of(params, x, edge_index, edge_mask, y, aux, kdrop)

    _step = _sharded_step(tx, mesh, axis_name, local_grads, _any_seed)

    def step(state: TrainState, seeds: jnp.ndarray, key: jax.Array):
        return _step((arrays, rows, labels), state, seeds, key)

    return step


def make_hetero_tiered_train_step(
    model,
    tx,
    sampler,                      # DistHeteroNeighborSampler
    feats,                        # Dict[NodeType, Sharded|TieredSharded]
    labels: jnp.ndarray,          # [S, c_target] target-type labels
    mesh: Mesh,
    batch_size: int,
    axis_name: Optional[str] = None,
    route: str = "auto",
    hier_load_factor: Optional[float] = None,
):
    """Hetero analog of :func:`make_tiered_train_step` (VERDICT r4 #4):
    node types whose feature is a :class:`TieredShardedFeature` (e.g.
    IGBH paper features, ~350 GB — far past a v5e-16's HBM) gather their
    hot prefix in-jit and take cold rows from compact host staging;
    full-HBM types use the plain exchange.  Sampling happens OUTSIDE
    (two-stage pipeline: see :class:`HeteroTieredTrainPipeline`), exactly
    like the homo tiered step.

    Returns ``train(state, out, staged, key)`` with ``staged`` a dict
    ``{node_type: (rows [S, cold_cap, d], slots [S, cold_cap])}`` for the
    tiered types only.
    """
    axis_name = resolve_mesh_axes(mesh, axis_name)
    tiered = sorted(t for t, f in feats.items()
                    if isinstance(f, TieredShardedFeature))
    hot_rows, gather = _typed_gather(sampler, feats, labels, axis_name,
                                     mesh, route, hier_load_factor)
    grads_of = loss_and_grads(model, seed_loss(batch_size),
                              mean_over=axis_name)

    def local_grads(arrays_l, batch, params, key):
        hot_l, labels_l = arrays_l
        out, staged = batch
        x, y = gather(out, hot_l, labels_l, staged)
        edge_index, edge_mask, aux = graph_inputs(out)
        return grads_of(params, x, edge_index, edge_mask, y, aux, key)

    _step = _sharded_step(tx, mesh, axis_name, local_grads,
                          lambda batch: _any_seed_of(batch[0]))

    def train(state: TrainState, out, staged, key: jax.Array):
        return _step((hot_rows, labels), state,
                     (out, {t: tuple(staged[t]) for t in tiered}), key)

    return train


class HeteroTieredTrainPipeline(_ColdStagePipeline):
    """Hetero two-stage pipeline: jitted hetero sample → per-type host
    cold gather → jitted hetero train, double-buffered.

    The hetero twin of :class:`TieredTrainPipeline` (VERDICT r4 #4): each
    tiered node type routes + compacts its own cold requests (one jitted
    shard_map over the dict), the host gathers each type's compact id
    list (row-chunk parallel across ``stage_threads``), and the train
    step scatters every type's staged rows into its gather response.
    """

    def __init__(self, sampler, train_step, feats, mesh: Mesh,
                 axis_name: Optional[str] = None,
                 cold_caps=None,
                 stage_threads: Optional[int] = None,
                 route: str = "auto",
                 hier_load_factor: Optional[float] = None):
        from . import multihost
        from .dist_feature import compact_cold_requests

        self.sampler = sampler
        self.train_step = train_step
        self.mesh = mesh
        axis_name = resolve_mesh_axes(mesh, axis_name)
        mesh_shape = mesh_axis_sizes(mesh, axis_name)
        self.axis_name = axis_name
        self.tiered = {t: f for t, f in feats.items()
                       if isinstance(f, TieredShardedFeature)}
        cap_by_type = sampler.node_capacity
        self.cold_cap = {
            t: (2 * max(cap_by_type.get(t, 1), 1)
                if not cold_caps or t not in cold_caps else int(cold_caps[t]))
            for t in self.tiered}
        self._local = multihost.local_shard_range(mesh, axis_name)
        self.stores = {t: HostColdStore(f, shard_ids=self._local)
                       for t, f in self.tiered.items()}
        self._init_pools(stage_threads, "glt-hcold")
        # Per-type reused double buffers (see TieredTrainPipeline).
        self._staged_bufs = {
            t: [np.empty((len(self._local), self.cold_cap[t],
                          self.stores[t].dim), self.stores[t].dtype)
                for _ in range(2)]
            for t in self.tiered}
        self._staged_flip = 0
        self._staged_inflight = {t: [None, None] for t in self.tiered}
        self.max_cold_rows = {t: 0 for t in self.tiered}
        gspec = P(axis_name)
        tiered_types = sorted(self.tiered)

        def route_body(nodes_blk):
            slots, ids, dropped = {}, {}, {}
            for t in tiered_types:
                f = self.tiered[t]
                req = route_cold_requests(
                    nodes_blk[t][0], f.nodes_per_shard, f.hot_per_shard,
                    f.num_shards, axis_name, route=route,
                    mesh_shape=mesh_shape,
                    hier_load_factor=hier_load_factor)
                s, i, d = compact_cold_requests(req, self.cold_cap[t])
                slots[t], ids[t], dropped[t] = s[None], i[None], d[None]
            return slots, ids, dropped

        tspec = {t: gspec for t in tiered_types}
        self._route = jax.jit(jax.shard_map(
            route_body, mesh=mesh, in_specs=({t: gspec for t in tiered_types},),
            out_specs=(tspec, tspec, tspec), check_vma=False))

    def _stage_cold_async(self, out):
        from . import multihost

        nodes = {t: out.node[t] for t in self.tiered}
        slots, ids, dropped = self._route(nodes)
        self._record_dropped(dropped)

        def work():
            staged = {}
            futs = []
            arrs = {}
            flip = self._staged_flip
            self._staged_flip ^= 1
            for t in sorted(self.tiered):
                shards = sorted(ids[t].addressable_shards,
                                key=lambda sh: sh.index[0].start or 0)
                req = np.concatenate([np.asarray(sh.data)
                                      for sh in shards])
                st = self.stores[t]
                arr = self._staged_buffer(
                    self._staged_bufs[t], flip, self._staged_inflight[t],
                    (len(self._local), self.cold_cap[t], st.dim),
                    st.dtype)
                self.max_cold_rows[t] = max(
                    self.max_cold_rows[t],
                    int((req >= 0).sum(axis=1).max()))
                for j, s in enumerate(self._local):
                    futs += st.serve_into(arr[j], s, req[j],
                                          pool=self._gather_pool)
                arrs[t] = arr
            for fu in futs:
                fu.result()
            self._maybe_flush_on_stage_thread()
            for t, arr in arrs.items():
                rows = multihost.assemble_global(arr, self.mesh,
                                                 self.axis_name)
                self._staged_inflight[t][flip] = rows
                staged[t] = (rows, slots[t])
            return staged
        return self._pool.submit(work)


def init_hetero_dist_state(model, tx, sampler, feats,
                           rng: jax.Array) -> TrainState:
    """Replicated params/opt-state
    (:func:`~glt_tpu.models.train.init_hetero_state` over the sharded
    tables; ``feats`` values may be :class:`ShardedFeature` or
    :class:`TieredShardedFeature`)."""
    from ..models.train import init_hetero_state

    return init_hetero_state(
        model, tx, sampler,
        {t: _feature_space(f)[0] for t, f in feats.items()}, rng)
