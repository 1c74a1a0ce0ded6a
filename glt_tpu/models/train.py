"""Train and eval steps over sampled batches, and their epoch drivers.

The reference leaves training loops to user PyTorch code
(examples/train_sage_ogbn_products.py); here the step is part of the
framework, so that sample -> gather -> forward/backward -> update is one
XLA program.  Every factory in this file wraps the one body of
:mod:`~glt_tpu.models.step` and owns what differs: how a batch is
sampled and gathered, and whether the body runs eagerly on a loader's
``Batch`` or under a ``lax.scan`` over ``G`` seed batches (the canonical
epoch: :func:`make_scanned_node_train_step` + :func:`run_scanned_epoch`,
one host dispatch and one seed feed per ``G`` batches).  The supervised
loss is masked cross-entropy over the rows of real seeds, which lead the
node list by the sampler's first-occurrence contract.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from ..obs import compilewatch as _compilewatch
from ..obs import device as _device
from ..obs import flight as _flight
from ..obs import metrics as _metrics
from ..obs import profiler as _profiler
from ..obs.trace import span as _span
from ..typing import PADDING_ID
from .step import (TrainState, gated_update, graph_inputs,  # noqa: F401
                   hop_trimming, logit_bce_loss, loss_and_grads,
                   pair_bce_loss, seed_cross_entropy, seed_loss)

# Epoch-driver instrumentation (docs/observability.md).  Only the HOST
# loops are instrumented — the jitted step bodies must stay span-free
# (gltlint GLT010: a span inside a traced function runs once at trace
# time and vanishes from the compiled program).
_M_STEPS = _metrics.counter(
    "glt.train.steps", "train steps dispatched by the epoch drivers")
_M_EPOCHS = _metrics.counter(
    "glt.train.epochs", "scanned epochs driven")
_M_BLOCK_MS = _metrics.histogram(
    "glt.train.block_ms",
    "wall per [G, B] block: dispatch + (when a hook syncs) device wait")


def create_train_state(model, rng, sample_batch, tx) -> TrainState:
    params = model.init({"params": rng}, sample_batch.x,
                        sample_batch.edge_index, sample_batch.edge_mask)
    for leaf in jax.tree_util.tree_leaves(params):
        _device.register_owner("params", array=leaf)
    return TrainState(params=params, opt_state=tx.init(params),
                      step=jnp.zeros((), jnp.int32))


def _batch_inputs(batch):
    """:func:`~glt_tpu.models.step.graph_inputs` of a loader ``Batch``,
    which carries its seed ids and no per-hop counts: the rows that hold
    a seed are as many as the unique valid ids among them."""
    from ..ops.unique import unique_first_occurrence

    return batch.edge_index, batch.edge_mask, (
        batch.node_mask, unique_first_occurrence(batch.batch).count[None])


def _layout_of(hops, batch):
    """The layout among ``hops`` (one, several or none) that lays out
    ``batch``: the one with its node rows and edge slots.  A loader hands
    out batches of two shapes, its sampler's and the full-capacity
    sibling's that replays an overflowing batch, each under its own."""
    if hops is None:
        return None
    shape = (batch.node.shape[0], batch.edge_index.shape[1])
    layouts = [hops] if hasattr(hops, "node_bounds") else list(hops)
    for layout in layouts:
        if (layout.node_bounds[-1], layout.edge_bounds[-1]) == shape:
            return layout
    raise ValueError(
        f"no layout for a batch of {shape[0]} node rows and {shape[1]} "
        f"edge slots among {layouts}")


def _at_width(batch, batch_size: int):
    """``batch`` with its seed count set to the step's static width.  The
    count is static data of the ``Batch`` pytree, and a loader's trailing
    batch of an epoch has fewer seeds: left as it is, it would compile
    the step a second time.  The step reads the seed ids, never the
    count."""
    if getattr(batch, "batch_size", batch_size) == batch_size:
        return batch
    return dataclasses.replace(batch, batch_size=batch_size)


def make_train_step(model, tx, batch_size: int, dropout_seed: int = 0,
                    hops=None) -> Callable:
    """Build a jitted ``(state, batch) -> (state, loss, acc)`` step over a
    loader's ``Batch``.

    ``hops`` is the static layout of the loader's batches,
    ``sampler.hop_bounds`` (or several: the sampler's and its
    ``full_capacity_sibling()``'s, :func:`_layout_of` picks by the
    batch's shape when the step is traced): a model that trims runs
    trimmed to it as the scanned steps do
    (:func:`~glt_tpu.models.step.hop_trimming`), exact against the whole
    model.  Without a layout the model runs whole."""
    loss = seed_loss(batch_size)
    update = gated_update(tx)

    @jax.jit
    def step(state: TrainState, batch):
        rng = jax.random.fold_in(jax.random.PRNGKey(dropout_seed), state.step)
        edge_index, edge_mask, aux = _batch_inputs(batch)
        grads_of = loss_and_grads(model, loss, _layout_of(hops, batch))
        loss_v, acc, grads = grads_of(state.params, batch.x, edge_index,
                                      edge_mask, batch.y, aux, rng)
        return update(state, grads, jnp.any(batch.batch >= 0)), loss_v, acc

    def train_step(state: TrainState, batch):
        return step(state, _at_width(batch, batch_size))

    train_step.lower = lambda state, batch: step.lower(
        state, _at_width(batch, batch_size))
    return train_step


def make_eval_step(model, batch_size: int, hops=None) -> Callable:
    """``(params, batch) -> (loss, acc)``: the same loss on an
    evaluation-mode forward, trimmed to ``hops`` as
    :func:`make_train_step`'s."""
    loss = seed_loss(batch_size)

    @jax.jit
    def step(params, batch):
        edge_index, edge_mask, aux = _batch_inputs(batch)
        logits = model.apply(params, batch.x, edge_index, edge_mask,
                             train=False,
                             **hop_trimming(model, _layout_of(hops, batch)))
        return loss(logits, batch.y, aux)

    def eval_step(params, batch):
        return step(params, _at_width(batch, batch_size))

    eval_step.lower = lambda params, batch: step.lower(
        params, _at_width(batch, batch_size))
    return eval_step


def make_gather_xy(id2index=None, dedup: bool = False,
                   force: str = "auto", fused: str = "off"):
    """Pure ``(rows, labels, out) -> (x, y)`` batch gather.

    Feature rows and labels ride as arguments (not closures) so callers
    can jit without re-marshalling GB-scale captured arrays; ``id2index``
    (the hotness-reorder indirection) applies to feature ROWS only —
    labels stay indexed by global id; ``labels=None`` (a task without
    node labels: seed edges) gives ``y = None``.

    ``dedup=True`` fetches each unique row from HBM once and scatters it
    back to every batch position (bit-identical ``x``; see
    :func:`~glt_tpu.ops.dedup_gather.dedup_gather_rows`) — the win when
    the node list repeats ids (un-deduped leaf hops, hub nodes).
    ``force`` selects the row-gather kernel
    (:func:`~glt_tpu.ops.gather_pallas.gather_rows`).  ``fused`` != 'off'
    routes the whole dedup+gather through the one-dispatch
    :func:`~glt_tpu.ops.fused_frontier.fused_frontier` kernel
    ('auto'|'pallas'|'interpret'; same bits, unique rows never bounce
    through HBM) — it subsumes ``dedup``.
    """
    from ..ops.dedup_gather import dedup_gather_rows
    from ..ops.fused_frontier import fused_frontier
    from ..ops.gather_pallas import gather_rows

    def gather_xy(rows_arg, labels_arg, out):
        ids = out.node
        valid = ids >= 0
        gid = jnp.where(valid, ids, 0)
        with jax.named_scope("glt.gather.feat"):
            if fused != "off":
                x = fused_frontier(rows_arg, ids, id2index=id2index,
                                   force=fused).features
            elif dedup:
                x = dedup_gather_rows(rows_arg, ids, id2index=id2index,
                                      force=force)
            else:
                ridx = (gid if id2index is None
                        else jnp.take(id2index, gid, axis=0, mode="clip"))
                x = gather_rows(rows_arg, ridx, force=force)
                x = jnp.where(valid[:, None], x, 0)
        if labels_arg is None:
            return x, None
        with jax.named_scope("glt.gather.label"):
            y = jnp.where(valid,
                          jnp.take(labels_arg, gid, axis=0, mode="clip"),
                          PADDING_ID)
        return x, y

    return gather_xy


def make_cached_gather_xy(id2index=None, force: str = "auto"):
    """Dedup + cross-batch-cache batch gather:
    ``(cache, rows, labels, out) -> (cache, x, y)``.

    The node list is routed through one unique pass; unique ids are
    served by the :mod:`~glt_tpu.data.feature_cache` (hits from the HBM
    cache table, misses fetched from ``rows`` and inserted), then rows
    scatter back to every batch position — ``x`` is bit-identical to
    :func:`make_gather_xy`'s as long as ``rows`` is unchanged.  The
    returned cache must be threaded into the next call (scan carry /
    donated step argument).
    """
    from ..data.feature_cache import cache_gather
    from ..ops.gather_pallas import gather_rows
    from ..ops.unique import unique_first_occurrence

    def gather_xy(cache, rows_arg, labels_arg, out):
        ids = out.node.astype(jnp.int32)

        def fetch(fids):
            v = fids >= 0
            fidx = jnp.where(v, fids, 0)
            if id2index is not None:
                fidx = jnp.take(id2index, fidx, axis=0, mode="clip")
            return jnp.where(v[:, None],
                             gather_rows(rows_arg, fidx, force), 0)

        with jax.named_scope("glt.gather.feat"):
            uniq, inv, _ = unique_first_occurrence(ids)
            cache, urows = cache_gather(cache, uniq, fetch, force=force)
            x = jnp.take(urows, jnp.clip(inv, 0, inv.shape[0] - 1), axis=0)
            x = jnp.where((inv >= 0)[:, None], x, 0)
        valid = ids >= 0
        gid = jnp.where(valid, ids, 0)
        with jax.named_scope("glt.gather.label"):
            y = jnp.where(valid,
                          jnp.take(labels_arg, gid, axis=0, mode="clip"),
                          PADDING_ID)
        return cache, x, y

    return gather_xy


def _device_rows(rows, what: str):
    """``rows`` as a :class:`~glt_tpu.data.feature.Feature` that is whole
    in HBM, which the scanned steps need."""
    import numpy as np

    from ..data.feature import Feature

    if not isinstance(rows, Feature):
        rows = Feature(np.asarray(rows))
    if rows.hot_count < rows.size:
        raise ValueError(
            f"scanned {what} step needs device-resident rows")
    return rows


def _check_cache(feature_cache, rows_dtype, dim):
    """The cache table's dtype/width must match the feature rows, or the
    cached-path ``x`` would silently change dtype vs the naive path."""
    if feature_cache.table.dtype != rows_dtype:
        raise ValueError(
            f"feature_cache dtype {feature_cache.table.dtype} != feature "
            f"rows dtype {rows_dtype}; build it with cache_init(..., "
            f"dtype=rows.dtype)")
    if feature_cache.dim != dim:
        raise ValueError(
            f"feature_cache dim {feature_cache.dim} != feature dim {dim}")


def _batch_flags(out):
    """What a scanned step counts of a batch beside loss and accuracy:
    the capacity-overflow flag (0 for a sampler without a capacity) and,
    for a batch with negative pairs, a second column, the slots filled by
    the non-strict padding pass."""
    meta = out.metadata or {}
    ovf = (meta["overflow"].astype(jnp.int32) if "overflow" in meta
           else jnp.zeros((), jnp.int32))
    if "neg_strict" not in meta:
        return ovf
    return jnp.stack([ovf, jnp.sum(~meta["neg_strict"], dtype=jnp.int32)])


def _scanned_supervised(model, tx, loss, dropout_seed: int,
                        hops, batch_of, arrays_of, label: str, live,
                        feature_cache=None,
                        flag_counters=(None,), donate_state=False):
    """The wrapper of the scanned steps over sampled seed blocks: ONE
    jitted ``lax.scan`` of the body over ``seeds_blk [G, B]`` (seed
    edges: ``[G, 2, q]``), as ``step(state, seeds_blk, key) -> (state,
    losses [G], accs [G], overflows [G])`` compiling under the
    compilewatch ``label``; ``loss(z, y, aux) -> (loss, acc)`` is the
    task's (:func:`~glt_tpu.models.step.seed_loss`, or the pair loss of
    the link step).

    ``batch_of(arrays, cache, seeds, key) -> (cache, out, x, y)`` is the
    factory's own part: one batch sampled and gathered out of
    ``arrays_of()``, which ride as jit arguments.  ``cache`` is the
    feature cache (or ``None``): it rides the scan carry and, donated,
    the closure between calls (``step.feature_cache()`` reads it,
    ``step.set_feature_cache`` is the checkpoint-restore seam of
    glt_tpu.ckpt).  The dropout key is ``fold_in(PRNGKey(dropout_seed),
    state.step)``; a slot without a real seed is a no-op
    (:func:`~glt_tpu.models.step.gated_update`); ``overflows`` is each
    batch's capacity-overflow flag (zeros for an uncapped sampler): a
    flagged batch trained with its excess nodes' edges masked.

    The flags and the sampler's ``live_counts`` leave the scan as one
    ``[G, C]`` array, which ``step`` hands to
    :func:`~glt_tpu.obs.metrics.defer`: the flag columns count into
    ``flag_counters`` (one a column of :func:`_batch_flags`, ``None`` for
    a column nobody counts; the named ones are ``step.flag_counters``),
    the rest into ``live`` (the sampler's
    :class:`~glt_tpu.sampler.base.LiveCounters`) — with metrics off
    nothing of it is copied or read.

    ``donate_state`` donates the ``TrainState`` too, for a state too
    large to hold twice (a model with embedding tables): the caller's
    ``state`` is then consumed by the call.
    """
    grads_of = loss_and_grads(model, loss, hops)
    columns = tuple(flag_counters) + live.counters
    flag_cols = len(flag_counters)
    update = gated_update(tx)

    @partial(jax.jit, donate_argnums=(1, 2) if donate_state else (2,))
    def run(arrays, state: TrainState, cache, seeds_blk, key):
        def body(carry, inp):
            st, cache = carry
            seeds, k = inp
            cache, out, x, y = batch_of(arrays, cache, seeds, k)
            edge_index, edge_mask, aux = graph_inputs(out)
            rng = jax.random.fold_in(jax.random.PRNGKey(dropout_seed),
                                     st.step)
            loss, acc, grads = grads_of(st.params, x, edge_index,
                                        edge_mask, y, aux, rng)
            st = update(st, grads, jnp.any(seeds >= 0))
            counts = jnp.concatenate([_batch_flags(out).reshape(-1),
                                      out.live_counts])
            return (st, cache), (loss, acc, counts)

        keys = jax.random.split(key, seeds_blk.shape[0])
        (state, cache), (losses, accs, counts) = jax.lax.scan(
            body, (state, cache), (seeds_blk, keys))
        ovfs = counts[:, 0] if flag_cols == 1 else counts[:, :flag_cols]
        return state, cache, losses, accs, ovfs, counts

    holder = {"cache": feature_cache}

    def step(state: TrainState, seeds_blk, key):
        with _compilewatch.label(label):
            state, holder["cache"], losses, accs, ovfs, counts = run(
                arrays_of(), state, holder["cache"],
                jnp.asarray(seeds_blk, jnp.int32), key)
        _metrics.defer(columns, counts, live.per_row)
        return state, losses, accs, ovfs

    step.flag_counters = tuple(c for c in flag_counters if c is not None)
    step.feature_cache = lambda: holder["cache"]
    step.set_feature_cache = lambda cache: holder.update(cache=cache)
    return step


def make_scanned_node_train_step(model, tx, sampler, rows, labels,
                                 batch_size: int, dropout_seed: int = 0,
                                 dedup: bool = False, feature_cache=None,
                                 gather_force: str = "auto",
                                 fused_frontier: str = "off"):
    """ONE jitted program trains ``G`` consecutive seed-node batches:
    multi-hop sampling, :func:`make_gather_xy` and the step's body under
    ``lax.scan`` (:func:`_scanned_supervised`); the model is trimmed to
    ``sampler.hop_bounds`` where it can be.

    Returns ``step(state, seeds_blk [G, B], key) -> (state, losses [G],
    accs [G], overflows [G])``; seed blocks are -1 padded.  With a capped
    sampler, monitor ``overflows`` and re-run hot batches at full
    capacity (or raise the cap) if the rate matters.

    The options choose the in-scan feature gather and leave ``x``
    bit-identical: ``dedup`` / ``gather_force`` / ``fused_frontier`` as
    :func:`make_gather_xy`'s ``dedup`` / ``force`` / ``fused`` ('auto'
    serves the autotune winner for this table/batch shape — autotune at
    the CAPPED shape before building the step); ``feature_cache`` threads
    a cross-batch HBM cache (:func:`make_cached_gather_xy`) through the
    scan carry AND across blocks (buffers donated — read the live state
    via ``step.feature_cache()``), and wins over ``fused_frontier``: its
    unique-pass bookkeeping IS the fusion's dedup half.
    """
    g = sampler.graph
    labels = jnp.asarray(labels)
    rows = _device_rows(rows, "node")
    hot_rows = rows.hot_rows
    if feature_cache is not None:
        _check_cache(feature_cache, hot_rows.dtype, hot_rows.shape[-1])
        cached_xy = make_cached_gather_xy(rows.id2index,
                                          force=gather_force)
    gather_xy = make_gather_xy(rows.id2index, dedup=dedup,
                               force=gather_force, fused=fused_frontier)

    def batch_of(arrays, cache, seeds, k):
        indptr, indices, eids, rows_arg, labels_arg = arrays
        out = sampler._sample_impl(indptr, indices, eids, seeds, k)
        if cache is None:
            x, y = gather_xy(rows_arg, labels_arg, out)
        else:
            cache, x, y = cached_xy(cache, rows_arg, labels_arg, out)
        return cache, out, x, y

    return _scanned_supervised(
        model, tx, seed_loss(batch_size), dropout_seed, sampler.hop_bounds,
        batch_of,
        lambda: (g.indptr, g.indices, g.gather_edge_ids, hot_rows, labels),
        "scanned_node_step", sampler.live, feature_cache)


def node_seed_blocks(train_idx, batch_size: int, group: int, rng):
    """Shuffled ``[G, B]`` seed blocks, -1 padded (epoch driver for
    :func:`make_scanned_node_train_step`)."""
    import numpy as np

    ids = np.asarray(train_idx)[rng.permutation(len(train_idx))]
    per_block = batch_size * group
    for lo in range(0, len(ids), per_block):
        blk = np.full((group, batch_size), -1, np.int64)
        chunk = ids[lo: lo + per_block]
        blk.reshape(-1)[: chunk.shape[0]] = chunk
        yield blk


def run_scanned_epoch(step, state, train_idx, batch_size: int,
                      group: int, rng, base_key, start_block: int = 0,
                      on_block=None):
    """One epoch through a scanned train step (node, hetero or link
    variant).

    ``train_idx`` is ``[n]`` seed nodes, or ``[2, n]`` seed edges for a
    :func:`make_scanned_link_train_step` (blocks ``[G, 2, q]``,
    :func:`link_seed_blocks`).
    Shuffles ``train_idx`` into ``[G, B]`` blocks, pre-stages them to
    the device, drives ``step`` per block, and reduces the metrics with
    ONE device concat + ONE host fetch — per-element ``list(ls)`` slices
    and per-array fetches both put host round trips on the critical
    path.  Returns ``(state, losses [n_real], accs [n_real],
    overflow_count)`` as host numpy (the fetch is the epoch's sync
    point); ``overflow_count`` is 0 for steps without an overflow
    channel.  What else a step counts (``step.flag_counters``, the
    sampler's live counts) it hands to
    :func:`~glt_tpu.obs.metrics.defer` itself; nothing of it is read here.
    The host's own parts carry spans: ``glt.train.seed_stage`` (shuffle
    and the blocks' ``device_put``), ``glt.train.scanned_block_dispatch``
    a block, ``glt.train.epoch_fetch`` (the concatenations and the three
    fetches), all but the first inside ``glt.train.scanned_epoch``.

    ``start_block``/``on_block`` are the resume seam
    (:class:`~glt_tpu.ckpt.driver.TrainLoop`): the first ``start_block``
    blocks are skipped WITHOUT disturbing the key schedule — block ``i``
    always trains under ``fold_in(base_key, i)``, a pure function of its
    position — so an epoch resumed mid-way replays the identical
    remaining batch stream.  ``on_block(state, block_idx)`` fires after
    each block completes (checkpoint cadence, supervisor polls, fault
    hooks); it forces the block's device work to finish first, so state
    captured inside the hook is the exact post-block state.
    """
    import time

    import numpy as np

    seed_blocks = (link_seed_blocks if np.ndim(train_idx) == 2
                   else node_seed_blocks)
    with _span("train.seed_stage"):
        blocks = [jax.device_put(jnp.asarray(b.astype(np.int32)))
                  for b in seed_blocks(train_idx, batch_size, group, rng)]
    n_real = -(-np.shape(train_idx)[-1] // batch_size)
    # Real batches already consumed before the resume point: the loss
    # trim below only accounts for the blocks this call actually runs.
    n_real = max(0, n_real - int(start_block) * group)
    losses, accs, ovfs = [], [], []
    with _span("train.scanned_epoch", blocks=len(blocks),
               start_block=int(start_block)):
        t_epoch0 = time.perf_counter()
        for i, blk in enumerate(blocks):
            if i < start_block:
                continue
            t_blk0 = time.perf_counter()
            with _span("train.scanned_block_dispatch"):
                res = step(state, blk, jax.random.fold_in(base_key, i))
            _M_STEPS.inc()
            state = res[0]
            losses.append(res[1])
            accs.append(res[2])
            if len(res) > 3:
                ovfs.append(res[3])
            if on_block is not None:
                # The hook may checkpoint: block on this block's update
                # first so the captured TrainState is post-block exact
                # (dispatch is async; a capture of an in-flight state
                # would still be *correct* — device_get syncs — but the
                # explicit wait keeps save timing honest in traces).
                # The sync is the hook's contract, not an accidental
                # per-batch fetch (GLT013 fires only when a hook is set).
                # gltlint: disable-next=dispatch-in-epoch-loop
                jax.block_until_ready(state)
                on_block(state, i)
            blk_ms = (time.perf_counter() - t_blk0) * 1e3
            _M_BLOCK_MS.observe(blk_ms)
            # Spike-triggered profiler capture (no-op while disarmed).
            _profiler.spike_observe(blk_ms)
        _M_EPOCHS.inc()
        # Epoch boundary: refresh glt.device.* gauges (absent on CPU)
        # and advance the live-bytes leak watch.
        _device.observe_epoch()
        _flight.record("train.epoch",
                       blocks=len(blocks) - int(start_block),
                       start_block=int(start_block),
                       duration_ms=(time.perf_counter() - t_epoch0) * 1e3)
        # The epoch's own host fetches are the sync; both spans close
        # around the last of them so the scanned epoch's trace duration
        # is truthful.
        with _span("train.epoch_fetch"):
            losses = (np.asarray(jax.device_get(
                jnp.concatenate(losses)))[:n_real] if losses
                else np.zeros((0,), np.float32))
            accs = (np.asarray(jax.device_get(
                jnp.concatenate(accs)))[:n_real] if accs
                else np.zeros((0,), np.float32))
            if not ovfs:
                return state, losses, accs, 0
            # [batches] overflow flags, or [batches, k] with the overflow
            # flag in column 0: one fetch.
            flags = np.asarray(jax.device_get(jnp.concatenate(ovfs)))
    return state, losses, accs, int(
        flags.reshape(flags.shape[0], -1)[:, 0].sum())


def _resident_rows(f, whole: bool = True):
    """The ``[N_t, d]`` device array of one type's table: a
    :class:`~glt_tpu.data.feature.Feature`'s hot rows (``whole``: all of
    them must be in HBM), a ``jax.Array`` as it is, anything else placed."""
    import numpy as np

    from ..data.feature import Feature

    if isinstance(f, Feature):
        if whole and f.hot_count < f.size:
            raise ValueError(
                "scanned hetero step needs device-resident features")
        return f.hot_rows
    return f if isinstance(f, jax.Array) else jnp.asarray(np.asarray(f))


def init_hetero_state(model, tx, sampler, feats, rng) -> TrainState:
    """Params/opt-state for hetero models from a hetero sampler's edge
    types (:class:`~glt_tpu.sampler.hetero_neighbor_sampler.
    HeteroNeighborSampler` or the distributed one) and the tables'
    widths.  Parameter shapes follow the feature widths, not the row
    counts: one row and one edge slot each, so that initialising never
    runs the model at the batch's size."""
    from ..typing import reverse_edge_type

    rows = {t: _resident_rows(f, whole=False) for t, f in feats.items()
            if t in sampler.node_capacity}
    revs = [reverse_edge_type(et) for et in sampler.edge_types]
    params = model.init(
        {"params": rng},
        {t: jnp.zeros((1, r.shape[-1]), r.dtype) for t, r in rows.items()},
        {et: jnp.full((2, 1), PADDING_ID, jnp.int32) for et in revs},
        {et: jnp.zeros((1,), bool) for et in revs})
    return TrainState(params=params, opt_state=tx.init(params),
                      step=jnp.zeros((), jnp.int32))


def hetero_gather_xy(rows, labels, out, batch_size: int):
    """``(x {type: [N_t, d]}, y [batch_size])`` of a hetero sample: every
    type's rows in the table's own dtype, zero off the node list, and the
    labels of the seed rows (the only ones a loss reads; -1 on padding).
    ``rows`` / ``labels`` ride as arguments, as in :func:`make_gather_xy`.
    """
    x = {}
    with jax.named_scope("glt.gather.feat"):
        for t, node in out.node.items():
            if t not in rows:
                continue
            valid = node >= 0
            xt = jnp.take(rows[t], jnp.where(valid, node, 0), axis=0,
                          mode="clip")
            x[t] = jnp.where(valid[:, None], xt, 0)
    with jax.named_scope("glt.gather.label"):
        seed_ids = out.node[out.input_type][:batch_size]
        y = jnp.where(seed_ids >= 0,
                      jnp.take(labels, jnp.maximum(seed_ids, 0), axis=0,
                               mode="clip"),
                      PADDING_ID)
    return x, y


_M_HETERO_OVF = _metrics.counter(
    "glt.hetero.overflowed_batches",
    "scanned hetero batches that overflowed a node or frontier capacity "
    "(a column of the step's deferred counts)")


def make_scanned_hetero_train_step(model, tx, sampler, feats, labels,
                                   batch_size: int, dropout_seed: int = 0,
                                   seed_hops: bool = False):
    """The hetero analog of :func:`make_scanned_node_train_step`: typed
    multi-hop sampling (:class:`HeteroNeighborSampler._sample_impl`),
    :func:`hetero_gather_xy` and the step's body under ``lax.scan``.
    Sized by its sampler: per-type node rows and per-relation edge slots
    are the sampler's static capacities (the product of the fanouts only
    where neither the exact clamp nor a calibrated ``node_capacity``
    bounds them), recorded when the step is built as
    ``glt.hetero.node_rows{type}`` and ``glt.hetero.edge_slots{edge_type}``.
    Rows keep the table's dtype into the model (16-bit rows meet the
    first projection as they are).

    Args:
      sampler: a :class:`HeteroNeighborSampler`.
      feats: dict ``node_type -> Feature | [N_t, d] array`` (device
        resident; a ``jax.Array`` is used where it is).
      labels: dict ``node_type -> [N_t] int array`` — the sampler's
        ``input_type`` entry supplies the supervised target.
      seed_hops: hand the model ``sampler.hop_bounds``: an
        :class:`~glt_tpu.models.rgat.RGNN` then runs every layer over
        the typed hop blocks that reach the seeds only (the same seed
        logits); ``False`` runs it whole.

    Returns ``step(state, seeds_blk [G, B], key) -> (state, losses [G],
    accs [G], overflows [G])``.
    """
    import numpy as np

    from ..typing import as_str

    tgt = sampler.input_type
    graph_arrays = {et: (g.indptr, g.indices, g.gather_edge_ids)
                    for et, g in sampler.graphs.items()}
    rows = {t: _resident_rows(f) for t, f in feats.items()}
    labels_tgt = jnp.asarray(np.asarray(labels[tgt]))
    widths, cap = sampler._widths, sampler._capacity
    hops = sampler.hop_bounds
    for t, n in cap.items():
        _metrics.gauge("glt.hetero.node_rows", "node rows of one type in "
                       "the batch of the last scanned hetero step built",
                       {"type": t}).set(n)
    for et, b in hops.edge_bounds.items():
        _metrics.gauge("glt.hetero.edge_slots", "edge slots of one "
                       "relation in the batch of the last scanned hetero "
                       "step built", {"edge_type": as_str(et)}).set(b[-1])

    def batch_of(arrays, cache, seeds, k):
        graph_args, rows_args, labels_arg = arrays
        out = sampler._sample_impl(widths, cap, graph_args, {tgt: seeds}, k)
        return (cache, out) + hetero_gather_xy(rows_args, labels_arg, out,
                                               batch_size)

    return _scanned_supervised(
        model, tx, seed_loss(batch_size), dropout_seed,
        hops if seed_hops else None, batch_of,
        lambda: (graph_arrays, rows, labels_tgt), "scanned_hetero_step",
        live=sampler.live, flag_counters=(_M_HETERO_OVF,))


def _take_rows(rows_arg, id2index, node):
    """``x`` of a node list by plain ``jnp.take``, zero on padding: the
    link and subgraph steps' gather.  (:func:`make_gather_xy` goes
    through ``gather_rows``, which lowers to another program.)"""
    valid = node >= 0
    gid = jnp.where(valid, node, 0)
    ridx = (gid if id2index is None
            else jnp.take(id2index, gid, axis=0, mode="clip"))
    return jnp.where(valid[:, None],
                     jnp.take(rows_arg, ridx, axis=0, mode="clip"), 0)


def _scan_unsupervised(tx, grads_of, batch_of):
    """The jitted scan of the subgraph step, which carries ``(params,
    opt_state)`` and reports losses only: ``run(arrays, params,
    opt_state, blocks, key)`` with ``batch_of(arrays, *slot, key) -> (out, x, y,
    aux, any_valid)`` one batch of the block's leading axis.  The model
    runs in evaluation mode (no dropout key), as it always has here."""
    update = gated_update(tx)

    @jax.jit
    def run(arrays, params, opt_state, blocks, key):
        def body(st, inp):
            out, x, y, aux, any_valid = batch_of(arrays, *inp)
            edge_index, edge_mask, _ = graph_inputs(out)
            loss, _, grads = grads_of(st.params, x, edge_index, edge_mask,
                                      y, aux, None)
            return update(st, grads, any_valid), loss

        keys = jax.random.split(key, blocks[0].shape[0])
        st, losses = jax.lax.scan(
            body, TrainState(params, opt_state, jnp.zeros((), jnp.int32)),
            blocks + (keys,))
        return st.params, st.opt_state, losses

    return run


def init_train_state(model, tx, feature_dim: int, rng,
                     dtype=jnp.float32) -> TrainState:
    """Params/opt-state of a homogeneous model from the rows' width
    alone (one row, one padded edge slot), as
    :func:`init_hetero_state` does for the typed ones: parameter shapes
    do not follow the batch, so initialising never runs it."""
    params = model.init({"params": rng}, jnp.zeros((1, feature_dim), dtype),
                        jnp.full((2, 1), PADDING_ID, jnp.int32),
                        jnp.zeros((1,), bool))
    return TrainState(params=params, opt_state=tx.init(params),
                      step=jnp.zeros((), jnp.int32))


_M_LINK_OVF = _metrics.counter(
    "glt.link.overflowed_batches",
    "scanned link batches whose seed-union sample overflowed its node "
    "capacity (a column of the step's deferred counts)")
_M_LINK_PADDED = _metrics.counter(
    "glt.link.neg_padded_slots",
    "negative slots of scanned link batches that no strict trial filled "
    "(the non-strict padding pass; same deferred counts)")


def make_scanned_link_train_step(model, tx, sampler, rows,
                                 loss_fn=pair_bce_loss,
                                 neg_sampling=None, group: int = 8):
    """ONE jitted program trains ``G`` consecutive seed-edge batches:
    negative sampling (strict trials + padding), multi-hop sampling from
    the seed union, :func:`make_gather_xy` and the step's body under
    ``lax.scan`` (:func:`_scanned_supervised`) — the TPU answer to the
    reference's per-worker in-flight batch concurrency
    (dist_options.py:21-100).  The model is trimmed to the union's
    ``hop_bounds`` where it can be: the loss reads seed rows only.

    Args:
      sampler: :class:`~glt_tpu.sampler.neighbor_sampler.NeighborSampler`
        of ``q`` seed edges a batch; its ``node_capacity``, if any, is
        the seed union's (``calibrate_node_capacity(..., neg_sampling=...)``).
      rows: device-resident feature matrix / Feature (split_ratio 1.0).
      loss_fn: ``(z, meta) -> (loss, acc)`` (or a scalar loss) given the
        seed rows' embeddings ``z`` (every row, for a model that does not
        trim) and the batch metadata (``edge_label_index``,
        ``edge_label`` for binary mode, triplet indices for triplet
        mode).  The default is upstream's unsupervised objective,
        :func:`~glt_tpu.models.step.pair_bce_loss`.
      neg_sampling: the loader's :class:`NegativeSampling` (or None).

    Returns ``step(state, edges_blk [G, 2, q], key) -> (state, losses
    [G], accs [G], flags [G] or [G, 2])``, the shape
    :func:`run_scanned_epoch` drives; seed-edge blocks are -1 padded, and
    a batch without a seed edge moves nothing.  ``flags`` is the overflow
    flag; with binary negatives column 0 is the flag and column 1 the
    negative slots left to the non-strict padding pass
    (``glt.link.overflowed_batches`` / ``glt.link.neg_padded_slots``).
    """
    g = sampler.graph
    rows = _device_rows(rows, "link")
    hot_rows = rows.hot_rows
    gather_xy = make_gather_xy(rows.id2index)

    mode = None if neg_sampling is None else neg_sampling.mode
    amount = 0 if neg_sampling is None else int(round(neg_sampling.amount))
    cdf = None if neg_sampling is None else neg_sampling.cdf()
    impl = partial(sampler._sample_edges_impl, mode, amount, cdf is not None)
    q = sampler.batch_size
    union = sampler.seed_union(neg_sampling)
    _metrics.gauge("glt.link.seed_union_width", "seed slots the last "
                   "scanned link step built samples from").set(
        union.batch_size)
    _metrics.gauge("glt.link.node_rows", "node rows of the batch of the "
                   "last scanned link step built").set(union.node_capacity)

    def batch_of(arrays, cache, edges, k):
        indptr, indices, eids, sorted_indices, rows_arg, cdf_arg = arrays
        s, d = edges[0], edges[1]
        out = impl(indptr, indices, eids, sorted_indices, s, d, cdf_arg, k)
        meta = dict(out.metadata)
        if mode == "binary":
            pos = jnp.where(s >= 0, 1, PADDING_ID)
            meta["edge_label"] = jnp.concatenate(
                [pos, jnp.zeros((q * amount,), jnp.int32)])
        x, _ = gather_xy(rows_arg, None, out)
        return cache, out, x, meta

    def loss(z, meta, aux):
        value = loss_fn(z, meta)
        return value if isinstance(value, tuple) else (
            value, jnp.zeros((), jnp.float32))

    def arrays_of():
        sorted_ix = g.sorted_indices if mode is not None else g.indices
        cdf_arg = jnp.zeros((1,), jnp.float32) if cdf is None else cdf
        return (g.indptr, g.indices, g.gather_edge_ids, sorted_ix, hot_rows,
                cdf_arg)

    return _scanned_supervised(
        model, tx, loss, 0, union.hop_bounds, batch_of, arrays_of,
        "scanned_link_step", live=sampler.live_counters(union),
        flag_counters=((_M_LINK_OVF, _M_LINK_PADDED) if mode == "binary"
                       else (_M_LINK_OVF,)))


def make_scanned_subgraph_train_step(model, tx, sampler, rows, loss_fn,
                                     max_degree: int):
    """ONE jitted program trains a block of induced-subgraph batches
    (SEAL-style configs: tiny batches, dispatch-bound): hop expansion,
    induced extraction (:func:`~glt_tpu.ops.subgraph.node_subgraph`),
    feature gather and the step's body under ``lax.scan`` over the seed
    block's leading axis.

    ``loss_fn(z, out, y) -> scalar`` gets node embeddings over the
    extracted subgraph, the per-batch :class:`SamplerOutput` (graph-
    direction COO), and the per-batch label block ``y``.  Seeds are
    DEDUPED in the node list, so positional slicing of ``z`` mispairs
    whenever a seed repeats — use ``out.metadata['seed_index']``
    (``[B_seeds]`` local indices of the seed slots, -1 for padding).

    Returns ``step(params, opt_state, seeds [G, B], y [G, ...], key)``; a
    batch without a seed moves nothing.
    """
    from ..ops.subgraph import node_subgraph
    from ..ops.unique import relabel_by_reference
    from ..sampler.base import SamplerOutput

    g = sampler.graph
    rows = _device_rows(rows, "subgraph")
    if not sampler.last_hop_dedup:
        # Same guard as NeighborSampler.subgraph(): the induced extract
        # relabels against a UNIQUE node set.
        raise ValueError(
            "scanned subgraph step requires last_hop_dedup=True")
    id2index = rows.id2index
    k_deg = int(max_degree)

    def batch_of(arrays, seeds, y, k):
        indptr, indices, eids, sub_eids, rows_arg = arrays
        base = sampler._sample_impl(indptr, indices, eids, seeds, k)
        sub = node_subgraph(indptr, indices, base.node, k_deg,
                            edge_ids=sub_eids)
        ref = base.node[: seeds.shape[0]]
        out = SamplerOutput(
            node=base.node, row=sub.rows, col=sub.cols, edge=sub.eids,
            batch=seeds, node_mask=base.node_mask, edge_mask=sub.mask,
            num_sampled_nodes=base.num_sampled_nodes,
            metadata={"seed_index": relabel_by_reference(ref, seeds)})
        return (out, _take_rows(rows_arg, id2index, out.node), y, out,
                jnp.any(seeds >= 0))

    run = _scan_unsupervised(
        tx, loss_and_grads(model, lambda z, y, out: (loss_fn(z, out, y),
                                                     None)), batch_of)

    def step(params, opt_state, seeds_blk, y_blk, key):
        with _compilewatch.label("scanned_subgraph_step"):
            return run((g.indptr, g.indices, g.gather_edge_ids, g.edge_ids,
                        rows.hot_rows), params, opt_state,
                       (jnp.asarray(seeds_blk, jnp.int32),
                        jnp.asarray(y_blk)), key)

    return step


def shuffled_positions(n: int, rng, block: int):
    """The positions ``0..n-1`` in a shuffled order, ``block`` at a time,
    without holding a permutation of all of them: position ``i`` of the
    pass is a keyed bijection of ``i`` (a four-round Feistel network over
    the next power of four, walked until it lands under ``n``), the key
    drawn from ``rng``.  Memory and time are those of one block."""
    import numpy as np

    half = max(1, -(-(max(n, 2) - 1).bit_length() // 2))
    low = np.uint64((1 << half) - 1)
    keys = rng.integers(0, 1 << 32, size=4, dtype=np.uint64)

    def mix(v):
        left, right = v >> np.uint64(half), v & low
        for k in keys:
            f = (right * np.uint64(0x9E3779B1) + k) & np.uint64(0xFFFFFFFF)
            f = ((f ^ (f >> np.uint64(15))) * np.uint64(0x85EBCA6B)
                 ) & np.uint64(0xFFFFFFFF)
            left, right = right, left ^ ((f ^ (f >> np.uint64(13))) & low)
        return (left << np.uint64(half)) | right

    for lo in range(0, n, block):
        pos = mix(np.arange(lo, min(lo + block, n), dtype=np.uint64))
        while True:
            out = pos >= np.uint64(n)
            if not out.any():
                break
            pos[out] = mix(pos[out])
        yield pos.astype(np.int64)


def link_seed_blocks(edge_index, batch_size: int, group: int, rng):
    """Shuffled seed-edge ``[G, 2, q]`` blocks (``[:, 0]`` sources,
    ``[:, 1]`` destinations), -1 padded: the epoch driver's blocks for
    :func:`make_scanned_link_train_step`, as :func:`node_seed_blocks` for
    seed nodes.  The shuffle is drawn a block at a time
    (:func:`shuffled_positions`): a pass over a graph's own edges never
    holds a permutation, or a permuted copy, of all of them.
    """
    import numpy as np

    e = np.asarray(edge_index)
    per_block = batch_size * group
    for pos in shuffled_positions(e.shape[1], rng, per_block):
        blk = np.full((2, per_block), -1, np.int64)
        blk[:, : pos.shape[0]] = e[:, pos]
        yield blk.reshape(2, group, batch_size).transpose(1, 0, 2)



def make_scanned_hetero_link_train_step(model, tx, sampler, edge_type,
                                        neg_sampling):
    """ONE jitted program trains ``G`` consecutive batches of typed seed
    edges of relation ``edge_type`` with binary negatives: the strict
    negative draw, the typed sample from the two-type seed union
    (:meth:`HeteroNeighborSampler.edges_program`), the model's own
    lookups of its node-embedding tables, forward, the pair loss
    (:func:`~glt_tpu.models.step.logit_bce_loss` of the model's pair
    logits), backward and the update, under ``lax.scan``
    (:func:`_scanned_supervised`) -- upstream's
    ``examples/hetero/bipartite_sage_unsup.py`` training loop.

    The batch's ``x`` is ``(node ids {type: [N_t]} (-1 on padding),
    pair index [2, 2q])``: ``model``
    (:class:`~glt_tpu.models.bipartite.BipartiteSAGE`, or any model
    with ``table_rows`` that takes such an ``x`` and returns pair
    logits) reads its inputs out of its tables, whose dense gradient the
    optimiser applies to every row (``glt.embed.update``).  The
    ``TrainState`` is donated through the scan and across calls: the
    caller's ``state`` is consumed.  The model runs whole (no hop
    trimming).

    Returns ``step(state, edges_blk [G, 2, q], key) -> (state, losses
    [G], accs [G], flags [G, 2])``, the shape :func:`run_scanned_epoch`
    drives; ``flags[:, 1]`` is the negative slots of the non-strict
    padding pass (``glt.link.neg_padded_slots``; column 0 is 0: the
    union runs under the exact clamp and never overflows).  Gauge
    ``glt.embed.table_rows{type}`` holds each table's rows, counter
    ``glt.embed.rows{type}`` each batch's looked-up rows.
    """
    from ..sampler.base import LiveCounters

    if neg_sampling is None or neg_sampling.mode != "binary":
        raise ValueError("the typed link step trains binary negatives")
    amount = int(round(neg_sampling.amount))
    cdf = neg_sampling.cdf()
    impl, widths, cap = sampler.edges_program(
        edge_type, "binary", amount, cdf is not None)
    q = sampler.batch_size
    types = sorted(model.table_rows)
    for t, n in model.table_rows.items():
        _metrics.gauge("glt.embed.table_rows", "rows of one node type's "
                       "embedding table in the last step built over it",
                       {"type": t}).set(n)
    union = sampler.live_counters(widths, cap)
    rows_read = tuple(_metrics.counter(
        "glt.embed.rows", "rows of one node type that batches looked up "
        "in its embedding table (a column of the step's deferred counts)",
        {"type": t}) for t in types)
    live = LiveCounters(rows_read + union.counters, union.per_row)
    graph_arrays = {et: (g.indptr, g.indices, g.gather_edge_ids)
                    for et, g in sampler.graphs.items()}
    seed_graph = sampler.graphs[edge_type]

    def batch_of(arrays, cache, edges, k):
        graph_args, sorted_arg, cdf_arg = arrays
        s, d = edges[0], edges[1]
        out = impl(graph_args, sorted_arg, s, d, cdf_arg, k)
        meta = dict(out.metadata)
        meta["edge_label"] = jnp.concatenate(
            [jnp.where(s >= 0, 1, PADDING_ID),
             jnp.zeros((q * amount,), jnp.int32)])
        rows = jnp.stack([jnp.sum(out.node_mask[t], dtype=jnp.int32)
                          for t in types])
        out = dataclasses.replace(
            out, live_counts=jnp.concatenate([rows, out.live_counts]))
        x = ({t: out.node[t] for t in types}, meta["edge_label_index"])
        return cache, out, x, meta

    def arrays_of():
        return (graph_arrays, seed_graph.sorted_indices,
                jnp.zeros((1,), jnp.float32) if cdf is None else cdf)

    return _scanned_supervised(
        model, tx, lambda z, meta, aux: logit_bce_loss(z, meta),
        0, None, batch_of, arrays_of, "scanned_hetero_link_step", live,
        flag_counters=(None, _M_LINK_PADDED), donate_state=True)
