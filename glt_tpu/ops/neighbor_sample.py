"""Fixed-fanout random neighbor sampling over CSR, XLA-native.

TPU rethink of the reference's CUDA sampler (``csrc/cuda/random_sampler.cu``):
the CUDA kernel assigns one warp per seed row and runs reservoir sampling over
the row's full adjacency (random_sampler.cu:87-106), sizing its ragged output
with a cub scan + a forced device->host sync (random_sampler.cu:288-300).

On TPU we avoid both the O(degree) reservoir walk and the dynamic output:

* output is **static** ``[num_seeds, fanout]`` with sentinel padding
  (PADDING_ID = -1), so the whole multi-hop pipeline stays inside one jit;
* without-replacement sampling uses **Floyd's algorithm** — O(fanout^2)
  per row independent of degree, a much better fit for power-law graphs
  than a reservoir pass over million-edge rows;
* randomness is counter-based (threefry via jax.random), keyed per
  (key, slot), reproducible under jit/vmap/shard_map — mirroring the
  curand Philox stream-per-thread setup (random_sampler.cu:71-73).

All functions are pure and shard_map-compatible: inputs/outputs are plain
arrays, no host syncs.

**The A/B seam** (the gather_pallas.py pattern applied to sampling):
``sample_neighbors(force=...)`` routes the memory-bound half of the hop
— the ``indices[start + pos]`` / ``edge_ids[start + pos]`` random reads
— through either XLA's generic gather or the degree-binned Pallas DMA
kernel (:mod:`.sample_pallas`).  The *draw* (Floyd / with-replacement
positions) always runs here in XLA: pltpu's PRNG is not threefry-bit-
compatible with jax.random, and bit-identical output across paths is
what lets every existing sampler/loader/dist test double as a
correctness oracle.  ``force='auto'`` serves the winner memoized by
:func:`~glt_tpu.ops.sample_pallas.autotune_sample` per exact
(batch, fanout, dtype) key — XLA until a measurement exists.  The
``GLT_SAMPLE_FORCE`` env var overrides (``pallas``/``xla``/
``interpret`` — the last runs the Pallas path in interpret mode so the
seam is exercisable end to end on CPU).

**The chunk rule** (the XLA arm; PERF.md §6, PR 35).  A frontier is a
static ``[w]`` buffer whose tail holds no node: 38-58 % of the widest
read's rows in the one-chip GraphSAGE cells, 89 % of the rows a shard of
the dist step serves.  XLA's scalar gather costs a dead slot what it
costs a live one, so the hop is split into what is arithmetic and what is
a random read.  The random reads (the two row-pointer reads, then
``indices[flat]`` and ``edge_ids[flat]``) run :data:`CHUNK_ROWS` frontier
rows at a time, in a loop whose trip count is the number of chunks in
which some row holds an id, a fact of the input: one prefix of live rows
(the one-chip samplers), ``S`` prefixes (the dist served matrix) and a
scattered frontier fall out of the same rule, and nothing is compacted,
sorted by id or calibrated.  A skipped row reads as a padding row does
(degree 0, every slot ``PADDING_ID``).  **The draw stays one call over
the whole width**: it is elementwise, and ``jax.random`` defines the
per-(key, slot) stream by the shape it is asked for, so a draw made a
chunk at a time would be another stream; kept whole, ``nbrs``, ``eids``
and ``mask`` are the whole read's bit for bit.  A frontier of at most one
chunk lowers to the single fusion it always was.  On the chip a dead row
costs more than a live one (padding reads ``indices[0]``, and reads of one
address serialise), so skipping its chunks gives back more than its share.
The dist step's served feature and label read runs the same two helpers
over the request matrix it serves (``parallel/dist_feature.py``).
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..typing import PADDING_ID

#: Frontier rows in one chunk of the hop's random reads (module docstring,
#: "The chunk rule").  One constant for every caller, chosen on the chip
#: (``scripts/hop_read_micro.py``, whose docstring keeps the table; TPU
#: v5e, libtpu 0.0.34): at the widest reads (153,600 and 614,400 rows) the
#: loop at 2,560 rows costs 0.90-1.03 of the whole read with every row
#: live and 0.26-0.41 of it at 40 % live; 1,024 pays 19 % at a full
#: frontier (150 trips), 5,120 and 10,240 pay 8-15 %.
CHUNK_ROWS = 2560


class NeighborOutput(NamedTuple):
    """One-hop sampling result (cf. sampler/base.py:301 ``NeighborOutput``)."""
    nbrs: jnp.ndarray       # [B, fanout] neighbor global ids, -1 padded
    eids: Optional[jnp.ndarray]  # [B, fanout] global edge ids, -1 padded
    mask: jnp.ndarray       # [B, fanout] bool validity


def _row_offsets_and_degrees(indptr, seeds):
    """Per-seed CSR offsets/degrees; invalid (negative) seeds get degree 0."""
    valid = seeds >= 0
    safe = jnp.where(valid, seeds, 0)
    start = indptr[safe]
    deg = indptr[safe + 1] - start
    deg = jnp.where(valid, deg, 0)
    return start, deg.astype(jnp.int32)


def _live_chunks(seeds: jnp.ndarray):
    """The chunks of :data:`CHUNK_ROWS` frontier rows in which some row
    holds an id: ``(order, n)``, every chunk's index with the ``n`` live
    ones first.  The last chunk of a width that is no multiple of the
    chunk is judged by its own rows alone."""
    n_chunks = -(-seeds.shape[0] // CHUNK_ROWS)
    live = jnp.pad(seeds >= 0, (0, n_chunks * CHUNK_ROWS - seeds.shape[0])
                   ).reshape(n_chunks, CHUNK_ROWS).any(axis=1)
    return (jnp.argsort(~live, stable=True).astype(jnp.int32),
            jnp.sum(live.astype(jnp.int32)))


def _read_live_chunks(order, n, width: int, per_row: int, read, fill):
    """``read(offset)`` over the ``n`` live chunks, written into ``[width
    * per_row]`` buffers pre-filled with ``fill``: ``read`` takes the
    first element of a chunk and returns one ``[CHUNK_ROWS * per_row]``
    block for each buffer.  The trip count is the traced ``n``.  A last
    chunk that would run past the width starts ``CHUNK_ROWS`` rows before
    its end instead (what ``dynamic_slice`` does to such a start anyway):
    the rows it shares with the chunk before are read to the same values.
    """
    last = (width - CHUNK_ROWS) * per_row

    def body(i, bufs):
        off = jnp.minimum(order[i] * (CHUNK_ROWS * per_row), last)
        return tuple(lax.dynamic_update_slice_in_dim(b, v, off, 0)
                     for b, v in zip(bufs, read(off)))

    return lax.fori_loop(0, n, body, fill)


def read_rows(seeds: jnp.ndarray) -> jnp.ndarray:
    """Frontier rows whose random reads :func:`sample_neighbors` issues
    for ``seeds`` (its XLA arm): the live chunks' rows, and the whole
    width where it is at most one chunk and no loop exists.  What the
    counter ``glt.sample.read_rows{hop}`` counts."""
    width = seeds.shape[0]
    if width <= CHUNK_ROWS:
        return jnp.full((), width, jnp.int32)
    return _live_chunks(seeds)[1] * CHUNK_ROWS


def _draw_positions(deg: jnp.ndarray, fanout: int, key: jax.Array,
                    with_replacement: bool):
    """Per-row draw positions + validity mask: ``(pos [B, fanout],
    mask [B, fanout])`` with ``pos[i, k] < max(deg[i], 1)``.

    Shared by the XLA and Pallas sampling paths — the draw is the
    bit-identity anchor between them (both gather ``indices[start +
    where(mask, pos, 0)]``), so it must run through jax.random on both.
    """
    b = deg.shape[0]
    slot_ids = jnp.arange(fanout, dtype=jnp.int32)  # [k]

    if with_replacement:
        pos = jax.random.randint(
            key, (b, fanout), 0, jnp.maximum(deg, 1)[:, None], dtype=jnp.int32
        )
        mask = (slot_ids[None, :] < jnp.where(deg > 0, fanout, 0)[:, None])
        return pos, mask

    # Floyd's uniform k-subset algorithm, unrolled over the (static,
    # small) fanout.  For rows with deg <= fanout we take slots 0..deg-1
    # directly; Floyd only engages when deg > fanout.
    chosen = jnp.full((b, fanout), -1, jnp.int32)
    keys = jax.random.split(key, fanout)
    for i in range(fanout):
        j = deg - fanout + i                       # [B], >= 0 when deg > fanout
        t = jax.random.randint(
            keys[i], (b,), 0, jnp.maximum(j + 1, 1), dtype=jnp.int32
        )
        dup = jnp.any(chosen == t[:, None], axis=1)
        floyd_pos = jnp.where(dup, j, t)
        pos_i = jnp.where(deg > fanout, floyd_pos, i)
        chosen = chosen.at[:, i].set(pos_i)
    mask = slot_ids[None, :] < jnp.minimum(deg, fanout)[:, None]
    return chosen, mask


def _draw_positions_by_id(deg: jnp.ndarray, fanout: int, key: jax.Array,
                          with_replacement: bool, seeds: jnp.ndarray):
    """Layout-invariant draw: positions keyed per ``(key, seed id)``.

    :func:`_draw_positions` keys randomness per (key, buffer slot), so
    the same id draws *different* neighbors when it appears at a
    different position (or more than once) in the request buffer.  The
    hierarchical dedup-then-exchange transport
    (:class:`glt_tpu.parallel.dist_sampler.HierarchicalRouting`) serves
    each host-unique id once and broadcasts the response back to every
    requesting slot — which is only bit-identical to the flat path if
    a given id draws the same positions regardless of where (and how
    often) it sits in the buffer.  Here each row derives its own key
    with ``fold_in(key, id)``; everything else (Floyd's structure, the
    duplicate test, the masks) mirrors :func:`_draw_positions` exactly.
    """
    b = deg.shape[0]
    slot_ids = jnp.arange(fanout, dtype=jnp.int32)  # [k]
    row_keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.where(seeds >= 0, seeds, 0).astype(jnp.int32))

    if with_replacement:
        pos = jax.vmap(
            lambda k, m: jax.random.randint(k, (fanout,), 0, m,
                                            dtype=jnp.int32)
        )(row_keys, jnp.maximum(deg, 1))
        mask = (slot_ids[None, :] < jnp.where(deg > 0, fanout, 0)[:, None])
        return pos, mask

    chosen = jnp.full((b, fanout), -1, jnp.int32)
    keys = jax.vmap(lambda k: jax.random.split(k, fanout))(row_keys)
    for i in range(fanout):
        j = deg - fanout + i                       # [B], >= 0 when deg > fanout
        t = jax.vmap(
            lambda k, m: jax.random.randint(k, (), 0, m, dtype=jnp.int32)
        )(keys[:, i], jnp.maximum(j + 1, 1))
        dup = jnp.any(chosen == t[:, None], axis=1)
        floyd_pos = jnp.where(dup, j, t)
        pos_i = jnp.where(deg > fanout, floyd_pos, i)
        chosen = chosen.at[:, i].set(pos_i)
    mask = slot_ids[None, :] < jnp.minimum(deg, fanout)[:, None]
    return chosen, mask


def draw_positions(deg: jnp.ndarray, fanout: int, key: jax.Array,
                   with_replacement: bool, seeds: jnp.ndarray,
                   key_by: str = "slot"):
    """Draw dispatcher shared by the XLA and Pallas paths.

    ``key_by='slot'`` is the historical per-(key, buffer slot) stream;
    ``key_by='id'`` keys per (key, seed id) so draws are invariant to
    request-buffer layout (required by hierarchical routing).
    """
    if key_by == "slot":
        return _draw_positions(deg, fanout, key, with_replacement)
    if key_by == "id":
        return _draw_positions_by_id(deg, fanout, key, with_replacement,
                                     seeds)
    raise ValueError(f"key_by must be 'slot' or 'id', got {key_by!r}")


def sample_neighbors(
    indptr: jnp.ndarray,
    indices: jnp.ndarray,
    seeds: jnp.ndarray,
    fanout: int,
    key: jax.Array,
    edge_ids: Optional[jnp.ndarray] = None,
    with_replacement: bool = False,
    with_edge: bool = True,
    force: str = "auto",
    key_by: str = "slot",
) -> NeighborOutput:
    """Sample up to ``fanout`` neighbors per seed from a CSR graph.

    Args:
      indptr: ``[N+1]`` CSR row pointers.
      indices: ``[E]`` CSR column (neighbor) ids.
      seeds: ``[B]`` seed node ids; negative entries are padding.
      fanout: static per-seed sample size. ``fanout == -1`` is not supported
        here (full expansion is :func:`glt_tpu.ops.subgraph.node_subgraph`).
      key: PRNG key; results are a pure function of (graph, seeds, key).
      edge_ids: optional ``[E]`` global edge ids; defaults to CSR positions,
        matching the reference's implicit edge ids.
      with_replacement: if True, draw i.i.d. uniform neighbors instead of a
        uniform subset.
      with_edge: when False, skip edge-id materialisation entirely
        (``eids`` is None) — saves one random gather over the edge array
        per hop, the dominant cost at wide frontiers (the reference's
        ``Sample`` vs ``SampleWithEdge`` split, random_sampler.cu:267,310).
      force: neighbor-read kernel seam — 'auto' | 'pallas' | 'xla' |
        'interpret' (see module docstring).  ``GLT_SAMPLE_FORCE``
        overrides.
      key_by: randomness keying — 'slot' (per buffer position, the
        historical stream) or 'id' (per seed id, layout-invariant; used
        by the hierarchical dedup-then-exchange transport so flat and
        hier routing stay bit-identical).

    Returns:
      :class:`NeighborOutput` with static ``[B, fanout]`` arrays.  Rows with
      ``degree <= fanout`` return the full (untruncated) neighbor list in CSR
      order, as the reference sampler does (random_sampler.cu:79-85).
    """
    if fanout <= 0:
        raise ValueError(f"fanout must be positive, got {fanout}")
    env = os.environ.get("GLT_SAMPLE_FORCE")
    if env in ("pallas", "xla", "interpret"):
        force = env
    seeds = seeds.astype(jnp.int32)
    if force != "xla":
        # Lazy import: sample_pallas imports the draw/offset helpers
        # from this module.
        from . import sample_pallas as _sp

        params = _sp.auto_params(seeds.shape[0], fanout, indices.dtype)
        if force in ("pallas", "interpret") or params is not None:
            return _sp.sample_neighbors_pallas(
                indptr, indices, seeds, fanout, key, edge_ids=edge_ids,
                with_replacement=with_replacement, with_edge=with_edge,
                params=params, interpret=(force == "interpret"),
                key_by=key_by)
    width = seeds.shape[0]
    chunked = width > CHUNK_ROWS
    if chunked:
        order, n = _live_chunks(seeds)
        start, deg = _read_live_chunks(
            order, n, width, 1,
            lambda off: _row_offsets_and_degrees(
                indptr, lax.dynamic_slice_in_dim(seeds, off, CHUNK_ROWS)),
            (jnp.zeros((width,), indptr.dtype),
             jnp.zeros((width,), jnp.int32)))
    else:
        start, deg = _row_offsets_and_degrees(indptr, seeds)
    pos, mask = draw_positions(deg, fanout, key, with_replacement, seeds,
                               key_by=key_by)
    flat = start[:, None] + jnp.where(mask, pos, 0)
    tables = ((indices, edge_ids) if with_edge and edge_ids is not None
              else (indices,))
    if chunked:
        flat_1d = flat.reshape(-1)

        def read(off):
            at = lax.dynamic_slice_in_dim(flat_1d, off, CHUNK_ROWS * fanout)
            return tuple(t[at] for t in tables)

        blocks = _read_live_chunks(
            order, n, width, fanout, read,
            tuple(jnp.full((width * fanout,), PADDING_ID, t.dtype)
                  for t in tables))

        def gathered(k):
            return blocks[k].reshape(width, fanout)
    else:
        def gathered(k):
            return tables[k][flat]
    nbrs = jnp.where(mask, gathered(0), PADDING_ID).astype(jnp.int32)
    if not with_edge:
        eids = None
    elif edge_ids is None:
        eids = jnp.where(mask, flat, PADDING_ID).astype(jnp.int32)
    else:
        eids = jnp.where(mask, gathered(1), PADDING_ID).astype(jnp.int32)
    return NeighborOutput(nbrs=nbrs, eids=eids, mask=mask)


def lookup_degrees(indptr: jnp.ndarray, seeds: jnp.ndarray) -> jnp.ndarray:
    """Per-seed out-degree (cf. ``LookupDegreeKernel``, csrc/cuda/graph.cu:30)."""
    _, deg = _row_offsets_and_degrees(indptr, seeds.astype(jnp.int32))
    return deg
