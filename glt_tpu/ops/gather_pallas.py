"""Pallas row-gather kernels: the feature-lookup hot op.

TPU counterpart of the reference's ``GatherTensorKernel``
(csrc/cuda/unified_tensor.cu:48-81): there, one warp copies each requested
row from GPU/peer/pinned-host memory.

Three generations of kernel live here (two as lessons, one current):

* **round 3 (retired):** one async DMA per requested row, pipelined.
  Measured honestly, XLA's native gather beat it ~2x at 512B rows:
  per-row DMAs are **issue-rate bound**, not bandwidth bound.

* **round 5 (superseded):** fixed 8-row tiles, fixed 8-slot ring.  It
  coalesced sorted runs into block DMAs, but every DMA was 4KB at d=128
  — deep enough to beat per-row issue, far too shallow to stream.

* **tiled, parameterized (current):** the same sorted-run coalescing,
  but the two knobs that set DMA depth and overlap are now free
  parameters swept by the autotuner:

    - ``tile_rows`` — table rows per block DMA.  Bigger tiles amortize
      DMA setup and stream deeper; the width-specialized defaults hold
      the DMA *byte* depth roughly constant (~16KB) across row widths,
      so d=64 tables use 32-row tiles where d=256 uses 16.
    - ``ring_depth`` — VMEM tile slots == DMAs in flight.  The copy
      ring is double-buffered in the general sense: while rows of tile
      ``j`` are copied out to the output block, the DMAs for tiles
      ``j+1 .. j+ring_depth-1`` are already streaming.

  Width specialization also covers **d=64** (the common "half-lane"
  embedding width): the table is viewed as ``[N/2, 128]`` paired rows,
  the kernel moves full 128-lane rows (the lanes a 64-wide DMA would
  pad to anyway), and an XLA epilogue selects the requested half.

``gather_rows(force='auto')`` stays the A/B seam: it consults a
per-(row width, batch, dtype) decision table filled by
:func:`autotune_gather_rows` at warmup (eager; each timing ends in a
host value fetch).  The autotuner sweeps the (tile_rows, ring_depth)
grid per shape and memoizes the winning *parameters*, not just the
kernel choice; the full sweep table is exported
(:func:`autotune_table`), including every candidate the compiler
refused and its message.  Because the table is keyed by the exact batch
size, an occupancy-capped loader shape gets its own sweep instead of
inheriting the full-cap winner.

**On the TPU v5e (PR 21 bring-up, jax 0.9.0 / libtpu 0.0.34).**  The
f32 kernel compiles under Mosaic at every swept point.  Tables of a
packed dtype (bf16, int8) are refused — "cannot statically prove that
index in dimension 1 is a multiple of 8" for the one-row load at a
dynamic sublane offset, and, behind it, "Failed to prove that a tile
index in dimension 0 is divisible by the tiling (8)" for the block DMA's
dynamic HBM start — so :func:`pallas_gather_supported` admits 4-byte
rows only and ``auto`` never selects :func:`gather_rows_pallas_dq`;
``force='pallas'`` still reaches it and raises the compiler's message.
"""
from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tpu_limits
from ..store import quant

_CHUNK = 256  # output rows per grid step (batch padded to a multiple)
_LANE = tpu_limits.LANE
_MIN_TILE = tpu_limits.SUBLANE_F32
# Output rows per pallas_call.  The five scalar-prefetch descriptor
# arrays cost ~16 B of SMEM per output row, so one launch over the
# config-1 node capacity (139,264 rows = 2.13 MB) overruns the 1 MB of
# SMEM; the grid is issued in segments whose descriptors take half of
# it.  Segments are independent: every descriptor is chunk-relative.
_SEG_ROWS = (tpu_limits.SMEM_BYTES // 2) // 16 // _CHUNK * _CHUNK
# Sublane count of the packed scale/zero input block (== quant.
# SCALE_ZERO_ROWS): row 0 = scale, row 1 = zero, padded to the f32
# tiling floor so the block satisfies GLT019.
_SZ_ROWS = 8

# The (tile_rows, ring_depth) grid the autotuner sweeps — and the grid
# the static VMEM model (analysis/kernelmodel.py GLT017) verifies every
# point of, via VMEM_MODEL_DOMAIN below.
CANDIDATE_TILE_ROWS = (8, 16, 32)
CANDIDATE_RING_DEPTHS = (4, 8)

# Dimension domain for the static VMEM model: analysis/kernelmodel.py
# resolves this dict through the symbol table and checks the closed-form
# VMEM accounting of _gather_sorted_pallas at EVERY assignment of these
# symbols against tpu_limits.VMEM_BYTES.  tile_rows/ring_depth are the
# sweep axes (same tuples the autotuner crosses); `d` is the widest
# feature row the kernel is modeled at.
VMEM_MODEL_DOMAIN = {
    "tile_rows": CANDIDATE_TILE_ROWS,
    "ring_depth": CANDIDATE_RING_DEPTHS,
    "d": tpu_limits.MODEL_MAX_LANES,
}

# Decision table for force='auto': (d, b, dtype) ->
#   ("xla", None) | ("pallas", (tile_rows, ring_depth)).
# Filled by autotune_gather_rows (eager warmup only — a traced call can
# not time anything, it just reads this table).
_AUTO: dict = {}
# Per-key sweep timings for the bench's autotune table:
# (d, b, dtype) -> {"xla": ms, "t8_r4": ms, ...}.
_AUTO_TIMES: dict = {}
# Per-key candidates the compiler refused, with its message:
# (d, b, dtype) -> {"t8_r4": "MosaicError: ...", ...}.
_AUTO_REFUSED: dict = {}


def _sublane_min(dtype) -> int:
    """Smallest legal second-to-last tile dim for this dtype (f32 8,
    bf16 16, int8/fp8 32 — pallas_guide.md 'Tiling Constraints')."""
    return tpu_limits.sublane_min(jnp.dtype(dtype).itemsize)


def default_gather_params(d: int, dtype=jnp.float32) -> tuple:
    """Width-specialized (tile_rows, ring_depth) defaults.

    Holds DMA depth near 16KB per block across row widths — the depth
    where a v5-class DMA engine streams instead of paying setup per
    transfer — and keeps enough ring slots for ~2 tiles of copy-out
    latency to hide behind in-flight DMAs.
    """
    row_bytes = max(int(d) * jnp.dtype(dtype).itemsize, 1)
    tile = max(_sublane_min(dtype),
               min(32, tpu_limits.DMA_DEPTH_TARGET_BYTES // row_bytes))
    tile = max(_MIN_TILE, (tile // _MIN_TILE) * _MIN_TILE)
    return tile, 8


def candidate_gather_params(d: int, dtype=jnp.float32) -> list:
    """The (tile_rows, ring_depth) grid :func:`autotune_gather_rows`
    sweeps for one shape.  Small by design: 3 tile depths x 2 ring
    depths, pruned to legal sublane multiples for the dtype."""
    lo = _sublane_min(dtype)
    tiles = sorted({t for t in CANDIDATE_TILE_ROWS if t >= lo})
    return [(t, r) for t in tiles for r in CANDIDATE_RING_DEPTHS]


def _plan_tiled(idx: jnp.ndarray, n: int, tile: int):
    """XLA prologue: sort ids and coalesce them into aligned tile DMAs.

    Returns static-shape descriptor arrays for the kernel:
      order     [B]  sorted position -> original position
      dstart    [G, _CHUNK] first table row of each DMA
      row_lo/hi [G, _CHUNK] chunk-relative sorted-row range served per DMA
      ndma      [G]  live DMA count per chunk
      off       [B]  row offset of each sorted row inside its tile
    """
    b = idx.shape[0]
    nchunk = b // _CHUNK
    idx = jnp.clip(idx.astype(jnp.int32), 0, n - 1)
    order = jnp.argsort(idx, stable=True)
    sidx = idx[order]
    # Aligned tiles, clamped so the block DMA never overruns the table.
    dstart_row = jnp.clip((sidx // tile) * tile, 0, n - tile)
    off = (sidx - dstart_row).astype(jnp.int32)

    r = jnp.arange(b, dtype=jnp.int32)
    rel = r % _CHUNK
    chunk = r // _CHUNK
    prev = jnp.concatenate(
        [jnp.full((1,), -1, dstart_row.dtype), dstart_row[:-1]])
    # A new DMA starts at every distinct tile and at every chunk boundary
    # (a tile straddling two chunks is fetched once per chunk).
    head = (dstart_row != prev) | (rel == 0)
    gidx = jnp.cumsum(head.astype(jnp.int32)) - 1
    first = gidx[0::_CHUNK]                       # [G]
    dma_j = gidx - first[chunk]                   # [B], in [0, _CHUNK)
    ndma = gidx[_CHUNK - 1::_CHUNK] - first + 1   # [G]

    # Scatter per-DMA descriptors; non-head rows land in an overflow
    # column that is sliced off.
    col = jnp.where(head, dma_j, _CHUNK)
    dstart = (jnp.zeros((nchunk, _CHUNK + 1), jnp.int32)
              .at[chunk, col].set(dstart_row)[:, :_CHUNK])
    row_lo = (jnp.full((nchunk, _CHUNK + 1), _CHUNK, jnp.int32)
              .at[chunk, col].set(rel)[:, :_CHUNK])
    row_hi = jnp.concatenate(
        [row_lo[:, 1:], jnp.full((nchunk, 1), _CHUNK, jnp.int32)], axis=1)
    return order, dstart, row_lo, row_hi, ndma, off


def _make_tiled_kernel(tile: int, nbuf: int):
    """Kernel body over a (tile_rows, ring_depth) parameter point."""

    def kernel(dstart_ref, row_lo_ref, row_hi_ref, ndma_ref, off_ref,
               table_ref, out_ref, tiles, sems):
        c = pl.program_id(0)
        nd = ndma_ref[c]

        def dma(j):
            slot = lax.rem(j, nbuf)
            start = dstart_ref[c, j]
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(start, tile)], tiles.at[slot],
                sems.at[slot])

        # Fill the ring: up to `nbuf` block DMAs in flight before the
        # first copy-out touches a buffer.
        for k in range(nbuf):
            @pl.when(k < nd)
            def _():
                dma(k).start()

        def body(j, _):
            slot = lax.rem(j, nbuf)
            dma(j).wait()
            lo = row_lo_ref[c, j]
            hi = row_hi_ref[c, j]

            def copy_row(s, _):
                o = off_ref[c * _CHUNK + s]
                out_ref[pl.ds(s, 1), :] = tiles[slot, pl.ds(o, 1), :]
                return _

            lax.fori_loop(lo, hi, copy_row, None)
            # Only after this tile's rows are consumed may its buffer
            # slot be reissued (slot j % nbuf == slot (j + nbuf) % nbuf):
            # the next tile's DMA streams while later tiles copy out.
            @pl.when(j + nbuf < nd)
            def _():
                dma(j + nbuf).start()
            return _

        lax.fori_loop(0, nd, body, None)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("interpret", "tile_rows", "ring_depth"))
def _gather_sorted_pallas(table, idx_p, interpret, tile_rows, ring_depth):
    """Core call: gather clip(idx_p) from a lane-aligned table.

    ``idx_p`` is already padded to a _CHUNK multiple; returns rows in
    the ORIGINAL (unsorted) order.  ``table`` last dim must be a
    multiple of 128.
    """
    bp = idx_p.shape[0]
    n, d = table.shape
    order, dstart, row_lo, row_hi, ndma, off = _plan_tiled(
        idx_p, n, tile_rows)
    segments = []
    for lo in range(0, bp, _SEG_ROWS):
        rows = min(_SEG_ROWS, bp - lo)
        c0, c1 = lo // _CHUNK, (lo + rows) // _CHUNK
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(c1 - c0,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((_CHUNK, d), lambda c, *_: (c, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((ring_depth, tile_rows, d), table.dtype),
                pltpu.SemaphoreType.DMA((ring_depth,)),
            ],
        )
        segments.append(pl.pallas_call(
            _make_tiled_kernel(tile_rows, ring_depth),
            out_shape=jax.ShapeDtypeStruct((rows, d), table.dtype),
            grid_spec=grid_spec,
            interpret=interpret,
        )(dstart[c0:c1], row_lo[c0:c1], row_hi[c0:c1], ndma[c0:c1],
          off[lo:lo + rows], table))
    sorted_out = jnp.concatenate(segments)

    # Un-permute: sorted row k belongs at original position order[k].
    inv = (jnp.zeros((bp,), jnp.int32)
           .at[order].set(jnp.arange(bp, dtype=jnp.int32)))
    return jnp.take(sorted_out, inv, axis=0)


def _make_tiled_dequant_kernel(tile: int, nbuf: int, mode: str):
    """The tiled gather kernel with a dequantize epilogue on copy-out.

    Identical DMA structure to :func:`_make_tiled_kernel` — compressed
    table rows stream HBM->VMEM at their narrow storage width and widen
    to f32 only as each row is copied to the output block, so the DMA
    ring moves 2x (bf16) / 4x (int8) fewer bytes than a raw f32 gather.

    ``mode`` is static: ``"widen"`` is a plain f32 astype (bf16 —
    deliberately NOT ``x * 1 + 0``, which would flip ``-0.0``);
    ``"affine"`` applies the per-column ``(x + k) * scale`` /
    constant-column select from the ``sz`` input block (row 0 = scale,
    row 1 = zero, row 2 = k).  The formulas mirror :func:`glt_tpu.
    store.quant.dequantize` exactly — add-then-mul is
    contraction-proof (quant module docstring), so the XLA arm of the
    seam agrees bit-for-bit.
    """

    def kernel(dstart_ref, row_lo_ref, row_hi_ref, ndma_ref, off_ref,
               table_ref, sz_ref, out_ref, tiles, sems):
        c = pl.program_id(0)
        nd = ndma_ref[c]
        scale = sz_ref[0:1, :]
        zero = sz_ref[1:2, :]
        kvec = sz_ref[2:3, :]

        def dma(j):
            slot = lax.rem(j, nbuf)
            start = dstart_ref[c, j]
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(start, tile)], tiles.at[slot],
                sems.at[slot])

        for k in range(nbuf):
            @pl.when(k < nd)
            def _():
                dma(k).start()

        def body(j, _):
            slot = lax.rem(j, nbuf)
            dma(j).wait()
            lo = row_lo_ref[c, j]
            hi = row_hi_ref[c, j]

            def copy_row(s, _):
                o = off_ref[c * _CHUNK + s]
                row = tiles[slot, pl.ds(o, 1), :].astype(jnp.float32)
                if mode == "affine":
                    row = jnp.where(scale > 0.0, (row + kvec) * scale,
                                    zero)
                out_ref[pl.ds(s, 1), :] = row
                return _

            lax.fori_loop(lo, hi, copy_row, None)
            @pl.when(j + nbuf < nd)
            def _():
                dma(j + nbuf).start()
            return _

        lax.fori_loop(0, nd, body, None)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "tile_rows",
                                             "ring_depth", "mode"))
def _gather_sorted_pallas_dq(table, sz, idx_p, interpret, tile_rows,
                             ring_depth, mode):
    """Dequantizing twin of :func:`_gather_sorted_pallas`: compressed
    ``table`` in, f32 rows out.  ``sz`` is the ``[_SZ_ROWS, d]`` f32
    scale/zero block (:func:`glt_tpu.store.quant.scale_zero_rows`)."""
    bp = idx_p.shape[0]
    n, d = table.shape
    order, dstart, row_lo, row_hi, ndma, off = _plan_tiled(
        idx_p, n, tile_rows)
    segments = []
    for lo in range(0, bp, _SEG_ROWS):
        rows = min(_SEG_ROWS, bp - lo)
        c0, c1 = lo // _CHUNK, (lo + rows) // _CHUNK
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(c1 - c0,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((_SZ_ROWS, d), lambda c, *_: (0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((_CHUNK, d), lambda c, *_: (c, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((ring_depth, tile_rows, d), table.dtype),
                pltpu.SemaphoreType.DMA((ring_depth,)),
            ],
        )
        segments.append(pl.pallas_call(
            _make_tiled_dequant_kernel(tile_rows, ring_depth, mode),
            out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
            grid_spec=grid_spec,
            interpret=interpret,
        )(dstart[c0:c1], row_lo[c0:c1], row_hi[c0:c1], ndma[c0:c1],
          off[lo:lo + rows], table, sz))
    sorted_out = jnp.concatenate(segments)

    inv = (jnp.zeros((bp,), jnp.int32)
           .at[order].set(jnp.arange(bp, dtype=jnp.int32)))
    return jnp.take(sorted_out, inv, axis=0)


def gather_rows_pallas_dq(table: jnp.ndarray, idx: jnp.ndarray,
                          spec, interpret: bool = False,
                          tile_rows: int = None,
                          ring_depth: int = None) -> jnp.ndarray:
    """Gather compressed ``table[idx]`` and dequantize on-chip to f32.

    Same shape contract as :func:`gather_rows_pallas`; ``spec`` is the
    store's :class:`~glt_tpu.store.quant.QuantSpec`.  int8 tables obey
    the 32-sublane tiling floor through the same
    :func:`candidate_gather_params` pruning as any 1-byte dtype.
    """
    b = idx.shape[0]
    n, d = table.shape
    mode = "affine" if spec.codec == "int8" else "widen"
    if tile_rows is None or ring_depth is None:
        dt, dr = default_gather_params(d if d % _LANE == 0 else 128,
                                       table.dtype)
        if tile_rows is None:
            rows = n if d % _LANE == 0 else n // 2
            lo = _sublane_min(table.dtype)
            tile_rows = max(lo, min(dt, (rows // lo) * lo))
        if ring_depth is None:
            ring_depth = dr
    bp = -(-b // _CHUNK) * _CHUNK
    idx_p = jnp.concatenate(
        [idx.astype(jnp.int32), jnp.zeros((bp - b,), jnp.int32)])

    if d % _LANE == 0:
        if n < tile_rows:
            raise ValueError(f"table rows {n} must be >= {tile_rows}")
        sz = jnp.asarray(quant.scale_zero_rows(spec, d))
        out = _gather_sorted_pallas_dq(table, sz, idx_p, interpret,
                                       tile_rows, ring_depth, mode)
        return out[:b]
    if d == 64:
        # Paired-row view, as in gather_rows_pallas.  Column j of the
        # original table lands in lanes j AND 64 + j of the paired
        # view, so scale/zero are tiled twice along lanes; dequant runs
        # on the full 128-lane row BEFORE the half-select (the same
        # per-element formula either side of the select).
        if n % 2 != 0:
            raise ValueError(f"d=64 path needs an even row count, got {n}")
        if n // 2 < tile_rows:
            raise ValueError(
                f"paired table rows {n // 2} must be >= {tile_rows}")
        idx_c = jnp.clip(idx_p, 0, n - 1)
        sz64 = quant.scale_zero_rows(spec, 64)
        sz = jnp.asarray(
            jnp.concatenate([jnp.asarray(sz64), jnp.asarray(sz64)], axis=1))
        paired = _gather_sorted_pallas_dq(
            table.reshape(n // 2, _LANE), sz, idx_c // 2, interpret,
            tile_rows, ring_depth, mode)
        half = jnp.take_along_axis(
            paired.reshape(bp, 2, 64),
            (idx_c % 2)[:, None, None], axis=1)[:, 0]
        return half[:b]
    raise ValueError(f"dim {d} must be a multiple of 128 (or exactly 64)")


def gather_rows_pallas(table: jnp.ndarray, idx: jnp.ndarray,
                       interpret: bool = False,
                       tile_rows: int = None,
                       ring_depth: int = None) -> jnp.ndarray:
    """Gather ``table[idx]`` via coalesced block DMAs.

    Args:
      table: ``[N, d]`` feature matrix (HBM-resident).  ``d % _LANE == 0``
        runs natively; ``d == 64`` runs through the paired-row view
        (``N`` must be even); other widths raise.  ``N >= tile_rows``.
      idx: ``[B]`` int32 row ids; out-of-range/negative ids are clamped
        (callers mask padding rows).  ``B`` is padded internally to a
        multiple of 256.
      tile_rows / ring_depth: DMA tile depth and copy-ring slots; None
        picks the width-specialized default
        (:func:`default_gather_params`).
    """
    b = idx.shape[0]
    n, d = table.shape
    # NOTE: tile_rows/ring_depth are static Python ints (jit static
    # args) — no coercions here, so the transitive host-sync analysis
    # (GLT001) sees this body as jnp-pure from every traced caller.
    if tile_rows is None or ring_depth is None:
        dt, dr = default_gather_params(d if d % _LANE == 0 else 128,
                                       table.dtype)
        if tile_rows is None:
            # Defaults adapt to tiny tables: the deepest legal tile not
            # exceeding the table height (explicit tile_rows still
            # raises past the table — the autotuner relies on that).
            rows = n if d % _LANE == 0 else n // 2
            tile_rows = max(_MIN_TILE,
                            min(dt, (rows // _MIN_TILE) * _MIN_TILE))
        if ring_depth is None:
            ring_depth = dr
    bp = -(-b // _CHUNK) * _CHUNK
    idx_p = jnp.concatenate(
        [idx.astype(jnp.int32), jnp.zeros((bp - b,), jnp.int32)])

    if d % _LANE == 0:
        if n < tile_rows:
            raise ValueError(f"table rows {n} must be >= {tile_rows}")
        out = _gather_sorted_pallas(table, idx_p, interpret, tile_rows,
                                    ring_depth)
        return out[:b]
    if d == 64:
        # Paired-row view: [N/2, 128].  The kernel moves full 128-lane
        # rows (a 64-lane DMA pads to 128 lanes in VMEM anyway); the
        # epilogue selects the requested half per original position.
        if n % 2 != 0:
            raise ValueError(f"d=64 path needs an even row count, got {n}")
        if n // 2 < tile_rows:
            raise ValueError(
                f"paired table rows {n // 2} must be >= {tile_rows}")
        idx_c = jnp.clip(idx_p, 0, n - 1)
        paired = _gather_sorted_pallas(table.reshape(n // 2, _LANE),
                                       idx_c // 2, interpret, tile_rows,
                                       ring_depth)
        half = jnp.take_along_axis(
            paired.reshape(bp, 2, 64),
            (idx_c % 2)[:, None, None], axis=1)[:, 0]
        return half[:b]
    raise ValueError(f"dim {d} must be a multiple of 128 (or exactly 64)")


def _xla_gather(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(table, jnp.clip(idx, 0, table.shape[0] - 1), axis=0)


def pallas_gather_supported(table, idx, tile_rows: int = _MIN_TILE) -> bool:
    """Shape and dtype constraints of the tiled kernel.  4-byte rows
    only: Mosaic refuses the packed-dtype row load (module docstring)."""
    n, d = table.shape
    if table.dtype.itemsize != 4:
        return False
    if d % _LANE == 0:
        return n >= tile_rows
    return d == 64 and n % 2 == 0 and n // 2 >= tile_rows


def _auto_key(table, idx):
    return (int(table.shape[1]), int(idx.shape[0]), str(table.dtype))


def _fmt_params(params) -> str:
    return "xla" if params is None else f"t{params[0]}_r{params[1]}"


def autotune_gather_rows(table: jnp.ndarray, idx: jnp.ndarray,
                         iters: int = 3) -> str:
    """Sweep XLA vs the tiled kernel's (tile_rows, ring_depth) grid for
    this (row width, batch, dtype) and memoize the winner for
    ``gather_rows(force='auto')``.

    Call EAGERLY at warmup (loader construction / bench setup) — never
    from inside a trace.  Each timing ends in a host value fetch.
    Off-TPU backends and unsupported shapes pin 'xla' without a sweep.
    A candidate the compiler refuses is recorded under its name with
    the compiler's message (:func:`autotune_table` ``refused``) and
    counted in ``glt.gather.autotune_refused`` — it never reads as
    "XLA was faster"; a failure of the XLA arm itself propagates.

    Returns ``'pallas'`` or ``'xla'`` (the per-candidate landscape is
    kept in :func:`autotune_table`).  The key includes the exact batch
    size, so an occupancy-capped shape is swept on its own rather than
    inheriting the full-cap winner.
    """
    key = _auto_key(table, idx)
    if key in _AUTO:
        return "xla" if _AUTO[key] is None else "pallas"
    winner = None          # None = xla; else (tile_rows, ring_depth)
    times: dict = {}
    refused: dict = {}
    if (jax.default_backend() == "tpu"
            and pallas_gather_supported(table, idx)):
        def timed(fn):
            float(fn(table, idx)[0, 0])  # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(table, idx)
            float(out[0, 0])             # host fetch: the sync
            return (time.perf_counter() - t0) / iters * 1e3

        best = times["xla"] = timed(_xla_gather)
        for params in candidate_gather_params(table.shape[1],
                                              table.dtype):
            if not pallas_gather_supported(table, idx, params[0]):
                continue
            try:
                t = timed(functools.partial(
                    gather_rows_pallas, tile_rows=params[0],
                    ring_depth=params[1]))
            except Exception as e:  # noqa: BLE001 — sweep boundary: recorded
                refused[_fmt_params(params)] = tpu_limits.compiler_message(e)
                continue
            times[_fmt_params(params)] = t
            if t < best:
                best, winner = t, params
    _AUTO[key] = winner
    _AUTO_TIMES[key] = times
    _AUTO_REFUSED[key] = refused
    choice = "xla" if winner is None else "pallas"
    # Autotune runs host-side at warmup (never under trace — GLT010), so
    # the kernel decision is safe to publish here.
    from ..obs import metrics as _metrics

    _metrics.counter("glt.gather.autotune_runs",
                     "gather kernel sweep warmups").inc()
    _metrics.counter("glt.gather.autotune_refused",
                     "gather sweep candidates the compiler refused"
                     ).inc(len(refused))
    _metrics.gauge("glt.gather.pallas_selected",
                   "1 if the last gather autotune picked the tiled "
                   "Pallas kernel", labels={"d": str(key[0])},
                   ).set(1.0 if choice == "pallas" else 0.0)
    return choice


def autotune_table() -> dict:
    """The sweep landscape, JSON-ready: ``{"d128_b139264_float32":
    {"winner": "t32_r8", "ms": {"xla": 4.1, "t8_r4": ...}, "refused":
    {"t32_r4": "MosaicError: ..."}}, ...}``.  An empty ``ms`` means the
    shape was pinned to XLA without a sweep (off-TPU or unsupported)."""
    out = {}
    for key, winner in _AUTO.items():
        d, b, dt = key
        out[f"d{d}_b{b}_{dt}"] = {
            "winner": _fmt_params(winner),
            "ms": {k: round(v, 4)
                   for k, v in _AUTO_TIMES.get(key, {}).items()},
            "refused": dict(_AUTO_REFUSED.get(key, {})),
        }
    return out


def reset_autotune() -> None:
    """Drop all memoized decisions (tests / re-calibration)."""
    _AUTO.clear()
    _AUTO_TIMES.clear()
    _AUTO_REFUSED.clear()


def gather_rows(table: jnp.ndarray, idx: jnp.ndarray,
                force: str = "auto", dequant=None) -> jnp.ndarray:
    """Gather rows, choosing the best implementation.

    force: 'auto' | 'pallas' | 'xla'.  'auto' reads the decision table
    filled by :func:`autotune_gather_rows` (XLA until a measurement
    exists) and runs the winning (tile_rows, ring_depth) point.  The
    ``GLT_GATHER_FORCE`` env var overrides ``force``.

    dequant: optional :class:`~glt_tpu.store.quant.QuantSpec` for a
    compressed ``table``.  The Pallas arm (``force='pallas'`` only)
    widens rows to f32 in the copy-out epilogue (compressed bytes over
    the DMA ring); the XLA arm gathers compressed rows and dequantizes
    post-gather with the identical formula, so both arms agree
    bit-for-bit.  ``dequant=None`` (or a raw spec) is byte-for-byte the
    pre-codec path.
    """
    env = os.environ.get("GLT_GATHER_FORCE")
    if env in ("pallas", "xla"):
        force = env
    if dequant is not None and dequant.is_compressed:
        # Mosaic refuses the dequant kernel's packed-dtype row load
        # (module docstring), so 'auto' is the XLA arm for every
        # compressed table; only an explicit 'pallas' reaches the kernel.
        if force == "pallas":
            return gather_rows_pallas_dq(table, idx, dequant)
        return quant.dequantize(_xla_gather(table, idx), dequant)
    if force == "pallas":
        params = _AUTO.get(_auto_key(table, idx))
        if params is not None:
            return gather_rows_pallas(table, idx, tile_rows=params[0],
                                      ring_depth=params[1])
        return gather_rows_pallas(table, idx)
    if force == "auto":
        params = _AUTO.get(_auto_key(table, idx))
        if params is not None:
            return gather_rows_pallas(table, idx, tile_rows=params[0],
                                      ring_depth=params[1])
    return _xla_gather(table, idx)
