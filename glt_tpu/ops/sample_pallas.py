"""Pallas degree-binned neighbor-sampling kernel: the sampling hot op.

TPU counterpart of the reference's warp-per-seed CUDA sampler
(``csrc/cuda/random_sampler.cu:87-106``): there, one warp walks each
seed row's adjacency with a Philox stream per thread.  Here the hop is
split along the compute/memory boundary:

* the **draw** (Floyd / with-replacement positions) stays in XLA via the
  shared :func:`~glt_tpu.ops.neighbor_sample._draw_positions` — pltpu's
  kernel PRNG is not threefry-bit-compatible with jax.random, and the
  draw is vector math, not the wall;
* the **neighbor read** — ``indices[start + pos]``, a random gather over
  the edge array, the bytes the sample stage exists to move — runs as
  tiled DMAs with the ring discipline of gather_pallas.py.

**Degree binning.**  Random row windows have wildly different widths on
power-law graphs; a tile mixing degree-4 and degree-4000 rows stalls on
its hub row.  Seeds are bucketed by degree class (``deg <= edges[b]``)
and stable-sorted by bin, so each per-bin kernel launch sees tiles of
comparable work and uses a window width ``W_b`` sized to its class.
Per row the kernel DMAs the 128-aligned window ``indices[estart :
estart + W_b]`` covering ``[start, start + deg)`` into a VMEM ring
(``ring_depth`` slots in flight while earlier rows copy out) and
selects the ``fanout`` drawn lanes with a broadcasted-iota masked sum
(dynamic LANE indexing is unsupported on TPU).  Rows above the last bin
edge (hubs) fall through to an XLA epilogue gather — a handful of rows
whose windows would blow the VMEM ring.

``autotune_sample`` sweeps (tile_rows, ring_depth, bin_edges) against
the XLA path per **exact** (batch, fanout, dtype) key.  Off-TPU
backends pin 'xla': on CPU the seam resolves honestly to the XLA path
(interpret mode exists for correctness tests, not for winning
benchmarks).

**On the TPU v5e the kernel does not compile** (:data:`TPU_REFUSAL`,
PR 21 bring-up).  Until the window geometry is redesigned ``auto`` is
the XLA path there and ``force='pallas'`` raises Mosaic's message; the
kernel is exercised in interpret mode only.
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

from ..typing import PADDING_ID
from . import tpu_limits
from .neighbor_sample import (NeighborOutput, _row_offsets_and_degrees,
                              draw_positions)

_LANE = tpu_limits.LANE

# Decision table for sample_neighbors(force='auto'):
#   (batch, fanout, dtype) -> None (= xla) | (tile_rows, ring_depth,
#   bin_edges).  Filled by autotune_sample at eager warmup only.
_AUTO: dict = {}
# Per-key sweep timings for the bench's sample_autotune table:
#   (batch, fanout, dtype) -> {"xla": ms, "t128_r4_e64x512": ms, ...}.
_AUTO_TIMES: dict = {}
# Per-key candidates the compiler refused, with its message ("all" when
# TPU_REFUSAL ruled the whole sweep out).
_AUTO_REFUSED: dict = {}

DEFAULT_BIN_EDGES = (64, 512)

#: Why no point of the sweep compiles on the TPU v5e (jax 0.9.0, libtpu
#: 0.0.34; every ``candidate_sample_params`` point at each config-1 hop
#: shape).  A 1-D int32 array is tiled (1024) in HBM, and a window slice
#: of it must be a 1024-multiple long from a start Mosaic can prove is
#: one; the per-row windows here are 128-aligned (256..2176 lanes) at a
#: data-dependent start.  With 1024-aligned windows the next refusal is
#: "Failed to prove that a tile index in dimension 0 is divisible by the
#: tiling (1024)".  While this is set :func:`autotune_sample` runs no
#: sweep on a TPU and records the reason; set it to None once the
#: kernel compiles there.
TPU_REFUSAL = (
    "MosaicError: INTERNAL: Mosaic failed to compile TPU kernel: Slice "
    "shape along dimension 0 must be aligned to tiling (1024), but is 640."
    " The MLIR operation involved: tpu.memref_slice (memref<29388800xi32, "
    "#tpu.tiled<(1024),[1]>, #tpu.memory_space<hbm>>, i32) -> "
    "memref<640xi32, ...>")

# The (tile_rows, ring_depth, bin_edges) grid :func:`autotune_sample`
# sweeps — and the grid the static VMEM model (analysis/kernelmodel.py
# GLT017) verifies every point of, via VMEM_MODEL_DOMAIN below.
CANDIDATE_TILE_ROWS = (128, 256)
CANDIDATE_RING_DEPTHS = (4, 8)
CANDIDATE_BIN_EDGES = ((64, 512), (32, 256, 2048))

# Widest fanout the static VMEM model assumes (production fanouts run
# 5-25; the out block is [tile, fanout] so fanout bounds its lanes).
MODEL_MAX_FANOUT = 64

# Dimension domain for the static VMEM model: analysis/kernelmodel.py
# resolves this dict through the symbol table and checks the closed-form
# VMEM accounting of _binned_take_sorted at EVERY assignment of these
# symbols against tpu_limits.VMEM_BYTES.  The per-bin window width `w`
# is derived inside the function (`_bin_width(edge)` over the bin-edges
# layout), so the domain only needs the sweep axes themselves.
VMEM_MODEL_DOMAIN = {
    "tile": CANDIDATE_TILE_ROWS,
    "ring": CANDIDATE_RING_DEPTHS,
    "bin_edges": CANDIDATE_BIN_EDGES,
    "fanout": MODEL_MAX_FANOUT,
}


def _bin_width(edge: int) -> int:
    """Window lanes for a degree class: the smallest 128-multiple that
    covers any ``[start, start + deg)`` run with ``deg <= edge`` from a
    128-aligned (possibly end-clamped) window start — the aligned start
    can sit up to 127 elements before ``start``, hence the ``+127``.
    ``edge`` is always a static Python int (a bin-edges entry)."""
    return -(-(edge + _LANE - 1) // _LANE) * _LANE


def default_sample_params() -> tuple:
    """(tile_rows, ring_depth, bin_edges) fallback when no sweep ran."""
    return 128, 4, DEFAULT_BIN_EDGES


def candidate_sample_params() -> list:
    """The (tile_rows, ring_depth, bin_edges) grid
    :func:`autotune_sample` sweeps for one shape.  Two bin layouts — a
    shallow pair for near-uniform graphs and a three-class ladder whose
    top bin keeps power-law hubs off the XLA epilogue — crossed with the
    tile/ring depths that bound per-launch VMEM at ring * W * 4B."""
    return [(t, r, e)
            for e in CANDIDATE_BIN_EDGES
            for t in CANDIDATE_TILE_ROWS
            for r in CANDIDATE_RING_DEPTHS]


def pallas_sample_supported(indices: jnp.ndarray,
                            bin_edges=DEFAULT_BIN_EDGES) -> bool:
    """Autotune gate: sweeping a bin layout whose widest window exceeds
    the whole edge array is pointless (the kernel pads and still runs —
    correctness is unconditional — but XLA wins such toy graphs)."""
    return int(indices.shape[0]) >= _bin_width(max(bin_edges))


def _plan_binned(start, deg, bin_edges, tile: int, e: int):
    """XLA prologue: degree-class ids, clamped window starts, and the
    bin-sorted descriptor arrays the per-bin kernels consume.

    Every bin launch receives the FULL sorted descriptor set and skips
    foreign rows via a per-row ``binid == b`` guard — the guard is the
    same predicate on DMA start and wait, so the ring stays consistent
    across skipped rows.
    """
    b = deg.shape[0]
    nbins = len(bin_edges)
    edges_arr = jnp.asarray(bin_edges, jnp.int32)
    # deg <= edges[i] -> bin i; deg > edges[-1] -> nbins (hub epilogue).
    binid = jnp.searchsorted(edges_arr, deg, side="left").astype(jnp.int32)
    warr = jnp.asarray([_bin_width(x) for x in bin_edges] + [_LANE],
                       jnp.int32)
    w_row = warr[jnp.clip(binid, 0, nbins)]
    start = start.astype(jnp.int32)
    # 128-aligned window start, end-clamped so estart + W never overruns
    # the edge array; off + pos < W still holds because start + pos is a
    # valid edge index (< e <= estart + W).
    estart = jnp.clip((start // _LANE) * _LANE, 0,
                      jnp.maximum(e - w_row, 0))
    off = (start - estart).astype(jnp.int32)

    order = jnp.argsort(binid, stable=True)
    bp = -(-b // tile) * tile
    pad = bp - b
    binid_s = jnp.concatenate(
        [binid[order], jnp.full((pad,), nbins, jnp.int32)])
    estart_s = jnp.concatenate([estart[order], jnp.zeros((pad,), jnp.int32)])
    off_s = jnp.concatenate([off[order], jnp.zeros((pad,), jnp.int32)])
    # Original row i lives at sorted slot inv[i].
    inv = (jnp.zeros((b,), jnp.int32)
           .at[order].set(jnp.arange(b, dtype=jnp.int32)))
    return binid, binid_s, estart_s, off_s, order, inv, bp


def _make_bin_kernel(bin_id: int, tile: int, nbuf: int, w: int,
                     fanout: int):
    """Kernel for one degree class: per-row windowed DMA ring + masked
    lane select (dynamic sublane indexing is fine; dynamic LANE indexing
    is not — the iota/masked-sum picks the drawn lanes vectorized over
    fanout)."""

    def kernel(binid_ref, estart_ref, off_ref, pos_ref, src_ref, out_ref,
               chunks, sems):
        c = pl.program_id(0)
        base = c * tile

        def dma(j):
            slot = lax.rem(j, nbuf)
            return pltpu.make_async_copy(
                src_ref.at[pl.ds(estart_ref[base + j], w)],
                chunks.at[slot], sems.at[slot])

        # Fill the ring: up to `nbuf` row windows streaming before the
        # first copy-out.  Start and wait share the row's bin predicate,
        # so a skipped row never leaves a dangling DMA on its slot.
        for k in range(nbuf):
            @pl.when(binid_ref[base + k] == bin_id)
            def _():
                dma(k).start()

        def body(j, carry):
            slot = lax.rem(j, nbuf)

            @pl.when(binid_ref[base + j] == bin_id)
            def _():
                dma(j).wait()
                prow = pos_ref[j, :] + off_ref[base + j]      # [fanout]
                chunk = chunks[slot, :]                       # [w]
                sel = (lax.broadcasted_iota(jnp.int32, (fanout, w), 1)
                       == prow[:, None])
                vals = jnp.sum(jnp.where(sel, chunk[None, :], 0), axis=1)
                out_ref[pl.ds(j, 1), :] = vals[None, :].astype(jnp.int32)

            # Slot j % nbuf is free for row j + nbuf only after row j's
            # copy-out (or if row j never used it — then its last DMA
            # was already waited at an earlier body step).
            @pl.when((j + nbuf < tile)
                     & (binid_ref[base + j + nbuf] == bin_id))
            def _():
                dma(j + nbuf).start()

            return carry

        lax.fori_loop(0, tile, body, None)

    return kernel


def _binned_take_sorted(src, binid_s, estart_s, off_s, pos_s, bin_edges,
                        tile: int, ring: int, fanout: int,
                        interpret: bool):
    """Run one kernel per degree class over the full sorted descriptor
    set and merge per-bin outputs by the bin predicate."""
    bp = binid_s.shape[0]
    acc = jnp.zeros((bp, fanout), jnp.int32)
    for b_id, edge in enumerate(bin_edges):
        w = _bin_width(edge)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bp // tile,),
            in_specs=[
                # fanout (<=64) is deliberately narrower than the
                # 128-lane register: Mosaic pads the row in-register and
                # the padding cost (~2x on the [tile, fanout] blocks,
                # still <1% of VMEM) beats doubling every descriptor and
                # output buffer to a 128 stride end to end.
                # gltlint: disable-next=unaligned-tile-shape
                pl.BlockSpec((tile, fanout), lambda c, *_: (c, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            # Same narrow-fanout trade as the in block above.
            # gltlint: disable-next=unaligned-tile-shape
            out_specs=pl.BlockSpec((tile, fanout), lambda c, *_: (c, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                # ring=4 sits under the 8-sublane int32 floor; the slots
                # are row-granular DMA landing pads (never a tiled
                # compute operand), so the floor costs padding only —
                # deepening the ring to 8 would double live DMAs for no
                # measured gain (ROADMAP item 1 sweep).
                # gltlint: disable-next=unaligned-tile-shape
                pltpu.VMEM((ring, w), jnp.int32),
                pltpu.SemaphoreType.DMA((ring,)),
            ],
        )
        out_b = pl.pallas_call(
            _make_bin_kernel(b_id, tile, ring, w, fanout),
            out_shape=jax.ShapeDtypeStruct((bp, fanout), jnp.int32),
            grid_spec=grid_spec,
            interpret=interpret,
        )(binid_s, estart_s, off_s, pos_s, src)
        acc = jnp.where((binid_s == b_id)[:, None], out_b, acc)
    return acc


def sample_neighbors_pallas(
    indptr: jnp.ndarray,
    indices: jnp.ndarray,
    seeds: jnp.ndarray,
    fanout: int,
    key: jax.Array,
    edge_ids=None,
    with_replacement: bool = False,
    with_edge: bool = True,
    params=None,
    interpret: bool = False,
    key_by: str = "slot",
) -> NeighborOutput:
    """Degree-binned Pallas neighbor sampling — bit-identical to
    :func:`~glt_tpu.ops.neighbor_sample.sample_neighbors` (same draw,
    same ``[B, fanout]`` -1-padded contract).

    Args:
      params: ``(tile_rows, ring_depth, bin_edges)`` from the autotune
        table, or None for :func:`default_sample_params`.
      interpret: run the kernels in Pallas interpret mode (CPU tests).
    """
    if fanout <= 0:
        raise ValueError(f"fanout must be positive, got {fanout}")
    tile, ring, bin_edges = (params if params is not None
                             else default_sample_params())
    seeds = seeds.astype(jnp.int32)
    b = seeds.shape[0]
    nbins = len(bin_edges)
    # Windowed DMAs read whole W-lane windows; graphs with fewer edges
    # than the widest window (tiny test fixtures, mostly) get the edge
    # arrays padded up so the end-clamped window start never underruns.
    # Padding lanes are never *selected* — start + pos is always a real
    # edge index for valid mask positions.
    wmax = _bin_width(max(bin_edges))
    e = max(int(indices.shape[0]), wmax)
    pad_e = e - int(indices.shape[0])
    start, deg = _row_offsets_and_degrees(indptr, seeds)
    pos, mask = draw_positions(deg, fanout, key, with_replacement, seeds,
                               key_by=key_by)
    pos0 = jnp.where(mask, pos, 0).astype(jnp.int32)

    binid, binid_s, estart_s, off_s, order, inv, bp = _plan_binned(
        start, deg, bin_edges, tile, e)
    pos_s = jnp.concatenate(
        [pos0[order], jnp.zeros((bp - b, fanout), jnp.int32)])
    flat = start[:, None] + pos0
    hub = binid >= nbins

    def take(src):
        src = src.astype(jnp.int32)
        if pad_e:
            src = jnp.concatenate([src, jnp.zeros((pad_e,), jnp.int32)])
        sorted_vals = _binned_take_sorted(
            src, binid_s, estart_s, off_s, pos_s, bin_edges, tile, ring,
            fanout, interpret)
        vals = jnp.take(sorted_vals, inv, axis=0)
        # Hub epilogue: rows past the last bin edge read straight from
        # HBM via XLA (index 0 for the non-hub majority — a cached row).
        safe = jnp.where(hub[:, None], flat, 0)
        return jnp.where(hub[:, None], src[safe], vals)

    nbrs = jnp.where(mask, take(indices), PADDING_ID).astype(jnp.int32)
    if not with_edge:
        eids = None
    elif edge_ids is None:
        eids = jnp.where(mask, flat, PADDING_ID).astype(jnp.int32)
    else:
        eids = jnp.where(mask, take(edge_ids), PADDING_ID).astype(jnp.int32)
    return NeighborOutput(nbrs=nbrs, eids=eids, mask=mask)


def _auto_key(batch: int, fanout: int, dtype) -> tuple:
    return (int(batch), int(fanout), str(jnp.dtype(dtype)))


def auto_params(batch: int, fanout: int, dtype):
    """The memoized winner for this exact shape, or None (= xla / not
    swept).  Read by ``sample_neighbors(force='auto')`` at trace time."""
    return _AUTO.get(_auto_key(batch, fanout, dtype))


def _fmt_params(params) -> str:
    if params is None:
        return "xla"
    t, r, e = params
    return f"t{t}_r{r}_e{'x'.join(str(x) for x in e)}"


def autotune_sample(indptr: jnp.ndarray, indices: jnp.ndarray,
                    seeds: jnp.ndarray, fanout: int,
                    key=None, edge_ids=None,
                    with_replacement: bool = False,
                    with_edge: bool = True, iters: int = 3) -> str:
    """Sweep XLA vs the binned kernel's (tile_rows, ring_depth,
    bin_edges) grid for this exact (batch, fanout, dtype) and memoize
    the winner for ``sample_neighbors(force='auto')``.

    Call EAGERLY at warmup (loader construction / bench setup) — never
    from inside a trace.  Each timing ends in a host value fetch.
    Off-TPU backends pin 'xla' without a sweep, and so does a TPU while
    :data:`TPU_REFUSAL` stands.  A candidate the compiler refuses is
    recorded under its name with the compiler's message
    (:func:`sample_autotune_table` ``refused``) and counted in
    ``glt.sample.autotune_refused`` — it never reads as "XLA was
    faster"; a failure of the XLA arm itself propagates.

    Returns ``'pallas'`` or ``'xla'``; the per-candidate landscape lands
    in :func:`sample_autotune_table`.  Keys by the exact batch size — a
    capped loader shape gets its own sweep instead of inheriting the
    full-cap winner.
    """
    from ..obs import compilewatch as _compilewatch
    from ..obs import metrics as _metrics
    from .neighbor_sample import sample_neighbors as _sample_xla

    akey = _auto_key(seeds.shape[0], fanout, indices.dtype)
    if akey in _AUTO:
        return "xla" if _AUTO[akey] is None else "pallas"
    if key is None:
        key = jax.random.PRNGKey(0)
    winner = None          # None = xla; else (tile, ring, bin_edges)
    times: dict = {}
    refused: dict = {}
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and TPU_REFUSAL is not None:
        refused["all"] = TPU_REFUSAL
    elif on_tpu:
        def timed(fn):
            int(fn(indptr, indices, seeds, key).nbrs[0, 0])  # compile+warm
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(indptr, indices, seeds, key)
            int(out.nbrs[0, 0])                  # host fetch: the sync
            return (time.perf_counter() - t0) / iters * 1e3

        def xla_fn(ip, ix, sd, k):
            return _sample_xla(ip, ix, sd, fanout, k, edge_ids=edge_ids,
                               with_replacement=with_replacement,
                               with_edge=with_edge, force="xla")

        best = times["xla"] = timed(jax.jit(xla_fn))
        for params in candidate_sample_params():
            if not pallas_sample_supported(indices, params[2]):
                continue

            def pfn(ip, ix, sd, k, _p=params):
                return sample_neighbors_pallas(
                    ip, ix, sd, fanout, k, edge_ids=edge_ids,
                    with_replacement=with_replacement,
                    with_edge=with_edge, params=_p)

            try:
                # Label the kernel-entry jit call site so
                # glt.compile.*{program=} attributes the sweep's
                # compiles and the storm detector covers them.
                with _compilewatch.label(
                        f"sample_pallas_{_fmt_params(params)}"):
                    t = timed(jax.jit(pfn))
            except Exception as e:  # noqa: BLE001 — sweep boundary: recorded
                refused[_fmt_params(params)] = tpu_limits.compiler_message(e)
                continue
            times[_fmt_params(params)] = t
            if t < best:
                best, winner = t, params
    _AUTO[akey] = winner
    _AUTO_TIMES[akey] = times
    _AUTO_REFUSED[akey] = refused
    choice = "xla" if winner is None else "pallas"
    # Autotune runs host-side at warmup (never under trace — GLT010), so
    # the kernel decision is safe to publish here.
    _metrics.counter("glt.sample.autotune_runs",
                     "sample kernel sweep warmups").inc()
    _metrics.counter("glt.sample.autotune_refused",
                     "sample sweep candidates the compiler refused"
                     ).inc(len(refused))
    _metrics.gauge("glt.sample.pallas_selected",
                   "1 if the last sample autotune picked the binned "
                   "Pallas kernel", labels={"fanout": str(fanout)},
                   ).set(1.0 if choice == "pallas" else 0.0)
    return choice


def sample_autotune_table() -> dict:
    """The sweep landscape, JSON-ready: ``{"b512_f10_int32": {"winner":
    "t128_r4_e64x512", "ms": {"xla": 2.1, ...}, "refused": {...}},
    ...}``.  Empty ``ms`` means the shape was pinned to XLA without a
    sweep (off-TPU, or on a TPU under :data:`TPU_REFUSAL`, which
    ``refused`` then carries)."""
    out = {}
    for akey, winner in _AUTO.items():
        b, f, dt = akey
        out[f"b{b}_f{f}_{dt}"] = {
            "winner": _fmt_params(winner),
            "ms": {k: round(v, 4)
                   for k, v in _AUTO_TIMES.get(akey, {}).items()},
            "refused": dict(_AUTO_REFUSED.get(akey, {})),
        }
    return out


def reset_autotune() -> None:
    """Drop all memoized decisions (tests / re-calibration)."""
    _AUTO.clear()
    _AUTO_TIMES.clear()
    _AUTO_REFUSED.clear()
