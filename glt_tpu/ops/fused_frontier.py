"""Fused frontier dedup + feature gather: one dispatch, zero HBM bounce.

The unfused pipeline (:func:`~glt_tpu.ops.dedup_gather.dedup_gather_rows`)
materialises the ``[U, d]`` unique-row block in HBM and then re-reads it
for the scatter-back — two full passes over the frontier's feature bytes.
When the unique block fits VMEM there is no reason for it to ever touch
HBM: this kernel DMAs each unique row **once** from the feature table
into a VMEM-resident buffer and serves every duplicate position straight
out of that buffer, fusing dedup-gather and scatter-back into a single
``pallas_call``.

Division of labor (mirrors the sampling seam in sample_pallas.py):

* **ordering** stays in XLA — :func:`unique_first_occurrence` computes
  the first-occurrence unique ids and inverse permutation, the
  bit-identity anchor shared with the unfused path;
* **bytes** move in the kernel — phase A (grid step 0) streams the
  ``count`` live unique rows through a ring of per-row DMAs into the
  persistent VMEM buffer (scratch persists across sequential grid
  steps); phase B copies ``out[i] = buf[inverse[i]]`` per 256-row output
  chunk via dynamic-sublane loads.

The contract is the dedup_gather_rows contract, bit for bit: ``features``
matches ``where(ids >= 0, table[id2index[ids]], 0)`` exactly, so every
existing train/dist test doubles as a correctness oracle.  Frontiers
whose unique block exceeds the VMEM budget (or feature widths not a
multiple of 128 lanes) fall back to the unfused path — same bits, two
HBM passes.  ``GLT_FUSED_FORCE`` (``pallas``/``xla``/``interpret``)
overrides the seam; off-TPU ``auto`` resolves to the XLA path and
interpret mode keeps CPU tests hardware-free.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tpu_limits
from ..store import quant
from .gather_pallas import gather_rows
from .unique import unique_first_occurrence

_CHUNK = 256
_LANE = tpu_limits.LANE
_SUBLANE = tpu_limits.SUBLANE_F32
# Sublane count of the packed scale/zero/k input block (== quant.
# SCALE_ZERO_ROWS, padded to the f32 tiling floor for GLT019).
_SZ_ROWS = 8
# Unique-block VMEM budget: 3/8 of the core's VMEM (~6 MB of 16) leaves
# headroom for the output chunk, double-buffered DMA metadata, and
# whatever the surrounding scanned step keeps live.  Derived from
# tpu_limits so the runtime gate (fused_frontier_supported) and the
# static model (analysis/kernelmodel.py GLT017) can never disagree.
DEFAULT_VMEM_BUDGET = tpu_limits.VMEM_BYTES * 3 // 8
_RING = 8

# Dimension domain for the static VMEM model.  The scratch buffer is
# [up, d] where both dims are runtime-sized but their PRODUCT is gated
# by fused_frontier_supported (up * d * itemsize <= DEFAULT_VMEM_BUDGET),
# so the model checks the gate's corner points jointly: at each feature
# width, the deepest unique block the runtime gate admits.
VMEM_MODEL_DOMAIN = {
    ("up", "d"): (
        (DEFAULT_VMEM_BUDGET // (tpu_limits.LANE * 4), tpu_limits.LANE),
        (DEFAULT_VMEM_BUDGET // (512 * 4), 512),
        (DEFAULT_VMEM_BUDGET // (tpu_limits.MODEL_MAX_LANES * 4),
         tpu_limits.MODEL_MAX_LANES),
    ),
}


class FusedFrontier(NamedTuple):
    """One-dispatch frontier: ids deduped and features gathered."""
    unique_ids: jnp.ndarray   # [B] first-occurrence unique ids, -1 padded
    inverse: jnp.ndarray      # [B] position -> unique slot, -1 at padding
    features: jnp.ndarray     # [B, d], bit-identical to dedup_gather_rows


def fused_frontier_supported(table: jnp.ndarray, ids: jnp.ndarray,
                             vmem_budget: Optional[int] = None) -> bool:
    """True when the unique block fits the VMEM budget and the feature
    width tiles the 128-lane register exactly (the fused kernel does
    whole-row DMAs/copies; odd widths go to the unfused path)."""
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    d = int(table.shape[1])
    up = -(-int(ids.shape[0]) // _SUBLANE) * _SUBLANE
    return d % _LANE == 0 and up * d * table.dtype.itemsize <= budget


def _make_fused_kernel(up: int, nbuf: int, chunk: int):
    def kernel(uid_ref, nu_ref, inv_ref, table_ref, out_ref, buf, sems):
        c = pl.program_id(0)

        # Phase A (first grid step only): stream the live unique rows
        # into the persistent VMEM buffer.  `buf` is scratch, which on
        # TPU persists across the sequential grid — later steps reuse
        # the rows filled here.
        @pl.when(c == 0)
        def _():
            nu = nu_ref[0]

            def dma(j):
                return pltpu.make_async_copy(
                    table_ref.at[pl.ds(uid_ref[j], 1)],
                    buf.at[pl.ds(j, 1)],
                    sems.at[lax.rem(j, nbuf)])

            for k in range(nbuf):
                @pl.when(k < nu)
                def _():
                    dma(k).start()

            def fill(j, carry):
                @pl.when(j < nu)
                def _():
                    dma(j).wait()

                @pl.when(j + nbuf < nu)
                def _():
                    dma(j + nbuf).start()

                return carry

            lax.fori_loop(0, up, fill, None)

        # Phase B (every grid step): serve this output chunk from the
        # buffer.  Dynamic-SUBLANE indexing (pl.ds over rows) is
        # supported; inv_ref is pre-clipped so padding rows read slot 0
        # harmlessly (the XLA epilogue zeroes them).
        def copy_row(s, carry):
            iv = inv_ref[c * chunk + s]
            out_ref[pl.ds(s, 1), :] = buf[pl.ds(iv, 1), :]
            return carry

        lax.fori_loop(0, chunk, copy_row, None)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "ring_depth"))
def _fused_gather(table, uidx, count, inv, interpret=False,
                  ring_depth=_RING):
    """[B, d] rows with ``out[i] = table[uidx[inv[i]]]`` for ``inv[i] >=
    0`` positions (padding rows carry garbage; caller zeroes them)."""
    b = inv.shape[0]
    d = table.shape[1]
    n = table.shape[0]
    up = -(-b // _SUBLANE) * _SUBLANE
    bp = -(-b // _CHUNK) * _CHUNK
    uid_p = jnp.concatenate(
        [jnp.clip(uidx.astype(jnp.int32), 0, n - 1),
         jnp.zeros((up - b,), jnp.int32)])
    inv_p = jnp.concatenate(
        [jnp.clip(inv.astype(jnp.int32), 0, up - 1),
         jnp.zeros((bp - b,), jnp.int32)])
    nu = jnp.asarray(count, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bp // _CHUNK,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_CHUNK, d), lambda c, *_: (c, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((up, d), table.dtype),
            pltpu.SemaphoreType.DMA((ring_depth,)),
        ],
    )
    out = pl.pallas_call(
        _make_fused_kernel(up, ring_depth, _CHUNK),
        out_shape=jax.ShapeDtypeStruct((bp, d), table.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(uid_p, nu, inv_p, table)
    return out[:b]


def _make_fused_dequant_kernel(up: int, nbuf: int, chunk: int, mode: str):
    """Fused kernel with a dequantize epilogue in phase B.

    Phase A streams COMPRESSED unique rows into the VMEM buffer (a bf16
    buffer holds 2x, an int8 buffer 4x the frontier of a raw f32 one —
    the VMEM gate in :func:`fused_frontier_supported` already counts
    storage bytes); each phase-B copy widens to f32 through the shared
    decode formulas of :func:`glt_tpu.store.quant.dequantize` (see the
    quant module docstring for why affine is add-then-mul).
    """

    def kernel(uid_ref, nu_ref, inv_ref, table_ref, sz_ref, out_ref,
               buf, sems):
        c = pl.program_id(0)
        scale = sz_ref[0:1, :]
        zero = sz_ref[1:2, :]
        kvec = sz_ref[2:3, :]

        @pl.when(c == 0)
        def _():
            nu = nu_ref[0]

            def dma(j):
                return pltpu.make_async_copy(
                    table_ref.at[pl.ds(uid_ref[j], 1)],
                    buf.at[pl.ds(j, 1)],
                    sems.at[lax.rem(j, nbuf)])

            for k in range(nbuf):
                @pl.when(k < nu)
                def _():
                    dma(k).start()

            def fill(j, carry):
                @pl.when(j < nu)
                def _():
                    dma(j).wait()

                @pl.when(j + nbuf < nu)
                def _():
                    dma(j + nbuf).start()

                return carry

            lax.fori_loop(0, up, fill, None)

        def copy_row(s, carry):
            iv = inv_ref[c * chunk + s]
            row = buf[pl.ds(iv, 1), :].astype(jnp.float32)
            if mode == "affine":
                row = jnp.where(scale > 0.0, (row + kvec) * scale, zero)
            out_ref[pl.ds(s, 1), :] = row
            return carry

        lax.fori_loop(0, chunk, copy_row, None)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "mode",
                                             "ring_depth"))
def _fused_gather_dq(table, sz, uidx, count, inv, interpret=False,
                     mode="widen", ring_depth=_RING):
    """Dequantizing twin of :func:`_fused_gather`: compressed ``table``
    in, f32 rows out.  ``sz`` is the ``[_SZ_ROWS, d]`` f32
    scale/zero/k block."""
    b = inv.shape[0]
    d = table.shape[1]
    n = table.shape[0]
    up = -(-b // _SUBLANE) * _SUBLANE
    bp = -(-b // _CHUNK) * _CHUNK
    uid_p = jnp.concatenate(
        [jnp.clip(uidx.astype(jnp.int32), 0, n - 1),
         jnp.zeros((up - b,), jnp.int32)])
    inv_p = jnp.concatenate(
        [jnp.clip(inv.astype(jnp.int32), 0, up - 1),
         jnp.zeros((bp - b,), jnp.int32)])
    nu = jnp.asarray(count, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bp // _CHUNK,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((_SZ_ROWS, d), lambda c, *_: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_CHUNK, d), lambda c, *_: (c, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((up, d), table.dtype),
            pltpu.SemaphoreType.DMA((ring_depth,)),
        ],
    )
    out = pl.pallas_call(
        _make_fused_dequant_kernel(up, ring_depth, _CHUNK, mode),
        out_shape=jax.ShapeDtypeStruct((bp, d), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(uid_p, nu, inv_p, table, sz)
    return out[:b]


def fused_frontier(table: jnp.ndarray, ids: jnp.ndarray,
                   id2index: Optional[jnp.ndarray] = None,
                   force: str = "auto",
                   vmem_budget: Optional[int] = None,
                   dequant=None) -> FusedFrontier:
    """Dedup + gather a frontier in one dispatch.

    Bit-identical to running :func:`unique_first_occurrence` +
    :func:`~glt_tpu.ops.dedup_gather.dedup_gather_rows` separately, on
    both the fused and fallback paths.

    Args:
      table: ``[N, d]`` feature rows.
      ids: ``[B]`` frontier ids, -1 padded.
      id2index: optional hotness indirection applied to unique ids.
      force: 'auto' | 'pallas' | 'xla' | 'interpret' — the fused-kernel
        seam; ``GLT_FUSED_FORCE`` env overrides.  'interpret' runs the
        kernel in Pallas interpret mode (CPU tests); 'pallas'/'interpret'
        still fall back to XLA when the frontier exceeds the VMEM budget.
      vmem_budget: unique-block byte budget (default ~6 MB).
      dequant: optional :class:`~glt_tpu.store.quant.QuantSpec` for a
        compressed ``table``.  The fused kernel buffers compressed
        unique rows (2x/4x frontier capacity under the same VMEM gate)
        and widens to f32 in the phase-B epilogue; the fallback
        dequantizes post-gather with the identical formula, so both
        arms still agree bit-for-bit.  Padding rows are zeroed AFTER
        dequantization (``dequantize(0) != 0`` for int8).
    """
    env = os.environ.get("GLT_FUSED_FORCE")
    if env in ("pallas", "xla", "interpret"):
        force = env
    ids = ids.astype(jnp.int32)
    uniq, inv, cnt = unique_first_occurrence(ids)
    uvalid = uniq >= 0
    uidx = jnp.where(uvalid, uniq, 0)
    if id2index is not None:
        uidx = jnp.take(id2index, uidx, axis=0, mode="clip")
    compressed = dequant is not None and dequant.is_compressed
    # 'auto' engages the f32 kernel on a TPU.  The dequant kernel is out
    # of it: Mosaic refuses the one-row load from the packed bf16/int8
    # buffer at a dynamic sublane offset ("cannot statically prove that
    # index in dimension 0 is a multiple of 8", TPU v5e, PR 21), so a
    # compressed table takes the unfused arm unless 'pallas' is forced,
    # which raises that message.
    use = (force in ("pallas", "interpret")
           or (force == "auto" and jax.default_backend() == "tpu"
               and not compressed))
    if use and fused_frontier_supported(table, ids, vmem_budget):
        if compressed:
            mode = "affine" if dequant.codec == "int8" else "widen"
            sz = jnp.asarray(
                quant.scale_zero_rows(dequant, int(table.shape[1])))
            rows = _fused_gather_dq(table, sz, uidx, cnt, inv,
                                    interpret=(force == "interpret"),
                                    mode=mode)
        else:
            rows = _fused_gather(table, uidx, cnt, inv,
                                 interpret=(force == "interpret"))
        x = jnp.where((inv >= 0)[:, None], rows, 0)
    else:
        # Unfused fallback — dedup_gather_rows verbatim (two HBM passes,
        # same bits).  inv only references valid unique slots (< cnt),
        # so both paths read identical source rows.
        urows = gather_rows(table, uidx, dequant=dequant)
        urows = jnp.where(uvalid[:, None], urows, 0)
        rows = jnp.take(urows, jnp.clip(inv, 0, inv.shape[0] - 1), axis=0)
        x = jnp.where((inv >= 0)[:, None], rows, 0)
    return FusedFrontier(unique_ids=uniq, inverse=inv, features=x)
