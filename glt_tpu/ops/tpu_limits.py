"""TPU device limits: the single source of truth for kernel sizing.

Every number here is a hardware (or hardware-adjacent) constant that
both the Pallas kernels (``ops/``) and the static device-program
verifier (``analysis/kernelmodel.py``) reason about.  Keeping them in
one importable module means the kernels and the analyzer can never
disagree: the analyzer resolves these names through its symbol table,
so editing a value here re-checks every kernel against the new limit
on the next lint run.

Sources: pallas_guide.md "Tiling Constraints" / "Memory Spaces"
(VMEM ~16 MB/core; min tile (sublane, lane) per dtype: float32 (8,128),
bfloat16 (16,128), int8/fp8 (32,128)) and the DMA-depth calibration of
gather_pallas.py round 5 (~16 KB block DMAs are where a v5-class DMA
engine streams instead of paying setup per transfer).

Stdlib-only on purpose: the analyzer's CI job runs without the JAX
stack, and nothing below needs an array library.
"""
from __future__ import annotations

# Per-core VMEM.  The hard ceiling the closed-form VMEM model
# (GLT017 vmem-budget-exceeded) checks every candidate kernel
# parameter point against.
VMEM_BYTES = 16 * 2**20

# Scalar memory: where PrefetchScalarGridSpec operands land.  The v5e
# compiler reports the capacity itself when a kernel overruns it ("Used
# 2.13M of 1.00M smem", gather_rows_pallas at 139,264 ids).
SMEM_BYTES = 1 * 2**20

# Last-dimension register width: every VMEM tile is LANE lanes wide,
# and narrower last dims are padded up to it.
LANE = 128

# Minimum second-to-last (sublane) tile dim by dtype width: 4-byte
# types tile (8, 128), 2-byte (16, 128), 1-byte (32, 128).
SUBLANE_F32 = 8
SUBLANE_BF16 = 16
SUBLANE_INT8 = 32

# Block-DMA byte depth the width-specialized gather defaults aim for:
# deep enough to stream, small enough to keep ring slots cheap.
DMA_DEPTH_TARGET_BYTES = 1 << 14

# Widest feature row (in lanes) the static VMEM model assumes for
# runtime-sized last dims (a table's `d` is only known at trace time;
# the model bounds it here so the closed-form accounting stays total).
MODEL_MAX_LANES = 2048


def sublane_min(itemsize: int) -> int:
    """Smallest legal sublane tile dim for an ``itemsize``-byte dtype
    (f32 8, bf16 16, int8/fp8 32 — pallas_guide.md)."""
    return max(SUBLANE_F32, 32 // max(int(itemsize), 1))


def compiler_message(exc: BaseException, limit: int = 400) -> str:
    """One line naming what a compiler refused: ``Type: message`` with
    whitespace collapsed and the tail (Mosaic appends the kernel's
    serialized body) cut at ``limit``.  The form the autotune tables and
    ``chip_smoke.py`` record a refused kernel in."""
    text = " ".join(f"{type(exc).__name__}: {exc}".split())
    text = text.split(" ... additional diagnostics were skipped")[0]
    return text if len(text) <= limit else text[:limit] + "..."
