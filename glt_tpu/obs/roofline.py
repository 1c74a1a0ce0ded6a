"""Gather roofline: a measured device-memcpy bandwidth ceiling.

``gather_gb_s`` is expressed as a fraction of what the chip can
actually stream, beside the fraction of the datasheet HBM number
(``est_hbm_fraction`` divides by the device kind's published peak).
PyTorch-Direct and GIDS (PAPERS.md) both anchor their irregular-gather
claims the same way: achieved vs a *measured* sequential-copy peak.

Methodology (docs/observability.md "Roofline"):

  * the probe is ``x -> x + 1.0`` over a contiguous f32 buffer under
    jit: one HBM read + one HBM write per pass = ``2 * nbytes`` traffic,
    the same in/out streaming a memcpy pays, with no gather indirection;
  * passes chain (``x = step(x)``) so one host fetch at the end syncs
    the whole timed region;
  * ``memcpy_gb_s = 2 * nbytes * iters / elapsed``; a gather variant's
    ``roofline_fraction(gather_gb_s, memcpy_gb_s)`` is then the number
    ROADMAP item 1 names as its success metric (within ~2x of 1.0).
"""
from __future__ import annotations

import os
import time
from typing import Dict

#: Datasheet HBM bandwidth (GB/s) by device-kind substring, most
#: specific first (matched against a lowercased, space-stripped
#: ``device_kind``; the v5e reports "TPU v5 lite").  ``GLT_HBM_GBPS``
#: overrides it for hardware the table doesn't know; a kind that is in
#: neither is an error, never a default.
DEVICE_HBM_GB_S = (
    ("v6e", 1640.0),
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5lite", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)

#: Published bf16 matmul peak (TFLOP/s), same keying.  Only the kinds a
#: record in this repo has a source for: v5e, Google Cloud documentation
#: "TPU v5e" (197 TFLOP/s bf16).
DEVICE_BF16_TFLOPS = (
    ("v5e", 197.0),
    ("v5lite", 197.0),
)


def _device_peak(table, kind: str, what: str) -> float:
    canon = str(kind).lower().replace(" ", "")
    for sub, value in table:
        if sub in canon:
            return value
    raise LookupError(
        f"no {what} on record for device_kind {kind!r}; add its published "
        f"peak to glt_tpu/obs/roofline.py with the source")


def peak_bf16_tflops(kind: str) -> float:
    """Published bf16 peak of ``device_kind``; unknown kinds raise."""
    return _device_peak(DEVICE_BF16_TFLOPS, kind, "bf16 peak")


def measure_memcpy_roofline(nbytes: int = 1 << 27, iters: int = 10,
                            warmup: int = 2) -> Dict[str, float]:
    """Measure the streaming-copy bandwidth of the default device.

    Returns ``{"memcpy_gb_s", "bytes", "iters", "elapsed_s"}``.  The
    default 128 MiB buffer is large enough to defeat on-chip caching on
    any current TPU; shrink ``nbytes`` for CPU smoke runs.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = max(int(nbytes) // 4, 1024)
    x = jnp.zeros((n,), jnp.float32)
    step = jax.jit(lambda a: a + 1.0)
    for _ in range(max(warmup, 1)):
        x = step(x)
    float(np.asarray(jax.device_get(x[0])))   # compile + true sync
    t0 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
    float(np.asarray(jax.device_get(x[0])))   # host fetch = true sync
    elapsed = time.perf_counter() - t0
    moved_gb = 2.0 * n * 4 * iters / 1e9
    return {
        "memcpy_gb_s": moved_gb / max(elapsed, 1e-9),
        "bytes": float(n * 4),
        "iters": float(iters),
        "elapsed_s": elapsed,
    }


def roofline_fraction(achieved_gb_s: float, roofline_gb_s: float) -> float:
    """Achieved bandwidth as a fraction of the measured roofline."""
    return float(achieved_gb_s) / max(float(roofline_gb_s), 1e-9)


def peak_hbm_gb_s() -> Dict[str, object]:
    """Resolve the peak HBM bandwidth WITH its provenance.

    Returns ``{"gb_s": float, "source": str}`` where source is ``env``
    (a numeric ``GLT_HBM_GBPS`` override) or ``device_kind:<kind>`` (the
    datasheet table).  A device kind in neither raises ``LookupError``:
    a fraction against a guessed ceiling is worse than no fraction.
    """
    env = os.environ.get("GLT_HBM_GBPS")
    if env:
        return {"gb_s": float(env), "source": "env"}
    import jax

    kind = jax.devices()[0].device_kind
    return {"gb_s": _device_peak(DEVICE_HBM_GB_S, kind, "HBM peak"),
            "source": f"device_kind:{kind}"}
