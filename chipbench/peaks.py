"""Published peaks, and the bytes and operations a call needs.

The table is a copy of ``glt_tpu/obs/roofline.py``'s
(``DEVICE_HBM_GB_S``, ``DEVICE_BF16_TFLOPS``: keyed by ``device_kind``,
an unknown kind raises), cut to the kinds with a source.  The byte
function is a copy of ``glt_tpu/obs/attrib.py::gather_expected_bytes``
with the write counted too.  The program's
copies stay as they are; a later PR may delete them.
"""
from __future__ import annotations

#: device_kind substring (lower case, spaces stripped) ->
#: (HBM GB/s, bf16 TFLOP/s).  Source: Google Cloud documentation,
#: "TPU v5e": 16 GB HBM2e at 819 GB/s, 197 TFLOP/s bf16 per chip.  The
#: v5e reports itself as "TPU v5 lite".
PEAKS = (
    ("v5e", (819.0, 197.0)),
    ("v5lite", (819.0, 197.0)),
)


def peaks_of(device_kind: str) -> dict:
    canon = str(device_kind).lower().replace(" ", "")
    for sub, (hbm, flops) in PEAKS:
        if sub in canon:
            return {"hbm_gb_s": hbm, "bf16_tflops": flops}
    raise LookupError(
        f"no published peak on record for device_kind {device_kind!r}: "
        f"add it to chipbench/peaks.py with its source")


def gather_bytes(rows: int, dim: int, itemsize: int = 4) -> int:
    """HBM bytes a row gather has to move: each row read once and
    written once."""
    return 2 * int(rows) * int(dim) * int(itemsize)
