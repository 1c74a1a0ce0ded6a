"""Counter-based draws shared by the generator and the plain reference.

A draw is ``mix32(counter ^ key)`` on 32-bit unsigned integers, with
``key`` made from ``(--seed, stream)``.  The same few integer operations
run under ``jax.numpy`` on the device and under ``numpy`` on the host and
give the same bits, so the reference recomputes any value the generator
made from its position alone.
"""
from __future__ import annotations

import numpy as np

DEGREE, NEIGHBOUR, FEATURE, LABEL = 1, 2, 3, 4

_M1, _M2 = 0x7FEB352D, 0x846CA68B
_MASK = 0xFFFFFFFF


def mix32(x):
    """The ``lowbias32`` integer finaliser on a uint32 array (numpy or
    jax.numpy): every input bit reaches every output bit."""
    x = x ^ (x >> 16)
    x = x * np.uint32(_M1)
    x = x ^ (x >> 15)
    x = x * np.uint32(_M2)
    return x ^ (x >> 16)


def _mix32_int(x: int) -> int:
    x &= _MASK
    x ^= x >> 16
    x = (x * _M1) & _MASK
    x ^= x >> 15
    x = (x * _M2) & _MASK
    return x ^ (x >> 16)


def stream_key(seed: int, stream: int) -> int:
    """The 32-bit key of one stream of one seed (a Python int)."""
    return _mix32_int(int(seed) * 0x9E3779B1 + int(stream) * 0x85EBCA77
                      + 0x165667B1)


def unit_open(h, xp):
    """``h`` (uint32) to float32 strictly inside (0, 1): 23 bits + 1/2."""
    return ((h >> 9).astype(xp.float32) + xp.float32(0.5)) \
        * xp.float32(2.0 ** -23)


def unit_signed(h, xp):
    """``h`` (uint32) to float32 in [-1, 1), exactly representable, so
    the device and the host agree bit for bit."""
    return (h >> 8).astype(xp.float32) * xp.float32(2.0 ** -23) \
        - xp.float32(1.0)
