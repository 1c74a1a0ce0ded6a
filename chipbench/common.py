"""What every driver shares: the run's environment and small helpers."""
from __future__ import annotations

import contextlib
import time
from typing import NamedTuple

import numpy as np


class Env(NamedTuple):
    """One run: a cell's files, the seed, the chips, traced or not."""
    config: dict
    traffic: dict
    seed: int
    devices: list
    trace: bool
    log: object             # callable(str): progress lines on stderr


class Window(NamedTuple):
    """What a driver's measured window returns."""
    attempted: int          # batches (closed loop) or requests (open loop)
    failed: int
    metrics: dict           # end-to-end values by name, as measured
    steps: int              # finished batches, the "/step" of the readers
    counters: dict          # whatever the readers may want, by name


def span(name: str):
    """A host span in the profiler's own trace, on the device's clock."""
    import jax

    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def seed_stream(train_idx: np.ndarray, count: int, rng) -> np.ndarray:
    """``count`` training seeds: shuffled passes over ``train_idx``, one
    after another, as epochs follow each other."""
    out = []
    have = 0
    while have < count:
        perm = train_idx[rng.permutation(train_idx.shape[0])]
        out.append(perm[: count - have])
        have += out[-1].shape[0]
    return np.concatenate(out)


@contextlib.contextmanager
def timed(log, what: str):
    t0 = time.perf_counter()
    yield
    log(f"{what}: {time.perf_counter() - t0:.2f} s")
