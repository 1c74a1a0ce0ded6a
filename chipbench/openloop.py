"""Open-loop load generator.

Started as a copy of ``benchmarks/bench_serving.py::open_loop`` (arrival
times drawn up front, each worker takes the next scheduled arrival,
sleeps until it is due and fires; a late worker fires at once, which is
how an overloaded open system behaves).  Changed here: the requests are
drawn up front too, from the run's seed; a request's latency runs from
the time it was DUE, not from the time it left, so a stall is charged
to every request it delays; and how late each request left is recorded,
so a starved generator is not read as a fast server.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List, NamedTuple

import numpy as np


class Outcome(NamedTuple):
    due_s: float        # scheduled send time, from the window's start
    late_s: float       # how long after that it left
    latency_s: float    # due -> reply (nan when it failed)
    kind: str           # "ok" or the failure's class name


def poisson_arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Arrival times of a Poisson process of ``rate`` inside
    ``[0, seconds)``."""
    n = max(16, int(rate * seconds * 1.5) + 16)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < seconds:
        t = np.concatenate(
            [t, t[-1] + np.cumsum(rng.exponential(1.0 / rate, size=n))])
    return t[t < seconds]


def run(send: Callable[[int, object], None], requests: List[object],
        arrivals: np.ndarray, threads: int, join_s: float
        ) -> List[Outcome]:
    """Fire ``requests[i]`` at ``arrivals[i]`` through ``send(worker,
    request)``, which raises on failure.  Returns one outcome each."""
    count = len(requests)
    out: List[Outcome] = [None] * count
    lock = threading.Lock()
    next_i = [0]
    t_start = time.perf_counter()

    def worker(idx: int) -> None:
        while True:
            with lock:
                i = next_i[0]
                if i >= count:
                    return
                next_i[0] += 1
            due = float(arrivals[i])
            delay = due - (time.perf_counter() - t_start)
            if delay > 0:
                time.sleep(delay)
            sent = time.perf_counter() - t_start
            try:
                send(idx, requests[i])
                kind, lat = "ok", time.perf_counter() - t_start - due
            except Exception as e:  # noqa: BLE001 — recorded per request
                kind, lat = type(e).__name__, float("nan")
            out[i] = Outcome(due, sent - due, lat, kind)

    pool = [threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(threads)]
    for t in pool:
        t.start()
    deadline = time.perf_counter() + float(arrivals[-1] if count else 0) \
        + join_s
    for t in pool:
        t.join(timeout=max(0.0, deadline - time.perf_counter()))
    if any(t.is_alive() for t in pool):
        raise RuntimeError("load generator threads did not finish")
    return out
