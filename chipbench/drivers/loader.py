"""Closed loop over ``NeighborLoader(shuffle=True)``: upstream's own
loader benchmark, and the path of a user who brings a model.  Every
batch is ended by ``block_until_ready`` on its features, labels and
edges; no model runs."""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import checks
from chipbench import data
from chipbench.common import Window, span, timed


class Driver:
    def __init__(self, env):
        from glt_tpu.loader import NeighborLoader

        self.env = env
        cfg, sam = env.config, env.config["sampling"]
        self.batch, self.fanout = int(sam["batch_size"]), list(sam["fanout"])
        with timed(env.log, "generate + place"):
            self.d = data.build_one_chip(cfg, env.seed, env.devices[0],
                                         env.log)
        with timed(env.log, "loader construction (its warm-up sweeps)"):
            self.loader = NeighborLoader(
                self.d.dataset, self.fanout, self.d.train_idx,
                batch_size=self.batch, shuffle=True,
                frontier_cap=sam["frontier_cap"],
                node_capacity=sam["node_capacity"], seed=env.seed)
        with timed(env.log, "first batches (compile or cache)"):
            self._warm(self.loader, 3)
            sib = self.loader.sampler.full_capacity_sibling()
            if sib is not self.loader.sampler:
                # The loader replays an overflowing batch through this
                # twin at full capacity: its shapes are this cell's too.
                self._warm(NeighborLoader(
                    self.d.dataset, self.fanout,
                    self.d.train_idx[: self.batch], batch_size=self.batch,
                    sampler=sib), 1)

    @staticmethod
    def _ready(batch):
        jax.block_until_ready((batch.x, batch.y, batch.edge_index,
                               batch.edge_mask, batch.node))

    def _warm(self, loader, n: int) -> None:
        for i, b in enumerate(loader):
            self._ready(b)
            if i + 1 >= n:
                break

    def window(self, seconds: float) -> Window:
        overflow0 = self.loader.overflow_batches
        done = seeds = 0
        t0 = time.perf_counter()
        t_last = t0
        while t_last - t0 < seconds:
            it = iter(self.loader)
            while True:
                with span("loader_next"):
                    b = next(it, None)
                if b is None:
                    break
                with span("batch_wait"):
                    self._ready(b)
                t_last = time.perf_counter()
                done += 1
                seeds += int(b.batch_size)
                if t_last - t0 >= seconds:
                    it.close()
                    break
        dt = t_last - t0
        return Window(
            attempted=done, failed=0,
            metrics={"seeds_per_s": seeds / dt}, steps=done,
            counters={"node_rows": self.loader.sampler.node_capacity,
                      "overflow_replayed":
                          self.loader.overflow_batches - overflow0,
                      "window_s": dt})

    def check(self) -> dict:
        rng = np.random.default_rng([self.env.seed, 13])
        b = next(iter(self.loader))
        checks.check_batch(
            self.d.ref, {"node": b.node, "node_mask": b.node_mask,
                         "seeds": b.batch, "x": b.x, "y": b.y,
                         "edge_index": b.edge_index,
                         "edge_mask": b.edge_mask},
            self.batch, self.fanout, "loader batch", rng)
        return {}

    def close(self) -> None:
        pass
