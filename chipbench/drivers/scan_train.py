"""Closed loop through the example's default driver: ``run_scanned_epoch``
over ``make_scanned_node_train_step`` (examples/train_sage_products.py,
``--group 8``): sample, gather, forward, backward and update of ``group``
batches in one program."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks
from chipbench import data
from chipbench import reference
from chipbench.common import Window, seed_stream, span, timed


class Driver:
    def __init__(self, env):
        import optax

        from glt_tpu.models import (TrainState, make_scanned_node_train_step,
                                    run_scanned_epoch)
        from glt_tpu.sampler import NeighborSampler

        self.env = env
        cfg, sam = env.config, env.config["sampling"]
        self.batch, self.fanout = int(sam["batch_size"]), list(sam["fanout"])
        self.group = int(env.traffic["group"])
        with timed(env.log, "generate + place"):
            self.d = data.build_one_chip(cfg, env.seed, env.devices[0],
                                         env.log)
        ds = self.d.dataset
        self.model = data.make_model(cfg)
        tx = optax.adam(1e-3)
        self.sampler = NeighborSampler(
            ds.get_graph(), self.fanout, batch_size=self.batch,
            frontier_cap=sam["frontier_cap"], with_edge=False,
            node_capacity=sam["node_capacity"])
        self.feat = ds.get_node_feature()
        self.labels = np.asarray(ds.get_node_label())
        x0 = jnp.zeros((self.sampler.node_capacity, self.feat.shape[1]),
                       self.feat.dtype)
        ei0 = jnp.full((2, self.sampler.edge_capacity), -1, jnp.int32)
        m0 = jnp.zeros((self.sampler.edge_capacity,), bool)
        params = self.model.init({"params": jax.random.PRNGKey(env.seed)},
                                 x0, ei0, m0)
        self.state = TrainState(params=params, opt_state=tx.init(params),
                                step=jnp.zeros((), jnp.int32))
        sstep = make_scanned_node_train_step(
            self.model, tx, self.sampler, self.feat, self.labels, self.batch)

        def step(state, blk, key):
            with span("scan_group_dispatch"):
                return sstep(state, blk, key)

        self.step = step
        self.run_epoch = run_scanned_epoch
        self.rng = np.random.default_rng([env.seed, 11])
        self.key = jax.random.PRNGKey(env.seed + 100)
        self.per_call = int(env.traffic["groups_per_call"])
        # Warm-up at the window's own shapes: one call compiles (or reads
        # the cache) and runs every program the window will.
        with timed(env.log, "first call (compile or cache)"):
            self._groups(self.per_call)

    def _groups(self, n: int):
        """``n`` groups through the program's epoch driver; returns when
        its own loss fetch has waited for the last."""
        seeds = seed_stream(self.d.train_idx, n * self.group * self.batch,
                            self.rng)
        self.key, sub = jax.random.split(self.key)
        with span("scanned_epoch"):
            self.state, losses, accs, ovf = self.run_epoch(
                self.step, self.state, seeds, self.batch, self.group,
                self.rng, sub)
        return seeds.shape[0], losses, ovf

    def window(self, seconds: float) -> Window:
        calls = seeds = ovf = 0
        losses = []
        t0 = time.perf_counter()
        t_last = t0
        while t_last - t0 < seconds:
            n, ls, o = self._groups(self.per_call)
            t_last = time.perf_counter()
            calls, seeds, ovf = calls + 1, seeds + n, ovf + o
            losses.append(ls)
        dt = t_last - t0
        self.losses = np.concatenate(losses)
        steps = calls * self.per_call * self.group
        return Window(attempted=steps, failed=int(ovf),
                      metrics={"seeds_per_s": seeds / dt}, steps=steps,
                      counters={"node_rows": self.sampler.node_capacity,
                                "window_s": dt})

    def check(self) -> dict:
        from glt_tpu.models.train import make_gather_xy
        from glt_tpu.sampler import NodeSamplerInput

        cfg = self.env.config
        checks.check(bool(np.isfinite(self.losses).all()),
                     f"non-finite training loss {self.losses}")
        rng = np.random.default_rng([self.env.seed, 13])
        gather_xy = jax.jit(make_gather_xy(self.feat.id2index))
        labels_dev = jnp.asarray(self.labels)
        for attempt in range(4):
            seeds = seed_stream(self.d.train_idx, self.batch, rng)
            out = self.sampler.sample_from_nodes(
                NodeSamplerInput(seeds.astype(np.int32)))
            meta = out.metadata or {}
            if not bool(np.asarray(meta.get("overflow", False))):
                break
        else:
            raise checks.CheckFailure(
                "four batches in a row overflowed node_capacity "
                f"{self.sampler.node_capacity}")
        x, y = gather_xy(self.feat.hot_rows, labels_dev, out)
        ei = jnp.stack([out.row, out.col])
        checks.check_batch(
            self.d.ref, {"node": out.node, "node_mask": out.node_mask,
                         "seeds": out.batch, "x": x, "y": y,
                         "edge_index": ei, "edge_mask": out.edge_mask},
            self.batch, self.fanout, "train-scan batch", rng)
        got = jax.jit(lambda p, x, ei, em: self.model.apply(
            p, x, ei, em, train=False)[: self.batch])(
            self.state.params, x, ei, out.edge_mask)
        weights = reference.layer_weights(self.state.params,
                                          len(self.fanout))
        want = jax.jit(lambda w, x, ei, em: reference.sage_forward(
            w, x, ei[0], ei[1], em)[: self.batch])(
            weights, x, ei, out.edge_mask)
        err = checks.check_logits(got, want, cfg["check"]["logits_rtol"],
                                  "train-scan logits")
        return {"logits_err": err}

    def close(self) -> None:
        pass
