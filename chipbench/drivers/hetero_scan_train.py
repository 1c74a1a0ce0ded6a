"""Closed loop through the IGBH example's default driver:
``run_scanned_epoch`` over ``make_scanned_hetero_train_step``
(examples/rgat_igbh.py, ``--group``): typed sampling, per-type gather,
R-GAT forward, backward and update of ``group`` batches in one program."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks
from chipbench import data_hetero
from chipbench import reference_hetero
from chipbench.common import Window, seed_stream, span, timed
# What this cell needs of the program, named before anything is generated:
# a checkout without the typed capacities or upstream's RGNN stops here.
from glt_tpu.models import (init_hetero_state,
                            make_scanned_hetero_train_step,
                            run_scanned_epoch)
from glt_tpu.models.rgat import RGNN  # noqa: F401
from glt_tpu.models.train import hetero_gather_xy
from glt_tpu.sampler import NodeSamplerInput
from glt_tpu.sampler.hetero_neighbor_sampler import (
    HeteroNeighborSampler)


class Driver:
    def __init__(self, env):
        import optax

        from glt_tpu.obs import metrics as registry

        self.env = env
        cfg, sam = env.config, env.config["sampling"]
        self.batch, self.fanout = int(sam["batch_size"]), list(sam["fanout"])
        self.group = int(env.traffic["group"])
        if env.trace:
            registry.enable()       # the step's gauges are set when built
        with timed(env.log, "generate + place"):
            self.d = data_hetero.build_hetero_one_chip(cfg, env.seed, env.log)
        self.model = data_hetero.make_model(cfg)
        tx = optax.adam(1e-3)
        self.sampler = HeteroNeighborSampler(
            self.d.graphs, self.fanout, self.d.seed_type,
            batch_size=self.batch, frontier_cap=sam["frontier_cap"],
            node_capacity=sam["node_capacity"],
            frontier_capacity=sam["frontier_capacity"])
        self.state = init_hetero_state(self.model, tx, self.sampler,
                                       self.d.feats,
                                       jax.random.PRNGKey(env.seed))
        sstep = make_scanned_hetero_train_step(
            self.model, tx, self.sampler, self.d.feats,
            {self.d.seed_type: self.d.labels}, self.batch, seed_hops=True)

        @functools.wraps(sstep)
        def step(state, blk, key):
            with span("scan_group_dispatch"):
                return sstep(state, blk, key)

        self.step = step
        self.rng = np.random.default_rng([env.seed, 11])
        # One shuffled pass over the training papers at a time, cut into
        # calls: permuting 600,000 ids anew for every call of 256 seeds
        # (what ``seed_stream`` does) is 20 ms of host time a call, during
        # which the device waits.
        self._pass, self._at = np.empty((0,), np.int64), 0
        self.key = jax.random.PRNGKey(env.seed + 100)
        self.per_call = int(env.traffic["groups_per_call"])
        # Warm-up at the window's own shapes: one call compiles (or reads
        # the cache) and runs every program the window will.
        with timed(env.log, "first call (compile or cache)"):
            self._groups(self.per_call)

    def _groups(self, n: int):
        """``n`` groups through the program's epoch driver; returns when
        its own loss fetch has waited for the last."""
        count = n * self.group * self.batch
        if self._at + count > self._pass.shape[0]:
            self._pass = seed_stream(
                self.d.train_idx, max(self.d.train_idx.shape[0], count),
                self.rng)
            self._at = 0
        seeds = self._pass[self._at: self._at + count]
        self._at += count
        self.key, sub = jax.random.split(self.key)
        with span("scanned_epoch"):
            self.state, losses, accs, ovf = run_scanned_epoch(
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
                      counters={"node_rows": sum(
                          self.sampler.node_capacity.values()),
                                "window_s": dt})

    def check(self) -> dict:
        cfg = self.env.config
        checks.check(bool(np.isfinite(self.losses).all()),
                     f"non-finite training loss {self.losses}")
        ref, tgt = self.d.ref, self.d.seed_type
        edges = ref.check_transposes(
            {et: (g.topo.indptr, g.topo.indices)
             for et, g in self.d.graphs.items()})
        rng = np.random.default_rng([self.env.seed, 13])
        for attempt in range(4):
            seeds = seed_stream(self.d.train_idx, self.batch, rng)
            out = self.sampler.sample_from_nodes(
                NodeSamplerInput(seeds.astype(np.int32)))
            if not bool(np.asarray((out.metadata or {}).get(
                    "overflow", False))):
                break
        else:
            raise checks.CheckFailure(
                "four batches in a row overflowed node_capacity "
                f"{self.sampler.node_capacity}")
        rows = {t: f.hot_rows for t, f in self.d.feats.items()}
        x, y = jax.jit(functools.partial(
            hetero_gather_xy, batch_size=self.batch))(
            rows, jnp.asarray(self.d.labels), out)
        reference_hetero.check_hetero_batch(
            ref, {"node": out.node, "node_mask": out.node_mask, "x": x,
                  "y": y, "seeds": out.batch[tgt], "row": out.row,
                  "col": out.col, "edge_mask": out.edge_mask},
            tgt, self.batch, self.fanout, "hetero-train-scan batch", rng)
        # The step's own forward: the model with the sampler's layout.
        ei = {et: jnp.stack([out.row[et], out.col[et]]) for et in out.row}
        got = jax.jit(lambda p, x, ei, em: self.model.apply(
            p, x, ei, em, train=False, hops=self.sampler.hop_bounds)[
            : self.batch])(self.state.params, x, ei, out.edge_mask)
        got = np.asarray(got)
        live = {}
        for et in out.row:
            em = np.asarray(out.edge_mask[et])
            live[et] = (np.asarray(out.row[et])[em],
                        np.asarray(out.col[et])[em])
        want = reference_hetero.rgnn_seed_logits(
            reference_hetero.layer_weights(
                self.state.params, self.model.edge_types,
                self.model.num_layers), x, live, tgt, self.batch)
        err = checks.check_logits(got, want, cfg["check"]["logits_rtol"],
                                  "hetero-train-scan logits")
        return {"logits_err": err, "transposed_edges": edges}

    def close(self) -> None:
        pass
