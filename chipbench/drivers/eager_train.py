"""Closed loop over ``NeighborLoader`` into ``make_train_step``: the path
of upstream's single-device examples (``examples/train_sage_ogbn_*.py``:
a loader's batches, one optimiser step each) and the only one on which a
feature table larger than the chip can train, its cold tail gathered on
the host inside the loader's collate.  One train step is in flight: step
``k`` is dispatched, then step ``k-1``'s loss is waited for."""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import checks
from chipbench import data
from chipbench import data_tiered
from chipbench import reference
from chipbench import reference_tiered
from chipbench.common import Window, span, timed
from chipbench.drivers.link_scan_train import four_bits
# What this cell needs of the program, named before anything is
# generated: a checkout without the order-only hotness sort (and so
# without the constructor from tiers and the eager step's layout, which
# came with it) stops here.
from glt_tpu.data import in_degree_order  # noqa: F401
from glt_tpu.loader import NeighborLoader
from glt_tpu.models import init_train_state, make_train_step


class Driver:
    def __init__(self, env):
        import optax

        from glt_tpu.obs import metrics as registry

        self.env, self.registry = env, registry
        cfg, sam = env.config, env.config["sampling"]
        self.batch, self.fanout = int(sam["batch_size"]), list(sam["fanout"])
        if env.trace:
            registry.enable()       # the tiers' gauge is set when built
        with timed(env.log, "generate + place"):
            self.d = data_tiered.build_tiered(cfg, env.seed, env.devices[0],
                                              env.log)
        self.feat = self.d.dataset.get_node_feature()
        self.feat.set_cold_width(cfg["tiering"]["cold_width"])
        with timed(env.log, "loader construction (its warm-up sweeps)"):
            self.loader = NeighborLoader(
                self.d.dataset, self.fanout, self.d.train_idx,
                batch_size=self.batch, shuffle=True,
                prefetch=int(env.traffic["prefetch"]),
                frontier_cap=sam["frontier_cap"],
                node_capacity=sam["node_capacity"], seed=env.seed)
        sampler = self.loader.sampler
        sib = sampler.full_capacity_sibling()
        self.layouts = tuple(dict.fromkeys((sampler.hop_bounds,
                                            sib.hop_bounds)))
        self.model = data.make_model(cfg)
        tx = optax.adam(1e-3)
        self.state = init_train_state(
            self.model, tx, self.feat.shape[1], jax.random.PRNGKey(env.seed))
        # (the dropout seed is a constant of the compiled step: one for
        # every --seed, or every run would compile its own)
        self.step = make_train_step(self.model, tx, self.batch,
                                    hops=self.layouts)
        with timed(env.log, "first batches (compile or cache)"):
            self._warm(self.loader, 3)
            self.feat.warm_gather(sampler.node_capacity)
            if sib is not sampler:
                # The loader replays an overflowing batch through this
                # twin at full capacity: another node width, so another
                # plan, merge and train program, all this cell's too.
                self._warm(NeighborLoader(
                    self.d.dataset, self.fanout,
                    self.d.train_idx[: self.batch], batch_size=self.batch,
                    sampler=sib), 1)
                self.feat.warm_gather(sib.node_capacity)
        self.losses = np.zeros((0,), np.float32)

    def _warm(self, loader, n: int) -> None:
        """Train ``n`` batches of the loader's own node width (a replayed
        batch has another, and is no warm-up of this one; the first step
        also differs from the rest: its state is not yet a step's
        result)."""
        mine = 0
        for i, b in enumerate(loader):
            self.state, loss, _ = self.step(self.state, b)
            jax.block_until_ready(loss)
            mine += b.node.shape[0] == loader.sampler.node_capacity
            if mine >= n or i + 1 >= 8 * n:
                break

    def window(self, seconds: float) -> Window:
        overflow0 = self.loader.overflow_batches
        snap0 = self.registry.snapshot()
        done = seeds = 0
        losses, prev = [], None
        t0 = time.perf_counter()
        t_last = t0

        def finish(prev):
            nonlocal done, seeds, t_last
            with span("loss_wait"):
                losses.append(float(prev[0]))
            t_last = time.perf_counter()
            done, seeds = done + 1, seeds + prev[1]

        while t_last - t0 < seconds:
            it = iter(self.loader)
            while True:
                with span("loader_next"):
                    b = next(it, None)
                if b is None:
                    break
                with span("train_dispatch"):
                    self.state, loss, _ = self.step(self.state, b)
                if prev is not None:
                    finish(prev)
                prev = (loss, int(b.batch_size))
                if t_last - t0 >= seconds:
                    it.close()
                    break
        if prev is not None:
            finish(prev)
        dt = t_last - t0
        self.losses = np.asarray(losses, np.float32)
        counters = {"node_rows": self.loader.sampler.node_capacity,
                    "overflow_replayed":
                        self.loader.overflow_batches - overflow0,
                    "window_s": dt}
        if self.registry.enabled():
            snap1 = self.registry.snapshot()
            hot, cold, sent = (
                snap1.get(k, 0) - snap0.get(k, 0) for k in (
                    "glt.feature.hot_rows", "glt.feature.cold_rows",
                    "glt.feature.cold_rows_sent"))
            if hot + cold:
                counters.update(
                    cold_row_share=100.0 * cold / (hot + cold),
                    hot_rows_per_step=hot / max(done, 1),
                    cold_rows_per_step=cold / max(done, 1),
                    cold_rows_sent_per_step=sent / max(done, 1))
        return Window(
            attempted=done, failed=int((~np.isfinite(self.losses)).sum()),
            metrics={"seeds_per_s": seeds / dt}, steps=done,
            counters=counters)

    def check(self) -> dict:
        cfg = self.env.config
        checks.check(bool(np.isfinite(self.losses).all()),
                     f"non-finite training loss {self.losses}")
        rng = np.random.default_rng([self.env.seed, 13])
        b = next(iter(self.loader))
        checks.check_batch(
            self.d.ref, {"node": b.node, "node_mask": b.node_mask,
                         "seeds": b.batch, "x": b.x, "y": b.y,
                         "edge_index": b.edge_index,
                         "edge_mask": b.edge_mask},
            self.batch, self.fanout, "train-eager batch", rng)
        detail = reference_tiered.check_tiers(
            self.d.indices, self.d.shapes.num_nodes, self.d.hot_count,
            self.feat.id2index, self.feat.hot_rows.shape[0], b.node,
            "train-eager tiers")
        # The step's own forward: the model under the batch's layout,
        # evaluation mode, the trained parameters.
        layout = next(h for h in self.layouts
                      if h.node_bounds[-1] == b.node.shape[0])
        forward = jax.jit(lambda p, x: self.model.apply(
            p, x, b.edge_index, b.edge_mask, train=False,
            hops=layout)[: self.batch])
        got = forward(self.state.params, b.x)
        weights = reference.layer_weights(self.state.params,
                                          len(self.fanout))
        want = jax.jit(lambda w, x, ei, em: reference.sage_forward(
            w, x, ei[0], ei[1], em)[: self.batch])(
            weights, b.x, b.edge_index, b.edge_mask)
        detail["logits_err"] = checks.check_logits(
            got, want, cfg["check"]["logits_rtol"], "train-eager logits")
        # The other reading the tolerance was set between: parameters and
        # rows rounded to four mantissa bits, which must not pass.
        detail["logits_err_4bit"] = checks.check_logits(
            forward(jax.tree.map(four_bits, self.state.params),
                    four_bits(b.x)), want, np.inf, "four mantissa bits")
        return detail

    def close(self) -> None:
        self.feat.close()
