"""Closed loop over ``make_dist_train_step`` on a mesh of every chip the
cell asks for: ``ShardedGraph`` / ``ShardedFeature`` wholly in HBM, one
seed batch per chip per step drawn from that shard's own training seeds
(as ``chip_smoke.stage_dist`` and examples/dist_train_sage.py do), every
option of the step at its default."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks
from chipbench import data
from chipbench import reference
from chipbench.common import Window, span, timed


class Driver:
    def __init__(self, env):
        import optax

        from glt_tpu.parallel import init_dist_state, make_dist_train_step

        self.env = env
        cfg, sam = env.config, env.config["sampling"]
        self.batch, self.fanout = int(sam["batch_size"]), list(sam["fanout"])
        with timed(env.log, "generate on the mesh"):
            self.d = data.build_sharded(cfg, env.seed, env.devices)
        d = self.d
        self.model = data.make_model(cfg)
        tx = optax.adam(1e-3)
        self.state = init_dist_state(
            self.model, tx, d.graph, d.feature,
            jax.random.PRNGKey(env.seed), self.fanout, self.batch,
            frontier_cap=sam["frontier_cap"])
        self.step = make_dist_train_step(
            self.model, tx, d.graph, d.feature, d.labels, d.mesh,
            self.fanout, self.batch, frontier_cap=sam["frontier_cap"])
        self.rng = np.random.default_rng([env.seed, 11])
        self.it = 0
        with timed(env.log, "first dist step (compile or cache)"):
            jax.block_until_ready(self._one_step())
        t0 = time.perf_counter()
        jax.block_until_ready(self._one_step())
        env.log(f"warm dist step: {time.perf_counter() - t0:.3f} s")

    def _seeds(self) -> np.ndarray:
        return np.stack([self.rng.choice(p, self.batch, replace=False)
                         for p in self.d.train_idx]).astype(np.int32)

    def _one_step(self):
        with span("dist_step_dispatch"):
            self.state, loss, acc = self.step(
                self.state, jnp.asarray(self._seeds()),
                jax.random.PRNGKey(self.env.seed * 7919 + self.it))
        self.it += 1
        return loss

    def window(self, seconds: float) -> Window:
        losses = []
        t0 = time.perf_counter()
        pending = self._one_step()
        while pending is not None:
            # One step stays in flight, as in a training loop that reads
            # its losses late: the host's part hides behind the device's.
            nxt = (self._one_step()
                   if time.perf_counter() - t0 < seconds else None)
            with span("step_wait"):
                jax.block_until_ready(pending)
            t_last = time.perf_counter()
            losses.append(pending)
            pending = nxt
        dt = t_last - t0
        self.losses = np.asarray(jax.device_get(losses))
        steps = len(losses)
        s = len(self.env.devices)
        return Window(attempted=steps, failed=0,
                      metrics={"seeds_per_s": steps * s * self.batch / dt},
                      steps=steps, counters={"window_s": dt})

    def check(self) -> dict:
        """Shard 0's batch out of the step's own sampling and gather
        calls (``dist_sample_multi_hop`` then ``exchange_gather_xy``, at
        their defaults, in one ``shard_map``), held to the reference; the
        model's evaluation-mode forward on that batch likewise."""
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from glt_tpu.parallel import (dist_sample_multi_hop,
                                      exchange_gather_xy)

        cfg, d = self.env.config, self.d
        checks.check(bool(np.isfinite(self.losses).all()),
                     f"non-finite training loss {self.losses}")
        g, c, s = d.graph, d.shapes.nodes_per_shard, d.shapes.num_shards
        fanout, cap = self.fanout, cfg["sampling"]["frontier_cap"]

        def local(indptr, indices, eids, rows, labels, seeds, key):
            key = jax.random.fold_in(key, lax.axis_index("shard"))
            out = dist_sample_multi_hop(
                indptr[0], indices[0], eids[0], seeds[0], key, fanout, c,
                s, "shard", cap)
            x, y = exchange_gather_xy(out.node, rows[0], labels[0], c, s,
                                      "shard")
            # The step masks padding labels after the exchange
            # (dist_train._gather_xy_local); so does its probe.
            y = jnp.where(out.node >= 0, y, -1)
            return tuple(a[None] for a in (
                out.node, out.node_mask, out.row, out.col, out.edge_mask,
                x, y))

        sp = P("shard")
        probe = jax.jit(jax.shard_map(
            local, mesh=d.mesh, in_specs=(sp,) * 6 + (P(),),
            out_specs=(sp,) * 7, check_vma=False))
        seeds = self._seeds()
        outs = probe(g.indptr, g.indices, g.edge_ids, d.feature.rows,
                     d.labels, jnp.asarray(seeds), jax.random.PRNGKey(5))
        # Shard 0's batch only crosses to the host.
        node, mask, row, col, em, x, y = (
            np.asarray(a.addressable_shards[0].data)[0] for a in outs)
        rng = np.random.default_rng([self.env.seed, 13])
        ei = np.stack([row, col])
        checks.check_batch(
            d.ref, {"node": node, "node_mask": mask, "seeds": seeds[0],
                    "x": x, "y": y, "edge_index": ei, "edge_mask": em},
            self.batch, fanout, "dist-train shard 0 batch", rng)
        dev = self.env.devices[0]
        put = lambda a: jax.device_put(a, dev)  # noqa: E731
        params = jax.device_put(self.state.params, dev)
        xd, eid, emd = put(x), put(ei), put(em)
        got = jax.jit(lambda p, x, ei, em: self.model.apply(
            p, x, ei, em, train=False)[: self.batch])(params, xd, eid, emd)
        want = jax.jit(lambda w, x, ei, em: reference.sage_forward(
            w, x, ei[0], ei[1], em)[: self.batch])(
            reference.layer_weights(params, len(fanout)), xd, eid, emd)
        err = checks.check_logits(got, want, cfg["check"]["logits_rtol"],
                                  "dist-train logits")
        return {"logits_err": err}

    def close(self) -> None:
        pass
