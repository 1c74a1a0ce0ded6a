"""Closed loop through the unsupervised example's driver:
``run_scanned_epoch`` over ``make_scanned_link_train_step``
(examples/graph_sage_unsup_ppi.py): the strict negative draw, the sample
from the seed union, gather, forward, pair loss, backward and update of
``group`` seed-edge batches in one program.  Seed edges are the graph's
own directed edges, a shuffled pass drawn a call at a time."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks
from chipbench import data
from chipbench import reference
from chipbench import reference_link
from chipbench.common import Window, span, timed
# What this cell needs of the program, named before anything is generated:
# a checkout whose link step is not driven by ``run_scanned_epoch`` stops
# here.
from glt_tpu.models import (GraphSAGE, init_train_state,
                            make_scanned_link_train_step, run_scanned_epoch)
from glt_tpu.models.train import make_gather_xy, shuffled_positions
from glt_tpu.sampler import NegativeSampling, NeighborSampler
from glt_tpu.sampler.base import EdgeSamplerInput


def make_model(config: dict):
    """The configuration's GraphSAGE encoder: embeddings, no classes."""
    m = config["model"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": None}[m["matmul_dtype"]]
    return GraphSAGE(hidden_features=int(m["hidden"]),
                     out_features=int(m["embedding"]),
                     num_layers=len(config["sampling"]["fanout"]),
                     dropout_rate=float(m["dropout"]), dtype=dtype)


def edges_at(topo, pos) -> np.ndarray:
    """The directed edges ``[2, n]`` stored at CSR positions ``pos``: the
    source found in the row pointers, the destination read in place."""
    src = np.searchsorted(topo.indptr, pos, side="right") - 1
    return np.stack([src, topo.indices[pos]]).astype(np.int64)


def four_bits(a):
    """``a`` rounded to four mantissa bits, in float32."""
    m, e = np.frexp(np.asarray(a, np.float32))
    return jnp.asarray(np.ldexp(np.round(m * 32) / 32, e), jnp.float32)


class Driver:
    def __init__(self, env):
        import optax

        from glt_tpu.obs import metrics as registry

        self.env = env
        cfg, sam = env.config, env.config["sampling"]
        self.batch, self.fanout = int(sam["batch_size"]), list(sam["fanout"])
        self.group = int(env.traffic["group"])
        self.registry = registry
        if env.trace:
            registry.enable()       # the step's gauges are set when built
        with timed(env.log, "generate + place"):
            self.d = data.build_one_chip(cfg, env.seed, env.devices[0],
                                         env.log)
        ds = self.d.dataset
        self.graph = ds.get_graph()
        self.model = make_model(cfg)
        tx = optax.adam(1e-3)
        self.neg = NegativeSampling(sam["neg_sampling"], sam["amount"])
        self.amount = int(sam["amount"])
        self.sampler = NeighborSampler(
            self.graph, self.fanout, batch_size=self.batch,
            frontier_cap=sam["frontier_cap"], with_edge=False,
            node_capacity=sam["node_capacity"])
        self.union = self.sampler.seed_union(self.neg)
        self.feat = ds.get_node_feature()
        with timed(env.log, "column-sorted view of the CSR"):
            jax.block_until_ready(self.graph.sorted_indices)
        self._log_hbm("tables placed")
        self.state = init_train_state(self.model, tx, self.feat.shape[1],
                                      jax.random.PRNGKey(env.seed))
        sstep = make_scanned_link_train_step(
            self.model, tx, self.sampler, self.feat, neg_sampling=self.neg,
            group=self.group)

        def step(state, blk, key):
            with span("scan_group_dispatch"):
                return sstep(state, blk, key)

        step.flag_counters = sstep.flag_counters
        self.step = step
        self.rng = np.random.default_rng([env.seed, 11])
        self.key = jax.random.PRNGKey(env.seed + 100)
        self.per_call = int(env.traffic["groups_per_call"])
        self._pass = iter(())
        # Warm-up at the window's own shapes: one call compiles (or reads
        # the cache) and runs every program the window will.
        with timed(env.log, "first call (compile or cache)"):
            self._groups(self.per_call)
        self._log_hbm("first call done")

    def _log_hbm(self, when: str) -> None:
        stats = self.env.devices[0].memory_stats() or {}
        self.env.log(f"{when}: {stats.get('bytes_in_use', 0) / 1e9:.3f} GB "
                     f"in use, peak {stats.get('peak_bytes_in_use', 0) / 1e9:.3f}")

    def _seed_edges(self, count: int) -> np.ndarray:
        """The next ``count`` seed edges ``[2, count]`` of a shuffled pass
        over the graph's directed edges, a new pass when one ends: CSR
        positions drawn a call at a time."""
        pos = next(self._pass, None)
        if pos is None or pos.shape[0] < count:
            self._pass = shuffled_positions(self.graph.num_edges, self.rng,
                                            count)
            pos = next(self._pass)
        return edges_at(self.graph.topo, pos[:count])

    def _groups(self, n: int):
        """``n`` groups through the program's epoch driver; returns when
        its own loss fetch has waited for the last."""
        edges = self._seed_edges(n * self.group * self.batch)
        self.key, sub = jax.random.split(self.key)
        with span("scanned_epoch"):
            self.state, losses, accs, ovf = run_scanned_epoch(
                self.step, self.state, edges, self.batch, self.group,
                self.rng, sub)
        return edges.shape[1], losses, ovf

    def _padded_slots(self) -> float:
        return self.registry.snapshot().get("glt.link.neg_padded_slots", 0.0)

    def window(self, seconds: float) -> Window:
        calls = seeds = ovf = 0
        losses = []
        padded0 = self._padded_slots()
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
        counters = {"node_rows": self.union.node_capacity, "window_s": dt}
        if self.registry.enabled():
            counters["neg_padded_share"] = (
                100.0 * (self._padded_slots() - padded0)
                / (steps * self.batch * self.amount))
        return Window(attempted=steps, failed=int(ovf),
                      metrics={"seeds_per_s": seeds / dt}, steps=steps,
                      counters=counters)

    def check(self) -> dict:
        cfg = self.env.config
        checks.check(bool(np.isfinite(self.losses).all()),
                     f"non-finite training loss {self.losses}")
        rng = np.random.default_rng([self.env.seed, 13])
        q = self.batch
        for attempt in range(4):
            edges = self._seed_edges(q)
            out = self.sampler.sample_from_edges(EdgeSamplerInput(
                row=edges[0], col=edges[1], neg_sampling=self.neg))
            if not bool(np.asarray(out.metadata.get("overflow", False))):
                break
        else:
            raise checks.CheckFailure(
                "four batches in a row overflowed node_capacity "
                f"{self.union.node_capacity}")
        x, _ = jax.jit(make_gather_xy(self.feat.id2index))(
            self.feat.hot_rows, None, out)
        ei = jnp.stack([out.row, out.col])
        meta = out.metadata
        detail = reference_link.check_link_batch(
            self.d.ref, {"node": out.node, "node_mask": out.node_mask,
                         "x": x, "edge_index": ei,
                         "edge_mask": out.edge_mask,
                         "edge_label_index": meta["edge_label_index"],
                         "edge_label": meta["edge_label"],
                         "neg_strict": meta["neg_strict"]},
            edges[0], edges[1], q, self.amount, self.fanout,
            "link-train-scan batch", rng)
        # The step's own forward: the model with the seed union's layout,
        # evaluation mode, the trained parameters.
        pairs = meta["edge_label_index"]
        forward = jax.jit(lambda p, x: reference_link.pair_logits(
            self.model.apply(p, x, ei, out.edge_mask, train=False,
                             hops=self.union.hop_bounds), pairs))
        got = forward(self.state.params, x)
        weights = reference.layer_weights(self.state.params,
                                          len(self.fanout))
        want = reference_link.pair_logits(
            reference_link.sage_embed_blocked(weights, x, ei[0], ei[1],
                                              out.edge_mask), pairs)
        detail["logits_err"] = checks.check_logits(
            got, want, cfg["check"]["logits_rtol"],
            "link-train-scan pair logits")
        # The other reading the tolerance was set between: parameters and
        # rows rounded to four mantissa bits, which must not pass.
        detail["logits_err_4bit"] = checks.check_logits(
            forward(jax.tree.map(four_bits, self.state.params),
                    four_bits(x)), want, np.inf, "four mantissa bits")
        return detail

    def close(self) -> None:
        pass
