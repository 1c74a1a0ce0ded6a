"""Closed loop through upstream's recommendation example
(``examples/hetero/bipartite_sage_unsup.py``): ``run_scanned_epoch`` over
``make_scanned_hetero_link_train_step``: the typed seed-edge draw, strict
binary negatives, the typed sample from the two-type seed union, the
lookups of both embedding tables, forward, pair loss, backward and dense
Adam over every parameter, ``group`` batches a program.  Seed edges are
the training user->item edges, a shuffled pass by CSR position drawn a
call at a time."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks
from chipbench import data_bipartite
from chipbench import reference_bipartite
from chipbench.common import Window, span, timed
from chipbench.drivers.link_scan_train import edges_at
# What this cell needs of the program, named before anything is generated:
# a checkout without the typed link step or the tables stops here.
from glt_tpu.models import (BipartiteSAGE,
                            make_scanned_hetero_link_train_step,
                            run_scanned_epoch)
from glt_tpu.models.bipartite import init_state
from glt_tpu.models.train import shuffled_positions
from glt_tpu.sampler import NegativeSampling
from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler

UI = data_bipartite.UI


def make_model(config: dict) -> BipartiteSAGE:
    m, n = config["model"], config["data"]["node_types"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": None}[m["matmul_dtype"]]
    return BipartiteSAGE(int(n["user"]), int(n["item"]), int(m["hidden"]),
                         int(m["out"]), dtype=dtype)


def four_bits(a):
    """``a`` rounded to four mantissa bits, on the device."""
    m, e = jnp.frexp(a)
    return jnp.ldexp(jnp.round(m * 32) / 32, e)


class Driver:
    def __init__(self, env):
        import optax

        from glt_tpu.obs import metrics as registry

        self.env = env
        cfg, sam = env.config, env.config["sampling"]
        self.batch, self.fanout = int(sam["batch_size"]), list(sam["fanout"])
        self.group = int(env.traffic["group"])
        self.registry = registry
        self.lr = float(cfg["model"]["learning_rate"])
        if env.trace:
            registry.enable()       # the step's gauges are set when built
        with timed(env.log, "generate + place"):
            self.d = data_bipartite.build(cfg, env.seed, env.log)
        self.neg = NegativeSampling(sam["neg_sampling"], sam["amount"])
        self.sampler = HeteroNeighborSampler(
            self.d.graphs, self.fanout, "user", batch_size=self.batch)
        self.model = make_model(cfg)
        tx = optax.adam(self.lr)
        self._log_hbm("graph placed")
        self.state = init_state(self.model, tx, jax.random.PRNGKey(env.seed))
        sstep = make_scanned_hetero_link_train_step(
            self.model, tx, self.sampler, UI, self.neg)

        @functools.wraps(sstep)
        def step(state, blk, key):
            with span("scan_group_dispatch"):
                return sstep(state, blk, key)

        self.step = step
        self.impl = jax.jit(self.sampler.edges_program(UI, "binary", 1)[0])
        self.rng = np.random.default_rng([env.seed, 11])
        self.key = jax.random.PRNGKey(env.seed + 100)
        self.per_call = int(env.traffic["groups_per_call"])
        self._pass = iter(())
        self.num_edges = self.d.graphs[UI].num_edges
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
        """The next ``count`` training user->item edges ``[2, count]`` of a
        shuffled pass by CSR position, a new pass when one ends."""
        pos = next(self._pass, None)
        if pos is None or pos.shape[0] < count:
            self._pass = shuffled_positions(self.num_edges, self.rng, count)
            pos = next(self._pass)
        return edges_at(self.d.graphs[UI].topo, pos[:count])

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
        counters = {"window_s": dt}
        if self.registry.enabled():
            counters["neg_padded_share"] = (
                100.0 * (self._padded_slots() - padded0)
                / (steps * self.batch))
        return Window(attempted=steps, failed=int(ovf),
                      metrics={"seeds_per_s": seeds / dt}, steps=steps,
                      counters=counters)

    def _sample(self, edges, key):
        """One batch as the step samples it: the sampler's output and the
        batch in the reference's form."""
        g = {et: (gr.indptr, gr.indices, gr.gather_edge_ids)
             for et, gr in self.d.graphs.items()}
        s = jnp.asarray(edges[0], jnp.int32)
        d = jnp.asarray(edges[1], jnp.int32)
        out = self.impl(g, self.d.graphs[UI].sorted_indices, s, d,
                        jnp.zeros((1,), jnp.float32), key)
        label = jnp.concatenate([jnp.where(s >= 0, 1, -1),
                                 jnp.zeros((self.batch,), jnp.int32)])
        return out, {
            "ids": dict(out.node),
            "edge_index": {et: jnp.stack([out.row[et], out.col[et]])
                           for et in out.row},
            "edge_mask": dict(out.edge_mask),
            "pairs": out.metadata["edge_label_index"], "label": label}

    def check(self) -> dict:
        cfg, chk = self.env.config, self.env.config["check"]
        checks.check(bool(np.isfinite(self.losses).all()),
                     f"non-finite training loss {self.losses}")
        rng = np.random.default_rng([self.env.seed, 13])
        ref = self.d.ref
        with timed(self.env.log, "check: transposes"):
            detail = {"transposed_edges": ref.check_transposes(
                {et: (g.topo.indptr, g.topo.indices)
                 for et, g in self.d.graphs.items()})}
        # (a) the sampling guarantees, on a batch of the window's kind.
        t0 = time.perf_counter()
        edges = self._seed_edges(self.batch)
        out, b = self._sample(edges, jax.random.PRNGKey(self.env.seed + 7))
        meta = out.metadata
        detail.update(reference_bipartite.check_link_batch(
            ref, {"node": out.node, "node_mask": out.node_mask,
                  "x": b["ids"], "row": out.row, "col": out.col,
                  "edge_mask": out.edge_mask,
                  "edge_label_index": meta["edge_label_index"],
                  "edge_label": b["label"], "neg_strict": meta["neg_strict"]},
            edges[0], edges[1], self.batch, self.fanout,
            "hetero-link-train-scan batch", rng))
        # (b) the model's pair logits, evaluation mode, the trained state.
        forward = jax.jit(lambda p: self.model.apply(
            p, (b["ids"], b["pairs"]), b["edge_index"], b["edge_mask"]))
        p = self.state.params["params"]
        want = reference_bipartite.logits_of_rows(
            p, *(reference_bipartite.lookup(
                reference_bipartite.table_rows(p, t), b["ids"][t])
                for t in ("user", "item")), b)
        detail["logits_err"] = checks.check_logits(
            forward(self.state.params), want, chk["logits_rtol"],
            "hetero-link-train-scan pair logits")
        # The other reading the tolerance was set between: every
        # parameter, tables included, at four mantissa bits.
        detail["logits_err_4bit"] = checks.check_logits(
            forward(jax.tree_util.tree_map(four_bits, self.state.params)),
            want, np.inf, "four mantissa bits")
        self.env.log(f"check: batch and logits: "
                     f"{time.perf_counter() - t0:.2f} s")
        # (c) one scanned call from the window's state against dense Adam.
        t0 = time.perf_counter()
        blk_edges = self._seed_edges(self.group * self.batch)
        blk = blk_edges.reshape(2, self.group, self.batch).transpose(1, 0, 2)
        key = jax.random.PRNGKey(self.env.seed + 9)
        batches = [self._sample(blk[g], k)[1]
                   for g, k in enumerate(jax.random.split(key, self.group))]
        start = reference_bipartite.before_call(self.state, batches, rng)
        self.state, *_ = self.step(self.state, blk, key)
        detail.update(reference_bipartite.check_adam(
            start, self.state, batches, chk["adam_touched_rtol"],
            chk["adam_untouched_ulp"], "hetero-link-train-scan update",
            self.lr))
        self.env.log(f"check: dense Adam: {time.perf_counter() - t0:.2f} s")
        return detail

    def close(self) -> None:
        pass
