#!/usr/bin/env python3
"""Trimmed against whole model at a benchmark cell's own shapes.

    chiprun -- python scripts/trim_check.py --workload sage-products.train-scan
    chiprun -- python scripts/trim_check.py \\
        --workload rgat-igbh-small.hetero-train-scan

Builds the cell's scanned train step as the benchmark does, trains
``--groups`` calls, then takes fresh batches out of the step's own sampler
and runs the trained parameters through the model with dropout off, whole
and trimmed by ``sampler.hop_bounds`` as the step runs it (GraphSAGE by
``HopBounds``, R-GAT by the typed ``HeteroHopBounds``), in two precisions:

* ``f32``: float32 matmuls at ``highest`` precision.  Here the two are the
  same sums up to reassociation, so they must agree to ``--tol``: the
  largest difference of the seed logits over their RMS, of the loss, and of
  each parameter's gradient over that gradient's largest entry (or a
  thousandth of the largest entry of any parameter's, where that is
  larger: a gradient whose terms cancel is rounding noise on both sides).
* ``configured``: the cell's own matmul dtype (bf16).  XLA rounds
  intermediate results where its fusions end, and the fusions follow the
  shapes, so two programs of one formula differ by bf16 rounding noise.
  Each is held to the reference as the benchmark's ``correct`` holds the
  step's model (``chipbench.checks.check_logits``, RMS of the difference
  over the RMS, the configuration's ``logits_rtol``); the distance between
  the two and between their gradients is reported in the same norm (a
  gradient's RMS floored the same way).

Since PR 31 a GraphSAGE with the layout also aggregates its hop blocks as
contiguous sums (``models/conv.py::block_mean``) where the whole model
scatters, so for a GraphSAGE cell this is the block form against the
scatter form as well; the benchmark's own check of ``train-scan`` and
``dist-train`` calls the model without the layout and cannot see that.
The link cell is not taken here: its whole model's messages are
``f32[3747840,256]``, 3.8 GB a tensor and several alive in the backward
pass, and its own ``correct`` already holds the model WITH the union's
layout to the float32 reference.

The typed cell's whole model does not fit beside its feature tables (the
class-wide last layer alone is 12 KB a paper row and an edge slot), so in
that mode the batches' rows are gathered first and the tables' buffers are
freed before the comparison: the batch, its shapes and the parameters are
the cell's.

Prints one JSON line; exits 1 past a limit.  tests/test_models.py and
tests/test_rgat_igbh.py hold the float32 comparison at toy shapes on the
CPU; this is the chip's word at the real ones.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def grad_of(model, precision, batch_size: int, hops):
    """Jitted ``(params, batch) -> ((loss, seed logits), grads)`` of
    ``model`` in evaluation mode, whole (``hops=None``) or trimmed."""
    import jax

    from glt_tpu.models import seed_cross_entropy

    def loss_and_logits(p, batch):
        x, y, edge_index, edge_mask, node_mask, num_seeds = batch
        with jax.default_matmul_precision(precision):
            logits = model.apply(p, x, edge_index, edge_mask, train=False,
                                 hops=hops)
        loss, _ = seed_cross_entropy(logits, y, batch_size, node_mask,
                                     num_seeds)
        return loss, logits[:batch_size]
    return jax.jit(jax.value_and_grad(loss_and_logits, has_aux=True))


def homo_batches(drv, n: int, rng):
    """``n`` fresh ``(batch, reference seed logits)`` of a GraphSAGE cell."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference
    from chipbench.common import seed_stream
    from glt_tpu.models import make_gather_xy
    from glt_tpu.sampler import NodeSamplerInput

    gather_xy = jax.jit(make_gather_xy(drv.feat.id2index))
    labels = jnp.asarray(drv.labels)
    ref = jax.jit(lambda w, x, out: reference.sage_forward(
        w, x, out.row, out.col, out.edge_mask)[:drv.batch])
    weights = reference.layer_weights(drv.state.params, len(drv.fanout))
    for _ in range(n):
        seeds = seed_stream(drv.d.train_idx, drv.batch, rng)
        out = drv.sampler.sample_from_nodes(
            NodeSamplerInput(seeds.astype(np.int32)))
        x, y = gather_xy(drv.feat.hot_rows, labels, out)
        yield ((x, y, jnp.stack([out.row, out.col]), out.edge_mask,
                out.node_mask, out.num_sampled_nodes[0]),
               ref(weights, x, out))


def typed_batches(drv, n: int, rng):
    """The same of the typed cell, the reference over the live edges as
    the benchmark's check computes it."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference_hetero
    from chipbench.common import seed_stream
    from glt_tpu.models.train import hetero_gather_xy
    from glt_tpu.sampler import NodeSamplerInput

    tgt, bs = drv.d.seed_type, drv.batch
    gather = jax.jit(functools.partial(hetero_gather_xy, batch_size=bs))
    rows = {t: f.hot_rows for t, f in drv.d.feats.items()}
    weights = reference_hetero.layer_weights(
        drv.state.params, drv.model.edge_types, drv.model.num_layers)
    for _ in range(n):
        seeds = seed_stream(drv.d.train_idx, bs, rng)
        out = drv.sampler.sample_from_nodes(
            NodeSamplerInput(seeds.astype(np.int32)))
        x, y = gather(rows, jnp.asarray(drv.d.labels), out)
        live = {}
        for et in out.row:
            em = np.asarray(out.edge_mask[et])
            live[et] = (np.asarray(out.row[et])[em],
                        np.asarray(out.col[et])[em])
        want = reference_hetero.rgnn_seed_logits(weights, x, live, tgt, bs)
        yield ((x, y, {et: jnp.stack([out.row[et], out.col[et]])
                       for et in out.row}, out.edge_mask,
                out.node_mask[tgt], out.num_sampled_nodes[tgt][0]), want)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sage-products.train-scan")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--tol", type=float, default=1e-4)
    args = ap.parse_args()

    from chipbench import run as bench_run
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    from chipbench import checks
    from chipbench.common import Env
    from glt_tpu.obs import metrics as registry

    cell, config, traffic, _ = bench_run.load_cell(args.workload)
    env = Env(config=config, traffic=traffic, seed=args.seed,
              devices=jax.devices()[: int(cell["chips"])], trace=False,
              log=bench_run.log)
    registry.enable()       # the step's extents are recorded when built
    drv = bench_run.build_driver(env)       # warm-up trains one call
    gauges = {k: v for k, v in registry.snapshot().items()
              if k.startswith("glt.model.")}
    registry.disable()
    for _ in range(args.groups):
        drv._groups(1)
    params, hops, bs = drv.state.params, drv.sampler.hop_bounds, drv.batch
    typed = isinstance(hops.node_bounds, dict)
    rtol = float(config["check"]["logits_rtol"])
    rng = np.random.default_rng([args.seed, 17])
    batches = list((typed_batches if typed else homo_batches)(
        drv, args.batches, rng))
    if typed:
        for f in drv.d.feats.values():      # see the module docstring
            f.hot_rows.delete()

    grads = {(name, trimmed): grad_of(model, precision, bs,
                                      hops if trimmed else None)
             for name, model, precision in (
                 ("f32", drv.model.clone(dtype=None), "highest"),
                 ("configured", drv.model, "default"))
             for trimmed in (False, True)}

    def rms_rel(a, b):      # the benchmark's norm, no limit of its own
        return checks.check_logits(a, b, float("inf"), "trim_check")

    def grad_distance(g_t, g_w, norm):
        """Largest over the parameters of ``norm`` of the difference over
        ``norm`` of the whole model's gradient, floored (docstring)."""
        g_w = [np.asarray(b, np.float64)
               for b in jax.tree_util.tree_leaves(g_w)]
        floor = 1e-3 * max(norm(b) for b in g_w)
        return max(norm(np.asarray(a) - b) / max(norm(b), floor)
                   for a, b in zip(jax.tree_util.tree_leaves(g_t), g_w))

    def worst(d, key, value):
        d[key] = max(d.get(key, 0.0), float(value))

    f32, cfgd = {}, {}
    for batch, want in batches:
        (l_w, lg_w), g_w = grads["f32", False](params, batch)
        (l_t, lg_t), g_t = grads["f32", True](params, batch)
        lg_w, lg_t = np.asarray(lg_w), np.asarray(lg_t)
        worst(f32, "logits_maxabs_over_rms",
              np.abs(lg_t - lg_w).max() / np.sqrt((lg_w ** 2).mean()))
        worst(f32, "loss_rel", abs(float(l_t) - float(l_w)) / abs(float(l_w)))
        worst(f32, "grad_maxabs_over_max",
              grad_distance(g_t, g_w, lambda v: np.abs(v).max()))
        del g_w, g_t

        (l_w, lg_w), g_w = grads["configured", False](params, batch)
        (l_t, lg_t), g_t = grads["configured", True](params, batch)
        worst(cfgd, "whole_to_reference", rms_rel(lg_w, want))
        worst(cfgd, "trimmed_to_reference", rms_rel(lg_t, want))
        worst(cfgd, "trimmed_to_whole", rms_rel(lg_t, lg_w))
        worst(cfgd, "loss_rel", abs(float(l_t) - float(l_w)) / abs(float(l_w)))
        worst(cfgd, "grad_trimmed_to_whole",
              grad_distance(g_t, g_w, lambda v: np.sqrt((v ** 2).mean())))
        del g_w, g_t
    ok = (all(v <= args.tol for v in f32.values())
          and cfgd["trimmed_to_reference"] <= rtol
          and cfgd["whole_to_reference"] <= rtol)
    print(json.dumps({"ok": ok, "workload": args.workload,
                      "hops": hops if not typed else {
                          "node_bounds": hops.node_bounds,
                          "edge_bounds": {"__".join(et): b for et, b
                                          in hops.edge_bounds.items()}},
                      "layer_extents": drv.model.layer_extents(hops),
                      "gauges": gauges, "batches": args.batches,
                      "trained_steps": int(drv.state.step),
                      "loss": float(l_w), "tol": args.tol,
                      "logits_rtol": rtol,
                      "platform": jax.devices()[0].platform,
                      "f32": f32, "configured": cfgd}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
