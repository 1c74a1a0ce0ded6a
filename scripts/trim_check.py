#!/usr/bin/env python3
"""Trimmed against whole GraphSAGE at a benchmark cell's own shapes.

    chiprun -- python scripts/trim_check.py --workload sage-products.train-scan

Builds the cell's scanned train step as the benchmark does, trains
``--groups`` scan groups, then takes fresh batches out of the step's own
sampler and runs the trained parameters through the model with dropout
off, whole and trimmed by ``sampler.hop_bounds`` as the step runs it, in
two precisions:

* ``f32``: float32 matmuls at ``highest`` precision.  Here the two are the
  same sums up to reassociation, so they must agree to ``--tol``: the
  largest difference of the seed logits over their RMS, of the loss, and of
  each parameter's gradient over that gradient's largest entry.
* ``configured``: the cell's own matmul dtype (bf16).  XLA rounds
  intermediate results where its fusions end, and the fusions follow the
  shapes, so two programs of one formula differ by bf16 rounding noise.
  Each is held to the reference as the benchmark's ``correct`` holds the
  whole model (``chipbench.checks.check_logits``, RMS of the difference
  over the RMS, the configuration's ``logits_rtol``); the distance between
  the two and between their gradients is reported in the same norm.

Prints one JSON line; exits 1 past a limit.  tests/test_models.py holds
the float32 comparison at toy shapes on the CPU; this is the chip's word
at the real ones.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


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
    import jax.numpy as jnp
    import numpy as np

    from chipbench import checks, reference
    from chipbench.common import Env, seed_stream
    from glt_tpu.models import make_gather_xy, seed_cross_entropy
    from glt_tpu.sampler import NodeSamplerInput

    cell, config, traffic, _ = bench_run.load_cell(args.workload)
    env = Env(config=config, traffic=traffic, seed=args.seed,
              devices=jax.devices()[: int(cell["chips"])], trace=False,
              log=bench_run.log)
    drv = bench_run.build_driver(env)       # warm-up trains one call
    for _ in range(args.groups):
        drv._groups(1)
    params, sampler = drv.state.params, drv.sampler
    hops, bs = sampler.hop_bounds, drv.batch
    gather_xy = jax.jit(make_gather_xy(drv.feat.id2index))
    labels = jnp.asarray(drv.labels)
    rtol = float(config["check"]["logits_rtol"])

    def grad_of(model, precision):
        def loss_and_logits(p, x, y, out, hops):
            with jax.default_matmul_precision(precision):
                logits = model.apply(p, x, jnp.stack([out.row, out.col]),
                                     out.edge_mask, train=False, hops=hops)
            loss, _ = seed_cross_entropy(logits, y, bs, out.node_mask,
                                         out.num_sampled_nodes[0])
            return loss, logits[:bs]
        return jax.jit(jax.value_and_grad(loss_and_logits, has_aux=True),
                       static_argnums=4)

    grads = {"f32": grad_of(drv.model.clone(dtype=None), "highest"),
             "configured": grad_of(drv.model, "default")}
    ref = jax.jit(lambda w, x, out: reference.sage_forward(
        w, x, out.row, out.col, out.edge_mask)[:bs])
    weights = reference.layer_weights(params, len(drv.fanout))

    def rms_rel(a, b):      # the benchmark's norm, no limit of its own
        return checks.check_logits(a, b, float("inf"), "trim_check")

    def worst(d, key, value):
        d[key] = max(d.get(key, 0.0), float(value))

    rng = np.random.default_rng([args.seed, 17])
    f32, cfgd = {}, {}
    for _ in range(args.batches):
        seeds = seed_stream(drv.d.train_idx, bs, rng)
        out = sampler.sample_from_nodes(
            NodeSamplerInput(seeds.astype(np.int32)))
        x, y = gather_xy(drv.feat.hot_rows, labels, out)
        (l_w, lg_w), g_w = grads["f32"](params, x, y, out, None)
        (l_t, lg_t), g_t = grads["f32"](params, x, y, out, hops)
        lg_w, lg_t = np.asarray(lg_w), np.asarray(lg_t)
        worst(f32, "logits_maxabs_over_rms",
              np.abs(lg_t - lg_w).max() / np.sqrt((lg_w ** 2).mean()))
        worst(f32, "loss_rel", abs(float(l_t) - float(l_w)) / abs(float(l_w)))
        for a, b in zip(jax.tree_util.tree_leaves(g_t),
                        jax.tree_util.tree_leaves(g_w)):
            a, b = np.asarray(a), np.asarray(b)
            worst(f32, "grad_maxabs_over_max",
                  np.abs(a - b).max() / np.abs(b).max())

        want = ref(weights, x, out)
        (l_w, lg_w), g_w = grads["configured"](params, x, y, out, None)
        (l_t, lg_t), g_t = grads["configured"](params, x, y, out, hops)
        worst(cfgd, "whole_to_reference", rms_rel(lg_w, want))
        worst(cfgd, "trimmed_to_reference", rms_rel(lg_t, want))
        worst(cfgd, "trimmed_to_whole", rms_rel(lg_t, lg_w))
        worst(cfgd, "loss_rel", abs(float(l_t) - float(l_w)) / abs(float(l_w)))
        for a, b in zip(jax.tree_util.tree_leaves(g_t),
                        jax.tree_util.tree_leaves(g_w)):
            worst(cfgd, "grad_trimmed_to_whole", rms_rel(a, b))
    ok = (all(v <= args.tol for v in f32.values())
          and cfgd["trimmed_to_reference"] <= rtol
          and cfgd["whole_to_reference"] <= rtol)
    print(json.dumps({"ok": ok, "workload": args.workload, "hops": hops,
                      "batches": args.batches,
                      "trained_steps": int(drv.state.step),
                      "loss": float(l_w), "tol": args.tol,
                      "logits_rtol": rtol,
                      "platform": jax.devices()[0].platform,
                      "f32": f32, "configured": cfgd}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
