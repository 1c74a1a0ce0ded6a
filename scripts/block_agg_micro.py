#!/usr/bin/env python3
"""GraphSAGE's mean aggregation over one hop block, form by form, on the chip.

    chiprun --timeout 1800 -- python scripts/block_agg_micro.py

A hop block of the sampler is ``w`` frontier nodes times ``f`` edge slots,
slot ``s`` aggregating into destination row ``start + s // f``
(``sampler/neighbor_sampler.py::hop_bounds``).  This times, at the
benchmark cells' own ``(w, f, F)``, the mean over each destination's ``f``
slots as

* ``scatter``: ``x[src]`` then ``models/conv.py::scatter_mean`` (what a
  layer without a layout runs);
* ``A``: ``x[src]`` as ``[E, F]``, ``reshape(w, f, F)``, a masked sum over
  the middle axis;
* ``B``: ``f`` gathers of ``[w, F]`` by the columns of
  ``src.reshape(w, f)``, accumulated;
* ``C``: ``x[src]`` as ``[E, F]``, the sum as ``f`` strided slices
  ``msgs[j::f]`` added;
* ``D``: the slots transposed to fanout-major first (``src.reshape(w,
  f).T``, 4 bytes a slot), ONE gather ``[f * w, F]``, the sum as ``f``
  contiguous slabs added;
* ``shipped``: ``models/conv.py::block_mean`` on the block, placed at a
  dynamic start in ``num_dst`` rows, as ``SAGEConv`` runs it;

forward, and forward plus the gradient with respect to ``x`` (what layers
2-3 pay; layer 1's ``x`` is the feature rows and has none).  The ``layer``
rows run ``scatter`` and ``shipped`` over all the hop blocks one layer
reads.  Every form is checked against ``scatter`` on the device
(``max_err``, largest difference over the largest entry).

It is the go / no-go of the block aggregation (PERF.md §6, PR 31, has its
table): no benchmark cell runs it, nothing is asserted.  Times are host
clock over ``--reps`` back-to-back calls ended by one
``block_until_ready``; a program under 0.2 ms reads about 0.2 ms, the
host's dispatch.  Prints one JSON line last and writes
``chiprun_out/block_agg_micro.json``; refuses to time anything but a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (name, w, f, F, rows of x the block's sources come from)
BLOCKS = [
    ("products L1 hop3", 153_600, 5, 100, 402_944),
    ("products L1 hop2", 15_360, 10, 100, 402_944),
    ("products L1 hop1", 1_024, 15, 100, 402_944),
    ("link L1 hop3", 614_400, 5, 100, 897_280),
    ("link L1 hop2", 61_440, 10, 100, 897_280),
    ("link L1 hop1", 4_096, 15, 100, 897_280),
    ("dist L1 hop3", 153_600, 5, 128, 937_984),
    ("dist L1 hop2", 15_360, 10, 128, 937_984),
    ("dist L1 hop1", 1_024, 15, 128, 937_984),
    ("products L2 hop2", 15_360, 10, 256, 169_984),
    ("link L2 hop2", 61_440, 10, 256, 679_936),
]
# (name, hop blocks (w, f) the layer reads, F, source rows, destination rows)
LAYERS = [
    ("layer products L1", ((1024, 15), (15360, 10), (153600, 5)), 100,
     402_944, 169_984),
    ("layer link L1", ((4096, 15), (61440, 10), (614400, 5)), 100,
     897_280, 679_936),
    ("layer dist L1", ((1024, 15), (15360, 10), (153600, 5)), 128,
     937_984, 169_984),
    ("layer products L2", ((1024, 15), (15360, 10)), 256, 169_984, 16_384),
    ("layer link L2", ((4096, 15), (61440, 10)), 256, 679_936, 65_536),
]


def timed(fn, args, reps: int) -> float:
    """Milliseconds a call of jitted ``fn(*args)``, steady state."""
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def forms(w: int, f: int):
    """``name -> (x, src, mask, start) -> [w, F]`` means of one block."""
    import jax.numpy as jnp

    from glt_tpu.models import conv

    def mean(total, mask2, axis):
        cnt = mask2.sum(axis).astype(total.dtype)
        return total / jnp.maximum(cnt, 1)[:, None]

    def take(x, idx):
        return jnp.take(x, jnp.clip(idx, 0, x.shape[0] - 1), axis=0)

    def scatter(x, src, mask, start):
        dst = jnp.arange(w * f, dtype=jnp.int32) // f
        return conv.scatter_mean(take(x, src), dst, w, mask)

    def form_a(x, src, mask, start):
        msgs = jnp.where(mask[:, None], take(x, src), 0)
        return mean(msgs.reshape(w, f, -1).sum(1), mask.reshape(w, f), 1)

    def form_b(x, src, mask, start):
        s2, m2 = src.reshape(w, f), mask.reshape(w, f)
        total = sum(jnp.where(m2[:, j, None], take(x, s2[:, j]), 0)
                    for j in range(f))
        return mean(total, m2, 1)

    def form_c(x, src, mask, start):
        msgs = take(x, src)
        total = sum(jnp.where(mask[j::f, None], msgs[j::f], 0)
                    for j in range(f))
        return mean(total, mask.reshape(w, f), 1)

    def form_d(x, src, mask, start):
        s_t, m_t = src.reshape(w, f).T, mask.reshape(w, f).T
        msgs = take(x, s_t.ravel()).reshape(f, w, -1)
        return mean(jnp.where(m_t[:, :, None], msgs, 0).sum(0), m_t, 0)

    def shipped(x, src, mask, start):
        dst = start + jnp.arange(w * f, dtype=jnp.int32) // f
        return layer_shipped(((w, f),), w)(x, src, dst, mask)

    return {"scatter": scatter, "A": form_a, "B": form_b, "C": form_c,
            "D": form_d, "shipped": shipped}


def layer_scatter(num_dst: int):
    import jax.numpy as jnp

    from glt_tpu.models import conv

    def run(x, src, dst, mask):
        msgs = jnp.take(x, jnp.clip(src, 0, x.shape[0] - 1), axis=0)
        return conv.scatter_mean(msgs, dst, num_dst, mask)
    return run


def layer_shipped(blocks, num_dst: int):
    from glt_tpu.models import conv

    def run(x, src, dst, mask):
        return conv.block_mean(x, src, dst, mask, blocks, num_dst)
    return run


def fwd_and_bwd(fn):
    """``fn`` and its gradient w.r.t. ``x`` under a fixed cotangent."""
    import jax
    import jax.numpy as jnp

    def run(x, cot, *rest):
        return jax.value_and_grad(
            lambda x_: jnp.vdot(fn(x_, *rest), cot))(x)
    return run


def max_err(got, want) -> float:
    import numpy as np
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def bench(fns, x, cot, rest, reps):
    """``{form: {"fwd", "fwd_bwd", "max_err", "grad_max_err"}}``; the first
    of ``fns`` is the one the others are compared with."""
    import jax
    out, want, want_g = {}, None, None
    for name, fn in fns.items():
        fwd, both = jax.jit(fn), jax.jit(fwd_and_bwd(fn))
        got = fwd(x, *rest)
        _, got_g = both(x, cot, *rest)
        if want is None:
            want, want_g = got, got_g
        out[name] = {"fwd": round(timed(fwd, (x, *rest), reps), 4),
                     "fwd_bwd": round(timed(both, (x, cot, *rest), reps), 4),
                     "max_err": max_err(got, want),
                     "grad_max_err": max_err(got_g, want_g)}
    return out


def inputs(rng, blocks, width, num_src, num_dst):
    """``x, cotangent, src, dst, mask`` of a layer over ``blocks`` as the
    sampler lays them out: starts ascending, each block's frontier partly
    padding, 20 % of the slots masked (a node of degree under its
    fanout)."""
    import jax.numpy as jnp
    import numpy as np
    src, dst, mask, start = [], [], [], 0
    for w, f in blocks:
        live = int(w * 0.8)
        slot = np.arange(w * f) // f
        m = (rng.random(w * f) < 0.8) & (slot < live)
        src.append(np.where(m, rng.integers(0, num_src, w * f), -1))
        dst.append(np.where(slot < live, start + slot, -1))
        mask.append(m)
        start = min(start + live, num_dst - 1)
    x = rng.standard_normal((num_src, width), dtype=np.float32)
    cot = rng.standard_normal((num_dst, width), dtype=np.float32)
    return (jnp.asarray(x), jnp.asarray(cot),
            jnp.asarray(np.concatenate(src), jnp.int32),
            jnp.asarray(np.concatenate(dst), jnp.int32),
            jnp.asarray(np.concatenate(mask)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shapes", default="",
                    help="comma list of name prefixes (default all)")
    ap.add_argument("--forms", default="",
                    help="comma list of forms beside scatter (default all)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "block_agg_micro.json"))
    ap.add_argument("--any-device", action="store_true",
                    help="rehearse on whatever backend there is (a CPU "
                         "time is no device time)")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from glt_tpu.utils import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.any_device:
        print(f"refusing to time on {dev.platform}", file=sys.stderr)
        return 2
    want = [s for s in args.shapes.split(",") if s]
    only = [s for s in args.forms.split(",") if s]

    def wanted(name):
        return not want or any(name.startswith(p) for p in want)

    results = []
    for name, w, f, width, num_src in BLOCKS:
        if not wanted(name):
            continue
        rng = np.random.default_rng(args.seed)
        x, cot, src, _, mask = inputs(rng, ((w, f),), width, num_src, w)
        fns = {k: v for k, v in forms(w, f).items()
               if k == "scatter" or not only or k in only}
        start = jax.numpy.asarray(0, jax.numpy.int32)
        results.append({"block": name, "w": w, "f": f, "F": width,
                        "ms": bench(fns, x, cot, (src, mask, start),
                                    args.reps)})
        print(json.dumps(results[-1]), flush=True)
    for name, blocks, width, num_src, num_dst in LAYERS:
        if not wanted(name):
            continue
        rng = np.random.default_rng(args.seed)
        x, cot, src, dst, mask = inputs(rng, blocks, width, num_src, num_dst)
        fns = {"scatter": layer_scatter(num_dst),
               "shipped": layer_shipped(blocks, num_dst)}
        results.append({"layer": name, "blocks": blocks, "F": width,
                        "num_dst": num_dst,
                        "ms": bench(fns, x, cot, (src, dst, mask),
                                    args.reps)})
        print(json.dumps(results[-1]), flush=True)

    print("| shape | form | fwd ms | fwd+bwd ms | max err |")
    print("|---|---|---|---|---|")
    for r in results:
        for form, t in r["ms"].items():
            print(f"| {r.get('block') or r['layer']} | {form} | {t['fwd']} "
                  f"| {t['fwd_bwd']} | {t['max_err']:.1e} |")
    record = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "reps": args.reps, "results": results}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    # Scratch output of one process, read after it ends.
    # gltlint: disable-next=non-atomic-state-publish
    with open(args.out, "w") as fh:
        json.dump(record, fh, indent=1)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
