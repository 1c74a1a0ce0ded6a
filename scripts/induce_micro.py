#!/usr/bin/env python3
"""The last hop's inducer, piece by piece, on the chip.

    chiprun --timeout 2400 -- python scripts/induce_micro.py

Times, at the benchmark cells' own last-hop widths, the four random passes
of the map form (``dense_induce_final``: scatter-max into the id map, its
read-back, the ``[m]`` winner read, the node-buffer scatter) beside the
pieces of the sorted form (``_sorted_induce_final``: four ``lax.sort``
calls, the segmented fill, the contiguous store) and both whole, each as
one jitted program over the same device arrays.  The GraphSAGE cells share
``(known, m)`` = (169,984, 768,000) and differ in the id map (2.45 M,
6.94 M, 27.8 M nodes); the typed cell's inducers are far narrower.

It is the go / no-go of ``ops/unique.py::induce_final`` (PERF.md §6, PR
29, has its table): no benchmark cell runs it, nothing is asserted.  Times
are host clock over ``--reps`` back-to-back calls ended by one
``block_until_ready``; a program under 0.2 ms reads about 0.2 ms, the
host's dispatch.  A shape takes three to five minutes, most of it
compiling (``--shapes`` picks some).  Prints a table and writes
``chiprun_out/induce_micro.json``; refuses to time anything but a TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (name, id-map nodes, known = static bound on earlier nodes, candidates m,
#  node-buffer capacity)
SHAPES = [
    ("sage-products", 2_449_029, 169_984, 768_000, 402_944),
    ("sage-papers100m-chip1", 6_941_247, 169_984, 768_000, 344_320),
    ("sage-papers100m-dist4", 27_764_989, 169_984, 768_000, 937_984),
    ("rgat paper", 1_000_000, 12_672, 55_040, 48_128),
    ("rgat author", 1_926_066, 5_280, 35_840, 20_224),
    ("rgat fos", 190_449, 5_280, 34_560, 23_808),
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


def fill_associative(head, value):
    """The segmented copy scan as ``lax.associative_scan`` (what
    ``ops/unique.py::_run_fill`` is measured against)."""
    import jax
    import jax.numpy as jnp

    def later(a, b):
        return a[0] | b[0], jnp.where(b[0], b[1], a[1])
    return jax.lax.associative_scan(later, (head, value))[1]


def fill_cummax(head, value, value_bits: int):
    """The same scan as packed running maxima: the run index (a prefix
    sum, so monotone) in the high bits carries a slice of the head's
    value in the low bits; as many passes as the value needs."""
    import jax
    import jax.numpy as jnp
    n = head.shape[0]
    run = jnp.cumsum(head.astype(jnp.int32))
    run_bits = max(1, int(n).bit_length())
    chunk = 31 - run_bits
    out, shift = jnp.zeros_like(value), 0
    while shift < value_bits:
        part = (value >> shift) & ((1 << chunk) - 1)
        packed = jnp.where(head, (run << chunk) | part, 0)
        got = jax.lax.cummax(packed) & ((1 << chunk) - 1)
        out = out | (got << shift)
        shift += chunk
    return out


def bench_shape(name, num_nodes, known, m, cap, reps, rng, whole_only):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from glt_tpu.ops import unique as U

    n = known + m
    # A prior buffer six tenths full; candidates with heavy repeats (a
    # squared uniform: hubs), a few of them known, 3 % padding.
    prior = rng.choice(num_nodes, size=int(known * 0.6), replace=False)
    prior = np.concatenate([prior, np.full(known - prior.size, -1)])
    cand = (rng.random(m) ** 2 * num_nodes).astype(np.int64)
    cand[rng.random(m) < 0.03] = -1
    state = U.dense_induce_init(num_nodes, cap)
    state, _ = jax.jit(U.dense_induce)(state, jnp.asarray(prior, jnp.int32))
    cand = jnp.asarray(cand, jnp.int32)
    jax.block_until_ready((state, cand))
    rows = {}

    def row(label, fn, *args):
        rows[label] = round(timed(jax.jit(fn), args, reps), 4)

    def result():
        return {"shape": name, "num_nodes": num_nodes, "known": known,
                "m": m, "capacity": cap, "same_as_map": same, "ms": rows}

    # -- both forms whole, and that they agree at this width on this chip --
    row("map: dense_induce_final", U.dense_induce_final, state, cand)
    row("sorted: _sorted_induce_final",
        lambda s, c: U._sorted_induce_final(s, c, known), state, cand)
    (a_state, a_local), (b_state, b_local) = (
        jax.jit(U.dense_induce_final)(state, cand),
        jax.jit(lambda s, c: U._sorted_induce_final(s, c, known))(state, cand))
    same = bool(np.array_equal(a_local, b_local)
                and np.array_equal(a_state.node_buf[:cap],
                                   b_state.node_buf[:cap])
                and int(a_state.count) == int(b_state.count))
    if whole_only:
        return result()

    # -- the map form's four random passes ---------------------------------
    valid = cand >= 0
    safe = jnp.where(valid, cand, num_nodes)
    pos_m = jnp.arange(m, dtype=jnp.int32)
    row("map 1: scatter-max s32[N+2]",
        lambda seen, i, v: seen.at[i].max(v), state.seen,
        jnp.where(valid, safe, num_nodes + 1),
        jnp.where(valid, U._PROV_BASE - pos_m, 0))
    row("map 2: seen[safe]", lambda seen, i: seen[i], state.seen, safe)
    perm = jnp.asarray(rng.integers(0, m, m), jnp.int32)
    row("map 3: local_new[winner]", lambda a, i: a[i], pos_m, perm)
    row("map 4: node_buf scatter",
        lambda buf, i, v: buf.at[i].set(v), state.node_buf,
        jnp.minimum(perm, cap), cand)

    # -- the sorted form's pieces -------------------------------------------
    ids = jnp.concatenate([state.node_buf[:known], cand])
    keys = jnp.where(ids >= 0, ids, U._INT32_MAX)
    pos = jnp.arange(n, dtype=jnp.int32)
    sort = functools.partial(jax.lax.sort, is_stable=False)
    row("sort, 1 operand", lambda k: sort(k), keys)
    row("sort 1: (id, pos), 2 keys",
        lambda k, p: sort((k, p), num_keys=2), keys, pos)
    sk, sp = sort((keys, pos), num_keys=2)
    row("sort 2: 1 key, 2 payloads",
        lambda a, b, c: sort((a, b, c), num_keys=1), sp, sk, pos)
    row("sort 3 / 4: 1 key, 1 payload",
        lambda a, b: sort((a, b), num_keys=1), sp, sk)
    row("sort 4 as a stable sort",
        lambda a, b: jax.lax.sort((a, b), num_keys=1), sp, sk)
    head = (sk != jnp.concatenate([jnp.full((1,), -1, jnp.int32), sk[:-1]])
            ) & (sk != U._INT32_MAX)
    row("fill: _run_fill (shipped)", U._run_fill, head, sp)
    row("fill: lax.associative_scan", fill_associative, head, sp)
    row("fill: packed cummax",
        lambda h, v: fill_cummax(h, v, int(n).bit_length()), head, sp)
    row("cumsum s32[known+m]", lambda a: jnp.cumsum(a),
        head.astype(jnp.int32))
    want = np.asarray(U._run_fill(head, sp))
    for label, got in (("associative_scan", fill_associative(head, sp)),
                       ("cummax", fill_cummax(head, sp,
                                              int(n).bit_length()))):
        if not np.array_equal(want, np.asarray(got)):
            rows[f"fill {label} WRONG"] = -1.0

    def store(buf, window, count):
        stored = jax.lax.dynamic_update_slice(
            jnp.concatenate([buf[:cap], jnp.full((m,), -1, jnp.int32)]),
            window, (count,))
        return jnp.concatenate([stored[:cap], buf[cap:]])
    row("store: window at count", store, state.node_buf, cand, state.count)
    return result()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shapes", default="",
                    help="comma list of shape-name prefixes (default all)")
    ap.add_argument("--whole-only", action="store_true",
                    help="time the two forms whole and compare their "
                         "outputs; skip the pieces")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "induce_micro.json"))
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
    results = []
    for shape in SHAPES:
        if want and not any(shape[0].startswith(w) for w in want):
            continue
        rng = np.random.default_rng(args.seed)
        results.append(bench_shape(*shape, args.reps, rng, args.whole_only))
        print(json.dumps(results[-1]), flush=True)
    labels = list(results[0]["ms"]) if results else []
    print("same as the map form: " + ", ".join(
        f"{r['shape']} {r['same_as_map']}" for r in results))
    print("| ms a call | " + " | ".join(r["shape"] for r in results) + " |")
    print("|---|" + "---|" * len(results))
    for label in labels:
        print(f"| {label} | " + " | ".join(
            str(r["ms"].get(label, "")) for r in results) + " |")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "reps": args.reps, "results": results}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
