#!/usr/bin/env python3
"""The inducer of every hop, on the chip: the id map beside the sorts.

    chiprun --timeout 2400 -- python scripts/induce_micro.py

**The last hop, piece by piece** (``SHAPES``, ``--only last``; PR 29).
Times, at the benchmark cells' own last-hop widths, the four random passes
of the map form (``dense_induce_final``: scatter-max into the id map, its
read-back, the ``[m]`` winner read, the node-buffer scatter) beside the
pieces of the sorted form (``_sorted_induce``: four ``lax.sort`` calls,
the segmented fill, the contiguous store) and both whole, each as one
jitted program over the same device arrays.  The GraphSAGE cells share
``(known, m)`` = (169,984, 768,000) and differ in the id map (2.45 M,
6.94 M, 27.8 M nodes); the typed cell's inducers are far narrower.

**The chain, hop by hop** (``CHAINS``, ``--only chains``; PR 33).  For
every cell's chain of inducer calls (the seeds' own dedup, then every
hop) it times each call before the last in both forms from the same prior
state (``dense_induce`` beside ``_sorted_induce``; the map is an argument
there, so the map form's time holds one copy of it, 0.27 ms at 27.8 M
nodes), then whole programs from a fresh state, which is what a sampler
compiles: the hops before the last with the map (its ``4 B x (N + 2)``
memset included) and without, and the whole chain as the parent ran it
(map, then the sorted last hop) and as every chain whose buffer covers its
bound runs it now (no map).  ``same_as_map`` says whether ``local``,
``node_buf`` and ``count`` of every hop agree bit for bit.

It is the go / no-go of ``ops/unique.py::induce`` (PERF.md §6, PRs 29 and
33, keeps the whole-chain numbers; the full tables are in ``git show
4efd33c:PERF.md``): no benchmark cell runs it.  Times are host clock
over ``--reps`` back-to-back calls ended by one ``block_until_ready``; a
program under 0.2 ms reads about 0.2 ms, the host's dispatch.  A last-hop
shape takes three to five minutes, most of it compiling (``--shapes``
picks some), a chain about one.  Prints tables and writes
``chiprun_out/induce_micro.json``; refuses to time anything but a TPU and
exits 1 if any two forms disagree.
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


# (name, id-map nodes, node-buffer capacity, widths of the chain's calls:
#  the seeds, then each hop's candidates; 0 = no call).  The bound on known
#  nodes before a call is the sum of the widths before it.  The typed cell's
#  institute chain (capacity 1,280 under its bound 2,560) keeps the map and
#  is not here.
CHAINS = [
    ("sage-products", 2_449_029, 402_944, (1_024, 15_360, 153_600, 768_000)),
    ("sage-papers100m-chip1", 6_941_247, 344_320,
     (1_024, 15_360, 153_600, 768_000)),
    ("sage-papers100m-tiered", 27_764_989, 404_480,
     (1_024, 15_360, 153_600, 768_000)),
    ("sage-unsup-products", 2_449_029, 897_280,
     (4_096, 61_440, 614_400, 3_072_000)),
    ("rgat paper", 1_000_000, 48_128, (32, 480, 12_160, 55_040)),
    ("rgat author", 1_926_066, 20_224, (0, 480, 4_800, 35_840)),
    ("rgat fos", 190_449, 23_808, (0, 480, 4_800, 34_560)),
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
    row("sorted: _sorted_induce",
        lambda s, c: U._sorted_induce(s, c, known), state, cand)
    (a_state, a_local), (b_state, b_local) = (
        jax.jit(U.dense_induce_final)(state, cand),
        jax.jit(lambda s, c: U._sorted_induce(s, c, known))(state, cand))
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


def bench_chain(name, num_nodes, cap, widths, reps, rng):
    """One cell's chain: every call before the last in both forms, the
    calls before the last and the whole chain as one program each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from glt_tpu.ops import unique as U

    # Distinct seeds with a few pads; candidates with heavy repeats (a
    # squared uniform: hubs) and 3 % padding, as the last-hop shapes have.
    hops = []
    for k, m in enumerate(widths):
        if k == 0:
            c = rng.choice(num_nodes, size=m, replace=False)
        else:
            c = (rng.random(m) ** 2 * num_nodes).astype(np.int64)
        c[rng.random(m) < 0.03] = -1
        hops.append(jnp.asarray(c, jnp.int32))
    knowns = [min(int(k), num_nodes)
              for k in np.cumsum((0,) + tuple(widths[:-1]))]
    calls = [k for k, m in enumerate(widths) if m]
    last = calls[-1]
    assert U.chain_is_sorted(knowns[last], cap), name
    rows, same = {"keys (known + m), call by call": " / ".join(
        f"{knowns[k]:,} + {widths[k]:,}" for k in calls)}, True

    def outputs(state, local):
        return local, state.node_buf[:cap], state.count

    def agree(a, b):
        return all(np.array_equal(x, y) for x, y in zip(
            jax.tree.leaves(a), jax.tree.leaves(b)))

    # -- call by call, from the map chain's own prior state ---------------
    state = U.dense_induce_init(num_nodes, cap)
    for k in calls[:-1]:
        by_map = jax.jit(U.dense_induce)
        by_sort = jax.jit(functools.partial(U._sorted_induce,
                                            known=knowns[k]))
        mapless = state._replace(seen=None)
        label = f"call {k}"
        rows[f"{label}: map"] = round(timed(by_map, (state, hops[k]), reps),
                                      4)
        rows[f"{label}: sorted"] = round(
            timed(by_sort, (mapless, hops[k]), reps), 4)
        nxt, local = by_map(state, hops[k])
        same &= agree(outputs(nxt, local),
                      outputs(*by_sort(mapless, hops[k])))
        state = nxt

    # -- whole programs from a fresh state, as a sampler compiles them -----
    def chain(hops, map_until, upto):
        """Calls ``[:upto]``; those before ``map_until`` on the id map."""
        state = (U.dense_induce_init(num_nodes, cap) if map_until
                 else U.induce_init(num_nodes, cap, knowns[last]))
        out = []
        for k in calls:
            if k >= upto:
                break
            if k < map_until:
                state, local = U.dense_induce(state, hops[k])
            else:
                state, local = U._sorted_induce(state, hops[k], knowns[k])
            out.append(outputs(state, local))
        return out
    forms = {"before the last hop: map": (last, last),
             "before the last hop: sorted": (0, last),
             "whole chain: map, sorted last hop (parent)": (last, last + 1),
             "whole chain: sorted (no map)": (0, last + 1)}
    got = {}
    for label, (map_until, upto) in forms.items():
        fn = jax.jit(functools.partial(chain, map_until=map_until,
                                       upto=upto))
        rows[label] = round(timed(fn, (hops,), reps), 4)
        got[label] = fn(hops)
    labels = list(forms)
    same &= agree(got[labels[0]], got[labels[1]])
    same &= agree(got[labels[2]], got[labels[3]])
    return {"chain": name, "num_nodes": num_nodes, "capacity": cap,
            "widths": list(widths), "knowns": knowns,
            "same_as_map": bool(same), "ms": rows}


def print_table(results, key):
    labels = []
    for r in results:
        labels += [label for label in r["ms"] if label not in labels]
    print("same as the map form: " + ", ".join(
        f"{r[key]} {r['same_as_map']}" for r in results))
    print("| ms a call | " + " | ".join(r[key] for r in results) + " |")
    print("|---|" + "---|" * len(results))
    for label in labels:
        print(f"| {label} | " + " | ".join(
            str(r["ms"].get(label, "")) for r in results) + " |")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shapes", default="",
                    help="comma list of shape- and chain-name prefixes "
                         "(default all)")
    ap.add_argument("--only", choices=["last", "chains"], default=None,
                    help="the last hop's pieces, or the chains (default "
                         "both)")
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

    def wanted(name):
        return not want or any(name.startswith(w) for w in want)
    results, chains = [], []
    for shape in SHAPES if args.only != "chains" else ():
        if wanted(shape[0]):
            rng = np.random.default_rng(args.seed)
            results.append(bench_shape(*shape, args.reps, rng,
                                       args.whole_only))
            print(json.dumps(results[-1]), flush=True)
    for chain in CHAINS if args.only != "last" else ():
        if wanted(chain[0]):
            rng = np.random.default_rng(args.seed)
            chains.append(bench_chain(*chain, args.reps, rng))
            print(json.dumps(chains[-1]), flush=True)
    if results:
        print_table(results, "shape")
    if chains:
        print_table(chains, "chain")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "reps": args.reps, "results": results,
                   "chains": chains}, fh, indent=1)
    return 0 if all(r["same_as_map"] for r in results + chains) else 1


if __name__ == "__main__":
    sys.exit(main())
