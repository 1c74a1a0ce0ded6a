#!/usr/bin/env python3
"""The hop's neighbour read on the chip: whole, and by its live chunks.

    chiprun --timeout 1500 -- python scripts/hop_read_micro.py

Times ``ops/neighbor_sample.py::sample_neighbors`` (its XLA arm) at the
benchmark cells' own hop widths, over graphs of the cells' sizes (Lomax
degrees, degree-proportional frontier ids), as one jitted program a form:
``whole`` is the read the parent ran (``CHUNK_ROWS`` patched above the
width: one fusion over the static ``[w, f]`` block) and ``C=<rows>`` the
read that visits only the chunks of ``C`` frontier rows in which some row
holds an id (a ``fori_loop`` whose bound is traced; the module docstring
has the rule).  Every form is run at live shares 100 / 60 / 40 / 10 % of
the frontier (a prefix of ids, the tail ``-1``; the dist shape is four
such prefixes, as the served request matrix holds one a requester) and
held to ``whole`` bit for bit.  ``--cond`` adds the fallback the issue
named, the same chunks under ``lax.cond`` in a static loop, at one ``C``.

It is the go / no-go of the chunk rule (PERF.md §6, PR 35): **go if at
100 % live a chunked form costs within 3 % of the whole one and at 40 %
live at most 50 % of it.**  No benchmark cell runs this script.  Times
are host clock over ``--reps`` back-to-back calls ended by one
``block_until_ready``; a program under 0.2 ms reads about 0.2 ms, the
host's dispatch.  Prints the table, writes
``chiprun_out/hop_read_micro.json``, refuses to time anything but a TPU
and exits 1 if a chunked form disagrees with the whole one.

**Found** (my chip run, PR 35: TPU v5 lite, jax 0.9.0, jaxlib 0.9.0, libtpu
0.0.34; 20 calls a time; ms a call, then ns a slot for ``whole`` and the
share of ``whole`` for every other form; every form bit-identical to
``whole`` at every shape and share)::

  shape (w x f)                 live         whole        C=1024        C=2560        C=5120       C=10240     cond 5120
  products hop3 153,600 x 5     100%   13.51  17.6   16.05  1.19   13.26  0.98   14.63  1.08   14.59  1.08   16.11  1.19
  products hop3 153,600 x 5      60%   14.40  18.7    9.95  0.69    8.28  0.58    9.11  0.63    9.08  0.63    9.94  0.69
  products hop3 153,600 x 5      40%   14.83  19.3    6.91  0.47    5.79  0.39    6.33  0.43    6.32  0.43    6.85  0.46
  products hop3 153,600 x 5      10%   15.50  20.2    2.34  0.15    2.06  0.13    2.18  0.14    2.80  0.18    2.22  0.14
  products hop2 15,360 x 10     100%    2.82  18.3    2.98  1.06    2.83  1.00    2.85  1.01    3.71  1.32    2.85  1.01
  products hop2 15,360 x 10      60%    2.84  18.5    1.88  0.66    1.99  0.70    2.00  0.71    2.00  0.70    2.00  0.70
  products hop2 15,360 x 10      40%    2.89  18.8    1.33  0.46    1.57  0.54    2.10  0.73    2.10  0.73    2.09  0.72
  products hop2 15,360 x 10      10%    2.97  19.3    0.62  0.21    0.69  0.23    1.21  0.41    2.25  0.76    1.20  0.40
  chip1 hop3 153,600 x 5        100%   13.63  17.7   16.19  1.19   14.05  1.03   15.65  1.15   14.76  1.08   17.15  1.26
  chip1 hop3 153,600 x 5         60%   14.39  18.7   10.04  0.70    8.75  0.61    9.72  0.67    9.19  0.64   10.56  0.73
  chip1 hop3 153,600 x 5         40%   14.79  19.3    6.97  0.47    6.10  0.41    6.74  0.46    6.39  0.43    7.26  0.49
  chip1 hop3 153,600 x 5         10%   15.37  20.0    2.34  0.15    2.13  0.14    2.29  0.15    2.80  0.18    2.31  0.15
  chip1 hop2 15,360 x 10        100%    2.79  18.1    3.04  1.09    2.96  1.06    2.99  1.07    3.79  1.36    2.99  1.07
  chip1 hop2 15,360 x 10         60%    2.86  18.6    1.91  0.67    2.08  0.73    2.11  0.74    2.03  0.71    2.10  0.73
  chip1 hop2 15,360 x 10         40%    2.90  18.9    1.35  0.47    1.65  0.57    2.24  0.77    2.11  0.73    2.23  0.77
  chip1 hop2 15,360 x 10         10%    2.95  19.2    0.62  0.21    0.72  0.24    1.29  0.44    2.24  0.76    1.27  0.43
  link hop3 614,400 x 5         100%   55.92  18.2   57.81  1.03   50.29  0.90   55.64  0.99   55.54  0.99  121.18  2.17
  link hop3 614,400 x 5          60%   60.82  19.8   36.06  0.59   31.55  0.52   34.76  0.57   34.69  0.57   73.68  1.21
  link hop3 614,400 x 5          40%   63.28  20.6   25.18  0.40   22.17  0.35   24.31  0.38   24.27  0.38   49.92  0.79
  link hop3 614,400 x 5          10%   66.95  21.8    8.85  0.13    8.09  0.12    8.63  0.13    8.63  0.13   14.28  0.21
  dist hop3 served 614,400 x 5  100%  103.32  33.6  108.80  1.05   94.74  0.92  106.34  1.03  102.89  1.00  172.05  1.67
  dist hop3 served 614,400 x 5   60%  139.53  45.4   67.00  0.48   58.58  0.42   65.53  0.47   63.46  0.45  104.32  0.75
  dist hop3 served 614,400 x 5   40%  157.64  51.3   46.12  0.29   40.51  0.26   45.13  0.29   43.76  0.28   70.45  0.45
  dist hop3 served 614,400 x 5   10%  184.81  60.2   14.80  0.08   13.40  0.07   14.55  0.08   20.49  0.11   19.67  0.11

**Go, with ``CHUNK_ROWS`` = 2,560.**  At the widest reads (hop 3: 153,600
rows, the link cell's and the dist served matrix's 614,400) the loop at
2,560 costs 0.90-1.03 of the whole read at 100 % live and 0.26-0.41 of it
at 40 %: both marks met.  Hop 2 (15,360 rows, 2.8 ms) misses both by the
chunk's granularity, six chunks: 1.00 / 1.06 at 100 % and 0.54 / 0.57 at 40
% (three of six chunks run); 1,024 rows would meet 0.46 there and pay 19 %
at hop 3, where the time is.  5,120 and 10,240 cost 8-15 % at a full
hop-3 frontier (1,024: 19 %, 150 trips); 2,560 is the one size within 3 %
of the whole read at every hop-3 shape.  **Which form won and why**: the
``fori_loop`` with a traced bound keeps the gather's per-slot cost (17-19
ns a slot whole, 16.4-18.3 inside the loop at 2,560); the ``lax.cond``
form in a static loop is never faster and at 120 chunks twice as slow
(the conditionals' operands are copied in and out of every branch).
**What the table says besides**: a dead row costs MORE than a live one
(the whole read slows from 13.5 to 15.5 ms as the live share falls from
100 to 10 %; with the edge-id table read too, from 103 to 185 ms): every
padding slot reads ``indices[0]``, one address, and the reads of one
address serialise.  That is why the dist step paid 348 ns a live edge
(ledger, PR 34) and why skipping the dead chunks gains more than their
share of the rows.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (graph, nodes, edges): the cells' own sizes.  A shard of `dist-train`
# holds what `chip1` holds: a sixteenth of papers100M.
GRAPHS = {
    "products": (2_449_029, 123_718_280),
    "papers-share": (6_941_247, 100_980_367),
}
# (name, graph, frontier rows w, fanout f, prefixes, edge ids read too)
SHAPES = [
    ("products hop3", "products", 153_600, 5, 1, False),
    ("products hop2", "products", 15_360, 10, 1, False),
    ("chip1 hop3", "papers-share", 153_600, 5, 1, False),
    ("chip1 hop2", "papers-share", 15_360, 10, 1, False),
    ("link hop3", "products", 614_400, 5, 1, False),
    ("dist hop3 served", "papers-share", 614_400, 5, 4, True),
]
CHUNKS = (1_024, 2_560, 5_120, 10_240)
LIVE = (1.0, 0.6, 0.4, 0.1)


def build_graph(nodes, edges, seed):
    """``indptr`` (Lomax degrees, exponent 1.8, summing to ``edges``) on
    the host, neighbour ids and a second table of the same length (read as
    edge ids are) drawn on the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    deg = rng.pareto(1.8, nodes) + 1e-3
    deg = np.floor(deg * (edges / deg.sum())).astype(np.int64)
    short = edges - int(deg.sum())
    deg[rng.integers(0, nodes, short)] += 1     # ties land twice: fix below
    deg[0] += edges - int(deg.sum())
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    assert indptr[-1] == edges
    draw = jax.jit(lambda k: jax.random.randint(
        k, (edges,), 0, nodes, dtype=jnp.int32))
    k1, k2 = jax.random.split(jax.random.key(seed))
    return indptr, (jnp.asarray(indptr), draw(k1), draw(k2))


def frontier(indptr_host, w, prefixes, share, seed):
    """``prefixes`` runs of ``w / prefixes`` rows, each a prefix of ids
    (sources of uniformly drawn edges: in proportion to degree, as a
    sampled frontier is) then ``-1``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    run = w // prefixes
    live = int(round(share * run))
    out = np.full((prefixes, run), -1, np.int32)
    pos = rng.integers(0, indptr_host[-1], (prefixes, live))
    out[:, :live] = np.searchsorted(indptr_host, pos, side="right") - 1
    return out.reshape(-1)


def cond_form(ns, chunk):
    """The fallback: every chunk of ``chunk`` rows in a static loop, its
    reads under ``lax.cond`` on whether the chunk holds an id.  The draw
    stays whole, as in the shipped form."""
    import jax.numpy as jnp
    from jax import lax

    def sample(indptr, indices, seeds, fanout, key, edge_ids=None):
        w = seeds.shape[0]
        tables = (indices,) if edge_ids is None else (indices, edge_ids)

        def rows(s):
            return ns._row_offsets_and_degrees(indptr, s)

        def no_rows(s):
            return (jnp.zeros(s.shape, indptr.dtype),
                    jnp.zeros(s.shape, jnp.int32))

        parts = [seeds[c:c + chunk] for c in range(0, w, chunk)]
        alive = [jnp.any(p >= 0) for p in parts]
        sd = [lax.cond(a, rows, no_rows, p) for a, p in zip(alive, parts)]
        start = jnp.concatenate([s for s, _ in sd])
        deg = jnp.concatenate([d for _, d in sd])
        pos, mask = ns.draw_positions(deg, fanout, key, False, seeds)
        flat = start[:, None] + jnp.where(mask, pos, 0)

        def reads(at):
            return tuple(t[at] for t in tables)

        def no_reads(at):
            return tuple(jnp.full(at.shape, -1, t.dtype) for t in tables)

        got = [lax.cond(a, reads, no_reads, flat[c:c + chunk])
               for a, c in zip(alive, range(0, w, chunk))]
        out = [jnp.where(mask, jnp.concatenate([g[k] for g in got]), -1)
               for k in range(len(tables))]
        return out + [mask]

    return sample


def timed(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--shapes", default="",
                    help="comma-separated prefixes of shape names")
    ap.add_argument("--cond", type=int, default=0,
                    help="also time the lax.cond form at this chunk")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="graphs and widths a hundredth the size, on any "
                         "backend: the control flow, never a time")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from glt_tpu.ops import neighbor_sample as ns
    from glt_tpu.utils import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"refusing to time {dev.platform}: this is a chip "
              f"measurement", file=sys.stderr)
        return 1
    enable_compile_cache()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except Exception:           # noqa: BLE001 - a version, not a result
        libtpu_version = None
    import jaxlib
    meta = {"rehearsal": args.rehearse, "device_kind": dev.device_kind,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
            "reps": args.reps}
    print(json.dumps(meta))

    wanted = [p for p in args.shapes.split(",") if p]
    shapes = [s for s in SHAPES
              if not wanted or any(s[0].startswith(p) for p in wanted)]
    cut = 100 if args.rehearse else 1
    key = jax.random.key(7)
    records, bad = [], 0
    graphs = {}
    for name, gname, w, f, prefixes, with_eids in shapes:
        w //= cut
        if gname not in graphs:
            graphs.clear()                       # one graph in HBM at a time
            graphs[gname] = build_graph(*(n // cut for n in GRAPHS[gname]),
                                        seed=args.seed)
        indptr_host, graph = graphs[gname]

        # The graph rides as arguments: closed over, it would be compiled
        # into every program as a constant.
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in graph]
        specs.append(jax.ShapeDtypeStruct((w,), jnp.int32))

        def form(chunk):
            ns.CHUNK_ROWS = chunk                # read as the form is traced

            def run(indptr, indices, eids, seeds):
                out = ns.sample_neighbors(
                    indptr, indices, seeds, f, key,
                    edge_ids=eids if with_eids else None,
                    with_edge=with_eids, force="xla")
                return [a for a in out if a is not None]

            return jax.jit(run).lower(*specs).compile()

        forms = [("whole", form(w))]
        forms += [(f"C={c}", form(c)) for c in
                  (c // cut for c in CHUNKS) if c < w]
        if args.cond and args.cond // cut < w:
            cf = cond_form(ns, args.cond // cut)
            forms.append((f"cond C={args.cond}", jax.jit(
                lambda indptr, indices, eids, seeds: cf(
                    indptr, indices, seeds, f, key,
                    eids if with_eids else None)
            ).lower(*specs).compile()))
        for share in LIVE:
            seeds = jnp.asarray(frontier(indptr_host, w, prefixes, share,
                                         args.seed + 1))
            ref, whole_ms = None, None
            for label, fn in forms:
                ms = timed(fn, (*graph, seeds), args.reps)
                out = [np.asarray(a) for a in fn(*graph, seeds)]
                if ref is None:
                    ref, whole_ms = out, ms
                same = all(np.array_equal(a, b) for a, b in zip(ref, out))
                bad += not same
                rec = {"shape": name, "w": w, "f": f, "prefixes": prefixes,
                       "edge_ids": with_eids, "live_share": share,
                       "form": label, "ms": ms,
                       "ns_per_slot": ms * 1e6 / (w * f),
                       "of_whole": ms / whole_ms, "same_as_whole": same}
                records.append(rec)
                print(f"{name:18s} w={w:7d} f={f:2d} live={share:4.0%} "
                      f"{label:12s} {ms:8.3f} ms {rec['ns_per_slot']:6.2f} "
                      f"ns/slot {rec['of_whole']:5.2f} of whole "
                      f"{'same' if same else 'DIFFERENT'}", flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "hop_read_micro.json"), "w") as fh:
        json.dump({"meta": meta, "records": records}, fh, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
