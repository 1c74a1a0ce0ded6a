#!/usr/bin/env python3
"""The dist step's served feature and label read on the chip: whole, and
by its live chunks.

    python scripts/gather_read_micro.py        # on a host with a TPU

Times ``parallel/dist_feature.py::_request_rows`` at the shape one shard
of the ``sage-papers100m-dist4.dist-train`` cell serves: a table of one
shard's rows (6,941,248 x f32[128]) and its int32 labels, read at the
``[S * b]`` = 4 x 937,984 request slots the exact flat exchange lands on
it, each requester's bucket a prefix of distinct live ids then padding.
``whole`` is the read the parent ran (``CHUNK_ROWS`` patched above the
width: one take a table over every slot) and ``C=<rows>`` the read that
visits only the chunks of ``C`` slots in which some slot holds a request
(``neighbor_sample``'s chunk rule, a ``fori_loop`` whose bound is
traced).  Every form runs at live shares 9.9 % (what the cell's shards
serve), 40 % and 100 % of each bucket and is held to ``whole`` bit for
bit.

It is the go / no-go of the chunked served read (PERF.md §6):
**go if at 9.9 % live the read at 2,560 costs at most a third of the
whole one.**  No benchmark cell runs this script.  Times are host clock
over ``--reps`` back-to-back calls ended by one ``block_until_ready``.
Prints the table, writes ``chiprun_out/gather_read_micro.json``, refuses
to time anything but a TPU (``--rehearse``: a hundredth of the sizes on
any backend, no time stated) and exits 1 if a form disagrees with
``whole``.

**Found** (TPU v5 lite, jax 0.9.0, jaxlib 0.9.0, libtpu 0.0.34; 20 calls
a time; ms a call, then the share of ``whole``; every form bit-identical
to ``whole`` at every share)::

  live      whole           C=1280          C=2560          C=5120
  9.9 %     95.25  1.000    11.27  0.118    10.85  0.114    10.53  0.111
  40 %      86.86  1.000    35.50  0.409    33.25  0.383    31.44  0.362
  100 %     69.97  1.000    83.50  1.193    77.68  1.110    72.92  1.042

**Go, with ``CHUNK_ROWS`` = 2,560**: at the cell's 9.9 % the chunked
read costs 0.114 of the whole one.  A dead slot costs more than a live
one here too (the whole read takes 70 ms with every slot live, 95 ms at
9.9 %: every dead slot reads row 0).  5,120 would save 0.3 ms of this
read and cost the hop reads 8-15 % (``hop_read_micro.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROWS = 6_941_248            # ceil(27,764,989 / 4): one shard's table
DIM = 128
BUCKET = 937_984            # the node buffer: one requester's bucket
SHARDS = 4
CHUNKS = (1_280, 2_560, 5_120)
LIVE = (0.099, 0.4, 1.0)


def requests(rows, share, seed, cut):
    """``[SHARDS * bucket]`` local ids: each bucket a prefix of
    ``share`` of it, distinct ids of the table, then ``-1``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    bucket = BUCKET // cut
    live = int(round(share * bucket))
    out = np.full((SHARDS, bucket), -1, np.int32)
    for p in range(SHARDS):
        out[p, :live] = rng.choice(rows, live, replace=False)
    return out.reshape(-1)


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
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="a hundredth of the sizes, on any backend: the "
                         "control flow, never a time")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from glt_tpu.ops import neighbor_sample as ns
    from glt_tpu.parallel.dist_feature import _request_rows
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
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version, "reps": args.reps}
    print(json.dumps(meta))

    cut = 100 if args.rehearse else 1
    n_rows, width = ROWS // cut, SHARDS * (BUCKET // cut)
    k1, k2 = jax.random.split(jax.random.key(args.seed))
    table = jax.jit(lambda k: jax.random.normal(k, (n_rows, DIM)))(k1)
    labels = jax.jit(lambda k: jax.random.randint(
        k, (n_rows,), 0, 172, dtype=jnp.int32))(k2)

    def form(chunk):
        ns.CHUNK_ROWS = chunk                    # read as the form is traced

        def run(rows, labs, local):
            ok = local >= 0
            return _request_rows(local, [(rows, ok, "glt.gather.feat"),
                                         (labs, ok, "glt.gather.label")])[0]

        specs = [jax.ShapeDtypeStruct(table.shape, table.dtype),
                 jax.ShapeDtypeStruct(labels.shape, labels.dtype),
                 jax.ShapeDtypeStruct((width,), jnp.int32)]
        return jax.jit(run).lower(*specs).compile()

    forms = [("whole", form(width + 1))]
    forms += [(f"C={c}", form(c // cut)) for c in CHUNKS]
    records, bad = [], 0
    for share in LIVE:
        local = jnp.asarray(requests(n_rows, share, args.seed + 1, cut))
        ref, whole_ms = None, None
        for label, fn in forms:
            ms = timed(fn, (table, labels, local), args.reps)
            out = [np.asarray(a) for a in fn(table, labels, local)]
            if ref is None:
                ref, whole_ms = out, ms
            same = all(a.tobytes() == b.tobytes() for a, b in zip(ref, out))
            bad += not same
            rec = {"live_share": share, "form": label, "ms": ms,
                   "ns_per_slot": ms * 1e6 / width, "of_whole": ms / whole_ms,
                   "same_as_whole": same}
            records.append(rec)
            print(f"live={share:5.1%} {label:14s} {ms:9.3f} ms "
                  f"{rec['ns_per_slot']:6.2f} ns/slot {rec['of_whole']:5.3f} "
                  f"of whole {'same' if same else 'DIFFERENT'}", flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "gather_read_micro.json")
    with open(path + ".tmp", "w") as fh:
        json.dump({"meta": meta, "records": records}, fh, indent=1)
    os.replace(path + ".tmp", path)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
