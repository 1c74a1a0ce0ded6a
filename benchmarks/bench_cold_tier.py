"""Cold-tier (host-DRAM) feature staging cost at papers100M scale.

Config 5 lives or dies on this number (VERDICT r3 weak #3): each batch's
cold rows are gathered host-side (:class:`HostColdStore`) and fed to the
device while the previous batch trains
(:class:`~glt_tpu.parallel.dist_train.TieredTrainPipeline`).  This bench
measures, for a papers100M-shaped tier (111M rows x 128 f32 by default =
57GB host array, allocated lazily), over a hot-ratio sweep:

  * ``stage_ms``      — route (in-jit all_to_all) + host gather + feed,
                        the full cold stage for one batch;
  * ``train_ms``      — a stand-in train step (jitted matmul chain sized
                        via --train-flops);
  * ``serial_ms``     — stage then train, no overlap;
  * ``overlap_ms``    — steady-state step with the staging thread
                        overlapping the train step (the pipeline's
                        double-buffering), ideally max(stage, train);
  * ``added_ms``      — overlap_ms - train_ms: what the cold tier
                        actually costs per batch after overlap.

Run (CPU mesh; the host gather is the same code a pod host runs):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/bench_cold_tier.py --rows 16000000

A second section (``--store-rows > 0``, on by default) drops below the
host tier to the disk store (glt_tpu.store, docs/storage.md): a synthetic
feature file ~4x the configured DRAM budget is served through
``Feature.from_store`` (mmap reads + async DRAM stager, warmed by the
empirical access frequencies), a skewed epoch is timed against the
all-DRAM path, and the record carries the acceptance metrics —
``store_epoch_ms``, ``dram_hit_rate``, ``bytes_from_{hbm,dram,disk}``,
``disk_bytes_per_epoch``, ``budget_ok``, ``store_bit_identical``.

Two further sections (ISSUE 18, docs/refresh.md + docs/storage.md
"Compressed tiers"): ``--codec-rows > 0`` runs the per-codec gather A/B
(raw vs bf16 vs int8 HBM tables, ``gather_gb_s_effective_*`` = logical
f32 bytes/sec, speedup ratios vs raw), and ``--refresh-rows > 0`` runs
the layer-wise whole-graph refresh driver over a store >= 4x its DRAM
budget, raw and int8 side by side — ``refresh_nodes_per_s``,
``refresh_bytes_from_{hbm,dram,disk}``, ``refresh_stage_errors``,
``dram_hit_rate`` and the compressed/raw output parity.

Prints one JSON line per record (also written, one line each, atomically
to $GLT_BENCH_OUT).
"""
import argparse
import concurrent.futures
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=float, default=111_059_956,
                    help="total feature rows (papers100M = 111059956)")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--cap", type=int, default=16384,
                    help="sampled node-list width per shard per batch")
    ap.add_argument("--hot-ratios", type=float, nargs="+",
                    default=[0.5, 0.25, 0.1, 0.05])
    ap.add_argument("--stage-threads", type=int, nargs="+",
                    default=[1, 2, 4, 8],
                    help="gather-pool sizes for the thread-scaling curve "
                         "(VERDICT r4 #5); numpy fancy indexing releases "
                         "the GIL, so the curve tracks host cores")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--cold-alpha", type=float, default=2.0,
                    help="staging capacity factor: cold_cap = alpha * cap."
                         " The pipelines record max_cold_rows so a re-run"
                         " can right-size this (the host->device feed"
                         " scales with it)")
    ap.add_argument("--train-flops", type=float, default=2e9,
                    help="stand-in train step cost (flops)")
    ap.add_argument("--store-rows", type=int, default=65536,
                    help="disk-tier section: synthetic store rows "
                         "(0 skips the section)")
    ap.add_argument("--store-dim", type=int, default=64)
    ap.add_argument("--store-budget-frac", type=float, default=0.25,
                    help="DRAM budget as a fraction of the store's bytes"
                         " (0.25 = features are 4x the budget)")
    ap.add_argument("--store-hot-ratio", type=float, default=0.1,
                    help="HBM hot-prefix fraction of the store-backed "
                         "feature")
    ap.add_argument("--store-batches", type=int, default=64)
    ap.add_argument("--store-batch", type=int, default=512)
    ap.add_argument("--codec-rows", type=int, default=32768,
                    help="per-codec gather A/B section: HBM table rows "
                         "(0 skips the section)")
    ap.add_argument("--codec-dim", type=int, default=128)
    ap.add_argument("--codec-batch", type=int, default=8192)
    ap.add_argument("--codec-iters", type=int, default=16)
    ap.add_argument("--refresh-rows", type=int, default=16384,
                    help="whole-graph refresh section: graph nodes "
                         "(0 skips the section)")
    ap.add_argument("--refresh-dim", type=int, default=64)
    ap.add_argument("--refresh-degree", type=int, default=8)
    ap.add_argument("--refresh-layers", type=int, default=2)
    ap.add_argument("--refresh-block", type=int, default=512)
    ap.add_argument("--refresh-budget-frac", type=float, default=0.25,
                    help="refresh DRAM budget as a fraction of the input "
                         "store's bytes (0.25 = store is 4x the budget)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from glt_tpu.parallel import multihost
    from glt_tpu.parallel.dist_feature import (
        HostColdStore,
        TieredShardedFeature,
        compact_cold_requests,
        route_cold_requests,
    )

    S = args.devices
    devs = jax.devices()
    if len(devs) < S:
        raise SystemExit(f"need {S} devices, have {len(devs)} "
                         f"(set XLA_FLAGS/JAX_PLATFORMS)")
    mesh = Mesh(np.array(devs[:S]), ("shard",))
    n = int(args.rows)
    c = -(-n // S)
    d = args.dim
    rng = np.random.default_rng(0)

    # Stand-in train step: a chained matmul sized to --train-flops.
    m = max(128, int((args.train_flops / 4) ** (1 / 3)) // 128 * 128)
    reps = max(1, int(args.train_flops / (2 * m ** 3)))
    A = jnp.asarray(rng.normal(size=(m, m)).astype(np.float32))

    @jax.jit
    def train(x):
        for _ in range(reps):
            x = x @ A
        return x

    xt = jnp.asarray(rng.normal(size=(m, m)).astype(np.float32))
    train(xt).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(args.iters):
        xt = train(xt)
    float(np.asarray(xt).ravel()[0])   # host fetch = true sync
    train_ms = (time.perf_counter() - t0) / args.iters * 1e3

    gspec = P("shard")
    results = []
    for hr in args.hot_ratios:
        h = min(c, max(1, int(round(c * hr))))
        # Lazily-allocated zero pages: a 57GB tier costs only the pages
        # the gathers actually touch (mirrors an mmapped feature file).
        cold = np.zeros((S, c - h, d), np.float32)
        f = TieredShardedFeature(hot=jnp.zeros((1, 1, d)), cold=cold,
                                 nodes_per_shard=c, hot_per_shard=h,
                                 num_shards=S)
        store = HostColdStore(f)
        cold_cap = int(args.cold_alpha * args.cap)

        def route_body(nodes):
            req = route_cold_requests(nodes[0], c, h, S, "shard")
            slots, ids, dropped = compact_cold_requests(req, cold_cap)
            return slots[None], ids[None], dropped[None]

        route = jax.jit(jax.shard_map(
            route_body, mesh=mesh, in_specs=(gspec,),
            out_specs=(gspec, gspec, gspec), check_vma=False))

        def node_lists(k):
            # Uniform ids over the full (relabeled) space: cold fraction
            # == 1 - hot_ratio in expectation; -1 pad tail like a real
            # sampler output.
            ids = rng.integers(0, n, (S, args.cap)).astype(np.int32)
            ids[:, -args.cap // 8:] = -1
            return jax.device_put(
                jnp.asarray(ids), NamedSharding(mesh, gspec))

        dropped_total = 0
        # Mirror the pipeline's optimized staging: reused (unzeroed)
        # double buffers when device_put copies, row-chunk gather fanned
        # over a configurable thread pool (serve_into).
        from glt_tpu.parallel.dist_train import _ColdStagePipeline

        reuse = _ColdStagePipeline._device_put_copies()
        bufs = [np.empty((S, cold_cap, d), np.float32) for _ in range(2)]
        flip = [0]
        gather_pool = None

        def stage(nodes):
            nonlocal dropped_total
            slots, ids, dropped = route(nodes)
            req = np.asarray(ids)
            dropped_total += int(np.asarray(dropped).sum())
            if reuse:
                staged = bufs[flip[0]]
                flip[0] ^= 1
            else:
                staged = np.empty((S, cold_cap, d), np.float32)
            futs = []
            for s in range(S):
                futs += store.serve_into(staged[s], s, req[s],
                                         pool=gather_pool)
            for fu in futs:
                fu.result()
            rows = multihost.assemble_global(staged, mesh, "shard")
            jax.block_until_ready((rows, slots))
            return rows, slots

        batches = [node_lists(k) for k in range(args.iters + 2)]
        stage(batches[0])  # warm (compile + first-touch faults)

        # Thread-scaling curve: stage-only time per gather-pool size.
        stage_ms_by_threads = {}
        for nthreads in args.stage_threads:
            gather_pool = (concurrent.futures.ThreadPoolExecutor(
                max_workers=nthreads) if nthreads > 1 else None)
            stage(batches[0])  # warm pool
            t0 = time.perf_counter()
            for i in range(args.iters):
                stage(batches[i + 1])
            stage_ms_by_threads[nthreads] = round(
                (time.perf_counter() - t0) / args.iters * 1e3, 2)
            if gather_pool is not None:
                gather_pool.shutdown()
        best_threads = min(stage_ms_by_threads,
                           key=stage_ms_by_threads.get)
        gather_pool = (concurrent.futures.ThreadPoolExecutor(
            max_workers=best_threads) if best_threads > 1 else None)

        # Count drops over ONE pass only (the loops below re-stage the
        # same batches; accumulating across them would over-count).
        dropped_total = 0
        t0 = time.perf_counter()
        for i in range(args.iters):
            stage(batches[i + 1])
        stage_ms = (time.perf_counter() - t0) / args.iters * 1e3
        one_pass_dropped = dropped_total

        # Serial: stage then train, per batch.
        xt_l = xt
        t0 = time.perf_counter()
        for i in range(args.iters):
            stage(batches[i + 1])
            xt_l = train(xt_l)
        float(np.asarray(xt_l).ravel()[0])
        serial_ms = (time.perf_counter() - t0) / args.iters * 1e3

        # Overlapped: staging thread works on batch k+1 while the device
        # trains batch k (the TieredTrainPipeline schedule).
        pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        fut = pool.submit(stage, batches[0])
        xt_l = xt
        t0 = time.perf_counter()
        for i in range(args.iters):
            fut.result()
            fut = pool.submit(stage, batches[i + 1])
            xt_l = train(xt_l)
            float(np.asarray(xt_l).ravel()[0])  # sync inside the window
        overlap_ms = (time.perf_counter() - t0) / args.iters * 1e3
        fut.result()
        pool.shutdown()
        if gather_pool is not None:
            gather_pool.shutdown()

        cold_rows = int((np.asarray(batches[1]) >= 0).sum() * (1 - hr))
        rec = {
            "metric": "cold_tier_staging",
            "hot_ratio": hr,
            "cold_cap": cold_cap,
            "dropped_requests": one_pass_dropped,
            "rows_total": n,
            "dim": d,
            "cap_per_shard": args.cap,
            "est_cold_rows_per_batch": cold_rows,
            "stage_ms": round(stage_ms, 2),
            "stage_ms_by_threads": stage_ms_by_threads,
            "stage_threads_best": best_threads,
            "staged_buffer_reuse": reuse,
            "train_ms": round(train_ms, 2),
            "serial_ms": round(serial_ms, 2),
            "overlap_ms": round(overlap_ms, 2),
            "added_ms_vs_hot_only": round(overlap_ms - train_ms, 2),
            "overlap_efficiency": round(
                (stage_ms + train_ms) / max(overlap_ms, 1e-9), 3),
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)

    if args.store_rows > 0:
        rec = _bench_disk_store(args)
        results.append(rec)
        print(json.dumps(rec), flush=True)

    if args.codec_rows > 0:
        rec = _bench_codec_gather(args)
        results.append(rec)
        print(json.dumps(rec), flush=True)

    if args.refresh_rows > 0:
        rec = _bench_refresh(args)
        results.append(rec)
        print(json.dumps(rec), flush=True)

    bench_out = os.environ.get("GLT_BENCH_OUT")
    if bench_out:
        tmp = f"{bench_out}.tmp-{os.getpid()}"
        with open(tmp, "w") as fh:
            for rec in results:
                fh.write(json.dumps(rec) + "\n")
        os.replace(tmp, bench_out)


def _bench_disk_store(args):
    """Disk-tier epoch: store-backed Feature vs the all-DRAM path."""
    import jax.numpy as jnp

    from glt_tpu.data.feature import Feature
    from glt_tpu.store import DiskFeatureStore, write_feature_store

    n, d = args.store_rows, args.store_dim
    rng = np.random.default_rng(7)
    feats = rng.normal(size=(n, d)).astype(np.float32)
    budget = max(1, int(feats.nbytes * args.store_budget_frac))

    # Skewed epoch over a fixed permutation: zipf ranks concentrate
    # traffic on a minority of rows — the regime a frequency residency
    # policy exists for.  -1 pad tail like a real sampler output.
    perm = rng.permutation(n)
    ranks = rng.zipf(1.3, size=(args.store_batches, args.store_batch))
    ids = perm[(ranks - 1) % n].astype(np.int32)
    ids[:, -args.store_batch // 8:] = -1

    # Prefetch oracle: empirical access frequencies — what the partition
    # book's sample_prob statistics estimate ahead of the run
    # (glt_tpu.partition.residency_scores).
    flat = ids.ravel()
    scores = np.bincount(flat[flat >= 0], minlength=n).astype(np.float64)

    with tempfile.TemporaryDirectory() as td:
        write_feature_store(os.path.join(td, "store"), feats)
        store = DiskFeatureStore(os.path.join(td, "store"))
        f_disk = Feature.from_store(
            store, budget, split_ratio=args.store_hot_ratio,
            stage_threads=2, prefetch_scores=scores)
        f_dram = Feature(feats, split_ratio=args.store_hot_ratio)
        batches = [jnp.asarray(b) for b in ids]

        # Pass 1 (warm + correctness): the acceptance bar is
        # bit-identity with the all-DRAM tiered path, batch by batch.
        identical = True
        for b in batches:
            identical &= bool(np.array_equal(
                np.asarray(f_disk.gather(b)), np.asarray(f_dram.gather(b))))
        stats = f_disk.store_stats()
        budget_ok = stats["resident_bytes"] <= budget

        # Pass 2 (timed, stager warm): the steady-state epoch.
        f_disk._stager.epoch_stats()                  # reset epoch mark
        t0 = time.perf_counter()
        for b in batches:
            f_disk.gather(b).block_until_ready()
        store_epoch_ms = (time.perf_counter() - t0) * 1e3
        epoch = f_disk._stager.epoch_stats()

        t0 = time.perf_counter()
        for b in batches:
            f_dram.gather(b).block_until_ready()
        dram_epoch_ms = (time.perf_counter() - t0) * 1e3

        f_disk.close()
        rec = {
            "metric": "disk_store_epoch",
            "store_rows": n,
            "store_dim": d,
            "store_bytes": int(feats.nbytes),
            "store_budget_bytes": budget,
            "store_hot_ratio": args.store_hot_ratio,
            "epoch_batches": args.store_batches,
            "store_bit_identical": identical,
            "budget_ok": bool(budget_ok),
            "resident_bytes": int(stats["resident_bytes"]),
            "store_epoch_ms": round(store_epoch_ms, 2),
            "dram_epoch_ms": round(dram_epoch_ms, 2),
            "dram_hit_rate": round(epoch["hit_rate"], 4),
            "bytes_from_hbm": int(f_disk.bytes_from_hbm),
            "bytes_from_dram": int(epoch["bytes_from_dram"]),
            "bytes_from_disk": int(epoch["bytes_from_disk"]),
            "disk_bytes_per_epoch": int(epoch["bytes_from_disk"]),
            "stage_depth_max": int(epoch["stage_depth_max"]),
        }
    return rec


def _bench_codec_gather(args):
    """Per-codec gather A/B: effective (logical f32) bandwidth.

    The compressed tiers move 2x (bf16) / 4x (int8) fewer wire bytes
    per row and widen on-chip in the gather epilogue, so the honest
    comparison is LOGICAL bytes per second — the f32 payload the model
    consumes, whatever width crossed the bus.  The
    ``gather_effective_speedup_*`` ratios carry the >=2x int8
    aspiration (obs.regress); on the CPU backend they mostly price the
    dequant epilogue, on TPU they price the HBM transfer win.
    """
    import jax.numpy as jnp

    from glt_tpu.data.feature import Feature
    from glt_tpu.store import DiskFeatureStore, write_feature_store

    n, d = args.codec_rows, args.codec_dim
    rng = np.random.default_rng(11)
    feats = rng.normal(size=(n, d)).astype(np.float32)
    ids = jnp.asarray(rng.integers(0, n, args.codec_batch), jnp.int32)
    rec = {"metric": "codec_gather", "codec_rows": n, "codec_dim": d,
           "codec_batch": args.codec_batch}
    eff = {}
    with tempfile.TemporaryDirectory() as td:
        for codec in ("raw", "bf16", "int8"):
            root = os.path.join(td, codec)
            write_feature_store(root, feats, codec=codec)
            feat = Feature.from_store(DiskFeatureStore(root),
                                      dram_budget_bytes=1 << 20,
                                      split_ratio=1.0)
            feat.gather(ids).block_until_ready()          # compile + warm
            t0 = time.perf_counter()
            for _ in range(args.codec_iters):
                out = feat.gather(ids)
            out.block_until_ready()
            dt = time.perf_counter() - t0
            logical = args.codec_iters * int(ids.size) * d * 4
            eff[codec] = logical / dt / 1e9
            rec[f"gather_gb_s_effective_{codec}"] = round(eff[codec], 3)
            feat.close()
    rec["gather_effective_speedup_bf16"] = round(
        eff["bf16"] / max(eff["raw"], 1e-9), 3)
    rec["gather_effective_speedup_int8"] = round(
        eff["int8"] / max(eff["raw"], 1e-9), 3)
    return rec


def _bench_refresh(args):
    """Whole-graph refresh over a store >= 4x the DRAM budget.

    Runs the layer-wise driver twice — raw f32 input store and int8 —
    and records throughput, per-tier byte counts, staging health and
    the compressed/raw output parity (relative max error over the final
    embeddings).  The graph's neighbors are window-local, the layout a
    partition-sorted node ordering produces, so the block-ahead
    prefetch keeps the DRAM hit rate meaningful at any budget.
    """
    import jax
    import jax.numpy as jnp

    from glt_tpu.models.sage import GraphSAGE
    from glt_tpu.refresh import RefreshDriver, sage_refresh_layers
    from glt_tpu.store import DiskFeatureStore, write_feature_store

    n, d = args.refresh_rows, args.refresh_dim
    rng = np.random.default_rng(13)
    deg = rng.integers(1, args.refresh_degree + 1, n)
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(deg)
    window = max(4 * args.refresh_block, 64)
    offsets = rng.integers(-window, window, indptr[-1])
    owners = np.repeat(np.arange(n, dtype=np.int64), deg)
    indices = (owners + offsets) % n
    feats = rng.normal(size=(n, d)).astype(np.float32)
    budget = max(1, int(feats.nbytes * args.refresh_budget_frac))

    model = GraphSAGE(hidden_features=d, out_features=d // 2,
                      num_layers=args.refresh_layers, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, d)),
                        jnp.zeros((2, 1), jnp.int32),
                        jnp.ones((1,), bool))
    fns = sage_refresh_layers(model, params)

    def run(codec, td):
        root = os.path.join(td, f"in_{codec}")
        write_feature_store(root, feats, codec=codec)
        drv = RefreshDriver(
            indptr, indices, fns, DiskFeatureStore(root),
            os.path.join(td, f"out_{codec}"),
            block_size=args.refresh_block,
            max_degree=args.refresh_degree,
            dram_budget_bytes=budget, stage_threads=2)
        rep = drv.run()
        emb = DiskFeatureStore(rep["out_root"]).read_rows(
            np.arange(n, dtype=np.int64))
        return rep, emb

    with tempfile.TemporaryDirectory() as td:
        rep_raw, emb_raw = run("raw", td)
        rep_q, emb_q = run("int8", td)

    scale = max(float(np.abs(emb_raw).max()), 1e-9)
    rec = {
        "metric": "refresh",
        "refresh_rows": n,
        "refresh_dim": d,
        "refresh_layers": args.refresh_layers,
        "refresh_block": args.refresh_block,
        "refresh_budget_bytes": budget,
        "refresh_store_bytes": int(feats.nbytes),
        "refresh_nodes_per_s": round(rep_q["nodes_per_s"], 1),
        "refresh_nodes_per_s_raw": round(rep_raw["nodes_per_s"], 1),
        "refresh_bytes_from_hbm": int(rep_q["bytes_from_hbm"]),
        "refresh_bytes_from_dram": int(rep_q["bytes_from_dram"]),
        "refresh_bytes_from_disk": int(rep_q["bytes_from_disk"]),
        "refresh_stage_errors": int(rep_raw["stage_errors"]
                                    + rep_q["stage_errors"]),
        "dram_hit_rate": round(rep_q["dram_hit_rate"], 4),
        "refresh_parity_rel_err": round(
            float(np.abs(emb_q - emb_raw).max()) / scale, 5),
    }
    return rec


if __name__ == "__main__":
    main()
