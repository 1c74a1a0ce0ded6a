"""Distributed-loader throughput bench.

Mirrors the reference's ``benchmarks/api/bench_dist_neighbor_loader.py``
(:26-83): per-epoch loader wall time + batches/s + sampled edges/s for
the worker-mode ``DistNeighborLoader`` (mp sampling subprocesses feeding
the trainer over the shm ring) and, separately, the in-jit mesh sampler
(``DistNeighborSampler`` over the 8-virtual-device CPU mesh — the path
that runs over ICI on a real pod).

On this container both run on CPU, so the numbers are **code-path
characterisation** (pipeline overheads, serialization, ring throughput),
not TPU speed — the honest framing BASELINE.md uses for config 5.

Prints one JSON line per mode.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_bench_dataset(n=20000, deg=8, dim=64, seed=0):
    """Top-level so mp spawn workers can pickle + rebuild it."""
    from glt_tpu.data import Dataset

    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, n * deg)
    feat = rng.normal(size=(n, dim)).astype(np.float32)
    labels = (np.arange(n) % 16).astype(np.int32)
    return (Dataset()
            .init_graph(np.stack([src, dst]), graph_mode="HOST",
                        num_nodes=n)
            .init_node_features(feat)
            .init_node_labels(labels))


def bench_worker_mode(args):
    from glt_tpu.distributed import DistNeighborLoader, MpSamplingWorkerOptions

    loader = DistNeighborLoader(
        args.fanout, np.arange(args.num_seeds), batch_size=args.batch_size,
        dataset_builder=build_bench_dataset, builder_args=(),
        worker_options=MpSamplingWorkerOptions(
            num_workers=args.workers,
            channel_capacity_bytes=256 << 20),
        last_hop_dedup=args.last_hop_dedup)
    try:
        for _ in loader:        # warm epoch: worker startup + compiles
            pass
        t0 = time.perf_counter()
        batches = edges = 0
        for batch in loader:
            batches += 1
            edges += int(np.asarray(batch.edge_mask).sum())
        dt = time.perf_counter() - t0
    finally:
        loader.shutdown()
    print(json.dumps({
        "metric": "dist_loader_worker_mode_epoch",
        "value": round(dt, 3), "unit": "s",
        "batches_per_s": round(batches / dt, 2),
        "m_edges_per_s": round(edges / dt / 1e6, 3),
        "num_workers": args.workers, "batch_size": args.batch_size,
        "last_hop_dedup": args.last_hop_dedup,
        "note": "cpu code-path characterisation",
    }))


def exchange_bytes_per_shard(batch_size, fanouts, num_shards,
                             load_factor=None, frontier_cap=None):
    """Analytic per-shard per-batch all-to-all payload (bytes).

    Each hop moves one id leg ``[S, cap]`` out and two result legs
    ``[S, cap, fanout]`` (neighbors + edge ids) back, all int32.  With
    ``load_factor`` α the per-owner cap shrinks from the full frontier
    width to ``ceil(α*w/S)`` (dist_sampler.exchange_one_hop).
    """
    from glt_tpu.parallel.dist_sampler import bounded_remote_cap
    from glt_tpu.sampler.neighbor_sampler import hop_widths

    widths = hop_widths(batch_size, list(fanouts), frontier_cap)
    total = 0
    for w, f in zip(widths, fanouts):
        cap = (w if load_factor is None
               else bounded_remote_cap(w, load_factor, num_shards))
        total += num_shards * cap * 4 * (1 + 2 * f)
    return total


def bench_mesh_sampler(args):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from glt_tpu.parallel import DistNeighborSampler, shard_graph

    n_dev = min(8, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("shard",))
    ds = build_bench_dataset()
    sg = shard_graph(ds.get_graph().topo, n_dev)
    rng = np.random.default_rng(0)
    n = ds.get_graph().num_nodes
    # Shard-local seed batches (the split_seeds training layout): hop 0
    # is exchange-free under the bounded path.
    c = sg.nodes_per_shard
    seed_batches = [
        jnp.asarray(np.stack([
            rng.integers(s * c, min((s + 1) * c, n), args.batch_size)
            for s in range(n_dev)]).astype(np.int32))
        for _ in range(args.iters + 2)]
    acc = jax.jit(lambda tot, e: tot + e.sum())

    def run(alpha):
        samp = DistNeighborSampler(sg, mesh, num_neighbors=args.fanout,
                                   batch_size=args.batch_size,
                                   last_hop_dedup=args.last_hop_dedup,
                                   exchange_load_factor=alpha)
        tot = jnp.zeros((), jnp.int32)
        dropped = 0
        for i in range(2):
            tot = acc(tot, samp.sample_from_nodes(
                seed_batches[i]).num_sampled_edges)
        int(tot)
        tot = jnp.zeros((), jnp.int32)
        t0 = time.perf_counter()
        for i in range(args.iters):
            out = samp.sample_from_nodes(seed_batches[2 + i])
            tot = acc(tot, out.num_sampled_edges)
            if alpha is not None:
                dropped += int(np.asarray(
                    out.metadata["exchange_dropped"]).sum())
        edges = int(tot)
        dt = time.perf_counter() - t0
        return edges, dt, dropped

    edges, dt, _ = run(None)
    alpha = args.exchange_load_factor
    b_edges, b_dt, b_dropped = run(alpha)
    full_mb = exchange_bytes_per_shard(args.batch_size, args.fanout,
                                       n_dev) / 1e6
    bounded_mb = exchange_bytes_per_shard(args.batch_size, args.fanout,
                                          n_dev, alpha) / 1e6
    print(json.dumps({
        "metric": "dist_mesh_sampler_throughput",
        "value": round(edges / dt / 1e6, 3), "unit": "M sampled edges/s",
        "devices": n_dev, "batch_size": args.batch_size,
        "batches_per_s": round(args.iters * n_dev / dt, 2),
        "last_hop_dedup": args.last_hop_dedup,
        "bounded_m_edges_per_s": round(b_edges / b_dt / 1e6, 3),
        "bounded_batches_per_s": round(args.iters * n_dev / b_dt, 2),
        "exchange_load_factor": alpha,
        "exchange_mb_per_shard_batch_full": round(full_mb, 3),
        "exchange_mb_per_shard_batch_bounded": round(bounded_mb, 3),
        "exchange_reduction_x": round(full_mb / max(bounded_mb, 1e-9), 2),
        "bounded_dropped_requests": b_dropped,
        "bounded_sampled_edges_frac": round(b_edges / max(edges, 1), 4),
        "note": "virtual CPU mesh unless run on a pod",
    }))


def bench_hetero_mesh(args):
    """Hetero bounded-exchange + tiered-staging characterisation
    (VERDICT r4 #4 done-criterion): per-edge-type exchange bytes with and
    without ``exchange_load_factor``, plus the per-type cold-stage vs
    train split of the hetero tiered pipeline."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from glt_tpu.data.topology import CSRTopo
    from glt_tpu.models.rgat import RGAT
    from glt_tpu.parallel import (
        DistHeteroNeighborSampler,
        HeteroTieredTrainPipeline,
        init_hetero_dist_state,
        make_hetero_tiered_train_step,
        shard_feature,
        shard_feature_tiered,
        shard_hetero_graph,
    )

    n_dev = min(8, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("shard",))
    rng = np.random.default_rng(0)
    U, I, classes = 4096, 2048, 16
    labels = (np.arange(U) % classes).astype(np.int32)
    deg_ui = 6
    u_src = np.repeat(np.arange(U), deg_ui)
    i_dst = rng.integers(0, I, U * deg_ui)
    ET_UI = ("user", "clicks", "item")
    ET_IU = ("item", "rev_clicks", "user")
    topos = {ET_UI: CSRTopo(np.stack([u_src, i_dst]), num_nodes=U),
             ET_IU: CSRTopo(np.stack([i_dst, u_src]), num_nodes=I)}
    sharded = shard_hetero_graph(topos, n_dev)
    dim = 64
    item_feat = rng.normal(size=(I, dim)).astype(np.float32)
    user_feat = rng.normal(size=(U, dim)).astype(np.float32)
    lab = jnp.asarray(labels.reshape(n_dev, -1))
    bs = args.batch_size // 4 or 64
    cu = -(-U // n_dev)
    seed_batches = [
        jnp.asarray(np.stack([
            rng.integers(s * cu, min((s + 1) * cu, U), bs)
            for s in range(n_dev)]).astype(np.int32))
        for _ in range(args.iters + 2)]

    def run(alpha):
        samp = DistHeteroNeighborSampler(
            sharded, mesh, args.fanout, "user", batch_size=bs,
            exchange_load_factor=alpha, seed=0)

        def batch_edges(out):
            return sum(jnp.sum(m.astype(jnp.int32))
                       for m in out.edge_mask.values())

        # Warmup (compiles) — excluded from BOTH the timer and the
        # edge/drop counters, matching bench_mesh_sampler.
        tot = None
        for sd in seed_batches[:2]:
            e = batch_edges(samp.sample_from_nodes(sd))
            tot = e if tot is None else tot + e
        int(jax.device_get(tot))
        tot = None
        dropped_dev = None
        t0 = time.perf_counter()
        for sd in seed_batches[2:]:
            out = samp.sample_from_nodes(sd)
            e = batch_edges(out)
            tot = e if tot is None else tot + e
            if alpha is not None and out.metadata:
                # Accumulate ON DEVICE: a per-iteration device_get would
                # put a host round trip inside the timed loop that the
                # unbounded run never pays, biasing the comparison.
                d = jnp.sum(out.metadata["exchange_dropped"])
                dropped_dev = d if dropped_dev is None else dropped_dev + d
        edges = int(jax.device_get(tot))
        dt = time.perf_counter() - t0
        dropped = (0 if dropped_dev is None
                   else int(jax.device_get(dropped_dev)))
        return edges, dt, dropped

    edges, dt, _ = run(None)
    alpha = args.exchange_load_factor
    b_edges, b_dt, b_dropped = run(alpha)

    # Tiered pipeline: item features host-tiered, one timed epoch.
    feats = {"user": shard_feature(user_feat, n_dev),
             "item": shard_feature_tiered(item_feat, n_dev,
                                          hot_ratio=0.25)}
    samp = DistHeteroNeighborSampler(sharded, mesh, args.fanout, "user",
                                     batch_size=bs,
                                     exchange_load_factor=alpha, seed=0)
    model = RGAT(edge_types=[ET_IU, ET_UI], hidden_features=32,
                 out_features=classes, target_type="user", num_layers=2,
                 conv="gat", dropout_rate=0.0)
    tx = optax.adam(1e-3)
    state = init_hetero_dist_state(model, tx, samp, feats,
                                   jax.random.PRNGKey(0))
    train = make_hetero_tiered_train_step(model, tx, samp, feats, lab,
                                          mesh, batch_size=bs)
    pipe = HeteroTieredTrainPipeline(samp, train, feats, mesh)
    batches_np = [np.asarray(b) for b in seed_batches]
    state, losses, _ = pipe.run_epoch(state, batches_np[:2],
                                      jax.random.PRNGKey(1))  # warm
    float(jax.device_get(losses[-1]))
    t0 = time.perf_counter()
    state, losses, _ = pipe.run_epoch(state, batches_np,
                                      jax.random.PRNGKey(2))
    float(jax.device_get(losses[-1]))
    tiered_dt = time.perf_counter() - t0
    cold_drops = pipe.flush_dropped()
    max_cold = dict(pipe.max_cold_rows)
    pipe.close()

    print(json.dumps({
        "metric": "dist_hetero_mesh",
        "devices": n_dev, "batch_size": bs, "fanout": args.fanout,
        "m_edges_per_s_full": round(edges / dt / 1e6, 3),
        "m_edges_per_s_bounded": round(b_edges / b_dt / 1e6, 3),
        "exchange_load_factor": alpha,
        "bounded_dropped_requests": b_dropped,
        "bounded_sampled_edges_frac": round(b_edges / max(edges, 1), 4),
        "tiered_epoch_s": round(tiered_dt, 3),
        "tiered_ms_per_batch": round(
            tiered_dt / len(batches_np) * 1e3, 2),
        "tiered_cold_dropped": cold_drops,
        "tiered_max_cold_rows": max_cold,
        "note": "virtual CPU mesh unless run on a pod",
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--modes", nargs="+",
                    default=["worker", "mesh", "hetero"],
                    choices=["worker", "mesh", "hetero"])
    ap.add_argument("--fanout", type=int, nargs="+", default=[10, 5])
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--num-seeds", type=int, default=4096)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--iters", type=int, default=10)
    # Default True = the library's default exact semantics; pass
    # --no-last-hop-dedup to bench the leaf-block fast mode.
    ap.add_argument("--last-hop-dedup",
                    action=argparse.BooleanOptionalAction, default=True)
    ap.add_argument("--exchange-load-factor", type=float, default=2.0,
                    help="alpha for the capacity-bounded exchange "
                         "comparison in mesh mode")
    ap.add_argument("--platform", default="cpu",
                    help="'cpu' (default; 8 virtual devices for the mesh "
                         "mode) or '' for the machine's own platform")
    args = ap.parse_args()
    if args.platform:
        # Before the first jax import: JAX reads both at start-up.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ["JAX_PLATFORMS"] = args.platform
    if "worker" in args.modes:
        bench_worker_mode(args)
    if "mesh" in args.modes:
        bench_mesh_sampler(args)
    if "hetero" in args.modes:
        bench_hetero_mesh(args)


if __name__ == "__main__":
    main()
