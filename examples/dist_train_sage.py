"""Distributed GraphSAGE over a device mesh — the papers100M-style config.

TPU rebuild of the reference's examples/distributed/dist_train_sage_supervised.py:
instead of per-machine partitions + RPC sampling workers + DDP, the graph
and features are sharded across a jax Mesh and the whole iteration
(all-to-all sampling, feature gather, fwd/bwd, grad pmean) is one jitted
program (glt_tpu.parallel.dist_train).

On a single-chip dev box run with virtual devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/dist_train_sage.py --devices 8 --scale 0.002
"""
import argparse
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import numpy as np


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--scale", type=float, default=0.002)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--fanout", type=int, nargs="+", default=[10, 5])
    ap.add_argument("--frontier-cap", type=int, default=1024)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from examples.datasets import require_devices, synthetic_products
    from glt_tpu.models import GraphSAGE
    from glt_tpu.parallel import (
        init_dist_state,
        make_dist_train_step,
        put_sharded,
        shard_feature,
        shard_graph,
    )

    mesh = Mesh(np.array(require_devices(args.devices)), ("shard",))

    ds, train_idx = synthetic_products(scale=args.scale, graph_mode="HOST")
    topo = ds.get_graph().topo
    feat = ds.get_node_feature()._host_full
    labels = np.asarray(ds.get_node_label())

    # Placed on the mesh once: a step fed default-device arrays
    # re-shards the whole graph and feature table on every call.
    g = put_sharded(shard_graph(topo, args.devices), mesh, "shard")
    f = put_sharded(shard_feature(feat, args.devices), mesh, "shard")
    pad = args.devices * g.nodes_per_shard - labels.shape[0]
    lab = put_sharded(np.pad(labels, (0, pad), constant_values=-1)
                      .reshape(args.devices, g.nodes_per_shard),
                      mesh, "shard")

    model = GraphSAGE(hidden_features=128, out_features=47,
                      num_layers=len(args.fanout), dropout_rate=0.0)
    tx = optax.adam(1e-3)
    state = init_dist_state(model, tx, g, f, jax.random.PRNGKey(0),
                            args.fanout, args.batch_size)
    step = make_dist_train_step(model, tx, g, f, lab, mesh, args.fanout,
                                args.batch_size,
                                frontier_cap=args.frontier_cap)

    # per-shard disjoint seed split (dist_train_sage_supervised.py:76)
    rng = np.random.default_rng(0)
    per_shard = [train_idx[train_idx // g.nodes_per_shard == s]
                 for s in range(args.devices)]
    steps_per_epoch = min(max(1, len(p) // args.batch_size)
                          for p in per_shard)

    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        losses = []
        for it in range(steps_per_epoch):
            seeds = np.stack([
                rng.choice(p, args.batch_size,
                           replace=len(p) < args.batch_size)
                for p in per_shard]).astype(np.int32)
            state, loss, acc = step(state, jnp.asarray(seeds),
                                    jax.random.PRNGKey(epoch * 1000 + it))
            losses.append(loss)
        jax.block_until_ready(losses[-1])
        dt = time.perf_counter() - t0
        print(f"epoch {epoch}: loss={float(np.mean(jax.device_get(losses))):.4f} "
              f"time={dt:.2f}s "
              f"subgraphs/s={steps_per_epoch * args.devices / dt:.1f}")


if __name__ == "__main__":
    main()
