"""Supervised GraphSAGE trained through the server-client deployment.

TPU rebuild of the reference's
``examples/distributed/dist_train_sage_supervised_with_server.py``: the
sampling fleet runs on dedicated *server* processes (which own the graph
+ features and stream sampled batches over sockets); *client* trainer
processes hold only the model and consume ``RemoteNeighborLoader``.  The
reference separates the roles so graph storage and sampling CPUs scale
independently of the training accelerators — identical motivation here:
the TPU host keeps its chip on the train step while sampling servers run
anywhere.

Demo topology (single machine): N_SERVERS server processes x 1 trainer
client per server, spawned with multiprocessing.

    python examples/dist_train_sage_with_server.py --servers 2 --epochs 3
"""
import argparse
import multiprocessing as mp
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import numpy as np


def build_dataset(scale: float = 0.02):
    from examples.datasets import synthetic_products

    ds, _ = synthetic_products(scale=scale, graph_mode="HOST")
    return ds


def server_proc(scale, conn):
    """Server role: owns the dataset, streams sampled batches."""
    from glt_tpu.distributed.dist_server import init_server

    srv = init_server(build_dataset(scale), dataset_builder=build_dataset,
                      builder_args=(scale,))
    conn.send(srv.addr)
    conn.recv()           # blocks until the trainer says shutdown
    srv.shutdown()


def trainer_proc(rank, world, addr, scale, epochs, batch_size,
                 num_workers=0):
    """Client role: remote loader + jitted train step, no local graph."""
    import jax
    import optax

    from examples.datasets import synthetic_products
    from glt_tpu.distributed import RemoteSamplingWorkerOptions
    from glt_tpu.distributed.dist_client import RemoteNeighborLoader
    from glt_tpu.distributed.dist_context import init_client_context
    from glt_tpu.models import (GraphSAGE, create_train_state,
                                make_train_step)

    init_client_context(num_clients=world, client_rank=rank,
                        num_servers=world)
    # Per-rank disjoint seed split (the reference splits train_idx across
    # trainer ranks, dist_train_sage_supervised.py:76).
    _, train_idx = synthetic_products(scale=scale, graph_mode="HOST")
    classes = 47  # ogbn-products label space
    seeds = train_idx[rank::world]
    # num_workers=0 keeps the demo to one sampling thread per server —
    # right-sized for a small host; raise it on real server machines.
    loader = RemoteNeighborLoader(
        addr, [15, 10, 5], seeds, batch_size=batch_size,
        worker_options=RemoteSamplingWorkerOptions(
            num_workers=num_workers, buffer_capacity=8, prefetch_size=4,
            channel_capacity_bytes=64 << 20))
    try:
        model = GraphSAGE(hidden_features=128, out_features=classes)
        first = next(iter(loader))
        tx = optax.adam(1e-3)
        state = create_train_state(model, jax.random.PRNGKey(0), first, tx)
        step = make_train_step(model, tx, batch_size=batch_size)
        for epoch in range(epochs):
            t0 = time.time()
            tot_l = tot_a = nb = 0
            for batch in loader:
                state, loss, acc = step(state, batch)
                tot_l += float(loss); tot_a += float(acc); nb += 1
            print(f"[client {rank}] epoch {epoch}: loss {tot_l/nb:.4f} "
                  f"acc {tot_a/nb:.4f} ({time.time()-t0:.2f}s)")
    finally:
        loader.shutdown()


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--workers", type=int, default=0,
                    help="mp sampling workers per server producer")
    args = ap.parse_args()

    ctx = mp.get_context("spawn")
    servers, pipes = [], []
    for _ in range(args.servers):
        parent, child = ctx.Pipe()
        p = ctx.Process(target=server_proc, args=(args.scale, child))
        p.start()
        servers.append(p)
        pipes.append(parent)
    addrs = [pipe.recv() for pipe in pipes]
    print(f"servers up at {addrs}")

    trainers = [ctx.Process(target=trainer_proc,
                            args=(r, args.servers, addrs[r], args.scale,
                                  args.epochs, args.batch_size,
                                  args.workers))
                for r in range(args.servers)]
    for t in trainers:
        t.start()
    for t in trainers:
        t.join()
    for pipe in pipes:
        pipe.send("shutdown")
    for p in servers:
        p.join(timeout=15)
        if p.is_alive():
            p.terminate()
    print("done")


if __name__ == "__main__":
    main()
