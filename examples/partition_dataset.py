"""Offline dataset partitioning CLI.

TPU rebuild of the reference's
``examples/distributed/partition_ogbn_dataset.py``: partition a graph +
features into the on-disk layout ``DistDataset.load`` consumes
(``META.json`` + ``node_pb``/``edge_pb`` + ``part{i}/graph|node_feat``),
with either uniform random assignment or the hotness-aware frequency
partitioner (per-trainer access probabilities from
``NeighborSampler.sample_prob``, the ``CalNbrProb`` pipeline).

    python examples/partition_dataset.py --out /tmp/parts --num-parts 4
    python examples/partition_dataset.py --out /tmp/parts --num-parts 4 \\
        --partitioner frequency --cache-ratio 0.1
"""
import argparse
import sys

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import numpy as np


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--num-parts", type=int, default=4)
    ap.add_argument("--scale", type=float, default=0.01,
                    help="synthetic ogbn-products scale (real data loads "
                         "from disk when present; see examples/datasets.py)")
    ap.add_argument("--partitioner", choices=["random", "frequency"],
                    default="random")
    ap.add_argument("--fanout", type=int, nargs="+", default=[15, 10, 5])
    ap.add_argument("--cache-ratio", type=float, default=0.1,
                    help="hot-cache fraction per partition (frequency)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="greedy-assignment granularity; 0 = adaptive "
                         "(>=20 chunks per partition)")
    args = ap.parse_args()

    from examples.datasets import synthetic_products
    from glt_tpu.partition import FrequencyPartitioner, RandomPartitioner

    ds, train_idx = synthetic_products(scale=args.scale, graph_mode="HOST")
    topo = ds.get_graph().topo
    n = topo.num_nodes
    feat = np.asarray(ds.node_features._host_full)
    edge_index = np.stack(topo.to_coo())
    chunk = args.chunk_size or min(10000, max(n // (20 * args.num_parts), 1))
    print(f"partitioning {n} nodes / {topo.num_edges} edges "
          f"into {args.num_parts} parts ({args.partitioner})")

    if args.partitioner == "random":
        part = RandomPartitioner(args.out, args.num_parts, n, edge_index,
                                 node_feat=feat,
                                 chunk_size=chunk)
    else:
        # Per-trainer hotness: each rank's seed slice drives sample_prob
        # (cf. partition_ogbn_dataset.py + neighbor_sampler.py:435-562).
        from glt_tpu.sampler import NeighborSampler

        sampler = NeighborSampler(ds.get_graph(), args.fanout,
                                  batch_size=1024)
        probs = [
            np.asarray(sampler.sample_prob(
                train_idx[r::args.num_parts], n))
            for r in range(args.num_parts)]
        part = FrequencyPartitioner(args.out, args.num_parts, n, edge_index,
                                    probs=probs, node_feat=feat,
                                    cache_ratio=args.cache_ratio,
                                    chunk_size=chunk)
    part.partition()
    print(f"wrote partition layout to {args.out}")

    from glt_tpu.partition import load_partition
    graph, node_feat, _, node_pb, edge_pb, meta = load_partition(args.out, 0)
    print(f"verified part0: {node_feat.ids.shape[0]} owned feature rows, "
          f"{graph.eids.shape[0]} edges, meta={meta}")


if __name__ == "__main__":
    main()
