"""Find a link configuration's ``node_capacity`` once: the seed union's.

    python chipbench/calibrate_link.py \\
        --config chipbench/configs/sage-unsup-products.json \\
        [--seed 0 --check-seed 1 --check-seed 2] [--batch-size 512]

``calibrate.py`` for seed edges: ``calibrate_node_capacity`` (pct 99,
margin 1.05, 24 batches, seed 0) over batches of ``batch_size`` seed
edges of a shuffled pass over the graph's own edges, each with its
binary negatives, sampled from the seed union by a sampler that holds no
capacity but the graph's node count.  The number is written into the
configuration file by hand, so that every seed shares one compiled
program; ``--check-seed`` generates another graph and says how many of
its batches would overflow it.  The result is a count of unique nodes,
so a CPU run finds it; at the published size the chip is quicker.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-seed", type=int, action="append", default=[])
    ap.add_argument("--batches", type=int, default=24)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--pct", type=float, default=99.0)
    ap.add_argument("--margin", type=float, default=1.05)
    args = ap.parse_args()

    import jax

    from chipbench import data
    from chipbench.drivers.link_scan_train import edges_at
    from glt_tpu.models.train import shuffled_positions
    from glt_tpu.sampler import (NegativeSampling, NeighborSampler,
                                 calibrate_node_capacity, measure_occupancy)

    with open(args.config) as fh:
        config = json.load(fh)
    sam = config["sampling"]
    q = args.batch_size or sam["batch_size"]
    neg = NegativeSampling(sam["neg_sampling"], sam["amount"])
    cap = None
    for seed in [args.seed] + args.check_seed:
        d = data.build_one_chip(config, seed, jax.devices()[0])
        graph = d.dataset.get_graph()
        probe = NeighborSampler(graph, sam["fanout"], batch_size=q,
                                frontier_cap=sam["frontier_cap"],
                                with_edge=False)
        pos = next(shuffled_positions(
            graph.num_edges, np.random.default_rng(42), args.batches * q))
        edges = edges_at(graph.topo, pos).reshape(2, args.batches, q)
        counts = measure_occupancy(probe, edges.transpose(1, 0, 2), neg)
        if cap is None:
            cap = calibrate_node_capacity(
                probe, counts=counts, pct=args.pct, margin=args.margin,
                neg_sampling=neg)
        union = probe.seed_union(neg)
        print(json.dumps({
            "seed": seed, "batch_size": q, "node_capacity": int(cap),
            "seed_union_width": union.batch_size,
            "clamped_node_capacity": union.node_capacity,
            "product_of_fanouts": union.batch_size + union.edge_capacity,
            "unique_nodes_min_median_max": [
                int(counts.min()), int(np.median(counts)),
                int(counts.max())],
            "batches_over_capacity": int((counts > cap).sum()),
            "batches": args.batches}), flush=True)
        del d, graph, probe
    return 0


if __name__ == "__main__":
    sys.exit(main())
