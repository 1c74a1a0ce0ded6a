"""Find a heterogeneous configuration's per-type ``node_capacity`` once.

    python chipbench/calibrate_hetero.py \\
        --config chipbench/configs/rgat-igbh-small.json [--batch-size 128]

``calibrate.py`` for typed graphs: ``calibrate_hetero_node_capacity``
(pct 99 held jointly over all thresholds, margin 1.05, 24 batches of
shuffled training seeds, seed 0: per-type node capacities and
per-(type, hop) frontier widths) on the benchmark's own generator, the result written into the configuration
file by hand so that every seed shares one compiled program.  The result
is a count of unique nodes per type, so a CPU run finds it; the feature
tables are not made.
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
    ap.add_argument("--batches", type=int, default=24)
    ap.add_argument("--batch-size", type=int, action="append")
    ap.add_argument("--pct", type=float, default=99.0)
    ap.add_argument("--margin", type=float, default=1.05)
    args = ap.parse_args()

    from chipbench import data_hetero
    from chipbench.common import seed_stream
    from glt_tpu.sampler.hetero_neighbor_sampler import (
        HeteroNeighborSampler, calibrate_hetero_node_capacity,
        hetero_hop_widths, measure_hetero_occupancy)

    with open(args.config) as fh:
        config = json.load(fh)
    sam = config["sampling"]
    d = data_hetero.build_hetero_one_chip(config, args.seed,
                                          with_features=False)
    for batch in args.batch_size or [sam["batch_size"]]:
        probe = HeteroNeighborSampler(
            d.graphs, sam["fanout"], d.seed_type, batch_size=batch,
            frontier_cap=sam["frontier_cap"])
        rng = np.random.default_rng(42)
        seeds = seed_stream(d.train_idx, args.batches * batch, rng)
        counts = measure_hetero_occupancy(
            probe, seeds.reshape(args.batches, -1).astype(np.int32))
        caps, fronts = calibrate_hetero_node_capacity(
            probe, counts=counts, pct=args.pct, margin=args.margin)
        fit = HeteroNeighborSampler(
            d.graphs, sam["fanout"], d.seed_type, batch_size=batch,
            node_capacity=caps, frontier_capacity=fronts)
        _, worst = hetero_hop_widths(
            probe.edge_types, probe.num_neighbors, {d.seed_type: batch},
            probe.num_hops)
        print(json.dumps({
            "batch_size": batch, "node_capacity": caps,
            "frontier_capacity": fronts,
            "edge_slots": sum(b[-1] for b in
                              fit.hop_bounds.edge_bounds.values()),
            "clamped_edge_slots": sum(
                b[-1] for b in probe.hop_bounds.edge_bounds.values()),
            "clamped_node_capacity": probe.node_capacity,
            "worst_case_node_capacity": worst,
            "unique_nodes_min_median_max": {
                t: [int(c.sum(1).min()), int(np.median(c.sum(1))),
                    int(c.sum(1).max())] for t, c in counts.items()},
            "new_nodes_per_hop_max": {t: c.max(0).tolist()
                                      for t, c in counts.items()},
            "batches": args.batches, "seed": args.seed, "pct": args.pct,
            "margin": args.margin}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
