"""Find a configuration's ``node_capacity`` once, as the example does.

    python chipbench/calibrate.py --config chipbench/configs/sage-products.json

``examples/train_sage_products.py --auto-cap`` calibrates at start-up
with ``calibrate_node_capacity`` (pct 99, margin 1.05, 24 batches of
shuffled training seeds).  The benchmark does it once, here, on its own
generator, and writes the number into the configuration file, so that
every seed shares one compiled program.  The result is a count of
unique nodes, so a CPU run finds it.
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
    args = ap.parse_args()

    import jax

    from chipbench import data
    from chipbench.common import seed_stream
    from glt_tpu.sampler import (NeighborSampler, calibrate_node_capacity,
                                 measure_occupancy)

    with open(args.config) as fh:
        config = json.load(fh)
    sam = config["sampling"]
    d = data.build_one_chip(config, args.seed, jax.devices()[0])
    probe = NeighborSampler(d.dataset.get_graph(), sam["fanout"],
                            batch_size=sam["batch_size"],
                            frontier_cap=sam["frontier_cap"],
                            with_edge=False)
    rng = np.random.default_rng(42)
    seeds = seed_stream(d.train_idx, args.batches * sam["batch_size"], rng)
    counts = measure_occupancy(
        probe, seeds.reshape(args.batches, -1).astype(np.int32))
    cap = calibrate_node_capacity(probe, counts=counts)
    print(json.dumps({
        "node_capacity": int(cap),
        "full_node_capacity": int(probe.full_node_capacity),
        "unique_nodes_min_median_max": [int(counts.min()),
                                        int(np.median(counts)),
                                        int(counts.max())],
        "batches": args.batches, "seed": args.seed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
