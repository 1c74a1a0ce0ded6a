"""Find a tiered configuration's ``node_capacity`` and cold width once.

    python chipbench/calibrate_tiered.py \\
        --config chipbench/configs/sage-papers100m-tiered-chip1.json \\
        [--split-ratio 0.45 --split-ratio 0.5 ...] [--check-seed 1]

``calibrate.py`` for a feature table in two tiers, with no row of it
made: the topology is generated, the hotness order counted and sorted on
the device, and 24 batches of shuffled training seeds are sampled by a
sampler that holds no capacity.  ``node_capacity`` is
``calibrate_node_capacity`` of their unique nodes (pct 99, margin 1.05),
the cold width ``calibrate_cold_width`` of their cold rows (the nodes
whose rank in the order is ``floor(split_ratio * N)`` or more; pct 99,
margin 1.05, a multiple of 1024) at every ``--split-ratio`` asked for,
so one run serves the configuration's whole ``split_ratio`` rule.  The
numbers are written into the configuration file by hand, so that every
seed shares one set of compiled programs; ``--check-seed`` generates
another graph and says how many of its batches would pass either.  The
results are counts, so a CPU run finds them; at the published size the
chip is quicker.
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
    ap.add_argument("--split-ratio", type=float, action="append")
    ap.add_argument("--batches", type=int, default=24)
    ap.add_argument("--pct", type=float, default=99.0)
    ap.add_argument("--margin", type=float, default=1.05)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from chipbench import data, data_tiered, gen
    from chipbench.common import seed_stream
    from glt_tpu.data import (CSRTopo, Graph, calibrate_cold_width,
                              in_degree_order)
    from glt_tpu.sampler import (NeighborSampler, NodeSamplerInput,
                                 calibrate_node_capacity)

    with open(args.config) as fh:
        config = json.load(fh)
    sam = config["sampling"]
    ratios = args.split_ratio or [config["tiering"]["split_ratio"]]
    sh = gen.shapes_of(config, 1)
    n = sh.num_nodes
    cap, widths = None, {}
    for seed in [args.seed] + args.check_seed:
        indptr, indices, _ = data_tiered.generate_topology(
            sh, seed, data.one_chip_mesh(jax.devices()[0]))
        _, id2index = in_degree_order(indices.reshape(-1), n)
        graph = Graph(CSRTopo.from_csr_arrays(
            np.asarray(indptr).reshape(-1), np.asarray(indices).reshape(-1),
            edge_ids=np.arange(sh.num_edges, dtype=np.int32)))
        del indptr, indices
        probe = NeighborSampler(graph, sam["fanout"],
                                batch_size=sam["batch_size"],
                                frontier_cap=sam["frontier_cap"])
        train = gen.train_seeds(
            sh, seed, int(config["data"]["train_seeds"])).reshape(-1)
        seeds = seed_stream(train, args.batches * sam["batch_size"],
                            np.random.default_rng(42))
        floors = jnp.asarray([int(n * r) for r in ratios], jnp.int32)

        @jax.jit
        def count(node, counts):
            rank = jnp.where(node >= 0,
                             id2index[jnp.clip(node, 0, n - 1)], -1)
            return jnp.concatenate([
                jnp.sum(counts)[None],
                jnp.sum(rank[None, :] >= floors[:, None], axis=1)])

        rows = []
        for batch in seeds.reshape(args.batches, -1).astype(np.int32):
            out = probe.sample_from_nodes(NodeSamplerInput(batch))
            rows.append(count(out.node, out.num_sampled_nodes))
        rows = np.asarray(jax.device_get(jnp.stack(rows)))
        unique, cold = rows[:, 0], rows[:, 1:]
        if cap is None:
            cap = calibrate_node_capacity(probe, counts=unique, pct=args.pct,
                                          margin=args.margin)
            widths = {r: calibrate_cold_width(
                None, None, pct=args.pct, margin=args.margin,
                counts=cold[:, i]) for i, r in enumerate(ratios)}
        print(json.dumps({
            "seed": seed, "node_capacity": int(cap),
            "full_node_capacity": int(probe.full_node_capacity),
            "unique_nodes_min_median_max": [
                int(unique.min()), int(np.median(unique)),
                int(unique.max())],
            "batches_over_capacity": int((unique > cap).sum()),
            "batches": args.batches,
            "by_split_ratio": [{
                "split_ratio": r, "hot_rows": int(n * r),
                "cold_width": int(widths[r]),
                "cold_rows_min_median_max": [
                    int(cold[:, i].min()), int(np.median(cold[:, i])),
                    int(cold[:, i].max())],
                "cold_share_of_valid_rows_median": round(float(
                    np.median(cold[:, i] / unique)), 4),
                "batches_over_width": int((cold[:, i] > widths[r]).sum())}
                for i, r in enumerate(ratios)]}), flush=True)
        del graph, probe, id2index
    return 0


if __name__ == "__main__":
    sys.exit(main())
