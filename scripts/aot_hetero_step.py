"""AOT-compile a heterogeneous cell's scanned step for a described TPU and
print its ``memory_analysis``: no chip, no feature table.

    JAX_PLATFORMS=cpu TPU_ACCELERATOR_TYPE=v5litepod-1 \\
    TPU_WORKER_HOSTNAMES=localhost python scripts/aot_hetero_step.py \\
        --config chipbench/configs/rgat-igbh-small.json --batch-size 128 \\
        [--node-capacity '{"paper": ...}' --frontier-capacity '{...}']
        [--whole-last-layer]

The relations are generated (the sampler reads their node counts and the
step closes over their CSR arrays); the feature tables enter as shapes
only, through an outer ``jax.jit`` that builds the step around them.
This is how the configuration file's ``batch_rule.tried`` bytes were
found (.claude/skills/verify).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch-size", type=int, required=True)
    ap.add_argument("--node-capacity", default=None)
    ap.add_argument("--frontier-capacity", default=None)
    ap.add_argument("--group", type=int, default=2)
    ap.add_argument("--whole-last-layer", action="store_true",
                    help="the step without seed_hops: every layer, the "
                         "class-wide last one too, over every sampled row "
                         "and edge slot")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import data_hetero
    from glt_tpu.models import (init_hetero_state,
                                make_scanned_hetero_train_step)
    from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler

    with open(args.config) as fh:
        config = json.load(fh)
    d, sam = config["data"], config["sampling"]
    caps = (json.loads(args.node_capacity) if args.node_capacity
            else sam["node_capacity"])
    fronts = (json.loads(args.frontier_capacity) if args.frontier_capacity
              else sam["frontier_capacity"])
    built = data_hetero.build_hetero_one_chip(config, 0, with_features=False)
    sampler = HeteroNeighborSampler(
        built.graphs, sam["fanout"], built.seed_type,
        batch_size=args.batch_size, frontier_cap=sam["frontier_cap"],
        node_capacity=caps, frontier_capacity=fronts)
    model = data_hetero.make_model(config)
    tx = optax.adam(1e-3)
    dtype = jnp.dtype(d["feature_dtype"])
    stub = {t: jnp.zeros((1, d["feature_dim"]), dtype)
            for t in d["node_types"]}
    state = init_hetero_state(model, tx, sampler, stub, jax.random.PRNGKey(0))
    labels = {built.seed_type: built.labels}

    def program(rows, state, seeds, key):
        step = make_scanned_hetero_train_step(
            model, tx, sampler, rows, labels, args.batch_size,
            seed_hops=not args.whole_last_layer)
        return step(state, seeds, key)

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1", chip_config_name="default",
        chips_per_host_bounds=(1, 1, 1), num_slices=1)
    where = SingleDeviceSharding(topo.devices[0])

    def spec(a, shape=None):
        return jax.ShapeDtypeStruct(shape or a.shape, a.dtype, sharding=where)

    operands = ({t: spec(r, (int(d["node_types"][t]), r.shape[1]))
                 for t, r in stub.items()},
                jax.tree.map(spec, state),
                jax.ShapeDtypeStruct((args.group, args.batch_size),
                                     jnp.int32, sharding=where),
                spec(jax.random.PRNGKey(0)))
    t0 = time.perf_counter()
    compiled = jax.jit(program).trace(*operands).lower(
        lowering_platforms=("tpu",)).compile()
    m = compiled.memory_analysis()
    gb = {k: round(getattr(m, k + "_size_in_bytes") / 1e9, 3)
          for k in ("argument", "temp", "output", "alias", "generated_code")}
    gb["total"] = round(gb["argument"] + gb["temp"] + gb["output"]
                        - gb["alias"], 3)
    print(json.dumps({
        "batch_size": args.batch_size,
        "whole_last_layer": args.whole_last_layer,
        "node_capacity": sampler.node_capacity,
        "edge_slots": sum(b[-1] for b in
                          sampler.hop_bounds.edge_bounds.values()),
        "layer_extents": model.layer_extents(sampler.hop_bounds),
        "memory_gb": gb, "compile_s": round(time.perf_counter() - t0, 1)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
