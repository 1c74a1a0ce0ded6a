"""AOT-compile a link cell's scanned step for a described TPU and print
its ``memory_analysis``: no chip, no graph, no feature table.

    JAX_PLATFORMS=cpu TPU_ACCELERATOR_TYPE=v5litepod-1 \\
    TPU_WORKER_HOSTNAMES=localhost python scripts/aot_link_step.py \\
        --config chipbench/configs/sage-unsup-products.json \\
        [--batch-size 512] [--node-capacity 1300000] [--group 4]

``scripts/aot_hetero_step.py`` for seed edges.  The sampler reads only
the graph's node count; the CSR, its column-sorted view and the feature
table enter as shapes, through an outer ``jax.jit`` that builds the step
around them.  This is how the configuration file's ``batch_rule.tried``
bytes were found (.claude/skills/verify).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class ShapeGraph:
    """What the link step reads of a ``Graph``, filled with tracers."""
    gather_edge_ids = None

    def __init__(self, num_nodes: int):
        self.num_nodes = num_nodes
        self.indptr = self.indices = self.sorted_indices = None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--node-capacity", type=int, default=None)
    ap.add_argument("--group", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.drivers.link_scan_train import make_model
    from glt_tpu.data import Feature
    from glt_tpu.models import init_train_state, make_scanned_link_train_step
    from glt_tpu.sampler import NegativeSampling, NeighborSampler

    with open(args.config) as fh:
        config = json.load(fh)
    d, sam = config["data"], config["sampling"]
    q = args.batch_size or sam["batch_size"]
    cap = args.node_capacity or sam["node_capacity"]
    graph = ShapeGraph(int(d["num_nodes"]))
    neg = NegativeSampling(sam["neg_sampling"], sam["amount"])
    sampler = NeighborSampler(graph, sam["fanout"], batch_size=q,
                              frontier_cap=sam["frontier_cap"],
                              with_edge=False, node_capacity=cap)
    union = sampler.seed_union(neg)
    model = make_model(config)
    tx = optax.adam(1e-3)
    dtype = jnp.dtype(d["feature_dtype"])
    state = init_train_state(model, tx, d["feature_dim"],
                             jax.random.PRNGKey(0), dtype)

    def program(indptr, indices, sorted_indices, rows, state, edges, key):
        graph.indptr, graph.indices = indptr, indices
        graph.sorted_indices = sorted_indices
        feat = Feature.__new__(Feature)
        feat.__dict__.update(_hot=rows, _id2index=None,
                             _hot_count=rows.shape[0], _n=rows.shape[0])
        step = make_scanned_link_train_step(model, tx, sampler, feat,
                                            neg_sampling=neg)
        return step(state, edges, key)

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1", chip_config_name="default",
        chips_per_host_bounds=(1, 1, 1), num_slices=1)
    where = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=where)

    n, e = int(d["num_nodes"]), int(d["num_edges"])
    operands = (spec((n + 1,), jnp.int32), spec((e,), jnp.int32),
                spec((e,), jnp.int32), spec((n, d["feature_dim"]), dtype),
                jax.tree.map(lambda a: spec(a.shape, a.dtype), state),
                spec((args.group, 2, q), jnp.int32),
                spec((2,), jnp.uint32))
    t0 = time.perf_counter()
    compiled = jax.jit(program).trace(*operands).lower(
        lowering_platforms=("tpu",)).compile()
    m = compiled.memory_analysis()
    gb = {k: round(getattr(m, k + "_size_in_bytes") / 1e9, 3)
          for k in ("argument", "temp", "output", "alias", "generated_code")}
    gb["total"] = round(gb["argument"] + gb["temp"] + gb["output"]
                        - gb["alias"], 3)
    print(json.dumps({
        "batch_size": q, "seed_union_width": union.batch_size,
        "node_capacity": union.node_capacity,
        "edge_slots": union.edge_capacity,
        "layer_extents": model.layer_extents(union.hop_bounds),
        "memory_gb": gb, "compile_s": round(time.perf_counter() - t0, 1)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
