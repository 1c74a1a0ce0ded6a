"""AOT-compile ``bipartite-sage-taobao``'s scanned typed link step for a
described TPU and print its ``memory_analysis``: no chip, no graph, no
table.

    JAX_PLATFORMS=cpu TPU_ACCELERATOR_TYPE=v5litepod-1 \\
    TPU_WORKER_HOSTNAMES=localhost python scripts/aot_bipartite_step.py \\
        --config chipbench/configs/bipartite-sage-taobao.json [--group 4]

As ``scripts/aot_link_step.py``: the sampler reads only each relation's
node counts; the three CSRs, the column-sorted view and the
``TrainState`` (tables and Adam moments, as shapes: ``jax.eval_shape``)
enter through an outer ``jax.jit`` that builds the step around them and
donates the state, as the step does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class ShapeGraph:
    """What the sampler and the step read of a ``Graph``; the arrays are
    filled with tracers."""
    gather_edge_ids = None

    def __init__(self, num_nodes: int, num_dst: int, num_edges: int):
        self.num_nodes, self.num_edges = num_nodes, num_edges
        self.topo = type("Topo", (), {"indices": np.array([num_dst - 1])})
        self.indptr = self.indices = self.sorted_indices = None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--group", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import data_bipartite as db
    from chipbench.drivers.hetero_link_scan_train import make_model
    from glt_tpu.models import make_scanned_hetero_link_train_step
    from glt_tpu.models.bipartite import init_state
    from glt_tpu.sampler import NegativeSampling
    from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler

    with open(args.config) as fh:
        config = json.load(fh)
    sam = config["sampling"]
    ui, ii = db.relations(config)
    nu, ni = ui.num_src, ui.num_dst
    sizes = {db.UI: (nu, ni, ui.num_edges), db.IU: (ni, nu, ui.num_edges),
             db.II: (ni, ni, ii.num_edges)}
    graphs = {et: ShapeGraph(*s) for et, s in sizes.items()}
    q = sam["batch_size"]
    sampler = HeteroNeighborSampler(graphs, sam["fanout"], "user",
                                    batch_size=q)
    neg = NegativeSampling(sam["neg_sampling"], sam["amount"])
    model = make_model(config)
    tx = optax.adam(config["model"]["learning_rate"])
    state = jax.eval_shape(lambda k: init_state(model, tx, k),
                           jax.random.PRNGKey(0))
    _, widths, cap = sampler.edges_program(db.UI, "binary", sam["amount"])

    def program(arrays, sorted_indices, state, edges, key):
        for et, (indptr, indices) in arrays.items():
            graphs[et].indptr, graphs[et].indices = indptr, indices
        graphs[db.UI].sorted_indices = sorted_indices
        step = make_scanned_hetero_link_train_step(model, tx, sampler,
                                                   db.UI, neg)
        return step(state, edges, key)

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1", chip_config_name="default",
        chips_per_host_bounds=(1, 1, 1), num_slices=1)
    where = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=where)

    arrays = {et: (spec((n + 1,), jnp.int32), spec((e,), jnp.int32))
              for et, (n, _, e) in sizes.items()}
    operands = (arrays, arrays[db.UI][1],
                jax.tree.map(lambda a: spec(a.shape, a.dtype), state),
                spec((args.group, 2, q), jnp.int32), spec((2,), jnp.uint32))
    t0 = time.perf_counter()
    compiled = jax.jit(program, donate_argnums=(2,)).trace(
        *operands).lower(lowering_platforms=("tpu",)).compile()
    m = compiled.memory_analysis()
    gb = {k: round(getattr(m, k + "_size_in_bytes") / 1e9, 3)
          for k in ("argument", "temp", "output", "alias", "generated_code")}
    gb["total"] = round(gb["argument"] + gb["temp"] + gb["output"]
                        - gb["alias"], 3)
    print(json.dumps({
        "batch_size": q, "group": args.group,
        "seed_union": widths[0], "node_rows": cap,
        "edge_slots": sum(sum(widths[h][et[0]] * f[h]
                              for h in range(len(f)))
                          for et, f in sampler.num_neighbors.items()),
        "memory_gb": gb, "compile_s": round(time.perf_counter() - t0, 1)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
