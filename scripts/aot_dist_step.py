"""AOT-compile a dist cell's train step for a described v5e:2x2 host and
print its ``memory_analysis``: no chip, no graph, no feature table.

    JAX_PLATFORMS=cpu python scripts/aot_dist_step.py \\
        --config chipbench/configs/sage-papers100m-dist4.json

``scripts/aot_link_step.py`` for ``make_dist_train_step``: the sharded
CSR, feature rows and labels enter as shapes with a ``NamedSharding``
over a mesh of the described devices, through an outer ``jax.jit`` that
builds the step around them.  What the chip's compiler refuses of the
``shard_map`` program (a collective, a per-shard output, the memory of
one chip) it refuses here, at no chip time; a four-chip call is four
times a one-chip call's price.
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
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chipbench import data, gen
    from glt_tpu.models.step import TrainState
    from glt_tpu.parallel import (ShardedFeature, ShardedGraph,
                                  make_dist_train_step)
    from glt_tpu.sampler.neighbor_sampler import hop_bounds

    with open(args.config) as fh:
        config = json.load(fh)
    sam = config["sampling"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    s = len(topo.devices)
    sh = gen.shapes_of(config, s)
    mesh = Mesh(np.array(topo.devices), ("shard",))
    model, tx = data.make_model(config), optax.adam(1e-3)
    fanout, batch = list(sam["fanout"]), int(sam["batch_size"])
    hb = hop_bounds(batch, fanout, sam["frontier_cap"])
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((hb.node_bounds[-1], sh.feature_dim), jnp.float32),
            jnp.full((2, hb.edge_bounds[-1]), -1, jnp.int32),
            jnp.zeros((hb.edge_bounds[-1],), bool)))
    state = TrainState(params=params,
                       opt_state=jax.eval_shape(tx.init, params),
                       step=jax.ShapeDtypeStruct((), jnp.int32))

    def program(indptr, indices, edge_ids, rows, labels, state, seeds, key):
        g = ShardedGraph(indptr, indices, edge_ids, sh.nodes_per_shard,
                         sh.num_nodes, s)
        f = ShardedFeature(rows, sh.nodes_per_shard, s)
        step = make_dist_train_step(model, tx, g, f, labels, mesh, fanout,
                                    batch, frontier_cap=sam["frontier_cap"])
        return step(state, seeds, key)

    def spec(shape, dt, part=P("shard")):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, part))

    c, e = sh.nodes_per_shard, sh.num_edges // s
    operands = (spec((s, c + 1), jnp.int32), spec((s, e), jnp.int32),
                spec((s, e), jnp.int32),
                spec((s, c, sh.feature_dim), jnp.float32),
                spec((s, c), jnp.int32),
                jax.tree.map(lambda a: spec(a.shape, a.dtype, P()), state),
                spec((s, batch), jnp.int32), spec((2,), jnp.uint32, P()))
    t0 = time.perf_counter()
    compiled = jax.jit(program).trace(*operands).lower(
        lowering_platforms=("tpu",)).compile()
    m = compiled.memory_analysis()
    gb = {k: round(getattr(m, k + "_size_in_bytes") / 1e9, 3)
          for k in ("argument", "temp", "output", "alias", "generated_code")}
    text = compiled.as_text()
    print(json.dumps({
        "chips": s, "batch_size": batch, "nodes_per_shard": c,
        "memory_gb_per_chip": gb,
        "all_to_all": text.count(" all-to-all("),
        "all_reduce": text.count(" all-reduce("),
        "compile_s": round(time.perf_counter() - t0, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
