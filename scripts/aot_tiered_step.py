"""AOT-compile the programs of a tiered loader cell for a described TPU
and add up what they need beside the resident tables: no chip, no graph,
no feature table.

    JAX_PLATFORMS=cpu TPU_ACCELERATOR_TYPE=v5litepod-1 \\
    TPU_WORKER_HOSTNAMES=localhost python scripts/aot_tiered_step.py \\
        --config chipbench/configs/sage-papers100m-tiered-chip1.json \\
        [--split-ratio 0.5 ... --cold-width w] [--node-capacity n] [--setup]

``scripts/aot_link_step.py`` for a loader cell whose feature table does
not fit: the sampler's program at the calibrated capacity and at its
full-capacity sibling's, the tiered gather's plan and merge at both node
widths, and the eager train step under both layouts, each compiled with
tables as shapes only.  The ``split_ratio`` rule of the configuration
file (its ``tiering.rule``) reads the sum printed last: the resident
tables at that ratio, the batches the loader holds in flight, and the
largest program's temporaries, arguments that are no table, and results.
``--setup`` also compiles the data set's own generation programs
(topology, hotness order, hot rows), which run before anything else is
resident.  Bytes, never a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class ShapeGraph:
    """What the node sampler reads of a ``Graph``, filled with tracers."""
    gather_edge_ids = None

    def __init__(self, num_nodes: int):
        self.num_nodes = num_nodes
        self.indptr = self.indices = None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--split-ratio", type=float, action="append")
    ap.add_argument("--node-capacity", type=int, default=None)
    ap.add_argument("--cold-width", type=int, default=None)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--setup", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import data, data_tiered, gen
    from glt_tpu.data import Feature
    from glt_tpu.data.reorder import _device_in_degree_order
    from glt_tpu.loader.transform import Batch
    from glt_tpu.models import init_train_state, make_train_step
    from glt_tpu.sampler import NeighborSampler

    with open(args.config) as fh:
        config = json.load(fh)
    d, sam = config["data"], config["sampling"]
    n, e, dim = int(d["num_nodes"]), int(d["num_edges"]), d["feature_dim"]
    q = sam["batch_size"]
    cap = args.node_capacity or sam["node_capacity"]
    tier = config["tiering"]
    # (ratio, cold width) pairs: the ones asked for at one width, else
    # every ratio the file has tried at its own calibrated width.
    pairs = [(r, args.cold_width or tier["cold_width"])
             for r in args.split_ratio or []] \
        or [(t["split_ratio"], t["cold_width"]) for t in tier["tried"]]

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1", chip_config_name="default",
        chips_per_host_bounds=(1, 1, 1), num_slices=1)
    where = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=where)

    def compiled_gb(fn, *operands, tables=0.0):
        """GB a program needs beside ``tables`` GB of its arguments."""
        t0 = time.perf_counter()
        m = jax.jit(fn).trace(*operands).lower(
            lowering_platforms=("tpu",)).compile().memory_analysis()
        gb = {k: getattr(m, k + "_size_in_bytes") / 1e9
              for k in ("argument", "temp", "output", "alias")}
        gb["beside_tables"] = (gb["argument"] - tables + gb["temp"]
                               + gb["output"] - gb["alias"])
        gb["compile_s"] = time.perf_counter() - t0
        return {k: round(v, 3) for k, v in gb.items()}

    graph = ShapeGraph(n)
    sampler = NeighborSampler(graph, sam["fanout"], batch_size=q,
                              frontier_cap=sam["frontier_cap"],
                              node_capacity=cap)
    sibling = sampler.full_capacity_sibling()
    model = data.make_model(config)
    tx = optax.adam(1e-3)
    state = init_train_state(model, tx, dim, jax.random.PRNGKey(0))
    state_spec = jax.tree.map(lambda a: spec(a.shape, a.dtype), state)
    step = make_train_step(model, tx, q, hops=(sampler.hop_bounds,
                                               sibling.hop_bounds))
    i32, f32 = jnp.int32, jnp.float32
    gb_topo = (4 * (n + 1) + 4 * e) / 1e9
    programs = {}
    for name, s in (("capped", sampler), ("full", sibling)):
        rows, slots = s.node_capacity, s.edge_capacity

        def sample(indptr, indices, seeds, key, s=s):
            graph.indptr, graph.indices = indptr, indices
            return s._sample_impl(indptr, indices, None, seeds, key)

        programs[f"sample.{name}"] = compiled_gb(
            sample, spec((n + 1,), i32), spec((e,), i32), spec((q,), i32),
            spec((2,), jnp.uint32), tables=gb_topo)
        batch = Batch(
            x=spec((rows, dim), f32), y=spec((rows,), i32),
            edge_index=spec((2, slots), i32), edge_id=spec((slots,), i32),
            node=spec((rows,), i32), node_mask=spec((rows,), bool),
            edge_mask=spec((slots,), bool), batch=spec((q,), i32),
            batch_size=q)
        programs[f"train.{name}"] = compiled_gb(step, state_spec, batch)
        programs[f"train.{name}"]["layer_extents"] = model.layer_extents(
            s.hop_bounds)

    out = {"node_capacity": sampler.node_capacity,
           "full_node_capacity": sibling.node_capacity,
           "edge_slots": sampler.edge_capacity, "by_ratio": []}
    for ratio, width in pairs:
        hot = int(n * ratio)
        feat = Feature.__new__(Feature)
        feat.__dict__.update(_hot_count=hot, _quant=None, dtype=f32,
                             _n=n, _dim=dim)
        gb_hot = hot * dim * 4 / 1e9
        mine = dict(programs)
        for name, rows in (("capped", sampler.node_capacity),
                           ("full", sibling.node_capacity)):
            w = min(width, rows)

            def plan(id2index, ids, offset, w=w):
                return feat._plan_impl(id2index, ids, offset, width=w)

            mine[f"plan.{name}"] = compiled_gb(
                plan, spec((n,), i32), spec((rows,), i32), spec((), i32),
                tables=4 * n / 1e9)
            mine[f"merge.{name}"] = compiled_gb(
                feat._merge_impl, spec((hot, dim), f32), spec((rows,), i32),
                spec((w,), i32), spec((w, dim), f32), tables=gb_hot)
        # Resident: topology with its edge ids, labels (the loader's
        # device copy), id2index, the hot rows.
        resident = gb_topo + 4 * e / 1e9 + 2 * 4 * n / 1e9 + gb_hot
        c = sampler.node_capacity
        in_flight = (args.prefetch + 1) * (
            c * (4 + 1) + sampler.edge_capacity * (4 * 3 + 1)) / 1e9 \
            + 2 * c * dim * 4 / 1e9 + 2 * width * dim * 4 / 1e9
        worst = max(mine, key=lambda k: mine[k]["beside_tables"])
        out["by_ratio"].append({
            "split_ratio": ratio, "hot_rows": hot, "cold_width": width,
            "resident_gb": round(resident, 3),
            "in_flight_gb": round(in_flight, 3),
            "largest_program": worst,
            "total_gb": round(resident + in_flight
                              + mine[worst]["beside_tables"], 3),
            "programs": {k: mine[k] for k in mine
                         if k.startswith(("plan", "merge"))}})
    out["programs"] = programs

    if args.setup:
        sh = gen.shapes_of(config, 1)
        scale = gen.lomax_scale(sh.mean_degree, sh.degree_alpha,
                                sh.max_degree)
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(topo.devices[:1]), ("shard",))
        body = gen._shard_body(sh, scale, False)

        def topology(keys):
            indptr, indices, _, labels, short = body(keys)
            return indptr, indices, labels, short

        sharded = jax.shard_map(topology, mesh=mesh, in_specs=(P(),),
                                out_specs=(P("shard"),) * 4,
                                check_vma=False)
        out["setup"] = {
            "topology": compiled_gb(sharded, spec((4,), jnp.uint32)),
            "order": compiled_gb(
                lambda ind: _device_in_degree_order.__wrapped__(ind, n),
                spec((e,), i32)),
            "hot_rows": compiled_gb(
                lambda nodes, key: data_tiered.rows_of(nodes, key, dim),
                spec((int(n * max(r for r, _ in pairs)),), i32),
                spec((), jnp.uint32))}
    print(json.dumps(out, indent=1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
