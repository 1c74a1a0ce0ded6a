"""From a configuration file to a ``Dataset`` whose feature table is
larger than the chip: topology on the device, rows tier by tier.

``data.build_one_chip`` makes every row in one call and fetches the table
whole; a table of 14 GB fits neither beside the edges nor the pattern.
Here the generator's one program makes the topology and the labels (its
rows are never asked for, so XLA drops them), the hotness order is counted
and sorted where the neighbour ids live
(``glt_tpu.data.reorder.in_degree_order`` on the device array), and the
rows are made in hotness order from the same counters as ``gen.py``'s
(feature ``[v, j]`` of ``(seed, v * d + j)``): the hot prefix in one call
and kept on the device, the cold tail block by block and fetched.  The
program's containers are then built through the constructors a user
calls: ``CSRTopo.from_csr_arrays`` / ``Graph`` from host arrays as
``build_one_chip`` does, ``Feature.from_tiers`` from the two tiers.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from chipbench import data
from chipbench import draws
from chipbench import gen
from chipbench import reference

COLD_BLOCK_ROWS = 1 << 21           # 1 GiB of f32[128] rows a fetch


class Tiered(NamedTuple):
    dataset: object         # glt_tpu.data.Dataset
    ref: object             # reference.RefData
    train_idx: np.ndarray   # [train_seeds] int64
    shapes: object
    indices: np.ndarray     # [E] the stored neighbour ids, host copy
    hot_count: int


def hot_count_of(config: dict) -> int:
    """``floor(split_ratio * N)``, as ``Feature`` counts it."""
    return int(int(config["data"]["num_nodes"])
               * float(config["tiering"]["split_ratio"]))


def generate_topology(sh, seed: int, mesh):
    """``(indptr, indices, labels)`` of ``gen.generate``'s one program,
    the rows left out of its results (and so of the compiled program)."""
    import jax
    from jax.sharding import PartitionSpec as P

    scale = gen.lomax_scale(sh.mean_degree, sh.degree_alpha, sh.max_degree)
    body = gen._shard_body(sh, scale, False)

    def topology(keys):
        indptr, indices, _, labels, short = body(keys)
        return indptr, indices, labels, short

    fn = jax.jit(jax.shard_map(topology, mesh=mesh, in_specs=(P(),),
                               out_specs=(P("shard"),) * 4, check_vma=False))
    indptr, indices, labels, short = fn(np.asarray(
        [draws.stream_key(seed, s) for s in gen.STREAMS], np.uint32))
    if np.asarray(short).any():
        raise ValueError("the degree sequence could not be made to sum to "
                         "the file's edge count")
    return indptr, indices, labels


def rows_of(nodes, key, dim: int):
    """The generator's feature rows of ``nodes`` (traced; ``gen.py``'s
    own expression, by node id in place of position)."""
    import jax.numpy as jnp

    cnt = (nodes.astype(jnp.uint32)[:, None] * jnp.uint32(dim)
           + jnp.arange(dim, dtype=jnp.uint32)[None, :])
    return draws.unit_signed(draws.mix32(cnt ^ key), jnp)


def build_tiered(config: dict, seed: int, device,
                 log=lambda msg: None) -> Tiered:
    """Generate on ``device``; the hot prefix stays there, the cold tail
    goes to host memory, nothing is held twice."""
    import jax
    import jax.numpy as jnp

    from glt_tpu.data import (CSRTopo, Dataset, Feature, Graph,
                              in_degree_order)

    t0 = time.perf_counter()
    sh = gen.shapes_of(config, 1)
    n, d = sh.num_nodes, sh.feature_dim
    hot = hot_count_of(config)
    indptr_d, indices_d, labels_d = generate_topology(
        sh, seed, data.one_chip_mesh(device))
    jax.block_until_ready(indices_d)
    t1 = time.perf_counter()
    order, id2index = in_degree_order(indices_d.reshape(-1), n)
    jax.block_until_ready(id2index)
    t2 = time.perf_counter()
    indptr = np.asarray(indptr_d).reshape(-1)
    indices = np.asarray(indices_d).reshape(-1)
    labels = np.asarray(labels_d).reshape(-1)[:n]
    del indptr_d, indices_d, labels_d             # free the device copies
    t3 = time.perf_counter()

    key = jnp.uint32(draws.stream_key(seed, draws.FEATURE))
    make = jax.jit(rows_of, static_argnums=2)
    cold = np.empty((n - hot, d), np.float32)
    for lo in range(hot, n, COLD_BLOCK_ROWS):
        hi = min(lo + COLD_BLOCK_ROWS, n)
        cold[lo - hot: hi - hot] = np.asarray(make(order[lo:hi], key, d))
    t4 = time.perf_counter()
    hot_rows = make(order[:hot], key, d)
    del order
    feature = Feature.from_tiers(hot_rows, cold, id2index)
    jax.block_until_ready(feature.hot_rows)
    t5 = time.perf_counter()

    topo = CSRTopo.from_csr_arrays(
        indptr, indices,
        edge_ids=np.arange(indices.shape[0], dtype=np.int32))
    ds = Dataset(graph=Graph(topo), node_features=feature,
                 node_labels=labels)
    jax.block_until_ready(ds.get_graph().indices)   # Graph places lazily
    log(f"topology generated on the device in {t1 - t0:.2f} s, hotness "
        f"order there in {t2 - t1:.2f} s, topology fetched in "
        f"{t3 - t2:.2f} s, {n - hot} cold rows made and fetched in "
        f"{t4 - t3:.2f} s, {hot} hot rows made in {t5 - t4:.2f} s, Dataset "
        f"built and placed in {time.perf_counter() - t5:.2f} s")
    ref = reference.RefData(sh, seed, indptr[None, :])
    train = gen.train_seeds(sh, seed, int(config["data"]["train_seeds"]))
    return Tiered(ds, ref, train.reshape(-1), sh, indices, hot)
