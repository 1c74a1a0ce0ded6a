"""From a configuration file to the program's own containers.

The generator (``gen.py``) makes the tables on the device.  The program's
one-chip containers (``CSRTopo`` / ``Graph`` / ``Feature`` / ``Dataset``)
are built from host arrays only, so the one-chip path fetches the tables
once and hands them to the public constructors a user calls; the
four-chip path fills ``ShardedGraph`` / ``ShardedFeature`` with the
generator's sharded device arrays as they are.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from chipbench import gen
from chipbench import reference


class OneChip(NamedTuple):
    dataset: object         # glt_tpu.data.Dataset
    ref: object             # reference.RefData
    train_idx: np.ndarray   # [train_seeds] int64
    shapes: object


def one_chip_mesh(device):
    from jax.sharding import Mesh

    return Mesh(np.array([device]), ("shard",))


def build_one_chip(config: dict, seed: int, device,
                   log=lambda msg: None) -> OneChip:
    """Generate on ``device``, then build the program's ``Dataset``."""
    from glt_tpu.data import CSRTopo, Dataset, Feature, Graph

    import jax

    t0 = time.perf_counter()
    sh = gen.shapes_of(config, 1)
    made = gen.generate(sh, seed, one_chip_mesh(device), with_edge_ids=False)
    jax.block_until_ready(made.rows)
    t1 = time.perf_counter()
    indptr = np.asarray(made.indptr).reshape(-1)
    indices = np.asarray(made.indices).reshape(-1)
    rows = np.asarray(made.rows).reshape(sh.num_nodes, sh.feature_dim)
    labels = np.asarray(made.labels).reshape(-1)
    del made                                  # free the device copies
    t2 = time.perf_counter()
    topo = CSRTopo.from_csr_arrays(
        indptr, indices,
        edge_ids=np.arange(indices.shape[0], dtype=np.int32))
    ds = Dataset(graph=Graph(topo), node_features=Feature(rows),
                 node_labels=labels)
    jax.block_until_ready(ds.get_graph().indices)   # Graph places lazily
    log(f"generated on the device in {t1 - t0:.2f} s, fetched in "
        f"{t2 - t1:.2f} s, Dataset built and placed in "
        f"{time.perf_counter() - t2:.2f} s")
    ref = reference.RefData(sh, seed, indptr[None, :])
    train = gen.train_seeds(sh, seed, int(config["data"]["train_seeds"]))
    return OneChip(ds, ref, train.reshape(-1), sh)


class Sharded(NamedTuple):
    graph: object           # glt_tpu.parallel.ShardedGraph
    feature: object         # glt_tpu.parallel.ShardedFeature
    labels: object          # [S, c] int32 device array, sharded
    ref: object
    train_idx: np.ndarray   # [S, train_seeds / S] int64
    shapes: object
    mesh: object


def build_sharded(config: dict, seed: int, devices) -> Sharded:
    """Generate each shard on its own chip; nothing passes the host or
    device 0 except the row pointers the reference keeps."""
    from jax.sharding import Mesh

    from glt_tpu.parallel import ShardedFeature, ShardedGraph

    s = len(devices)
    sh = gen.shapes_of(config, s)
    mesh = Mesh(np.array(devices), ("shard",))
    made = gen.generate(sh, seed, mesh, with_edge_ids=True)
    g = ShardedGraph(indptr=made.indptr, indices=made.indices,
                     edge_ids=made.edge_ids,
                     nodes_per_shard=sh.nodes_per_shard,
                     num_nodes=sh.num_nodes, num_shards=s)
    f = ShardedFeature(rows=made.rows, nodes_per_shard=sh.nodes_per_shard,
                       num_shards=s)
    ref = reference.RefData(sh, seed, np.asarray(made.indptr))
    train = gen.train_seeds(sh, seed, int(config["data"]["train_seeds"]))
    return Sharded(g, f, made.labels, ref, train, sh, mesh)


def make_model(config: dict):
    """The configuration's GraphSAGE, as the examples build it."""
    import jax.numpy as jnp

    from glt_tpu.models import GraphSAGE

    m = config["model"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": None}[m["matmul_dtype"]]
    return GraphSAGE(hidden_features=int(m["hidden"]),
                     out_features=int(config["data"]["num_classes"]),
                     num_layers=len(config["sampling"]["fanout"]),
                     dtype=dtype)
