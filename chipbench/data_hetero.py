"""From a heterogeneous configuration file to the program's containers.

``gen_hetero`` makes each relation and each type's rows on the device;
the program's ``Graph`` / ``Feature`` are built from host arrays, so each
table is fetched and handed to the public constructor, one at a time:
the generated copy of a table is dropped before its placed copy exists
beside the next one (two copies of IGBH-small's author rows are 7.9 GB).
"""
from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from chipbench import gen_hetero
from chipbench import reference_hetero


class HeteroChip(NamedTuple):
    graphs: dict            # edge type -> glt_tpu.data.Graph
    feats: dict             # node type -> glt_tpu.data.Feature
    labels: np.ndarray      # [N_seed_type] int32
    ref: object             # reference_hetero.RefHetero
    train_idx: np.ndarray   # [train_seeds] int64
    seed_type: str


def build_hetero_one_chip(config: dict, seed: int, log=lambda msg: None,
                          with_features: bool = True) -> HeteroChip:
    """Generate on the default device, relation by relation and type by
    type, and build the program's containers."""
    import jax

    from glt_tpu.data import CSRTopo, Feature, Graph

    d = config["data"]
    t0 = time.perf_counter()
    graphs, made = {}, {}
    for rel in gen_hetero.relations_of(config):
        m = made[rel.etype] = gen_hetero.generate_relation(rel, seed)
        pairs = [(rel.etype, m.indptr, m.indices)]
        if rel.transpose is not None:
            pairs.append((rel.rev_etype, m.rev_indptr, m.rev_indices))
        for etype, indptr, indices in pairs:
            graphs[etype] = Graph(CSRTopo.from_csr_arrays(
                indptr, indices,
                edge_ids=np.arange(indices.shape[0], dtype=np.int32)))
            jax.block_until_ready(graphs[etype].indices)    # placed lazily
    t1 = time.perf_counter()
    feats = {}
    if with_features:
        # The largest table first: it is generated while the least else
        # is resident.
        order = sorted(d["node_types"], key=lambda t: -d["node_types"][t])
        for t in order:
            rows = gen_hetero.generate_features(
                int(d["node_types"][t]), int(d["feature_dim"]),
                d["feature_dtype"], list(d["node_types"]).index(t), seed)
            host = np.asarray(rows)
            del rows                                # free the device copy
            feats[t] = Feature(host)
            jax.block_until_ready(feats[t].hot_rows)
    log(f"{len(graphs)} relations made, fetched and placed in "
        f"{t1 - t0:.2f} s, {len(feats)} feature tables in "
        f"{time.perf_counter() - t1:.2f} s")
    ref = reference_hetero.RefHetero(config, seed, made)
    seed_type = d["label_type"]
    n_seed = int(d["node_types"][seed_type])
    labels = gen_hetero.label_values(np.arange(n_seed), int(d["num_classes"]),
                                     gen_hetero.label_key(seed), np)
    # Upstream's split: the first 60 % of the papers train.
    train = np.arange(int(d["train_seeds"]), dtype=np.int64)
    return HeteroChip(graphs, feats, labels, ref, train, seed_type)


def make_model(config: dict):
    """The configuration's R-GAT: upstream's ``RGNN('rgat')``, as
    ``examples/rgat_igbh.py`` builds it."""
    import jax.numpy as jnp

    from glt_tpu.models.rgat import RGNN
    from glt_tpu.typing import reverse_edge_type

    m, d = config["model"], config["data"]
    ets = []
    for r in d["relations"]:
        s, name, t = r["type"]
        ets.append((s, name, t))
        if r.get("transpose"):
            ets.append((t, r["transpose"], s))
    dtype = {"bfloat16": jnp.bfloat16, "float32": None}[m["matmul_dtype"]]
    return RGNN(
        sorted(reverse_edge_type(et) for et in ets),
        out_features=int(d["num_classes"]), target_type=d["label_type"],
        hidden_features=int(m["hidden"]), num_layers=int(m["num_layers"]),
        heads=int(m["heads"]), dropout_rate=float(m["dropout"]),
        dtype=dtype)
