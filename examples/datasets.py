"""Example datasets: real OGB data when present on disk, synthetic otherwise.

The container has no network egress, so examples default to synthetic
graphs shaped like their real counterparts (node/edge counts scaled by
--scale).  Drop pre-downloaded OGB .npy files under DATA_ROOT to run the
real thing:

    DATA_ROOT/<name>/{indptr,indices,feat,labels,train_idx}.npy
"""
from __future__ import annotations

import os

import numpy as np

from glt_tpu.data import CSRTopo, Dataset

DATA_ROOT = os.environ.get("GLT_DATA_ROOT", "/root/data")


def require_devices(n: int):
    """The first ``n`` jax devices.  Too few is an error that names the
    platform and the count found — never a silent move to another
    backend."""
    import jax

    devices = jax.devices()
    if len(devices) < n:
        raise SystemExit(
            f"need {n} devices, found {len(devices)} on platform "
            f"{devices[0].platform!r}; for a virtual CPU mesh run with "
            f"JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return devices[:n]


def _from_disk(name: str, graph_mode: str):
    root = os.path.join(DATA_ROOT, name)
    if not os.path.isdir(root):
        return None
    load = lambda f: np.load(os.path.join(root, f + ".npy"), mmap_mode="r")
    topo = CSRTopo((np.asarray(load("indptr")), np.asarray(load("indices"))),
                   layout="CSR")
    ds = Dataset()
    ds.graph = __import__("glt_tpu.data.graph", fromlist=["Graph"]).Graph(
        topo, mode=graph_mode)
    ds.init_node_features(np.asarray(load("feat")))
    ds.init_node_labels(np.asarray(load("labels")))
    return ds, np.asarray(load("train_idx"))


def synthetic_products(scale: float = 0.01, dim: int = 100,
                       num_classes: int = 47, graph_mode: str = "DEVICE",
                       seed: int = 0):
    """ogbn-products-shaped synthetic graph (2.45M nodes / 62M edges at
    scale=1.0) with learnable community structure."""
    real = _from_disk("ogbn-products", graph_mode)
    if real is not None:
        return real

    rng = np.random.default_rng(seed)
    n = max(1000, int(2_449_029 * scale))
    deg = 12
    labels = rng.integers(0, num_classes, n).astype(np.int32)
    # Community-biased neighbors: ~70% same-class, rest uniform.
    indptr = (np.arange(n + 1) * deg).astype(np.int64)
    targets = rng.integers(0, n, (n, deg), dtype=np.int64)
    same_mask = rng.random((n, deg)) < 0.7
    # redirect same-class picks to a random member of the same class
    class_members = [np.flatnonzero(labels == c) for c in range(num_classes)]
    for c in range(num_classes):
        rows = np.flatnonzero(labels == c)
        picks = rng.choice(class_members[c], size=(rows.shape[0], deg))
        targets[rows] = np.where(same_mask[rows], picks, targets[rows])
    indices = targets.reshape(-1)

    feat = (np.eye(num_classes, dtype=np.float32)[labels]
            @ rng.normal(0, 1, (num_classes, dim)).astype(np.float32))
    feat += rng.normal(0, 0.5, (n, dim)).astype(np.float32)

    topo = CSRTopo((indptr.astype(np.int32), indices.astype(np.int32)),
                   layout="CSR")
    from glt_tpu.data.graph import Graph

    ds = Dataset(graph=Graph(topo, mode=graph_mode))
    ds.init_node_features(feat)
    ds.init_node_labels(labels)
    train_idx = rng.permutation(n)[: int(n * 0.1)]
    return ds, train_idx


def synthetic_ppi(scale: float = 1.0, dim: int = 50, seed: int = 0,
                  graph_mode: str = "DEVICE"):
    """PPI-shaped graph for unsupervised link prediction."""
    rng = np.random.default_rng(seed)
    n = max(500, int(14_755 * scale))
    deg = 14
    indptr = (np.arange(n + 1) * deg).astype(np.int64)
    indices = rng.integers(0, n, n * deg, dtype=np.int64)
    feat = rng.normal(size=(n, dim)).astype(np.float32)
    topo = CSRTopo((indptr.astype(np.int32), indices.astype(np.int32)),
                   layout="CSR")
    from glt_tpu.data.graph import Graph

    ds = Dataset(graph=Graph(topo, mode=graph_mode,
                             with_sorted_columns=True))
    ds.init_node_features(feat)
    src, dst = topo.to_coo()
    return ds, np.stack([src, dst])


def _synthetic_citation_hetero(node_counts, relations, scale, seed,
                               graph_mode, label_type="paper", classes=8):
    """Shared builder for citation-shaped hetero benchmarks.

    ``node_counts``: type -> (floor, base) scaled by ``scale``.
    ``relations``: (src_t, rel, dst_t, degree, reversed_rel) — a reverse
    edge type is emitted whenever ``reversed_rel`` is set.  Labels live on
    ``label_type``; its features are noisy one-hot labels so the task is
    learnable, other types get pure-noise features.
    """
    rng = np.random.default_rng(seed)
    n = {t: max(floor, int(base * scale))
         for t, (floor, base) in node_counts.items()}

    ei = {}
    for src_t, rel, dst_t, deg, rev in relations:
        src = np.repeat(np.arange(n[src_t]), deg)
        dst = rng.integers(0, n[dst_t], n[src_t] * deg)
        edges = np.stack([src, dst])
        ei[(src_t, rel, dst_t)] = edges
        if rev is not None:
            ei[(dst_t, rev, src_t)] = edges[::-1]

    labels = rng.integers(0, classes, n[label_type]).astype(np.int32)
    feats = {t: rng.normal(size=(c, classes)).astype(np.float32)
             for t, c in n.items()}
    feats[label_type] = (np.eye(classes, dtype=np.float32)[labels]
                         + feats[label_type] * 0.3)
    ds = (Dataset()
          .init_graph(ei, graph_mode=graph_mode, num_nodes=n)
          .init_node_features(feats)
          .init_node_labels({label_type: labels}))
    return ds, np.arange(n[label_type]), classes


def igbh_from_disk(name: str = "igbh-tiny", graph_mode: str = "HOST"):
    """Load a converted IGB-heterogeneous dataset (scripts/convert_ogb.py
    ``igbh`` subcommand): per-type ``<type>__feat.npy`` /
    ``paper__labels.npy`` and per-relation
    ``<src>__<rel>__<dst>__edges.npy``.  Reverse edge types (``rev_<rel>``)
    are added for cross-type relations, matching the synthetic builder's
    convention.  Returns ``(ds, train_idx, classes)`` or None if absent.
    """
    root = os.path.join(DATA_ROOT, name)
    if not os.path.isdir(root):
        return None
    ei, feats, labels = {}, {}, None
    for f in sorted(os.listdir(root)):
        if not f.endswith(".npy"):
            continue
        stem = f[:-4]
        arr = np.load(os.path.join(root, f), mmap_mode="r")
        if stem.endswith("__edges"):
            src_t, rel, dst_t = stem[: -len("__edges")].split("__")
            edges = np.asarray(arr)
            ei[(src_t, rel, dst_t)] = edges
            if src_t != dst_t:
                ei[(dst_t, f"rev_{rel}", src_t)] = edges[::-1]
        elif stem.endswith("__feat"):
            feats[stem[: -len("__feat")]] = np.asarray(arr, np.float32)
        elif stem == "paper__labels":
            labels = np.asarray(arr)
    if labels is None or not ei:
        return None
    train_path = os.path.join(root, "train_idx.npy")
    train_idx = (np.asarray(np.load(train_path)) if os.path.exists(train_path)
                 else np.flatnonzero(labels >= 0))
    classes = int(labels.max()) + 1
    n = {t: f.shape[0] for t, f in feats.items()}
    ds = (Dataset()
          .init_graph(ei, graph_mode=graph_mode, num_nodes=n)
          .init_node_features(feats)
          .init_node_labels({"paper": labels.astype(np.int32)}))
    return ds, train_idx, classes


def synthetic_igbh(scale: float = 1.0, seed: int = 0,
                   graph_mode: str = "DEVICE", use_real: bool = False):
    """IGBH-tiny-shaped hetero graph: IGBH's four node types (paper,
    author, institute, fos) and seven relations, named as upstream's
    ``examples/igbh/dataset.py`` names them.

    With ``use_real=True``, loads a converted real IGBH from
    ``DATA_ROOT/igbh-tiny`` (scripts/convert_ogb.py) — honoring the
    caller's ``graph_mode`` — and raises if it is absent.  The default
    always builds the synthetic fixture (``scale``/``seed`` honored), so
    benchmarks never silently change shape based on ambient disk state.
    """
    if use_real:
        real = igbh_from_disk("igbh-tiny", graph_mode=graph_mode)
        if real is None:
            raise FileNotFoundError(
                f"use_real=True but no converted IGBH under "
                f"{DATA_ROOT}/igbh-tiny (run scripts/convert_ogb.py)")
        return real
    return _synthetic_citation_hetero(
        {"paper": (200, 1000), "author": (150, 800), "institute": (20, 80),
         "fos": (30, 120)},
        [("paper", "cites", "paper", 4, None),
         ("paper", "written_by", "author", 3, "rev_written_by"),
         ("author", "affiliated_to", "institute", 1, "rev_affiliated_to"),
         ("paper", "topic", "fos", 2, "rev_topic")],
        scale, seed, graph_mode)


def synthetic_mag(scale: float = 1.0, seed: int = 0,
                  graph_mode: str = "DEVICE"):
    """OGB-MAG-shaped hetero graph (the reference's
    examples/hetero/train_hgt_mag.py dataset): paper / author /
    institution / field_of_study with MAG's four canonical relations
    (+ reverses), venue labels on papers."""
    return _synthetic_citation_hetero(
        {"paper": (300, 1500), "author": (200, 1000),
         "institution": (30, 100), "field_of_study": (50, 200)},
        [("paper", "cites", "paper", 4, None),
         ("author", "writes", "paper", 3, "rev_writes"),
         ("author", "affiliated_with", "institution", 1,
          "rev_affiliated_with"),
         ("paper", "has_topic", "field_of_study", 2, "rev_has_topic")],
        scale, seed, graph_mode)
