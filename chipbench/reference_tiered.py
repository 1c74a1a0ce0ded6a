"""The plain reference of a two-tier feature store: which rows are hot.

Beside ``reference.py`` (whose ``RefData.features`` recomputes any row
from the generator's counters whatever tier the program keeps it in, and
whose ``sage_forward`` is the model): the hotness order upstream's
``sort_by_in_degree`` defines (python/data/reorder.py:18-40), in plain
numpy and none of the program's code.  The in-degree of every node is
``np.bincount`` over the stored neighbour ids; rows are ordered by
descending in-degree, ties by ascending node id (a stable sort);
``id2index[v]`` is node ``v``'s rank; the hot set is the
``floor(split_ratio * N)`` nodes of lowest rank.
"""
from __future__ import annotations

import numpy as np

from chipbench import checks


def in_degree(indices: np.ndarray, num_nodes: int) -> np.ndarray:
    return np.bincount(np.asarray(indices), minlength=num_nodes)[:num_nodes]


def expected_id2index(indices: np.ndarray, num_nodes: int) -> np.ndarray:
    """Rank of every node in the hotness order."""
    order = np.argsort(-in_degree(indices, num_nodes), kind="stable")
    id2index = np.empty((num_nodes,), np.int64)
    id2index[order] = np.arange(num_nodes)
    return id2index


def check_tiers(indices: np.ndarray, num_nodes: int, hot_count: int,
                got_id2index, got_hot_rows: int, node, what: str) -> dict:
    """The program's ``id2index`` and hot tier against the reference's
    order, and ``node`` (a checked batch's node list, ``-1`` padded)
    against both tiers: the comparison of its rows means something only
    if some of them were served from each."""
    want = expected_id2index(indices, num_nodes)
    got = np.asarray(got_id2index).astype(np.int64)
    checks.check(got.shape == want.shape,
                 f"{what}: id2index {got.shape} for {num_nodes} nodes")
    checks.check(int(got_hot_rows) == int(hot_count),
                 f"{what}: the hot tier holds {got_hot_rows} rows where "
                 f"floor(split_ratio * N) is {hot_count}")
    bad = np.flatnonzero((got < hot_count) != (want < hot_count))
    checks.check(bad.size == 0,
                 f"{what}: the hot set is not the {hot_count} nodes of "
                 f"highest in-degree (ties by id): {bad.size} nodes are "
                 f"in the wrong tier, e.g. node {bad[:1]}")
    checks.check(bool((got == want).all()),
                 f"{what}: id2index differs from the in-degree order at "
                 f"{int((got != want).sum())} nodes")
    node = np.asarray(node)
    rank = want[node[node >= 0]]
    n_hot = int((rank < hot_count).sum())
    n_cold = int(rank.shape[0] - n_hot)
    checks.check(n_hot > 0 and n_cold > 0,
                 f"{what}: the checked batch holds {n_hot} hot and "
                 f"{n_cold} cold rows; it must hold rows of both tiers")
    return {"checked_hot_rows": n_hot, "checked_cold_rows": n_cold}
