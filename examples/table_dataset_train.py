"""Train from table-format storage (the PAI/ODPS ingestion path).

TPU rebuild of the reference's ``examples/pai`` scripts: graph edges and
node features arrive as table records — ``(src, dst)`` rows for edges,
``(id, "f1:f2:...:fd")`` rows for nodes, label as the last feature column
— through a ``common_io``-compatible reader.  On PAI the reader factory
defaults to ``common_io.table.TableReader``; anywhere else any object
with ``read(batch_size, allow_smaller_final_batch=True)`` + ``close()``
works (here: an in-memory reader over synthetic records).

    python examples/table_dataset_train.py
"""
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import numpy as np


class ListTableReader:
    """Minimal common_io-shaped reader over in-memory records."""

    def __init__(self, records):
        self._records = list(records)
        self._pos = 0

    def read(self, batch_size, allow_smaller_final_batch=True):
        if self._pos >= len(self._records):
            raise StopIteration
        got = self._records[self._pos: self._pos + batch_size]
        self._pos += len(got)
        return got

    def close(self):
        pass


def synthetic_tables(n=2000, deg=8, classes=6, seed=0):
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, n * deg)
    labels = rng.integers(0, classes, n)
    feats = (np.eye(classes)[labels]
             + rng.normal(0, .3, (n, classes))).astype(np.float32)
    edge_records = list(zip(src.tolist(), dst.tolist()))
    node_records = [
        (i, ":".join(f"{v:.5f}" for v in feats[i]) + f":{labels[i]}")
        for i in range(n)]
    return {"edges": edge_records, "nodes": node_records}, classes


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax
    import optax

    from glt_tpu.data.table_dataset import TableDataset
    from glt_tpu.loader import NeighborLoader
    from glt_tpu.models import (GraphSAGE, create_train_state,
                                make_train_step)

    tables, classes = synthetic_tables()
    ds = TableDataset.from_tables(
        {"edge": "edges"}, {"node": "nodes"},
        reader_factory=lambda name: ListTableReader(tables[name]),
        graph_mode="DEVICE", label_from_last_column=True,
        reader_batch_size=256)
    n = ds.get_graph().num_nodes
    print(f"loaded from tables: {n} nodes, "
          f"{ds.get_graph().topo.num_edges} edges")

    bs = 128
    loader = NeighborLoader(ds, [5, 5], np.arange(n), batch_size=bs,
                            shuffle=True, seed=0)
    model = GraphSAGE(hidden_features=64, out_features=classes)
    first = next(iter(loader))
    tx = optax.adam(5e-3)
    state = create_train_state(model, jax.random.PRNGKey(0), first, tx)
    step = make_train_step(model, tx, batch_size=bs)
    for epoch in range(3):
        t0 = time.time()
        tot_l = tot_a = nb = 0
        for batch in loader:
            state, loss, acc = step(state, batch)
            tot_l += float(loss); tot_a += float(acc); nb += 1
        print(f"epoch {epoch}: loss {tot_l/nb:.4f} acc {tot_a/nb:.4f} "
              f"({time.time()-t0:.2f}s)")


if __name__ == "__main__":
    main()
