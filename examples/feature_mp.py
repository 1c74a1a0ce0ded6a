"""Feature store shared across processes, zero-copy.

TPU rebuild of the reference's ``examples/feature_mp.py``: there, a
``Feature`` built from CUDA-IPC handles is passed to spawned workers that
gather rows device-side.  On a TPU host the sharable tier is host DRAM:
``share_dataset`` puts the graph + feature pages in POSIX shared memory
once, workers ``attach_dataset`` and gather from the same physical pages
— no per-worker copy of a papers100M-scale feature matrix.

    python examples/feature_mp.py
"""
import multiprocessing as mp
import sys

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import numpy as np


def build():
    from glt_tpu.data import Dataset

    rng = np.random.default_rng(0)
    n, deg, dim = 5000, 8, 64
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, n * deg)
    feat = np.arange(n, dtype=np.float32)[:, None] * np.ones(
        (1, dim), np.float32)
    return (Dataset()
            .init_graph(np.stack([src, dst]), graph_mode="HOST",
                        num_nodes=n)
            .init_node_features(feat))


def worker(rank, handle, q):
    from glt_tpu.data import attach_dataset

    ds = attach_dataset(handle)          # maps, doesn't copy
    ids = np.arange(rank * 100, rank * 100 + 50)
    rows = np.asarray(ds.get_node_feature().gather(ids))
    ok = bool((rows[:, 0] == ids).all())
    q.put((rank, ok, float(rows.sum())))


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    from glt_tpu.data import share_dataset

    ds = build()
    handle = share_dataset(ds)
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(target=worker, args=(r, handle, q))
             for r in range(3)]
    for p in procs:
        p.start()
    for _ in procs:
        rank, ok, s = q.get()
        print(f"worker {rank}: gather-correct={ok} checksum={s:.0f}")
        assert ok
    for p in procs:
        p.join()
    handle.unlink()
    print("feature_mp: 3 workers gathered from one shared copy")


if __name__ == "__main__":
    main()
