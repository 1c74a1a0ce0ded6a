"""Supervised GraphSAGE on a REAL dataset: the sklearn digits k-NN graph.

Config-1's EXACT pipeline (the code path of train_sage_products.py —
NeighborSampler, occupancy auto-cap, bf16 matmuls, fused scanned-epoch
train step) on real features/labels: 1797 handwritten-digit images, 64 raw
pixel features, 10 classes, symmetric 8-NN graph
(scripts/make_digits_graph.py; the data ships in-repo under
data/digits-knn).  Reports held-out test accuracy against the non-graph
baselines recorded in the dataset's META.json (k-NN ~0.975, logistic
regression ~0.958 on the same split).

    python examples/train_sage_digits.py --epochs 30

A user with a converted real ogbn-products runs the identical pipeline
via examples/train_sage_products.py --data-root <dir> instead.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import optax

import examples.datasets as exds
from glt_tpu.loader import NeighborLoader
from glt_tpu.models import (
    GraphSAGE,
    TrainState,
    make_eval_step,
    make_scanned_node_train_step,
    run_scanned_epoch,
)
from glt_tpu.sampler import NeighborSampler, calibrate_node_capacity
from examples.train_sage_products import seed_batches


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--fanout", type=int, nargs="+", default=[15, 10, 5])
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--bf16", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--group", type=int, default=4,
                    help="batches per fused scan-group program")
    ap.add_argument("--auto-cap", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--data-root", default=None)
    args = ap.parse_args()
    if args.data_root:
        exds.DATA_ROOT = args.data_root
    elif not os.path.isdir(os.path.join(exds.DATA_ROOT, "digits-knn")):
        # The in-repo copy (the default for this example).
        exds.DATA_ROOT = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "data")

    loaded = exds._from_disk("digits-knn", graph_mode="DEVICE")
    if loaded is None:
        raise FileNotFoundError(
            "data/digits-knn missing — run scripts/make_digits_graph.py")
    ds, train_idx = loaded
    root = os.path.join(exds.DATA_ROOT, "digits-knn")
    test_idx = np.load(os.path.join(root, "test_idx.npy"))
    with open(os.path.join(root, "META.json")) as fh:
        meta = json.load(fh)
    classes = int(np.asarray(ds.get_node_label()).max()) + 1

    model = GraphSAGE(hidden_features=args.hidden, out_features=classes,
                      num_layers=len(args.fanout),
                      dtype=jax.numpy.bfloat16 if args.bf16 else None)
    tx = optax.adam(args.lr)

    node_cap = None
    if args.auto_cap:
        probe = NeighborSampler(ds.get_graph(), args.fanout,
                                batch_size=args.batch_size, with_edge=False)
        rng_cal = np.random.default_rng(42)
        cal = [b for b, _ in zip(
            seed_batches(train_idx, args.batch_size, rng_cal), range(6))]
        node_cap = calibrate_node_capacity(probe, cal)
        print(f"auto-cap: node_capacity {node_cap} "
              f"({node_cap / probe.full_node_capacity:.0%} of worst case)")

    sampler = NeighborSampler(ds.get_graph(), args.fanout,
                              batch_size=args.batch_size, with_edge=False,
                              node_capacity=node_cap)
    feat = ds.get_node_feature()
    labels = np.asarray(ds.get_node_label())
    x0 = jax.numpy.zeros((sampler.node_capacity, feat.shape[1]), feat.dtype)
    ei0 = jax.numpy.full((2, sampler.edge_capacity), -1, jax.numpy.int32)
    m0 = jax.numpy.zeros((sampler.edge_capacity,), bool)
    params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)
    state = TrainState(params=params, opt_state=tx.init(params),
                       step=jax.numpy.zeros((), jax.numpy.int32))
    # The fused scanned epoch (the only compiled epoch driver): G
    # consecutive sample->gather->train batches per XLA program.
    step = make_scanned_node_train_step(
        model, tx, sampler, feat, labels, args.batch_size)
    rng = np.random.default_rng(0)

    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        state, losses, accs, _ovf = run_scanned_epoch(
            step, state, train_idx, args.batch_size, args.group, rng,
            jax.random.PRNGKey(100 + epoch))
        dt = time.perf_counter() - t0
        if epoch % 5 == 0 or epoch == args.epochs - 1:
            print(f"epoch {epoch}: "
                  f"loss={float(np.mean(losses)):.4f} "
                  f"train_acc={float(np.mean(accs)):.4f} "
                  f"time={dt:.2f}s")

    # Held-out accuracy through the SAME sampling pipeline (eval mode).
    ev = make_eval_step(model, batch_size=args.batch_size)
    loader = NeighborLoader(ds, args.fanout, test_idx,
                            batch_size=args.batch_size, sampler=sampler)
    accs, weights = [], []
    for b in loader:
        _, acc = ev(state.params, b)
        accs.append(float(acc))
        weights.append(b.batch_size)   # valid seeds (trailing batch < bs)
    test_acc = float(np.average(accs, weights=weights))
    base = meta.get("baseline_acc", {})
    print(f"TEST accuracy: {test_acc:.4f}  "
          f"(baselines on same split: {base})")
    return test_acc


if __name__ == "__main__":
    main()
