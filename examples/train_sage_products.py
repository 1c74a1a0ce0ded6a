"""Supervised GraphSAGE on (synthetic) ogbn-products, single TPU device.

The TPU rebuild of the reference's flagship example
(examples/train_sage_ogbn_products.py): NeighborLoader with fanout
[15, 10, 5], batch 1024, 3-layer GraphSAGE, per-epoch loss/acc + sampled
subgraphs/sec.

    python examples/train_sage_products.py --scale 0.01 --epochs 3
    python examples/train_sage_products.py --scale 0.01 --split-ratio 0.5
"""
import argparse
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import jax
import numpy as np
import optax

from examples.datasets import synthetic_products
from glt_tpu.loader import NeighborLoader
from glt_tpu.models import (
    GraphSAGE,
    create_train_state,
    make_train_step,
)
from glt_tpu.sampler import NeighborSampler


def seed_batches(train_idx, batch_size, rng):
    """Shuffled [batch_size] seed chunks, trailing batch -1 padded."""
    ids = train_idx[rng.permutation(train_idx.shape[0])]
    for lo in range(0, ids.shape[0], batch_size):
        chunk = ids[lo: lo + batch_size].astype(np.int32)
        if chunk.shape[0] < batch_size:
            chunk = np.pad(chunk, (0, batch_size - chunk.shape[0]),
                           constant_values=-1)
        yield chunk


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--fanout", type=int, nargs="+", default=[15, 10, 5])
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--frontier-cap", type=int, default=8192)
    # Occupancy-sized node capacity (VERDICT r4 #1): calibrate the padded
    # node buffer to p99 of measured unique-node counts instead of the
    # zero-dedup worst case — feature gather + train segment ops scale
    # with the padded width.  Overflow batches (<1% by construction)
    # train with their excess-node edges masked; the rate is reported.
    ap.add_argument("--auto-cap", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--node-cap", type=int, default=None,
                    help="explicit padded node capacity (overrides "
                         "--auto-cap calibration)")
    ap.add_argument("--cap-batches", type=int, default=24,
                    help="calibration batches for --auto-cap")
    # bf16 matmuls (f32 params/aggregation/loss) — the MXU's native mixed
    # precision; loss-curve parity asserted in tests/test_models.py.
    ap.add_argument("--bf16", action=argparse.BooleanOptionalAction,
                    default=True)
    # Fused scanned epoch (DEFAULT, the only compiled epoch driver —
    # the overlapped "train k + sample k+1" path was deleted after three
    # rounds at 0.97-0.99x; see glt_tpu/models/train.py docstring): one
    # program trains --group consecutive batches (sample+gather+fwd/bwd+
    # update under lax.scan) — amortises host dispatch + seed feeds;
    # equivalence tested exactly
    # (tests/test_models.py::test_scanned_node_step_matches_serial).
    ap.add_argument("--group", type=int, default=8,
                    help="scan G batches per program (0 = eager "
                         "two-program loader loop)")
    # Exact final-hop dedup is the default; --no-last-hop-dedup opts into
    # the leaf-block fast mode (tree-unrolled GraphSAGE semantics).
    ap.add_argument("--last-hop-dedup",
                    action=argparse.BooleanOptionalAction, default=True)
    # Upstream's tiering (Dataset.init_node_features(split_ratio=,
    # sort_func=sort_by_in_degree)): that share of the feature rows,
    # hottest first by in-degree, stays in HBM, the rest in host memory,
    # gathered per batch inside the loader's collate.  The scanned step
    # needs resident rows, so a ratio under 1 takes the loader path.
    ap.add_argument("--split-ratio", type=float, default=1.0,
                    help="share of the feature rows kept in HBM "
                         "(< 1: the eager loader loop, --group 0)")
    ap.add_argument("--data-root", default=None,
                    help="dir holding converted real datasets "
                         "(scripts/convert_ogb.py); overrides "
                         "GLT_DATA_ROOT")
    args = ap.parse_args()
    if args.data_root:
        import examples.datasets as _exds

        _exds.DATA_ROOT = args.data_root

    ds, train_idx = synthetic_products(scale=args.scale)
    if args.split_ratio < 1.0:
        from glt_tpu.data import sort_by_in_degree

        whole = ds.get_node_feature()
        ds.init_node_features(whole.cpu_get(np.arange(whole.size)),
                              split_ratio=args.split_ratio,
                              sort_func=sort_by_in_degree)
        args.group = 0
        print(f"split_ratio {args.split_ratio}: "
              f"{ds.get_node_feature().hot_count} of {whole.size} rows "
              f"in HBM by in-degree, the rest in host memory")
    model = GraphSAGE(hidden_features=args.hidden, out_features=47,
                      num_layers=len(args.fanout),
                      dtype=jax.numpy.bfloat16 if args.bf16 else None)
    tx = optax.adam(1e-3)

    node_cap = args.node_cap
    probe = None
    if node_cap is None and args.auto_cap:
        from glt_tpu.sampler import calibrate_node_capacity

        probe = NeighborSampler(ds.get_graph(), args.fanout,
                                batch_size=args.batch_size,
                                frontier_cap=args.frontier_cap,
                                with_edge=False,
                                last_hop_dedup=args.last_hop_dedup)
        rng_cal = np.random.default_rng(42)
        cal = [b for b, _ in zip(
            seed_batches(train_idx, args.batch_size, rng_cal),
            range(args.cap_batches))]
        node_cap = calibrate_node_capacity(probe, cal)
        print(f"auto-cap: node_capacity {node_cap} "
              f"({node_cap / probe.full_node_capacity:.0%} of worst-case "
              f"{probe.full_node_capacity})")
        if node_cap >= probe.full_node_capacity:
            # No headroom at this scale — reuse the probe so its
            # compiled program serves the training pipeline instead of
            # compiling a twin.
            node_cap = None

    def build_sampler_and_state():
        from glt_tpu.models import TrainState

        sampler = probe if (probe is not None and node_cap is None) else \
            NeighborSampler(ds.get_graph(), args.fanout,
                            batch_size=args.batch_size,
                            frontier_cap=args.frontier_cap,
                            with_edge=False,
                            last_hop_dedup=args.last_hop_dedup,
                            node_capacity=node_cap)
        feat = ds.get_node_feature()
        labels = np.asarray(ds.get_node_label())
        x0 = jax.numpy.zeros((sampler.node_capacity, feat.shape[1]),
                             feat.dtype)
        ei0 = jax.numpy.full((2, sampler.edge_capacity), -1,
                             jax.numpy.int32)
        m0 = jax.numpy.zeros((sampler.edge_capacity,), bool)
        params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)
        state = TrainState(params=params, opt_state=tx.init(params),
                           step=jax.numpy.zeros((), jax.numpy.int32))
        return sampler, feat, labels, state

    if args.group > 0:
        from glt_tpu.models import (
            make_scanned_node_train_step,
            run_scanned_epoch,
        )

        sampler, feat, labels, state = build_sampler_and_state()
        sstep = make_scanned_node_train_step(
            model, tx, sampler, feat, labels, args.batch_size)
        rng = np.random.default_rng(0)

        def run_epoch(state, epoch):
            state, losses, accs, ovf = run_scanned_epoch(
                sstep, state, train_idx, args.batch_size, args.group,
                rng, jax.random.PRNGKey(100 + epoch))
            if ovf:
                print(f"  overflow batches: {ovf}/{len(losses)}")
            return state, list(losses), list(accs)
    else:
        loader = NeighborLoader(ds, args.fanout, train_idx,
                                batch_size=args.batch_size, shuffle=True,
                                frontier_cap=args.frontier_cap,
                                last_hop_dedup=args.last_hop_dedup,
                                node_capacity=node_cap)
        first = next(iter(loader))
        state = create_train_state(model, jax.random.PRNGKey(0), first, tx)
        feat = ds.get_node_feature()
        if feat.plans_gathers:
            from glt_tpu.data import calibrate_cold_width

            # A static cold width, as the node capacity is static: every
            # batch then runs the same two gather programs.
            cal = [b.node for b, _ in zip(loader, range(args.cap_batches))]
            feat.set_cold_width(calibrate_cold_width(feat, cal))
            print(f"cold width {feat.cold_width} rows a batch")
        # The loader's batches keep the sampler's static layout, so the
        # model runs trimmed as the scanned step's does (a replayed batch
        # under the full-capacity sibling's).
        step = make_train_step(
            model, tx, batch_size=args.batch_size,
            hops=(loader.sampler.hop_bounds,
                  loader.sampler.full_capacity_sibling().hop_bounds))

        def run_epoch(state, epoch):
            losses, accs = [], []
            for batch in loader:
                state, loss, acc = step(state, batch)
                losses.append(loss)
                accs.append(acc)
            return state, losses, accs

    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        state, losses, accs = run_epoch(state, epoch)
        jax.block_until_ready(losses[-1])
        dt = time.perf_counter() - t0
        print(f"epoch {epoch}: loss={float(np.mean(jax.device_get(losses))):.4f} "
              f"acc={float(np.mean(jax.device_get(accs))):.4f} "
              f"time={dt:.2f}s "
              f"subgraphs/s={len(losses) / dt:.1f}")


if __name__ == "__main__":
    main()
