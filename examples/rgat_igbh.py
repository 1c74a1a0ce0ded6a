"""R-GAT on a (synthetic) IGBH-shaped heterogeneous graph.

TPU rebuild of the reference's examples/igbh R-GAT training: hetero
neighbor sampling over IGBH's four node types and seven relations,
upstream's ``RGNN('rgat')`` (``--hidden 512 --heads 4 --layers 3 --fanout
15,10,5`` are its defaults), paper-node classification.  The scanned
path (``--group``, the default) sizes its sampler by calibration and
reports overflowed batches beside loss and accuracy.
"""
import argparse
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from examples.datasets import synthetic_igbh
from glt_tpu.loader.hetero_neighbor_loader import HeteroNeighborLoader
from glt_tpu.models.rgat import RGNN
from glt_tpu.typing import reverse_edge_type


def run_distributed(args):
    """Multi-chip IGBH (BASELINE config 4): per-edge-type sharded CSRs,
    multi-type exchange sampling, fused R-GAT step over a device mesh
    (cf. the reference's examples/igbh distributed R-GAT).

    Run on a dev box:
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        JAX_PLATFORMS=cpu python examples/rgat_igbh.py --distributed 8
    """
    from jax.sharding import Mesh

    from glt_tpu.parallel import (
        DistHeteroNeighborSampler,
        init_hetero_dist_state,
        make_hetero_dist_train_step,
        put_sharded,
        shard_feature,
        shard_hetero_graph,
    )

    from examples.datasets import require_devices

    n_dev = args.distributed
    mesh = Mesh(np.array(require_devices(n_dev)), ("shard",))

    ds, train_idx, classes = synthetic_igbh(scale=args.scale, use_real=args.use_real)
    topos = {et: g.topo for et, g in ds.graph.items()}
    # Placed on the mesh once: a step fed default-device arrays
    # re-shards them on every call (put_sharded).
    sharded = {et: put_sharded(g, mesh, "shard")
               for et, g in shard_hetero_graph(topos, n_dev).items()}
    feats = {t: put_sharded(
                 shard_feature(np.asarray(ds.node_features[t]._host_full),
                               n_dev), mesh, "shard")
             for t in ds.get_node_types()}
    labels = np.asarray(ds.node_labels["paper"])
    per = sharded[("paper", "cites", "paper")].nodes_per_shard
    lab = put_sharded(np.pad(labels, (0, n_dev * per - labels.shape[0]),
                             constant_values=-1).reshape(n_dev, per),
                      mesh, "shard")

    # Per-shard seed pools bound the usable batch size.
    owned = [train_idx[(train_idx // per) == s] for s in range(n_dev)]
    if min(len(o) for o in owned) == 0:
        raise RuntimeError(
            f"{n_dev} shards over {len(train_idx)} paper seeds leaves at "
            f"least one shard without any seeds; use fewer devices or a "
            f"larger --scale")
    bs = min(args.batch_size, min(len(o) for o in owned))
    # frontier_cap CUTS the sampling semantics (nodes past the cap are
    # never expanded, silently): the distributed sampler has neither the
    # exact clamp nor calibrated capacities yet.
    sampler = DistHeteroNeighborSampler(sharded, mesh, args.fanout, "paper",
                                        batch_size=bs, frontier_cap=512,
                                        seed=0)
    model = build_model(args, ds, classes)
    tx = optax.adam(5e-3)
    state = init_hetero_dist_state(model, tx, sampler, feats,
                                   jax.random.PRNGKey(0))
    step = make_hetero_dist_train_step(model, tx, sampler, feats, lab,
                                       mesh, batch_size=bs)

    steps_per_epoch = max(min(len(o) for o in owned) // bs, 1)
    for epoch in range(args.epochs):
        rngs = [np.random.default_rng(1000 * epoch + s) for s in range(n_dev)]
        t0 = time.perf_counter()
        losses, accs = [], []
        for it in range(steps_per_epoch):
            seeds = np.stack([rngs[s].choice(owned[s], bs, replace=False)
                              for s in range(n_dev)]).astype(np.int32)
            state, loss, acc = step(state, jnp.asarray(seeds),
                                    jax.random.PRNGKey(epoch * 1000 + it))
            losses.append(loss)
            accs.append(acc)
        jax.device_get(losses[-1])
        dt = time.perf_counter() - t0  # before the summary fetches below
        print(f"epoch {epoch}: loss={float(np.mean(jax.device_get(losses))):.4f} "
              f"acc={float(np.mean(jax.device_get(accs))):.4f} "
              f"time={dt:.2f}s")


def build_model(args, ds, classes):
    """Upstream's ``RGNN('rgat')`` over the batch's (reversed) edge types."""
    batch_ets = [reverse_edge_type(et) for et in ds.get_edge_types()]
    return RGNN(batch_ets, hidden_features=args.hidden, out_features=classes,
                target_type="paper", num_layers=args.layers,
                heads=args.heads, dropout_rate=args.dropout,
                dtype=jnp.bfloat16 if args.bf16 else None)


def main():
    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--use-real", action="store_true",
                    help="load the converted real IGBH from DATA_ROOT/"
                         "igbh-tiny instead of the synthetic fixture")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--distributed", type=int, default=0, metavar="N",
                    help="train on an N-device mesh (0 = single device)")
    # G-batch scan (DEFAULT): one program trains --group consecutive
    # hetero batches — config-4's eager loader loop is dispatch-bound;
    # equivalence tested in
    # tests/test_hetero.py::test_scanned_hetero_step_matches_eager.
    ap.add_argument("--group", type=int, default=8,
                    help="scan G batches per program (0 = eager loader)")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--dropout", type=float, default=0.2)
    ap.add_argument("--fanout", type=lambda s: [int(f) for f in s.split(",")],
                    default=[15, 10, 5],
                    help="per-hop fanout of every relation, e.g. 15,10,5")
    ap.add_argument("--data-root", default=None,
                    help="dir holding a converted IGBH "
                         "(scripts/convert_ogb.py igbh); overrides "
                         "GLT_DATA_ROOT")
    args = ap.parse_args()
    if args.data_root:
        import examples.datasets as _exds

        _exds.DATA_ROOT = args.data_root

    if args.distributed:
        return run_distributed(args)

    ds, train_idx, classes = synthetic_igbh(scale=args.scale, use_real=args.use_real)

    model = build_model(args, ds, classes)

    if args.group > 0:
        from glt_tpu.models import (
            init_hetero_state,
            make_scanned_hetero_train_step,
            run_scanned_epoch,
        )
        from glt_tpu.sampler.hetero_neighbor_sampler import (
            HeteroNeighborSampler,
            calibrate_hetero_node_capacity,
        )

        sampler = HeteroNeighborSampler(ds.graph, args.fanout, "paper",
                                        batch_size=args.batch_size,
                                        seed=0)
        # Occupancy-sized buffers and frontiers (pct 99 held jointly,
        # margin 1.05, 24 batches): a batch past them is flagged, never
        # cut silently.
        rng = np.random.default_rng(42)
        probe = [rng.choice(train_idx, min(args.batch_size, len(train_idx)),
                            replace=False) for _ in range(24)]
        caps, fronts = calibrate_hetero_node_capacity(sampler, probe)
        print(f"node capacity {caps} of {sampler.node_capacity}")
        sampler = HeteroNeighborSampler(
            ds.graph, args.fanout, "paper", batch_size=args.batch_size,
            seed=0, node_capacity=caps, frontier_capacity=fronts)
        feats = {t: ds.get_node_feature(t)
                 for t in ds.get_node_types()}
        labels = {"paper": np.asarray(ds.node_labels["paper"])}
        tx = optax.adam(5e-3)
        state = init_hetero_state(model, tx, sampler, feats,
                                  jax.random.PRNGKey(0))
        sstep = make_scanned_hetero_train_step(
            model, tx, sampler, feats, labels, args.batch_size,
            seed_hops=True)
        rng = np.random.default_rng(0)
        for epoch in range(args.epochs):
            t0 = time.perf_counter()
            state, losses, accs, ovf = run_scanned_epoch(
                sstep, state, train_idx, args.batch_size, args.group,
                rng, jax.random.PRNGKey(100 + epoch))
            dt = time.perf_counter() - t0
            print(f"epoch {epoch}: loss={float(np.mean(losses)):.4f} "
                  f"acc={float(np.mean(accs)):.4f} "
                  f"overflowed={ovf}/{len(losses)} time={dt:.2f}s")
        return

    loader = HeteroNeighborLoader(ds, args.fanout, ("paper", train_idx),
                                  batch_size=args.batch_size, shuffle=True)

    first = next(iter(loader))
    params = model.init({"params": jax.random.PRNGKey(0)}, first.x,
                        first.edge_index, first.edge_mask)
    tx = optax.adam(5e-3)
    opt_state = tx.init(params)
    bs = args.batch_size

    @jax.jit
    def step(params, opt_state, batch):
        def loss_fn(p):
            logits = model.apply(p, batch.x, batch.edge_index,
                                 batch.edge_mask)
            y = batch.y["paper"][:bs]
            valid = y >= 0
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:bs], jnp.where(valid, y, 0))
            loss = jnp.where(valid, ce, 0).sum() / jnp.maximum(valid.sum(), 1)
            acc = jnp.where(valid, jnp.argmax(logits[:bs], -1) == y,
                            False).sum() / jnp.maximum(valid.sum(), 1)
            return loss, acc

        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        losses, accs = [], []
        for batch in loader:
            params, opt_state, loss, acc = step(params, opt_state, batch)
            losses.append(loss)
            accs.append(acc)
        jax.block_until_ready(losses[-1])
        print(f"epoch {epoch}: loss={float(np.mean(jax.device_get(losses))):.4f} "
              f"acc={float(np.mean(jax.device_get(accs))):.4f} "
              f"time={time.perf_counter() - t0:.2f}s")


if __name__ == "__main__":
    main()
