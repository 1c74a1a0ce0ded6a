"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process drives the config-1 data path once, at full width, through
the calls a user makes (``examples/train_sage_products.py``,
``examples/dist_train_sage.py``, ``init_server`` + ``InferenceClient``),
checks what comes out by the repo's own means, and prints two lines on
its standard output: the full report (one JSON object: versions, the
compile-cache directory, every stage's record, the kernel table, the
probes), then, as the LAST line, the verdict a driver parses —

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with exactly those keys.  Config 1 is GraphSAGE
hidden 256 x 3, bf16 matmuls, 47 classes, 100 features, batch 1024,
fanout [15, 10, 5], ``frontier_cap`` 8192, on
``synthetic_products(scale=1.0)`` — 2,449,029 nodes, 29.4 M edges.
Weights are random from a seed; depth is cut to a few steps.

Stages, in order:

* ``probes``   — does ``jax.block_until_ready`` wait (a chain of 16
  8192^2 bf16 matmuls may not finish faster than the chip's published
  peak allows), and the host round trip of a trivial jitted call;
* ``scanned``  — ``NeighborSampler`` -> ``make_scanned_node_train_step``
  -> ``run_scanned_epoch``, two groups of G = 8; losses finite, zero
  compilations after the first group;
* ``eager``    — ``NeighborLoader`` (whose constructor runs the kernel
  sweeps) + ``create_train_state`` + ``make_train_step``; every batch
  checked against a host-side feature and label lookup;
* ``serving``  — one in-process server after ``engine.warmup()``, a
  handful of ``InferenceClient`` requests of 1-100 seeds, every reply
  checked against the graph, features and labels;
* ``kernels``  — each Pallas kernel compiled with ``interpret=False`` at
  the config-1 shapes and compared bit-for-bit with its XLA arm; a
  kernel Mosaic refuses is listed as ``refused`` with the message, and
  the stage fails unless the refused set is exactly ``KNOWN_REFUSED``;
* ``dist``     — ``make_dist_train_step`` on a mesh of ALL local devices,
  graph and features sharded S ways; one addressable shard per device.

The script refuses any platform but ``tpu`` and has no size, platform
or skip switch; ``tests/test_chip_smoke.py`` drives the same stage
functions at toy size on the CPU, kernels in interpret mode.  A failing
stage is recorded, the remaining stages still run (a chip call is too
dear to learn about one failure at a time), and the exit code is 1.
None of the numbers printed here is a speed.
"""
from __future__ import annotations

import gc
import json
import sys
import time
import traceback

import numpy as np

#: Config 1 (BASELINE.json, examples/train_sage_products.py defaults).
CONFIG1 = {
    "scale": 1.0,
    "hidden": 256,
    "fanout": (15, 10, 5),
    "batch": 1024,
    "frontier_cap": 8192,
    "group": 8,
    "groups": 2,
    "eager_batches": 3,
    "serving_requests": (1, 3, 17, 64, 100),
    "seed_buckets": (8, 32, 128),
    "dist_steps": 3,
    "probe_matmul_n": 8192,
    "probe_matmul_chain": 16,
    # Rows of the table the compressed (bf16 / int8) kernel checks use:
    # a prefix, so the host-side int8 calibration stays cheap.  What
    # Mosaic accepts does not depend on the table height.
    "dq_rows": 1 << 18,
}

#: Kernel families Mosaic refuses on the TPU v5e (jax 0.9.0, libtpu
#: 0.0.34), each out of its ``auto`` arm by an explicit line in its
#: module.  The kernels stage fails when the refused set differs.
KNOWN_REFUSED = frozenset({
    "gather_dq_bf16",   # ops/gather_pallas.py: packed-dtype row load
    "gather_dq_int8",
    "sample",           # ops/sample_pallas.py: TPU_REFUSAL
    "fused_dq_bf16",    # ops/fused_frontier.py: packed-dtype row load
    "fused_dq_int8",
})


class SmokeFailure(RuntimeError):
    """A check on what a stage produced did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def device_facts() -> dict:
    """Platform, kind and count as JAX reports them, and the versions."""
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    dev = jax.devices()
    return {
        "device": {"platform": dev[0].platform,
                   "kind": dev[0].device_kind, "count": len(dev)},
        "versions": {"python": sys.version.split()[0],
                     "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
    }


def verdict(ok: bool, facts: dict) -> dict:
    """The last stdout line: exactly ``ok`` and ``device``, the device
    exactly ``platform`` / ``kind`` / ``count`` as JAX reports them.
    Everything else the run learned goes on the report line before it."""
    return {"ok": bool(ok), "device": dict(facts["device"])}


class CompileMeter:
    """Sums what ``jax.monitoring`` reports while the process runs:
    seconds spent in backend compilation (cache reads included) and the
    persistent cache's hits and misses."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration_s: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += float(duration_s)
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event.endswith("/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/cache_misses"):
            self.cache_misses += 1

    def snapshot(self) -> tuple:
        return (self.seconds, self.compiles, self.cache_hits,
                self.cache_misses)


def run_stage(report: dict, meter: CompileMeter, name: str, fn, *args,
              **kwargs):
    """Run one stage; record ok / wall seconds / compile seconds and
    whatever the stage returns.  A stage that raises is recorded with
    its traceback on stderr and makes the whole run fail."""
    _log(f"stage {name}: start")
    before = meter.snapshot()
    t0 = time.perf_counter()
    try:
        detail = fn(*args, **kwargs)
        ok = True
    except Exception as e:  # noqa: BLE001 — recorded; exit code becomes 1
        from glt_tpu.ops.tpu_limits import compiler_message

        traceback.print_exc()
        detail, ok = {"error": compiler_message(e)}, False
    after = meter.snapshot()
    # A refused kernel's traceback keeps its frames' arrays alive until
    # the collector runs; the next stage starts from what is really held.
    gc.collect()
    report["stages"][name] = {
        "ok": ok,
        "wall_s": round(time.perf_counter() - t0, 2),
        "compile_s": round(after[0] - before[0], 2),
        "compiles": after[1] - before[1],
        "cache_hits": after[2] - before[2],
        "cache_misses": after[3] - before[3],
        **detail,
    }
    _log(f"stage {name}: {'ok' if ok else 'FAILED'} "
         f"{report['stages'][name]['wall_s']}s")
    return ok


# -- stages -----------------------------------------------------------------

def stage_probes(matmul_n: int, chain: int, peak_flops=None) -> dict:
    """(a) Does ``block_until_ready`` wait?  (b) The host round trip."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run_chain(a, w):
        return jax.lax.fori_loop(0, chain, lambda _, x: x @ w, a)

    eye = jnp.eye(matmul_n, dtype=jnp.bfloat16)
    float(run_chain(eye, eye)[0, 0])               # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(run_chain(eye, eye))
    block_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(run_chain(eye, eye)[0, 0])               # host value fetch
    fetch_s = time.perf_counter() - t0
    flops = chain * 2.0 * matmul_n ** 3
    waits = None
    if peak_flops is not None:
        waits = flops / block_s <= peak_flops
        check(waits,
              f"block_until_ready returned after {block_s * 1e3:.3f} ms "
              f"for {flops:.3g} FLOP: faster than the chip's peak "
              f"{peak_flops:.3g} FLOP/s allows, so it does not wait here "
              f"(host-fetch timing of the same chain: "
              f"{fetch_s * 1e3:.3f} ms)")

    triv = jax.jit(lambda a: a + 1)
    z = jnp.zeros((), jnp.int32)
    for _ in range(3):
        z = triv(z)
    int(z)
    trips = []
    for _ in range(7):
        t0 = time.perf_counter()
        int(triv(z))                                # dispatch+run+fetch
        trips.append(time.perf_counter() - t0)
    return {
        "matmul_chain_block_until_ready_ms": round(block_s * 1e3, 3),
        "matmul_chain_host_fetch_ms": round(fetch_s * 1e3, 3),
        "block_until_ready_waits": waits,
        "host_roundtrip_ms": round(float(np.median(trips)) * 1e3, 3),
    }


def _config1_model(hidden: int, fanout, classes: int = 47):
    import jax.numpy as jnp

    from glt_tpu.models import GraphSAGE

    return GraphSAGE(hidden_features=hidden, out_features=classes,
                     num_layers=len(fanout), dtype=jnp.bfloat16)


def stage_scanned(ds, train_idx, *, hidden, fanout, batch, frontier_cap,
                  group, groups) -> dict:
    """The scanned driver of examples/train_sage_products.py."""
    import jax
    import jax.numpy as jnp
    import optax

    from glt_tpu.models import (TrainState, make_scanned_node_train_step,
                                run_scanned_epoch)
    from glt_tpu.obs import compilewatch
    from glt_tpu.sampler import NeighborSampler

    model = _config1_model(hidden, fanout)
    tx = optax.adam(1e-3)
    sampler = NeighborSampler(ds.get_graph(), list(fanout),
                              batch_size=batch, frontier_cap=frontier_cap,
                              with_edge=False)
    feat = ds.get_node_feature()
    labels = np.asarray(ds.get_node_label())
    x0 = jnp.zeros((sampler.node_capacity, feat.shape[1]), feat.dtype)
    ei0 = jnp.full((2, sampler.edge_capacity), -1, jnp.int32)
    m0 = jnp.zeros((sampler.edge_capacity,), bool)
    params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)
    state = TrainState(params=params, opt_state=tx.init(params),
                       step=jnp.zeros((), jnp.int32))
    sstep = make_scanned_node_train_step(model, tx, sampler, feat, labels,
                                         batch)

    steps = group * groups
    check(len(train_idx) >= steps * batch,
          f"need {steps * batch} training seeds, have {len(train_idx)}")
    compilewatch.install()
    after_block = []
    state, losses, accs, ovf = run_scanned_epoch(
        sstep, state, train_idx[: steps * batch], batch, group,
        np.random.default_rng(0), jax.random.PRNGKey(100),
        on_block=lambda _s, _i: after_block.append(
            compilewatch.total_compiles()))
    check(losses.shape == (steps,), f"losses shape {losses.shape}")
    check(bool(np.isfinite(losses).all() and np.isfinite(accs).all()),
          f"non-finite loss/acc: {losses} {accs}")
    check(int(jax.device_get(state.step)) == steps,
          f"state.step {state.step} != {steps}")
    later = after_block[-1] - after_block[0]
    check(later == 0,
          f"{later} compilations after the first scan group: "
          f"{compilewatch.counts()}")
    return {"steps": steps, "node_capacity": sampler.node_capacity,
            "loss_first": round(float(losses[0]), 4),
            "loss_last": round(float(losses[-1]), 4),
            "compiles_after_first_group": later}


def _check_batch(batch, feat, labels, batch_size: int, what: str) -> None:
    """A loader batch against a host-side lookup: static shapes, seeds
    first, -1 / zero padding exactly off the node mask."""
    node = np.asarray(batch.node)
    mask = np.asarray(batch.node_mask)
    x = np.asarray(batch.x)
    y = np.asarray(batch.y)
    check(x.shape == (node.shape[0], feat.shape[1]),
          f"{what}: x {x.shape} vs node {node.shape}")
    check(bool(np.isfinite(x).all()), f"{what}: non-finite features")
    check(bool(((node >= 0) == mask).all()),
          f"{what}: node ids are not -1 exactly off the node mask")
    seeds = np.asarray(batch.batch)
    check(bool((node[:batch_size][seeds >= 0] == seeds[seeds >= 0]).all()),
          f"{what}: seeds do not lead the node list")
    check(bool((x == feat.cpu_get(node)).all()),
          f"{what}: gathered features differ from the host lookup")
    check(bool((y == np.where(mask, labels[np.where(mask, node, 0)],
                              -1)).all()),
          f"{what}: gathered labels differ from the host lookup")
    ei = np.asarray(batch.edge_index)
    em = np.asarray(batch.edge_mask)
    check(bool((ei[:, em] >= 0).all() and (ei[:, em] < node.shape[0]).all()
               and mask[ei[:, em]].all()),
          f"{what}: a live edge points at a padding slot")


def stage_eager(ds, train_idx, *, hidden, fanout, batch, frontier_cap,
                batches) -> dict:
    """The eager loader driver (``--group 0``) of the same example."""
    import jax
    import optax

    from glt_tpu.loader import NeighborLoader
    from glt_tpu.models import create_train_state, make_train_step
    from glt_tpu.ops.gather_pallas import autotune_table
    from glt_tpu.ops.sample_pallas import sample_autotune_table

    model = _config1_model(hidden, fanout)
    tx = optax.adam(1e-3)
    feat = ds.get_node_feature()
    labels = np.asarray(ds.get_node_label())
    loader = NeighborLoader(ds, list(fanout), train_idx[: batches * batch],
                            batch_size=batch, shuffle=True,
                            frontier_cap=frontier_cap)
    first = next(iter(loader))
    state = create_train_state(model, jax.random.PRNGKey(0), first, tx)
    step = make_train_step(model, tx, batch_size=batch)
    losses = []
    last = None
    for i, b in enumerate(loader):
        _check_batch(b, feat, labels, batch, f"eager batch {i}")
        state, loss, acc = step(state, b)
        losses.append(loss)
        last = b
    losses = np.asarray(jax.device_get(losses))
    check(losses.shape == (batches,), f"{losses.shape[0]} batches")
    check(bool(np.isfinite(losses).all()), f"non-finite loss {losses}")

    # The same gather fed host ids (the loader feeds device ids).
    ids = np.asarray(last.node)
    got = np.asarray(feat.gather(ids))
    check(bool((got == feat.cpu_get(ids)).all()),
          "Feature.gather(host ids) differs from the host lookup")
    return {"batches": batches,
            "loss_last": round(float(losses[-1]), 4),
            "gather_autotune": autotune_table(),
            "sample_autotune": sample_autotune_table()}


def _check_reply(reply, seeds, topo, feat, labels) -> None:
    """A served ego-subgraph against the graph, features and labels.
    Replies are request-local and compact: no padding slot survives."""
    node = np.asarray(reply.node)
    n = node.shape[0]
    k = len(seeds)
    what = f"reply to {k} seeds"
    check(reply.batch_size == k and np.asarray(reply.batch).tolist()
          == list(seeds), f"{what}: seed block {reply.batch}")
    check(node[:k].tolist() == list(seeds), f"{what}: seeds do not lead")
    check(bool((node >= 0).all()) and len(set(node.tolist())) == n,
          f"{what}: node list holds padding or repeats")
    check(bool(np.asarray(reply.node_mask).all()
               and np.asarray(reply.edge_mask).all()),
          f"{what}: a compact reply carries a masked slot")
    x = np.asarray(reply.x)
    check(x.shape == (n, feat.shape[1]), f"{what}: x shape {x.shape}")
    check(bool((x == feat.cpu_get(node)).all()),
          f"{what}: features differ from the host lookup")
    check(bool((np.asarray(reply.y) == labels[node]).all()),
          f"{what}: labels differ")
    ei = np.asarray(reply.edge_index)
    check(ei.shape[0] == 2 and bool((ei >= 0).all() and (ei < n).all()),
          f"{what}: edge index out of range")
    # row = neighbor side, col = seed side: node[row] is an out-neighbor
    # of node[col] in the CSR.
    indptr, indices = topo.indptr, topo.indices
    for src, dst in zip(node[ei[1]].tolist(), node[ei[0]].tolist()):
        nbrs = indices[indptr[src]: indptr[src + 1]]
        check(dst in nbrs, f"{what}: {src}->{dst} is not a graph edge")


def stage_serving(ds, *, fanout, frontier_cap, seed_buckets,
                  requests) -> dict:
    """One in-process serving front answering a handful of requests."""
    from glt_tpu.distributed import init_server
    from glt_tpu.serving import InferenceClient, ServingOptions

    topo = ds.get_graph().topo
    feat = ds.get_node_feature()
    labels = np.asarray(ds.get_node_label())
    rng = np.random.default_rng(7)
    srv = init_server(ds, serving=ServingOptions(
        num_neighbors=list(fanout), seed_buckets=tuple(seed_buckets),
        max_seeds_per_request=max(requests), frontier_cap=frontier_cap))
    try:
        srv.serving.engine.warmup()
        cli = InferenceClient(srv.addr, timeout=120.0)
        try:
            for k in requests:
                seeds = rng.choice(topo.num_nodes, size=k,
                                   replace=False).astype(np.int64)
                _check_reply(cli.subgraph(seeds), seeds.tolist(), topo,
                             feat, labels)
            stats = cli.stats()
        finally:
            cli.close()
    finally:
        srv.shutdown()
    check(stats["completed"] >= len(requests),
          f"server completed {stats['completed']} of {len(requests)}")
    check(stats["compiled_buckets"] == sorted(seed_buckets),
          f"compiled buckets {stats['compiled_buckets']}")
    return {"requests": list(requests),
            "compiled_buckets": stats["compiled_buckets"]}


def _once(fn):
    """``fn`` as a thunk that runs at most once (an XLA-arm reference is
    only computed if some point of its kernel got as far as an answer)."""
    cell = []

    def get():
        if not cell:
            cell.append(np.asarray(fn()))
        return cell[0]

    return get


def _bits_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and a.tobytes() == b.tobytes())


class _KernelTable:
    """Per kernel row: which points matched their XLA arm bit-for-bit,
    which the compiler refused (with the first message), which ran and
    disagreed."""

    def __init__(self):
        self.rows = {}

    def run(self, family: str, shape: str, point: str, kernel, reference):
        from glt_tpu.ops.tpu_limits import compiler_message

        row = self.rows.setdefault(f"{family}/{shape}", {
            "family": family, "ok": [], "refused": [], "mismatch": [],
            "message": None})
        try:
            got = np.asarray(kernel())
        except Exception as e:  # noqa: BLE001 — recorded in the table
            row["refused"].append(point)
            row["message"] = row["message"] or compiler_message(e)
            return
        row["ok" if _bits_equal(got, reference()) else "mismatch"].append(
            point)

    def refused_families(self) -> set:
        return {r["family"] for r in self.rows.values() if r["refused"]}

    def mismatches(self) -> list:
        return [f"{k}:{p}" for k, r in self.rows.items()
                for p in r["mismatch"]]


def stage_kernels(ds, *, fanout, batch, frontier_cap, dq_rows,
                  interpret: bool) -> dict:
    """Every Pallas kernel against its XLA arm, bit for bit.

    ``interpret=False`` compiles under Mosaic (the chip); ``True`` runs
    the same calls in interpret mode (the CPU test), where nothing is
    refused."""
    import functools

    import jax
    import jax.numpy as jnp

    from glt_tpu.ops import gather_pallas as gp
    from glt_tpu.ops import sample_pallas as sp
    from glt_tpu.ops.fused_frontier import (DEFAULT_VMEM_BUDGET,
                                            fused_frontier)
    from glt_tpu.ops.neighbor_sample import sample_neighbors
    from glt_tpu.sampler.neighbor_sampler import (hop_widths,
                                                  max_sampled_nodes)
    from glt_tpu.store import quant

    table = _KernelTable()
    feat = ds.get_node_feature()
    graph = ds.get_graph()
    n, d = feat.shape
    rng = np.random.default_rng(11)
    node_cap = max_sampled_nodes(batch, list(fanout), frontier_cap)

    # The bench's padding: feature width 100 is outside the kernel's
    # gate, 128 is its native width.
    d128 = -(-d // 128) * 128
    hot128 = jnp.pad(feat.hot_rows, ((0, 0), (0, d128 - d)))
    # Random rows with repeats, plus the table's last rows: the block
    # DMA's end clamp starts those tiles off their alignment.
    ids = rng.integers(0, n, node_cap).astype(np.int32)
    ids[:64] = np.arange(n - 64, n)
    ids = jnp.asarray(ids)

    ref = _once(lambda: jax.jit(gp._xla_gather)(hot128, ids))
    for t, r in gp.candidate_gather_params(d128, hot128.dtype):
        table.run("gather_f32", f"d{d128}_b{node_cap}", f"t{t}_r{r}",
                  functools.partial(gp.gather_rows_pallas, hot128, ids,
                                    interpret=interpret, tile_rows=t,
                                    ring_depth=r), ref)

    # Compressed tables over a row prefix (CONFIG1 "dq_rows").
    rows = min(n, dq_rows)
    host128 = np.asarray(hot128[:rows])
    dq_ids = jnp.asarray(rng.integers(0, rows, node_cap).astype(np.int32))
    fused_n = min(node_cap,
                  DEFAULT_VMEM_BUDGET // (d128 * 4) // 8 * 8)
    fused_ids = np.full((fused_n,), -1, np.int32)
    live = fused_n - fused_n // 8
    fused_ids[:live] = rng.integers(0, rows, live)   # repeats + padding
    fused_ids = jnp.asarray(fused_ids)
    fused_mode = "interpret" if interpret else "pallas"

    def fused(tbl, force, spec=None):
        return jax.jit(lambda t, i: fused_frontier(
            t, i, force=force, dequant=spec).features)(tbl, fused_ids)

    table.run("fused_f32", f"d{d128}_b{fused_n}", "default",
              lambda: fused(hot128, fused_mode),
              _once(lambda: fused(hot128, "xla")))
    for codec in ("bf16", "int8"):
        enc, spec = quant.encode(host128, codec)
        enc = jnp.asarray(enc)
        dq_ref = _once(lambda: jax.jit(lambda t, i: quant.dequantize(
            gp._xla_gather(t, i), spec))(enc, dq_ids))
        for t, r in gp.candidate_gather_params(d128, enc.dtype):
            table.run(f"gather_dq_{codec}", f"d{d128}_b{node_cap}",
                      f"t{t}_r{r}",
                      functools.partial(gp.gather_rows_pallas_dq, enc,
                                        dq_ids, spec, interpret=interpret,
                                        tile_rows=t, ring_depth=r), dq_ref)
        table.run(f"fused_dq_{codec}", f"d{d128}_b{fused_n}", "default",
                  lambda: fused(enc, fused_mode, spec),
                  _once(lambda: fused(enc, "xla", spec)))

    # Each hop's exact (width, fanout) of the config-1 sampler.  Every
    # thunk below is consumed inside its own loop iteration.
    key = jax.random.PRNGKey(3)
    for w, f in zip(hop_widths(batch, list(fanout), frontier_cap), fanout):
        seeds = jnp.asarray(rng.integers(0, n, w).astype(np.int32))

        def hop(sample, **kw):
            return jax.jit(lambda ip, ix, sd, k: sample(
                ip, ix, sd, f, k, with_edge=False, **kw).nbrs)(
                    graph.indptr, graph.indices, seeds, key)

        xla_hop = _once(lambda: hop(sample_neighbors, force="xla"))
        for p in sp.candidate_sample_params():
            if sp.pallas_sample_supported(graph.indices, p[2]):
                table.run("sample", f"w{w}_f{f}", sp._fmt_params(p),
                          lambda: hop(sp.sample_neighbors_pallas, params=p,
                                      interpret=interpret), xla_hop)

    check(not table.mismatches(),
          f"kernels disagree with their XLA arm: {table.mismatches()}")
    expected = set() if interpret else set(KNOWN_REFUSED)
    check(table.refused_families() == expected,
          f"refused kernel families {sorted(table.refused_families())} != "
          f"expected {sorted(expected)}: {table.rows}")
    return {"kernels": {k: {f: v for f, v in r.items() if f != "family"}
                        for k, r in table.rows.items()}}


def stage_dist(ds, train_idx, *, hidden, fanout, batch, frontier_cap,
               steps, devices) -> dict:
    """examples/dist_train_sage.py's calls at config-1 widths, on a mesh
    of every device given."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from glt_tpu.parallel import (init_dist_state, make_dist_train_step,
                                  put_sharded, shard_feature, shard_graph)

    s = len(devices)
    mesh = Mesh(np.array(devices), ("shard",))

    def in_use():
        stats = [dev.memory_stats() for dev in devices]
        return (None if any(st is None for st in stats)
                else [int(st["bytes_in_use"]) for st in stats])

    before = in_use()
    topo = ds.get_graph().topo
    labels = np.asarray(ds.get_node_label())
    g = put_sharded(shard_graph(topo, s), mesh, "shard")
    f = put_sharded(shard_feature(ds.get_node_feature()._host_full, s),
                    mesh, "shard")
    pad = s * g.nodes_per_shard - labels.shape[0]
    lab = put_sharded(np.pad(labels, (0, pad), constant_values=-1)
                      .reshape(s, g.nodes_per_shard), mesh, "shard")

    model = _config1_model(hidden, fanout)
    tx = optax.adam(1e-3)
    state = init_dist_state(model, tx, g, f, jax.random.PRNGKey(0),
                            list(fanout), batch, frontier_cap=frontier_cap)
    step = make_dist_train_step(model, tx, g, f, lab, mesh, list(fanout),
                                batch, frontier_cap=frontier_cap)
    rng = np.random.default_rng(0)
    per_shard = [train_idx[train_idx // g.nodes_per_shard == i]
                 for i in range(s)]
    check(all(len(p) > 0 for p in per_shard),
          f"a shard owns no training seed: {[len(p) for p in per_shard]}")
    losses = []
    for it in range(steps):
        seeds = np.stack([rng.choice(p, batch, replace=len(p) < batch)
                          for p in per_shard]).astype(np.int32)
        state, loss, acc = step(state, jnp.asarray(seeds),
                                jax.random.PRNGKey(it))
        losses.append(loss)
    losses = np.asarray(jax.device_get(losses))
    check(bool(np.isfinite(losses).all()), f"non-finite loss {losses}")

    for name, arr in (("g.indices", g.indices), ("f.rows", f.rows),
                      ("labels", lab)):
        shards = arr.addressable_shards
        check(len(shards) == s
              and {sh.device for sh in shards} == set(devices)
              and all(sh.data.shape[0] == 1 for sh in shards),
              f"{name}: {len(shards)} addressable shards of leading dims "
              f"{[sh.data.shape[0] for sh in shards]} on {s} devices")
    # Device 0 also holds the single-device dataset of the earlier
    # stages, so the balance is judged on what THIS stage added.
    after = in_use()
    held = None
    if before is not None:
        held = [a - b for a, b in zip(after, before)]
        check(min(held) > 0 and max(held) <= 2 * min(held),
              f"bytes the dist stage holds per device differ by more than "
              f"2x: {held}")
    return {"mesh_devices": s, "steps": steps,
            "loss_last": round(float(losses[-1]), 4),
            "bytes_in_use_per_device": after,
            "bytes_held_by_stage_per_device": held}


# -- driver -----------------------------------------------------------------

def run_all(cfg: dict, devices, meter: CompileMeter, *, interpret: bool,
            peak_flops=None) -> dict:
    """Every stage at the sizes in ``cfg``; returns the report whose
    ``ok`` is the conjunction of the stages'."""
    from examples.datasets import synthetic_products

    report = {"ok": False, "stages": {}}
    run_stage(report, meter, "probes", stage_probes,
              cfg["probe_matmul_n"], cfg["probe_matmul_chain"],
              peak_flops=peak_flops)

    _log(f"building synthetic_products(scale={cfg['scale']})")
    t0 = time.perf_counter()
    ds, train_idx = synthetic_products(scale=cfg["scale"])
    report["dataset"] = {
        "nodes": int(ds.get_graph().topo.num_nodes),
        "edges": int(ds.get_graph().topo.num_edges),
        "train_seeds": int(len(train_idx)),
        "build_s": round(time.perf_counter() - t0, 1)}

    width = {k: cfg[k] for k in ("hidden", "fanout", "batch",
                                 "frontier_cap")}
    run_stage(report, meter, "scanned", stage_scanned, ds, train_idx,
              group=cfg["group"], groups=cfg["groups"], **width)
    run_stage(report, meter, "eager", stage_eager, ds, train_idx,
              batches=cfg["eager_batches"], **width)
    run_stage(report, meter, "serving", stage_serving, ds,
              fanout=cfg["fanout"], frontier_cap=cfg["frontier_cap"],
              seed_buckets=cfg["seed_buckets"],
              requests=cfg["serving_requests"])
    run_stage(report, meter, "kernels", stage_kernels, ds,
              fanout=cfg["fanout"], batch=cfg["batch"],
              frontier_cap=cfg["frontier_cap"], dq_rows=cfg["dq_rows"],
              interpret=interpret)
    run_stage(report, meter, "dist", stage_dist, ds, train_idx,
              steps=cfg["dist_steps"], devices=devices, **width)
    report["ok"] = all(st["ok"] for st in report["stages"].values())
    return report


def main() -> int:
    from glt_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    facts = device_facts()
    _log(json.dumps(facts))
    platform = facts["device"]["platform"]
    if platform != "tpu":
        print(f"chip_smoke.py runs on platform 'tpu' only; JAX found "
              f"platform {platform!r} ({facts['device']['count']} x "
              f"{facts['device']['kind']!r})", file=sys.stderr)
        return 1

    import jax

    from glt_tpu.obs.roofline import peak_bf16_tflops

    peak = peak_bf16_tflops(facts["device"]["kind"]) * 1e12
    t0 = time.perf_counter()
    report = run_all(CONFIG1, jax.devices(), CompileMeter(),
                     interpret=False, peak_flops=peak)
    ok = report.pop("ok")
    print(json.dumps({"report": {
        "ok": ok, **facts, "compile_cache_dir": cache_dir,
        "wall_s": round(time.perf_counter() - t0, 1), **report}}),
        flush=True)
    print(json.dumps(verdict(ok, facts)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
