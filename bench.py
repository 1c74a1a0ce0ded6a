"""Headline benchmark: neighbor-sampling throughput (sampled edges/sec).

Metric definition follows the reference's ``benchmarks/api/bench_sampler.py``
(:27-54): multi-hop neighbor sampling with fanout [15, 10, 5], batch 1024,
on an ogbn-products-scale graph, reporting "Sampled Edges per sec (M)".

Graph: **power-law** degree sequence (``benchmarks/graph_gen.py``), so both
kernel branches (Floyd's k-subset for ``deg > fanout``, take-all for
``deg <= fanout``) and hub rows are exercised — not the uniform
fixed-degree graph of rounds 1-2.

Baselines (see BASELINE.md "Baseline anchors"):
  * ``vs_ref_cpu`` — MEASURED: the reference's own CPU sampling engine
    (``csrc/cpu/random_sampler.cc`` + ``inducer.cc``) compiled from
    /root/reference and run on this host over the *same* graph and seed
    batches (``benchmarks/ref_baseline/run_ref_cpu.py``).
  * ``vs_baseline`` — ESTIMATED single-A100 throughput for the reference's
    CUDA engine on this metric; derivation in BASELINE.md (launch/sync
    overhead-bound ceiling analysis, cross-checked against published
    GPU-sampler numbers). The reference publishes no absolute number.

Timing is reported three ways to separate host dispatch from device time:
  * pipelined  — enqueue all iterations; a device-side running total
    chains every batch, and ONE host fetch of that scalar at the end is
    the sync point (headline; matches the async prefetch the training
    loop actually uses).
  * dispatch   — per-call time until the async dispatch returns (host
    cost only).
  * serialized — fetch each batch's edge count to host every iteration
    (per-batch latency: device step + one host round trip).

NOTE on sync: every timed region ends in a **host value fetch**.  On
the TPU v5e ``jax.block_until_ready`` waits as well (chip_smoke.py's
matmul-chain probe checks it on every run); the fetch is kept because
it also chains the device-side totals the loops accumulate.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Runs on a TPU and refuses any other backend; ``GLT_BENCH_SCALE=small``
asks for the CPU-sized smoke explicitly (tiny graph, any backend).  A
run that does not finish — an exception, or the ``GLT_BENCH_DEADLINE``
watchdog — exits non-zero.
"""
import json
import os
import sys
import time

import numpy as np

from glt_tpu.obs import prune_unmeasured  # stdlib-only; no jax at import

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarks"))


def _round(v, nd):
    """Round a measured value; ``None`` (not measured) passes through so
    ``prune_unmeasured`` drops the key — never emit an in-band sentinel
    like ``-1.0`` (it's indistinguishable from a measured value)."""
    return None if v is None else round(v, nd)


def _emit(out: dict) -> None:
    """Print the one JSON result line; GLT_BENCH_OUT also writes it to a
    file so ``scripts/bench_compare.py --fresh`` can judge this run
    against a BENCH_r*.json history without scraping stdout."""
    line = json.dumps(out)
    print(line, flush=True)
    path = os.environ.get("GLT_BENCH_OUT")
    if path:
        # Atomic publish (GLT011): bench_compare / obs.regress read this
        # file from other processes — never expose a torn line.
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(line + "\n")
        os.replace(tmp, path)

# Estimated single-A100 sampled-edges/sec (M) for the reference CUDA engine,
# fanout [15,10,5] batch 1024 (derivation: BASELINE.md "Baseline anchors").
BASELINE_A100_M = 600.0
# Measured on this host (1 CPU thread), reference CPU engine, identical
# power-law graph + seeds: benchmarks/ref_baseline/run_ref_cpu.py.
REF_CPU_MEASURED_M = 5.776

FANOUT = [15, 10, 5]
BATCH = 1024
WARMUP = 3
ITERS = 20


def _progress(msg: str) -> None:
    """Stage markers on stderr (stdout stays one JSON line)."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# Filled section by section; the watchdog prints it, marked partial,
# before it fails the run at the deadline.
_PARTIAL = {}
_DONE = False


def make_routing_only_fn(widths, node_cap, nodes_per_shard, num_shards,
                         route="auto"):
    """Jitted program running JUST the routing prologue one dist batch
    pays: one ``build_routing`` per hop frontier plus the single shared
    plan the fused feature+label gather builds over the node capacity.
    Isolates ``dist_routing_ms`` from the exchange's sampling and
    collective legs (build_routing is collective-free, so this runs
    outside shard_map).  Also imported by the dist-path smoke test.
    """
    import jax
    import jax.numpy as jnp

    from glt_tpu.parallel.dist_sampler import build_routing

    widths = [int(w) for w in widths]

    @jax.jit
    def fn(ids):
        # Sums over every Routing field defeat dead-code elimination.
        tot = jnp.zeros((), jnp.int32)
        for w in widths:
            r = build_routing(ids[:w], nodes_per_shard, num_shards,
                              route=route)
            tot = tot + r.buckets.sum() + r.slot.sum() + r.dropped
        r = build_routing(ids[:node_cap], nodes_per_shard, num_shards,
                          route=route)
        return tot + r.buckets.sum() + r.slot.sum() + r.dropped

    return fn


def _watchdog(deadline_s: float) -> None:
    import threading

    def guard():
        time.sleep(deadline_s)
        if not _DONE:
            _progress(f"deadline {deadline_s:.0f}s hit — emitting "
                      f"partial results")
            out = prune_unmeasured(dict(_PARTIAL))
            out.setdefault("metric",
                           "neighbor_sampling_throughput_f15_10_5_b1024")
            out.setdefault("value", -1)
            out.setdefault("unit", "M sampled edges/s")
            out.setdefault("vs_baseline", -1)
            out["partial"] = True
            _emit(out)
            os._exit(1)

    threading.Thread(target=guard, daemon=True,
                     name="bench-watchdog").start()


def main():
    small = os.environ.get("GLT_BENCH_SCALE") == "small"
    import contextlib

    import jax

    from glt_tpu.utils import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()
    _progress(f"platform {dev[0].platform!r}, kind {dev[0].device_kind!r}, "
              f"{len(dev)} device(s)")
    if dev[0].platform != "tpu" and not small:
        raise SystemExit(
            f"bench.py measures a TPU; JAX found platform "
            f"{dev[0].platform!r} ({len(dev)} x {dev[0].device_kind!r}). "
            f"Set GLT_BENCH_SCALE=small for the CPU-sized smoke.")

    _watchdog(float(os.environ.get("GLT_BENCH_DEADLINE", "2700")))

    from glt_tpu.data.graph import Graph
    from glt_tpu.data.topology import CSRTopo
    from glt_tpu.sampler.base import NodeSamplerInput
    from glt_tpu.sampler.neighbor_sampler import NeighborSampler
    from glt_tpu.utils import profile
    from graph_gen import build_graph, seed_batches

    # --- host round-trip probe: a trivial jit round trip measures the
    # host<->device dispatch+fetch latency of this machine.  Median of 7
    # after warmup.
    _progress("host round-trip probe")
    import jax.numpy as _jnp

    _triv = jax.jit(lambda a: a + 1)
    z = _jnp.zeros((), _jnp.int32)
    for _ in range(3):
        z = _triv(z)
    int(z)
    rtts = []
    for _ in range(7):
        t0 = time.perf_counter()
        int(_triv(z))  # dispatch + execute + fetch
        rtts.append(time.perf_counter() - t0)
    host_roundtrip_ms = float(np.median(rtts) * 1e3)
    _PARTIAL["host_roundtrip_ms"] = round(host_roundtrip_ms, 2)

    _progress("building graph")
    n, indptr, indices = build_graph(small)

    # Bypass CSRTopo's COO round-trip: install CSR arrays directly.
    topo = CSRTopo.__new__(CSRTopo)
    topo._indptr = indptr.astype(np.int32)
    topo._indices = indices.astype(np.int32)
    topo._edge_ids = np.arange(indices.shape[0], dtype=np.int32)
    topo._edge_weights = None
    graph = Graph(topo, mode="DEVICE")

    import jax.numpy as jnp

    # with_edge=False matches the reference bench exactly: its sampler
    # default is with_edge=False (neighbor_sampler.py:44) and
    # bench_sampler.py uses the default — edge ids are never gathered.
    sampler = NeighborSampler(graph, FANOUT, batch_size=BATCH, seed=0,
                              with_edge=False)
    # Pre-stage seed batches in HBM (the reference's pinned-memory
    # DataLoader + .to(device) prefetch).
    batches = [jnp.asarray(b.astype(np.int32))
               for b in seed_batches(n, BATCH, WARMUP + ITERS)]

    # Device-side running total: chains a data dependency through every
    # batch so one final host fetch waits for ALL of them.
    acc_edges = jax.jit(lambda tot, nse: tot + nse.sum())

    _progress("sampler warmup (first compile)")
    total = jnp.zeros((), jnp.int32)
    for i in range(WARMUP):
        out = sampler.sample_from_nodes(NodeSamplerInput(batches[i]))
        total = acc_edges(total, out.num_sampled_edges)
    int(total)  # sync

    # --- pipelined (headline): enqueue everything, one fetch at the end.
    # GLT_PROFILE_DIR captures a jax profiler trace of this region.
    _progress("pipelined sampler timing")
    # GLT_PROFILE_TRIGGER_DIR arms spike/SLO-triggered captures for the
    # rest of the run (obs/profiler.py; no-op when unset).
    from glt_tpu.obs import profiler as obs_profiler
    obs_profiler.maybe_arm_from_env()
    prof_dir = os.environ.get("GLT_PROFILE_DIR")
    ctx = profile.trace(prof_dir) if prof_dir else contextlib.nullcontext()
    meter = profile.ThroughputMeter()
    with ctx, meter.measure():
        total = jnp.zeros((), jnp.int32)
        dispatch_s = 0.0
        t0 = time.perf_counter()
        for i in range(ITERS):
            td = time.perf_counter()
            with profile.annotate("sample_batch"):
                out = sampler.sample_from_nodes(
                    NodeSamplerInput(batches[WARMUP + i]))
            dispatch_s += time.perf_counter() - td
            total = acc_edges(total, out.num_sampled_edges)
        total_edges = float(int(total))  # host fetch = true sync
        pipelined_s = time.perf_counter() - t0
        meter.add(edges=total_edges, batches=ITERS)

    _progress("serialized sampler timing")
    # --- serialized: per-batch latency (device + host round trip). ---
    t0 = time.perf_counter()
    for i in range(ITERS):
        out = sampler.sample_from_nodes(NodeSamplerInput(batches[WARMUP + i]))
        np.asarray(out.num_sampled_edges)  # per-batch fetch = true sync
    serialized_s = time.perf_counter() - t0

    _PARTIAL.update({
        "metric": "neighbor_sampling_throughput_f15_10_5_b1024",
        "value": round(total_edges / pipelined_s / 1e6, 3),
        "unit": "M sampled edges/s",
        "vs_baseline": round(total_edges / pipelined_s / 1e6
                             / BASELINE_A100_M, 4),
        "serialized_ms_per_batch": round(serialized_s / ITERS * 1e3, 3),
        "pipelined_ms_per_batch": round(pipelined_s / ITERS * 1e3, 3),
    })

    # --- no-dedup leaves (secondary): last_hop_dedup=False skips the
    # inducer at the widest frontier — same edge multiset and shapes;
    # revisited interior nodes become fresh leaves (tree-unrolled
    # GraphSAGE semantics).  Separately reported, NOT the headline,
    # because the node-list contract differs from the reference's.
    _progress("no-dedup leaves timing")
    s_fast = NeighborSampler(graph, FANOUT, batch_size=BATCH, seed=0,
                             with_edge=False, last_hop_dedup=False)
    total = jnp.zeros((), jnp.int32)
    for i in range(2):
        total = acc_edges(total, s_fast.sample_from_nodes(
            NodeSamplerInput(batches[i])).num_sampled_edges)
    int(total)  # warm
    total = jnp.zeros((), jnp.int32)
    t0 = time.perf_counter()
    for i in range(ITERS):
        out = s_fast.sample_from_nodes(NodeSamplerInput(batches[WARMUP + i]))
        total = acc_edges(total, out.num_sampled_edges)
    fast_edges = float(int(total))
    fast_s = time.perf_counter() - t0
    fast_m = fast_edges / fast_s / 1e6

    # --- batched (secondary metric; the JSON's "value"/"vs_baseline"
    # come from the pipelined meter above): G batches chained per device
    # program, the TPU analog of the reference's per-worker in-flight
    # concurrency (worker_concurrency async batches,
    # dist_options.py:21-100).  Device-time parity with single-stream at
    # batch 1024; amortises host dispatch.
    _progress("batched G8 timing")
    G = 8
    rounds = max(ITERS // G, 1)
    stacked = [jnp.stack(batches[WARMUP + r * G: WARMUP + (r + 1) * G])
               for r in range(rounds)]
    total = jnp.zeros((), jnp.int32)
    total = acc_edges(total, sampler.sample_from_nodes_batched(
        stacked[0]).num_sampled_edges)
    int(total)  # warm
    total = jnp.zeros((), jnp.int32)
    t0 = time.perf_counter()
    for r in range(rounds):
        out = sampler.sample_from_nodes_batched(stacked[r])
        total = acc_edges(total, out.num_sampled_edges)
    batched_edges = float(int(total))
    batched_s = time.perf_counter() - t0
    batched_m = batched_edges / batched_s / 1e6

    # --- train-side metrics (VERDICT r3 #2/#4, r4 #1/#2/#3): occupancy
    # calibration, sample/gather/train split at BOTH the worst-case cap
    # (round-4-comparable) and the occupancy-sized cap with bf16 matmuls
    # (the flagship config), then one ACTUAL measured config-1 epoch on
    # the flagship path — the same code path the README quotes.
    import optax

    from glt_tpu.data.feature import Feature
    from glt_tpu.models import (
        GraphSAGE,
        TrainState,
        make_train_step,
    )
    from glt_tpu.loader.transform import to_batch
    from glt_tpu.models.train import make_gather_xy
    from glt_tpu.sampler.neighbor_sampler import calibrate_node_capacity

    _progress("train-side section: building model/feature")
    hidden = 64 if small else 256
    dim, classes, fcap = (32, 47, 1024) if small else (100, 47, 8192)
    t_iters = 4 if small else 10
    rng_np = np.random.default_rng(1)
    feat = Feature(rng_np.normal(0, 1, (n, dim)).astype(np.float32))
    labels = jnp.asarray(rng_np.integers(0, classes, n).astype(np.int32))
    tx = optax.adam(1e-3)
    base = jax.random.PRNGKey(7)
    hot = feat.hot_rows

    def sync(x):
        return float(np.asarray(jax.device_get(x)).ravel()[0])

    def measure_paths(model, tsampler, tag):
        """Warm + time sample / gather / train / serial for one
        (model, sampler) config.  Every timed region ends in a host
        fetch (module docstring).
        The fused (scanned) path is timed at epoch scale below — the
        overlapped single-program path was deleted (three rounds at
        0.97-0.99x; see glt_tpu/models/train.py)."""
        cap, ecap = tsampler.node_capacity, tsampler.edge_capacity
        x0 = jnp.zeros((cap, dim), jnp.float32)
        ei0 = jnp.full((2, ecap), -1, jnp.int32)
        m0 = jnp.zeros((ecap,), bool)
        params = model.init({"params": jax.random.PRNGKey(0)}, x0, ei0, m0)
        state0 = TrainState(params=params, opt_state=tx.init(params),
                            step=jnp.zeros((), jnp.int32))
        _gather = jax.jit(make_gather_xy(feat.id2index))

        def gather_j(out):
            return _gather(hot, labels, out)

        tstep = make_train_step(model, tx, batch_size=BATCH)
        tg = tsampler.graph

        def sample_first(seeds, key):
            # The sampler's own jitted program (the scanned path traces
            # the same _sample_impl; no second compile of sampling).
            return tsampler._sample_jit(tg.indptr, tg.indices,
                                        tg.gather_edge_ids,
                                        jnp.asarray(seeds, jnp.int32),
                                        key)

        _progress(f"[{tag}] warm compiles (sample/gather/train)")
        out0 = sample_first(batches[0], jax.random.fold_in(base, 999))
        x, y = gather_j(out0)
        b0 = to_batch(out0, x=x, y=y, batch_size=BATCH)
        st, l, _ = tstep(state0, b0)
        sync(l)

        _progress(f"[{tag}] train/gather/sample timing")
        st = state0
        t0 = time.perf_counter()
        for i in range(t_iters):
            st, l, _ = tstep(st, b0)
        sync(l)
        r = {"train_ms": (time.perf_counter() - t0) / t_iters * 1e3}

        tot = jnp.zeros((), jnp.float32)
        accf = jax.jit(lambda t, x: t + x.sum())
        t0 = time.perf_counter()
        for i in range(t_iters):
            x, _ = gather_j(out0)
            tot = accf(tot, x)
        sync(tot)
        r["gather_ms_naive"] = (time.perf_counter() - t0) / t_iters * 1e3

        # Dedup variant on the SAME batch: unique -> row gather ->
        # scatter back (bit-identical x).  The headline gather_ms is the
        # per-shape winner — the warmup auto-pick the loaders use.
        _gather_d = jax.jit(make_gather_xy(feat.id2index, dedup=True))
        x, _ = _gather_d(hot, labels, out0)   # warm compile
        sync(accf(jnp.zeros((), jnp.float32), x))
        tot = jnp.zeros((), jnp.float32)
        t0 = time.perf_counter()
        for i in range(t_iters):
            x, _ = _gather_d(hot, labels, out0)
            tot = accf(tot, x)
        sync(tot)
        r["gather_ms_dedup"] = (time.perf_counter() - t0) / t_iters * 1e3
        r["gather_ms"] = min(r["gather_ms_naive"], r["gather_ms_dedup"])
        r["gather_path"] = ("dedup" if r["gather_ms_dedup"]
                            <= r["gather_ms_naive"] else "naive")

        tot = jnp.zeros((), jnp.int32)
        t0 = time.perf_counter()
        for i in range(t_iters):
            o = sample_first(batches[(WARMUP + i) % len(batches)],
                             jax.random.fold_in(base, i))
            tot = acc_edges(tot, o.num_sampled_edges)
        sync(tot)
        r["sample_ms"] = (time.perf_counter() - t0) / t_iters * 1e3

        _progress(f"[{tag}] serial step timing")
        st = state0
        t0 = time.perf_counter()
        for i in range(t_iters):
            o = sample_first(batches[(WARMUP + i) % len(batches)],
                             jax.random.fold_in(base, i))
            x, y = gather_j(o)
            st, l, _ = tstep(st, to_batch(o, x=x, y=y, batch_size=BATCH))
        sync(l)
        r["serial_step_ms"] = (time.perf_counter() - t0) / t_iters * 1e3
        r["_handles"] = {"sample": sample_first, "state0": state0,
                        "tstep": tstep, "gather": gather_j}
        return r

    # Round-4-comparable baseline: worst-case cap, f32.
    model_f32 = GraphSAGE(hidden_features=hidden, out_features=classes,
                          num_layers=len(FANOUT), dropout_rate=0.0)
    tsampler = NeighborSampler(graph, FANOUT, batch_size=BATCH, seed=0,
                               with_edge=False, frontier_cap=fcap)
    full = measure_paths(model_f32, tsampler, "full-cap f32")
    cap = tsampler.node_capacity

    # --- occupancy calibration (VERDICT r4 #1): actual unique-node count
    # per batch vs the worst-case padded cap.  Reuses the full sampler's
    # compiled program; counts ride device-side, ONE fetch at the end.
    _progress("occupancy measurement")
    from glt_tpu.sampler.neighbor_sampler import measure_occupancy

    occ_n = 8 if small else 24
    occ = measure_occupancy(
        tsampler, [batches[i % len(batches)] for i in range(occ_n)])
    node_cap = calibrate_node_capacity(
        tsampler, None, counts=occ, multiple=64 if small else 256)
    occupancy_p50 = float(np.percentile(occ, 50))
    occupancy_p99 = float(np.percentile(occ, 99))

    # Flagship config: occupancy-sized cap + bf16 matmuls.
    model_bf16 = GraphSAGE(hidden_features=hidden, out_features=classes,
                           num_layers=len(FANOUT), dropout_rate=0.0,
                           dtype=jnp.bfloat16)
    csampler = NeighborSampler(graph, FANOUT, batch_size=BATCH, seed=0,
                               with_edge=False, frontier_cap=fcap,
                               node_capacity=node_cap)
    capped = measure_paths(model_bf16, csampler, "occ-cap bf16")

    # --- gather variants (ISSUE 2): dedup ratio, cross-batch HBM cache
    # hit rate, and per-variant delivered bandwidth on the SAME sampled
    # batches.  Payload bandwidth = valid rows x d x 4B / time — the
    # useful bytes the model consumes, identical numerator across
    # variants so the times are directly comparable.
    _progress("gather variants: dedup / cache / bandwidth")
    from glt_tpu.data.feature_cache import cache_init, cache_stats
    from glt_tpu.models.train import make_cached_gather_xy
    from glt_tpu.ops.dedup_gather import dedup_counts

    c_sample_first = capped["_handles"]["sample"]
    gouts = [c_sample_first(batches[(WARMUP + i) % len(batches)],
                            jax.random.fold_in(base, 600 + i))
             for i in range(t_iters)]
    accf = jax.jit(lambda t, x: t + x.sum())

    @jax.jit
    def dd(tot, o):
        v, u = dedup_counts(o.node)
        return tot[0] + v, tot[1] + u

    counts = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    for o in gouts:
        counts = dd(counts, o)
    n_valid, n_uniq = float(int(counts[0])), float(int(counts[1]))
    dedup_ratio = n_valid / max(n_uniq, 1.0)
    payload_gb = n_valid * dim * 4 / 1e9  # useful bytes across all gouts

    def one_pass(fn):
        """One pass over the distinct batches, ONE host fetch at the end
        (the sync — module docstring)."""
        tot = jnp.zeros((), jnp.float32)
        t0 = time.perf_counter()
        for o in gouts:
            tot = accf(tot, fn(o))
        sync(tot)
        return time.perf_counter() - t0

    gnaive = jax.jit(make_gather_xy(feat.id2index))
    gdedup = jax.jit(make_gather_xy(feat.id2index, dedup=True))
    gcached = jax.jit(make_cached_gather_xy(feat.id2index))
    cache_cap = min(n, 1 << 17)   # <= 131072 rows (~50 MB at d=100 f32)
    gcache_state = [cache_init(n, cache_cap, dim, jnp.float32)]

    def run_cached(o):
        gcache_state[0], x, _ = gcached(gcache_state[0], hot, labels, o)
        return x

    one_pass(lambda o: gnaive(hot, labels, o)[0])      # compile warm
    t_naive = one_pass(lambda o: gnaive(hot, labels, o)[0])
    one_pass(lambda o: gdedup(hot, labels, o)[0])
    t_dedup = one_pass(lambda o: gdedup(hot, labels, o)[0])
    # Cached variant: pass 1 runs COLD (compile + fills; its counters =
    # true cross-batch reuse among distinct batches), the timed pass 2
    # is the warm steady state (repeat visits served from the HBM cache).
    one_pass(run_cached)
    s_cold = cache_stats(gcache_state[0])
    t_cached = one_pass(run_cached)
    s_warm = cache_stats(gcache_state[0])
    warm_hits = s_warm["hits"] - s_cold["hits"]
    warm_lookups = s_warm["lookups"] - s_cold["lookups"]
    variant_s = {"naive": t_naive, "dedup": t_dedup,
                 "dedup_cache": t_cached}
    gather_best = min(variant_s, key=variant_s.get)
    gather_gb_s = {k: payload_gb / v for k, v in variant_s.items()}
    _PARTIAL.update({
        "dedup_ratio": round(dedup_ratio, 3),
        "cache_hit_rate": round(warm_hits / max(warm_lookups, 1), 4),
        "cache_hit_rate_cold": round(s_cold["hit_rate"], 4),
        "gather_gb_s_naive": round(gather_gb_s["naive"], 3),
        "gather_gb_s_dedup": round(gather_gb_s["dedup"], 3),
        "gather_gb_s_dedup_cache": round(gather_gb_s["dedup_cache"], 3),
    })

    # --- memcpy roofline (ISSUE 6 / ROADMAP item 1's success metric):
    # the measured streaming-copy ceiling of THIS device through THIS
    # runtime, so the gather bandwidths above read as achieved-vs-peak
    # fractions rather than fractions of a datasheet constant
    # (est_hbm_fraction).  Methodology: glt_tpu/obs/roofline.py.
    _progress("memcpy roofline")
    from glt_tpu.obs.roofline import measure_memcpy_roofline, roofline_fraction

    roof = measure_memcpy_roofline(nbytes=1 << 22 if small else 1 << 27,
                                   iters=3 if small else 10)
    memcpy_roofline_gb_s = roof["memcpy_gb_s"]
    gather_roofline_frac = roofline_fraction(gather_gb_s[gather_best],
                                             memcpy_roofline_gb_s)
    # Per-variant achieved-vs-measured-peak fractions (ISSUE 10): the
    # headline gather_roofline_frac is the winner's; each variant's own
    # fraction rides beside it so a regression in ONE path (e.g. the
    # capped-shape tile choice) is visible even while another variant
    # holds the headline.
    gather_roofline_by_variant = {
        f"gather_roofline_frac_{k}": round(
            roofline_fraction(v, memcpy_roofline_gb_s), 4)
        for k, v in gather_gb_s.items()}
    _PARTIAL.update({
        "memcpy_roofline_gb_s": round(memcpy_roofline_gb_s, 2),
        "gather_roofline_frac": round(gather_roofline_frac, 4),
        **gather_roofline_by_variant,
    })

    # --- obs overhead (ISSUE 6 acceptance: metrics-disabled overhead on
    # the serial step < 2%): (a) the measured per-call cost of a disabled
    # span + histogram-timer + counter-inc triple; (b) the serial step
    # re-run with that triple at the host boundary, A/B against the
    # uninstrumented serial loop above.
    _progress("obs disabled-overhead (no-op probe + serial step A/B)")
    from glt_tpu.obs import metrics as obs_metrics
    from glt_tpu.obs.trace import span as obs_span

    obs_metrics.disable()
    _c_probe = obs_metrics.counter("glt.bench.noop_probe", "overhead probe")
    _h_probe = obs_metrics.histogram("glt.bench.noop_probe_ms")
    noop_n = 200_000
    t0 = time.perf_counter()
    for _ in range(noop_n):
        with obs_span("noop"), _h_probe.time():
            _c_probe.inc()
    obs_noop_ns = (time.perf_counter() - t0) / noop_n * 1e9
    st = capped["_handles"]["state0"]
    tstep_c = capped["_handles"]["tstep"]
    gather_j_c = capped["_handles"]["gather"]
    sample_first_c = capped["_handles"]["sample"]
    t0 = time.perf_counter()
    for i in range(t_iters):
        with obs_span("bench.serial_step"), _h_probe.time():
            o = sample_first_c(batches[(WARMUP + i) % len(batches)],
                               jax.random.fold_in(base, 700 + i))
            x, y = gather_j_c(o)
            st, l, _ = tstep_c(st, to_batch(o, x=x, y=y,
                                            batch_size=BATCH))
            _c_probe.inc()
    sync(l)
    serial_obs_ms = (time.perf_counter() - t0) / t_iters * 1e3
    obs_overhead_frac = (serial_obs_ms
                         / max(capped["serial_step_ms"], 1e-9) - 1.0)
    _PARTIAL.update({
        "obs_noop_ns_per_call": round(obs_noop_ns, 1),
        "serial_step_ms_obs_disabled": round(serial_obs_ms, 2),
        "obs_disabled_overhead_frac": round(obs_overhead_frac, 4),
    })

    # --- per-stage roofline attribution (ISSUE 13): expected-bytes
    # models (glt_tpu/obs/attrib.py) over the measured per-stage times,
    # so every pipeline stage — not just gather — reads as a fraction of
    # the measured memcpy ceiling.  The headline gather_roofline_frac
    # above stays authoritative (measured payload bytes); the table's
    # gather row uses the same payload numerator per batch.  train's
    # bytes prefer XLA's own cost_analysis accounting, falling back to
    # the analytic 5x-params + 2x-features floor.
    _progress("stage roofline attribution")
    from glt_tpu.obs import attrib

    cnt2 = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    t0 = time.perf_counter()
    for o in gouts:
        cnt2 = dd(cnt2, o)
    sync(cnt2[0])
    dedup_ms = (time.perf_counter() - t0) / len(gouts) * 1e3

    o0 = gouts[0]
    x0b, y0b = gather_j_c(o0)
    b_attr = to_batch(o0, x=x0b, y=y0b, batch_size=BATCH)
    train_bytes = attrib.compiled_cost_bytes(tstep_c, st, b_attr)
    train_bytes_source = "xla_cost_analysis"
    if train_bytes is None:
        train_bytes_source = "analytic"
        train_bytes = attrib.train_expected_bytes(
            attrib.param_nbytes(st.params),
            csampler.node_capacity * dim * 4)
    stage_ms = {
        "sample": capped["sample_ms"],
        "dedup": dedup_ms,
        "gather": capped["gather_ms"],
        "train": capped["train_ms"],
    }
    stage_bytes = {
        "sample": attrib.sample_expected_bytes(BATCH, FANOUT),
        "dedup": attrib.dedup_expected_bytes(csampler.node_capacity),
        "gather": attrib.gather_expected_bytes(
            n_valid / max(len(gouts), 1), dim),
        "train": train_bytes,
    }
    stage_roofline = attrib.stage_roofline_table(
        stage_ms, stage_bytes, memcpy_roofline_gb_s)
    _PARTIAL.update({
        "stage_roofline": stage_roofline,
        "train_bytes_source": train_bytes_source,
        **{k: v for k, v in attrib.flat_roofline_fracs(
            stage_roofline, skip=("gather",)).items()},
    })

    # Tiled-DMA Pallas kernel sweep at its native width (d % 128 == 0):
    # pad the feature rows to 128 columns and sweep the (tile_rows,
    # ring_depth) grid against XLA's gather on real sampled id patterns
    # at BOTH gather shapes this run uses — the full worst-case cap and
    # the occupancy-calibrated cap.  Autotune is keyed by exact batch
    # size, so the capped shape gets its own winner instead of
    # inheriting the full-cap point; gather_rows(force='auto') serves
    # each shape its own measured (tile, ring).
    _progress("pallas tiled kernel sweep (d=128, full + capped shapes)")
    from glt_tpu.ops.gather_pallas import (
        autotune_gather_rows,
        autotune_table,
    )

    # None = not measured on this backend (omitted from the JSON — the
    # sentinel-leak fix; see prune_unmeasured).
    kernel_choice, t_xla128, t_pal128 = "xla", None, None
    gather_autotune = None
    if jax.default_backend() == "tpu":
        hot128 = jnp.pad(hot, ((0, 0), (0, 128 - dim % 128)))
        rng_pr = np.random.default_rng(9)
        probe_full = jnp.asarray(
            rng_pr.integers(0, n, cap).astype(np.int32))
        probe_capped = jnp.clip(gouts[0].node.astype(jnp.int32), 0, n - 1)
        # A candidate the compiler refuses is in the table's "refused"
        # map with its message; anything else that fails, fails the run.
        kernel_choice = autotune_gather_rows(hot128, probe_capped)
        if int(probe_full.shape[0]) != int(probe_capped.shape[0]):
            autotune_gather_rows(hot128, probe_full)
        table = autotune_table()
        key128 = (f"d128_b{int(probe_capped.shape[0])}_"
                  f"{hot128.dtype}")
        entry = table.get(key128, {"ms": {}})
        t_xla128 = entry["ms"].get("xla")
        pal = {k: v for k, v in entry["ms"].items() if k != "xla"}
        t_pal128 = min(pal.values()) if pal else None
        gather_autotune = table
    _PARTIAL.update(prune_unmeasured({
        "gather_xla_ms_d128": _round(t_xla128, 3),
        "gather_pallas_ms_d128": _round(t_pal128, 3),
        "gather_kernel_choice": kernel_choice,
    }))

    # --- sampling-wall sweep (ISSUE 15): degree-binned Pallas sampling
    # vs XLA.  autotune_sample runs at each hop's EXACT (width, fanout)
    # shape for both samplers this bench uses (full + occupancy-capped —
    # the day-one exact-shape keying; a capped hop never inherits the
    # full-cap winner), then the full multi-hop program is A/B-timed
    # with the neighbor-read seam pinned each way.  Off-TPU the sweep
    # pins 'xla' (empty ms maps, the table still records the exact-shape
    # keys) and the pallas side of the A/B is omitted — a CPU run's
    # numbers stay honest rather than flattering.
    _progress("sampling kernel sweep (degree-binned pallas vs xla)")
    from glt_tpu.obs import compilewatch as obs_compilewatch
    from glt_tpu.ops.sample_pallas import (
        autotune_sample,
        sample_autotune_table,
    )

    sample_kernel_choice = "xla"
    for smp in (tsampler, csampler):
        for w_hop, f_hop in zip(smp._widths, smp.num_neighbors):
            probe = jnp.arange(int(w_hop), dtype=jnp.int32) % n
            ch = autotune_sample(graph.indptr, graph.indices, probe,
                                 int(f_hop), with_edge=smp.with_edge)
            if ch == "pallas":
                sample_kernel_choice = "pallas"
    sample_autotune = sample_autotune_table()

    def time_forced_sampler(force):
        sv = NeighborSampler(graph, FANOUT, batch_size=BATCH, seed=0,
                             with_edge=False, frontier_cap=fcap,
                             sample_force=force)

        def go(i):
            return sv._sample_jit(graph.indptr, graph.indices,
                                  graph.gather_edge_ids,
                                  batches[(WARMUP + i) % len(batches)],
                                  jax.random.fold_in(base, 700 + i))

        tot = jnp.zeros((), jnp.int32)
        tot = acc_edges(tot, go(0).num_sampled_edges)   # warm compile
        sync(tot)
        tot = jnp.zeros((), jnp.int32)
        t0 = time.perf_counter()
        for i in range(t_iters):
            tot = acc_edges(tot, go(i).num_sampled_edges)
        sync(tot)
        return (time.perf_counter() - t0) / t_iters * 1e3

    t_samp_xla = time_forced_sampler("xla")
    t_samp_pal = None
    # The Pallas side runs where the kernel compiles: while
    # sample_pallas.TPU_REFUSAL stands it is out of 'auto' and
    # force='pallas' raises Mosaic's message, so it is not timed.
    from glt_tpu.ops.sample_pallas import TPU_REFUSAL as _sample_refusal

    if jax.default_backend() == "tpu" and _sample_refusal is None:
        with obs_compilewatch.label("sample_pallas_ab"):
            t_samp_pal = time_forced_sampler("pallas")
    # Delivered-fraction-of-memcpy for the sample stage under each
    # kernel (attrib.py's expected-bytes floor over the measured time).
    samp_bytes = attrib.sample_expected_bytes(BATCH, FANOUT)

    def _samp_frac(ms):
        return (samp_bytes / (ms * 1e-3) / 1e9) / max(
            memcpy_roofline_gb_s, 1e-9)

    _PARTIAL.update(prune_unmeasured({
        "sample_ms_xla": _round(t_samp_xla, 3),
        "sample_ms_pallas": _round(t_samp_pal, 3),
        "sample_kernel_choice": sample_kernel_choice,
        "sample_roofline_frac_xla": _round(_samp_frac(t_samp_xla), 4),
        "sample_roofline_frac_pallas": _round(
            None if t_samp_pal is None else _samp_frac(t_samp_pal), 4),
        "sample_autotune": sample_autotune,
    }))

    # --- fused frontier kernel A/B (ISSUE 15 tentpole, part 2): the
    # one-dispatch dedup+gather vs the two-pass unfused path on the SAME
    # capped sampled node list at d=128 (the kernel's native width — the
    # bench feature dim pads up exactly like the gather sweep above).
    # TPU-only: on CPU force='auto' resolves to the unfused fallback, so
    # the A/B would time the same program twice.
    fused_frontier_ms = fused_unfused_ms = None
    if jax.default_backend() == "tpu":
        from glt_tpu.ops.dedup_gather import dedup_gather_rows
        from glt_tpu.ops.fused_frontier import (
            fused_frontier,
            fused_frontier_supported,
        )

        fids = gouts[0].node.astype(jnp.int32)
        if fused_frontier_supported(hot128, fids):
            with obs_compilewatch.label(
                    f"fused_frontier_u{int(fids.shape[0])}"):
                ffj = jax.jit(lambda t, i: fused_frontier(
                    t, i, force="pallas").features)
                dgj = jax.jit(lambda t, i: dedup_gather_rows(t, i))

                def _time_ff(fn):
                    sync(fn(hot128, fids)[0, 0])    # warm compile
                    t0 = time.perf_counter()
                    for _ in range(t_iters):
                        out = fn(hot128, fids)
                    sync(out[0, 0])
                    return ((time.perf_counter() - t0)
                            / t_iters * 1e3)

                fused_frontier_ms = _time_ff(ffj)
                fused_unfused_ms = _time_ff(dgj)
    _PARTIAL.update(prune_unmeasured({
        "fused_frontier_ms": _round(fused_frontier_ms, 3),
        "fused_frontier_ms_unfused": _round(fused_unfused_ms, 3),
    }))

    # --- MEASURED config-1 epochs (VERDICT r4 #2): the exact
    # examples/train_sage_products.py pipeline — 240 batches of 1024
    # (10% of 2.45M products nodes).  Two epoch drivers remain after the
    # overlapped path's deletion: the serial two-program reference and
    # the fused scanned route (the flagship — one compiled program per
    # G-batch scan group; see glt_tpu/models/train.py).
    _progress("measured config-1 epoch (serial reference)")
    n_epoch_batches = 20 if small else 240
    sample_first = capped["_handles"]["sample"]
    state0 = capped["_handles"]["state0"]
    tstep = capped["_handles"]["tstep"]
    gather_j = capped["_handles"]["gather"]
    rng_ep = np.random.default_rng(5)
    seed_batches_ep = [
        jnp.asarray(rng_ep.integers(0, n, BATCH).astype(np.int32))
        for _ in range(n_epoch_batches)]
    # GLT_OBS_TRACE=/path.json captures a Chrome trace of this measured
    # epoch (the epoch drivers + loaders are span-instrumented); view in
    # ui.perfetto.dev or `python -m glt_tpu.obs summarize`.
    obs_trace_path = os.environ.get("GLT_OBS_TRACE")
    if obs_trace_path:
        from glt_tpu.obs import start_trace, stop_trace
        start_trace()
    overflow_rate = None    # omitted if the sampler has no overflow channel
    st = state0
    flags = []
    t0 = time.perf_counter()
    for i, sd in enumerate(seed_batches_ep):
        with obs_span("bench.serial_epoch_step"):
            o = sample_first(sd, jax.random.fold_in(base, 5000 + i))
            if o.metadata:
                flags.append(o.metadata["overflow"])
            x, y = gather_j(o)
            st, l, _ = tstep(st, to_batch(o, x=x, y=y,
                                          batch_size=BATCH))
    sync(l)
    epoch_s = time.perf_counter() - t0
    if flags:
        overflow_rate = float(np.asarray(
            jax.device_get(jnp.stack(flags))).mean())

    # --- fused scanned epoch (the flagship): one program trains G=8
    # consecutive batches under lax.scan — sample, dedup, gather,
    # fwd/bwd, update, with no id round-tripping through host dispatch
    # between stages.
    _progress("fused scanned epoch (G8)")
    from glt_tpu.models import make_scanned_node_train_step
    from glt_tpu.obs import compilewatch as obs_compilewatch

    Gn = 4 if small else 8
    sstep = make_scanned_node_train_step(model_bf16, tx, csampler, feat,
                                         labels, BATCH)
    blocks = [np.stack([np.asarray(seed_batches_ep[(i * Gn + j)
                                                   % n_epoch_batches])
                        for j in range(Gn)])
              for i in range(-(-n_epoch_batches // Gn))]
    st2, ls, _, _ = sstep(state0, jnp.asarray(blocks[0]),
                       jax.random.fold_in(base, 400))  # warm 1
    st2, ls, _, _ = sstep(st2, jnp.asarray(blocks[0]),
                       jax.random.fold_in(base, 401))  # warm 2 (committed)
    sync(ls[-1])
    # Steady state must recompile ZERO programs: the delta across the
    # timed (post-warm) epoch is the runtime check of gltlint GLT003,
    # tracked DOWN with a <= 0 aspiration by regress.py.
    compiles_after_warm = obs_compilewatch.total_compiles()
    t0 = time.perf_counter()
    st2 = state0
    for i, blk in enumerate(blocks):
        st2, ls, _, _ = sstep(st2, jnp.asarray(blk),
                           jax.random.fold_in(base, 500 + i))
    sync(ls[-1])
    epoch_scanned_s = time.perf_counter() - t0
    compile_count_epoch = (obs_compilewatch.total_compiles()
                           - compiles_after_warm)
    if obs_trace_path:
        stop_trace(obs_trace_path)
        _progress(f"obs trace written to {obs_trace_path}")
    _PARTIAL["epoch_s_config1_scanned"] = round(epoch_scanned_s, 2)
    _PARTIAL["scanned_group"] = Gn

    # Fused-frontier scanned epoch: the same G-scan with the in-scan
    # feature gather routed through the one-dispatch dedup+gather kernel.
    # Timed only where the kernel actually engages (TPU + 128-multiple
    # feature width) — elsewhere 'auto' resolves to the unfused fallback
    # and the timing would re-measure the scanned epoch under a new name.
    scanned_fused_step_ms = None
    if jax.default_backend() == "tpu" and dim % 128 == 0:
        sstep_f = make_scanned_node_train_step(
            model_bf16, tx, csampler, feat, labels, BATCH,
            fused_frontier="auto")
        st3, ls, _, _ = sstep_f(state0, jnp.asarray(blocks[0]),
                                jax.random.fold_in(base, 420))  # warm 1
        st3, ls, _, _ = sstep_f(st3, jnp.asarray(blocks[0]),
                                jax.random.fold_in(base, 421))  # warm 2
        sync(ls[-1])
        t0 = time.perf_counter()
        st3 = state0
        for i, blk in enumerate(blocks):
            st3, ls, _, _ = sstep_f(st3, jnp.asarray(blk),
                                    jax.random.fold_in(base, 600 + i))
        sync(ls[-1])
        scanned_fused_step_ms = ((time.perf_counter() - t0)
                                 / n_epoch_batches * 1e3)
        _PARTIAL["scanned_fused_step_ms"] = round(scanned_fused_step_ms, 2)

    # The headline step: per-batch cost of the winning epoch driver
    # (serial two-program, fused scan, or fused scan + fused frontier).
    scanned_step_ms = epoch_scanned_s / n_epoch_batches * 1e3
    step_candidates = {"serial": capped["serial_step_ms"],
                       "scanned": scanned_step_ms}
    if scanned_fused_step_ms is not None:
        step_candidates["scanned_fused"] = scanned_fused_step_ms
    best_path = min(step_candidates, key=step_candidates.get)
    best_step_ms = step_candidates[best_path]

    # --- distributed path on THIS chip (VERDICT r4 #6): the shard_map
    # sampler + fused dist train step on a 1-device mesh.  The collectives
    # are degenerate, so the delta vs the single-device path is the
    # device-side cost of the routing machinery itself (owner bucketing
    # sorts, request scatters, response unscatters).
    _progress("dist path on-chip (1-device mesh)")
    from jax.sharding import Mesh

    from glt_tpu.parallel import (
        DistNeighborSampler,
        init_dist_state,
        make_dist_train_step,
        shard_feature,
        shard_graph,
    )

    from glt_tpu.parallel.sharding import put_sharded

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("shard",))
    # Pre-place the sharded arrays on the mesh ONCE — passing host/
    # unsharded arrays makes every jitted call re-transfer the whole
    # graph + feature (measured: a 5 s/step artifact, not device time).
    sg = put_sharded(shard_graph(topo, 1), mesh1, "shard")
    dseeds = [jnp.asarray(np.asarray(b).reshape(1, BATCH))
              for b in batches]

    def time_dist_sampler(ds):
        o = ds.sample_from_nodes(dseeds[0])         # warm compile
        tot = jnp.zeros((), jnp.int32)
        tot = acc_edges(tot, o.num_sampled_edges)
        sync(tot)
        tot = jnp.zeros((), jnp.int32)
        t0 = time.perf_counter()
        for i in range(t_iters):
            o = ds.sample_from_nodes(dseeds[(WARMUP + i) % len(dseeds)])
            tot = acc_edges(tot, o.num_sampled_edges)
        sync(tot)
        return (time.perf_counter() - t0) / t_iters * 1e3

    dsampler = DistNeighborSampler(sg, mesh1, num_neighbors=FANOUT,
                                   batch_size=BATCH, frontier_cap=fcap,
                                   seed=0, exchange_load_factor=2.0)
    dist_sample_ms = time_dist_sampler(dsampler)
    dist_route_path = dsampler.route

    # Routing A/B (ISSUE 3): the same program with each bucketing path
    # forced — the device-side cost delta of the sort-free routing.
    _progress("dist routing A/B (sort vs onepass)")
    dist_sample_ms_ab = {}
    for rp in ("sort", "onepass"):
        dvar = DistNeighborSampler(sg, mesh1, num_neighbors=FANOUT,
                                   batch_size=BATCH, frontier_cap=fcap,
                                   seed=0, exchange_load_factor=2.0,
                                   route=rp)
        dist_sample_ms_ab[rp] = time_dist_sampler(dvar)

    # Hop breakdown: routing prologue measured standalone (one
    # build_routing per hop frontier + the shared gather plan), local
    # sampling = the single-device sampler on the same shapes, and the
    # collective/stitch residual.
    _progress("dist hop breakdown (routing-only program)")
    from glt_tpu.sampler.neighbor_sampler import hop_widths as _hop_widths

    widths1 = _hop_widths(BATCH, FANOUT, fcap)
    rfn = make_routing_only_fn(widths1, cap, sg.nodes_per_shard, 1,
                               route=dist_route_path)
    route_ids = jnp.asarray(
        np.random.default_rng(3).integers(0, n, cap).astype(np.int32))
    int(rfn(route_ids))   # warm compile + fetch sync
    t0 = time.perf_counter()
    for _ in range(t_iters):
        rtot = rfn(route_ids)
    int(rtot)
    dist_routing_ms = (time.perf_counter() - t0) / t_iters * 1e3
    dist_local_sample_ms = full["sample_ms"]
    dist_collective_ms = max(
        dist_sample_ms - dist_routing_ms - dist_local_sample_ms, 0.0)
    _PARTIAL.update({
        "dist_route_path": dist_route_path,
        "dist_sample_ms_sort": round(dist_sample_ms_ab["sort"], 2),
        "dist_sample_ms_onepass": round(dist_sample_ms_ab["onepass"], 2),
        "dist_routing_ms": round(dist_routing_ms, 2),
        "dist_local_sample_ms": round(dist_local_sample_ms, 2),
        "dist_collective_ms": round(dist_collective_ms, 2),
    })

    sf = put_sharded(shard_feature(np.asarray(feat.hot_rows), 1),
                     mesh1, "shard")
    dlabels = jax.device_put(
        jnp.asarray(np.asarray(labels).reshape(1, -1)),
        jax.sharding.NamedSharding(mesh1,
                                   jax.sharding.PartitionSpec("shard")))
    dstate = init_dist_state(model_f32, tx, sg, sf, jax.random.PRNGKey(0),
                             FANOUT, BATCH, frontier_cap=fcap)
    dstep = make_dist_train_step(model_f32, tx, sg, sf, dlabels, mesh1,
                                 FANOUT, BATCH, frontier_cap=fcap,
                                 exchange_load_factor=2.0)
    # Warm TWICE: call 1 takes the fresh (uncommitted) state, call 2 the
    # mesh-committed output state — a different input sharding, i.e. a
    # second compile that must not land inside the timed loop.
    st, l, _ = dstep(dstate, dseeds[0], jax.random.fold_in(base, 300))
    st, l, _ = dstep(st, dseeds[1], jax.random.fold_in(base, 299))
    sync(l)
    t0 = time.perf_counter()
    for i in range(t_iters):
        st, l, _ = dstep(st, dseeds[(WARMUP + i) % len(dseeds)],
                         jax.random.fold_in(base, 301 + i))
    sync(l)
    dist_step_ms = (time.perf_counter() - t0) / t_iters * 1e3
    _PARTIAL.update({"dist_sample_ms_tpu": round(dist_sample_ms, 2),
                     "dist_step_ms_tpu": round(dist_step_ms, 2)})

    # Fused-epoch shape for the dist path (ISSUE 10b): G batches scanned
    # inside ONE shard_map program — the dispatch/state-refeed overhead
    # that made the on-chip dist step 62.6 ms vs 51.9 serial (r05) is
    # paid once per G.  Bit-identity with the serial dist step is
    # asserted in tests/test_fused_epoch.py.
    _progress("dist scanned epoch step (G4)")
    from glt_tpu.parallel import make_scanned_dist_train_step

    Gd = 4
    dsstep = make_scanned_dist_train_step(
        model_f32, tx, sg, sf, dlabels, mesh1, FANOUT, BATCH,
        frontier_cap=fcap, exchange_load_factor=2.0)
    dblk = [jnp.stack([dseeds[(r * Gd + j) % len(dseeds)]
                       for j in range(Gd)])
            for r in range(max(t_iters // Gd, 1))]
    dst2, dls, _ = dsstep(dstate, dblk[0], jax.random.fold_in(base, 320))
    dst2, dls, _ = dsstep(dst2, dblk[0], jax.random.fold_in(base, 321))
    sync(dls[-1])
    t0 = time.perf_counter()
    for r, blk in enumerate(dblk):
        dst2, dls, _ = dsstep(dst2, blk, jax.random.fold_in(base, 330 + r))
    sync(dls[-1])
    dist_scanned_step_ms = ((time.perf_counter() - t0)
                            / (len(dblk) * Gd) * 1e3)
    _PARTIAL["dist_scanned_step_ms_tpu"] = round(dist_scanned_step_ms, 2)

    # Hierarchical ICI/DCN routing A/B (ISSUE 17): the same dist train
    # step with the topology seam pinned each way on a 2-D (host, chip)
    # mesh, driven by a zipf-skewed frontier (the hub-heavy workload the
    # per-host dedup exists for).  Needs >= 4 devices to form a real
    # 2 x (C >= 2) grid — a one-chip machine skips it (keys pruned);
    # CPU smoke runs with a forced 8-device host cover it.
    # hier_dedup_factor is MEASURED on the frontier: flat request slots
    # over host-unique DCN slots; dcn_bytes_* come from the static
    # per-step byte model the glt.dist.collective_bytes counters use.
    dist_flat_step_ms = dist_hier_step_ms = None
    dcn_bytes_flat = dcn_bytes_hier = hier_dedup_factor = None
    n_all = len(jax.devices())
    if n_all >= 4:
        _progress("dist hier routing A/B (2-D mesh, zipf frontier)")
        from jax import lax
        from jax.sharding import PartitionSpec as _P

        from glt_tpu.parallel.dist_sampler import (
            build_hier_routing,
            resolve_mesh_axes,
        )

        Hh = 2
        Cc = n_all // Hh
        S2 = Hh * Cc
        mesh2 = Mesh(np.array(jax.devices()[: S2]).reshape(Hh, Cc),
                     ("host", "chip"))
        axis2 = resolve_mesh_axes(mesh2)
        # Per-shard batch smaller than the headline BATCH: the A/B reads
        # a relative cost, and S2 devices each carry a full frontier.
        HB = min(256, BATCH)
        sg2 = put_sharded(shard_graph(topo, S2), mesh2, axis2)
        sf2 = put_sharded(shard_feature(np.asarray(feat.hot_rows), S2),
                          mesh2, axis2)
        c2 = sg2.nodes_per_shard
        lab_np = np.full((S2, c2), 0, np.int32)
        flat_l = np.asarray(labels).reshape(-1)
        for s2i in range(S2):
            lo2, hi2 = s2i * c2, min((s2i + 1) * c2, flat_l.shape[0])
            if lo2 < flat_l.shape[0]:
                lab_np[s2i, : hi2 - lo2] = flat_l[lo2:hi2]
        lab2 = jax.device_put(
            jnp.asarray(lab_np),
            jax.sharding.NamedSharding(mesh2,
                                       jax.sharding.PartitionSpec(axis2)))
        zr = np.random.default_rng(11)
        zseeds = [jnp.asarray(np.minimum(
            zr.zipf(1.5, size=(S2, HB)).astype(np.int64) - 1,
            n - 1).astype(np.int32)) for _ in range(max(t_iters, 2))]

        hier_ab_ms = {}
        hier_ab_bytes = {}
        for rt in ("flat", "hier"):
            st2 = init_dist_state(model_f32, tx, sg2, sf2,
                                  jax.random.PRNGKey(0), FANOUT, HB,
                                  frontier_cap=fcap)
            step2 = make_dist_train_step(model_f32, tx, sg2, sf2, lab2,
                                         mesh2, FANOUT, HB,
                                         frontier_cap=fcap, route=rt)
            hier_ab_bytes[rt] = dict(step2.collective_bytes)
            st2, l2, _ = step2(st2, zseeds[0],
                               jax.random.fold_in(base, 400))
            st2, l2, _ = step2(st2, zseeds[1 % len(zseeds)],
                               jax.random.fold_in(base, 401))
            sync(l2)
            t0 = time.perf_counter()
            for i in range(t_iters):
                st2, l2, _ = step2(st2, zseeds[i % len(zseeds)],
                                   jax.random.fold_in(base, 402 + i))
            sync(l2)
            hier_ab_ms[rt] = (time.perf_counter() - t0) / t_iters * 1e3
        dist_flat_step_ms = hier_ab_ms["flat"]
        dist_hier_step_ms = hier_ab_ms["hier"]
        dcn_bytes_flat = hier_ab_bytes["flat"]["dcn"]
        dcn_bytes_hier = hier_ab_bytes["hier"]["dcn"]

        def _dedup_counts(i_blk):
            hr = build_hier_routing(i_blk[0], sg2.nodes_per_shard, Hh,
                                    Cc, "host", "chip")
            flat_slots = lax.psum(
                jnp.sum((hr.base.buckets >= 0).astype(jnp.int32)), axis2)
            uniq_slots = lax.psum(
                jnp.sum((hr.uniq >= 0).astype(jnp.int32)), axis2)
            return jnp.stack([flat_slots, uniq_slots])

        cfn = jax.jit(jax.shard_map(
            _dedup_counts, mesh=mesh2, in_specs=(_P(axis2),),
            out_specs=_P(), check_vma=False))
        counts2 = np.asarray(cfn(zseeds[0]))
        hier_dedup_factor = float(counts2[0]) / float(max(counts2[1], 1))
        _PARTIAL.update({
            "dist_flat_step_ms": round(dist_flat_step_ms, 2),
            "dist_hier_step_ms": round(dist_hier_step_ms, 2),
            "dcn_bytes_flat": dcn_bytes_flat,
            "dcn_bytes_hier": dcn_bytes_hier,
            "hier_dedup_factor": round(hier_dedup_factor, 3),
        })

    # Analytic train FLOPs (fwd 2 matmuls/layer over the padded node cap;
    # bwd ~2x fwd) -> achieved TFLOP/s on the train-only step.
    dims = [dim] + [hidden] * (len(FANOUT) - 1) + [classes]

    def tflops(width, ms):
        fwd = sum(2 * 2 * width * dims[i] * dims[i + 1]
                  for i in range(len(dims) - 1))
        return 3 * fwd / (ms / 1e3) / 1e12

    edges_per_sec_m = meter.rate("edges") / 1e6

    # Achieved-bandwidth fraction — the MFU analog for this memory-bound
    # workload.  Sampling: each sampled edge costs >= one 4B random
    # neighbor read; dedup adds ~3 reads + 2 writes of 4B per candidate
    # over the id map.  Feature gather: the MEASURED payload bandwidth of
    # the winning gather variant (valid rows x d x 4B / time) — the other
    # half of the engine's HBM budget, previously unreported.
    est_sampling_gb_s = edges_per_sec_m * 1e6 * (4 + 20) / 1e9
    est_traffic_gb_s = est_sampling_gb_s + gather_gb_s[gather_best]
    # Peak bandwidth is resolved per device (env GLT_HBM_GBPS, else the
    # device-kind table; an unknown kind raises), with its provenance
    # labelled in the output.  The CPU-sized smoke has no HBM and emits
    # none of these keys.
    from glt_tpu.obs import device as obs_device
    from glt_tpu.obs.roofline import peak_hbm_gb_s
    hbm_keys = {}
    if dev[0].platform == "tpu":
        hbm_bw = peak_hbm_gb_s()
        hbm_keys = {
            "est_hbm_fraction": round(est_traffic_gb_s / hbm_bw["gb_s"], 4),
            "hbm_bw_gb_s": round(hbm_bw["gb_s"], 1),
            "hbm_bw_source": str(hbm_bw["source"]),
        }

    global _DONE
    _DONE = True
    # Unmeasured metrics are None and PRUNED from the line — the JSON
    # omits what this run didn't measure instead of leaking sentinels.
    _emit(prune_unmeasured({
        "metric": "neighbor_sampling_throughput_f15_10_5_b1024",
        "value": round(edges_per_sec_m, 3),
        "unit": "M sampled edges/s",
        "vs_baseline": round(edges_per_sec_m / BASELINE_A100_M, 4),
        "vs_ref_cpu": round(edges_per_sec_m / REF_CPU_MEASURED_M, 2),
        "graph": "power-law avg-deg-25 products-scale",
        "host_roundtrip_ms": round(host_roundtrip_ms, 2),
        "nodedup_leaves_m_edges_s": round(fast_m, 3),
        "batched_g8_m_edges_s": round(batched_m, 3),
        "dispatch_ms_per_batch": round(dispatch_s / ITERS * 1e3, 3),
        "serialized_ms_per_batch": round(serialized_s / ITERS * 1e3, 3),
        "pipelined_ms_per_batch": round(pipelined_s / ITERS * 1e3, 3),
        "batched_ms_per_batch": round(batched_s / (rounds * G) * 1e3, 3),
        "est_hbm_traffic_gb_s": round(est_traffic_gb_s, 2),
        "est_hbm_traffic_gb_s_sampling": round(est_sampling_gb_s, 2),
        **hbm_keys,
        # Measured counterparts beside the estimate: the same traffic
        # over the MEASURED memcpy ceiling, and the device-reported
        # peak HBM use (None -> pruned on memory_stats-less backends).
        "hbm_fraction_measured": round(
            est_traffic_gb_s / max(memcpy_roofline_gb_s, 1e-9), 4),
        "hbm_peak_bytes": obs_device.peak_bytes_in_use(),
        "compile_count_epoch": compile_count_epoch,
        # Round-4-comparable split (worst-case cap, f32).  gather_ms is
        # the per-shape WINNER of naive vs dedup (the warmup auto-pick);
        # both variants are reported beside it.
        "sample_ms": round(full["sample_ms"], 2),
        "gather_ms": round(full["gather_ms"], 2),
        "gather_ms_naive": round(full["gather_ms_naive"], 2),
        "gather_ms_dedup": round(full["gather_ms_dedup"], 2),
        "gather_path": full["gather_path"],
        # Gather-variant A/B on the occ-capped config (same sampled
        # batches): dedup ratio, cross-batch cache hit rates, delivered
        # bandwidth per variant, and the tiled-DMA kernel race at d=128.
        "gather_path_best": gather_best,
        "gather_batch_ms_naive": round(t_naive / len(gouts) * 1e3, 2),
        "gather_batch_ms_dedup": round(t_dedup / len(gouts) * 1e3, 2),
        "gather_batch_ms_dedup_cache": round(
            t_cached / len(gouts) * 1e3, 2),
        "dedup_ratio": round(dedup_ratio, 3),
        "cache_hit_rate": round(warm_hits / max(warm_lookups, 1), 4),
        "cache_hit_rate_cold": round(s_cold["hit_rate"], 4),
        "cache_capacity_rows": cache_cap,
        "gather_gb_s_naive": round(gather_gb_s["naive"], 3),
        "gather_gb_s_dedup": round(gather_gb_s["dedup"], 3),
        "gather_gb_s_dedup_cache": round(gather_gb_s["dedup_cache"], 3),
        # Achieved-vs-peak (ISSUES 6/10): the measured memcpy ceiling,
        # the winning gather variant's fraction of it, and every
        # variant's own fraction beside it.
        "memcpy_roofline_gb_s": round(memcpy_roofline_gb_s, 2),
        "gather_roofline_frac": round(gather_roofline_frac, 4),
        **gather_roofline_by_variant,
        "gather_xla_ms_d128": _round(t_xla128, 3),
        "gather_pallas_ms_d128": _round(t_pal128, 3),
        "gather_kernel_choice": kernel_choice,
        # Per-(width, batch, tile, ring) sweep landscape of the tiled
        # kernel (None off-TPU; see ops/gather_pallas.autotune_table).
        "gather_autotune": gather_autotune,
        # Sampling-wall A/B (ISSUE 15): the multi-hop program with the
        # neighbor-read seam pinned each way, the per-hop exact-shape
        # sweep landscape, and delivered-fraction-of-memcpy under each
        # kernel.  Pallas-side keys are omitted off-TPU (honest xla win).
        "sample_ms_xla": _round(t_samp_xla, 3),
        "sample_ms_pallas": _round(t_samp_pal, 3),
        "sample_kernel_choice": sample_kernel_choice,
        "sample_roofline_frac_xla": _round(_samp_frac(t_samp_xla), 4),
        "sample_roofline_frac_pallas": _round(
            None if t_samp_pal is None else _samp_frac(t_samp_pal), 4),
        "sample_autotune": sample_autotune,
        # One-dispatch dedup+gather vs the two-pass unfused path on the
        # same capped node list at d=128 (TPU only).
        "fused_frontier_ms": _round(fused_frontier_ms, 3),
        "fused_frontier_ms_unfused": _round(fused_unfused_ms, 3),
        "train_ms": round(full["train_ms"], 2),
        "serial_step_ms": round(full["serial_step_ms"], 2),
        "train_step_tflops": round(tflops(cap, full["train_ms"]), 2),
        # Occupancy calibration (VERDICT r4 #1).
        "occupancy_p50": round(occupancy_p50, 0),
        "occupancy_p99": round(occupancy_p99, 0),
        "node_cap_full": cap,
        "node_cap_calibrated": node_cap,
        "cap_fraction": round(node_cap / cap, 3),
        "overflow_rate": _round(overflow_rate, 4),
        # Flagship config (occupancy cap + bf16 matmuls).
        "sample_ms_capped": round(capped["sample_ms"], 2),
        "gather_ms_capped": round(capped["gather_ms"], 2),
        "gather_path_capped": capped["gather_path"],
        "train_ms_capped_bf16": round(capped["train_ms"], 2),
        "serial_step_ms_capped": round(capped["serial_step_ms"], 2),
        "train_step_tflops_bf16": round(
            tflops(node_cap, capped["train_ms"]), 2),
        # Steady-state per-batch cost of the fused scanned epoch — the
        # headline step contender after the overlapped path's deletion.
        "scanned_step_ms": round(scanned_step_ms, 2),
        "scanned_fused_step_ms": _round(scanned_fused_step_ms, 2),
        "best_step_path": best_path,
        "best_step_ms": round(best_step_ms, 2),
        "sampling_overhead_frac": round(
            best_step_ms / max(capped["train_ms"], 1e-9) - 1.0, 3),
        "subgraphs_per_s": round(1e3 / best_step_ms, 1),
        # Distributed path on the real chip (1-device mesh: degenerate
        # collectives, so this isolates the routing machinery's device
        # cost vs the single-device programs above).  The hop breakdown
        # splits it: routing prologue (standalone build_routing program,
        # A/B seam GLT_ROUTE_FORCE), local sampling (the single-device
        # sampler at the same shapes), and the collective/stitch
        # residual.
        "dist_sample_ms_tpu": round(dist_sample_ms, 2),
        "dist_step_ms_tpu": round(dist_step_ms, 2),
        "dist_scanned_step_ms_tpu": round(dist_scanned_step_ms, 2),
        "dist_route_path": dist_route_path,
        "dist_sample_ms_sort": round(dist_sample_ms_ab["sort"], 2),
        "dist_sample_ms_onepass": round(dist_sample_ms_ab["onepass"], 2),
        "dist_routing_ms": round(dist_routing_ms, 2),
        "dist_local_sample_ms": round(dist_local_sample_ms, 2),
        "dist_collective_ms": round(dist_collective_ms, 2),
        "dist_routing_overhead": round(
            dist_sample_ms / max(full["sample_ms"], 1e-9), 2),
        # Hierarchical ICI/DCN routing A/B (ISSUE 17) — pruned on
        # meshes under 4 devices.
        "dist_flat_step_ms": _round(dist_flat_step_ms, 2),
        "dist_hier_step_ms": _round(dist_hier_step_ms, 2),
        "dcn_bytes_flat": dcn_bytes_flat,
        "dcn_bytes_hier": dcn_bytes_hier,
        "hier_dedup_factor": _round(hier_dedup_factor, 3),
        # MEASURED epochs — the serial two-program reference and the
        # fused scanned route (examples/train_sage_products.py default),
        # not estimates.
        "epoch_s_config1_measured": round(epoch_s, 2),
        "epoch_s_config1_scanned": round(epoch_scanned_s, 2),
        "scanned_group": Gn,
        "epoch_best": round(min(epoch_s, epoch_scanned_s), 2),
        "epoch_best_path": ("serial" if epoch_s <= epoch_scanned_s
                            else "scanned"),
        # Steady-state per-batch overhead of the winning epoch path over
        # the pure train step (the <20% target metric).
        "sampling_overhead_frac_epoch": round(
            (min(epoch_s, epoch_scanned_s) / n_epoch_batches * 1e3)
            / max(capped["train_ms"], 1e-9) - 1.0, 3),
        "epoch_batches": n_epoch_batches,
        "epoch_s_est_config1": round(n_epoch_batches * best_step_ms / 1e3,
                                     2),
        # Obs instrumentation cost (ISSUE 6 acceptance: < 2% disabled).
        "obs_noop_ns_per_call": round(obs_noop_ns, 1),
        "serial_step_ms_obs_disabled": round(serial_obs_ms, 2),
        "obs_disabled_overhead_frac": round(obs_overhead_frac, 4),
        # Per-stage roofline attribution (ISSUE 13): expected-bytes
        # models over measured stage times; gather_roofline_frac above
        # stays the headline, the other stages ride beside it.
        "stage_roofline": stage_roofline,
        "train_bytes_source": train_bytes_source,
        **attrib.flat_roofline_fracs(stage_roofline, skip=("gather",)),
    }))


if __name__ == "__main__":
    main()
