"""The bipartite cell's own benchmark code at ``tiny-bipartite-sage``
size on the CPU: the generator against its reference, the typed link
batch check (and what it must catch), the dense-Adam comparison (and a
lazy Adam failing it), the driver end to end, the readers of the
``emb_*`` metrics, the files of
``bipartite-sage-taobao.hetero-link-train-scan``, and a checkout without
the typed link step failing at once."""
import importlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import checks, data_bipartite, reference_bipartite  # noqa
from chipbench.common import Env, Window  # noqa: E402

CELL = "bipartite-sage-taobao.hetero-link-train-scan"
METRICS = ["emb_lookup_ms", "emb_update_ms", "emb_update_roofline"]
UI = data_bipartite.UI


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def tiny():
    return _json("chipbench", "configs", "tiny-bipartite-sage.json")


@pytest.fixture(scope="module")
def driven(tiny):
    """The tiny cell as ``run.py`` drives it, traced: the driver, its
    window, the registry around the window, and its check."""
    import jax

    from glt_tpu.obs import compilewatch
    from glt_tpu.obs import metrics as registry

    traffic = _json("chipbench", "traffic", "hetero-link-train-scan.json")
    compilewatch.install()
    env = Env(tiny, traffic, 2 ** 31 + 5, jax.devices()[:1], True,
              lambda msg: None)
    try:
        driver = importlib.import_module(
            "chipbench.drivers." + traffic["driver"]).Driver(env)
        before = registry.snapshot()
        compiles = compilewatch.total_compiles()
        win = driver.window(1.0)
        compiles = compilewatch.total_compiles() - compiles
        after = registry.snapshot()
        detail = driver.check()
    finally:
        registry.disable()
        registry.reset()
    return {"traffic": traffic, "driver": driver, "win": win,
            "registry": (before, after), "detail": detail,
            "compiles": compiles}


def test_the_generator_makes_the_files_counts_and_exact_transposes(driven,
                                                                   tiny):
    d = driven["driver"].d
    n, rels = tiny["data"]["node_types"], tiny["data"]["relations"]
    g = d.graphs
    assert (g[UI].num_nodes, g[UI].num_edges) == (n["user"],
                                                  rels[0]["num_edges"])
    assert g[data_bipartite.IU].num_nodes == n["item"]
    assert g[data_bipartite.II].num_edges == 2 * rels[1]["num_edges"]
    degree = np.diff(g[UI].topo.indptr)
    assert degree.min() >= 1 and degree.sum() == rels[0]["num_edges"]
    # the reference recomputes every user->item edge from its position
    src, dst = d.ref.forward_edges(d.ref.rels[UI][0],
                                   np.arange(g[UI].num_edges))
    assert (dst == g[UI].topo.indices).all()
    assert (np.diff(g[UI].topo.indptr)[src[:1]] > 0).all()
    assert driven["detail"]["transposed_edges"] == (
        rels[0]["num_edges"] + rels[1]["num_edges"])


def test_a_wrong_transpose_is_caught(driven):
    d = driven["driver"].d
    csr = {et: (gr.topo.indptr, gr.topo.indices.copy())
           for et, gr in d.graphs.items()}
    ip, idx = csr[data_bipartite.IU]
    idx[[0, -1]] = idx[[-1, 0]]
    with pytest.raises(checks.CheckFailure, match="transposed slot"):
        d.ref.check_transposes(csr)


def _batch_of(driver, key_seed=3):
    import jax

    edges = driver._seed_edges(driver.batch)
    out, b = driver._sample(edges, jax.random.PRNGKey(key_seed))
    meta = out.metadata
    batch = {"node": dict(out.node), "node_mask": dict(out.node_mask),
             "x": dict(b["ids"]), "row": dict(out.row),
             "col": dict(out.col), "edge_mask": dict(out.edge_mask),
             "edge_label_index": meta["edge_label_index"],
             "edge_label": b["label"], "neg_strict": meta["neg_strict"]}
    return edges, batch


def _check(driver, edges, batch):
    return reference_bipartite.check_link_batch(
        driver.d.ref, batch, edges[0], edges[1], driver.batch,
        driver.fanout, "tiny batch", np.random.default_rng(0))


def test_batch_check_passes_the_sampler_and_catches_what_it_must(driven):
    driver = driven["driver"]
    edges, batch = _batch_of(driver)
    got = _check(driver, edges, batch)
    assert got["neg_strict"] + got["neg_padded"] == driver.batch
    assert got["sampled_edges"] > 0

    # a node before the last hop missing a sampled edge
    em = {k: np.asarray(v).copy() for k, v in batch["edge_mask"].items()}
    key = next(k for k, v in em.items() if v.any())
    em[key][np.flatnonzero(em[key])[0]] = False
    with pytest.raises(checks.CheckFailure, match="min\\(degree, fanout\\)"):
        _check(driver, edges, dict(batch, edge_mask=em))
    # a negative flagged strict that is an edge
    eli = np.asarray(batch["edge_label_index"]).copy()
    q = driver.batch
    eli[:, q] = eli[:, 0]
    strict = np.asarray(batch["neg_strict"]).copy()
    strict[0] = True
    with pytest.raises(checks.CheckFailure, match="flagged strict"):
        _check(driver, edges, dict(batch, edge_label_index=eli,
                                   neg_strict=strict))
    # x that is not the node ids
    x = {t: np.asarray(v).copy() for t, v in batch["x"].items()}
    x["item"][0] += 1
    with pytest.raises(checks.CheckFailure, match="not the node ids"):
        _check(driver, edges, dict(batch, x=x))


def test_the_pair_logits_tolerance_lies_between_bf16_and_four_bits(driven,
                                                                   tiny):
    detail = driven["detail"]
    rtol = tiny["check"]["logits_rtol"]
    assert detail["logits_err"] < rtol < detail["logits_err_4bit"]


def test_the_window_trains_closed_loop_with_no_compile(driven):
    win, traffic = driven["win"], driven["traffic"]
    per_call = traffic["group"] * traffic["groups_per_call"]
    assert win.attempted == win.steps and win.steps % per_call == 0
    assert win.steps >= per_call and win.failed == 0
    assert win.metrics["seeds_per_s"] > 0 and driven["compiles"] == 0
    assert np.isfinite(driven["driver"].losses).all()


def test_the_registry_counts_the_tables_and_the_typed_samples(driven, tiny):
    before, after = driven["registry"]
    n = tiny["data"]["node_types"]
    steps = driven["win"].steps
    for t in ("user", "item"):
        assert after[f"glt.embed.table_rows{{type={t}}}"] == n[t]
        rows = (after[f"glt.embed.rows{{type={t}}}"]
                - before.get(f"glt.embed.rows{{type={t}}}", 0))
        assert 0 < rows <= steps * n[t]
    batches = after["glt.sample.batches"] - before.get(
        "glt.sample.batches", 0)
    assert batches == steps
    assert after['glt.sample.edges{hop=2}'] > before.get(
        'glt.sample.edges{hop=2}', 0)


def test_dense_adam_matches_and_a_lazy_adam_fails(driven, tiny):
    import jax
    import jax.numpy as jnp

    detail, chk = driven["detail"], tiny["check"]
    assert detail["adam_touched_err"] <= chk["adam_touched_rtol"]
    assert detail["adam_untouched_ulp"] <= chk["adam_untouched_ulp"]
    assert all(r > 0 and u > 0 for r, u in detail["adam_rows"].values())

    driver = driven["driver"]
    blk = driver._seed_edges(driver.group * driver.batch).reshape(
        2, driver.group, driver.batch).transpose(1, 0, 2)
    key = jax.random.PRNGKey(21)
    batches = [driver._sample(blk[g], k)[1]
               for g, k in enumerate(jax.random.split(key, driver.group))]
    rng = np.random.default_rng(1)
    start = reference_bipartite.before_call(driver.state, batches, rng)
    old = jax.device_get(driver.state)
    driver.state, *_ = driver.step(driver.state, blk, key)
    args = (chk["adam_touched_rtol"], chk["adam_untouched_ulp"], "tiny",
            driver.lr)
    reference_bipartite.check_adam(start, driver.state, batches, *args)
    # A lazy Adam: the rows no batch of the call read keep their state.
    lazy = driver.state
    for t in ("user", "item"):
        keep = jnp.asarray(start["others"][t])
        for tree, was in ((lazy.params, old.params),
                          (lazy.opt_state[0].mu, old.opt_state[0].mu),
                          (lazy.opt_state[0].nu, old.opt_state[0].nu)):
            rows = reference_bipartite.table_rows(tree["params"], t)
            old_rows = reference_bipartite.table_rows(was["params"], t)
            leaf = tree["params"][f"{t}_emb"]
            leaf["table"] = rows.at[keep].set(
                old_rows[start["others"][t]]).reshape(leaf["table"].shape)
    with pytest.raises(checks.CheckFailure, match="lazy"):
        reference_bipartite.check_adam(start, lazy, batches, *args)


def test_on_a_cpu_only_the_counters_are_read(driven, tiny):
    from chipbench import run

    bench = _json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == METRICS
    ctx = {"trace": None, "window": driven["win"], "config": tiny,
           "traffic": driven["traffic"], "chips": 1, "compiles": 0,
           "registry": driven["registry"], "memory_peak_bytes": 0,
           "peaks": None}
    assert run.read_layer_metrics(mine, ctx) == {}
    # the metrics every cell reports read this cell's counters
    shares = ["sample_edge_live_share", "sample_last_frontier_live_share",
              "node_live_share", "sample_read_live_share"]
    shared = [m for m in bench["per_layer"] if m["name"] in shares]
    assert all("workloads" not in m for m in shared)
    # Earlier tests in this process may have left deeper hops' counters
    # in the registry, which the last-hop shares would read: keep two.
    two = [{k: v for k, v in snap.items()
            if not re.search(r"hop=([3-9]|\d\d)", k)}
           for snap in driven["registry"]]
    got = run.read_layer_metrics(shared, dict(ctx, registry=tuple(two)))
    assert sorted(got) == sorted(shares)
    assert all(0 < v["value"] <= 100 for v in got.values())


def test_emb_readers_read_a_scoped_device_trace(monkeypatch):
    """The three metrics out of a hand-made device trace with the
    program's scopes (device ops exist on the chip only)."""
    from chipbench import peaks, run, scopes
    from chipbench.reducers import emb_update_roofline

    ms = 1e6
    times = [("glt.sample.hop1", 2 * ms), ("glt.embed.lookup", 3 * ms),
             ("glt.model.dense", 5 * ms), ("glt.embed.update", 40 * ms),
             ("glt.step.update", 1 * ms), (None, 1 * ms)]
    monkeypatch.setattr(scopes, "traced_file", lambda: "trace.xplane.pb")
    monkeypatch.setattr(scopes, "scope_map", lambda path: {})
    monkeypatch.setattr(scopes, "scoped_self_times",
                        lambda trace, smap: times)
    cfg = _json("chipbench", "configs", "bipartite-sage-taobao.json")
    n = cfg["data"]["node_types"]
    gauges = {f"glt.embed.table_rows{{type={t}}}": float(v)
              for t, v in n.items()}
    bench = _json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    ctx = {"trace": {}, "window": Window(4, 0, {}, 4, {}), "config": cfg,
           "traffic": {}, "chips": 1, "compiles": 0,
           "registry": ({}, gauges), "memory_peak_bytes": 0,
           "peaks": peaks.peaks_of("TPU v5 lite")}
    got = {k: v["value"] for k, v in run.read_layer_metrics(mine, ctx).items()}
    assert got["emb_lookup_ms"] == 0.75 and got["emb_update_ms"] == 10.0
    rows = n["user"] + n["item"]
    # 24 B x 5.15 M rows x 64 floats over 10 ms a step, over 819 GB/s
    want = 100.0 * (24 * rows * 64) / 10e-3 / 819e9
    assert got["emb_update_roofline"] == pytest.approx(want)
    assert emb_update_roofline.update_bytes(rows, 64) == 24 * rows * 64
    assert 0 < want < 100
    # a program without the gauge (the parent): nothing to read
    spec = _json("chipbench", "layer_metrics", "emb_update_roofline.json")
    reader = importlib.import_module("chipbench.reducers." + spec["reducer"])
    assert reader.read(dict(ctx, registry=({}, {})), spec["params"]) is None
    assert reader.read(dict(ctx, peaks=None), spec["params"]) is None


def test_the_cells_files_and_the_configuration_say_what_the_issue_asks():
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and "seed edges" in cell["why"]
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["file"] == "chipbench/configs/bipartite-sage-taobao.json"
    cfg = _json(entry["file"])
    assert cfg["reduced"] == [] and cfg["name"] == entry["name"]
    d, sam, model = cfg["data"], cfg["sampling"], cfg["model"]
    assert d["node_types"] == {"user": 987994, "item": 4162024}
    ui, ii = d["relations"]
    assert ui["type"] == list(UI) and ui["num_edges"] == 80120647
    assert ui["transpose"] == "rev_to"
    assert ii["type"] == ["item", "to", "item"] and ii["symmetric"]
    assert 2 * ii["num_edges"] == 41620240
    assert (model["hidden"], model["out"], model["table_dtype"],
            model["matmul_dtype"], model["learning_rate"]) == (
        64, 64, "float32", "bfloat16", 0.001)
    assert (sam["batch_size"], sam["fanout"], sam["neg_sampling"],
            sam["amount"], sam["trials"], sam["padding"]) == (
        2048, [8, 4], "binary", 1, 5, True)
    assert sam["frontier_cap"] is None and sam["node_capacity"] is None
    for key in ("counts", "degree_alpha", "degree_law", "item_item",
                "model", "loader", "upstream_file"):
        assert key in cfg["assumed"]
    assert "matmul_dtype" in cfg["departures"]
    chk = cfg["check"]
    for key in ("logits_rtol", "adam_touched_rtol", "adam_untouched_ulp"):
        assert isinstance(chk[key], (int, float)) and chk[key] > 0
    traffic = _json("chipbench", "traffic", cell["traffic"] + ".json")
    assert (traffic["driver"], traffic["group"], traffic["groups_per_call"]
            ) == ("hetero_link_scan_train", 4, 2)
    # the metrics, appended together, each with its reader
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(METRICS[0])
    assert names[at: at + 3] == METRICS
    for m in bench["per_layer"][at: at + 3]:
        assert m["workloads"] == [CELL] and m["moves"] == "seeds_per_s"
        assert m["layer"] == "embedding tables"
        spec = _json("chipbench", "layer_metrics", m["name"] + ".json")
        importlib.import_module("chipbench.reducers." + spec["reducer"])
    # one cell of four chips at most, as before
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_a_checkout_without_the_typed_link_step_fails_at_once():
    """The parent commit has no ``make_scanned_hetero_link_train_step``:
    the driver's first import stops it, before anything is generated."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import glt_tpu.models as m\n"
            "del m.make_scanned_hetero_link_train_step\n"
            "import chipbench.drivers.hetero_link_scan_train\n" % ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "make_scanned_hetero_link_train_step" in proc.stderr
