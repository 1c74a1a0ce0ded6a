"""The link cell's own benchmark code at ``tiny-sage-unsup`` size on the
CPU: the scanned link step against ``reference_link.py`` (loss and every
gradient), the pair-logits tolerance, the batch check, the driver end to
end, the readers of the ``link_*`` metrics, and the files of
``sage-unsup-products.link-train-scan``."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

from chipbench import checks, data, reference, reference_link  # noqa: E402
from chipbench.common import Env, Window  # noqa: E402

CELL = "sage-unsup-products.link-train-scan"
LINK_METRICS = ["link_device_busy_ms", "link_sample_hop_ms",
                "link_sample_induce_ms", "link_neg_sample_ms",
                "link_gather_ms", "link_gather_roofline",
                "link_model_device_ms", "link_model_agg_ms",
                "link_unscoped_share", "link_neg_padded_share"]


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def tiny():
    import jax

    cfg = _json("chipbench", "configs", "tiny-sage-unsup.json")
    return cfg, data.build_one_chip(cfg, 5, jax.devices()[0])


def _sampled(cfg, d, key=None, **sampler_args):
    """One link batch of the configuration: sampler, output, x."""
    import jax

    from glt_tpu.sampler import NegativeSampling, NeighborSampler
    from glt_tpu.sampler.base import EdgeSamplerInput

    sam = cfg["sampling"]
    q, graph = sam["batch_size"], d.dataset.get_graph()
    neg = NegativeSampling(sam["neg_sampling"], sam["amount"])
    sampler = NeighborSampler(graph, sam["fanout"], batch_size=q,
                              with_edge=False, **sampler_args)
    topo = graph.topo
    pos = np.arange(q) * 37 + 11
    src = np.searchsorted(topo.indptr, pos, side="right") - 1
    dst = topo.indices[pos]
    out = sampler.sample_from_edges(
        EdgeSamplerInput(row=src, col=dst, neg_sampling=neg),
        key=jax.random.PRNGKey(3) if key is None else key)
    x = d.dataset.get_node_feature().gather(out.node)
    return sampler, neg, (src, dst), out, x


def _batch(out, x):
    import jax.numpy as jnp

    meta = out.metadata
    return {"node": out.node, "node_mask": out.node_mask, "x": x,
            "edge_index": jnp.stack([out.row, out.col]),
            "edge_mask": out.edge_mask,
            "edge_label_index": meta["edge_label_index"],
            "edge_label": meta["edge_label"],
            "neg_strict": meta["neg_strict"]}


def test_scanned_link_step_agrees_with_the_reference_loss_and_gradients(tiny):
    """One batch through the scanned step with plain SGD at rate 1: the
    parameters move by exactly the gradients, which the float32 reference
    recomputes on the same sampled batch from its own forward."""
    import jax
    import optax

    from glt_tpu.models import (GraphSAGE, init_train_state,
                                make_scanned_link_train_step)

    cfg, d = tiny
    sam = cfg["sampling"]
    q, feat = sam["batch_size"], d.dataset.get_node_feature()
    model = GraphSAGE(hidden_features=32, out_features=32, num_layers=3,
                      dropout_rate=0.0)               # float32 matmuls
    tx = optax.sgd(1.0)
    state = init_train_state(model, tx, feat.shape[1], jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(8)
    batch_key = jax.random.split(key, 1)[0]
    sampler, neg, (src, dst), out, x = _sampled(cfg, d, key=batch_key)
    step = make_scanned_link_train_step(model, tx, sampler, feat,
                                        neg_sampling=neg)
    new, losses, accs, flags = step(state, np.stack([src, dst])[None], key)
    weights = reference.layer_weights(state.params, 3)
    want, grads = reference_link.pair_loss_and_grads(
        weights, x, out.row, out.col, out.edge_mask,
        out.metadata["edge_label_index"], out.metadata["edge_label"])
    np.testing.assert_allclose(float(losses[0]), float(want), rtol=2e-5)
    moved = reference.layer_weights(new.params, 3)
    for layer, (w0, w1, g) in enumerate(zip(weights, moved, grads)):
        for name, a, b, c in zip(("W_self", "b", "W_nbr"), w0, w1, g):
            np.testing.assert_allclose(
                np.asarray(a) - np.asarray(b), np.asarray(c), rtol=2e-3,
                atol=2e-6, err_msg=f"layer {layer} {name}")
            assert np.abs(np.asarray(c)).max() > 0
    # accuracy: the share of pairs whose logit has the label's sign
    z = reference.sage_forward(weights, x, out.row, out.col, out.edge_mask)
    logit = np.asarray(reference_link.pair_logits(
        z, out.metadata["edge_label_index"]))
    label = np.asarray(out.metadata["edge_label"])
    np.testing.assert_allclose(
        float(accs[0]), ((logit > 0) == (label > 0)).mean(), atol=1e-6)
    assert np.asarray(flags).tolist() == [[0, 0]]


def test_blocked_reference_forward_is_the_plain_one(tiny):
    import jax

    cfg, d = tiny
    _, _, _, out, x = _sampled(cfg, d)
    rng = np.random.default_rng(0)
    dims = [x.shape[1], 16, 16, 8]
    weights = [(rng.normal(size=(a, b)).astype(np.float32) / np.sqrt(a),
                rng.normal(size=(b,)).astype(np.float32),
                rng.normal(size=(a, b)).astype(np.float32) / np.sqrt(a))
               for a, b in zip(dims[:-1], dims[1:])]
    whole = reference.sage_forward(weights, x, out.row, out.col,
                                   out.edge_mask)
    blocked = reference_link.sage_embed_blocked(
        weights, x, out.row, out.col, out.edge_mask, block=700)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert jax.numpy.isfinite(blocked).all()


def test_batch_check_passes_the_sampler_and_catches_what_it_must(tiny):
    cfg, d = tiny
    sam = cfg["sampling"]
    _, _, (src, dst), out, x = _sampled(cfg, d)
    args = (src, dst, sam["batch_size"], sam["amount"], sam["fanout"],
            "batch")
    batch = _batch(out, x)
    detail = reference_link.check_link_batch(
        d.ref, batch, *args, np.random.default_rng(0))
    assert detail["neg_strict"] + detail["neg_padded"] == sam["batch_size"]
    assert detail["seed_union_nodes"] <= 4 * sam["batch_size"]
    # a strict flag on a pair that is an edge
    eli = np.asarray(batch["edge_label_index"]).copy()
    q = sam["batch_size"]
    eli[:, q] = eli[:, 0]
    with pytest.raises(checks.CheckFailure, match="flagged strict are edges"):
        reference_link.check_link_batch(
            d.ref, dict(batch, edge_label_index=eli), *args,
            np.random.default_rng(0))
    # positive pairs out of order
    with pytest.raises(checks.CheckFailure, match="seed edges in order"):
        reference_link.check_link_batch(
            d.ref, batch, src[::-1], dst[::-1], *args[2:],
            np.random.default_rng(0))
    # a wrong label
    label = np.asarray(batch["edge_label"]).copy()
    label[q + 1] = 1
    with pytest.raises(checks.CheckFailure, match="labels are not"):
        reference_link.check_link_batch(
            d.ref, dict(batch, edge_label=label), *args,
            np.random.default_rng(0))
    # a changed feature row
    with pytest.raises(checks.CheckFailure, match="features differ"):
        reference_link.check_link_batch(
            d.ref, dict(batch, x=x.at[3, 0].add(1)), *args,
            np.random.default_rng(0))
    # a capped frontier leaves interior nodes without their edges
    *_, cut, xc = _sampled(cfg, d, frontier_cap=64)
    with pytest.raises(checks.CheckFailure, match="min\\(degree, fanout\\)"):
        reference_link.check_link_batch(
            d.ref, _batch(cut, xc), *args, np.random.default_rng(0))
    assert reference_link.is_edge(d.ref, src[:4], dst[:4]).all()


def test_pair_logits_tolerance_passes_bf16_and_fails_four_bits():
    """``logits_rtol`` passes the program's bf16 matmuls (the step's own
    trimmed forward, at the parameters a window of training left) and
    fails the same forward with parameters and rows rounded to four
    mantissa bits: the driver's check reads both."""
    cfg = _json("chipbench", "configs", "tiny-sage-unsup.json")
    rtol = _json("chipbench", "configs",
                 "sage-unsup-products.json")["check"]["logits_rtol"]
    assert cfg["check"]["logits_rtol"] == rtol
    _, driver, _ = _drive(cfg, trace=False)
    detail = driver.check()
    assert detail["logits_err"] < rtol < detail["logits_err_4bit"] / 2
    # and the comparison itself refuses the four-bit reading
    want = np.ones((8,))
    with pytest.raises(checks.CheckFailure, match="logits differ"):
        checks.check_logits(want * (1 + detail["logits_err_4bit"]), want,
                            rtol, "4-bit")


def _drive(cfg, trace):
    import jax

    from glt_tpu.obs import compilewatch

    traffic = _json("chipbench", "traffic", "link-train-scan.json")
    compilewatch.install()
    env = Env(cfg, traffic, 2 ** 31 + 5, jax.devices()[:1], trace,
              lambda msg: None)
    driver = importlib.import_module(
        "chipbench.drivers." + traffic["driver"]).Driver(env)
    before = compilewatch.total_compiles()
    win = driver.window(1.0)
    assert compilewatch.total_compiles() == before
    return traffic, driver, win


def test_tiny_cell_runs_the_driver_end_to_end_and_reads_its_metrics():
    """What ``run.py`` does with a cell, on ``tiny-sage-unsup`` (the
    rehearsal list is an existing benchmark file, so the cell is not in
    it): the traced run's window, the check, and each ``link_*`` reader."""
    from chipbench import run
    from glt_tpu.obs import metrics as registry

    cfg = _json("chipbench", "configs", "tiny-sage-unsup.json")
    try:
        before = registry.snapshot()
        traffic, driver, win = _drive(cfg, trace=True)
        after = registry.snapshot()
        per_call = traffic["group"] * traffic["groups_per_call"]
        assert win.attempted == win.steps and win.steps % per_call == 0
        assert win.steps >= per_call and win.failed == 0
        assert win.metrics["seeds_per_s"] > 0
        detail = driver.check()
    finally:
        registry.disable()
        registry.reset()
    assert detail["logits_err"] < cfg["check"]["logits_rtol"]
    assert detail["neg_strict"] + detail["neg_padded"] == 32
    assert after["glt.link.node_rows"] == win.counters["node_rows"] == 4000
    assert after["glt.link.seed_union_width"] == 128
    bench = _json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == LINK_METRICS
    # On a CPU there is no device trace: the counter is read, the device
    # metrics are left out, nothing raises.
    ctx = {"trace": None, "window": win, "config": cfg, "traffic": traffic,
           "chips": 1, "compiles": 0, "registry": (before, after),
           "memory_peak_bytes": 0, "peaks": None}
    got = run.read_layer_metrics(mine, ctx)
    assert got == {"link_neg_padded_share": {"value": 0.0, "unit": "%"}}


def test_link_readers_read_a_scoped_device_trace(monkeypatch):
    """Every ``link_*`` metric out of a device trace with the program's
    scopes in it (hand-made: device ops exist on the chip only), and the
    busy time is the sum of the scoped metrics and the unscoped time."""
    from chipbench import peaks, run, scopes

    ms = 1e6
    times = [("glt.sample.hop1", 2 * ms), ("glt.sample.hop3", 48 * ms),
             ("glt.sample.induce", 20 * ms), ("glt.sample.negative", 3 * ms),
             ("glt.sample.relabel", 1 * ms), ("glt.gather.feat", 8 * ms),
             ("glt.model.msg", 60 * ms), ("glt.model.agg", 30 * ms),
             ("glt.model.dense", 40 * ms), ("glt.step.loss", 1 * ms),
             ("glt.step.update", 2 * ms), (None, 5 * ms)]
    total = sum(t for _, t in times)
    monkeypatch.setattr(scopes, "traced_file", lambda: "trace.xplane.pb")
    monkeypatch.setattr(scopes, "scope_map", lambda path: {})
    monkeypatch.setattr(scopes, "scoped_self_times",
                        lambda trace, smap: times)
    cfg = _json("chipbench", "configs", "sage-unsup-products.json")
    rows = 1000000
    trace = {"window": [0, int(total)], "devices": {"0": {
        "ops": [["fusion.1", "", 0, int(total)]], "async": [],
        "modules": []}}}
    win = Window(2, 0, {}, 2, {"node_rows": rows, "neg_padded_share": 0.25})
    bench = _json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    ctx = {"trace": trace, "window": win, "config": cfg, "traffic": {},
           "chips": 1, "compiles": 0,
           "registry": ({}, {"glt.link.node_rows": rows}),
           "memory_peak_bytes": 0, "peaks": peaks.peaks_of("TPU v5 lite")}
    got = {k: v["value"] for k, v in run.read_layer_metrics(mine, ctx).items()}
    assert sorted(got) == sorted(LINK_METRICS)
    assert got["link_sample_hop_ms"] == 25.0
    assert got["link_neg_sample_ms"] == 2.0
    assert got["link_model_agg_ms"] == 45.0
    parts = sum(got[k] for k in ("link_sample_hop_ms", "link_sample_induce_ms",
                                 "link_neg_sample_ms", "link_gather_ms",
                                 "link_model_device_ms"))
    busy = got["link_device_busy_ms"]
    unscoped = busy * got["link_unscoped_share"] / 100.0
    assert abs(parts + unscoped - busy) < 0.01 * busy
    # rows x 100 x 4 B x 2 over 4 ms a step, over 819 GB/s
    want = 100.0 * (2 * rows * 100 * 4) / 4e-3 / 819e9
    assert abs(got["link_gather_roofline"] - want) < 1e-9
    assert 0 < got["link_gather_roofline"] < 100
    # a program without the gauge (the parent): nothing to read
    spec = _json("chipbench", "layer_metrics", "link_gather_roofline.json")
    reader = importlib.import_module("chipbench.reducers." + spec["reducer"])
    assert reader.read(dict(ctx, registry=({}, {})), spec["params"]) is None
    assert reader.read(dict(ctx, trace=None, peaks=None),
                       spec["params"]) is None


def test_a_capacity_set_too_low_reports_failed_batches():
    cfg = _json("chipbench", "configs", "tiny-sage-unsup.json")
    low = dict(cfg, sampling=dict(cfg["sampling"], fanout=[2, 2, 2],
                                  node_capacity=128 + 256 + 512 + 8))
    _, driver, win = _drive(low, trace=False)
    assert driver.union.capped and driver.union.node_capacity == 904
    assert win.failed > 0 and win.failed <= win.attempted
    assert np.isfinite(driver.losses).all()


def test_the_cells_files_and_the_configuration_say_what_the_issue_asks():
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and "positive seed edges" in cell["why"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert len(entry["source"]) <= 200
    cfg = _json(entry["file"])
    assert sorted(cfg["reduced"]) == entry["reduced"]
    traffic = _json("chipbench", "traffic", cell["traffic"] + ".json")
    assert (traffic["group"], traffic["groups_per_call"],
            traffic["trace_seconds"]) == (4, 2, 6)
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "drivers", traffic["driver"] + ".py"))
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(LINK_METRICS[0])
    assert names[at: at + len(LINK_METRICS)] == LINK_METRICS
    for m in bench["per_layer"][at: at + len(LINK_METRICS)]:
        assert m["workloads"] == [CELL] and m["moves"] == "seeds_per_s"
        spec = _json("chipbench", "layer_metrics", m["name"] + ".json")
        importlib.import_module("chipbench.reducers." + spec["reducer"])
    products = _json("chipbench", "configs", "sage-products.json")
    d, sam, model = cfg["data"], cfg["sampling"], cfg["model"]
    for key in ("num_nodes", "num_edges", "feature_dim", "feature_dtype",
                "max_degree", "num_classes", "train_seeds"):
        assert d[key] == products["data"][key]        # the same graph
    assert cfg["assumed"]["degree_alpha"] == \
        products["assumed"]["degree_alpha"]
    assert (model["hidden"], model["embedding"], model["num_layers"],
            model["dropout"], model["matmul_dtype"]) == (
        256, 256, 3, 0.0, "bfloat16")
    assert sam["fanout"] == [15, 10, 5] and sam["frontier_cap"] is None
    assert (sam["neg_sampling"], sam["amount"], sam["trials"],
            sam["padding"]) == ("binary", 1, 5, True)
    assert sam["seed_union_width"] == 4 * sam["batch_size"]
    tried = cfg["batch_rule"]["tried"]
    assert sam["batch_size"] in [t["batch_size"] for t in tried]
    assert sam["batch_size"] == 1024 or "batch_size" in cfg["reduced"]
    floor = sam["seed_union_width"] * (1 + 15 + 150)
    assert floor <= sam["node_capacity"] <= d["num_nodes"]
