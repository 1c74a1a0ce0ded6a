"""The heterogeneous cell's own benchmark code at ``tiny-rgat`` size on the
CPU: generator against reference, the driver end to end, the logits
tolerance, the reader of ``hetero_gather_roofline``, and the files of
``rgat-igbh-small.hetero-train-scan``."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import checks, data_hetero, gen_hetero  # noqa: E402
from chipbench import reference_hetero  # noqa: E402
from chipbench.common import Env, Window  # noqa: E402

CELL = "rgat-igbh-small.hetero-train-scan"


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def tiny():
    cfg = _json("chipbench", "configs", "tiny-rgat.json")
    return cfg, data_hetero.build_hetero_one_chip(cfg, 5)


def test_generator_sizes_and_the_reference_recompute_it(tiny):
    cfg, d = tiny
    rels = gen_hetero.relations_of(cfg)
    assert len(d.graphs) == 7 and len(d.feats) == 4
    for rel in rels:
        g = d.graphs[rel.etype]
        assert g.topo.indptr.shape == (rel.num_src + 1,)
        assert g.topo.indices.shape == (rel.num_edges,)
        assert g.topo.indices.max() < rel.num_dst
        deg = np.diff(g.topo.indptr)
        assert deg.min() >= rel.min_degree + int(rel.self_loops) * (
            2 if rel.symmetric else 1)
        if rel.self_loops:      # one self loop a source row, up front
            assert (g.topo.indices[g.topo.indptr[:-1]]
                    == np.arange(rel.num_src)).all()
        if rel.symmetric:       # u in row v as often as v in row u
            assert rel.num_edges == 2 * (rel.drawn_edges + rel.num_src)
            src = np.repeat(np.arange(rel.num_src), deg)
            key = np.sort(src * rel.num_src + g.topo.indices)
            assert (key == np.sort(g.topo.indices * rel.num_src + src)).all()
        for node in (0, 7, rel.num_src - 1):
            lo, hi = g.topo.indptr[node], g.topo.indptr[node + 1]
            assert (d.ref.neighbours(rel.etype, node)
                    == g.topo.indices[lo:hi]).all()
    # popular destinations: the in-degree law is skewed
    topic = d.graphs[("fos", "rev_topic", "paper")]
    deg = np.diff(topic.topo.indptr)
    assert deg.max() > 5 * deg.mean()
    assert d.ref.check_transposes(
        {et: (g.topo.indptr, g.topo.indices)
         for et, g in d.graphs.items()}) == 14000 + 6000 + 2500 + 8000
    for t, f in d.feats.items():
        rows = np.asarray(f.hot_rows)
        assert rows.dtype.name == "bfloat16" and rows.shape[1] == 1024
        pick = np.array([0, 3, rows.shape[0] - 1])
        assert (rows[pick].astype(np.float32)
                == d.ref.features(t, pick)).all()
        assert (d.ref.features(t, [-1]) == 0).all()
    assert (d.labels[:50] == d.ref.labels(np.arange(50))).all()
    assert d.train_idx.tolist() == list(range(1200))
    # another seed, another graph, the same shapes
    other = gen_hetero.generate_relation(rels[1], 6)
    mine = d.ref.made[rels[1].etype]
    assert other.indices.shape == mine.indices.shape
    assert (other.indices != mine.indices).any()


@pytest.mark.parametrize("etype, slot, what", [
    (("author", "rev_written_by", "paper"), 17, "transposed slot"),
    # a paper's row under the symmetric relation: its self loop and its
    # forward edges first (slot 1 is forward), its transposed row last
    (("paper", "cites", "paper"), 1, "differ from the draws"),
    (("paper", "cites", "paper"), -1, "transposed slot")])
def test_a_wrong_transpose_is_caught(tiny, etype, slot, what):
    cfg, d = tiny
    csr = {et: (g.topo.indptr, g.topo.indices.copy())
           for et, g in d.graphs.items()}
    csr[etype][1][slot] += 1
    with pytest.raises(checks.CheckFailure, match=what):
        d.ref.check_transposes(csr)


def _batch(cfg, d, **sampler_args):
    import jax
    import jax.numpy as jnp

    from glt_tpu.models.train import hetero_gather_xy
    from glt_tpu.sampler import NodeSamplerInput
    from glt_tpu.sampler.hetero_neighbor_sampler import HeteroNeighborSampler

    sam = cfg["sampling"]
    sampler = HeteroNeighborSampler(d.graphs, sam["fanout"], d.seed_type,
                                    batch_size=sam["batch_size"],
                                    **sampler_args)
    out = sampler.sample_from_nodes(
        NodeSamplerInput(np.arange(sam["batch_size"]) * 3))
    x, y = hetero_gather_xy({t: f.hot_rows for t, f in d.feats.items()},
                            jnp.asarray(d.labels), out, sam["batch_size"])
    return sampler, out, x, y, {
        "node": out.node, "node_mask": out.node_mask, "x": x, "y": y,
        "seeds": out.batch[d.seed_type], "row": out.row, "col": out.col,
        "edge_mask": out.edge_mask}


def test_batch_check_passes_the_sampler_and_fails_a_capped_frontier(tiny):
    cfg, d = tiny
    sam = cfg["sampling"]
    args = (d.seed_type, sam["batch_size"], sam["fanout"], "batch",
            np.random.default_rng(0))
    *_, batch = _batch(cfg, d)
    reference_hetero.check_hetero_batch(d.ref, batch, *args)
    *_, cut = _batch(cfg, d, frontier_cap=8)
    with pytest.raises(checks.CheckFailure, match="min\\(degree, fanout\\)"):
        reference_hetero.check_hetero_batch(d.ref, cut, *args)
    wrong = dict(batch, x=dict(batch["x"]))
    wrong["x"]["fos"] = wrong["x"]["fos"].at[0, 0].add(1)
    with pytest.raises(checks.CheckFailure, match="fos rows differ"):
        reference_hetero.check_hetero_batch(d.ref, wrong, *args)


def test_reference_forward_tells_bf16_from_a_lower_precision(tiny):
    """The logits tolerance passes the program's bf16 matmuls (through the
    step's own forward, last layer over the seeds' hops) and fails inputs
    and weights rounded to four mantissa bits."""
    import jax
    import jax.numpy as jnp

    cfg, d = tiny
    sampler, out, x, _, _ = _batch(cfg, d)
    n = cfg["sampling"]["batch_size"]
    model = data_hetero.make_model(cfg)
    ei = {et: jnp.stack([out.row[et], out.col[et]]) for et in out.row}
    params = model.init({"params": jax.random.PRNGKey(0)}, x, ei,
                        out.edge_mask)
    live = {et: (np.asarray(out.row[et])[np.asarray(out.edge_mask[et])],
                 np.asarray(out.col[et])[np.asarray(out.edge_mask[et])])
            for et in out.row}
    want = reference_hetero.rgnn_seed_logits(
        reference_hetero.layer_weights(params, model.edge_types, 3), x,
        live, d.seed_type, n)

    def forward(p, x):
        return model.apply(p, x, ei, out.edge_mask, train=False,
                           hops=sampler.hop_bounds)[:n]

    rtol = _json("chipbench", "configs",
                 "rgat-igbh-small.json")["check"]["logits_rtol"]
    assert cfg["check"]["logits_rtol"] == rtol
    assert checks.check_logits(forward(params, x), want, rtol,
                               "bf16") < rtol / 2

    def crush(a):       # keep four mantissa bits
        m, e = np.frexp(np.asarray(a, np.float32))
        return jnp.asarray(np.ldexp(np.round(m * 32) / 32, e), jnp.float32)

    with pytest.raises(checks.CheckFailure, match="logits differ"):
        checks.check_logits(
            forward(jax.tree.map(crush, params), jax.tree.map(crush, x)),
            want, rtol, "4-bit")


def test_tiny_cell_runs_the_driver_end_to_end():
    """What ``run.py`` does with a cell, on ``tiny-rgat`` (the rehearsal
    list is an existing benchmark file, so the cell is not in it)."""
    import jax

    from glt_tpu.obs import compilewatch

    cfg = _json("chipbench", "configs", "tiny-rgat.json")
    traffic = _json("chipbench", "traffic", "hetero-train-scan.json")
    compilewatch.install()
    env = Env(cfg, traffic, 2 ** 31 + 5, jax.devices()[:1], False,
              lambda msg: None)
    driver = importlib.import_module(
        "chipbench.drivers." + traffic["driver"]).Driver(env)
    before = compilewatch.total_compiles()
    win = driver.window(1.0)
    assert compilewatch.total_compiles() == before
    per_call = traffic["group"] * traffic["groups_per_call"]
    assert win.attempted == win.steps and win.steps % per_call == 0
    assert win.steps >= per_call and win.failed == 0
    assert win.metrics["seeds_per_s"] > 0
    detail = driver.check()
    assert detail["logits_err"] < cfg["check"]["logits_rtol"]
    assert detail["transposed_edges"] == 30500


def test_gather_roofline_reader_has_nothing_to_read_without_a_trace():
    spec = _json("chipbench", "layer_metrics", "hetero_gather_roofline.json")
    reader = importlib.import_module("chipbench.reducers." + spec["reducer"])
    win = Window(8, 0, {}, 8, {})
    ctx = {"trace": None, "window": win, "peaks": None, "registry": ({}, {}),
           "config": _json("chipbench", "configs", "rgat-igbh-small.json")}
    assert reader.read(ctx, spec["params"]) is None
    assert reader.gather_bytes({"a": 10, "b": 6}, 1024, 2) == 2 * 16 * 2048


def test_the_cells_files_and_the_configuration_say_what_the_issue_asks():
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and bench["workloads"][-1] == cell
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["batch_size"]
    cfg = _json(entry["file"])
    traffic = _json("chipbench", "traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "drivers", traffic["driver"] + ".py"))
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 9 and all(m["name"].startswith("hetero_")
                                  and m["moves"] == "seeds_per_s"
                                  for m in mine)
    assert bench["per_layer"][-9:] == mine
    for m in mine:
        spec = _json("chipbench", "layer_metrics", m["name"] + ".json")
        importlib.import_module("chipbench.reducers." + spec["reducer"])
    d, sam, model = cfg["data"], cfg["sampling"], cfg["model"]
    assert d["node_types"] == {"paper": 1000000, "author": 1926066,
                               "institute": 14751, "fos": 190449}
    assert [r["num_edges"] for r in d["relations"]][0] == 12070502
    assert len(d["relations"]) + sum(
        1 for r in d["relations"] if r.get("transpose")) == 7
    assert (d["feature_dim"], d["feature_dtype"], d["num_classes"],
            d["train_seeds"]) == (1024, "bfloat16", 2983, 600000)
    assert (model["hidden"], model["num_layers"], model["heads"],
            model["dropout"]) == (512, 3, 4, 0.2)
    assert sam["fanout"] == [15, 10, 5] and sam["frontier_cap"] is None
    assert sorted(cfg["reduced"]) == ["batch_size"]
    assert set(sam["node_capacity"]) == set(d["node_types"])
    assert cfg["batch_rule"]["tried"]
    assert sam["batch_size"] in [t["batch_size"]
                                 for t in cfg["batch_rule"]["tried"]]
    # 6.41 GB of rows
    assert sum(d["node_types"].values()) * 1024 * 2 == 6412832768
