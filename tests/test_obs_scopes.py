"""Device scopes and host spans as a profiler sees them (tier-1, CPU).

Every ``glt.*`` scope of the taxonomy (``glt_tpu/obs/scopes.py``) is found
in the compiled HLO of the programs the benchmark's cells run, at the
``tiny-*`` rehearsal size; the scopes change metadata only; and
``obs.span`` shows on a ``jax.profiler`` session's host plane whether or
not a tracer is installed.
"""
import contextlib
import glob
import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import data
from glt_tpu import obs
from glt_tpu.obs.scopes import scoped
from tests.test_neighbor_sampler import sorted_slots  # noqa: F401 (fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SAMPLE = {"glt.sample.hop1", "glt.sample.hop2", "glt.sample.hop3",
          "glt.sample.induce"}
STEP = {"glt.gather.feat", "glt.gather.label", "glt.model.msg",
        "glt.model.agg", "glt.model.dense", "glt.step.loss",
        "glt.step.update"}
ROUTE = {"glt.route.bucket", "glt.route.payload", "glt.route.exchange"}
#: The whole taxonomy, by the program that must show it.
EXPECTED = {"sample": SAMPLE, "scan": SAMPLE | STEP,
            "dist": SAMPLE | STEP | ROUTE}


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _one_chip():
    from glt_tpu.sampler import NeighborSampler

    cfg = _config("tiny-sage")
    sam = cfg["sampling"]
    d = data.build_one_chip(cfg, 1, jax.devices()[0])
    sampler = NeighborSampler(d.dataset.get_graph(), sam["fanout"],
                              batch_size=sam["batch_size"], with_edge=False)
    return cfg, d, sampler


def _lower_sample():
    """``NeighborSampler._sample_impl``, the loader cell's program."""
    cfg, d, sampler = _one_chip()
    g = sampler.graph
    seeds = jnp.asarray(d.train_idx[: cfg["sampling"]["batch_size"]],
                        jnp.int32)
    return jax.jit(sampler._sample_impl).lower(
        g.indptr, g.indices, g.gather_edge_ids, seeds, jax.random.PRNGKey(0))


def _lower_scan():
    """A two-batch scanned step, the ``train-scan`` cell's program."""
    from glt_tpu.models import TrainState, make_scanned_node_train_step

    cfg, d, sampler = _one_chip()
    batch = cfg["sampling"]["batch_size"]
    model, tx = data.make_model(cfg), optax.adam(1e-3)
    feat = d.dataset.get_node_feature()
    params = model.init(
        {"params": jax.random.PRNGKey(1)},
        jnp.zeros((sampler.node_capacity, feat.shape[1]), feat.dtype),
        jnp.full((2, sampler.edge_capacity), -1, jnp.int32),
        jnp.zeros((sampler.edge_capacity,), bool))
    state = TrainState(params=params, opt_state=tx.init(params),
                       step=jnp.zeros((), jnp.int32))
    step = make_scanned_node_train_step(
        model, tx, sampler, feat, np.asarray(d.dataset.get_node_label()),
        batch)
    blk = jnp.asarray(d.train_idx[: 2 * batch].reshape(2, batch), jnp.int32)
    return jax.jit(step).lower(state, blk, jax.random.PRNGKey(2))


def _lower_dist():
    """``make_dist_train_step`` on a 2x2 CPU mesh's four devices, the
    ``dist-train`` cell's program."""
    from glt_tpu.parallel import init_dist_state, make_dist_train_step

    cfg = _config("tiny-sage-dist4")
    sam = cfg["sampling"]
    d = data.build_sharded(cfg, 1, jax.devices()[:4])
    model, tx = data.make_model(cfg), optax.adam(1e-3)
    state = init_dist_state(model, tx, d.graph, d.feature,
                            jax.random.PRNGKey(1), sam["fanout"],
                            sam["batch_size"],
                            frontier_cap=sam["frontier_cap"])
    step = make_dist_train_step(model, tx, d.graph, d.feature, d.labels,
                                d.mesh, sam["fanout"], sam["batch_size"],
                                frontier_cap=sam["frontier_cap"])
    seeds = jnp.asarray(np.stack([p[: sam["batch_size"]]
                                  for p in d.train_idx]), jnp.int32)
    return jax.jit(step).lower(state, seeds, jax.random.PRNGKey(3))


LOWER = {"sample": _lower_sample, "scan": _lower_scan, "dist": _lower_dist}
#: What is debug information in an HLO module's text: each instruction's
#: ``metadata={...}`` and the module's tables of files, functions,
#: locations and stack frames that ``stack_frame_id`` points into.
_METADATA = re.compile(
    r",? ?metadata=\{[^{}]*\}"
    r"|^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*",
    re.M)


@pytest.fixture(scope="module")
def compiled_text():
    """``{program: (with the scopes, with jax.named_scope a null
    context)}``, each compiled once for the module."""
    cache = {}

    def get(program):
        if program not in cache:
            scoped_text = LOWER[program]().compile().as_text()
            real = jax.named_scope
            jax.clear_caches()          # inner jits traced with the scopes
            jax.named_scope = lambda name: contextlib.nullcontext()
            try:
                plain_text = LOWER[program]().compile().as_text()
            finally:
                jax.named_scope = real
                jax.clear_caches()
            cache[program] = (scoped_text, plain_text)
        return cache[program]

    return get


@pytest.mark.parametrize("program", sorted(LOWER))
def test_every_scope_of_the_taxonomy_is_in_the_compiled_hlo(
        compiled_text, program):
    text, _ = compiled_text(program)
    found = set(re.findall(r"glt\.[a-z0-9_]+\.[a-z0-9_]+", " ".join(
        re.findall(r'op_name="([^"]*)"', text))))
    assert EXPECTED[program] <= found, EXPECTED[program] - found
    # and nothing outside the taxonomy
    assert found <= SAMPLE | STEP | ROUTE, found - (SAMPLE | STEP | ROUTE)


@pytest.mark.parametrize("program", sorted(LOWER))
def test_the_sorted_last_hop_stays_under_the_inducers_scope(
        compiled_text, program):
    """The four sorts a call of ``ops/unique.py::induce`` (the seeds and
    three hops; XLA folds two of the seeds' away) are in every cell's
    program and carry ``glt.sample.induce``, so ``sample_induce_ms``
    reads them and ``unscoped_share`` does not."""
    text, _ = compiled_text(program)
    sorts = [re.findall(r'op_name="([^"]*)"', line)
             for line in text.splitlines() if re.search(r"\bsort\(", line)]
    # The dist step's served feature read (4 x 656 request slots at the
    # tiny shape: past one chunk) orders its chunks by a sort of its own,
    # under the gather's scope.
    read = [names for names in sorts
            if names and "glt.gather.feat/" in names[0]]
    assert len(read) == (program == "dist"), read
    sorts = [names for names in sorts if names not in read]
    assert 4 * 3 < len(sorts) <= 4 * 4
    assert all(names and names[0].endswith("glt.sample.induce/sort")
               for names in sorts), sorts


@pytest.mark.parametrize("program", sorted(LOWER))
def test_no_cell_program_holds_an_id_map(compiled_text, program):
    """Every chain of the cells' programs is sorted: no ``s32[N + 2]``
    array, the id map of ``dense_induce``, is left in the compiled HLO."""
    text, _ = compiled_text(program)
    nodes = _config({"dist": "tiny-sage-dist4"}.get(
        program, "tiny-sage"))["data"]["num_nodes"]
    # the dist sampler's id space: equal shards of the padded node count
    for n in {nodes, -(-nodes // 4) * 4}:
        assert f"s32[{n + 2}]" not in text, n


@pytest.mark.parametrize("chain", ["sorted", "map"])
def test_the_engagement_gauge_is_set_for_every_hop(chain, sorted_slots):
    """``glt.sample.induce_sorted_slots{hop}`` reads ``known_k + m_k`` for
    the seeds (hop 0) and every hop of a sorted chain, 0 for every hop of
    a chain that keeps the id map (a capacity under the bound on known
    nodes).  tests/test_dist_train.py reads it for the dist sampler."""
    from glt_tpu.sampler import NeighborSampler
    from tests.test_neighbor_sampler import hop_graph

    # batch 8, fanout [3, 3, 2], frontier cap 16: candidates 24, 48, 32;
    # 8 + 24 + 48 = 80 nodes known at most before the last hop.
    widths = [8, 24, 48, 32]
    s = NeighborSampler(hop_graph(), [3, 3, 2], batch_size=8,
                        frontier_cap=16, with_edge=False,
                        node_capacity={"sorted": 80, "map": 79}[chain])
    g = s.graph
    jax.jit(s._sample_impl).lower(
        g.indptr, g.indices, g.gather_edge_ids, jnp.zeros((8,), jnp.int32),
        jax.random.PRNGKey(0))
    assert [sorted_slots(k) for k in range(4)] == [
        sum(widths[:k + 1]) if chain == "sorted" else 0 for k in range(4)]


def _without_debug_info(text):
    """An HLO module's text less its metadata, with every ``%name``
    numbered by first appearance: XLA derives instruction names from the
    name stack too (``%jvp_jit_take__.21``), and a name is a label."""
    numbers = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: numbers.setdefault(m.group(0),
                                               f"%{len(numbers)}"),
                  _METADATA.sub("", text))


@pytest.mark.parametrize("program", sorted(LOWER))
def test_the_scopes_change_metadata_only(compiled_text, program):
    text, plain = compiled_text(program)
    assert "glt." in text and "glt." not in plain
    assert text != plain
    assert _without_debug_info(text) == _without_debug_info(plain)


def test_scoped_opens_a_fresh_scope_per_call_and_keeps_the_function():
    @scoped("glt.test.outer")
    def twice(x, depth=1):
        """doc"""
        return twice(x, depth - 1) * 2 if depth else x + 1

    assert twice.__name__ == "twice" and twice.__doc__ == "doc"
    text = jax.jit(twice).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "glt.test.outer/glt.test.outer/add" in text
    # a second thread tracing at once keeps its own name stack
    barrier, seen = threading.Barrier(2, timeout=30), {}

    @scoped("glt.test.thread")
    def f(x, tag):
        barrier.wait()
        return x * 3

    def trace(tag):
        def g(x):
            with jax.named_scope(f"only_{tag}"):
                return f(x, tag) + 1

        seen[tag] = jax.make_jaxpr(g)(jnp.ones(2)).pretty_print(
            name_stack=True)

    threads = [threading.Thread(target=trace, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    for tag, other in (("a", "b"), ("b", "a")):
        assert f"only_{tag}" in seen[tag] and f"only_{other}" not in seen[tag]


# -- host spans on the profiler's clock ---------------------------------------

def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    return [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@contextlib.contextmanager
def _profile(trace_dir):
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def test_span_without_a_tracer_shows_on_the_profilers_host_plane(tmp_path):
    assert obs.current() is None
    with _profile(tmp_path):
        with obs.span("unit.no_tracer", k=1) as sp:
            assert sp.fence(jnp.ones(3)) is not None
            sp.set(more=2)
            assert sp.link("ab", 1) is sp and sp.context() is None
            assert sp.span_id is None and sp.trace_id is None
    assert "glt.unit.no_tracer" in _host_events(tmp_path)


def test_span_with_a_tracer_feeds_both_sinks(tmp_path):
    tracer = obs.start_trace()
    try:
        with _profile(tmp_path):
            with obs.span("unit.both") as sp:
                sp.fence({"loss": jnp.ones((64, 64)).sum(), "n": 3})
    finally:
        obs.stop_trace()
    assert "glt.unit.both" in _host_events(tmp_path)
    (event,) = [e for e in tracer.events if e["name"] == "unit.both"]
    assert event["args"]["device_wait_us"] >= 0


@pytest.mark.parametrize("capped", [True, False])
def test_loader_spans_and_the_overflow_wait(tmp_path, capped):
    """``loader.overflow_wait`` wraps the one blocking fetch of
    ``next()``, and is there only when the overflow check is active."""
    from glt_tpu.loader import NeighborLoader

    cfg = _config("tiny-sage")
    sam = cfg["sampling"]
    d = data.build_one_chip(cfg, 1, jax.devices()[0])
    loader = NeighborLoader(
        d.dataset, sam["fanout"], d.train_idx[: 3 * sam["batch_size"]],
        batch_size=sam["batch_size"], with_edge=False,
        node_capacity=544 if capped else None)   # the least allowed
    with _profile(tmp_path):
        batches = [jax.block_until_ready(b.x) for b in loader]
    assert len(batches) == 3
    names = _host_events(tmp_path)
    assert names.count("glt.loader.sample_dispatch") >= 3
    assert names.count("glt.loader.collate") == 3
    assert ("glt.loader.overflow_wait" in names) == capped
    if capped:
        assert names.count("glt.loader.overflow_wait") == 3
        assert loader.overflow_batches >= 1     # 544 rows hold few batches
        assert names.count("glt.loader.overflow_replay") \
            == loader.overflow_batches


def test_compile_cache_key_holds_the_metadata(monkeypatch, tmp_path):
    """A scope added without a change to the arithmetic must compile
    anew, or a profile shows the old names: see utils/compile_cache."""
    from glt_tpu.utils import compile_cache

    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    before = {f: getattr(jax.config, f) for f in flags}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        rx = jax.config.jax_hlo_source_file_canonicalization_regex
        here = os.path.abspath(__file__)
        assert re.sub(rx, "", here) == os.path.relpath(here, ROOT)
    finally:
        for f, v in before.items():
            jax.config.update(f, v)
