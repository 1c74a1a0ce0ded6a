"""Bit-identity of the degree-binned Pallas sampler vs the XLA path.

Every test runs the kernel in interpret mode (CPU, hardware-free — the
tier-1 contract); the draw is shared between paths, so any mismatch is a
neighbor-read bug, not randomness.  Covers the ISSUE 15 edge-case list:
ragged tails, degree-0 rows, all-invalid seeds, degree < fanout,
with/without replacement, edge-id on/off — over EVERY autotune candidate.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.ops.neighbor_sample import sample_neighbors
from glt_tpu.ops.sample_pallas import (
    _AUTO,
    _bin_width,
    auto_params,
    autotune_sample,
    candidate_sample_params,
    default_sample_params,
    pallas_sample_supported,
    reset_autotune,
    sample_autotune_table,
    sample_neighbors_pallas,
)


def _power_law_csr(n=300, seed=0, hub_deg=2500):
    """CSR with degree-0 rows, a hub past every bin edge, and ragged
    mid-size rows — the degree mix the binning exists for."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, 90, n)
    deg[5] = 0
    deg[11] = 0
    deg[7] = hub_deg            # > max bin edge in every candidate
    deg[23] = 513               # just past the (64, 512) top edge
    deg[29] = 64                # exactly on a bin edge
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(deg)
    e = int(indptr[-1])
    indices = rng.integers(0, n, e)
    edge_ids = rng.integers(0, 10 * e, e)
    return (jnp.asarray(indptr, jnp.int32), jnp.asarray(indices, jnp.int32),
            jnp.asarray(edge_ids, jnp.int32))


def _assert_bits_equal(ref, out, with_edge):
    assert jnp.array_equal(ref.nbrs, out.nbrs)
    assert jnp.array_equal(ref.mask, out.mask)
    if with_edge:
        assert jnp.array_equal(ref.eids, out.eids)
    else:
        assert ref.eids is None and out.eids is None


@pytest.mark.parametrize("params",
                         [None] + candidate_sample_params(),
                         ids=lambda p: "default" if p is None
                         else f"t{p[0]}_r{p[1]}_e{p[2]}")
def test_bit_identity_every_candidate(params):
    indptr, indices, edge_ids, = _power_law_csr()
    rng = np.random.default_rng(1)
    # Ragged batch: not a tile multiple, with invalid seeds sprinkled in.
    seeds = jnp.asarray(rng.integers(-2, 300, 173), jnp.int32)
    key = jax.random.PRNGKey(3)
    for wr in (False, True):
        for with_edge, egl in ((True, None), (True, edge_ids), (False, None)):
            ref = sample_neighbors(indptr, indices, seeds, 7, key,
                                   edge_ids=egl, with_replacement=wr,
                                   with_edge=with_edge, force="xla")
            out = sample_neighbors_pallas(indptr, indices, seeds, 7, key,
                                          edge_ids=egl, with_replacement=wr,
                                          with_edge=with_edge, params=params,
                                          interpret=True)
            _assert_bits_equal(ref, out, with_edge)


def test_degree_below_fanout_and_zero_degree():
    # Tiny graph: every row's degree < fanout, two rows degree 0, edge
    # array far smaller than any bin window (exercises source padding).
    row = np.array([0, 0, 1, 3])
    col = np.array([1, 2, 2, 0])
    indptr = np.zeros(7, np.int32)
    np.add.at(indptr, row + 1, 1)
    indptr = jnp.asarray(np.cumsum(indptr), jnp.int32)
    indices = jnp.asarray(col, jnp.int32)
    seeds = jnp.asarray([0, 1, 2, 4, 5, -1], jnp.int32)
    key = jax.random.PRNGKey(0)
    ref = sample_neighbors(indptr, indices, seeds, 5, key, force="xla")
    out = sample_neighbors_pallas(indptr, indices, seeds, 5, key,
                                  interpret=True)
    _assert_bits_equal(ref, out, True)
    # Full untruncated rows in CSR order where deg <= fanout.
    assert np.asarray(out.nbrs)[0, :2].tolist() == [1, 2]


def test_all_invalid_seeds():
    indptr, indices, _ = _power_law_csr(n=50, hub_deg=40)
    seeds = jnp.full((17,), -1, jnp.int32)
    out = sample_neighbors_pallas(indptr, indices, seeds, 4,
                                  jax.random.PRNGKey(9), interpret=True)
    assert not bool(out.mask.any())
    assert bool((out.nbrs == -1).all()) and bool((out.eids == -1).all())


def test_seam_force_and_env_override(monkeypatch):
    indptr, indices, edge_ids = _power_law_csr(n=80, hub_deg=100)
    seeds = jnp.asarray(np.arange(40) % 80, jnp.int32)
    key = jax.random.PRNGKey(5)
    ref = sample_neighbors(indptr, indices, seeds, 6, key,
                           edge_ids=edge_ids, force="xla")
    via_seam = sample_neighbors(indptr, indices, seeds, 6, key,
                                edge_ids=edge_ids, force="interpret")
    _assert_bits_equal(ref, via_seam, True)
    monkeypatch.setenv("GLT_SAMPLE_FORCE", "interpret")
    via_env = sample_neighbors(indptr, indices, seeds, 6, key,
                               edge_ids=edge_ids)
    _assert_bits_equal(ref, via_env, True)
    monkeypatch.setenv("GLT_SAMPLE_FORCE", "xla")
    pinned = sample_neighbors(indptr, indices, seeds, 6, key,
                              edge_ids=edge_ids, force="interpret")
    _assert_bits_equal(ref, pinned, True)


def test_interpret_inside_scan():
    # The scanned train steps trace sample_neighbors under lax.scan —
    # interpret mode must lower there too.
    indptr, indices, _ = _power_law_csr(n=60, hub_deg=70)
    seeds_blk = jnp.asarray(
        np.random.default_rng(2).integers(-1, 60, (3, 16)), jnp.int32)
    key = jax.random.PRNGKey(1)

    def epoch(force):
        def body(c, s):
            out = sample_neighbors(indptr, indices, s, 4,
                                   jax.random.fold_in(key, c), force=force)
            return c + 1, (out.nbrs, out.eids)
        return jax.lax.scan(body, jnp.zeros((), jnp.int32), seeds_blk)[1]

    nb_x, ei_x = jax.jit(lambda: epoch("xla"))()
    nb_p, ei_p = jax.jit(lambda: epoch("interpret"))()
    assert jnp.array_equal(nb_x, nb_p) and jnp.array_equal(ei_x, ei_p)


def test_bin_width_alignment():
    assert _bin_width(64) == 256
    assert _bin_width(512) == 640
    assert _bin_width(1) == 128
    for edge in (32, 64, 100, 512, 2048):
        w = _bin_width(edge)
        assert w % 128 == 0
        # Any [start, start+deg) run with deg <= edge fits the window
        # from a 128-aligned start (start - aligned <= 127).
        assert w >= edge + 127


def test_autotune_exact_shape_keys_and_cpu_pins_xla():
    # Off-TPU, the sweep must pin 'xla' (honest resolution) while still
    # keying by the EXACT (batch, fanout, dtype) — two batch sizes are
    # two table entries, never one shared winner.
    reset_autotune()
    try:
        indptr, indices, _ = _power_law_csr(n=100, hub_deg=120)
        for b in (32, 48):
            choice = autotune_sample(indptr, indices,
                                     jnp.arange(b, dtype=jnp.int32) % 100, 5)
            if jax.default_backend() != "tpu":
                assert choice == "xla"
        table = sample_autotune_table()
        assert set(table) == {"b32_f5_int32", "b48_f5_int32"}
        if jax.default_backend() != "tpu":
            assert all(v["winner"] == "xla" for v in table.values())
            assert auto_params(32, 5, jnp.int32) is None
        # The seam serves 'auto' from the memoized table without error.
        out = sample_neighbors(indptr, indices,
                               jnp.arange(32, dtype=jnp.int32) % 100, 5,
                               jax.random.PRNGKey(0), force="auto")
        assert out.nbrs.shape == (32, 5)
    finally:
        reset_autotune()


def test_pallas_sample_supported_gate():
    _, indices, _ = _power_law_csr(n=300, hub_deg=2500)
    assert pallas_sample_supported(indices, (64, 512))
    assert not pallas_sample_supported(jnp.zeros((100,), jnp.int32),
                                       (64, 512))
    t, r, edges = default_sample_params()
    assert t > 0 and r > 0 and len(edges) >= 2
