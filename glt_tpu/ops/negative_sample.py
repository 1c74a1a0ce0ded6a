"""Random negative edge sampling, XLA-native.

Rebuild of ``csrc/cuda/random_negative_sampler.cu``: the CUDA kernel draws
uniform (row, col) pairs, rejects existing edges with a per-row binary search
(``EdgeInCSR``, random_negative_sampler.cu:37-54) over ``trials_num``
retries, compacts survivors with thrust, and optionally pads with non-strict
draws (:153-160).

TPU design: draw all ``trials x num`` candidates at once, test them with a
vectorised 32-step binary search over column-sorted CSR rows, and pick the
first passing trial per slot with an argmin — no compaction pass, no dynamic
shapes, no host sync.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..typing import PADDING_ID

_INT32_MAX = jnp.iinfo(jnp.int32).max


def edge_in_csr(
    indptr: jnp.ndarray,
    sorted_indices: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
) -> jnp.ndarray:
    """Vectorised membership test: does edge (src, dst) exist?

    ``sorted_indices`` must have columns sorted within each CSR row (the
    ``Graph`` class maintains this auxiliary view).  Classic branchless
    binary search, unrolled to 32 steps — same primitive the CUDA kernel
    runs per thread (random_negative_sampler.cu:37-54).
    """
    valid = (src >= 0) & (dst >= 0)
    s = jnp.where(valid, src, 0)
    lo = indptr[s].astype(jnp.int32)
    hi = indptr[s + 1].astype(jnp.int32)
    row_end = hi
    d = dst.astype(jnp.int32)
    last = sorted_indices.shape[0] - 1
    # Branchless lower_bound over [lo, hi): 32 unrolled halving steps cover
    # any int32-sized row.
    for _ in range(32):
        cond = lo < hi
        mid = lo + (hi - lo) // 2  # overflow-safe for E > 2^30
        mid_val = sorted_indices[jnp.clip(mid, 0, last)]
        go_right = cond & (mid_val < d)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(cond & ~go_right, mid, hi)
    in_row = lo < row_end
    exists = in_row & (sorted_indices[jnp.clip(lo, 0, last)] == d)
    return exists & valid


def weighted_draw(key: jax.Array, cdf: jnp.ndarray, shape) -> jnp.ndarray:
    """Categorical node draw (with replacement) by inverse-CDF lookup.

    ``cdf`` is the normalized cumulative node-weight vector (last entry
    1.0).  Replaces the reference/PyG ``torch.multinomial(weight, ...,
    replacement=True)`` draw (sampler/base.py:84-145 ``weight``) with a
    branchless ``searchsorted`` — one fused gather-free kernel, no host
    sync, exact per-draw distribution.
    """
    u = jax.random.uniform(key, shape)
    idx = jnp.searchsorted(cdf, u, side="right")
    return jnp.clip(idx, 0, cdf.shape[0] - 1).astype(jnp.int32)


def weight_to_cdf(weight) -> jnp.ndarray:
    """Normalized inclusive cumsum of a non-negative node-weight vector."""
    w = jnp.asarray(weight, jnp.float32)
    c = jnp.cumsum(w)
    return c / c[-1]


class NegativeSampleOutput(NamedTuple):
    src: jnp.ndarray   # [num] sampled source ids (-1 where nothing found)
    dst: jnp.ndarray   # [num]
    mask: jnp.ndarray  # [num] bool
    # [num] bool: the slot passed a strict trial, so it is not an edge;
    # False under ``padding`` is the non-strict pass (a drawn pair that
    # may be an edge), which ``mask`` alone cannot tell from a strict one.
    strict: jnp.ndarray


def sample_negative_edges(
    indptr: jnp.ndarray,
    sorted_indices: jnp.ndarray,
    num: int,
    key: jax.Array,
    num_nodes: int,
    trials: int = 5,
    padding: bool = True,
    num_dst_nodes: int = None,
    src_cdf: jnp.ndarray = None,
    dst_cdf: jnp.ndarray = None,
) -> NegativeSampleOutput:
    """Draw ``num`` node pairs that are (probably) not edges.

    Mirrors ``CUDARandomNegativeSampler::Sample``
    (random_negative_sampler.cu:118): ``trials`` strict rejection rounds,
    then, when ``padding`` is set, unfilled slots fall back to their last
    (possibly positive) draw so the output is always exactly ``num`` pairs —
    the reference's non-strict padding pass (:153-160).  ``strict`` says
    per slot which of the two it was.

    Hetero seed-edge types pass ``num_dst_nodes`` (dst drawn over the
    destination type's id space); ``src_cdf``/``dst_cdf`` switch the
    uniform draws to weighted ones (``NegativeSampling.weight``).
    """
    if num_dst_nodes is None:
        num_dst_nodes = num_nodes
    ks, kd = jax.random.split(key)
    if src_cdf is not None:
        src = weighted_draw(ks, src_cdf, (trials, num))
    else:
        src = jax.random.randint(ks, (trials, num), 0, num_nodes,
                                 dtype=jnp.int32)
    if dst_cdf is not None:
        dst = weighted_draw(kd, dst_cdf, (trials, num))
    else:
        dst = jax.random.randint(kd, (trials, num), 0, num_dst_nodes,
                                 dtype=jnp.int32)
    exists = edge_in_csr(indptr, sorted_indices, src.ravel(), dst.ravel())
    exists = exists.reshape(trials, num)
    # First passing trial per slot; INT32_MAX when none pass.
    trial_idx = jnp.arange(trials, dtype=jnp.int32)[:, None]
    score = jnp.where(exists, _INT32_MAX, trial_idx)
    best = jnp.argmin(score, axis=0)
    ok = jnp.take_along_axis(~exists, best[None, :], axis=0)[0]
    pick = lambda a: jnp.take_along_axis(a, best[None, :], axis=0)[0]
    out_src, out_dst = pick(src), pick(dst)
    if padding:
        return NegativeSampleOutput(out_src, out_dst, jnp.ones_like(ok), ok)
    out_src = jnp.where(ok, out_src, PADDING_ID)
    out_dst = jnp.where(ok, out_dst, PADDING_ID)
    return NegativeSampleOutput(out_src, out_dst, ok, ok)
