"""Dedup-aware feature row gather: fetch each unique row ONCE.

The sampler's node lists carry heavy duplication whenever the inducer is
bypassed — ``last_hop_dedup=False`` leaves every final-hop neighbor
un-deduped (power-law graphs repeat hub nodes across the whole frontier),
and raw multi-hop candidate lists repeat interior nodes across hops.  The
reference pays a hash-table pass to avoid refetching those rows
(csrc/cuda/inducer.cu); here the same economy is a pure-XLA sandwich that
stays inside the caller's jit:

    unique (first-occurrence order)  ->  row gather of the uniques
    ->  scatter rows back to every original batch position

The scatter-back step makes the output **bit-identical** to the naive
``table[ids]`` gather — same rows, same order, zeros at padding — so the
batch contract (``batch.node[:batch_size] == seeds``) is untouched: dedup
happens in row-fetch space, never in node-list space.

HBM economics: the unique gather touches ``U`` rows instead of ``B``
(``U/B`` = the dedup ratio the bench reports); the scatter-back reads the
``[B, d]`` unique-row block sequentially, which streams at full bandwidth
instead of random-row latency.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..obs.scopes import scoped
from .gather_pallas import gather_rows
from .unique import unique_first_occurrence


@scoped("glt.gather.feat")
def dedup_gather_rows(table: jnp.ndarray, ids: jnp.ndarray,
                      id2index: Optional[jnp.ndarray] = None,
                      force: str = "auto") -> jnp.ndarray:
    """Gather ``table`` rows for (duplicated, -1-padded) global ``ids``.

    Bit-identical to the naive masked gather
    ``where(ids >= 0, table[id2index[ids]], 0)`` but each distinct id's
    row is fetched from HBM exactly once.  jit/vmap/scan safe (static
    shapes throughout).

    Args:
      table: ``[N, d]`` feature rows (device-resident).
      ids: ``[B]`` int ids; negative entries are padding (zero rows out).
      id2index: optional ``[N]`` hotness indirection applied to unique
        ids before the row gather.
      force: gather implementation seam, see
        :func:`~glt_tpu.ops.gather_pallas.gather_rows`.
    """
    ids = ids.astype(jnp.int32)
    uniq, inv, _ = unique_first_occurrence(ids)
    uvalid = uniq >= 0
    uidx = jnp.where(uvalid, uniq, 0)
    if id2index is not None:
        uidx = jnp.take(id2index, uidx, axis=0, mode="clip")
    urows = jnp.where(uvalid[:, None], gather_rows(table, uidx, force), 0)
    # Scatter-back: position i reads unique slot inv[i] (-1 = padding).
    rows = jnp.take(urows, jnp.clip(inv, 0, inv.shape[0] - 1), axis=0)
    return jnp.where((inv >= 0)[:, None], rows, 0)


def dedup_counts(ids: jnp.ndarray) -> tuple:
    """``(valid, unique)`` id counts as device scalars (bench's dedup
    ratio = unique/valid; no host sync here)."""
    ids = ids.astype(jnp.int32)
    res = unique_first_occurrence(ids)
    return jnp.sum((ids >= 0).astype(jnp.int32)), res.count
