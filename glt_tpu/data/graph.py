"""Device-resident graph storage.

Rebuild of the reference's ``Graph`` (python/data/graph.py:124-239 +
csrc/cuda/graph.cu).  The CUDA version has three residency modes — CPU,
ZERO_COPY (pinned host memory read over UVA) and CUDA/DMA (full HBM copy).
The TPU analogues are:

* ``'DEVICE'`` — CSR arrays live in TPU HBM as jax Arrays (≈ DMA mode);
* ``'HOST'``   — CSR stays in host numpy; sampling runs on CPU backend or
  the arrays stream to device per call (≈ CPU mode).

ZERO_COPY has no TPU equivalent (no UVA); its role — graphs larger than one
device — is covered by sharding the graph across a mesh instead
(:mod:`glt_tpu.parallel`).  Lazy init mirrors ``Graph.lazy_init``
(data/graph.py:160-188).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .topology import CSRTopo

_MODES = ("DEVICE", "HOST")


class Graph:
    """CSR graph with lazily materialised device arrays.

    Args:
      topo: host :class:`CSRTopo`.
      mode: 'DEVICE' (HBM-resident) or 'HOST'.
      with_sorted_columns: also build a column-sorted CSR view used by the
        strict negative sampler's binary search
        (csrc/cuda/random_negative_sampler.cu:37-54).
    """

    def __init__(self, topo: CSRTopo, mode: str = "DEVICE",
                 with_sorted_columns: bool = False):
        mode = mode.upper()
        if mode not in _MODES:
            raise ValueError(f"mode must be one of {_MODES}, got {mode!r}")
        self.topo = topo
        self.mode = mode
        self._indptr: Optional[jnp.ndarray] = None
        self._indices: Optional[jnp.ndarray] = None
        self._edge_ids: Optional[jnp.ndarray] = None
        self._sorted_indices: Optional[jnp.ndarray] = None
        self._with_sorted_columns = with_sorted_columns
        self._trivial_edge_ids: Optional[bool] = None

    # -- lazy init (cf. data/graph.py:160-188) -----------------------------
    def lazy_init(self) -> None:
        if self._indptr is not None:
            return
        # ensure_compile_time_eval: materialisation must stay eager even when
        # a Graph property is first touched inside a jit trace — otherwise
        # tracers would be cached on the object and leak.
        with jax.ensure_compile_time_eval():
            as_arr = jnp.asarray if self.mode == "DEVICE" else np.asarray
            # copy=False: already-int32 arrays (e.g. shared-memory
            # attaches) stay views instead of per-process copies.
            self._indptr = as_arr(self.topo.indptr.astype(np.int32,
                                                          copy=False))
            self._indices = as_arr(self.topo.indices.astype(np.int32,
                                                            copy=False))
            host_eids = self.topo.edge_ids.astype(np.int32, copy=False)
            self._edge_ids = as_arr(host_eids)
            # Trivial (positional) edge ids need no gather at sample time:
            # the sampler can emit CSR positions directly, skipping one
            # random read over the edge array per hop.
            self._trivial_edge_ids = bool(
                host_eids.shape[0] == 0
                or (host_eids[0] == 0
                    and host_eids[-1] == host_eids.shape[0] - 1
                    and np.array_equal(
                        host_eids,
                        np.arange(host_eids.shape[0], dtype=np.int32))))
            if self._with_sorted_columns:
                self._build_sorted_view()

    def _build_sorted_view(self) -> None:
        """The column-sorted view beside the placed arrays: sorted on the
        device where the graph lives there (a two-key sort of the edge
        array, under two seconds for ogbn-products' 123.7 M edges where
        the host's lexsort takes 47 s; my chip run, PR 30), else on the
        host."""
        with jax.ensure_compile_time_eval():
            if self.mode == "DEVICE":
                self._sorted_indices = _sort_columns_on_device(
                    self._indptr, self._indices)
            else:
                self._sorted_indices = _sort_columns_within_rows(
                    self.topo.indptr, self.topo.indices).astype(np.int32)

    @property
    def indptr(self) -> jnp.ndarray:
        self.lazy_init()
        return self._indptr

    @property
    def indices(self) -> jnp.ndarray:
        self.lazy_init()
        return self._indices

    @property
    def edge_ids(self) -> jnp.ndarray:
        self.lazy_init()
        return self._edge_ids

    @property
    def gather_edge_ids(self) -> Optional[jnp.ndarray]:
        """Edge-id array for samplers, or None when ids are positional
        (the sampler then emits CSR positions without a gather)."""
        self.lazy_init()
        return None if self._trivial_edge_ids else self._edge_ids

    @property
    def sorted_indices(self) -> jnp.ndarray:
        self.lazy_init()
        if self._sorted_indices is None:
            self._with_sorted_columns = True
            self._build_sorted_view()
        return self._sorted_indices

    @property
    def num_nodes(self) -> int:
        return self.topo.num_nodes

    @property
    def num_edges(self) -> int:
        return self.topo.num_edges

    def __repr__(self) -> str:
        return (f"Graph(num_nodes={self.num_nodes}, num_edges={self.num_edges},"
                f" mode={self.mode!r})")


@jax.jit
def _sort_columns_on_device(indptr: jnp.ndarray, indices: jnp.ndarray
                            ) -> jnp.ndarray:
    """:func:`_sort_columns_within_rows` as one device program: each edge
    position's row (a running count of the row starts at or before it),
    then a sort by (row, neighbour id)."""
    starts = jnp.zeros(indices.shape, jnp.int32).at[indptr[1:-1]].add(
        1, mode="drop")
    return jax.lax.sort((jnp.cumsum(starts), indices), num_keys=2)[1]


def _sort_columns_within_rows(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Sort neighbor ids within each CSR row (host-side, one-time prep)."""
    row = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    order = np.lexsort((indices, row))
    return np.asarray(indices)[order]
