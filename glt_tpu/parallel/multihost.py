"""Multi-host execution: process-spanning mesh + per-host array feeding.

TPU-native replacement for the reference's cross-machine plane.  There,
every process joins a torch-RPC universe: ``init_rpc`` all-gathers
``(role, world_size, rank)`` tuples from every process to build name
tables (distributed/rpc.py:236-292), with rendezvous via the
``MASTER_ADDR``/``MASTER_PORT`` env convention
(distributed/dist_options.py:75-100), and every cross-host sample/feature
request is an RPC.

On TPU none of that machinery survives: the cross-host plane is
``jax.distributed`` — one coordinator process, every process contributes
its local chips to ONE global :class:`~jax.sharding.Mesh`, and the
collectives inside the jitted programs (`dist_sampler`, `dist_feature`,
`dist_train`) ride ICI within a host and DCN between hosts, routed by XLA
from the same sharding annotations that drove the single-process path.
The "name table" is the device mesh; the "partition book" stays
arithmetic.  What this module adds is the *host-side seam*:

* :func:`initialize` — rendezvous (env-var conventions kept from the
  reference: ``MASTER_ADDR``/``MASTER_PORT``, plus ``GLT_*`` overrides);
* :func:`global_mesh` — a mesh over every process's devices;
* per-host **global array assembly** — each process feeds only the shard
  blocks it owns (graph CSR blocks, feature rows, labels, seed batches)
  via ``jax.make_array_from_process_local_data``, so no host ever
  materialises another host's partition.

Single-process meshes are the degenerate case: every helper works
unchanged when ``jax.process_count() == 1``, so the training-step
builders in :mod:`~glt_tpu.parallel.dist_train` need no changes at all —
the same jitted program runs on a laptop mesh, a v5e-8, or a multi-host
v5e-16 (4 processes x 4 chips).

Emulation without a pod (the reference's single-host multi-process test
strategy, SURVEY §4): spawn N processes with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=K``
and a localhost coordinator; collectives cross process boundaries over
gloo.  See tests/test_multihost.py.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..data.topology import CSRTopo
from .sharding import ShardedFeature, ShardedGraph, shard_graph_blocks


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the multi-host runtime (idempotent).

    Defaults come from the environment, keeping the reference's rendezvous
    convention (dist_options.py:75-100): ``MASTER_ADDR``/``MASTER_PORT``
    form the coordinator address, ``WORLD_SIZE``/``RANK`` (or the
    explicit ``GLT_NUM_PROCESSES``/``GLT_PROCESS_ID``) give the fleet
    shape.  On Cloud TPU pods with no env set, ``jax.distributed``
    auto-detects all three from the TPU metadata server.
    """
    # NOTE: must not touch the backend (jax.devices / process_count)
    # before jax.distributed.initialize — only the is_initialized check
    # below is safe.
    if jax.distributed.is_initialized():
        return
    if coordinator_address is None:
        addr = os.environ.get("GLT_COORDINATOR_ADDR")
        if addr is None:
            host = os.environ.get("MASTER_ADDR")
            port = os.environ.get("MASTER_PORT")
            addr = f"{host}:{port}" if host and port else None
        coordinator_address = addr
    if num_processes is None:
        n = os.environ.get("GLT_NUM_PROCESSES",
                           os.environ.get("WORLD_SIZE"))
        num_processes = int(n) if n is not None else None
    if process_id is None:
        r = os.environ.get("GLT_PROCESS_ID", os.environ.get("RANK"))
        process_id = int(r) if r is not None else None
    # A multi-process CPU fleet needs a cross-process collectives
    # implementation — without one XLA rejects the first process-spanning
    # computation ("Multiprocess computations aren't implemented on the
    # CPU backend").  Gloo ships in jaxlib; select it before the backend
    # client is created.  TPU/GPU fleets ignore this knob.
    if "cpu" in (os.environ.get("JAX_PLATFORMS")
                 or jax.config.jax_platforms or ""):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def shutdown() -> None:
    if jax.distributed.is_initialized():
        jax.distributed.shutdown()


# -- deadline-bounded collectives ------------------------------------------
#
# The characteristic multihost failure mode is the forever-hang: one
# preempted host leaves every surviving peer blocked inside a collective
# with no exception and no timeout.  With GLT_MULTIHOST_TIMEOUT_S set,
# every host-side collective in this module runs under the supervisor's
# deadline wrapper and a dead/straggling peer surfaces as a structured
# BarrierTimeoutError the training loop converts into a
# checkpoint-and-exit (docs/distributed.md "Fleet supervision").  Unset
# (the default), behavior is exactly as before — zero wrapper overhead.

#: Env var: seconds a multihost barrier/collective may block before a
#: structured BarrierTimeoutError; 0/unset = unbounded (legacy).
TIMEOUT_ENV = "GLT_MULTIHOST_TIMEOUT_S"


def collective_deadline_secs() -> float:
    """The configured collective deadline (0.0 = unbounded)."""
    try:
        return float(os.environ.get(TIMEOUT_ENV, "0") or 0.0)
    except ValueError:
        return 0.0


def _bounded(fn, what: str):
    """Run a host-side collective under the configured deadline."""
    deadline = collective_deadline_secs()
    if deadline <= 0:
        return fn()
    from ..distributed.supervisor import run_with_deadline

    return run_with_deadline(fn, deadline, what=what)


def barrier(name: str, timeout_s: Optional[float] = None) -> None:
    """A named cross-process barrier that cannot hang forever.

    Single-process: immediate no-op.  Fleet: ``sync_global_devices``
    under ``timeout_s`` (default: the :data:`TIMEOUT_ENV` deadline;
    unbounded when neither is set).  Raises
    :class:`~glt_tpu.distributed.supervisor.BarrierTimeoutError` on
    expiry — the caller checkpoints and exits (TrainLoop does both).
    """
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    def sync():
        multihost_utils.sync_global_devices(name)

    if timeout_s is None:
        _bounded(sync, what=f"barrier {name!r}")
    else:
        from ..distributed.supervisor import run_with_deadline

        run_with_deadline(sync, float(timeout_s),
                          what=f"barrier {name!r}")


def global_mesh(axis_name: str = "shard") -> Mesh:
    """One-axis mesh over every device of every process.

    ``jax.devices()`` orders devices so each process's block is
    contiguous, so shard ``s`` of any array sharded on ``axis_name`` is
    addressable exactly by the process owning device ``s``.
    """
    return Mesh(np.array(jax.devices()), (axis_name,))


def global_mesh_2d(host_axis: str = "host", chip_axis: str = "chip",
                   num_hosts: Optional[int] = None) -> Mesh:
    """Two-axis ``(host, chip)`` mesh: devices reshaped
    ``[n_hosts, chips_per_host]`` in flat device order.

    The hierarchical router (:class:`~glt_tpu.parallel.dist_sampler.
    HierarchicalRouting`) reads the fabric off the axis names: the
    ``chip_axis`` rows ride ICI, the ``host_axis`` columns ride DCN.
    Because the grid is a row-major reshape of ``jax.devices()``, shard
    ``s`` of a dim-0-sharded array lands on grid cell
    ``(s // chips_per_host, s % chips_per_host)`` — flat-path code
    addressing the combined ``(host_axis, chip_axis)`` axis sees exactly
    the 1-D :func:`global_mesh` device order.

    Args:
      num_hosts: mesh rows; defaults to ``jax.process_count()`` (one row
        per process — the physical layout).  Override to emulate a pod
        shape, e.g. a single 8-device process testing a 2x4 mesh.

    Raises:
      ValueError: device count not divisible by ``num_hosts``, or a
        process's devices straddle a host-row boundary without covering
        whole rows (per-axis contiguity — required so per-host feeding
        keeps addressing contiguous flat shard ranges).
    """
    devs = np.array(jax.devices())
    n = devs.size
    h = jax.process_count() if num_hosts is None else int(num_hosts)
    if h <= 0 or n % h:
        raise ValueError(
            f"cannot reshape {n} devices onto {h} mesh rows "
            f"({host_axis!r} axis): not divisible")
    c = n // h
    grid = devs.reshape(h, c)
    # Per-axis contiguity: every host row must be a union of whole
    # process blocks, or every process block a union of whole rows —
    # otherwise some process would own a non-contiguous slice of a row
    # and the arithmetic partition book breaks down.
    for r in range(h):
        procs = {d.process_index for d in grid[r]}
        if len(procs) > 1:
            for p in procs:
                owned = [i for i, d in enumerate(devs)
                         if d.process_index == p]
                row_slice = set(range(r * c, (r + 1) * c))
                if not row_slice.issuperset(owned) and \
                        not row_slice.issubset(owned):
                    raise ValueError(
                        f"process {p} devices straddle mesh row {r} of "
                        f"axes ({host_axis!r}, {chip_axis!r}): it owns "
                        f"flat device slots {owned}, row {r} spans "
                        f"{sorted(row_slice)}; pick num_hosts so host "
                        f"rows align with process boundaries")
    return Mesh(grid, (host_axis, chip_axis))


def mesh_axes(mesh: Mesh):
    """The dim-0 sharding spec for ``mesh``: its axis name (1-D) or the
    full axis-name tuple (N-D, sharding dim 0 over all axes row-major).

    This is what makes every helper below 2-D-aware: a
    ``(host, chip)`` mesh shards dim 0 over both axes in flat device
    order, so per-host feeding and shard arithmetic are unchanged.
    """
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else names


def _dim0_spec(mesh: Mesh, axis_name):
    """Resolve a (possibly stale 1-D) ``axis_name`` against ``mesh``."""
    names = tuple(mesh.axis_names)
    if isinstance(axis_name, str) and len(names) == 1 \
            and axis_name in names:
        return axis_name
    if isinstance(axis_name, tuple) and tuple(axis_name) == names:
        return axis_name
    return mesh_axes(mesh)


def local_shard_range(mesh: Mesh, axis_name: str = "shard") -> range:
    """Global shard indices whose device lives in this process.

    The per-host feeding helpers build host data only for this range (the
    reference's "each machine loads its own partition",
    dist_dataset.py:77-164).  Raises if the local block is not contiguous
    — the contiguous-ownership invariant the arithmetic partition book
    depends on.
    """
    devs = mesh.devices.reshape(-1)
    mine = [i for i, d in enumerate(devs)
            if d.process_index == jax.process_index()]
    if not mine:
        return range(0)
    lo, hi = min(mine), max(mine) + 1
    if mine != list(range(lo, hi)):
        axes = tuple(mesh.axis_names)
        offending = [getattr(devs[i], "id", i) for i in mine]
        raise ValueError(
            f"local devices are not contiguous on mesh axes {axes!r} "
            f"(shape {tuple(mesh.devices.shape)}): process "
            f"{jax.process_index()} owns flat shard slots {mine} "
            f"(device ids {offending}), expected one contiguous run — "
            f"rebuild the mesh with global_mesh/global_mesh_2d so each "
            f"process's devices form a contiguous block in flat "
            f"(row-major) device order")
    return range(lo, hi)


def assemble_global(local_block: np.ndarray, mesh: Mesh,
                    axis_name: str = "shard") -> jax.Array:
    """Per-process ``[S_local, ...]`` block -> global ``[S, ...]`` array.

    Every process calls this with its own shards' slab; the result is one
    logical array sharded over ``axis_name`` whose device-local data never
    crossed hosts.  On a multi-axis mesh, dim 0 is sharded over *all*
    axes in row-major order (see :func:`mesh_axes`), so the flat shard
    numbering is identical to the 1-D case.
    """
    sharding = NamedSharding(mesh, P(_dim0_spec(mesh, axis_name)))
    num_shards = mesh.devices.size
    global_shape = (num_shards,) + tuple(local_block.shape[1:])
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(local_block), global_shape)


def agree_max(value: int) -> int:
    """Max of a host-side int across processes (single-process: identity).

    Used to agree on padding widths (e.g. the per-shard edge-block width)
    when each host computed its own from local partitions only.
    """
    if jax.process_count() == 1:
        return int(value)
    from jax.experimental import multihost_utils

    all_vals = _bounded(
        lambda: multihost_utils.process_allgather(
            np.asarray([value], np.int64)),
        what="agree_max allgather")
    return int(np.max(all_vals))


def agree_sum(arr: np.ndarray) -> np.ndarray:
    """Elementwise sum of a host array across processes.

    Used for global statistics assembled from per-partition data (e.g.
    in-degree hotness when each host holds only its partitions' edges).
    O(N * num_processes) gather — pass precomputed global stats instead
    when N is huge.
    """
    arr = np.asarray(arr)
    if jax.process_count() == 1:
        return arr
    from jax.experimental import multihost_utils

    return np.sum(_bounded(
        lambda: multihost_utils.process_allgather(arr),
        what="agree_sum allgather"), axis=0)


# -- per-host sharded construction ----------------------------------------

def shard_graph_global(topo: CSRTopo, mesh: Mesh,
                       axis_name: str = "shard") -> ShardedGraph:
    """Full-topology convenience: every host holds ``topo`` but builds and
    feeds only its own shards' CSR blocks.

    For hosts that hold only their partitions' edges, build local blocks
    with :func:`~glt_tpu.parallel.sharding.shard_graph_blocks` +
    :func:`agree_max` and assemble with :func:`assemble_global` (that is
    what :meth:`DistDataset.load <glt_tpu.distributed.dist_dataset.
    DistDataset.load>` does when given a mesh).
    """
    num_shards = mesh.devices.size
    rng = local_shard_range(mesh, axis_name)
    ip, ix, ei, c = shard_graph_blocks(topo, num_shards, shard_range=rng)
    return ShardedGraph(
        indptr=assemble_global(ip, mesh, axis_name),
        indices=assemble_global(ix, mesh, axis_name),
        edge_ids=assemble_global(ei, mesh, axis_name),
        nodes_per_shard=c, num_nodes=topo.num_nodes, num_shards=num_shards)


def shard_hetero_graph_global(topos, mesh: Mesh,
                              axis_name: str = "shard"):
    """Hetero analog of :func:`shard_graph_global`: every edge type's CSR
    sharded by its source type's ranges, each fed per host."""
    return {et: shard_graph_global(t, mesh, axis_name)
            for et, t in topos.items()}


def shard_feature_global(feature: np.ndarray, mesh: Mesh,
                         axis_name: str = "shard",
                         dtype=None) -> ShardedFeature:
    """``[N, d]`` rows (or this host's slice of them) -> per-host-fed
    :class:`ShardedFeature`.

    ``feature`` may be the full matrix (every host slices its own rows) —
    hosts holding only their partitions' rows should pass those through
    :func:`assemble_global` directly.
    """
    feature = np.asarray(feature)
    n, d = feature.shape
    num_shards = mesh.devices.size
    c = -(-n // num_shards)
    rng = local_shard_range(mesh, axis_name)
    rows = np.zeros((len(rng), c, d), feature.dtype if dtype is None
                    else np.dtype(dtype))
    for j, s in enumerate(rng):
        lo, hi = min(s * c, n), min((s + 1) * c, n)
        rows[j, : hi - lo] = feature[lo:hi]
    return ShardedFeature(rows=assemble_global(rows, mesh, axis_name),
                          nodes_per_shard=c, num_shards=num_shards)


def labels_global(labels: np.ndarray, mesh: Mesh, nodes_per_shard: int,
                  axis_name: str = "shard", fill: int = -1) -> jax.Array:
    """Global ``[N]`` labels -> ``[S, c]`` sharded block, fed per host."""
    labels = np.asarray(labels)
    n = labels.shape[0]
    num_shards = mesh.devices.size
    c = nodes_per_shard
    rng = local_shard_range(mesh, axis_name)
    blk = np.full((len(rng), c), fill, labels.dtype)
    for j, s in enumerate(rng):
        lo, hi = min(s * c, n), min((s + 1) * c, n)
        blk[j, : hi - lo] = labels[lo:hi]
    return assemble_global(blk, mesh, axis_name)


def feed_seeds(seeds: np.ndarray, mesh: Mesh,
               axis_name: str = "shard") -> jax.Array:
    """``[S, B]`` per-shard seed batch -> global array, fed per host.

    Every host may hold the full ``[S, B]`` matrix (the deterministic
    epoch split of :meth:`DistDataset.split_seeds` is reproducible from a
    shared seed) — each feeds only its own rows.
    """
    seeds = np.asarray(seeds)
    rng = local_shard_range(mesh, axis_name)
    return assemble_global(seeds[rng.start: rng.stop], mesh, axis_name)
