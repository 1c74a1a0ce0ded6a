"""Hierarchical ICI/DCN routing on 2-D (host, chip) meshes.

The contract under test is BIT-identity: ``route='hier'`` (per-chip
bucketing -> intra-host all_to_all -> per-host dedup -> cross-host
all_to_all of only the host-unique ids -> reverse) must produce values
byte-equal to ``route='flat'`` (one all-to-all over the combined axis)
for every exchange primitive and every train-step constructor, while the
static byte model shows the DCN leg shrinking.  Identity holds because
2-D meshes key neighbor draws per (key, id) — layout-invariant — so
serving a deduped id once equals serving every duplicate slot.
"""
import types

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from glt_tpu.data.topology import CSRTopo
from glt_tpu.models import GraphSAGE
from glt_tpu.parallel import (
    exchange_byte_model,
    exchange_gather,
    exchange_gather_hot,
    exchange_gather_xy,
    exchange_one_hop,
    hier_request_cap,
    init_dist_state,
    make_dist_train_step,
    make_scanned_dist_train_step,
    mesh_axis_sizes,
    resolve_mesh_axes,
    route_cold_requests,
    shard_feature,
    shard_graph,
)
from glt_tpu.parallel.dist_sampler import _topology_choice
from glt_tpu.parallel.dist_train import dist_step_byte_model
from glt_tpu.parallel.multihost import (
    global_mesh_2d,
    local_shard_range,
    mesh_axes,
)

N_DEV = 8


def _params_bits_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        if not (np.asarray(x) == np.asarray(y)).all():
            return False
    return True


# ---------------------------------------------------------------------------
# seam + static-model unit tests
# ---------------------------------------------------------------------------

def test_topology_choice_seam(monkeypatch):
    monkeypatch.delenv("GLT_ROUTE_FORCE", raising=False)
    ax2 = ("host", "chip")
    # 1-D meshes pin flat, even when forced.
    assert _topology_choice("auto", "shard", None) == "flat"
    assert _topology_choice("hier", "shard", (2, 4)) == "flat"
    monkeypatch.setenv("GLT_ROUTE_FORCE", "hier")
    assert _topology_choice("auto", "shard", (2, 4)) == "flat"
    # Env force beats the explicit argument on 2-D meshes.
    monkeypatch.setenv("GLT_ROUTE_FORCE", "flat")
    assert _topology_choice("hier", ax2, (2, 4)) == "flat"
    monkeypatch.setenv("GLT_ROUTE_FORCE", "hier")
    assert _topology_choice("flat", ax2, (1, 8)) == "hier"
    monkeypatch.delenv("GLT_ROUTE_FORCE")
    # Real 2-D grid defaults hier; degenerate grids default flat but can
    # be forced; bucketing tokens ('sort'/'onepass') are not topology.
    assert _topology_choice("auto", ax2, (2, 4)) == "hier"
    assert _topology_choice("sort", ax2, (2, 4)) == "hier"
    assert _topology_choice("auto", ax2, (1, 8)) == "flat"
    assert _topology_choice("auto", ax2, (8, 1)) == "flat"
    assert _topology_choice("hier", ax2, (1, 8)) == "hier"
    assert _topology_choice("flat", ax2, (2, 4)) == "flat"
    # No static mesh shape -> nothing to build the hier plan from.
    assert _topology_choice("auto", ax2, None) == "flat"


def test_hier_request_cap_bounds():
    # Lossless bound: a dest-host slab's uniques all live on ONE shard.
    assert hier_request_cap(8, 4, 8) == 8          # min(32, 8)
    assert hier_request_cap(8, 4, 1000) == 32      # min(32, 1000)
    assert hier_request_cap(8, 4, 1000, hier_load_factor=0.5) == 16
    # Explicit alpha never exceeds the lossless bound.
    assert hier_request_cap(8, 4, 4, hier_load_factor=0.5) == 4
    assert hier_request_cap(1, 1, 1, hier_load_factor=0.01) == 1


def test_exchange_byte_model_split():
    per_slot = (1 + 6) * 4
    ici_f, dcn_f = exchange_byte_model("flat", 2, 4, 8, 6)
    assert (ici_f, dcn_f) == (3 * 8 * per_slot, 1 * 4 * 8 * per_slot)
    ici_h, dcn_h = exchange_byte_model("hier", 2, 4, 8, 6, hier_cap=8)
    assert (ici_h, dcn_h) == (3 * 2 * 8 * per_slot, 1 * 8 * per_slot)
    # The point of the topology: DCN (the slow fabric) shrinks.
    assert dcn_h < dcn_f
    with pytest.raises(ValueError, match="topology"):
        exchange_byte_model("ring", 2, 4, 8, 6)


def test_dist_step_byte_model_prefers_hier_dcn():
    kw = dict(nodes_per_shard=8, num_shards=8, num_neighbors=[3, 3],
              batch_size=4, frontier_cap=None, feature_dim=8,
              axis_name=("host", "chip"), mesh_shape=(2, 4))
    flat = dist_step_byte_model(route="flat", **kw)
    hier = dist_step_byte_model(route="hier", **kw)
    auto = dist_step_byte_model(route="auto", **kw)
    assert flat["topology"] == "flat" and hier["topology"] == "hier"
    assert auto["topology"] == "hier"      # real 2-D grid defaults hier
    assert hier["dcn"] < flat["dcn"]
    # 1-D meshes attribute everything to ICI.
    one_d = dist_step_byte_model(
        nodes_per_shard=8, num_shards=8, num_neighbors=[3, 3],
        batch_size=4, frontier_cap=None, feature_dim=8,
        axis_name="shard", mesh_shape=None)
    assert one_d["topology"] == "flat" and one_d["dcn"] == 0
    assert one_d["ici"] > 0


def test_global_mesh_2d_shape_and_validation():
    mesh = global_mesh_2d(num_hosts=2)
    assert tuple(mesh.axis_names) == ("host", "chip")
    assert dict(mesh.shape) == {"host": 2, "chip": 4}
    # Row-major reshape of jax.devices(): flat order is the 1-D order.
    assert list(mesh.devices.reshape(-1)) == list(jax.devices())
    assert mesh_axes(mesh) == ("host", "chip")
    assert resolve_mesh_axes(mesh) == ("host", "chip")
    assert mesh_axis_sizes(mesh, ("host", "chip")) == (2, 4)
    one_d = Mesh(np.array(jax.devices()), ("shard",))
    assert mesh_axes(one_d) == "shard"
    assert mesh_axis_sizes(one_d, "shard") is None
    with pytest.raises(ValueError, match="not divisible"):
        global_mesh_2d(num_hosts=3)
    with pytest.raises(ValueError, match="not divisible"):
        global_mesh_2d(num_hosts=0)
    # Default rows = process_count (1 here): degenerate but valid.
    assert dict(global_mesh_2d().shape) == {"host": 1, "chip": N_DEV}


def test_local_shard_range_error_names_axes_and_devices():
    """Non-contiguous ownership must name the full mesh axis tuple and
    the offending device ids (not just 'not contiguous')."""
    me = jax.process_index()

    def dev(pi, i):
        return types.SimpleNamespace(process_index=pi, id=100 + i)

    grid = np.array([dev(me, 0), dev(me + 1, 1),
                     dev(me, 2), dev(me + 1, 3)],
                    dtype=object).reshape(2, 2)
    fake = types.SimpleNamespace(devices=grid,
                                 axis_names=("host", "chip"))
    with pytest.raises(ValueError) as ei:
        local_shard_range(fake, "host")
    msg = str(ei.value)
    assert "('host', 'chip')" in msg          # full axis tuple
    assert "(2, 2)" in msg                    # mesh shape
    assert "[0, 2]" in msg                    # flat shard slots owned
    assert "[100, 102]" in msg                # offending device ids
    assert "global_mesh_2d" in msg            # the fix


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------

def _cluster(n=64, classes=4, dim=8, seed=0):
    rng = np.random.default_rng(seed)
    labels = (np.arange(n) % classes).astype(np.int32)
    src, dst = [], []
    for c in range(classes):
        members = np.where(labels == c)[0]
        for i in members:
            for j in rng.choice(members, 3, replace=False):
                src.append(i)
                dst.append(j)
    topo = CSRTopo(np.stack([np.array(src), np.array(dst)]), num_nodes=n)
    feat = np.eye(classes, dtype=np.float32)[labels]
    feat = np.concatenate(
        [feat, rng.normal(0, .1, (n, dim - classes)).astype(np.float32)],
        1)
    return topo, feat, labels


def _mesh2d(h):
    return global_mesh_2d(num_hosts=h)


def _frontier(n, b=8, seed=3):
    """[S, b] frontier with cross-chip duplicates (hub ids 0 and 1 in
    every shard's list — the ids the per-host dedup collapses) and one
    padded slot."""
    rng = np.random.default_rng(seed)
    ids = np.stack([
        np.concatenate([[0, 1],
                        rng.integers(0, n, size=b - 2)]).astype(np.int32)
        for _ in range(N_DEV)])
    ids[0, -1] = -1
    return ids


# ---------------------------------------------------------------------------
# exchange primitives: flat vs hier, byte-equal
# ---------------------------------------------------------------------------

def _shard_call(mesh, body, *arrays):
    axis_name = resolve_mesh_axes(mesh)
    spec = P(axis_name)
    n_in = len(arrays)

    def wrapped(*blks):
        out = body(*[b[0] for b in blks])
        return jax.tree.map(lambda x: x[None], out)

    fn = jax.jit(jax.shard_map(
        wrapped, mesh=mesh, in_specs=(spec,) * n_in,
        out_specs=spec, check_vma=False))
    return jax.tree.map(np.asarray, fn(*arrays))


@pytest.mark.parametrize("num_hosts,remote_cap", [
    (2, None),   # real 2x4 grid, overflow-free buckets
    (2, 5),      # capacity-bounded buckets under both topologies
    (1, None),   # degenerate 1x8 grid, hier forced (DCN legs trivial)
])
def test_exchange_one_hop_flat_hier_bit_identity(num_hosts, remote_cap):
    topo, _, _ = _cluster()
    mesh = _mesh2d(num_hosts)
    axis_name = resolve_mesh_axes(mesh)
    ms = mesh_axis_sizes(mesh, axis_name)
    g = shard_graph(topo, N_DEV)
    seeds = jnp.asarray(_frontier(topo.num_nodes))
    key = jax.random.PRNGKey(5)

    def run(route):
        def body(ip, ix, ei, s):
            k = jax.random.fold_in(key, lax.axis_index(axis_name))
            nbrs, eids, mask, dropped, _ = exchange_one_hop(
                s, ip, ix, ei, g.nodes_per_shard, g.num_shards, 3, k,
                axis_name, remote_cap=remote_cap, route=route,
                mesh_shape=ms)
            return nbrs, eids, mask.astype(jnp.int32), dropped[None]

        return _shard_call(mesh, body, g.indptr, g.indices, g.edge_ids,
                           seeds)

    flat = run("flat")
    hier = run("hier")
    for a, b in zip(flat, hier):
        np.testing.assert_array_equal(a, b)
    # Padded seed slots stay inert: masked out under both topologies.
    assert not flat[2][0, -1].any()


@pytest.mark.parametrize("dedup", [False, True])
def test_exchange_gather_flat_hier_bit_identity(dedup):
    _, feat, _ = _cluster()
    mesh = _mesh2d(2)
    axis_name = resolve_mesh_axes(mesh)
    ms = mesh_axis_sizes(mesh, axis_name)
    f = shard_feature(feat, N_DEV)
    ids = jnp.asarray(_frontier(feat.shape[0]))

    def run(route):
        def body(i, rows):
            return exchange_gather(i, rows, f.nodes_per_shard,
                                   f.num_shards, axis_name, dedup=dedup,
                                   route=route, mesh_shape=ms)

        return _shard_call(mesh, body, ids, f.rows)

    flat = run("flat")
    hier = run("hier")
    np.testing.assert_array_equal(flat, hier)
    # Both equal the dense reference (padding -> zero rows).
    idn = np.asarray(ids)
    ref = np.where((idn >= 0)[..., None], feat[np.maximum(idn, 0)], 0.0)
    np.testing.assert_array_equal(hier, ref.astype(np.float32))


@pytest.mark.parametrize("dedup", [True, False])
def test_exchange_gather_xy_flat_hier_bit_identity(dedup):
    _, feat, labels = _cluster()
    mesh = _mesh2d(2)
    axis_name = resolve_mesh_axes(mesh)
    ms = mesh_axis_sizes(mesh, axis_name)
    f = shard_feature(feat, N_DEV)
    lab = jnp.asarray(labels.reshape(N_DEV, f.nodes_per_shard))
    ids = jnp.asarray(_frontier(feat.shape[0]))

    def run(route):
        def body(i, rows, lcol):
            x, y = exchange_gather_xy(
                i, rows, lcol, f.nodes_per_shard, f.num_shards,
                axis_name, dedup=dedup, route=route, mesh_shape=ms)
            return x, y

        return _shard_call(mesh, body, ids, f.rows, lab)

    xf, yf = run("flat")
    xh, yh = run("hier")
    np.testing.assert_array_equal(xf, xh)
    np.testing.assert_array_equal(yf, yh)
    # Label round trip is exact int32 (a payload collective of its own).
    idn = np.asarray(ids)
    ref_y = np.where(idn >= 0, labels[np.maximum(idn, 0)], 0)
    np.testing.assert_array_equal(yh, ref_y.astype(np.int32))


def test_tiered_cold_path_flat_hier_bit_identity():
    """route_cold_requests + compact host staging + exchange_gather_hot
    under both topologies: the request layout differs ([S*b] flat,
    [H*hier_cap] hier — a smaller staging vector is the point), but the
    gathered rows are byte-equal and match the dense reference."""
    _, feat, _ = _cluster()
    mesh = _mesh2d(2)
    axis_name = resolve_mesh_axes(mesh)
    ms = mesh_axis_sizes(mesh, axis_name)
    n, d = feat.shape
    c = n // N_DEV
    hot = c // 2
    hot_rows = jnp.asarray(
        feat.reshape(N_DEV, c, d)[:, :hot])          # [S, hot, d]
    cold_blocks = feat.reshape(N_DEV, c, d)[:, hot:]  # host-side store
    ids = jnp.asarray(_frontier(n))

    shapes = {}

    def run(route):
        def plan(i):
            return route_cold_requests(i, c, hot, N_DEV, axis_name,
                                       route=route, mesh_shape=ms)

        cr = _shard_call(mesh, plan, ids)             # [S, R]
        shapes[route] = cr.shape[1]
        cap = cr.shape[1]
        slots = np.full((N_DEV, cap), -1, np.int32)
        rows = np.zeros((N_DEV, cap, d), np.float32)
        for s in range(N_DEV):
            cold = np.where(cr[s] >= 0)[0]
            slots[s, :len(cold)] = cold
            rows[s, :len(cold)] = cold_blocks[s][cr[s][cold]]

        def serve(i, hr, srows, sslots):
            return exchange_gather_hot(
                i, hr, c, hot, N_DEV, axis_name, staged_rows=srows,
                staged_slots=sslots, route=route, mesh_shape=ms)

        return _shard_call(mesh, serve, ids, hot_rows,
                           jnp.asarray(rows), jnp.asarray(slots))

    flat = run("flat")
    hier = run("hier")
    np.testing.assert_array_equal(flat, hier)
    idn = np.asarray(ids)
    ref = np.where((idn >= 0)[..., None], feat[np.maximum(idn, 0)], 0.0)
    np.testing.assert_array_equal(hier, ref.astype(np.float32))
    # The hier request vector (and the host->device staging with it) is
    # strictly smaller than the flat one on this skew-free cap.
    assert shapes["hier"] < shapes["flat"]


# ---------------------------------------------------------------------------
# train steps: flat vs hier, byte-equal end to end
# ---------------------------------------------------------------------------

def _dist_setup2d(num_hosts=2, dim=8, bs=4):
    topo, feat, labels = _cluster(dim=dim)
    mesh = _mesh2d(num_hosts)
    g = shard_graph(topo, N_DEV)
    f = shard_feature(feat, N_DEV)
    lab = jnp.asarray(labels.reshape(N_DEV, g.nodes_per_shard))
    model = GraphSAGE(hidden_features=16, out_features=4, num_layers=2,
                      dropout_rate=0.0)
    tx = optax.adam(1e-2)
    rng = np.random.default_rng(1)
    seeds = np.stack([rng.choice(np.arange(s * 8, (s + 1) * 8), bs,
                                 replace=False)
                      for s in range(N_DEV)]).astype(np.int32)
    seeds[0, -1] = -1         # padded slot must stay inert on both hops
    return mesh, g, f, lab, model, tx, [3, 3], bs, seeds


def test_dist_train_step_flat_hier_bit_identity():
    mesh, g, f, lab, model, tx, fanouts, bs, seeds = _dist_setup2d()
    base = jax.random.PRNGKey(17)
    G = 2

    def run(route):
        st = init_dist_state(model, tx, g, f, jax.random.PRNGKey(0),
                             fanouts, bs)
        step = make_dist_train_step(model, tx, g, f, lab, mesh, fanouts,
                                    bs, route=route)
        losses, accs = [], []
        for i in range(G):
            st, loss, acc = step(st, jnp.asarray(seeds),
                                 jax.random.fold_in(base, i))
            losses.append(float(loss))
            accs.append(float(acc))
        return st, losses, accs, step.collective_bytes

    st_f, lf, af, bm_f = run("flat")
    st_h, lh, ah, bm_h = run("hier")
    assert lf == lh and af == ah
    assert _params_bits_equal(st_f.params, st_h.params)
    # The per-step byte model rides the step fn and shows the DCN win.
    assert bm_f["topology"] == "flat" and bm_h["topology"] == "hier"
    assert bm_h["dcn"] < bm_f["dcn"]


@pytest.mark.slow
def test_scanned_dist_step_flat_hier_bit_identity():
    """Scanned (lax.scan over dist_seed_blocks) half of the guarantee;
    slow: compiles two scanned dist programs."""
    mesh, g, f, lab, model, tx, fanouts, bs, seeds = _dist_setup2d()
    G = 2
    blk = np.stack([seeds] * G)
    blk[1, :, 0] += 1          # distinct second block
    base = jax.random.PRNGKey(29)

    outs = {}
    for route in ("flat", "hier"):
        st = init_dist_state(model, tx, g, f, jax.random.PRNGKey(0),
                             fanouts, bs)
        sstep = make_scanned_dist_train_step(model, tx, g, f, lab, mesh,
                                             fanouts, bs, route=route)
        st, losses, accs = sstep(st, blk, base)
        outs[route] = (st, [float(x) for x in losses],
                       [float(a) for a in accs])
        assert sstep.collective_bytes["topology"] == route

    assert outs["flat"][1] == outs["hier"][1]
    assert outs["flat"][2] == outs["hier"][2]
    assert _params_bits_equal(outs["flat"][0].params,
                              outs["hier"][0].params)


@pytest.mark.slow
def test_dist_fused_frontier_flat_hier_bit_identity():
    """PR 15's fused frontier (serving-side Pallas seam) must run inside
    the two-axis shard_map unchanged: flat vs hier byte-equal with
    fused_frontier='interpret'."""
    mesh, g, f, lab, model, tx, fanouts, bs, seeds = _dist_setup2d()
    key = jax.random.PRNGKey(7)

    outs = {}
    for route in ("flat", "hier"):
        step = make_dist_train_step(model, tx, g, f, lab, mesh, fanouts,
                                    bs, fused_frontier="interpret",
                                    route=route)
        st, loss, acc = step(
            init_dist_state(model, tx, g, f, jax.random.PRNGKey(0),
                            fanouts, bs),
            jnp.asarray(seeds), key)
        outs[route] = (float(loss), float(acc), st.params)

    assert outs["flat"][0] == outs["hier"][0]
    assert outs["flat"][1] == outs["hier"][1]
    assert _params_bits_equal(outs["flat"][2], outs["hier"][2])


@pytest.mark.slow
def test_hetero_dist_train_flat_hier_bit_identity():
    """Hetero path: per-edge-type hops ride the hierarchical topology;
    losses and final params byte-equal to flat."""
    from glt_tpu.models.rgat import RGAT
    from glt_tpu.parallel import (
        DistHeteroNeighborSampler,
        init_hetero_dist_state,
        make_hetero_dist_train_step,
        shard_hetero_graph,
    )

    mesh = _mesh2d(2)
    U, I, classes = 64, 32, 4
    rng = np.random.default_rng(0)
    labels = (np.arange(U) % classes).astype(np.int32)
    u_src = np.repeat(np.arange(U), 3)
    i_dst = np.concatenate([
        [(u % classes) + classes * ((u // classes + k) % (I // classes))
         for k in range(3)] for u in range(U)])
    ET_UI = ("user", "clicks", "item")
    ET_IU = ("item", "rev_clicks", "user")
    topos = {ET_UI: CSRTopo(np.stack([u_src, i_dst]), num_nodes=U),
             ET_IU: CSRTopo(np.stack([i_dst, u_src]), num_nodes=I)}
    sharded = shard_hetero_graph(topos, N_DEV)
    feats = {
        "user": shard_feature(
            rng.normal(0, .1, (U, classes)).astype(np.float32), N_DEV),
        "item": shard_feature(
            np.eye(classes, dtype=np.float32)[np.arange(I) % classes],
            N_DEV),
    }
    lab = jnp.asarray(labels.reshape(N_DEV, -1))
    bs = 4
    model = RGAT(edge_types=[ET_IU, ET_UI], hidden_features=16,
                 out_features=classes, target_type="user", num_layers=2,
                 conv="gat", dropout_rate=0.0)
    tx = optax.adam(1e-2)
    seeds = np.stack([
        np.random.default_rng(s).choice(np.arange(s * 8, (s + 1) * 8),
                                        bs, replace=False)
        for s in range(N_DEV)]).astype(np.int32)

    def run(route, G=2):
        samp = DistHeteroNeighborSampler(sharded, mesh, [3, 3], "user",
                                         batch_size=bs, frontier_cap=32,
                                         seed=0, route=route)
        st = init_hetero_dist_state(model, tx, samp, feats,
                                    jax.random.PRNGKey(0))
        step = make_hetero_dist_train_step(model, tx, samp, feats, lab,
                                           mesh, batch_size=bs,
                                           route=route)
        losses = []
        for it in range(G):
            st, loss, _ = step(st, jnp.asarray(seeds),
                               jax.random.PRNGKey(100 + it))
            losses.append(float(loss))
        return st, losses

    st_f, lf = run("flat")
    st_h, lh = run("hier")
    assert lf == lh
    assert _params_bits_equal(st_f.params, st_h.params)
