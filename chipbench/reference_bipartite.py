"""The plain reference of ``bipartite-sage-taobao``: the same semantics,
none of the program's code.

* ``RefBipartite``: the generator's relations recomputed from positions
  (``reference_hetero.RefHetero``, with the user->item draw by weight of
  ``data_bipartite``), and so every adjacency list, degree and edge.
* ``check_link_batch``: a typed seed-edge batch against what was asked
  for (the sampling guarantees of the typed and the link cells).
* The model, its loss and gradients and dense Adam: the benchmark's own
  copy of ``glt_tpu/testing/bipartite_reference.py``, upstream's
  ``examples/hetero/bipartite_sage_unsup.py`` in straightforward float32
  ``jax.numpy`` under ``default_matmul_precision("highest")``.  Its
  departures from upstream are of layout only: PyG's ``SAGEConv`` puts
  the bias on the neighbour side, this model's is on the root side (the
  same sum); the program stores a table's 64-wide rows two to a 128-lane
  row (``table_rows`` is the ``[N, 64]`` view); tables are read by node
  id, zero on padding.

``check_adam`` compares one scanned call of the step (``G`` batches) with
``G`` reference steps of dense Adam on the rows the batches read and on
rows they did not.
"""
from __future__ import annotations

from collections import Counter

import numpy as np

from chipbench import data_bipartite
from chipbench import reference_hetero
from chipbench.checks import check

UI, IU, II = data_bipartite.UI, data_bipartite.IU, data_bipartite.II


class RefBipartite(reference_hetero.RefHetero):
    """Recomputes what ``data_bipartite`` made, from positions and the
    items' weights."""

    def __init__(self, rels, seed: int, made: dict, cum: np.ndarray):
        ui, ii = rels
        self.counts = {"user": ui.num_src, "item": ui.num_dst}
        self.seed = seed
        self.made = dict(made)
        self.rels = {UI: (ui, True), IU: (ui, False), II: (ii, True)}
        self.cum = np.asarray(cum)
        self.key = data_bipartite.pick_key(seed)

    def forward_edges(self, rel, pos) -> tuple:
        if rel.etype != UI:
            return super().forward_edges(rel, pos)
        pos = np.asarray(pos, np.int64)
        src = np.searchsorted(self.made[UI].indptr, pos, side="right") - 1
        dst = data_bipartite.picked_items(pos.astype(np.uint32), self.cum,
                                          self.key, np)
        return src, dst.astype(np.int64)

    def is_edge(self, users, items) -> np.ndarray:
        return np.array([bool((self.neighbours(UI, int(u)) == int(i)).any())
                         for u, i in zip(users, items)], bool)


def _first_occurrence(ids):
    _, first = np.unique(ids, return_index=True)
    return ids[np.sort(first)]


def check_link_batch(ref, batch, src, dst, batch_size: int, fanouts,
                     what: str, rng, sources: int = 64) -> dict:
    """A typed seed-edge batch of ``batch_size`` user->item seed edges
    ``src -> dst`` (-1 padded) and as many binary negatives.  ``batch``:
    ``node``, ``node_mask``, ``x`` by type; ``row``, ``col``,
    ``edge_mask`` by batch key (reversed: the sampled relation is
    ``_reverse(key)``, ``col`` its source slot); ``edge_label_index``,
    ``edge_label``, ``neg_strict``.  Returns counts of what was seen."""
    node = {t: np.asarray(v) for t, v in batch["node"].items()}
    mask = {t: np.asarray(v) for t, v in batch["node_mask"].items()}
    for t in node:
        check(bool(((node[t] >= 0) == mask[t]).all()),
              f"{what}: {t} ids are not -1 exactly off the node mask")
        live = node[t][mask[t]]
        check(np.unique(live).size == live.size
              and (live.size == 0 or live.max() < ref.counts[t]),
              f"{what}: the {t} list repeats an id or leaves its type")
        check(bool((np.asarray(batch["x"][t]) == node[t]).all()),
              f"{what}: x[{t}] is not the node ids")
    q = batch_size
    src, dst = np.asarray(src), np.asarray(dst)
    real = src >= 0
    eli = np.asarray(batch["edge_label_index"])
    label = np.asarray(batch["edge_label"])
    check(eli.shape == (2, 2 * q) and label.shape == (2 * q,),
          f"{what}: pair index {eli.shape}, labels {label.shape}")
    check(bool((label[:q] == np.where(real, 1, -1)).all()
               and (label[q:] == 0).all()),
          f"{what}: labels are not 1 on the seed edges, -1 on their "
          f"padding and 0 on the negatives")
    check(bool((eli[:, :q][:, ~real] == -1).all()
               and (eli[:, :q][:, real] >= 0).all()
               and (eli[:, q:] >= 0).all()
               and eli.max() < 2 * q),
          f"{what}: a pair points outside the seed rows")
    check(bool((node["user"][eli[0, :q][real]] == src[real]).all()
               and (node["item"][eli[1, :q][real]] == dst[real]).all()),
          f"{what}: the positive pairs are not the given seed edges in "
          f"order")
    neg_u, neg_i = node["user"][eli[0, q:]], node["item"][eli[1, q:]]
    strict = np.asarray(batch["neg_strict"])
    check(strict.shape == (q,), f"{what}: strict flags {strict.shape}")
    hit = ref.is_edge(neg_u[strict], neg_i[strict])
    check(not hit.any(),
          f"{what}: {int(hit.sum())} negative slots flagged strict are "
          f"edges, e.g. {neg_u[strict][hit][:1]} -> {neg_i[strict][hit][:1]}")
    check(bool(ref.is_edge(src[real][:64], dst[real][:64]).all()),
          f"{what}: a given seed edge is not in the graph")
    lead = {"user": _first_occurrence(np.concatenate([src[real], neg_u])),
            "item": _first_occurrence(np.concatenate([dst[real], neg_i]))}
    for t, ids in lead.items():
        check(bool((node[t][: ids.size] == ids).all()),
              f"{what}: the {t} seeds [positive, negative] do not lead the "
              f"{t} list in first-occurrence order")

    hops = len(fanouts)
    edges = {}
    for key in batch["row"]:
        em = np.asarray(batch["edge_mask"][key])
        row = np.asarray(batch["row"][key])[em]
        col = np.asarray(batch["col"][key])[em]
        et = reference_hetero._reverse(key)     # the sampled relation
        check(bool((row >= 0).all() and (col >= 0).all()
                   and (row < node[et[2]].shape[0]).all()
                   and (col < node[et[0]].shape[0]).all()
                   and mask[et[2]][row].all() and mask[et[0]][col].all()),
              f"{what}: a live {et} edge points at a padding slot")
        edges[et] = (row, col)
    # Depth of every slot: typed breadth-first search from both seeds.
    depth = {t: np.full(node[t].shape, hops + 1, np.int64) for t in node}
    for t, ids in lead.items():
        depth[t][: ids.size] = 0
    for h in range(hops):
        reached = {t: np.zeros(node[t].shape, bool) for t in node}
        for (s_t, _, d_t), (row, col) in edges.items():
            reached[d_t][row[depth[s_t][col] == h]] = True
        for t in node:
            depth[t][reached[t] & (depth[t] > h + 1)] = h + 1
    sampled = 0
    for et, (row, col) in edges.items():
        s_t, _, d_t = et
        check(bool((depth[d_t][row] <= hops).all()
                   and (depth[s_t][col] < hops).all()),
              f"{what}: a {et} edge lies beyond {hops} hops of the seeds")
        inner = np.flatnonzero(depth[s_t] < hops)
        out_count = np.bincount(col, minlength=node[s_t].shape[0])
        want = np.minimum(ref.degree(et, node[s_t][inner]),
                          np.asarray(fanouts)[depth[s_t][inner]])
        bad = inner[out_count[inner] != want]
        check(bad.size == 0,
              f"{what}: {bad.size} {s_t} nodes first seen before the last "
              f"hop do not have min(degree, fanout) sampled {et} edges, "
              f"e.g. node {node[s_t][bad[:1]]} at hop "
              f"{depth[s_t][bad[:1]]} has {out_count[bad[:1]]}")
        order = np.argsort(col, kind="stable")
        starts = np.searchsorted(col[order],
                                 np.arange(node[s_t].shape[0] + 1))
        has = inner[out_count[inner] > 0]
        for slot in rng.choice(has, size=min(sources, has.size),
                               replace=False).tolist():
            got = Counter(node[d_t][row[order[
                starts[slot]:starts[slot + 1]]]].tolist())
            have = Counter(ref.neighbours(et, int(node[s_t][slot])).tolist())
            check(not (got - have),
                  f"{what}: {s_t} node {int(node[s_t][slot])} has sampled "
                  f"{et} neighbours {dict(got - have)} that its adjacency "
                  f"list does not hold")
        sampled += row.size
    return {"neg_strict": int(strict.sum()),
            "neg_padded": int((~strict).sum()),
            "seed_users": int(lead["user"].size),
            "seed_items": int(lead["item"].size), "sampled_edges": sampled}


# -- the model ----------------------------------------------------------------

def table_rows(p, node_type):
    """The ``[N, width]`` table of a type out of ``params['params']`` (or
    a moment's tree of the same shape): the model stores 64-wide rows two
    to a 128-lane row, in row-major order."""
    width = p["item_conv1"]["lin_nbr"]["kernel"].shape[0]
    return p[f"{node_type}_emb"]["table"].reshape(-1, width)


def lookup(table, ids):
    """Rows of ``table`` at ``ids``, zero where the id is -1."""
    import jax.numpy as jnp

    valid = ids >= 0
    return jnp.where(valid[:, None], table[jnp.where(valid, ids, 0)], 0.0)


def sage(c, x_src, x_dst, edge_index, mask):
    """``x_dst W_self + b + mean_{j -> i} x_src[j] W_nbr`` over the live
    edges ``edge_index[0] -> edge_index[1]``."""
    import jax
    import jax.numpy as jnp

    n = x_dst.shape[0]
    seg = jnp.where(mask, edge_index[1], n)
    msgs = jnp.where(mask[:, None],
                     x_src[jnp.where(mask, edge_index[0], 0)], 0.0)
    total = jax.ops.segment_sum(msgs, seg, num_segments=n + 1)[:n]
    cnt = jax.ops.segment_sum(mask.astype(jnp.float32), seg,
                              num_segments=n + 1)[:n]
    mean = total / jnp.maximum(cnt, 1.0)[:, None]
    return (x_dst @ c["lin_self"]["kernel"] + c["lin_self"]["bias"]
            + mean @ c["lin_nbr"]["kernel"])


def logits_of_rows(p, x_user, x_item, batch):
    """The ``[Q]`` pair logits of a batch whose rows are given (``p`` the
    tree under ``params['params']``; ``batch``: ``edge_index`` and
    ``edge_mask`` by batch key, ``pairs``)."""
    import jax
    import jax.numpy as jnp

    def dense(c, x):
        return x @ c["kernel"] + c["bias"]

    with jax.default_matmul_precision("highest"):
        ii = (batch["edge_index"][II], batch["edge_mask"][II])
        iu = (batch["edge_index"][IU], batch["edge_mask"][IU])
        relu = jax.nn.relu
        h = relu(sage(p["item_conv1"], x_item, x_item, *ii))
        h = relu(sage(p["item_conv2"], h, h, *ii))
        z_i = dense(p["item_lin"], h)
        ix = relu(sage(p["user_conv1"], x_item, x_item, *ii))
        u = relu(sage(p["user_conv2"], x_item, x_user, *iu))
        u = relu(sage(p["user_conv3"], ix, u, *iu))
        z_u = dense(p["user_lin"], u)
        row, col = batch["pairs"]
        z = jnp.concatenate([z_u[jnp.maximum(row, 0)],
                             z_i[jnp.maximum(col, 0)]], axis=-1)
        z = relu(dense(p["dec_lin1"], z))
        return dense(p["dec_lin2"], z)[:, 0]


def loss_of_rows(p, x_user, x_item, batch):
    """Mean ``binary_cross_entropy_with_logits`` over the pairs that are
    not padding."""
    import jax.numpy as jnp

    logits = logits_of_rows(p, x_user, x_item, batch)
    row, col = batch["pairs"]
    label = batch["label"]
    valid = (row >= 0) & (col >= 0) & (label >= 0)
    y = (label > 0).astype(jnp.float32)
    ce = (jnp.maximum(logits, 0.0) - logits * y
          + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return jnp.where(valid, ce, 0.0).sum() / jnp.maximum(valid.sum(), 1)


def adam(param, m, v, count, grad, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    """One dense Adam step of one array; ``count`` the steps before it."""
    import jax.numpy as jnp

    t = jnp.asarray(count, jnp.int32) + 1
    m = (1.0 - b1) * grad + b1 * m
    v = (1.0 - b2) * grad * grad + b2 * v
    m_hat = m / (1.0 - b1 ** t)
    v_hat = v / (1.0 - b2 ** t)
    return param + (-lr) * (m_hat / (jnp.sqrt(v_hat) + eps)), m, v


# -- (c): one scanned call against dense Adam ---------------------------------

TABLES = ("user", "item")


def _rows_of(state, rows):
    """``{type: (p, m, v)}`` of the tables at ``rows[type]`` (device)."""
    import jax.numpy as jnp

    adam_state = state.opt_state[0]
    out = {}
    for t in TABLES:
        at = jnp.asarray(rows[t])
        out[t] = tuple(table_rows(tree["params"], t)[at]
                       for tree in (state.params, adam_state.mu,
                                    adam_state.nu))
    return out


def _towers(tree):
    """The parameter tree without the tables."""
    return {k: v for k, v in tree["params"].items()
            if not k.endswith("_emb")}


def before_call(state, batches, rng, untouched: int = 4096):
    """What the comparison needs of the state a call starts from, taken
    before the call consumes it: the rows the call's ``batches`` read
    (``touched``, sorted ids by type), ``untouched`` other rows whose
    first moment is not zero, their ``(p, m, v)``, the towers' and the
    count."""
    import jax

    touched, others = {}, {}
    mu = state.opt_state[0].mu["params"]
    for t in TABLES:
        ids = np.concatenate([np.asarray(b["ids"][t]) for b in batches])
        touched[t] = np.unique(ids[ids >= 0])
        table = table_rows(mu, t)
        n = table.shape[0]
        cand = rng.choice(n, size=min(n, 64 * untouched), replace=False)
        cand = np.sort(cand[~np.isin(cand, touched[t])])
        live = np.asarray(jax.device_get(
            (table[cand] != 0).any(axis=1)))
        others[t] = cand[live][:untouched]
    rows = {t: np.concatenate([touched[t], others[t]]) for t in TABLES}
    host = jax.device_get
    return {"touched": touched, "others": others, "rows": rows,
            "tables": _rows_of(state, rows),
            "towers": tuple(host(_towers(tree)) for tree in (
                state.params, state.opt_state[0].mu,
                state.opt_state[0].nu)),
            "count": int(state.opt_state[0].count)}


def reference_call(start, batches, lr=1e-3):
    """``G`` serial reference steps from ``start`` (:func:`before_call`):
    each batch's gradient by its rows and the towers, then dense Adam on
    every kept row (a row the batch did not read has a zero gradient)
    and on the towers.  Returns ``{type: (p, m, v)}`` and the towers'
    ``(p, m, v)``."""
    import jax
    import jax.numpy as jnp

    tabs = {t: tuple(start["tables"][t]) for t in TABLES}
    towers = tuple(jax.tree_util.tree_map(jnp.asarray, t)
                   for t in start["towers"])
    grad_fn = jax.jit(jax.value_and_grad(loss_of_rows, argnums=(0, 1, 2)))
    step_fn = jax.jit(lambda p, m, v, c, g: jax.tree_util.tree_map(
        lambda a, b, d, e: adam(a, b, d, c, e, lr), p, m, v, g))
    losses = []
    for g, b in enumerate(batches):
        local = {}
        for t in TABLES:
            ids = np.asarray(b["ids"][t])
            at = np.searchsorted(start["touched"][t], np.maximum(ids, 0))
            local[t] = jnp.asarray(np.where(ids >= 0, at, -1), jnp.int32)
        x = {t: lookup(tabs[t][0], local[t]) for t in TABLES}
        value, (g_tw, g_u, g_i) = grad_fn(towers[0], x["user"], x["item"],
                                          b)
        losses.append(float(value))
        grads = {"user": g_u, "item": g_i}
        count = start["count"] + g
        for t in TABLES:
            dense = jnp.zeros_like(tabs[t][0]).at[
                jnp.maximum(local[t], 0)].add(
                jnp.where((local[t] >= 0)[:, None], grads[t], 0.0))
            tabs[t] = step_fn(*tabs[t], count, dense)
        new = step_fn(*towers, count, g_tw)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda x: x[i], new, is_leaf=lambda x: isinstance(x, tuple))
        towers = (pick(0), pick(1), pick(2))
    return tabs, towers, losses


def check_adam(start, after_state, batches, touched_rtol: float,
               untouched_ulp: float, what: str, lr=1e-3) -> dict:
    """The state one call left against :func:`reference_call`.  Rows the
    call read, and the towers: the largest of ``|system - reference| /
    |what the call's gradients wrote|`` (root-mean-square over the rows,
    per array) is within ``touched_rtol``.  Rows it did not read: within
    ``untouched_ulp`` units in the last place of the reference (their
    update depends on the old state only).  A lazy Adam, which leaves
    unread rows as they were, fails the second."""
    import jax

    want_tabs, want_towers, losses = reference_call(start, batches, lr)
    got_tabs = jax.device_get(_rows_of(after_state, start["rows"]))
    got_towers = tuple(jax.device_get(_towers(tree)) for tree in (
        after_state.params, after_state.opt_state[0].mu,
        after_state.opt_state[0].nu))
    names = ("params", "mu", "nu")
    # What the call's own gradients wrote: the step of a parameter, and
    # of a moment what is left over its start decayed ``G`` times (a
    # moment's net change can be near zero where the gradient is alike
    # from step to step, and would magnify any difference).
    g = len(batches)
    decay = dict(params=1.0, mu=0.9 ** g, nu=0.999 ** g)
    touched_err, ulps = {}, {}
    for t in TABLES:
        n_touched = start["touched"][t].size
        old = jax.device_get(start["tables"][t])
        for name, got, want, was in zip(names, got_tabs[t],
                                        jax.device_get(want_tabs[t]), old):
            got, want, was = (np.asarray(a, np.float64)
                              for a in (got, want, was))
            d = np.sqrt(((got[:n_touched] - want[:n_touched]) ** 2).mean())
            moved = np.sqrt(((want[:n_touched]
                              - decay[name] * was[:n_touched]) ** 2).mean())
            touched_err[f"{t}.{name}"] = float(d / max(moved, 1e-30))
            rest = slice(n_touched, None)
            spacing = np.spacing(np.abs(want[rest]).astype(np.float32))
            ulps[f"{t}.{name}"] = float(
                (np.abs(got[rest] - want[rest]) / spacing).max(initial=0.0))
            check(bool((np.abs(want[rest] - was[rest]) > 0).any()),
                  f"{what}: the reference moved no unread {t} row")
    for name, got, want, was in zip(names, got_towers,
                                    jax.device_get(want_towers),
                                    start["towers"]):
        got, w, o = (np.concatenate([np.asarray(a, np.float64).ravel()
                                   for a in jax.tree_util.tree_leaves(x)])
                   for x in (got, want, was))
        touched_err[f"towers.{name}"] = float(
            np.sqrt(((got - w) ** 2).mean())
            / max(np.sqrt(((w - decay[name] * o) ** 2).mean()), 1e-30))
    worst = max(touched_err, key=touched_err.get)
    check(touched_err[worst] <= touched_rtol,
          f"{what}: {worst} of the rows the call read differs from dense "
          f"Adam on the reference's gradient by {touched_err[worst]:.3g} "
          f"of its step, over {touched_rtol}")
    far = max(ulps, key=ulps.get)
    check(ulps[far] <= untouched_ulp,
          f"{what}: {far} of the rows the call did not read differs from "
          f"dense Adam by {ulps[far]:.3g} ulp, over {untouched_ulp} (a lazy "
          f"Adam leaves them unmoved)")
    return {"adam_touched_err": touched_err[worst], "adam_worst": worst,
            "adam_errs": {k: round(v, 5) for k, v in touched_err.items()},
            "adam_untouched_ulp": ulps[far],
            "adam_rows": {t: [int(start["touched"][t].size),
                              int(start["others"][t].size)]
                          for t in TABLES},
            "reference_losses": losses}
