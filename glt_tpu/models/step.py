"""The train step's body, written once.

Every ``make_*step`` factory (:mod:`~glt_tpu.models.train`,
:mod:`~glt_tpu.parallel.dist_train`) runs the same stages — sample,
gather ``(x, y)``, forward + loss + gradients, (mean over the mesh),
optimiser update — and owns only the first two and its wrapper (none, a
``lax.scan``, a ``shard_map``, both, or a staged input).  The other
stages live here, so that what a padded batch does to the optimiser,
which rows the loss reads and when a model runs trimmed is decided in one
place (docs/architecture.md "The train step").
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from ..obs import metrics as _metrics
from ..obs.scopes import scoped


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


@scoped("glt.step.loss")
def seed_cross_entropy(logits, y, batch_size: int, node_mask,
                       num_seeds=None):
    """Mean CE over valid seed rows (first ``batch_size`` slots).

    ``num_seeds`` (a traced count, ``SamplerOutput.num_sampled_nodes[0]``)
    keeps the loss to the rows that hold a seed: a partly padded seed
    batch has fewer unique seeds than ``batch_size``, and the rows behind
    them hold labelled hop-1 nodes.
    """
    sl = logits[:batch_size]
    sy = y[:batch_size]
    valid = (sy >= 0) & node_mask[:batch_size]
    if num_seeds is not None:
        valid &= jnp.arange(batch_size, dtype=jnp.int32) < num_seeds
    sy_safe = jnp.where(valid, sy, 0)
    ce = optax.softmax_cross_entropy_with_integer_labels(sl, sy_safe)
    n = jnp.maximum(valid.sum(), 1)
    loss = jnp.where(valid, ce, 0).sum() / n
    acc = jnp.where(valid, jnp.argmax(sl, -1) == sy_safe, False).sum() / n
    return loss, acc


def seed_loss(batch_size: int):
    """The supervised steps' loss: ``(logits, y, aux) -> (loss, acc)``
    over the rows of real seeds, ``aux = (node_mask, hop_counts)`` as
    :func:`graph_inputs` returns it (``hop_counts[0]`` unique seeds)."""
    def loss(logits, y, aux):
        node_mask, hop_counts = aux
        return seed_cross_entropy(logits, y, batch_size, node_mask,
                                  hop_counts[0])
    return loss


@scoped("glt.step.loss")
def pair_bce_loss(z, meta):
    """Upstream's unsupervised objective (examples/graph_sage_unsup_ppi.py,
    dist_sage_unsup.py): ``binary_cross_entropy_with_logits((z_src *
    z_dst).sum(-1), edge_label)`` over the pairs of
    ``meta['edge_label_index']`` (rows of ``z``) whose ``edge_label`` is
    not padding.  Returns ``(loss, acc)``, ``acc`` the share of pairs
    whose logit has the label's sign."""
    last = z.shape[0] - 1
    return _pair_bce(meta, lambda eli: (z[jnp.clip(eli[0], 0, last)]
                                        * z[jnp.clip(eli[1], 0, last)]
                                        ).sum(-1))


def _pair_bce(meta, logits_of):
    """Masked ``binary_cross_entropy_with_logits(logits, edge_label)``
    and the share of signs right, over the pairs that are not padding;
    ``logits_of(edge_label_index)`` scores the pairs."""
    eli, label = meta["edge_label_index"], meta["edge_label"]
    valid = (eli[0] >= 0) & (eli[1] >= 0) & (label >= 0)
    logits = logits_of(eli)
    ce = optax.sigmoid_binary_cross_entropy(
        logits, (label > 0).astype(logits.dtype))
    n = jnp.maximum(valid.sum(), 1)
    loss = jnp.where(valid, ce, 0).sum() / n
    acc = jnp.where(valid, (logits > 0) == (label > 0), False).sum() / n
    return loss, acc


@scoped("glt.step.loss")
def logit_bce_loss(logits, meta):
    """:func:`pair_bce_loss` for a model that scores its pairs itself (a
    decoder over ``meta['edge_label_index']``): ``logits`` is ``[Q]``."""
    return _pair_bce(meta, lambda eli: logits)


def graph_inputs(out):
    """``(edge_index, edge_mask, aux)`` of a sampler output, homogeneous
    or typed (dicts by relation; ``aux`` is the seed type's)."""
    if isinstance(out.row, dict):
        tgt = out.input_type
        edge_index = {et: jnp.stack([out.row[et], out.col[et]])
                      for et in out.row}
        return edge_index, out.edge_mask, (out.node_mask[tgt],
                                           out.num_sampled_nodes[tgt])
    return (jnp.stack([out.row, out.col]), out.edge_mask,
            (out.node_mask, out.num_sampled_nodes))


def hop_trimming(model, hops) -> dict:
    """``model.apply`` keywords that run ``model`` trimmed to ``hops``.

    A model that trims by the sampler's hop-block layout says so by
    having ``layer_extents(hops)`` and taking ``hops=`` (``GraphSAGE``;
    ``RGNN``, typed: summed over types and relations); any other model,
    and any model without a layout (``hops=None``), runs whole (``{}``).
    Engagement is a trace-time fact, recorded here when the step is
    built: ``glt.model.layer_edge_slots{layer=l}`` /
    ``glt.model.layer_node_rows{layer=l}`` (the rows layer ``l``
    computes) against ``glt.model.edge_slots`` / ``.node_rows``, and
    ``glt.model.layer_block_slots{layer=l}``: the edge slots layer ``l``
    aggregates hop block by hop block, without a scatter (a model that
    has ``layer_blocks(hops)``: ``GraphSAGE``, every slot; else 0).
    """
    if hops is None or not hasattr(model, "layer_extents"):
        return {}
    gauge, typed = _metrics.gauge, isinstance(hops.node_bounds, dict)
    whole = [sum(b[-1] for b in part.values()) if typed else part[-1]
             for part in (hops.edge_bounds, hops.node_bounds)]
    gauge("glt.model.edge_slots", "edge slots of the sampled batch of the "
          "last hop-trimmed step built").set(whole[0])
    gauge("glt.model.node_rows", "node rows of the sampled batch of the "
          "last hop-trimmed step built").set(whole[1])
    extents = model.layer_extents(hops)
    blocks = (model.layer_blocks(hops) if hasattr(model, "layer_blocks")
              else [()] * len(extents))
    for i, ((_, n_edge, n_dst), blks) in enumerate(zip(extents, blocks), 1):
        gauge("glt.model.layer_edge_slots", "edge slots one layer "
              "aggregates in that step", {"layer": str(i)}).set(n_edge)
        gauge("glt.model.layer_block_slots", "of them, aggregated hop "
              "block by hop block without a scatter",
              {"layer": str(i)}).set(sum(w * f for w, f in blks))
        gauge("glt.model.layer_node_rows", "rows one layer computes "
              "in that step", {"layer": str(i)}).set(n_dst)
    return {"hops": hops}


def loss_and_grads(model, loss, hops=None, mean_over=None):
    """``(params, x, edge_index, edge_mask, y, aux, dropout_key) ->
    (loss, acc, grads)``: forward, loss and backward.

    ``loss(z, y, aux) -> (loss, acc)`` is the only part that differs by
    task (:func:`seed_loss`; :func:`pair_bce_loss` or the caller's for
    the link step, whose ``y`` is the batch's pair metadata; the
    subgraph step wraps the caller's).  ``hops`` is whatever layout the sampler has: a model that
    trims runs trimmed (:func:`hop_trimming`).  ``dropout_key=None`` is
    the evaluation-mode forward.  ``mean_over`` names the mesh axes of an
    enclosing ``shard_map``: gradients, loss and accuracy are then
    ``pmean``-ed under ``glt.step.update``, one scope for the three
    because XLA combines them into one all-reduce.
    """
    trim = hop_trimming(model, hops)

    def run(params, x, edge_index, edge_mask, y, aux, dropout_key):
        mode = ({} if dropout_key is None
                else {"train": True, "rngs": {"dropout": dropout_key}})

        def loss_fn(p):
            z = model.apply(p, x, edge_index, edge_mask, **mode, **trim)
            return loss(z, y, aux)

        (value, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        if mean_over is not None:
            with jax.named_scope("glt.step.update"):
                grads = lax.pmean(grads, mean_over)
                value = lax.pmean(value, mean_over)
                acc = lax.pmean(acc, mean_over)
        return value, acc, grads

    return run


def gated_update(tx):
    """``(state, grads, any_valid) -> state``: the optimiser update and
    the step count, or nothing at all.

    A batch without a single real seed must be a no-op: its gradients are
    zero, but a stateful optimiser (Adam's momentum decay) would still
    move the parameters, and the step bump would shift every later
    dropout key — a padded trailing batch then equals the serial loop
    over the real batches only.  Inside a ``shard_map`` ``any_valid`` has
    to be the same on every shard.

    A model with embedding tables (leaves named as
    :data:`~glt_tpu.models.bipartite.TABLE`) has its update split by
    leaf: the tables' under ``glt.embed.update``, the rest under
    ``glt.step.update`` -- the same ``tx``, the same count and the same
    arithmetic, applied to two halves of the tree
    (:func:`_split_update`).  Without a table the program is the one it
    always was.
    """
    def run(state: TrainState, grads, any_valid) -> TrainState:
        def apply(s):
            tables = _table_leaves(s.params)
            if any(tables):
                params, opt_state = _split_update(tx, grads, s.opt_state,
                                                  s.params, tables)
                return TrainState(params, opt_state, s.step + 1)
            with jax.named_scope("glt.step.update"):
                updates, opt_state = tx.update(grads, s.opt_state,
                                               s.params)
                params = optax.apply_updates(s.params, updates)
            return TrainState(params, opt_state, s.step + 1)

        return lax.cond(any_valid, apply, lambda s: s, state)

    return run


def _table_leaves(params):
    """Per leaf of ``params``, in flattening order: is it a table."""
    from .bipartite import is_table

    return [is_table(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]]


def _split_update(tx, grads, opt_state, params, tables):
    """``tx``'s update applied to the leaves that are not tables (under
    ``glt.step.update``) and to the tables (under ``glt.embed.update``),
    each half a list of leaves.  Every node of ``opt_state`` shaped like
    ``params`` (Adam's moments) is split the same way, every other node
    (the count) is handed to both halves as it is; the halves' states are
    joined back into ``opt_state``'s structure, the count taken from the
    first (both advanced it alike)."""
    pdef = jax.tree_util.tree_structure(params)

    def like_params(node):
        return jax.tree_util.tree_structure(node) == pdef

    def half(tree, side):
        return [leaf for leaf, t in zip(pdef.flatten_up_to(tree), tables)
                if t == side]

    def join(rest, tabs):
        rest, tabs = iter(rest), iter(tabs)
        return pdef.unflatten([next(tabs) if t else next(rest)
                               for t in tables])

    new_params, states = [], []
    for side, scope in ((False, "glt.step.update"),
                        (True, "glt.embed.update")):
        state = jax.tree_util.tree_map(
            lambda n: half(n, side) if like_params(n) else n, opt_state,
            is_leaf=like_params)
        with jax.named_scope(scope):
            p = half(params, side)
            updates, state = tx.update(half(grads, side), state, p)
            new_params.append(optax.apply_updates(p, updates))
        states.append(state)
    opt_state = jax.tree_util.tree_map(
        lambda n, a, b: join(a, b) if like_params(n) else a,
        opt_state, *states, is_leaf=like_params)
    return join(*new_params), opt_state
