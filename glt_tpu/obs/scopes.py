"""Device scopes: the names of the program's stages inside a compiled program.

A host span (:func:`glt_tpu.obs.span`) cannot see into one XLA program;
``jax.named_scope`` can.  It puts a name on JAX's name stack while a
function is *traced*, the stack lands in every HLO instruction's
``metadata.op_name``, and the profiler's trace file carries the optimised
HLO of every module that ran, so a device op's time can be given to the
stage of the program that asked for it (``chipbench/scopes.py`` is the
reader; ``xprof``'s ``hlo_stats`` tool shows the same names).  A scope
changes metadata only: the compiled program is the same with and without.

The rule is the mirror of the span's: a scope goes only inside traced
code, a span never (gltlint GLT010).  Names are lower case, dot
separated, all under ``glt.``; no name takes a value that varies per
call, and no flag turns them off.  The whole taxonomy:

=====================  ====================================================
``glt.sample.hop<k>``  one hop's neighbour read: degree lookup, draw,
                       ``indices``/``edge_ids`` read (k from 1)
``glt.sample.induce``  dedup and relabel (``ops/unique.py``)
``glt.sample.negative`` the link path's negative draw and its membership
                       search in the column-sorted CSR
``glt.sample.relabel`` the link path's pair index (seed edges and
                       negatives located among the seed rows)
``glt.gather.feat``    feature rows out of the table
``glt.gather.label``   label rows
``glt.gather.merge``   a tiered gather's bookkeeping and the placement of
                       the rows the host sent among the hot ones
``glt.embed.lookup``   a learned node-embedding table's rows read by id
                       (``models/bipartite.py::NodeEmbedding``), and the
                       backward scatter of their gradient into the table
``glt.route.bucket``   owner bucketing of ids (``build_routing``)
``glt.route.payload``  assembling exchange payloads, un-permuting replies
``glt.route.exchange`` the ``all_to_all``/``ppermute`` calls themselves
``glt.model.msg``      the model's edge-slot gathers (``x[src]``; GAT's
                       attention logits and weighted messages)
``glt.model.agg``      segment reductions (sum, mean, softmax) and the
                       sum over relations
``glt.model.dense``    matmuls, with their bias, activation and dropout
``glt.step.loss``      the loss
``glt.step.update``    optimiser update (and the gradient all-reduce)
``glt.embed.update``   the same optimiser's update of the embedding tables'
                       leaves (``models/step.py::gated_update``)
=====================  ====================================================

Beside them, two metrics of the tables (docs/observability.md): the gauge
``glt.embed.table_rows{type}`` (a table's rows, set when a step over it
is built) and the counter ``glt.embed.rows{type}`` (the rows a batch
looked up, a column of the step's deferred counts).

Where scopes nest, a reader takes the outermost ``glt.*`` name: the
unique pass inside a dedup gather is gather work.
"""
from __future__ import annotations

import functools

import jax


def scoped(name: str):
    """Decorator: trace every call of the function under
    ``jax.named_scope(name)``.

    ``jax.named_scope`` is itself usable as a decorator, but then ONE
    context-manager object, with its saved outer context, serves every
    call: two threads tracing at once (serving workers, loader warm-up)
    would restore each other's name stacks.  This opens a fresh one per
    call.
    """
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorate
