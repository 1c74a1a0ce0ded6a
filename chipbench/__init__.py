"""The benchmark of glt_tpu: the yardstick later PRs are judged on.

``run.py`` is the command of ``BENCHMARK.json``.  Found by name from a
cell: ``configs/`` (sizes, source, guarantees), ``traffic/`` (a mix names
its driver and parameters), ``drivers/`` (one per kind of traffic),
``layer_metrics/`` + ``reducers/`` (one reader per per-layer metric).
The yardstick itself: ``gen.py`` + ``draws.py`` (seeded data on the
device), ``reference.py`` + ``checks.py`` (what decides ``correct``),
``openloop.py`` (load), ``tracered.py`` + ``breakdown.py`` (trace to
numbers), ``peaks.py`` (published peaks, bytes).  Tools run by hand:
``calibrate.py`` (``node_capacity``), ``sweep.py`` (the knee of an
open-loop cell).  ``rehearsal.json`` lists the ``tiny-*`` cells the
tests run on the CPU.
"""
