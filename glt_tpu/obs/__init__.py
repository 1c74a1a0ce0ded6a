"""glt_tpu.obs — unified tracing, metrics, and roofline profiling.

The library-wide observability subsystem (docs/observability.md):

  * **Tracing** (:mod:`.trace`): nested host-side spans with explicit
    device fencing, exported as Chrome-trace/Perfetto JSON; summarize
    with ``python -m glt_tpu.obs summarize trace.json``.  Every span is
    also a ``jax.profiler.TraceAnnotation("glt.<name>")``: a profiler
    session sees the program's spans on the device's clock.
  * **Device scopes** (:mod:`.scopes`): ``jax.named_scope`` under one
    ``glt.<layer>.<stage>`` taxonomy, read back from a profiler trace
    through the HLO metadata it carries (``chipbench/scopes.py``).
  * **Metrics** (:mod:`.metrics`): counters/gauges/histograms under one
    ``glt.*`` namespace with near-zero-cost no-op defaults; Prometheus
    text exposition serves the ``get_metrics`` op on ``DistServer``.
  * **Roofline** (:mod:`.roofline`): a measured device-memcpy bandwidth
    ceiling so ``gather_gb_s`` becomes an achieved-vs-peak fraction.

Tracer and metrics are OFF by default and cost roughly a global read +
branch (a span: one annotation object) per call site when off.  Two
rules say where each tool goes: **host spans** (``span()``, ``inc()``
and every other metric call) never inside a jit-traced function (gltlint
GLT010: the call would run once, at trace time); **device scopes**
(``jax.named_scope`` / :func:`.scopes.scoped`) only inside one, with a
name from the table in :mod:`.scopes`.  Read both with ``python
chipbench/scopes.py <trace dir>`` or ``xprof``'s ``hlo_stats`` tool
(docs/observability.md "Device scopes").

>>> from glt_tpu import obs
>>> obs.metrics.enable()
>>> tracer = obs.start_trace()
>>> with obs.span("epoch") as sp:
...     loss = step(...)
...     sp.fence(loss)                    # close waits for the device
>>> obs.stop_trace("/tmp/trace.json")
>>> obs.metrics.snapshot()["glt.loader.batches"]
"""
from . import attrib  # noqa: F401  (stdlib-only; jax imports are lazy)
from . import compilewatch  # noqa: F401  (stdlib-only; lazy jax)
from . import device  # noqa: F401  (stdlib-only; jax imports are lazy)
from . import flight  # noqa: F401  (stdlib-only; safe without jax)
from . import metrics  # noqa: F401  (stdlib-only; safe without jax)
from . import profiler  # noqa: F401  (stdlib-only; jax imports lazy)
from . import slo  # noqa: F401  (stdlib-only; safe without jax)
from .flight import (  # noqa: F401
    FlightRecorder,
    merge_flight_dumps,
    validate_flight_dump,
)
from .merge import merge_traces, span_tree_check  # noqa: F401
from .metrics import prune_unmeasured  # noqa: F401
from .slo import SloMonitor, SloSpec, default_specs  # noqa: F401
from .roofline import measure_memcpy_roofline, roofline_fraction  # noqa: F401
from .summarize import (  # noqa: F401
    format_flight_summary,
    format_summary,
    summarize_flight,
    summarize_trace,
)
from .trace import (  # noqa: F401
    Span,
    Tracer,
    auto_trace,
    auto_trace_export,
    current,
    install,
    span,
    start_trace,
    stop_trace,
    validate_chrome_trace,
)

__all__ = [
    "FlightRecorder",
    "SloMonitor",
    "SloSpec",
    "Span",
    "Tracer",
    "attrib",
    "auto_trace",
    "compilewatch",
    "device",
    "profiler",
    "auto_trace_export",
    "current",
    "default_specs",
    "flight",
    "format_flight_summary",
    "format_summary",
    "install",
    "measure_memcpy_roofline",
    "merge_flight_dumps",
    "merge_traces",
    "metrics",
    "prune_unmeasured",
    "slo",
    "validate_flight_dump",
    "roofline_fraction",
    "span",
    "span_tree_check",
    "start_trace",
    "stop_trace",
    "summarize_flight",
    "summarize_trace",
    "validate_chrome_trace",
]
