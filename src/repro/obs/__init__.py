"""Observability of collectives and serving: span tracing, model-error
monitoring, and a unified metrics registry.

Three pieces, one evidence surface:

* :mod:`repro.obs.trace` -- every engine collective emits a structured
  span (op, axes, bytes, chosen plan, cache status, predicted cost,
  measured wall time) exportable as Chrome-trace/Perfetto JSON.  The
  serving loop emits ``CAT_SERVE`` spans at its layer boundaries, all
  nested in ``serve.step`` (one ``step_once``): ``serve.schedule``
  (``queued``), ``serve.cow`` (``blocks``), ``serve.prefill`` (``rid``,
  ``tokens``, ``ctx``), ``serve.decode.batch`` (``rows``),
  ``serve.decode`` (``rows``, ``batch``), ``serve.sample`` (``rows``),
  ``serve.wait`` (the one host wait on the device) and ``serve.append``
  (``tokens``).  Every span also has a profiler sink: it opens a
  ``jax.profiler.TraceAnnotation`` with its args as event stats, so a
  ``jax.profiler`` trace holds it on the device ops' clock, whether the
  in-memory tracer is on or not.
* :mod:`repro.obs.model_error` -- an online aggregator binning spans
  by (op, topology, bytes-decile) and flagging drift of predicted vs
  measured time past the paper's 4% bound, with a recalibration
  recommendation.
* :mod:`repro.obs.registry` -- counters/gauges/histograms with
  Prometheus-text and JSON exporters; the engine's cache stats, the
  serving telemetry, and the bench counters all export through it.

Enable the in-memory record at runtime via ``launch/train.py --trace``
/ ``launch/serve.py --trace`` (plus ``--obs-report`` for the error
table and ``--metrics-out`` for the registry dump), or
programmatically::

    from repro import obs
    obs.enable_tracing(measure=True)
    ... run engine collectives, or serve ...
    obs.get_tracer().export_chrome("trace.json")

The profiler sink needs only a profiler session around the work
(``jax.profiler.start_trace(dir)`` ... ``stop_trace()``).
"""

from repro.obs.registry import (Counter, Gauge, Histogram,   # noqa: F401
                                MetricsRegistry, REGISTRY,
                                EXPORT_SCHEMA, validate_export,
                                export_engine_stats)
from repro.obs.trace import (Span, Tracer, TRACE_SCHEMA,     # noqa: F401
                             CAT_COLLECTIVE, CAT_PHASE, CAT_SERVE,
                             get_tracer, set_tracer, enable_tracing,
                             disable_tracing, load_chrome_trace,
                             collective_spans, validate_spans)
from repro.obs.model_error import (ModelErrorMonitor,        # noqa: F401
                                   ErrorBin, bytes_decile,
                                   DEFAULT_THRESHOLD)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "EXPORT_SCHEMA", "validate_export", "export_engine_stats",
    "Span", "Tracer", "TRACE_SCHEMA", "CAT_COLLECTIVE", "CAT_PHASE",
    "CAT_SERVE",
    "get_tracer", "set_tracer", "enable_tracing", "disable_tracing",
    "load_chrome_trace", "collective_spans", "validate_spans",
    "ModelErrorMonitor", "ErrorBin", "bytes_decile", "DEFAULT_THRESHOLD",
]
