"""Dependency-free observability: tracing, metrics, jit guards, drift.

Four modules, one contract: **near-zero cost when disabled**. Every
producer (serving engine, replay harness, batched DES, sweeps) holds its
tracer/registry/monitor as ``None`` by default and guards each recording
site with a single ``is not None`` check; the ``Null*`` classes cover
unconditional call sites. ``benchmarks/obs_bench.py`` gates the enabled-
path overhead (<3% decode fast path, <10% DES) and the histogram's
percentile error bound against ``numpy.percentile``.

- :mod:`~repro.obs.trace` — per-request span recording + Chrome
  trace-event / Perfetto JSON export (wall events also enter
  ``jax.profiler.TraceAnnotation``, so they land on the device trace's
  clock while a profile runs), and the shared monotonic
  :func:`~repro.obs.trace.timecall` timing helper.
- :mod:`~repro.obs.metrics` — counters, gauges, log-bucketed streaming
  histograms (exact-bound percentiles, mergeable snapshots).
- :mod:`~repro.obs.jax_hooks` — recompile + host transfer counters wired
  through ``compat.jit(label=...)``;
  :func:`~repro.obs.jax_hooks.assert_max_compiles`.
- :mod:`~repro.obs.monitor` — predicted-vs-measured wait drift alarm
  feeding the replay controller's re-solve cadence.
"""
from .jax_hooks import assert_max_compiles, to_host, trace_counts
from .metrics import (DEFAULT_PERCENTILES, Counter, Gauge,
                      HistogramSnapshot, MetricsRegistry, NullRegistry,
                      NULL_REGISTRY, StreamingHistogram, histogram_per_lane,
                      merge_snapshots)
from .monitor import DriftMonitor, DriftReport, predicted_wait_quantile
from .trace import (NULL_TRACER, NullTracer, Tracer, VIRTUAL_PID, WALL_PID,
                    monotonic, spans_by_request, timecall,
                    validate_request_trees)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "VIRTUAL_PID", "WALL_PID",
    "monotonic", "timecall", "spans_by_request", "validate_request_trees",
    "StreamingHistogram", "HistogramSnapshot", "merge_snapshots",
    "histogram_per_lane", "Counter", "Gauge", "MetricsRegistry",
    "NullRegistry", "NULL_REGISTRY", "DEFAULT_PERCENTILES",
    "assert_max_compiles", "to_host", "trace_counts",
    "DriftMonitor", "DriftReport", "predicted_wait_quantile",
]
