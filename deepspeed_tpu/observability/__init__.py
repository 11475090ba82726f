"""Unified serving/training observability.

All recording is host-side and allocation-light, around host boundaries
that already exist, honoring the async-dispatch design: nothing here forces
a device sync. Two clocks are in play, and one API joins them: a span taken
with :meth:`RequestTracer.scope` goes to the tracer's ring stamped with
``time.monotonic()`` (always on) **and** to ``jax.profiler.TraceAnnotation``,
so that whenever a profiler session is on it sits in the xplane on the
device trace's clock, next to the device lines. Everything else (metrics,
request timelines, the goodput ledger) is ``time.monotonic()`` /
``perf_counter`` alone.

- :mod:`metrics` — a process-wide registry of counters, gauges, and
  log-bucketed histograms (fixed-size numpy bucket arrays; p50/p90/p99
  derivable at read time). Rendered as Prometheus text by the serving
  daemon's ``GET /metrics`` and bridgeable into the ``monitor/`` fan-out
  (one ``(name, value, step)`` event schema shared with training).
- :mod:`tracing` — the span API (``scope``: the program's own phases,
  ``ds.train.*`` / ``ds.tick.*`` / ``ds.init.*``, with parent and self
  time) and per-request span timelines (submit → queue → admit → prefill
  chunks → fused K-waves → journal → finish) in bounded rings, exportable
  per-uid as JSON and in bulk as Chrome ``trace_event`` JSON (loadable in
  Perfetto / chrome://tracing).
- :mod:`profiler` — guarded on-demand ``jax.profiler`` captures (one at
  a time, duration-bounded) behind ``POST /debug/profile``.
- :mod:`xla` — compile observability: per-compile-key compile/retrace/hit
  telemetry (:class:`CompileWatch` wrapping every jit entry point), a
  compiling call taken apart into jax's own trace / lowering / backend
  compile or cache load (one tap on ``jax.monitoring``),
  cost-analysis FLOPs feeding the ``ds_train_mfu`` /
  ``ds_serving_wave_mfu`` gauges, and device-memory gauges.
- :mod:`goodput` — a wall-clock ledger attributing every training second
  to {useful step, compile, host-sync stall, checkpoint save/load,
  anomaly rollback, restart}, exported as
  ``ds_goodput_seconds_total{category=...}``.

Serving is gated by the ``observability`` config block
(:class:`ObservabilityConfig` in ``inference/v2/config_v2.py``); training
by :class:`TrainObservabilityConfig` (``config/feature_configs.py``).
Training runs have no HTTP server — they export through
``MetricsRegistry.write_textfile`` (atomic Prometheus textfile consumed
by ``ds_top --file``) and the ``monitor.write_registry`` bridge.
"""

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry, quantiles_from_counts)
from .tracing import RequestTracer, get_tracer
from .profiler import ProfilerBusy, ProfilerCapture, profile_dir
from .instruments import ServingInstruments
from .xla import (CompileWatch, TrainInstruments, WatchedJit,
                  cost_analysis_flops, install_backend_compile_listener,
                  refresh_memory_gauges)
from .goodput import CATEGORIES as GOODPUT_CATEGORIES, GoodputLedger

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "quantiles_from_counts",
    "RequestTracer", "get_tracer",
    "ProfilerBusy", "ProfilerCapture", "profile_dir",
    "ServingInstruments",
    "CompileWatch", "TrainInstruments", "WatchedJit", "cost_analysis_flops",
    "install_backend_compile_listener", "refresh_memory_gauges",
    "GOODPUT_CATEGORIES", "GoodputLedger",
]
