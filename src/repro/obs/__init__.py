"""Unified telemetry: metrics registry, request tracing, program spans,
step profiling.

Four pillars, stdlib-only but for the profiler (no prometheus_client /
opentelemetry):

* :mod:`repro.obs.metrics` — a thread-safe :class:`MetricsRegistry` of
  counters, gauges and bounded-bucket histograms, cheap enough for the
  serving host loop, exported as Prometheus text or JSON.  The serving
  engine, scheduler, KV pool, compression pipeline and trainer all publish
  into it, replacing the ad-hoc stat dicts that used to live on each.
* :mod:`repro.obs.trace` — per-request :class:`Span` lifecycle
  (enqueue -> admit -> prefill -> decode marks -> retire) yielding TTFT,
  time-per-output-token, queue wait and block-growth stalls, dumped as JSONL.
* :mod:`repro.obs.spans` — :func:`span`, the serving path's spans on the
  ``jax.profiler`` clock, with host seconds per phase and backend compiles
  charged to the innermost open span.
* :mod:`repro.obs.profile` — :class:`StepProfiler` wall-time ring buffer,
  plus the live roofline that ties an artifact's per-site shift-add budget
  to the throughput a *running* engine achieves (the same table
  ``BENCH_serving.json`` tracks offline).

Dependency rule: ``obs`` imports nothing from the rest of ``repro`` (jax only
lazily, for spans and the compile listener), so any layer — including
``kernels.dispatch`` — may publish into it without cycles.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               dump_metrics, get_global, merged_snapshot,
                               parse_prometheus, start_metrics_server)
from repro.obs.profile import StepProfiler, live_roofline, roofline
from repro.obs.spans import install_compile_counter, span
from repro.obs.trace import RequestTracer, Span

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "parse_prometheus",
    "get_global", "merged_snapshot", "dump_metrics", "start_metrics_server",
    "RequestTracer", "Span", "StepProfiler", "roofline", "live_roofline",
    "span", "install_compile_counter",
]
