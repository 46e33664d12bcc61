"""Unified observability layer: span tracing + one metrics namespace.

Two small, dependency-free pieces every layer of the stack reports into:

* :mod:`repro.obs.trace` — a low-overhead span **Tracer** (monotonic
  clock, thread-safe ring buffer, nested spans with categories,
  key/value args and parent ids, absolute ``perf_counter`` starts)
  exporting Chrome/Perfetto ``trace_event`` JSON.  The module-level
  tracer is DISABLED by default: every instrumentation point is a
  single attribute check + shared null context manager, with a tested
  overhead budget (≤2% of epoch time).

* :mod:`repro.obs.metrics` — a **MetricsRegistry** of counters and
  gauges under one dotted namespace (``engine.sm_rounds``,
  ``cluster.node3.fence_wait_s``, ``reads.mid_epoch_served``).  The
  existing stats dataclasses REGISTER into it (``register_object`` /
  ``register_provider``) instead of being hand-merged per benchmark;
  per-epoch ``snapshot()`` builds the time series that the JSON-lines
  exporter serializes.
"""
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import (Tracer, get_tracer, kernel_launch,
                             kernel_launch_counts, set_tracer, span)

__all__ = [
    "MetricsRegistry",
    "Tracer",
    "get_tracer",
    "set_tracer",
    "span",
    "kernel_launch",
    "kernel_launch_counts",
]
