"""Unified observability layer for the serving stack (DESIGN.md §15).

Three pillars, all zero-dependency host-side code (numpy only — jax is
imported lazily, by the phase spans alone — and no third-party
clients), shared by every layer of the serving stack:

  * :mod:`repro.obs.metrics` — a typed metrics registry (`Counter`,
    `Gauge`, `Histogram` with fixed log-scale latency/pull buckets,
    labeled by ``precision`` / ``pull_mode`` / ``priority_class`` /
    ``outcome``), JSON snapshot export and Prometheus text-exposition
    rendering.  The engines', admission controller's, fault injector's
    and stores' counters all live here; their ``stats()`` dicts are
    computed *from* the registry and stay byte-compatible.
  * :mod:`repro.obs.trace` — per-request span tracing on the caller's
    clock, exported as Chrome trace-event JSON loadable in Perfetto,
    with bounded memory via reservoir sampling over requests; and the
    ``repro.*`` phase spans on the JAX profiler's clock plus the
    compile counting behind ``cascade_compiles_total``.
  * :mod:`repro.obs.flight` — a crash flight recorder: a fixed-size
    ring buffer of structured events dumped to a JSON file when a
    request terminates ``failed`` or a store flush raises.

See docs/OBSERVABILITY.md for the metric catalog, span taxonomy and
flight-recorder event schema.
"""

from repro.obs.flight import FlightRecorder
from repro.obs.metrics import (LATENCY_BUCKETS_MS, PULL_FRAC_BUCKETS,
                               PULL_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry, null_registry,
                               summarize_latencies)
from repro.obs.trace import SpanTracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "null_registry",
    "summarize_latencies", "LATENCY_BUCKETS_MS", "PULL_FRAC_BUCKETS",
    "PULL_BUCKETS", "SpanTracer", "FlightRecorder",
]
