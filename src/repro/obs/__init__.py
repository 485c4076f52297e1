"""Host-pure observability for the serving stack: metrics, spans, and
the injectable :class:`~repro.obs.runtime.Observer` the engine reports to.

Three pieces (see docs/observability.md):

  * :mod:`repro.obs.metrics` — a low-overhead registry of counters /
    gauges / histograms (fixed log-spaced buckets) with a Prometheus
    text-exposition renderer and format validator.
  * :mod:`repro.obs.trace` — the repo's single monotonic clock source
    (:func:`repro.obs.trace.now`).
  * :mod:`repro.obs.runtime` — the :class:`Observer` seam wired through
    ``ServingEngine`` / ``Scheduler`` / ``PageAllocator`` /
    ``PromptLookupDrafter``, which opens the engine's spans through an
    injected factory (``jax.profiler.TraceAnnotation``), plus the
    zero-cost :data:`NULL_OBSERVER` default.

Like the Scheduler, every module here is contractually jax-free (lint
rule RA004, ``repro.analysis.lint.PURE_MODULES``): observability can
never add a device sync or an executable to the hot loop.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               validate_prometheus_text)
from repro.obs.runtime import NULL_OBSERVER, NullObserver, Observer
from repro.obs.trace import now

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "validate_prometheus_text",
    "now",
    "Observer", "NullObserver", "NULL_OBSERVER",
]
