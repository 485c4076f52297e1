"""The serving stack's one monotonic clock.

:func:`now` is the only ``time.perf_counter`` call site the serving
stack uses.  ``Request.t_submit`` / ``t_admit`` / ``t_first`` /
``t_done``, the step-phase histograms and the benchmark timings all read
it, so TTFT and its parts computed from request marks, phase durations
and benchmark timings can never disagree about what "a millisecond" was.

Spans are not recorded here: the :class:`~repro.obs.runtime.Observer`
opens them through the span factory its caller injects
(``jax.profiler.TraceAnnotation``), so they land on the profiler's
timeline beside the device's ops.  Host-pure by contract (lint rule
RA004).
"""
from __future__ import annotations

import time


def now() -> float:
    """The serving stack's one monotonic clock (seconds, float)."""
    return time.perf_counter()
