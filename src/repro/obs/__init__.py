"""Fleet observability: metrics registry, round tracer, SLO accounting.

The measurement plane of the serving stack (ROADMAP item 3's substrate):

``metrics``
    typed counters, gauges and streaming log-bucketed histograms behind
    one ``MetricsRegistry`` per fleet — bounded memory, mergeable,
    a single lock-consistent ``snapshot()``. Replaces the hand-rolled
    percentile math that used to live in ``serving/frontend.py``,
    ``serving/engine.py`` and ``serving/session.py``.

``trace``
    ``span``: the round's host phases as ``jax.profiler`` annotations,
    on the device's clock in any profiler trace; and span-based round
    tracing with explicit clock injection (the frontend's fake-clock
    discipline) and Chrome/Perfetto ``trace_event`` + JSON-lines export.
    Sampled: fencing the async round pipeline happens at trace-sample
    rounds ONLY.

``slo``
    per-tenant latency-objective tracking — target vs observed p99 and
    error-budget burn rate — surfaced in ``summary()["per_tenant"]``
    and the frontend's ``metrics`` wire op.

See docs/OBSERVABILITY.md for metric names, the span taxonomy and the
SLO semantics.
"""
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.slo import SLOTracker
from repro.obs.trace import RoundTracer, Span, span

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "RoundTracer", "SLOTracker", "Span", "span"]
