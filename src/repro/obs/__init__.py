"""`repro.obs` — the serving observability layer.

Three pieces, one bundle:

  * `metrics` — a `MetricsRegistry` of counters / gauges / percentile
    histograms (p50/p95/p99 faithful to numpy): per-request TTFT and
    inter-token latency, queue depth, cache occupancy per tier, spill/fetch
    bytes, speculative acceptance.  The scheduler's legacy ``stats`` /
    ``spill_stats`` dicts are live `CounterView`s over this registry.
  * `trace` — a structured span `Tracer` recording each request's lifecycle
    (submit → admit → prefill chunks → first token → decode steps →
    preempt/resume → finish) and the scheduler's cycle as Chrome-trace-event
    JSON loadable in Perfetto; `NullTracer` (the default) no-ops everything.
  * `Observability.span` — the one way the serving stack marks a phase: a
    `jax.profiler.TraceAnnotation` (the profiler's clock, beside the device
    trace) and a tracer span (window-wide) under one name.

`Observability` carries them through the serving stack
(`InferenceEngine(obs=...)`, `RequestScheduler(obs=...)`); every piece is
host-side only, and the A7 program audit (`python -m repro.analysis audit`)
proves the compiled decode/verify/pool-step programs are byte-identical
with the whole layer enabled vs absent.  docs/observability.md is the
catalog.
"""

from __future__ import annotations

import contextlib
import dataclasses

from jax.profiler import TraceAnnotation

from repro.obs.metrics import (Counter, CounterView, Gauge, Histogram,
                               MetricsRegistry, percentile)
from repro.obs.trace import (ENGINE_TRACK, SCHED_TRACK, NullTracer, Tracer,
                             clock_offset, request_track)

__all__ = ["Counter", "CounterView", "Gauge", "Histogram", "MetricsRegistry",
           "NullTracer", "Observability", "Tracer", "clock_offset",
           "percentile", "SCHED_TRACK", "ENGINE_TRACK", "request_track"]

# What `Observability.span` hands back with profiling and tracing both off:
# one shared instance, so an untraced span allocates nothing.
_NULL = contextlib.nullcontext()


@dataclasses.dataclass
class Observability:
    """The bundle a serving component records through.

    ``metrics`` is always a real registry (recording a counter is cheaper
    than branching around it); ``tracer`` defaults to the no-op
    `NullTracer`; ``profile`` puts every `span` on the profiler's host
    plane too.  One bundle may be shared across the engine, the scheduler,
    and the pool — their metric names are dot-prefixed (``engine.``,
    ``sched.``, ``pool.``, ``req.``) so a shared registry stays
    collision-free.
    """

    metrics: MetricsRegistry = dataclasses.field(default_factory=MetricsRegistry)
    tracer: NullTracer = dataclasses.field(default_factory=NullTracer)
    profile: bool = False

    def span(self, name: str, track: str = SCHED_TRACK):
        """One phase of the serving stack, as a context manager.

        With ``profile`` on it is a `jax.profiler.TraceAnnotation` (on a
        capture's host plane, the device trace's clock); with a live
        tracer, a ``B``/``E`` pair on ``track`` (the whole run, on
        ``perf_counter``).  Both at once give the span on both clocks, which
        `clock_offset` joins.  With both off it is the module's one shared
        ``nullcontext``."""
        if not self.profile and not self.tracer.enabled:
            return _NULL
        return _span(self.tracer, name, track, self.profile)


@contextlib.contextmanager
def _span(tracer: NullTracer, name: str, track: str, profile: bool):
    # The annotation opens first and closes last, so the tracer's clock
    # readings sit inside the profiler's by the cost of one tracer call.
    with TraceAnnotation(name) if profile else _NULL:
        with tracer.span(name, track):
            yield
