"""End-to-end observability for the serving stack.

One subsystem owns every measurement the serving path emits:

  * ``clock()`` — the single wall-clock source (monotonic
    ``time.perf_counter``) every serving-path timer reads, so spans from
    different components land on one comparable timeline;
  * ``Observability.span(name)`` — a host span on the profiler's clock
    (``jax.profiler.TraceAnnotation``) whose milliseconds land in the
    registry's ``span_ms{span=name}`` histogram and, when tracing, on the
    recorder's ``host`` track; ``NULL_SPAN`` is its free stand-in when
    no handle is attached;
  * ``LatencyHistogram`` — a streaming fixed-bucket log2 histogram:
    O(1) memory, O(1) observe, mergeable across shards/episodes, with
    nearest-rank quantiles whose bucket provably contains the true
    sample quantile;
  * ``MetricsRegistry`` — named counters / gauges / histograms (with
    optional labels) fed by the scheduler, the page allocator, the
    partition executor and the fleet loop; exports flat JSON and
    Prometheus text;
  * ``TraceRecorder`` — request-lifecycle and window spans on named
    tracks, exported as Chrome-trace JSON (loadable in Perfetto /
    ``chrome://tracing``), plus a validator the CI smoke runs;
  * ``SLOReport`` — p50/p90/p99 chunk latency, queue wait, goodput and
    cancel-rate lines distilled from a registry at end of serve.

The design constraint is *zero cost when disabled*: every producer takes
an ``Observability`` handle that may be ``None``, all stamps happen at
host-owned boundaries the serving loop already crosses (admission,
dispatch, window close, harvest), and instrumentation never adds a
host↔device sync — pinned by a test comparing decode outputs and ``scan_windows``
with obs on vs off.
"""

from repro.obs.clock import clock
from repro.obs.histogram import LatencyHistogram
from repro.obs.metrics import Counter, Gauge, MetricsRegistry
from repro.obs.slo import SLOReport, build_slo_report
from repro.obs.span import NULL_SPAN, Span
from repro.obs.trace import TraceRecorder, validate_chrome_trace


class Observability:
    """The one handle threaded through the serving stack.

    Bundles a ``MetricsRegistry`` (always) and a ``TraceRecorder``
    (unless ``trace=False``) behind a single optional argument: pass an
    ``Observability`` to ``ContinuousBatchingScheduler`` / ``serve_fleet``
    to instrument a run, or ``None`` (the default everywhere) to serve
    with zero instrumentation cost.
    """

    def __init__(self, trace: bool = True):
        self.metrics = MetricsRegistry()
        self.trace = TraceRecorder() if trace else None

    # the stack's single wall-clock source, re-exported for call sites
    # that already hold the handle
    clock = staticmethod(clock)

    def span(self, name: str, **args) -> Span:
        """A context manager timing one host phase as ``name`` (see
        ``repro.obs.span``); ``args`` ride on the profiler event."""

        return Span(self.metrics, self.trace, name, args)

    def slo_report(self) -> SLOReport:
        return build_slo_report(self.metrics)


__all__ = [
    "Observability",
    "NULL_SPAN",
    "Span",
    "clock",
    "LatencyHistogram",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "TraceRecorder",
    "validate_chrome_trace",
    "SLOReport",
    "build_slo_report",
]
