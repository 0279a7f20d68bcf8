"""paddle_tpu.observability — always-on telemetry for a serving fleet.

The profiler (``paddle_tpu.profiler``) answers "why was this step
slow?" with *sampled* device traces; this package answers "what is the
process doing right now, and what did it do just before it died?" with
three always-on layers (docs/observability.md):

  * **metrics** — a process-wide registry of labeled
    Counter/Gauge/Histogram with Prometheus text exposition and a JSON
    snapshot; subsystems with their own counter structs (the serving
    engine) publish as pull-time collector views, so the hot path
    writes nothing.
  * **spans** — trace/span ids on the profiler's clock: every span is
    a host event of whatever ``jax.profiler`` session is recording and
    an entry of a bounded ring, propagated across ``TCPStore`` and
    ``distributed.rpc`` boundaries, exportable as Chrome-trace JSONL.
  * **flight recorder** — a bounded ring of recent events (compiles,
    preemptions, fault fires, shed/timed-out requests, watchdog probe
    snapshots) dumped to a postmortem JSON file on a watchdog trip, an
    unhandled engine error, or SIGUSR2; read with
    ``python -m paddle_tpu.observability dump``.

Plus the **compile/retrace event log** (``jit_events``): every XLA
trace is recorded with fn/signature/elapsed and its trace, lower and
compile phases, and a retrace of an
already-warm signature increments an alarmable counter — "recompile
after warmup" stops being a flaky bench and becomes a monitorable
number. An optional scrape thread (``start_scrape_server``) serves
``/metrics`` and ``/healthz``.
"""
from . import flight, jit_events, latency, metrics, scrape, spans
from . import stepstats
from .flight import (
    FlightRecorder,
    dump,
    find_dumps,
    get_flight_recorder,
    install_signal_handler,
    record,
)
from .latency import LatencyDigest, SLOConfig, SLOTracker
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricFamily,
    MetricsRegistry,
    counter,
    gauge,
    get_registry,
    histogram,
    register_latency_view,
)
from .scrape import (
    ScrapeServer,
    health_snapshot,
    register_health_provider,
    start_scrape_server,
    unregister_health_provider,
)
from .stepstats import StepStats, register_stepstats_view
from .spans import (
    Span,
    current_span,
    current_trace_id,
    current_traceparent,
    export_chrome_trace,
    finished_spans,
    remote_span,
    span,
)

__all__ = [
    # metrics
    "Counter", "Gauge", "Histogram", "MetricFamily", "MetricsRegistry",
    "counter", "gauge", "histogram", "get_registry",
    "register_latency_view",
    # latency digests + SLO burn
    "LatencyDigest", "SLOConfig", "SLOTracker",
    # spans
    "Span", "span", "remote_span", "current_span", "current_trace_id",
    "current_traceparent", "finished_spans", "export_chrome_trace",
    # flight recorder
    "FlightRecorder", "get_flight_recorder", "record", "dump",
    "find_dumps", "install_signal_handler",
    # serving step observatory
    "StepStats", "register_stepstats_view",
    # scrape endpoint
    "ScrapeServer", "start_scrape_server", "register_health_provider",
    "unregister_health_provider", "health_snapshot",
    # submodules
    "flight", "jit_events", "latency", "metrics", "scrape", "spans",
    "stepstats",
]
