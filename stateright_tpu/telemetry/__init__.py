"""Unified observability layer for every checker backend.

Three parts, all process-local and always importable:

- ``metrics``: a registry of named counters, gauges, and log-scale
  histograms with cheap ``inc``/``set``/``observe`` calls and a
  ``snapshot() -> dict`` for reporters and benches.
- ``trace``: span and instant events with monotonic timestamps, an
  always-on in-memory ring buffer, an opt-in JSONL sink, and a Chrome
  trace-event exporter (loadable in Perfetto / ``chrome://tracing``),
  each span bridged into a ``jax.profiler`` annotation so host spans
  line up with XLA device traces, and parent ids that make one check's
  spans a tree (``CheckTracer``).
- ``attribution``: the opt-in wave-timeline attribution engine
  (``WaveAttribution``) — fenced per-wave wall-clock classified into
  device/host phases, with the overlap-headroom ledger
  ``scripts/gap_report.py`` renders.

The quantities GPU model-checking studies show must be observed *during*
runs — frontier width per wave, dedup hit-rate, hash-set load factor —
flow through here from every backend (host BFS/DFS, on-demand,
simulation, the TPU wave/drain loops, and the sharded mesh checker).
"""

from .attribution import WaveAttribution
from .coverage import CoverageLedger, DeviceCoverage
from .fleet import FleetFold, FleetInstruments, skew_stats
from .instruments import (
    BlockInstruments,
    CommsInstruments,
    TenantInstruments,
    WaveInstruments,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    discard_run_registry,
    metrics_registry,
    run_registries,
)
from .trace import (
    CheckTracer,
    JsonlSink,
    RunScopedTracer,
    Tracer,
    chrome_trace,
    chrome_trace_from_jsonl,
    get_tracer,
    instant,
    span,
    write_chrome_trace,
)

# The monitor server drags in http.server (and its email dependency) —
# cost only monitored runs should pay, so its symbols resolve lazily
# (PEP 562), matching the function-local imports in Checker.serve_monitor
# and bench.py's --monitor-port path.
_SERVER_SYMBOLS = frozenset({
    "FlightRecorder",
    "MonitorCore",
    "MonitorServer",
    "ProgressEstimator",
    "StallWatchdog",
    "prometheus_text",
    "prometheus_text_all_runs",
    "registry_hygiene_problems",
})


def __getattr__(name):
    if name in _SERVER_SYMBOLS:
        from . import server

        return getattr(server, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )


__all__ = [
    "BlockInstruments",
    "CheckTracer",
    "CommsInstruments",
    "Counter",
    "CoverageLedger",
    "DeviceCoverage",
    "FleetFold",
    "FleetInstruments",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MetricsRegistry",
    "MonitorCore",
    "MonitorServer",
    "ProgressEstimator",
    "RunScopedTracer",
    "StallWatchdog",
    "TenantInstruments",
    "Tracer",
    "WaveAttribution",
    "WaveInstruments",
    "chrome_trace",
    "chrome_trace_from_jsonl",
    "discard_run_registry",
    "get_tracer",
    "instant",
    "metrics_registry",
    "prometheus_text",
    "prometheus_text_all_runs",
    "registry_hygiene_problems",
    "run_registries",
    "skew_stats",
    "span",
    "write_chrome_trace",
]
