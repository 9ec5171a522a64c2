"""Unified observability layer (docs/OBSERVABILITY.md).

Three pieces, all host-side (never inside jit — the no-callback jaxpr
contract in analysis/jaxpr_audit.py is re-audited over the
instrumented entries):

- ``metrics`` — a thread-safe **metrics registry** (counters / gauges /
  histograms with labels) with Prometheus text exposition, served from
  the serving HTTP transport's ``/metrics`` route;
- ``tracing`` — **span tracing** layered on ``timer.Timer``: every
  scope is a ``lgbm:<name>`` host event in a ``jax.profiler`` trace
  (the ``profile_dir`` CLI param), and a ``TraceRecorder`` exports the
  same spans as Chrome trace-event JSON (Perfetto) without a profiler;
- ``manifest`` — per-run **manifest JSON**: config, device topology,
  compile counts (retrace guard), phase timings, metrics snapshot, and
  runtime collective wire bytes vs the static ``cost_budget.json``
  pins;
- ``recorder`` — the **flight recorder**: one JSONL record per
  boosting round (phases, learning curve, tree stats, throughput),
  enabled via the ``record_file=`` config/CLI param;
- ``anomaly`` — **sentinels** over the flight-record stream (NaN/Inf,
  loss spikes, throughput collapse, dead rounds) behind the
  ``anomaly_policy=off|warn|abort`` knob;
- ``aggregate`` — **fleet aggregation**: merges per-process registry
  snapshots and recorder streams host-side (files / ``/metrics``
  pulls, explicitly no jax collectives).
"""

from . import aggregate, anomaly, manifest, metrics, recorder, tracing
from .anomaly import AnomalyAbort, AnomalySentinel
from .recorder import FlightRecorder
from .manifest import build_manifest, write_manifest
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Sample,
    default_registry,
)

# NOTE: tracing's context manager is reached as `tracing.tracing(...)`
# — re-exporting the function here would shadow the submodule name.
from .tracing import TraceRecorder, span, start_tracing, stop_tracing

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Sample",
    "default_registry",
    "TraceRecorder",
    "span",
    "start_tracing",
    "stop_tracing",
    "metrics",
    "tracing",
    "manifest",
    "recorder",
    "anomaly",
    "aggregate",
    "AnomalyAbort",
    "AnomalySentinel",
    "FlightRecorder",
    "build_manifest",
    "write_manifest",
]
