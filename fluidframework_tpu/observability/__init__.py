"""Observability plane: flight recorder, recompile watchdog, metrics plane.

Four coordinated pieces (ISSUE 7; the op clock: ISSUE 38), all host-side
and all off the device hot path:

- ``flight_recorder``: a fixed-size ring buffer of trace events
  (begin/end/instant, monotonic ns timestamps, thread + label args) with a
  Chrome trace-event JSON exporter (Perfetto-loadable), plus the module
  globals ``install``/``recorder``/``span``/``instant`` the serving path
  calls — every call is a no-op costing one global read while no recorder
  is installed.
- ``RecompileWatchdog`` (in ``flight_recorder``): counts jit/shard_map
  executable-cache growth per registered program and emits an instant
  event when a fleet trace de-specializes mid-run.
- ``op_clock``: the op's own clock (ISSUE 38): the sequencer's wire stamp
  -> received -> applied on the device, one sample a feed weighted by its
  rows, as three mergeable histograms whose means add up; every status line
  carries it.
- ``metrics_plane``: Prometheus-text ``/metrics`` + JSON ``/status``
  rendering and a tiny HTTP server, aggregating any number of registered
  sources (engine health, histograms, staging gauges, scribe state,
  ordered-log depths).
"""

from .flight_recorder import (
    FlightRecorder,
    RecompileWatchdog,
    TraceEvent,
    install,
    instant,
    phase_totals,
    recorder,
    span,
    uninstall,
)
from .op_clock import OpClock
from .metrics_plane import (
    MetricsPlane,
    MetricsServer,
    parse_prometheus,
    render_prometheus,
)

__all__ = [
    "FlightRecorder",
    "MetricsPlane",
    "MetricsServer",
    "OpClock",
    "RecompileWatchdog",
    "TraceEvent",
    "install",
    "instant",
    "parse_prometheus",
    "phase_totals",
    "recorder",
    "render_prometheus",
    "span",
    "uninstall",
]
