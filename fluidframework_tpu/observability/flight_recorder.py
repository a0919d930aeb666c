"""Flight recorder: a low-overhead fixed-size ring of trace events.

The serving path (ingest -> staging upload -> megastep dispatch ->
error-latch readback, plus scribe fold/summarize/ack, checkpoint writes,
and migration events) brackets its phases with ``span(name, **labels)``
and drops point events with ``instant(name, **labels)``.  While no
recorder is installed both are no-ops costing one module-global read —
the instrumentation can stay compiled into the hot path permanently.

Events live in a preallocated ring (old events overwrite, ``dropped``
counts what fell off) and export to Chrome trace-event JSON ("X" complete
events + "i" instants), which Perfetto and chrome://tracing load
directly.  Timestamps are ``time.perf_counter_ns()`` (monotonic, one
clock for every thread of the process), so span nesting is exact within a
thread and cross-thread ordering is meaningful within the process.  While a
recorder is installed every span is also a ``jax.profiler.TraceAnnotation``
of the same name and labels: a profiler session, whoever started it, then
holds the host spans on its own clock beside the device's events.

A ``RecompileWatchdog`` registers named jitted programs and polls their
executable-cache sizes (``_cache_size``): growth after the first dispatch
means a program shape de-specialized (new geometry, a de-specializing
megastep trace) and paid an XLA compile mid-run — each growth bumps a
counter and emits an instant event naming the program.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, NamedTuple


class TraceEvent(NamedTuple):
    name: str
    ph: str  # "X" complete span | "i" instant
    ts_ns: int  # perf_counter_ns at span START (or instant time)
    dur_ns: int  # 0 for instants
    tid: int
    args: dict[str, Any] | None


# ``jax.profiler.TraceAnnotation`` once a recorder is installed (``install``
# imports it, so this module still loads in a process without JAX): every
# span is then mirrored into whatever profiler session is running, and lands
# on the thread's line of the xplane's host plane, on the profiler's clock,
# beside the device planes.  With no session running an annotation costs
# one flag read.
_ANNOTATION = None


class _Span:
    """Context manager recording one complete ("X") event on exit, and the
    same span as a profiler annotation."""

    __slots__ = ("_rec", "_name", "_args", "_t0", "_note")

    def __init__(self, rec: "FlightRecorder", name: str, args) -> None:
        self._rec = rec
        self._name = name
        self._args = args

    def __enter__(self) -> "_Span":
        note = self._note = (
            _ANNOTATION(self._name, **(self._args or {}))
            if _ANNOTATION is not None else None
        )
        if note is not None:
            note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **labels: Any) -> None:
        """Add labels known only once the work is done (bytes a pump read)."""
        self._args = {**(self._args or {}), **labels}
        if self._note is not None:
            self._note.set_metadata(**labels)

    def __exit__(self, *exc) -> None:
        t0 = self._t0
        self._rec._push(TraceEvent(
            self._name, "X", t0, time.perf_counter_ns() - t0,
            threading.get_ident(), self._args,
        ))
        if self._note is not None:
            self._note.__exit__(*exc)


class _NullSpan:
    """Shared no-op span: what ``span()`` hands out with no recorder."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def set(self, **_labels: Any) -> None:
        pass

    def __exit__(self, *_exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class FlightRecorder:
    """Fixed-capacity trace-event ring with Chrome-trace export."""

    def __init__(self, capacity: int = 65536) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._buf: list[TraceEvent | None] = [None] * capacity
        self._n = 0  # total events ever pushed (ring cursor = _n % capacity)
        self._lock = threading.Lock()

    # ------------------------------------------------------------- recording
    def _push(self, ev: TraceEvent) -> None:
        # One lock round per event: events are recorded per *phase* (a few
        # per dispatch), never per op, so contention is negligible and the
        # ring stays consistent under the consumer/server threads.
        with self._lock:
            self._buf[self._n % self.capacity] = ev
            self._n += 1

    def span(self, name: str, **labels: Any) -> _Span:
        return _Span(self, name, labels or None)

    def instant(self, name: str, **labels: Any) -> None:
        t = time.perf_counter_ns()
        self._push(TraceEvent(
            name, "i", t, 0, threading.get_ident(), labels or None
        ))

    # --------------------------------------------------------------- reading
    @property
    def dropped(self) -> int:
        """Events that fell off the ring (overwritten by wraparound)."""
        return max(0, self._n - self.capacity)

    def __len__(self) -> int:
        return min(self._n, self.capacity)

    def events(self) -> list[TraceEvent]:
        """Retained events, oldest first (ring unrolled)."""
        with self._lock:
            n, cap = self._n, self.capacity
            if n <= cap:
                out = self._buf[:n]
            else:
                cut = n % cap
                out = self._buf[cut:] + self._buf[:cut]
        return list(out)  # type: ignore[arg-type]

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            self._n = 0

    # --------------------------------------------------------------- export
    def chrome_trace(self, pid: int = 1) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable).

        Span starts are recorded in ``perf_counter_ns``; Chrome wants
        microseconds.  Instants carry ``"s": "t"`` (thread scope)."""
        trace_events = []
        for ev in self.events():
            rec: dict[str, Any] = {
                "name": ev.name,
                "ph": ev.ph,
                "ts": ev.ts_ns / 1e3,
                "pid": pid,
                "tid": ev.tid,
            }
            if ev.ph == "X":
                rec["dur"] = ev.dur_ns / 1e3
            else:
                rec["s"] = "t"
            if ev.args:
                rec["args"] = dict(ev.args)
            trace_events.append(rec)
        return {"traceEvents": trace_events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str, pid: int = 1) -> int:
        """Write the Chrome trace JSON; returns the event count written."""
        trace = self.chrome_trace(pid=pid)
        with open(path, "w") as f:
            json.dump(trace, f)
            f.write("\n")
        return len(trace["traceEvents"])


# ---------------------------------------------------------------------------
# Module-global recorder: the instrumentation seam the serving path calls
# ---------------------------------------------------------------------------

_RECORDER: FlightRecorder | None = None


def install(rec: FlightRecorder | None = None) -> FlightRecorder:
    """Install (and return) the process-global recorder.  Instrumented
    code starts recording immediately; pass None to install a fresh
    default-capacity ring."""
    global _RECORDER, _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    _RECORDER = rec if rec is not None else FlightRecorder()
    return _RECORDER


def uninstall() -> FlightRecorder | None:
    """Remove the global recorder (returns it); spans become no-ops."""
    global _RECORDER
    rec, _RECORDER = _RECORDER, None
    return rec


def recorder() -> FlightRecorder | None:
    return _RECORDER


def span(name: str, **labels: Any):
    """A span against the global recorder; free no-op when none installed."""
    rec = _RECORDER
    if rec is None:
        return _NULL_SPAN
    return rec.span(name, **labels)


def instant(name: str, **labels: Any) -> None:
    rec = _RECORDER
    if rec is not None:
        rec.instant(name, **labels)


# ---------------------------------------------------------------------------
# Trace analysis (shared by bench phase_shares and the fftpu-trace CLI)
# ---------------------------------------------------------------------------

def phase_totals(events: list[TraceEvent]) -> dict[str, float]:
    """Total wall seconds per span name (nested spans each count their own
    full duration — shares are per-phase attribution, not a partition)."""
    totals: dict[str, float] = {}
    for ev in events:
        if ev.ph == "X":
            totals[ev.name] = totals.get(ev.name, 0.0) + ev.dur_ns / 1e9
    return totals


def phase_shares(events: list[TraceEvent]) -> dict[str, float]:
    """Per-phase share of the summed span time, rounded (bench artifact
    rows; the fftpu-trace CLI prints the same view)."""
    totals = phase_totals(events)
    grand = sum(totals.values())
    if grand <= 0:
        return {}
    return {
        name: round(t / grand, 4)
        for name, t in sorted(totals.items(), key=lambda kv: -kv[1])
    }


# ---------------------------------------------------------------------------
# Recompile watchdog
# ---------------------------------------------------------------------------

class RecompileWatchdog:
    """Count executable-cache growth of registered jitted programs.

    ``jax.jit`` (and the jit(shard_map) fleet programs) keep one compiled
    executable per input-shape signature; ``_cache_size()`` reads that
    cache's size without touching the dispatch path.  Growth after the
    program's warmup dispatch means a NEW shape specialized — a megastep
    trace de-specializing (obliterate gate flip at a new geometry, a fresh
    cohort ladder rung, a restart at different capacity) and paying a
    multi-second XLA compile mid-serve.  ``poll()`` is host-side and
    cheap (one int read per program); engines call it once per ``step``.

    One caveat follows from the design: the registered programs are
    module-level / lru-cached on purpose (engine instances SHARE compile
    caches), so cache growth is a process-wide fact — when several engines
    serve in one process, each polling watchdog reports compiles any of
    them triggered.  ``recompiles`` counts every cache miss (warmup
    included — a clean boot compiles each program once per shape);
    ``despecializations`` counts only growth AFTER a program had already
    specialized, which is the mid-serve alarm signal and the only growth
    that emits a ``recompile`` instant event.
    """

    def __init__(self) -> None:
        self._progs: dict[str, tuple[Any, int]] = {}
        self.recompiles = 0  # every cache miss seen (warmup included)
        self.despecializations = 0  # growth after first specialization
        self.per_program: dict[str, int] = {}

    def register(self, name: str, fn: Any) -> None:
        """Track ``fn`` (idempotent).  ``fn`` must expose ``_cache_size()``
        as ``jax.jit`` callables do — anything else raises here, because a
        program that silently went untracked would report zero recompiles
        forever (a dispatch plane whose programs never compile declares a
        constant one).  The baseline is the CURRENT cache size, so
        compiles that already happened (warmup, shared module-level
        caches) are not charged."""
        if name in self._progs:
            return
        self._progs[name] = (fn, int(fn._cache_size()))
        self.per_program.setdefault(name, 0)

    def poll(self) -> int:
        """Check every registered program; returns NEW compiles seen this
        call.  Each growth emits a ``recompile`` instant event."""
        grew = 0
        for name, (fn, last) in list(self._progs.items()):
            size = int(fn._cache_size())
            if size > last:
                delta = size - last
                grew += delta
                self.recompiles += delta
                self.per_program[name] = self.per_program.get(name, 0) + delta
                if last > 0:
                    # The program had already specialized: this growth is a
                    # mid-serve DE-specialization (new shape), not warmup.
                    self.despecializations += delta
                    instant(
                        "recompile", program=name, cache_size=size,
                        added=delta,
                    )
            self._progs[name] = (fn, size)
        return grew
