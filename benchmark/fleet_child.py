"""The child that owns the chip: ``fleet_main.main(argv)`` unchanged.

    python benchmark/fleet_child.py [--profile-dir DIR --profile-seconds S] [--plant-fault SPEC] -- <fleet_main argv>

Three things are added around ``main`` and nothing inside it:

- every step that advanced ``rows`` is stamped the moment ``fc.step()``
  has returned (everything staged applied, the error latch read back: what a
  status line printed there would have proved), and every status line carries
  the stamps taken since the line before it as ``"applied": [[t_seen,
  t_applied, rows], ...]`` with ``"applied_dropped"`` beside it
  (``StampLog``, ``install_stamps``).  Apply lag is measured to the stamp and
  takes the line as its proof, so it no longer depends on how often lines are
  printed or where one falls among the steps (``lag.py``).  The stamps are
  taken here because a benchmark PR may not touch the program; when
  ``fleet_main`` stamps its own steps, ``install_stamps`` can go (PERF.md,
  section 7);
- after it returns, one more JSON line with the peak device memory
  (``fleet_main`` does not report it, and the result's ``device`` needs it);
- with ``--profile-dir`` (a traced run only), a side thread waits for the
  file ``DIR/go`` that the parent drops near the window's end, then runs
  ``jax.profiler.start_trace`` .. ``stop_trace`` for S seconds and writes
  ``DIR/clock.json`` with ``perf_counter_ns`` and the wall clock at both
  ends, so that flight-recorder spans (``perf_counter_ns``) can be laid over
  the device trace.  ``fleet_main`` has no profiler switch; when it gets one
  this wrapper can go (PERF.md, tracing list).

``--plant-fault alter_op:<rows>`` is the control of ``correct`` (``run.py
--plant-fault``; the self-test and the control runs on the chip, never a
result): once ``rows`` ops are staged, one character of the next insert fed to
the engine is altered, and a line ``{"planted": "alter_op", "doc": <index>}``
says in which document.  Every op is still applied exactly once and in order;
the device then holds a text no writer has, and the run has to read
``correct: false``.
"""

from __future__ import annotations

import json
import os
import re
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAMP_CAPACITY = 512    # a line a period empties it; the wide cell holds 1-2


class StampLog:
    """``[t_seen, t_applied, rows]`` per step that advanced ``rows``, on the
    clock the flight recorder uses (``perf_counter``: ``CLOCK_MONOTONIC``,
    which the parent reads too).  ``t_seen`` is the moment this iteration's
    ``select`` reported a ready socket; for an iteration that stepped on
    paused partitions alone, the iteration's start.  Bounded: past
    ``capacity`` a stamp is counted in ``dropped`` and not kept, and the
    benchmark fails the run."""

    def __init__(self, capacity: int = STAMP_CAPACITY,
                 clock=time.perf_counter) -> None:
        self.capacity, self.clock = capacity, clock
        self.entries: list[list] = []
        self.dropped = 0
        self.rows = 0
        self._t_iter = clock()
        self._t_seen: float | None = None

    def iteration(self) -> None:
        self._t_iter, self._t_seen = self.clock(), None

    def seen(self) -> None:
        self._t_seen = self.clock()

    def applied(self, rows: int) -> None:
        now = self.clock()
        if rows <= self.rows:
            return
        self.rows = rows
        if len(self.entries) >= self.capacity:
            self.dropped += 1
            return
        seen = self._t_seen if self._t_seen is not None else self._t_iter
        self.entries.append([seen, now, rows])

    def take(self) -> list[list]:
        out, self.entries = self.entries, []
        return out


def install_stamps(fleet_main, consumer_cls, log: StampLog) -> None:
    """Wrap what the stamps need of the serving loop: ``pump`` (an iteration
    starts), ``_drain_ready`` (``select`` reported work), the loop's
    ``next_status_due`` (asked once per iteration, right after ``fc.step()``
    has returned, with whether it stepped) and the status line's snapshot,
    which carries the stamps.  ``step`` itself is NOT wrapped: one Python
    frame between the loop and ``eng.step()`` made every trace of a new
    program shape under it half as slow again on the v5e's host (PERF.md,
    section 6, PR 27).  An attribute that is not there is an error here,
    never a silent fall-back."""
    pump, drain = consumer_cls.pump, consumer_cls._drain_ready
    due, snapshot = fleet_main.next_status_due, fleet_main.status_snapshot
    serving = None      # the consumer of the loop, once it has pumped

    def pump_(self, *args, **kwargs):
        nonlocal serving
        serving = self
        log.iteration()
        return pump(self, *args, **kwargs)

    def drain_(self, ready, sp):
        if ready:
            log.seen()
        return drain(self, ready, sp)

    def due_(due_at, now, every, stepped):
        if stepped:
            log.applied(serving.rows_staged)
        return due(due_at, now, every, stepped)

    def snapshot_(*args, **kwargs):
        snap = snapshot(*args, **kwargs)
        snap["applied"] = log.take()
        snap["applied_dropped"] = log.dropped
        return snap

    consumer_cls.pump, consumer_cls._drain_ready = pump_, drain_
    fleet_main.next_status_due = due_
    fleet_main.status_snapshot = snapshot_


_INSERTED = re.compile(rb'("seg":\s*")[a-j]')


def plant_fault(consumer_cls, spec: str) -> None:
    """The control: alter one answer where it is produced (see above)."""
    name, after = spec.split(":")
    if name != "alter_op":
        raise SystemExit(f"fleet_child: unknown fault {name}")
    after = int(after)
    init = consumer_cls.__init__

    def init_(self, *args, **kwargs):
        init(self, *args, **kwargs)
        ingest = self.engine.ingest_lines
        planted = False

        def ingest_(idx, data):
            nonlocal planted
            if not planted and self.rows_staged >= after:
                altered = _INSERTED.sub(rb"\1z", data, count=1)
                if altered != data:
                    planted, data = True, altered
                    print(json.dumps({"planted": name, "doc": idx}),
                          flush=True)
            return ingest(idx, data)

        self.engine.ingest_lines = ingest_

    consumer_cls.__init__ = init_


def _profile_when_asked(profile_dir: str, seconds: float,
                        stop: threading.Event) -> None:
    import jax

    go = os.path.join(profile_dir, "go")
    while not os.path.exists(go):
        if stop.wait(0.05):
            return
    clock = {"start_perf_ns": time.perf_counter_ns(),
             "start_wall_ns": time.time_ns()}
    # The device planes are what the reductions read; the Python tracer
    # would slow the serving thread and is most of a trace's bytes.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(profile_dir, profiler_options=options)
    clock["started_perf_ns"] = time.perf_counter_ns()
    stop.wait(seconds)
    clock["stop_perf_ns"] = time.perf_counter_ns()
    clock["stop_wall_ns"] = time.time_ns()
    jax.profiler.stop_trace()
    clock["stopped_perf_ns"] = time.perf_counter_ns()
    with open(os.path.join(profile_dir, "clock.json.tmp"), "w") as f:
        json.dump(clock, f)
    os.replace(os.path.join(profile_dir, "clock.json.tmp"),
               os.path.join(profile_dir, "clock.json"))


def main(argv: list[str]) -> int:
    cut = argv.index("--")
    own, fleet_argv = argv[:cut], argv[cut + 1:]
    profile_dir, seconds, fault = None, 5.0, None
    for i in range(0, len(own), 2):
        if own[i] == "--profile-dir":
            profile_dir = own[i + 1]
        elif own[i] == "--profile-seconds":
            seconds = float(own[i + 1])
        elif own[i] == "--plant-fault":
            fault = own[i + 1]
        else:
            raise SystemExit(f"fleet_child: unknown option {own[i]}")
    sys.path.insert(0, ROOT)
    from fluidframework_tpu.server import fleet_consumer, fleet_main

    install_stamps(fleet_main, fleet_consumer.FleetConsumer, StampLog())
    if fault is not None:
        plant_fault(fleet_consumer.FleetConsumer, fault)
    stop = threading.Event()
    prof = None
    if profile_dir is not None:
        prof = threading.Thread(
            target=_profile_when_asked, args=(profile_dir, seconds, stop),
            daemon=True)
        prof.start()
    try:
        rc = fleet_main.main(fleet_argv)
    finally:
        stop.set()
        if prof is not None:
            prof.join(timeout=280)
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [int(s.get("peak_bytes_in_use", 0)) for s in stats]
    print(json.dumps({"memory_peak_bytes": max(peaks),
                      "memory_peak_bytes_per_device": peaks,
                      "memory_stats_device0": stats[0]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
