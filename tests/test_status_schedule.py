"""``fleet_main.next_status_due``: the status line's fixed-rate schedule.

The lag of an op is measured from the first status line that proves it
applied, so how often a loop gets a line, and at which moment, is part of
what the fleet is seen to do.  The loop checks once per iteration; these
cases walk that check over seeded loop lengths without a fleet.  Below them,
since PR 38: the step stamps the program itself takes (``FleetConsumer``),
on the same streams.
"""

import os
import sys
import types

import pytest

from fluidframework_tpu.observability import OpClock
from fluidframework_tpu.server import fleet_consumer
from fluidframework_tpu.server.fleet_main import next_status_due

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

import lag  # noqa: E402

EVERY = 0.05


def _printed(iterations, every=EVERY, start=0.0):
    """The loop's own check (``fleet_main.main``), once per entry of
    ``iterations`` = (end time, stepped): the ends that print a line."""
    due = start + every
    out = []
    for now, stepped in iterations:
        nxt = next_status_due(due, now, every, stepped)
        if nxt is not None:
            due = nxt
            out.append(now)
    return out


def _loops(length, n, start=0.0, stepped=True):
    return [(start + length * (i + 1), stepped) for i in range(n)]


def _ticks(phase, n=200, tick=0.05):
    """A fleet faster than its traffic: every ``tick`` a burst arrives, a
    cohort step ends 25 ms and a straggler step 35 ms into the tick, and the
    loop looks in once more, idle, before the next burst."""
    out = []
    for i in range(n):
        t = phase + i * tick
        out += [(t + 0.025, True), (t + 0.035, True), (t + 0.0451, False)]
    return out


def _gaps(out):
    return [b - a for a, b in zip(out, out[1:])]


CASES = {
    # A loop shorter than the period: four of five loops are seen, never
    # every other one (the schedule this one replaces printed 50 of 100).
    "loop_41ms": (_loops(0.041, 100), lambda ends, out: (
        len(out) >= 80 and max(_gaps(out)) < 0.083)),
    # A loop longer than the period prints every time round.
    "loop_57ms": (_loops(0.057, 100), lambda ends, out: out == ends),
    # A loop of several periods too.
    "loop_4s": (_loops(4.06, 12), lambda ends, out: out == ends),
    # A stall of twenty periods is owed one line, then the grid goes on.
    "stall_1s": (
        _loops(0.001, 40) + _loops(0.001, 200, start=1.04),
        lambda ends, out: (
            [t for t in out if t < 1.045] == [1.041]
            and len([t for t in out if t > 1.045]) == 4)),
    # An idle fleet (1 ms sleeps) prints one line per period, each a period
    # after its due time.
    "idle": (_loops(0.001, 1000, stepped=False), lambda ends, out: (
        len(out) in (18, 19) and out[0] >= 2 * EVERY - 0.0015
        and all(abs(g - EVERY) < 0.0025 for g in _gaps(out)))),
    # An idle fleet that stalls a second is owed one line as well.
    "idle_stall_1s": (
        _loops(0.001, 40, stepped=False)
        + _loops(0.001, 100, start=1.04, stepped=False),
        lambda ends, out: [round(t, 2) for t in out] == [1.04, 1.09]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_status_schedule(case):
    iterations, holds = CASES[case]
    ends = [t for t, _stepped in iterations]
    out = _printed(iterations)
    assert holds(ends, out), (case, len(out), out[:8])


@pytest.mark.parametrize("phase_ms", [0, 7, 13, 21, 29, 38, 46])
def test_a_due_line_waits_for_the_step_that_makes_it_news(phase_ms):
    """Traffic and status grid share a period here (the benchmark's Zipf
    cell: 50 ms ticks, ``--status-every`` 0.05), so the grid point falls at
    the same place in every tick.  Wherever that is, the tick's line comes
    at the end of a step, never at an idle moment before the next burst, so
    what a burst's ops wait for their proof does not depend on the phase."""
    iterations = _ticks(phase_ms / 1000.0)
    stepped_at = {t for t, stepped in iterations if stepped}
    out = _printed(iterations)
    assert set(out) <= stepped_at
    assert len(out) >= 198                       # a line a tick
    assert max(_gaps(out)) < 0.0601


def test_next_due_is_on_the_grid_and_after_now():
    due = 10.0
    assert next_status_due(due, 9.99, EVERY, True) is None
    assert next_status_due(due, 10.04, EVERY, False) is None
    for now in (10.0, 10.01, 10.049, 10.05, 10.12, 11.0, 73.3):
        nxt = next_status_due(due, now, EVERY, True)
        assert nxt > now
        assert nxt - now <= EVERY + 1e-9
        steps = (nxt - due) / EVERY
        assert abs(steps - round(steps)) < 1e-6


def test_period_zero_prints_every_time_round():
    iterations = _loops(0.001, 30, stepped=False)
    assert _printed(iterations, every=0.0) == [t for t, _s in iterations]


# --------------------------------------------------------------------------
# The step stamps (PR 27's cases, until PR 38 only benchmark/selftest.py's,
# against the wrapper's log): here against the PROGRAM's own log, the one
# ``FleetConsumer`` keeps since PR 38 and ``fleet_main`` prints as
# ``applied``.  ``lag.stamps_of`` and ``lag.match_lags`` are the benchmark's.
# --------------------------------------------------------------------------
TICK, LINE_S = 0.05, 0.002
OPS_COHORT, OPS_STRAGGLERS = 30, 2
PHASES_MS = (0, 7, 13, 21, 29, 38, 46)
STEP_ENDS = ((0.025, 0.035), (0.011, 0.021))
_NO_SPAN = types.SimpleNamespace(set=lambda **_labels: None)


def _nothing_to_read(_n):
    raise BlockingIOError


# What ``select`` hands ``_drain_ready`` for one ready socket; the drain reads
# nothing from it (the test stages the rows itself).
_READY = [(types.SimpleNamespace(
    data=0, fileobj=types.SimpleNamespace(recv=_nothing_to_read)), 1)]


class _Loop:
    """A ``FleetConsumer`` with no socket, on a clock moved by hand, driven
    through the calls ``fleet_main``'s loop makes: ``pump`` (an iteration
    starts), ``_drain_ready`` (``select`` reported work), ``step``."""

    def __init__(self, monkeypatch):
        self.now = 0.0
        monkeypatch.setattr(
            fleet_consumer.time, "perf_counter", lambda: self.now)
        engine = types.SimpleNamespace(
            n_docs=1, _busy=set(), step=self._engine_step,
            counters=types.SimpleNamespace(
                get=lambda _name: 0, bump=lambda _name, _by=1: 0),
            op_clock=OpClock())
        self.fc = fleet_consumer.FleetConsumer("127.0.0.1", 0, engine, [])
        self.step_s = 0.0

    def _engine_step(self, in_flight=None) -> int:
        self.now += self.step_s
        return 1

    def iteration(self, at: float, ops: int, step_s: float) -> bool:
        """One turn of the loop starting at ``at``: ``ops`` rows arrive (0:
        none), the step takes ``step_s``.  Returns ``stepped``."""
        self.now = at
        assert self.fc.pump() == 0                 # no socket: reads nothing
        if ops:
            self.fc._drain_ready(_READY, _NO_SPAN)
            self.fc.rows_staged += ops
        if ops or step_s:
            self.step_s = step_s
            self.fc.step()
            return True
        return False

    def line(self):
        """A status line as ``lag.stamps_of`` takes it, LINE_S later."""
        return (self.now + LINE_S, self.fc.rows_staged,
                self.fc.take_applied(), self.fc.applied_dropped)


def _tick_stream(loop, phase: float, cohort: float, straggler: float,
                 n: int = 200):
    """The stream ``_ticks`` models with the program's own schedule AND its
    own stamp log: ``(groups, lines)`` as ``match_lags`` / ``stamps_of`` take
    them."""
    groups, lines, rows = [], [], 0
    due_line = -phase + EVERY
    for i in range(n):
        t = i * TICK
        last = 0.0
        for end, ops in ((cohort, OPS_COHORT), (straggler, OPS_STRAGGLERS),
                         (0.0451, 0)):
            if ops:
                rows += ops
                groups.append((t, rows, ops))
                stepped = loop.iteration(t + last, ops, end - last)
                last = end
            else:
                stepped = loop.iteration(t + end, 0, 0.0)
            nxt = next_status_due(due_line, loop.now, EVERY, stepped)
            if nxt is not None:
                due_line = nxt
                lines.append(loop.line())
    return groups, lines


def _p50_p95(groups, status):
    lags, unapplied = lag.match_lags(groups, status, give_up_at=1e3)
    return (round(lag.percentile(lags, 0.5), 9),
            round(lag.percentile(lags, 0.95), 9), unapplied)


def _by_stamp_and_by_line(loop, phase_ms, cohort, straggler):
    groups, lines = _tick_stream(loop, phase_ms / 1e3, cohort, straggler)
    groups = [g for g in groups if g[1] <= lines[-1][1]]
    by_stamp = [(t, r) for t, r, _s in lag.stamps_of(lines)]
    by_line = [(t, r) for t, r, _a, _d in lines]
    return _p50_p95(groups, by_stamp), _p50_p95(groups, by_line)


@pytest.mark.parametrize("ends", STEP_ENDS)
@pytest.mark.parametrize("phase_ms", PHASES_MS)
def test_lag_by_the_programs_stamps_is_the_steps_at_every_phase(
        monkeypatch, phase_ms, ends):
    cohort, straggler = ends
    by_stamp, _by_line = _by_stamp_and_by_line(
        _Loop(monkeypatch), phase_ms, cohort, straggler)
    # 30 of a tick's 32 ops end with the cohort step, 2 with the stragglers'.
    assert by_stamp == (round(cohort, 9), round(straggler, 9), 0), by_stamp


@pytest.mark.parametrize("ends", STEP_ENDS)
def test_lag_by_line_takes_two_levels_where_the_stamps_take_one(
        monkeypatch, ends):
    levels = {_by_stamp_and_by_line(_Loop(monkeypatch), p, *ends)[1]
              for p in PHASES_MS}
    assert len(levels) == 2, levels
    cohort, straggler = ends
    assert {round(v[0] - LINE_S, 6) for v in levels} == {
        round(cohort, 6), round(straggler, 6)}, levels


def test_a_stamp_whose_line_did_not_arrive_proves_nothing(monkeypatch):
    groups, lines = _tick_stream(_Loop(monkeypatch), 0.013, 0.025, 0.035, n=20)
    arrived = lines[:-3]
    status = [(t, r) for t, r, _s in lag.stamps_of(arrived)]
    lags, unapplied = lag.match_lags(groups, status, give_up_at=61.0)
    covered = arrived[-1][1]
    assert unapplied == groups[-1][1] - covered > 0
    assert sum(1 for x in lags if x > 50.0) == unapplied


def test_the_programs_log_on_the_loop(monkeypatch):
    """A line's stamps are in order and advance ``rows``, the last is the
    line's ``rows``, ``t_seen`` is where ``select`` reported the work (the
    iteration's start for a step on paused partitions or an ack alone), the
    log empties per line and stays bounded."""
    monkeypatch.setattr(fleet_consumer, "APPLIED_CAPACITY", 4)
    loop = _Loop(monkeypatch)
    lines, t = [], 100.0
    for burst in ([3, 1, 0], [0], [2, 0, 5], [1] * 6):
        for ops in burst:
            # [0] alone: paused partitions, a step that stages nothing.
            loop.iteration(t, ops, 0.010 if ops or burst == [0] else 0.0)
            t = loop.now + 0.001
        lines.append(loop.line())
        t += LINE_S
    assert [len(a) for _t, _r, a, _d in lines] == [2, 0, 2, 4]
    assert [d for _t, _r, _a, d in lines] == [0, 0, 0, 2]
    assert not loop.fc.applied                     # emptied by every line
    stamps = lag.stamps_of(lines[:3])
    assert [r for _t, r, _s in stamps] == [3, 4, 6, 11]
    assert all(abs((t - seen) - 0.010) < 1e-9 for t, _r, seen in stamps)
    with pytest.raises(lag.StampError, match="dropped"):
        lag.stamps_of(lines)
    # A step on an ack alone (nothing staged since the last stamp) leaves no
    # stamp; one that applies rows staged by an earlier pump is seen at its
    # own iteration's start.
    loop.iteration(t, 0, 0.004)
    assert loop.fc.take_applied() == []
    loop.fc.rows_staged += 2
    loop.iteration(t + 1.0, 0, 0.004)
    ((seen, applied, rows),) = loop.fc.take_applied()
    assert (seen, rows) == (t + 1.0, 19) and abs(applied - seen - 0.004) < 1e-9


@pytest.mark.parametrize("case", [
    "no_applied_field", "stamp_later_than_its_line",
    "last_stamp_is_not_the_lines_rows", "rows_without_a_stamp",
    "stamps_dropped", "rows_do_not_advance", "time_runs_backwards",
    "applied_before_seen"])
def test_a_broken_stream_of_the_programs_stamps_is_refused(monkeypatch, case):
    loop = _Loop(monkeypatch)
    loop.iteration(1.00, 1, 0.02)
    loop.iteration(1.03, 2, 0.07)
    arrival, rows, good, dropped = loop.line()
    assert (rows, dropped) == (3, 0) and [s[2] for s in good] == [1, 3]
    assert lag.stamps_of([(arrival, rows, good, dropped)])      # sound
    lines = {
        "no_applied_field": [(arrival, 3, None, None)],
        "stamp_later_than_its_line": [(1.05, 3, good, 0)],
        "last_stamp_is_not_the_lines_rows": [(arrival, 4, good, 0)],
        "rows_without_a_stamp": [(arrival, 3, good, 0), (1.3, 5, [], 0)],
        "stamps_dropped": [(arrival, 3, good, 2)],
        "rows_do_not_advance": [
            (arrival + 1, 3, [good[1], [1.11, 1.12, 3]], 0)],
        "time_runs_backwards": [(arrival, 3, [good[1], [0.9, 1.0, 4]], 0)],
        "applied_before_seen": [(arrival, 1, [[1.02, 1.00, 1]], 0)],
    }[case]
    with pytest.raises(lag.StampError):
        lag.stamps_of(lines)
