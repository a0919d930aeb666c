"""``fleet_main.next_status_due``: the status line's fixed-rate schedule.

The lag of an op is measured from the first status line that proves it
applied, so how often a loop gets a line, and at which moment, is part of
what the fleet is seen to do.  The loop checks once per iteration; these
cases walk that check over seeded loop lengths without a fleet.
"""

import pytest

from fluidframework_tpu.server.fleet_main import next_status_due

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
