"""Apply lag from the stamps the status stream carries, and the arithmetic of
the end-to-end metrics.  No I/O, no clock: everything here is a function of
recorded stamps, so the self-test can check it on a synthetic stream.

``eng.step()`` returns only when everything staged is applied and the error
latch is read back.  At that moment the child stamps the step:
``[t_seen, t_applied, rows]`` (``t_seen``: when ``select`` reported the work,
``rows``: ops applied so far), and the next status line carries the stamps
taken since the line before it (``fleet_child.StampLog``).  A stamp with
``rows = R`` whose line ARRIVED proves R ops applied on the device at
``t_applied``: the line is the proof, the stamp is the time.  The lag of the
op that was the i-th sent is ``t_applied`` of the first such stamp with
``rows >= i`` minus the op's DUE time (open loop: a stall is charged to every
op that was due during it).  How often lines are printed, and where a line
falls among the steps, no longer enters.  Child and parent read one clock
(``CLOCK_MONOTONIC``); ``clock_is_shared`` is the check.  Ops are matched in
send order; a pump can read a later op's socket before an earlier one's, so a
single lag can be off by one loop of the fleet.
"""

from __future__ import annotations

import bisect
import math


def match_lags(groups, status, give_up_at: float):
    """``groups``: one entry per flush, in send order — ``(due, end_count,
    n_ops)`` where ``end_count`` is the cumulative count of ops sent once
    the flush returned.  ``status``: ``(time, rows)`` per proof, in order
    (``rows`` never decreases): the stamps of the lines that arrived (or, for
    the old reduction, the lines' own arrivals).  Returns ``(lags,
    unapplied)``: one lag in seconds per op, and how many ops no proof
    covered — their lag is counted up to ``give_up_at``."""
    rows = [r for _t, r in status]
    lags: list[float] = []
    unapplied = 0
    for due, end_count, n_ops in groups:
        i = bisect.bisect_left(rows, end_count)
        if i < len(rows):
            lag = status[i][0] - due
        else:
            lag = give_up_at - due
            unapplied += n_ops
        lags.extend([lag] * n_ops)
    return lags, unapplied


def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def applied_rate(status, t0: float, t1: float) -> float | None:
    """Ops applied on the device per second inside [t0, t1], over whole
    loops: rows at the last proof inside the window minus rows at the
    first, over the time between the two.  Cutting the window at its own
    edges instead would count a fleet-wide loop of seconds in or out by
    where the edge happens to fall (about a tenth of a 45 s window)."""
    inside = [(t, r) for t, r in status if t0 <= t <= t1]
    if len(inside) < 2 or inside[-1][0] <= inside[0][0]:
        return None
    return (inside[-1][1] - inside[0][1]) / (inside[-1][0] - inside[0][0])


class StampError(ValueError):
    """The status stream's stamps cannot be what they claim to be."""


def clock_is_shared(info) -> bool:
    """``info``: ``time.get_clock_info`` of the clock both processes stamp
    with.  Only ``CLOCK_MONOTONIC`` has one origin for every process of a
    machine; a per-process clock would make a stamp and an arrival
    incomparable."""
    return (info.implementation == "clock_gettime(CLOCK_MONOTONIC)"
            and info.monotonic)


def stamps_of(lines):
    """``lines``: ``(arrival, rows, applied, dropped)`` per status line that
    ARRIVED, in arrival order: the line's ``rows``, its ``applied`` list of
    ``[t_seen, t_applied, rows]`` (``None`` where the line has no such
    field) and its ``applied_dropped``.  Returns ``(t_applied, rows, t_seen)``
    per stamp, in order.  Raises ``StampError`` where the stream breaks its
    own contract: there is no falling back to arrival times."""
    out: list[tuple[float, int, float]] = []
    last_t, last_rows = float("-inf"), 0
    for arrival, rows, applied, dropped in lines:
        if applied is None:
            raise StampError(
                f"status line rows={rows} has no 'applied' field: the child "
                "does not stamp its steps")
        if dropped:
            raise StampError(f"the child dropped {dropped} stamps "
                             "(applied_dropped > 0)")
        for t_seen, t_applied, r in applied:
            if not (t_seen <= t_applied and last_t <= t_applied):
                raise StampError(
                    f"stamp rows={r}: seen {t_seen}, applied {t_applied} "
                    f"after {last_t}: not in order")
            if r <= last_rows:
                raise StampError(
                    f"stamp rows={r} does not advance rows={last_rows}")
            if t_applied > arrival:
                raise StampError(
                    f"stamp rows={r} applied at {t_applied}, later than its "
                    f"line's arrival {arrival}: not one clock")
            out.append((t_applied, r, t_seen))
            last_t, last_rows = t_applied, r
        if rows != last_rows:
            raise StampError(
                f"status line rows={rows}, its last stamp rows={last_rows}")
    return out


def seen_to_applied(stamps, t0: float, t1: float) -> list[float]:
    """For every stamp applied inside [t0, t1]: the seconds from the moment
    the fleet saw the work to the moment it was applied, one loop of the
    fleet (pump, step) without the wait for the work."""
    return [t - seen for t, _r, seen in stamps if t0 <= t <= t1]


def phase_in_period(times, period: float) -> float | None:
    """Circular mean of ``times`` modulo ``period``, in ms: where in a
    period of the generator's tick a run's status lines fell."""
    if not times:
        return None
    w = 2.0 * math.pi / period
    angle = math.atan2(sum(math.sin(w * t) for t in times),
                       sum(math.cos(w * t) for t in times))
    return (angle % (2.0 * math.pi)) / w * 1e3
