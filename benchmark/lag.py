"""Apply lag from the status stream, and the arithmetic of the end-to-end
metrics.  No I/O, no clock: everything here is a function of recorded stamps,
so the self-test can check it on a synthetic stream.

``eng.step()`` returns only when everything staged is applied and the error
latch is read back, and ``fleet_main`` prints its status line after it, so a
line with ``rows = R`` proves R ops applied on the device.  The lag of the op
that was the i-th sent is the arrival time of the first status line with
``rows >= i`` minus the op's DUE time (open loop: a stall is charged to every
op that was due during it).  Ops are matched in send order; a pump can read a
later op's socket before an earlier one's, so a single lag can be off by one
loop of the fleet.
"""

from __future__ import annotations

import bisect


def match_lags(groups, status, give_up_at: float):
    """``groups``: one entry per flush, in send order — ``(due, end_count,
    n_ops)`` where ``end_count`` is the cumulative count of ops sent once
    the flush returned.  ``status``: ``(arrival, rows)`` per status line, in
    arrival order (``rows`` never decreases).  Returns ``(lags, unapplied)``:
    one lag in seconds per op, and how many ops no status line covered —
    their lag is counted up to ``give_up_at``."""
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
    loops: rows at the last status line inside the window minus rows at the
    first, over the time between the two.  Cutting the window at its own
    edges instead would count a fleet-wide loop of seconds in or out by
    where the edge happens to fall (about a tenth of a 45 s window)."""
    inside = [(t, r) for t, r in status if t0 <= t <= t1]
    if len(inside) < 2 or inside[-1][0] <= inside[0][0]:
        return None
    return (inside[-1][1] - inside[0][1]) / (inside[-1][0] - inside[0][0])


def advancing_gaps(status, t0: float, t1: float) -> list[float]:
    """For every status line inside [t0, t1] that advanced ``rows``, the
    seconds since the status line before it: the loop of the fleet (pump,
    step, status) that applied that work.  ``fleet_main`` prints at most one
    line per ``--status-every``, so that is the grain."""
    gaps = []
    for (pt, pr), (t, r) in zip(status, status[1:]):
        if t0 <= pt and t <= t1 and r > pr:
            gaps.append(t - pt)
    return gaps
