"""Per-layer metrics, one small reader per file, found by the metric's name in
BENCHMARK.json.  A reader declares NAME, UNIT, LAYER, MOVES and READS, and
``read(ctx)`` returns the value, or None where there is nothing to read (the
harness then leaves the metric out of the line).  ``ctx`` is what run.py
gathered: ``stamps`` (``(t_applied, rows, t_seen)`` per step whose status line
arrived), ``status`` (the same as ``(time, rows)``), ``parsed`` (the window's
status lines by arrival), ``groups`` (flushes),
``late``, ``w0``/``w1`` (the window, perf_counter seconds), ``ready``, and
``traced`` (traces.reduce_run: ``flight`` spans, ``modules``, ``busy_s`` ...).
"""


def window_delta(ctx, getter):
    """``getter(status dict)`` at the last status line inside the window
    minus at the first; None without two lines."""
    inside = [s for t, s in ctx["parsed"] if ctx["w0"] <= t <= ctx["w1"]]
    if len(inside) < 2:
        return None
    return getter(inside[-1]) - getter(inside[0])


def span_share(ctx, span: str):
    """Percent of the window inside the flight recorder's ``span`` spans."""
    import traces

    flight = ctx["traced"].get("flight")
    if not flight:
        return None
    return traces.busy_share(flight, span, ctx["w0"], ctx["w1"])
