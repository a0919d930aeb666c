NAME = "sequenced_to_applied_ms_p50"
UNIT = "ms"
LAYER = "consumer + ingest (fleet_consumer, native/ingest.cpp)"
MOVES = "apply_lag_p50_ms"
READS = "status lines, window delta of op_clock.sequenced_to_applied (the program's own clock, PR 38: the sequencer's wire stamp of a feed's oldest line -> the engine's sync boundary after the step that applied it, one sample a feed weighted by its rows): the median, interpolated inside its bucket (buckets grow by 2**0.25); absent where the status lines carry no op_clock (the parent of PR 38)"

# The readers of op_clock share this file's helpers (README_op_clock.md).
STAGES = ("sequenced_to_received", "received_to_applied",
          "sequenced_to_applied")
COUNTERS = ("rows", "unstamped_rows", "dropped_rows", "clock_steps")


def window_clock(ctx):
    """``(first, last)``: the status lines inside the window that carry an
    ``op_clock``, first and last; None without two of them."""
    inside = [s for t, s in ctx["parsed"]
              if ctx["w0"] <= t <= ctx["w1"] and "op_clock" in s]
    return (inside[0], inside[-1]) if len(inside) >= 2 else None


def stage_delta(ctx, stage: str):
    """The window's own histogram of ``stage``: ``{"base", "growth",
    "count", "sum", "buckets": {index: count}}`` as last line minus first
    line; None where there is nothing to read or nothing was resolved."""
    ends = window_clock(ctx)
    if ends is None:
        return None
    first, last = (s["op_clock"][stage] for s in ends)
    count = last["count"] - first["count"]
    if count <= 0:
        return None
    buckets = {}
    for i, c in last["buckets"].items():
        d = c - first["buckets"].get(i, 0)
        if d:
            buckets[int(i)] = d
    return {"base": last["base"], "growth": last["growth"], "count": count,
            "sum": last["sum"] - first["sum"], "buckets": buckets}


def counter_delta(ctx, name: str):
    ends = window_clock(ctx)
    if ends is None:
        return None
    return ends[1]["op_clock"][name] - ends[0]["op_clock"][name]


def mean_ms(delta):
    return None if delta is None else 1e3 * delta["sum"] / delta["count"]


def percentile_ms(delta, q: float):
    """The q-quantile of a window's histogram in ms: bucket ``i`` covers
    ``(base * growth**(i-1), base * growth**i]`` (bucket 0: everything up to
    ``base``), and the value is interpolated linearly inside the bucket that
    holds the q-th row."""
    if delta is None:
        return None
    target = q * delta["count"]
    cum = 0
    for i in sorted(delta["buckets"]):
        c = delta["buckets"][i]
        if cum + c >= target:
            upper = delta["base"] * delta["growth"] ** i
            lower = upper / delta["growth"] if i > 0 else 0.0
            return 1e3 * (lower + (upper - lower) * (target - cum) / c)
        cum += c
    return None


def read(ctx):
    return percentile_ms(stage_delta(ctx, "sequenced_to_applied"), 0.5)
