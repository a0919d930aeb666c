NAME = "op_clock_coverage_share"
UNIT = "%"
LAYER = "consumer + ingest (fleet_consumer, native/ingest.cpp)"
MOVES = "apply_lag_p50_ms"
READS = "status lines, window delta: op_clock.rows (rows the program's clock resolved) over rows (rows applied); a check like loop_named_share, must read 100: rows left out as unstamped or dropped lower it; absent where clock_steps moved inside the window (the wall clock was stepped: the window's stamps are not on one clock) or where the status lines carry no op_clock"


def read(ctx):
    from layer_metrics import sequenced_to_applied_ms_p50 as oc

    ends = oc.window_clock(ctx)
    if ends is None or oc.counter_delta(ctx, "clock_steps"):
        return None
    rows = ends[1]["rows"] - ends[0]["rows"]
    if rows <= 0:
        return None
    return 100.0 * oc.counter_delta(ctx, "rows") / rows
