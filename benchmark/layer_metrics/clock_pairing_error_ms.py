NAME = "clock_pairing_error_ms"
UNIT = "ms"
LAYER = "device"
MOVES = "applied_ops_per_s"
READS = "xplane 'readback' annotations against the same spans of the flight recorder: median of |annotation start laid on perf_counter the way traces.reduce_run lays device events (the last device event at clock.json's stop stamp) - the span's start|; a check on the labels of breakdown.idle_gaps"


def read(ctx):
    import host_plane
    import lag

    hp = host_plane.of(ctx)
    t = ctx["traced"]
    if (not hp or not hp["host"] or not hp["device"] or "clock" not in t
            or not t.get("flight")):
        return None
    notes = sorted(s for s, _d in hp["host"]["spans"].get("readback", ()))
    spans = sorted(s0 for n, s0, _s1, _a in t["flight"] if n == "readback")
    # Profiler clock -> perf_counter seconds, as traces.reduce_run does it.
    off = t["clock"]["stop_perf_ns"] - hp["device"]["last_ns"]
    laid = [(s + off) / 1e9 for s in notes]
    at = host_plane.align(laid, spans)
    if at is None:
        return None
    return 1e3 * lag.percentile(
        [abs(a - b) for a, b in zip(laid, spans[at:])], 0.5)
