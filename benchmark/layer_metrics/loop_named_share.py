NAME = "loop_named_share"
UNIT = "%"
LAYER = "consumer + ingest (fleet_consumer, native/ingest.cpp)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: union of the serving thread's top-level spans (pump, step, status, idle) clipped to the window, over the part of the window in which the loop ran; a check: under 98 a phase of fleet_main's loop has no span"

def read(ctx):
    import host_plane
    import traces

    flight = ctx["traced"].get("flight")
    top = [(s0, s1) for n, s0, s1, _a in flight or ()
           if n in host_plane.TOP_SPANS]
    if not top:
        return None
    # The loop's lifetime inside the window: fleet_main leaves the loop
    # (and the recorder stops) once the last planned row is applied, which
    # at a low rate is well before the window's end.
    w0 = max(ctx["w0"], min(s0 for s0, _s1 in top))
    w1 = min(ctx["w1"], max(s1 for _s0, s1 in top))
    if w1 <= w0:
        return None
    covered, _merged = traces.union(
        (max(s0, w0), min(s1, w1)) for s0, s1 in top if s1 > w0 and s0 < w1)
    return 100.0 * covered / (w1 - w0)
