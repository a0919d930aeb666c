NAME = "loop_ms_p50"
UNIT = "ms"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "status lines: arrival gaps before lines that advanced rows (grain: --status-every)"


def read(ctx):
    import lag

    gaps = lag.advancing_gaps(ctx["status"], ctx["w0"], ctx["w1"])
    return lag.percentile(gaps, 0.5) * 1e3 if gaps else None
