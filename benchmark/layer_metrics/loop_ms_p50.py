NAME = "loop_ms_p50"
UNIT = "ms"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "step stamps: select reported the work -> the step that applied it returned"


def read(ctx):
    import lag

    loops = lag.seen_to_applied(ctx["stamps"], ctx["w0"], ctx["w1"])
    return lag.percentile(loops, 0.5) * 1e3 if loops else None
