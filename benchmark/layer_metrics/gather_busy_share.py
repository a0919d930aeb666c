NAME = "gather_busy_share"
UNIT = "%"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'gather' spans (the cohort path's enqueue of _gather_cohort_jit: index upload and the asynchronous call, not the device's work) clipped to the window, over the window; nothing on the fleet-wide path"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "gather")
