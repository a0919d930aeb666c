NAME = "scatter_busy_share"
UNIT = "%"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'scatter' spans (the cohort path's enqueue of the scatter back into the fleet state) clipped to the window, over the window; nothing on the fleet-wide path"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "scatter")
