NAME = "readback_wait_share"
UNIT = "%"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'readback' spans clipped to the window, over the window"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "readback")
