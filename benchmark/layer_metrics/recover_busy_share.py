NAME = "recover_busy_share"
UNIT = "%"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'recover' spans (recover()'s host part after the error readback: the walk over the error vector and the lanes) clipped to the window, over the window"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "recover")
