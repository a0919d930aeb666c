NAME = "upload_busy_share"
UNIT = "%"
LAYER = "staging (models/staging.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'upload' spans clipped to the window, over the window"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "upload")
