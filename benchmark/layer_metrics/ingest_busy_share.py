NAME = "ingest_busy_share"
UNIT = "%"
LAYER = "consumer + ingest (fleet_consumer, native/ingest.cpp)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'ingest' spans clipped to the window, over the window"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "ingest")
