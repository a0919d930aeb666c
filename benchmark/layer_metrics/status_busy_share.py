NAME = "status_busy_share"
UNIT = "%"
LAYER = "consumer + ingest (fleet_consumer, native/ingest.cpp)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'status' spans (fleet_main's status line: the error-vector readback, health(), json.dumps and the write) clipped to the window, over the window"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "status")
