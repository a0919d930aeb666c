NAME = "pump_select_share"
UNIT = "%"
LAYER = "consumer + ingest (fleet_consumer, native/ingest.cpp)"
MOVES = "applied_ops_per_s"
READS = "flight recorder: 'pump.select' spans (the serving thread blocked in select with nothing to read; the wait of an open 'idle' span is counted there instead) clipped to the window, over the window"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "pump.select")
