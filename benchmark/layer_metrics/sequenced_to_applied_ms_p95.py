NAME = "sequenced_to_applied_ms_p95"
UNIT = "ms"
LAYER = "consumer + ingest (fleet_consumer, native/ingest.cpp)"
MOVES = "apply_lag_p95_ms"
READS = "status lines, window delta of op_clock.sequenced_to_applied (see sequenced_to_applied_ms_p50): the 95th percentile over the window's rows; absent where the status lines carry no op_clock"


def read(ctx):
    from layer_metrics import sequenced_to_applied_ms_p50 as oc

    return oc.percentile_ms(oc.stage_delta(ctx, "sequenced_to_applied"), 0.95)
