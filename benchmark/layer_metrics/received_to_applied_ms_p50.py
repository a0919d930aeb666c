NAME = "received_to_applied_ms_p50"
UNIT = "ms"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "status lines, window delta of op_clock.received_to_applied (a feed handed to ingest_lines -> the engine's sync boundary after the step that applied it: the rest of the pump, the step and its readback): the median over the window's rows; absent where the status lines carry no op_clock"


def read(ctx):
    from layer_metrics import sequenced_to_applied_ms_p50 as oc

    return oc.percentile_ms(oc.stage_delta(ctx, "received_to_applied"), 0.5)
