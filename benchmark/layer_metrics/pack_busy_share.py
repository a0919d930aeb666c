NAME = "pack_busy_share"
UNIT = "%"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'pack' spans (_select_k, _drain_into and stage.mark of a fleet-wide or cohort step) clipped to the window, over the window"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "pack")
