NAME = "compact_busy_share"
UNIT = "%"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'compact' spans (inside step: index and floor upload and the enqueue of the compaction of the acked documents, cohort, fleet-wide or lane) clipped to the window, over the window; nothing where the program has no such span"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "compact")
