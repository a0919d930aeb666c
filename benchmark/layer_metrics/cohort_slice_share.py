NAME = "cohort_slice_share"
UNIT = "%"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "status lines: health.cohort_steps and health.full_steps, window delta"


def read(ctx):
    from layer_metrics import window_delta

    cohort = window_delta(ctx, lambda s: s["health"]["cohort_steps"])
    full = window_delta(ctx, lambda s: s["health"]["full_steps"])
    if cohort is None or cohort + full == 0:
        return None
    return 100.0 * cohort / (cohort + full)
