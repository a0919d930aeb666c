NAME = "tree_rebase_busy_share"
UNIT = "%"
LAYER = "engine (tree_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: 'host_fold_rebase' spans (EditManager.add_sequenced and advance_min_seq, one per sequenced edit), clipped to the window, over the window"


def read(ctx):
    from layer_metrics import span_share

    return span_share(ctx, "host_fold_rebase")
