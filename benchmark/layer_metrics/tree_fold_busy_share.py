NAME = "tree_fold_busy_share"
UNIT = "%"
LAYER = "engine (tree_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "flight recorder: the host fold's spans before any row exists (host_fold_mark_alloc, host_fold_rebase, host_fold_compose, host_fold_translate; they follow each other inside 'ingest'), clipped to the window, over the window"

FOLD_SPANS = ("host_fold_mark_alloc", "host_fold_rebase",
              "host_fold_compose", "host_fold_translate")


def read(ctx):
    from layer_metrics import span_share

    shares = [span_share(ctx, s) for s in FOLD_SPANS]
    if all(s is None for s in shares):
        return None
    return sum(s for s in shares if s is not None)
