NAME = "acks_in_window"
UNIT = "count"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p95_ms"
READS = "status lines, window delta: health.acks_seen (documents whose feed carried a summaryAck, one per feed): the cell does what its name says; absent where the program does not count it"


def read(ctx):
    from layer_metrics import window_delta

    if not ctx["parsed"] or "acks_seen" not in ctx["parsed"][0][1]["health"]:
        return None
    return window_delta(ctx, lambda s: s["health"]["acks_seen"])
