NAME = "tree_compacts_in_window"
UNIT = "count"
LAYER = "engine (tree_batch_engine.py)"
MOVES = "apply_lag_p95_ms"
READS = "status lines: health.tree_compactions (fleet-wide tree_compact runs on the serving thread, counted since PR 28) from the first line inside the window to the done line; must be 0"


def read(ctx):
    inside = [s for t, s in ctx["parsed"] if ctx["w0"] <= t <= ctx["w1"]]
    last = ctx.get("final") or (inside[-1] if len(inside) > 1 else None)
    if not inside or last is None or (
            "tree_compactions" not in last["health"]):
        return None
    return (last["health"]["tree_compactions"]
            - inside[0]["health"]["tree_compactions"])
