NAME = "traces_in_window"
UNIT = "count"
LAYER = "compile cache (utils/compile_cache.py)"
MOVES = "apply_lag_p95_ms"
READS = "status lines: compile.traces (functions JAX traced to a jaxpr) from the first line inside the window to the done line; must be 0: a new shape stalls the loop for its trace and lowering even when the executable is cached"


def read(ctx):
    inside = [s for t, s in ctx["parsed"] if ctx["w0"] <= t <= ctx["w1"]]
    last = ctx.get("final") or (inside[-1] if len(inside) > 1 else None)
    if not inside or last is None or "traces" not in inside[0].get(
            "compile", {}):
        return None
    return last["compile"]["traces"] - inside[0]["compile"]["traces"]
