NAME = "compiles_in_window"
UNIT = "count"
LAYER = "compile cache (utils/compile_cache.py)"
MOVES = "apply_lag_p95_ms"
READS = "status lines: compile.requests - compile.cache_hits, plus health.despecializations, from the first line inside the window to the done line (a compile the window's ops caused can end after it); must be 0"


def _count(s):
    return (s["compile"]["requests"] - s["compile"]["cache_hits"]
            + s["health"].get("despecializations", 0))


def read(ctx):
    inside = [s for t, s in ctx["parsed"] if ctx["w0"] <= t <= ctx["w1"]]
    last = ctx.get("final") or (inside[-1] if len(inside) > 1 else None)
    if not inside or last is None:
        return None
    return _count(last) - _count(inside[0])
