NAME = "setup_trace_lower_s"
UNIT = "s"
LAYER = "compile cache (utils/compile_cache.py)"
MOVES = "setup_s"
READS = "status lines: compile.trace_seconds + compile.lower_seconds (JAX's jaxpr_trace_duration and jaxpr_to_mlir_module_duration, summed by CompileStats) at the first line inside the window: what the process spent tracing and lowering before the window"


def read(ctx):
    inside = [s for t, s in ctx["parsed"] if ctx["w0"] <= t <= ctx["w1"]]
    if not inside or "trace_seconds" not in inside[0].get("compile", {}):
        return None
    c = inside[0]["compile"]
    return c["trace_seconds"] + c["lower_seconds"]
