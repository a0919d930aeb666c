NAME = "generator_late_p95_ms"
UNIT = "ms"
LAYER = "generator, front, sequencer (parent process)"
MOVES = "apply_lag_p50_ms"
READS = "the harness's own stamps: when a flush returned minus when its ops were due"


def read(ctx):
    import lag

    return lag.percentile(ctx["late"], 0.95) * 1e3 if ctx["late"] else None
