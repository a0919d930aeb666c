NAME = "tree_device_fraction"
UNIT = "%"
LAYER = "engine (tree_batch_engine.py)"
MOVES = "applied_ops_per_s"
READS = "status lines: health.device_fraction (commits applied on the device path over commits ingested, since start) at the last line inside the window; must be 100"


def read(ctx):
    inside = [s for t, s in ctx["parsed"] if ctx["w0"] <= t <= ctx["w1"]]
    if not inside or "device_fraction" not in inside[-1]["health"]:
        return None
    return 100.0 * inside[-1]["health"]["device_fraction"]
