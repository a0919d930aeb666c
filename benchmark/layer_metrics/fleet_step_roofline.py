NAME = "fleet_step_roofline"
UNIT = "%"
LAYER = "kernels (ops/mergetree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "roofline.py (mean bytes a traced loop needed: touched documents' state in and out, op rows up) over 819 GB/s per chip, over the mean device time of the whole fleet-wide executions in the trace"


def read(ctx):
    import device_programs
    import roofline

    p = device_programs.split(ctx)
    if not p or not p["fleet"]["executions"]:
        return None
    need = device_programs.bytes_needed_per_loop(ctx)
    if need is None:
        return None
    return roofline.memory_roofline_share(
        need, p["fleet"]["ns"] / p["fleet"]["executions"] / 1e9,
        ctx["ready"]["device_kind"], ctx["spec"]["cell"]["chips"])
