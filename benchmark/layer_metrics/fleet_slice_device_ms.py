NAME = "fleet_slice_device_ms"
UNIT = "ms"
LAYER = "kernels (ops/mergetree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "xplane 'XLA Modules' line: device time of the whole fleet-wide step/megastep executions (those the trace's edges did not cut) over the slices they carried (flight recorder dispatch spans give K)"


def read(ctx):
    import device_programs

    p = device_programs.split(ctx)
    if not p or not p["fleet"]["slices"]:
        return None
    return p["fleet"]["ns"] / p["fleet"]["slices"] / 1e6
