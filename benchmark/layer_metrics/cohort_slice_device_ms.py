NAME = "cohort_slice_device_ms"
UNIT = "ms"
LAYER = "kernels (ops/mergetree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "xplane 'XLA Modules' line: device time of the whole cohort gather + step + scatter executions (those the trace's edges did not cut) over the slices they carried"


def read(ctx):
    import device_programs

    p = device_programs.split(ctx)
    if not p or not p["cohort"]["slices"]:
        return None
    return p["cohort"]["ns"] / p["cohort"]["slices"] / 1e6
