NAME = "compact_device_ms"
UNIT = "ms"
LAYER = "kernels (ops/mergetree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "xplane 'XLA Modules' line: mean device time of the whole executions of the cohort compaction (module names that contain compact_cohort; the device's first and last event left out, as device_programs.classify does); nothing where no such module ran"


def read(ctx):
    import compact_roofline

    events = ctx["traced"].get("module_events")
    if not events:
        return None
    ns, n = compact_roofline.whole_executions(events)
    return ns / n / 1e6 if n else None
