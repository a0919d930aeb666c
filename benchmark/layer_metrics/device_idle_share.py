NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "applied_ops_per_s"
READS = "xplane: 1 - union of the intervals on each device's 'XLA Ops' line over the span from the first to the last device event of the trace (the profiler starts lazily, so the clock's span would count its start-up as idleness), mean over devices"


def read(ctx):
    t = ctx["traced"]
    if not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
