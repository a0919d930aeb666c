NAME = "device_idle_in_status_share"
UNIT = "%"
LAYER = "device"
MOVES = "applied_ops_per_s"
READS = "xplane: the first device's idle gaps (host_plane.py) that fall inside the serving thread's 'status' annotations on the /host:CPU plane, both on the profiler's clock, over the span from the first to the last device event"


def read(ctx):
    import host_plane

    return host_plane.device_idle_inside(ctx, "status")
