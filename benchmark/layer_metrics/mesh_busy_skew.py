NAME = "mesh_busy_skew"
UNIT = "ratio"
LAYER = "mesh (parallel/mesh.py)"
MOVES = "applied_ops_per_s"
READS = "xplane: busy_ns (union of the 'XLA Ops' intervals) of every device plane of the trace, the busiest device's over the idlest's; 1.0 where every shard works as long as the others, absent with fewer than two devices.  A traced run's breakdown.mesh_devices keeps each device's busy seconds and the child's memory_peak_bytes_per_device"


def read(ctx):
    traced = ctx["traced"]
    devices = (traced.get("device_summary") or {}).get("devices") or []
    busy = [d["busy_ns"] for d in devices]
    if len(busy) < 2 or min(busy) <= 0:
        return None
    if traced.get("breakdown") is not None:
        # run.py copies the breakdown into the line after the readers ran.
        traced["breakdown"]["mesh_devices"] = {
            "busy_s": [ns / 1e9 for ns in busy],
            "memory_peak_bytes": ctx.get("tail", {}).get(
                "memory_peak_bytes_per_device"),
        }
    return max(busy) / min(busy)
