NAME = "mesh_step_device_ms"
UNIT = "ms"
LAYER = "mesh (parallel/mesh.py)"
MOVES = "apply_lag_p50_ms"
READS = "xplane 'XLA Modules' line of the traced device (the first device plane: one shard of the mesh): device time of the whole executions of the shard_map megastep program (those the trace's edges did not cut) over the slices they carried (flight recorder dispatch spans give K); the reduction is fleet_slice_device_ms's, device_programs.split as it stands"


def read(ctx):
    from layer_metrics import fleet_slice_device_ms

    return fleet_slice_device_ms.read(ctx)
