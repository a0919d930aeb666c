NAME = "mesh_step_roofline"
UNIT = "%"
LAYER = "mesh (parallel/mesh.py)"
MOVES = "apply_lag_p50_ms"
READS = "roofline.py (mean bytes a traced loop NEEDED over the whole fleet: touched documents' state in and out, op rows up) over 819 GB/s x the cell's chips, over the mean device time of a whole execution of the shard_map megastep program on the traced device; the reduction is fleet_step_roofline's, which already divides by the cell's chips"


def read(ctx):
    from layer_metrics import fleet_step_roofline

    return fleet_step_roofline.read(ctx)
