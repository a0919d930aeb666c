NAME = "shard_depth_skew"
UNIT = "ratio"
LAYER = "mesh (parallel/mesh.py)"
MOVES = "apply_lag_p50_ms"
READS = "status lines, window delta of health.shard_row_slots_scanned (per shard, the deepest take among its documents summed over the slices packed: where that shard's own row loop ended): the deepest shard's over the shallowest's; absent where the program does not count it (the parent of PR 32)"


def read(ctx):
    from layer_metrics import shard_ops_skew

    return shard_ops_skew.skew(shard_ops_skew.per_shard_delta(
        ctx, "shard_row_slots_scanned"))
