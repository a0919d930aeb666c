NAME = "compact_roofline"
UNIT = "%"
LAYER = "kernels (ops/mergetree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "compact_roofline.py (bytes the lanes of a traced compaction need, from the configuration's geometry alone: per-segment columns, obliterate table, nseg and min_seq, read and written once) over 819 GB/s per chip, over the mean device time of a whole cohort compaction in the trace; memory-bound side (no matrix unit involved)"


def read(ctx):
    import compact_roofline

    return compact_roofline.roofline_share(ctx)
