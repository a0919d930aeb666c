NAME = "tree_step_roofline"
UNIT = "%"
LAYER = "kernels (ops/tree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "tree_roofline.py (mean bytes a traced loop needed, from the configuration's geometry alone: the state of the documents that got an edit, in and out, their op rows and payload up) over 819 GB/s per chip, over the mean device time of a whole tree step execution in the trace; memory-bound side (no matrix unit involved)"


def read(ctx):
    import tree_roofline

    return tree_roofline.roofline_share(ctx)
