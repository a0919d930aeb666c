NAME = "kernel_remove_share"
UNIT = "%"
LAYER = "kernels (ops/mergetree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "xplane 'XLA Ops' events' metadata stat tf_op (host_plane.py): device self time under the apply_op branch scope 'remove' (its helpers ensure_boundary, open_slot, mark_range included), over the self time of all ops in whole executions of the step programs inside the traced span; the six kernel_*_share add to 100"


def read(ctx):
    import host_plane

    return host_plane.kernel_share(ctx, "remove")
