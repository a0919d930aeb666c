NAME = "kernel_own_scope_share"
UNIT = "%"
LAYER = "kernels (ops/mergetree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "host_plane.py: device self time of the step programs (the denominator of kernel_*_share) whose kernel scope is the instruction's OWN op_name (the event's tf_op, else the HloProto's entry); 100 - this - kernel_unscoped_share is what kernel_*_share attributed by splitting a fusion's time by the count of what was fused into it. A check on the shares, not a cost"


def read(ctx):
    import host_plane

    return host_plane.kernel_own_scope_share(ctx)
