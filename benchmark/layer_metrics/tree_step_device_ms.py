NAME = "tree_step_device_ms"
UNIT = "ms"
LAYER = "kernels (ops/tree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "xplane 'XLA Modules' line: mean device time of the whole executions of the tree step programs (jit_apply_nested_fleet at K = 1, jit_apply_nested_megastep above; the device's first and last event left out, as device_programs.classify does). Also lays that time out by the kernel's named scopes (tree_scopes.py) as breakdown.tree_step_scopes: the harness copies traced['breakdown'] into the line after the readers ran, and a model_config PR edits no harness file"


def read(ctx):
    import tree_roofline
    import tree_scopes

    events = ctx["traced"].get("module_events")
    if not events:
        return None
    ns, n = tree_roofline.whole_steps(events)
    if not n:
        return None
    scopes = tree_scopes.breakdown_of(ctx)
    if scopes and "breakdown" in ctx["traced"]:
        ctx["traced"]["breakdown"]["tree_step_scopes"] = scopes[:12]
    return ns / n / 1e6
