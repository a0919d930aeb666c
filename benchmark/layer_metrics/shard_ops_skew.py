NAME = "shard_ops_skew"
UNIT = "ratio"
LAYER = "mesh (parallel/mesh.py)"
MOVES = "apply_lag_p50_ms"
READS = "status lines, window delta of health.shard_ops (op rows packed for each shard of the mesh, host side): the busiest shard's over the idlest's; absent on one shard, or where a shard got no op.  A traced run's breakdown.mesh_window keeps the deltas themselves beside the rows applied between the same two lines (they have to add up to them)"


def window_health(ctx):
    """The first and the last status line inside the window, or None."""
    inside = [s for t, s in ctx["parsed"] if ctx["w0"] <= t <= ctx["w1"]]
    return (inside[0], inside[-1]) if len(inside) >= 2 else None


def per_shard_delta(ctx, key: str):
    """Window delta of the per-shard list ``health[key]``; None where the
    program has no such list."""
    ends = window_health(ctx)
    if ends is None or any(key not in s["health"] for s in ends):
        return None
    first, last = (s["health"][key] for s in ends)
    return [b - a for a, b in zip(first, last)]


def skew(delta):
    """max / min of a per-shard delta; None on one shard or where a shard's
    delta is 0 (no ratio to give)."""
    if not delta or len(delta) < 2 or min(delta) <= 0:
        return None
    return max(delta) / min(delta)


def read(ctx):
    delta = per_shard_delta(ctx, "shard_ops")
    breakdown = ctx.get("traced", {}).get("breakdown")
    if delta is not None and breakdown is not None:
        # run.py copies the breakdown into the line after the readers ran.
        first, last = window_health(ctx)
        breakdown["mesh_window"] = {
            "shard_ops": delta,
            "shard_row_slots_scanned": per_shard_delta(
                ctx, "shard_row_slots_scanned"),
            "rows_applied": last["rows"] - first["rows"],
        }
    return skew(delta)
