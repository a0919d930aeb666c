NAME = "lag_before_sequencer_ms_mean"
UNIT = "ms"
LAYER = "generator, front, sequencer (parent process)"
MOVES = "apply_lag_p50_ms"
READS = "the mean of the window's per-op lags (lag.match_lags over the flushes and the step stamps, as run.py's apply_lag_*: due -> applied) minus the mean of op_clock.sequenced_to_applied over the window's status lines (sequencer's stamp -> applied): what an op's lag holds BEFORE the sequencer stamps it (a late generator, the writers' edit, the submit); the two windows' edges differ by at most a tick; absent where an op is unmatched or the status lines carry no op_clock.  A traced run's breakdown.op_clock_window keeps the three stage means, the mean lag and the counters' deltas"


def read(ctx):
    import lag
    from layer_metrics import sequenced_to_applied_ms_p50 as oc

    deltas = {s: oc.stage_delta(ctx, s) for s in oc.STAGES}
    if deltas["sequenced_to_applied"] is None or not ctx["groups"]:
        return None
    groups = [(g[0], g[2], g[3]) for g in ctx["groups"]]
    lags, unapplied = lag.match_lags(groups, ctx["status"], float("inf"))
    if unapplied or not lags:
        return None
    lag_ms = 1e3 * sum(lags) / len(lags)
    breakdown = ctx.get("traced", {}).get("breakdown")
    if breakdown is not None:
        # run.py copies the breakdown into the line after the readers ran.
        breakdown["op_clock_window"] = {
            **{f"{s}_ms_mean": oc.mean_ms(d) for s, d in deltas.items()},
            "lag_ms_mean": lag_ms,
            **{c: oc.counter_delta(ctx, c) for c in oc.COUNTERS},
        }
    return lag_ms - oc.mean_ms(deltas["sequenced_to_applied"])
