"""One run of one cell of the benchmark (BENCHMARK.json at the repo's root).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent (this process) never imports JAX.  It starts the real front and
sequencer with in-process writers (the family's plant, ``plants/<module>.py``,
named by the configuration), and the device tier as a child that owns the
chip: ``benchmark/fleet_child.py``, which calls
``fluidframework_tpu.server.fleet_main.main`` unchanged.

1. Set-up (``setup_s``: start of this process to the first op of the window):
   join writers, wait for readiness, a ladder of gated bursts over the
   programs this cell's traffic reaches, then ``warm_seconds`` of the cell's
   own traffic, which runs straight on into the window.
2. Window (``--seconds``): open loop.  Every op has a due time from the seeded
   schedule and is sent when due, whether or not earlier ones were applied.
3. After the window (untimed): send nothing, wait for the child's ``done``
   line, compare device texts, writers and the host oracle.

Apply lag is measured to the stamp the child takes when the step that applied
an op returns, and takes the status line that carried the stamp as its proof
(``lag.py``); the same runs reduced by the lines' arrival times are kept under
the result's ``bench`` key, as evidence and never as a metric.

The last line of stdout is the result; README.md has the layout and PERF.md
the reasons.  ``--rehearse-cpu`` is the toy-size CPU rehearsal (platform
``cpu``, never a result); ``--sweep`` cuts the window into one segment per
rate for a knee sweep; ``--report`` writes everything the run learned to a
file.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import resource
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import lag as lagmod  # noqa: E402
from child import BenchFailure, Child, Gate, status_rows  # noqa: E402

STATUS_EVERY_S = 0.05
DRAIN_DEADLINE_S = 60.0
SETUP_DEADLINE_S = 1100.0
VERIFY_BUDGET_S = 20.0
VERIFY_MIN_SAMPLE = 512
TRACE_CAPACITY = 1 << 21
TRACE_LEAD_S = 0.5      # start_trace returns in 0.06 s; the device follows
WORK_DIR = os.path.join(HERE, ".work")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell, its configuration, its traffic mix and the cell's own
    parameters, each from the file its name in BENCHMARK.json leads to."""
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    own_path = os.path.join(HERE, "cells", name + ".json")
    own = _load(own_path) if os.path.exists(own_path) else {}
    params = {**traffic["params"], **own.get("params", {})}
    if "rate_ops_per_s" not in own.get("params", {}):
        raise BenchFailure(
            f"{own_path} has no params.rate_ops_per_s: a cell fixes its rate")

    def metrics(kind: str) -> list[dict]:
        return [m for m in bench[kind]
                if "workloads" not in m or name in m["workloads"]]

    return {"cell": cell, "config": config, "traffic": traffic, "own": own,
            "params": params, "end_to_end": metrics("end_to_end"),
            "per_layer": metrics("per_layer"), "bench": bench}


class Stream:
    """The seeded open-loop schedule, sent tick by tick.  One entry of
    ``groups`` per flush: (due, sent_at, count of ops sent so far, ops)."""

    def __init__(self, plant, tick_s: float) -> None:
        self.plant = plant
        self.tick_s = tick_s
        self.groups: list[tuple[float, float, int, int, int]] = []

    def send(self, ticks, docs, t0: float) -> None:
        """Send ops (tick index, doc index per op, sorted by tick) against
        the clock: tick k is due at ``t0 + (k + 1) * tick_s``."""
        plant = self.plant
        i, n = 0, len(ticks)
        while i < n:
            k = ticks[i]
            j = i
            per_doc: dict[int, int] = {}
            while j < n and ticks[j] == k:
                per_doc[docs[j]] = per_doc.get(docs[j], 0) + 1
                j += 1
            due = t0 + (k + 1) * self.tick_s
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            for d, m in per_doc.items():
                doc_id = plant.doc_ids[d]
                for _ in range(m):
                    plant.edit(doc_id)
                plant.flush(doc_id)
                self.groups.append(
                    (due, time.perf_counter(), plant.ops, m, d))
            i = j


def _rlimit(n_docs: int) -> None:
    # One firehose socket per document on each side of the front.
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    need = n_docs + 512
    if soft < need:
        if hard < need:
            raise BenchFailure(f"RLIMIT_NOFILE {hard} < {need} sockets")
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))


def run(args) -> tuple[int, dict | None]:
    t_start = time.perf_counter()
    # The child stamps with perf_counter too; a stamp and an arrival compare
    # only where that is one clock for every process of the machine.
    clock = time.get_clock_info("perf_counter")
    if not lagmod.clock_is_shared(clock):
        raise BenchFailure(
            f"perf_counter is {clock.implementation}, not CLOCK_MONOTONIC: "
            "the child's stamps and this process's clock do not compare")
    spec = load_cell(args.workload)
    cell, config, params = spec["cell"], spec["config"], dict(spec["params"])
    own = spec["own"]
    # Fail here, before any output, where the repo is not beside this file.
    import fluidframework_tpu.server.netserver  # noqa: F401

    gen = importlib.import_module(
        "generators." + spec["traffic"]["generator"])
    plant_mod = importlib.import_module(
        "plants." + config["plant"]["module"])
    n_docs = int(config["docs"])
    flags = list(config["fleet_main_flags"])
    scale = 1.0
    env = dict(os.environ)
    if args.rehearse_cpu:
        toy = config["rehearsal"]
        scale = toy["docs"] / n_docs
        n_docs = int(toy["docs"])
        flags = list(toy["fleet_main_flags"])
        env["JAX_PLATFORMS"] = "cpu"
        if config["mesh"]:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={config['mesh']}")
    params["rate_ops_per_s"] = float(params["rate_ops_per_s"]) * scale
    if config["mesh"]:
        flags += ["--mesh", str(config["mesh"])]
    tick_s = float(params["tick_s"])
    warm_s = float(own.get("warm_seconds", 5.0))
    ladder = [
        {"docs": max(1, int(round(b["docs"] * scale))),
         "depth": int(b.get("depth", 1))}
        for b in own.get("ladder", [])
    ]
    _rlimit(n_docs)

    # The whole plan is fixed by the seed before the child starts, so the
    # child can be told how many rows to apply before it exits.
    # A knee sweep is the same run with the window cut into segments of
    # --seconds each, one per rate, in the order given.
    seg_rates = ([r * scale for r in args.sweep] if args.sweep
                 else [params["rate_ops_per_s"]])
    seg_params = [{**params, "rate_ops_per_s": r} for r in seg_rates]
    window_s = float(args.seconds) * len(seg_rates)
    rates = gen.doc_rates(
        max(seg_params, key=lambda sp: sp["rate_ops_per_s"]), n_docs,
        args.seed)
    warm_ticks, warm_docs = gen.schedule(
        seg_params[0], n_docs, warm_s, args.seed, 1)
    warm_n_ticks = int(round(warm_s / tick_s))
    seg_n_ticks = int(round(float(args.seconds) / tick_s))
    ticks = [int(k) for k in warm_ticks]
    docs = [int(d) for d in warm_docs]
    for i, sp in enumerate(seg_params):
        tk, dc = gen.schedule(sp, n_docs, float(args.seconds), args.seed,
                              2 + i)
        ticks += [int(k) + warm_n_ticks + i * seg_n_ticks for k in tk]
        docs += [int(d) for d in dc]
    planned_win = len(ticks) - len(warm_ticks)
    # Ladder bursts go to the coldest documents, one op each per unit of
    # depth; which those are is fixed by the seed (through the rates).
    cold_first = sorted(range(n_docs), key=lambda d: (rates[d], d))
    planned = len(ticks) + sum(b["docs"] * b["depth"] for b in ladder)
    w = config["writers"]
    n_writers = [w["hot"] if r >= w["hot_threshold_ops_per_s"] else w["rest"]
                 for r in rates]
    touched = sorted(
        set(docs) | {d for b in ladder for d in cold_first[:b["docs"]]})

    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    plant = plant_mod.Plant(args.seed, n_docs,
                            config["plant"].get("params", {}))
    child = None
    try:
        cmd = [sys.executable, os.path.join(HERE, "fleet_child.py")]
        profile_dir = os.path.join(WORK_DIR, "profile")
        fr_path = os.path.join(WORK_DIR, "flight.json")
        trace_s = float(own.get("trace_seconds", 5.0))
        if args.trace:
            os.makedirs(profile_dir)
            cmd += ["--profile-dir", profile_dir,
                    "--profile-seconds", str(trace_s)]
        if args.plant_fault:
            # The control: one op altered half way through the window, where
            # the child feeds it to the engine.
            cmd += ["--plant-fault",
                    f"{args.plant_fault}:{planned - planned_win // 2}"]
        cmd += ["--", "--port", str(plant.port),
                "--docs", ",".join(plant.doc_ids),
                "--exit-after-rows", str(planned),
                "--status-every", str(STATUS_EVERY_S), *flags]
        if args.trace:
            cmd += ["--trace", fr_path, "--trace-capacity",
                    str(TRACE_CAPACITY)]
        child = Child("fleet", cmd, env, WORK_DIR, ROOT)
        # Writers join while the child initialises the chip; whichever side
        # is first, the firehose delivers the joins (catch-up or live).
        for d in touched:
            plant.join(plant.doc_ids[d], n_writers[d])
        t_joined = time.perf_counter()
        deadline = t_start + SETUP_DEADLINE_S
        t_ready, ready = child.wait_for(
            lambda s: s.get("ready"), deadline, "readiness")
        say(f"ready in {t_ready - t_start:.1f}s on {ready['platform']} "
            f"{ready['device_kind']} x{ready['device_count']}")
        want_platform = "cpu" if args.rehearse_cpu else "tpu"
        if ready["platform"] != want_platform:
            raise BenchFailure(
                f"the fleet runs on {ready['platform']!r}; this benchmark "
                f"measures a TPU (--rehearse-cpu is the toy rehearsal)")
        if ready["device_count"] < cell["chips"]:
            raise BenchFailure(
                f"{ready['device_count']} devices, the cell needs "
                f"{cell['chips']}")

        gate = Gate(child, plant)
        burst_s = []
        for b in ladder:
            ids = [plant.doc_ids[d] for d in cold_first[:b["docs"]]]

            def send(ids=ids, depth=b["depth"]):
                for doc_id in ids:
                    for _ in range(depth):
                        plant.edit(doc_id)
                    plant.flush(doc_id)

            tb = time.perf_counter()
            gate.burst(f"{b['docs']} docs x {b['depth']}", ids, send, deadline)
            burst_s.append([b["docs"], b["depth"], time.perf_counter() - tb])
        if not own.get("start_during_last_burst"):
            child.wait_rows(plant.ops, deadline, "the ladder to be applied")
        t_ladder = time.perf_counter()
        ladder_ops = plant.ops
        say(f"ladder of {len(ladder)} bursts applied in "
            f"{t_ladder - t_ready:.1f}s")

        gc.collect()
        gc.freeze()
        stream = Stream(plant, tick_s)
        t0 = time.perf_counter() + 0.1     # stream time 0: start of warm-up
        w0 = t0 + warm_n_ticks * tick_s    # first tick of the window opens
        w1 = w0 + window_s
        n_warm = len(warm_ticks)
        stream.send(ticks[:n_warm], docs[:n_warm], t0)
        n_warm_groups = len(stream.groups)
        if args.trace:
            # The traced span is the END of the window: stop_trace takes
            # ~30 s of the child's time (250,000 device events) and one run
            # in three it held the whole plant up for seconds, so it has to
            # come after the last op was sent.
            go_at = max(w0, w1 - trace_s - TRACE_LEAD_S)
            cut = next((i for i in range(n_warm, len(ticks))
                        if t0 + (ticks[i] + 1) * tick_s >= go_at), len(ticks))
            stream.send(ticks[n_warm:cut], docs[n_warm:cut], t0)
            with open(os.path.join(profile_dir, "go"), "w"):
                pass
            stream.send(ticks[cut:], docs[cut:], t0)
        else:
            stream.send(ticks[n_warm:], docs[n_warm:], t0)
        t_sent = time.perf_counter()
        gc.unfreeze()
        say(f"window sent; {plant.ops} ops, {plant.nacks} nacks")

        give_up = max(t_sent, w1) + DRAIN_DEADLINE_S
        final = None
        try:
            _t, final = child.wait_for(
                lambda s: s.get("done"), give_up, "the done line")
        except BenchFailure as e:
            say(f"no done line: {e}")
        tail: dict = {}
        if final is not None:
            # stop_trace can take minutes after a trace of many small ops.
            end = time.perf_counter() + 300.0
            try:
                while "memory_peak_bytes" not in tail:
                    _t, obj = child.next_json(end, "the memory line")
                    tail.update(obj)
                child.finish(end)
            except BenchFailure as e:
                say(f"child's exit: {e}")
    finally:
        if child is not None:
            child.kill()
        plant.stop()

    # ---------------------------------------------------------------- reduce
    lines = []           # (arrival, rows, applied, dropped) per status line
    parsed = []          # (arrival, dict) of those inside the window
    for t, line in child.lines:
        r = status_rows(line)
        if r is None:
            continue
        obj = json.loads(line)
        lines.append((t, r, obj.get("applied"), obj.get("applied_dropped")))
        if w0 - 1.0 <= t <= w1 + 1.0:
            parsed.append((t, obj))
    try:
        stamps = lagmod.stamps_of(lines)
    except lagmod.StampError as e:
        raise BenchFailure(f"the status stream's stamps: {e}") from None
    status = [(t, r) for t, r, _seen in stamps]      # the proofs, by stamp
    by_line = [(t, r) for t, r, _a, _d in lines]     # the old reduction
    win_groups = stream.groups[n_warm_groups:]
    op_groups = [(g[0], g[2], g[3]) for g in win_groups]
    lags, unapplied = lagmod.match_lags(op_groups, status, give_up)
    line_lags, _ = lagmod.match_lags(op_groups, by_line, give_up)
    failed = plant.nacks + unapplied
    late = [g[1] - g[0] for g in win_groups for _ in range(g[3])]
    setup_s = w0 - t_start

    correct, why = False, "no done line"
    check: dict = {}
    lanes: dict = {}
    if final is not None:
        h = final["health"]
        lanes = {k: h.get(k, 0) for k in (
            "quarantined_docs", "oracle_docs", "overflow_docs",
            "ingest_fallback_msgs")}
        # Compared first: every document offered its cap; the comparison is
        # a sample past those, so a control looks where its fault is.
        first = [d for d in range(n_docs)
                 if params.get("cap_ops_per_s")
                 and rates[d] >= float(params["cap_ops_per_s"]) - 1e-9]
        if args.plant_fault:
            said = [json.loads(line) for _t, line in child.lines
                    if line.startswith('{"planted"')]
            if not said:
                raise BenchFailure("the control's fault was never planted")
            if said[0]["doc"] not in first:
                first.insert(0, said[0]["doc"])
        check = plant.verify(
            final, [plant.doc_ids[d] for d in touched],
            [plant.doc_ids[d] for d in first],
            args.seed, VERIFY_BUDGET_S, VERIFY_MIN_SAMPLE)
        problems = [f"{k} = {v}" for k, v in lanes.items() if v]
        if not check["ok"]:
            problems.append(check["why"])
        if final["errors"]:
            problems.append(f"errors = {final['errors']}")
        if h.get("ingest_plane") != "native":
            problems.append(f"ingest plane {h.get('ingest_plane')}")
        if plant.nacks or failed:
            problems.append(f"{plant.nacks} nacks, {failed} ops failed")
        if final["rows"] != planned:
            problems.append(f"rows {final['rows']} != planned {planned}")
        correct, why = not problems, "; ".join(problems)
    say(f"correct: {correct} {why} {check}")

    values: dict[str, float] = {}
    if lags:
        values["apply_lag_p50_ms"] = lagmod.percentile(lags, 0.5) * 1e3
        values["apply_lag_p95_ms"] = lagmod.percentile(lags, 0.95) * 1e3
    rate = lagmod.applied_rate(status, w0, w1)
    if rate is not None:
        values["applied_ops_per_s"] = rate
    values["setup_s"] = setup_s

    ctx = {
        "spec": spec, "n_docs": n_docs, "w0": w0, "w1": w1,
        "status": status, "stamps": stamps, "parsed": parsed,
        "groups": win_groups,
        "late": late, "ready": ready, "final": final, "tail": tail,
        "flight_path": fr_path if args.trace else None,
        "profile_dir": profile_dir if args.trace else None,
    }
    device = {
        "platform": ready["platform"], "kind": ready["device_kind"],
        "count": int(ready["device_count"]),
        "memory_peak_bytes": int(tail.get("memory_peak_bytes", 0)),
    }
    out: dict = {"correct": bool(correct), "attempted": int(planned_win),
                 "failed": int(failed)}
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    layer_values: dict[str, float] = {}
    if args.trace:
        import traces

        traced = traces.reduce_run(ctx)
        ctx["traced"] = traced
        if traced.get("busy_s"):
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
        for m in spec["per_layer"]:
            mod = importlib.import_module("layer_metrics." + m["name"])
            v = mod.read(ctx)
            if v is not None:
                layer_values[m["name"]] = float(v)
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in layer_values.items()}
        if traced.get("breakdown"):
            out["breakdown"] = traced["breakdown"]
    else:
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in spec["end_to_end"]
                          if m["name"] in values}
    out["device"] = device
    in_win = [(t, a) for t, _r, a, _d in lines if w0 <= t <= w1]
    out["bench"] = {
        "lag_by_line_p50_ms": (lagmod.percentile(line_lags, 0.5) * 1e3
                               if line_lags else None),
        "lag_by_line_p95_ms": (lagmod.percentile(line_lags, 0.95) * 1e3
                               if line_lags else None),
        "lines_in_window": len(in_win),
        "stamps_in_window": sum(len(a) for _t, a in in_win),
        # Where in the generator's tick the lines that carried a proof
        # arrived (circular mean): the old reduction's level follows it.
        "line_phase_in_tick_ms": lagmod.phase_in_period(
            [t - t0 for t, a in in_win if a], tick_s),
    }
    if args.rehearse_cpu:
        out["rehearsal"] = True
    if args.plant_fault:
        out["planted_fault"] = args.plant_fault     # a control, not a result
    # Each number `correct` compared, beside its limit; last in the line.
    out["checks"] = {k: [v, 0] for k, v in {
        "docs_differ": 0 if check.get("ok") else 1,
        "host_lane_docs": sum(lanes.values()),
        "device_errors": (final or {}).get("errors", 0),
        "nacks": plant.nacks,
        "unapplied_ops": unapplied,
        "rows_short": planned - (final or {}).get("rows", 0),
    }.items()}

    report = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "seconds": args.seconds, "rate_ops_per_s": params["rate_ops_per_s"],
        "docs": n_docs, "rehearsal": bool(args.rehearse_cpu),
        "end_to_end": values, "per_layer": layer_values,
        "lag_samples": len(lags), "why": why, "verify": check,
        "ladder_burst_s": burst_s,
        "segments": _segments(ctx, seg_rates, float(args.seconds), win_groups,
                              lags, stream.groups, ladder_ops),
        "span_stats": ctx.get("traced", {}).get("span_stats"),
        "setup_split_s": {
            "joined_writers": t_joined - t_start,
            "ready": t_ready - t_start,
            "ladder": t_ladder - t_ready,
            "warm_traffic": w0 - t_ladder,
        },
        "compile_at_ready": ready.get("compile"),
        "compile_at_end": (final or {}).get("compile"),
        "status_lines_in_window": len(parsed),
        "health_at_end": (final or {}).get("health"),
        "traced": {k: v for k, v in ctx.get("traced", {}).items()
                   if k not in ("gaps", "flight", "span_stats")},
        # Seconds since the window opened: what a look at one tick needs.
        "window_stamps": [[seen - w0, t - w0, r] for t, r, seen in stamps
                          if w0 - 1.0 <= t <= w1 + 1.0],
        "window_lines": [[t - w0, r] for t, r in by_line
                         if w0 - 1.0 <= t <= w1 + 1.0],
        "window_flushes": [[g[0] - w0, g[1] - w0, g[2], g[3]]
                           for g in win_groups],
        "result": out,
    }
    if args.report:
        os.makedirs(os.path.dirname(os.path.abspath(args.report)),
                    exist_ok=True)
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1, default=float)
    say(json.dumps({k: report[k] for k in (
        "end_to_end", "per_layer", "segments", "setup_split_s",
        "compile_at_ready", "compile_at_end", "lag_samples")},
        default=float))
    return 0, out


def _segments(ctx, seg_rates, seg_s, win_groups, lags, groups, base):
    """Per-rate table of a knee sweep (one row in an ordinary run)."""
    rows = []
    op_due = [g[0] for g in win_groups for _ in range(g[3])]
    op_late = [g[1] - g[0] for g in win_groups for _ in range(g[3])]
    flight = ctx.get("traced", {}).get("flight") or []
    for i, rate in enumerate(seg_rates):
        a, b = ctx["w0"] + i * seg_s, ctx["w0"] + (i + 1) * seg_s
        sel = [j for j, d in enumerate(op_due) if a < d <= b + 1e-9]
        if not sel:
            continue
        seg_lags = [lags[j] for j in sel]
        backlog = [(t, _sent_by(groups, t, base) - r)
                   for t, r in ctx["status"] if a <= t <= b]
        inside = [s for t, s in ctx["parsed"] if a <= t <= b]
        shapes: dict = {}
        for name, s0, _s1, args_ in flight:
            if name == "dispatch" and a <= s0 <= b:
                key = (f"{args_.get('kind')}/{args_.get('lanes', 'fleet')}"
                       f"/k{args_.get('k', 1)}")
                shapes[key] = shapes.get(key, 0) + 1
        h0 = inside[0]["health"] if inside else {}
        h1 = inside[-1]["health"] if inside else {}
        loops = lagmod.seen_to_applied(ctx["stamps"], a, b)
        rows.append({
            "rate_ops_per_s": rate, "ops": len(sel),
            "lag_p50_ms": lagmod.percentile(seg_lags, 0.5) * 1e3,
            "lag_p95_ms": lagmod.percentile(seg_lags, 0.95) * 1e3,
            "generator_late_p95_ms": lagmod.percentile(
                [op_late[j] for j in sel], 0.95) * 1e3,
            "backlog_slope_ops_per_s": _slope(backlog),
            "backlog_at_end": backlog[-1][1] if backlog else None,
            "applied_ops_per_s": lagmod.applied_rate(ctx["status"], a, b),
            "loop_ms_p50": (lagmod.percentile(loops, 0.5) * 1e3
                            if loops else None),
            "cohort_steps": h1.get("cohort_steps", 0) - h0.get(
                "cohort_steps", 0),
            "full_steps": h1.get("full_steps", 0) - h0.get("full_steps", 0),
            "dispatch_shapes": shapes or None,
        })
    return rows


def _sent_by(groups, t: float, base: int) -> int:
    """Ops sent by time ``t`` (count after the last flush that ended by t)."""
    lo, hi = 0, len(groups)
    while lo < hi:
        mid = (lo + hi) // 2
        if groups[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    return groups[lo - 1][2] if lo else base


def _slope(points) -> float | None:
    """Least-squares slope of (t, y): ops/s by which the backlog grew."""
    if len(points) < 3:
        return None
    n = len(points)
    mt = sum(t for t, _ in points) / n
    my = sum(y for _, y in points) / n
    den = sum((t - mt) ** 2 for t, _ in points)
    return sum((t - mt) * (y - my) for t, y in points) / den if den else None


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="toy-size rehearsal on the CPU; never a result")
    p.add_argument("--sweep", default=None,
                   help="knee sweep: comma-separated rates; the window is "
                        "one segment of --seconds per rate, in this order")
    p.add_argument("--plant-fault", choices=("alter_op",), default=None,
                   help="the control of `correct`: the child alters one op "
                        "where it feeds it to the engine; the run has to "
                        "read correct: false (never a result)")
    p.add_argument("--report", default=None,
                   help="write everything the run learned to this file")
    args = p.parse_args(argv)
    args.sweep = ([float(x) for x in args.sweep.split(",")]
                  if args.sweep else None)
    try:
        rc, out = run(args)
    except BenchFailure as e:
        print(f"benchmark: FAILED - {e}", file=sys.stderr)
        return 1
    assert "jax" not in sys.modules, "the benchmark's parent imported JAX"
    for name, (value, limit) in out["checks"].items():
        print(f"check {name} = {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
