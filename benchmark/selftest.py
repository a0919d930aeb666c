"""Self-tests of the yardstick (not part of tests/: the benchmark checks
itself).  No chip, no JAX in this process.

    python3 benchmark/selftest.py
    python3 benchmark/selftest.py --cut-fixture <file.xplane.pb>   # record it anew

- the lag matcher and the window arithmetic on a synthetic status stream;
- the step stamps: on a stream whose status grid and traffic tick share a
  period, lag by stamps is the same at every phase of the grid while lag by
  line arrival takes two values; a stamp whose line never arrived proves
  nothing; a stream that breaks the stamps' contract is refused; the child's
  stamp log (``fleet_child.StampLog``, ``install_stamps``) on a fake loop;
- interval union, self times, gap labelling, the device reduction and the
  program split on synthetic events with a known idle share and per-program
  time;
- the device reduction and the program split on a trace recorded on the v5e
  (``selftest_data/zipf_trio.v5e.json.gz``: the scatter before a cohort
  trio, the trio, the idle gap after it and the next gather, 24,000 device
  events cut from a traced run of ``string1_zipf_steady``), against what its
  own modules line says;
- the traffic generator (fixed count, cap, determinism);
- the control of ``correct``: two rehearsed runs on the CPU (~25 s each), one
  sound and one with an op altered in the child, which has to read incorrect;
- the last-line schema, on a line built the way run.py builds it, and
  BENCHMARK.json against the files it names.
"""

from __future__ import annotations

import gzip
import importlib
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import device_programs  # noqa: E402
import fleet_child  # noqa: E402
import lag  # noqa: E402
import roofline  # noqa: E402
import traces  # noqa: E402

FIXTURE = os.path.join(HERE, "selftest_data", "zipf_trio.v5e.json.gz")


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-12)


def test_lag_matcher() -> None:
    # 3 flushes: 2 ops due at t=1.0 (sent count 2), 1 op due 1.05 (count 3),
    # 4 ops due 1.10 (count 7).  Status lines: rows 1 @1.02, 3 @1.20, 3 @1.25,
    # 6 @1.40 and nothing more: the last flush is never covered.
    groups = [(1.0, 2, 2), (1.05, 3, 1), (1.10, 7, 4)]
    status = [(1.02, 1), (1.20, 3), (1.25, 3), (1.40, 6)]
    lags, unapplied = lag.match_lags(groups, status, give_up_at=61.10)
    assert unapplied == 4
    assert len(lags) == 7
    assert close(lags[0], 0.20) and close(lags[1], 0.20)
    assert close(lags[2], 0.15)
    assert all(close(x, 60.0) for x in lags[3:])
    assert close(lag.percentile([1, 2, 3, 4, 5], 0.5), 3.0)
    assert close(lag.percentile([0.0, 10.0], 0.95), 9.5)
    # Whole loops inside [1.1, 1.5]: rows 3 @1.20 .. 6 @1.40.
    assert close(lag.applied_rate(status, 1.1, 1.5), 3 / 0.2)
    assert lag.applied_rate(status, 1.21, 1.3) is None
    # The same proofs as stamps on three lines that arrived 5 ms after their
    # last step (the line with rows 3 carries two steps; the one after it
    # none): lag ends at the stamp, not at the line.
    lines = [(1.025, 1, [[1.00, 1.02, 1]], 0),
             (1.205, 3, [[1.03, 1.10, 2], [1.11, 1.20, 3]], 0),
             (1.255, 3, [], 0),
             (1.405, 6, [[1.30, 1.40, 6]], 0)]
    stamps = lag.stamps_of(lines)
    assert [r for _t, r, _s in stamps] == [1, 2, 3, 6]
    by_stamp = [(t, r) for t, r, _s in stamps]
    lags, unapplied = lag.match_lags(groups, by_stamp, give_up_at=61.10)
    assert unapplied == 4 and close(lags[0], 0.10) and close(lags[2], 0.15)
    by_line = [(t, r) for t, r, _a, _d in lines]
    lags, _ = lag.match_lags(groups, by_line, give_up_at=61.10)
    assert close(lags[0], 0.205) and close(lags[2], 0.155)
    # From the moment the fleet saw the work to the moment it was applied.
    loops = lag.seen_to_applied(stamps, 1.05, 2.0)
    assert len(loops) == 3 and close(loops[0], 0.07) and close(loops[2], 0.10)
    assert close(lag.phase_in_period([0.012, 0.062, 0.112], 0.05), 12.0, 1e-6)
    assert lag.clock_is_shared(time.get_clock_info("perf_counter"))
    assert not lag.clock_is_shared(types.SimpleNamespace(
        implementation="clock_gettime(CLOCK_PROCESS_CPUTIME_ID)",
        monotonic=True))


# The stream that tests/test_status_schedule.py::_ticks models, with the
# program's own schedule: every 50 ms a burst of OPS_COHORT + OPS_STRAGGLERS
# ops is due; a cohort step ends ``cohort`` and a straggler step
# ``straggler`` seconds into the tick, then the loop looks in once more,
# idle; a line costs LINE_S before it arrives.
TICK, EVERY, LINE_S = 0.05, 0.05, 0.002
OPS_COHORT, OPS_STRAGGLERS = 30, 2
PHASES_MS = (0, 7, 13, 21, 29, 38, 46)
STEP_ENDS = ((0.025, 0.035), (0.011, 0.021))


def _tick_stream(phase: float, cohort: float, straggler: float, n: int = 200):
    """``(groups, lines)`` of ``n`` ticks, the status grid starting ``phase``
    seconds before the first tick: flushes as ``match_lags`` takes them, and
    ``(arrival, rows, applied, dropped)`` per line that the schedule printed.
    """
    from fluidframework_tpu.server.fleet_main import next_status_due

    now = 0.0
    log = fleet_child.StampLog(clock=lambda: now)
    groups, lines, rows = [], [], 0
    due_line = -phase + EVERY
    for i in range(n):
        t = i * TICK
        for end, ops in ((cohort, OPS_COHORT), (straggler, OPS_STRAGGLERS),
                         (0.0451, 0)):
            now = t                        # select reported the burst
            log.iteration()
            if ops:
                log.seen()
                rows += ops
                groups.append((t, rows, ops))
                now = t + end
                log.applied(rows)
            now = t + end
            nxt = next_status_due(due_line, now, EVERY, bool(ops))
            if nxt is not None:
                due_line = nxt
                lines.append((now + LINE_S, rows, log.take(), log.dropped))
    return groups, lines


def _p50_p95(groups, status):
    lags, unapplied = lag.match_lags(groups, status, give_up_at=1e3)
    return (round(lag.percentile(lags, 0.5), 9),
            round(lag.percentile(lags, 0.95), 9), unapplied)


def _by_stamp_and_by_line(phase_ms: int, cohort: float, straggler: float):
    groups, lines = _tick_stream(phase_ms / 1e3, cohort, straggler)
    # The run ends with the last line that arrived; what it proves counts.
    groups = [g for g in groups if g[1] <= lines[-1][1]]
    by_stamp = [(t, r) for t, r, _s in lag.stamps_of(lines)]
    by_line = [(t, r) for t, r, _a, _d in lines]
    return _p50_p95(groups, by_stamp), _p50_p95(groups, by_line)


def test_lag_by_stamp_is_the_steps_at_every_phase(phase_ms, ends) -> None:
    cohort, straggler = ends
    by_stamp, _by_line = _by_stamp_and_by_line(phase_ms, cohort, straggler)
    # 30 of a tick's 32 ops end with the cohort step, 2 with the stragglers'.
    assert by_stamp == (round(cohort, 9), round(straggler, 9), 0), by_stamp


test_lag_by_stamp_is_the_steps_at_every_phase.params = [
    (p, e) for e in STEP_ENDS for p in PHASES_MS]


def test_lag_by_line_takes_two_levels(ends) -> None:
    # The defect this measures around: one line a tick, on the cohort step
    # (its stragglers wait a tick for their proof) or on the straggler step
    # (the cohort's ops wait for it), chosen by the grid's phase alone.
    levels = {_by_stamp_and_by_line(p, *ends)[1] for p in PHASES_MS}
    assert len(levels) == 2, levels
    cohort, straggler = ends
    assert {round(v[0] - LINE_S, 6) for v in levels} == {
        round(cohort, 6), round(straggler, 6)}, levels


test_lag_by_line_takes_two_levels.params = [(e,) for e in STEP_ENDS]


def test_a_stamp_whose_line_did_not_arrive_proves_nothing() -> None:
    groups, lines = _tick_stream(0.013, 0.025, 0.035, n=20)
    arrived = lines[:-3]
    status = [(t, r) for t, r, _s in lag.stamps_of(arrived)]
    lags, unapplied = lag.match_lags(groups, status, give_up_at=61.0)
    covered = arrived[-1][1]
    assert unapplied == groups[-1][1] - covered > 0
    assert sum(1 for x in lags if x > 50.0) == unapplied


def test_stamps_of_refuses(case) -> None:
    good = [[1.00, 1.02, 1], [1.03, 1.10, 3]]
    lines = {
        "no_applied_field": [(1.2, 3, None, None)],
        "stamp_later_than_its_line": [(1.05, 3, good, 0)],
        "last_stamp_is_not_the_lines_rows": [(1.2, 4, good, 0)],
        "rows_without_a_stamp": [(1.2, 3, good, 0), (1.3, 5, [], 0)],
        "stamps_dropped": [(1.2, 3, good, 2)],
        "rows_do_not_advance": [(1.2, 3, [good[1], [1.11, 1.12, 3]], 0)],
        "time_runs_backwards": [(1.2, 3, [good[1], [0.9, 1.0, 4]], 0)],
        "applied_before_seen": [(1.2, 1, [[1.02, 1.00, 1]], 0)],
    }[case]
    try:
        lag.stamps_of(lines)
    except lag.StampError:
        return
    raise AssertionError(f"{case}: accepted")


test_stamps_of_refuses.params = [(c,) for c in (
    "no_applied_field", "stamp_later_than_its_line",
    "last_stamp_is_not_the_lines_rows", "rows_without_a_stamp",
    "stamps_dropped", "rows_do_not_advance", "time_runs_backwards",
    "applied_before_seen")]


def test_stamp_log_on_a_fake_loop() -> None:
    """``install_stamps`` around a consumer and a snapshot that do nothing:
    a line's stamps are in order, never decrease in ``rows``, the last is
    the line's ``rows``, the log empties per line and stays bounded."""
    now = [100.0]

    class Consumer:
        rows_staged = 0

        def pump(self, wait_s=0.02, idle=None):
            now[0] += 0.001
            return self._drain_ready([1] if self.incoming else [], None)

        def _drain_ready(self, ready, sp):
            self.rows_staged += self.incoming
            return self.incoming

        def step(self):
            now[0] += 0.010
            return 1

    fm = types.SimpleNamespace(
        next_status_due=lambda due, t, every, stepped: None,
        status_snapshot=lambda rows=0, **kw: {"rows": rows, **kw})
    log = fleet_child.StampLog(capacity=4, clock=lambda: now[0])
    fleet_child.install_stamps(fm, Consumer, log)
    fc = Consumer()
    lines = []
    for burst in ([3, 1, 0], [0], [2, 0, 5], [1] * 6):
        for fc.incoming in burst:
            staged = fc.pump()
            stepped = bool(staged or burst == [0])   # [0]: paused alone
            if stepped:
                fc.step()
            fm.next_status_due(0.0, now[0], 0.05, stepped)
        snap = fm.status_snapshot(rows=fc.rows_staged)
        now[0] += 0.002
        lines.append((now[0], snap["rows"], snap["applied"],
                      snap["applied_dropped"]))
    assert [len(a) for _t, _r, a, _d in lines] == [2, 0, 2, 4]
    assert [d for _t, _r, _a, d in lines] == [0, 0, 0, 2]
    assert not log.entries                      # emptied by every line
    stamps = lag.stamps_of(lines[:3])
    assert [r for _t, r, _s in stamps] == [3, 4, 6, 11]
    assert all(close(t - seen, 0.010) for t, _r, seen in stamps)
    try:
        lag.stamps_of(lines)
    except lag.StampError as e:
        assert "dropped" in str(e)
    else:
        raise AssertionError("a run that dropped stamps must fail")
    # A consumer without the calls the stamps need is an error, not a
    # silent fall-back to arrival times.
    try:
        fleet_child.install_stamps(fm, type("Bare", (), {}),
                                   fleet_child.StampLog())
    except AttributeError:
        pass
    else:
        raise AssertionError("install_stamps accepted a bare consumer")


def test_intervals() -> None:
    busy, merged = traces.union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert busy == 35 and merged == [[0, 20], [30, 45]]
    assert traces.gaps_between(merged, 5) == [(20, 30)]
    assert traces.gaps_between(merged, 11) == []
    # A while (0..100) enclosing two fusions (10..40, 50..70): self 50.
    st = traces.self_times([("while", 0, 100), ("fusion.1", 10, 30),
                            ("fusion.2", 50, 20), ("copy", 120, 5)])
    assert st == {"while": 50, "fusion.1": 30, "fusion.2": 20, "copy": 5}


def test_reduce_device_known_idle() -> None:
    # One device, traced 0..1000 ns on the ops line: busy 0-400 and 600-1000
    # (idle 20%); modules: the tail of a fleet step the trace's start cut
    # (0-10), gather 10-50, step 50-350, scatter 350-400 (a cohort trio), a
    # whole fleet step 600-900, and one the trace's end cut (900-1000).
    planes = {"/device:TPU:0": {
        "XLA Ops": [("fusion.a", 0, 400), ("fusion.b", 600, 400)],
        "XLA Modules": [("jit__fleet_step(4)", 0, 10),
                        ("jit__lambda_(1)", 10, 40),
                        ("jit__fleet_step(2)", 50, 300),
                        ("jit__scatter_cohort_jit(3)", 350, 50),
                        ("jit__fleet_step(4)", 600, 300),
                        ("jit__fleet_step(4)", 900, 100)],
    }}
    dev = traces.reduce_device(planes, min_gap_ns=100)
    assert dev["busy_ns"] == 800
    assert dev["last_ns"] - dev["first_ns"] == 1000
    assert dev["gaps"] == [(400, 600)]
    assert dev["modules"]["jit__fleet_step"] == {"count": 4, "ns": 710}
    # Only whole executions count: the two the edges cut are left out.
    split = device_programs.classify(dev["module_events"])
    assert split["cohort"] == {"ns": 390, "executions": 1}
    assert split["fleet"] == {"ns": 300, "executions": 1}
    # The gap 400..600 ns, host spans in seconds on the same axis.
    idle = traces.label_gaps(
        [(400e-9, 600e-9)],
        [("readback", 380e-9, 450e-9), ("ingest", 500e-9, 520e-9),
         ("warmup", 0.0, 1.0)])
    assert close(idle["readback"], 50e-9) and close(idle["ingest"], 20e-9)
    assert close(idle[traces.IDLE_ELSE], 130e-9)


def test_compiles_reader() -> None:
    # A compile the window's ops caused ends after the window: the reader
    # counts up to the done line, not to the last line inside the window.
    mod = importlib.import_module("layer_metrics.compiles_in_window")

    def line(req, hits, desp=0):
        return {"compile": {"requests": req, "cache_hits": hits},
                "health": {"despecializations": desp}}

    ctx = {"w0": 10.0, "w1": 20.0, "final": line(41, 40),
           "parsed": [(9.5, line(39, 39)), (10.5, line(40, 40)),
                      (19.0, line(40, 40))]}
    assert mod.read(ctx) == 1
    ctx["final"] = None
    assert mod.read(ctx) == 0
    ctx["parsed"] = []
    assert mod.read(ctx) is None


def cut_fixture(xplane_path: str) -> None:
    """Cut the fixture from a traced run's ``.xplane.pb`` (it is under
    ``benchmark/.work/profile/`` after ``--trace 1``): the device events from
    the first scatter to the end of the third gather, so that one whole
    cohort trio stands between two edge events.  Names go into a table and
    starts are differences, which is what keeps 24,000 events at 90 KB."""
    import xplane_dump

    planes = xplane_dump.read_planes(xplane_path)
    plane = sorted(planes)[0]
    mods = sorted(planes[plane]["XLA Modules"], key=lambda e: e[1])
    gathers = [m for m in mods if traces.program_of(m[0]) == "jit__lambda"]
    scatters = [m for m in mods if device_programs.SCATTER_MARK in m[0]]
    a, b = scatters[0][1], gathers[2][1] + gathers[2][2]

    def inside(events):
        return sorted((e for e in events if a <= e[1] and e[1] + e[2] <= b),
                      key=lambda e: e[1])

    ops = inside(planes[plane]["XLA Ops"])
    names = sorted({n for n, _s, _d in ops})
    index = {n: i for i, n in enumerate(names)}
    rows, prev = [], a
    for n, start, dur in ops:
        rows.append([index[n], start - prev, dur])
        prev = start
    os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
    with gzip.open(FIXTURE, "wt", compresslevel=9) as f:
        json.dump({"plane": plane, "names": names, "ops": rows,
                   "modules": [[n, s - a, d] for n, s, d in
                               inside(planes[plane]["XLA Modules"])]},
                  f, separators=(",", ":"))
    print(f"{FIXTURE}: {len(ops)} device events, {len(names)} names")


def test_recorded_trace() -> None:
    with gzip.open(FIXTURE, "rt") as f:
        fx = json.load(f)
    ops, t = [], 0
    for i, delta, dur in fx["ops"]:
        t += delta
        ops.append((fx["names"][i], t, dur))
    mods = [tuple(m) for m in fx["modules"]]
    dev = traces.reduce_device(
        {fx["plane"]: {"XLA Ops": ops, "XLA Modules": mods}})
    # What the modules line says: the scatter before, then gather, cohort
    # step, scatter, and the next gather.
    progs = [traces.program_of(m[0]) for m in mods]
    assert progs == ["jit__scatter_cohort_jit", "jit__lambda",
                     "jit__fleet_step", "jit__scatter_cohort_jit",
                     "jit__lambda"], progs
    split = device_programs.classify(dev["module_events"])
    assert split["fleet"] == {"ns": 0, "executions": 0}
    assert split["cohort"] == {"ns": sum(m[2] for m in mods[1:4]),
                               "executions": 1}
    assert dev["modules"]["jit__fleet_step"] == {"count": 1, "ns": mods[2][2]}
    # Busy time comes from the ops line alone and has to agree with the
    # programs' own durations; the idle share is then known: the gaps
    # between the five programs over the span.
    prog_ns = sum(m[2] for m in mods)
    assert close(dev["busy_ns"], prog_ns, 0.01), (dev["busy_ns"], prog_ns)
    span = dev["last_ns"] - dev["first_ns"]
    end = mods[-1][1] + mods[-1][2]
    assert close(span, end - mods[0][1], 0.001)
    idle = 100.0 * (1.0 - dev["busy_ns"] / span)
    assert close(idle, 100.0 * (1.0 - prog_ns / span), 0.05), idle
    assert close(idle, 31.58, 0.001), idle          # as recorded
    # The heaviest operation is the gather's read of a fleet-wide column.
    assert dev["ops"][0][0] == "fusion.36 s32[6144,32768]", dev["ops"][0]
    # Self times of nested events add up to the union of their intervals.
    assert close(dev["ops_total_ns"], dev["busy_ns"], 0.01), (
        dev["ops_total_ns"], dev["busy_ns"])


def test_roofline() -> None:
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    try:
        roofline.peaks("TPU v99")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device must be an error")
    need = roofline.step_bytes_needed(10, 100, 1000.0)
    assert need == 2 * 10 * 1000.0 + 100 * 64
    # 819e9 bytes in 2 s on one chip is 50% of the memory roofline.
    assert close(roofline.memory_roofline_share(819e9, 2.0, "TPU v5 lite"),
                 50.0)


def test_generator() -> None:
    gen = importlib.import_module("generators.poisson_docs")
    params = {"rate_ops_per_s": 2000, "doc_distribution": "zipf",
              "zipf_s": 0.99, "cap_ops_per_s": 16, "tick_s": 0.05}
    r = gen.doc_rates(params, 6144, seed=7)
    assert close(r.sum(), 2000.0, 1e-9) and r.max() <= 16 + 1e-9
    assert (r >= 16 - 1e-9).sum() > 5          # the head is capped
    t1, d1 = gen.schedule(params, 6144, 45.0, 7, 2)
    t2, d2 = gen.schedule(params, 6144, 45.0, 7, 2)
    assert len(t1) == 90_000 and (t1 == t2).all() and (d1 == d2).all()
    assert t1.max() < 900 and (t1[1:] >= t1[:-1]).all()
    t3, _ = gen.schedule(params, 6144, 45.0, 8, 2)
    assert (t1 != t3).any()


def test_a_rehearsed_run_is_correct_unless_an_op_was_altered(fault) -> None:
    """The control of ``correct`` at a size a test can hold: the whole of a
    run (``run.py --rehearse-cpu``, which only skips the look for a chip)
    with one op altered where the child feeds it to the engine has to read
    ``correct: false`` by ``docs_differ``; the same run without it, true."""
    import subprocess

    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           "string1_zipf_steady", "--seed", "2147483999", "--seconds", "6",
           "--trace", "0", "--rehearse-cpu"]
    if fault:
        cmd += ["--plant-fault", fault]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is (fault is None), (fault, line["checks"])
    assert line["checks"]["docs_differ"] == [1 if fault else 0, 0]
    assert line.get("planted_fault") == fault
    assert list(line)[-1] == "checks"
    assert p.stderr.strip().splitlines()[-1].startswith("check ")


test_a_rehearsed_run_is_correct_unless_an_op_was_altered.params = [
    (None,), ("alter_op",)]


def test_last_line_schema() -> None:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        mod = importlib.import_module("layer_metrics." + m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"]), m["name"]
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and "guarantees" in cfg
        plant = importlib.import_module("plants." + cfg["plant"]["module"])
        assert callable(plant.Plant)
    for w in bench["workloads"]:
        traffic = json.load(open(
            os.path.join(HERE, "traffic", w["traffic"] + ".json")))
        importlib.import_module("generators." + traffic["generator"])
        # The rate is the cell's, and lives in one place.
        assert "rate_ops_per_s" not in traffic["params"], w["traffic"]
        own = json.load(open(
            os.path.join(HERE, "cells", w["name"] + ".json")))
        assert own["params"]["rate_ops_per_s"] > 0, w["name"]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    # A line as run.py builds it: the driver's keys, then the evidence of
    # the old reduction under ``bench``, and last each number ``correct``
    # compared beside its limit.
    line = json.loads(json.dumps({
        "correct": True, "attempted": 10, "failed": 0,
        "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 1},
        "bench": {"lag_by_line_p50_ms": 105.0, "lag_by_line_p95_ms": 146.0,
                  "lines_in_window": 880, "stamps_in_window": 1650,
                  "line_phase_in_tick_ms": 31.0},
        "checks": {"docs_differ": [0, 0], "unapplied_ops": [0, 0]}}))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert all(len(v) == 2 for v in line["checks"].values())
    assert not set(line["bench"]) & e2e       # evidence, never a metric


def main() -> int:
    if sys.argv[1:2] == ["--cut-fixture"]:
        cut_fixture(sys.argv[2])
        return 0
    tests = [v for k, v in sorted(globals().items()) if k.startswith("test_")]
    n = 0
    for t in tests:
        # A test with ``params`` is one case per entry, each counted.
        for params in getattr(t, "params", [()]):
            t(*params)
            n += 1
            print(f"ok  {t.__name__}{list(params) if params else ''}")
    print(f"{n} self-tests passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
