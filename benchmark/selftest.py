"""Self-tests of the yardstick (not part of tests/: the benchmark checks
itself).  No chip, no JAX in this process.

    python3 benchmark/selftest.py
    python3 benchmark/selftest.py --cut-fixture <file.xplane.pb>   # record it anew

- the lag matcher and the window arithmetic on a synthetic status stream;
- interval union, self times, gap labelling, the device reduction and the
  program split on synthetic events with a known idle share and per-program
  time;
- the device reduction and the program split on a trace recorded on the v5e
  (``selftest_data/zipf_trio.v5e.json.gz``: the scatter before a cohort
  trio, the trio, the idle gap after it and the next gather, 24,000 device
  events cut from a traced run of ``string1_zipf_steady``), against what its
  own modules line says;
- the traffic generator (fixed count, cap, determinism);
- the last-line schema, on a line built the way run.py builds it, and
  BENCHMARK.json against the files it names.
"""

from __future__ import annotations

import gzip
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import device_programs  # noqa: E402
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
    # Loops that advanced rows: 1.02 -> 1.20 and 1.25 -> 1.40.
    gaps = lag.advancing_gaps(status, 1.0, 2.0)
    assert len(gaps) == 2 and close(gaps[0], 0.18) and close(gaps[1], 0.15)


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
    # A line as run.py builds it.
    line = json.loads(json.dumps({
        "correct": True, "attempted": 10, "failed": 0,
        "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 1}}))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())


def main() -> int:
    if sys.argv[1:2] == ["--cut-fixture"]:
        cut_fixture(sys.argv[2])
        return 0
    tests = [v for k, v in sorted(globals().items()) if k.startswith("test_")]
    for t in tests:
        t()
        print(f"ok  {t.__name__}")
    print(f"{len(tests)} self-tests passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
