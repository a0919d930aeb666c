"""The cell ``string4_uniform_wide`` rehearsed on the CPU, and its files.

``benchmark/run.py --workload string4_uniform_wide --rehearse-cpu`` is the
whole of a run but its look for a chip: the front, the sequencer and the
writers in this process's child, ``fleet_main --mesh 4`` on four virtual CPU
devices in the grandchild, 64 documents, the cell's ladder (K = 1, 2, 4), its
traffic scaled to the toy fleet, the comparison that decides ``correct``.  A
CPU run has no device plane, so the three readers of the device trace are
held by ``tests/test_mesh_served_fleet.py`` on a synthetic trace; every
reader of a counter or a span has to yield a value here.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "string4_uniform_wide"
CONFIG = "string_fleet_10k_mesh4"


sys.path.insert(0, os.path.join(REPO, "benchmark"))


def _load(*parts: str) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def _bench() -> dict:
    return _load("BENCHMARK.json")


def test_the_cell_rehearses_correct_with_every_counter_reader_yielding():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "3",
         "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert line["correct"] is True, line["checks"]
    assert all(v == [0, 0] for v in line["checks"].values()), line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    # The list-less readers of counters and spans, and the two new ones.
    for name in ("generator_late_p95_ms", "ingest_busy_share",
                 "upload_busy_share", "loop_ms_p50", "readback_wait_share",
                 "compiles_in_window", "shard_ops_skew", "shard_depth_skew"):
        assert name in line["metrics"], (name, sorted(line["metrics"]))
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["shard_ops_skew"]["value"] >= 1.0
    assert line["metrics"]["shard_depth_skew"]["value"] >= 1.0


def test_the_configuration_is_config_3_uncut_on_a_four_chip_mesh():
    bench = _bench()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    cfg = _load("benchmark", "configs", CONFIG + ".json")
    one = _load("benchmark", "configs", "string_fleet_1chip.json")
    assert (cfg["docs"], cfg["mesh"]) == (10000, 4)
    assert cfg["reduced_from"] == {}
    # Everything but the size and the mesh is string_fleet_1chip's.
    for key in ("plant", "geometry", "writers", "op_mix", "guarantees",
                "summary_acks_in_window", "fleet_main_flags"):
        assert cfg[key] == one[key], key
    assert cfg["rehearsal"]["docs"] == 64
    for key in ("cap_ops_per_s_per_doc", "max_obliterates_per_doc"):
        assert key in cfg["assumed"]


def test_the_cell_is_the_only_four_chip_cell_and_runs_the_accepted_traffic():
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "uniform_wide", 4)
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [CELL]
    own = _load("benchmark", "cells", CELL + ".json")
    assert own["params"]["rate_ops_per_s"] == 1600
    assert own["warm_seconds"] == 10
    assert [(b["docs"], b["depth"]) for b in own["ladder"]] == [
        (8, 1), (8, 33), (8, 97)]


@pytest.mark.parametrize("metric", [
    "mesh_step_device_ms", "mesh_step_roofline", "mesh_busy_skew",
    "shard_ops_skew", "shard_depth_skew"])
def test_a_new_reader_is_listed_for_the_cell_alone(metric):
    entry = next(m for m in _bench()["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "mesh (parallel/mesh.py)"
    mod = importlib.import_module("layer_metrics." + metric)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"])
