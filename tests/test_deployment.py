"""Buildable deployment (VERDICT r4 next #8): the compose topology's
process set — netserver shards via the launcher + the fleet tier through
fleet_main's ACTUAL ``python -m`` __main__ path — boots as real OS
processes, carries ops end to end, and the packaging artifacts
(pyproject.toml, Dockerfile, deploy/compose.yaml) agree with each other.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from fluidframework_tpu.native.ingest_native import available
from fluidframework_tpu.server.launcher import launch, shard_index

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_compose_topology_smoke():
    """Boot the deploy/compose.yaml topology in miniature: 2 launcher-
    supervised netserver shard PROCESSES, writers editing through real
    TCP per shard_index routing, and one fleet_main PROCESS per shard
    (``python -m`` — the exact compose command) draining the firehose to
    a device engine and reporting converged texts."""
    if not available():
        pytest.skip("native ingest encoder unavailable")
    from fluidframework_tpu.dds.shared_string import SharedString
    from fluidframework_tpu.driver.network_driver import NetworkDeltaConnection

    doc_ids = ["doc0", "doc1", "doc2", "doc3"]
    dep = launch({"shards": [{"name": "s0"}, {"name": "s1"}]})
    fleets: list[subprocess.Popen] = []
    try:
        by_shard: dict[int, list[str]] = {0: [], 1: []}
        for d in doc_ids:
            by_shard[shard_index(d, 2)].append(d)
        assert all(by_shard.values()), by_shard

        # Writers: standalone SharedStrings over the REAL TCP delta stream
        # (the raw merge-tree wire the fleet tier's native encoder parses).
        expected: dict[str, str] = {}
        for d in doc_ids:
            _host, port, _http = dep.endpoint_for(d)
            ss = SharedString(client_id=f"w-{d}")
            conn = NetworkDeltaConnection(
                "127.0.0.1", port, d, ss.client_id, "write",
                listener=ss.process, nack_listener=None, signal_listener=None,
            )
            if conn.join_msg is not None:
                ss.process(conn.join_msg)
            conn.pump(block_s=0.2)
            ss.insert_text(0, f"content-{d}")
            for m in ss.take_outbox():
                conn.submit(m)
            conn.sync()
            conn.pump()
            expected[d] = ss.text
            assert expected[d] == f"content-{d}"
            conn.disconnect()

        for si, shard in enumerate(dep.shards):
            docs = ",".join(by_shard[si])
            fleets.append(subprocess.Popen(
                [sys.executable, "-m", "fluidframework_tpu.server.fleet_main",
                 "--port", str(shard.port), "--docs", docs,
                 # One op row per doc: exit only after EVERY doc's firehose
                 # catch-up landed (exiting at 1 races the other doc's
                 # in-flight catch-up bytes).
                 "--exit-after-rows", str(len(by_shard[si]))],
                stdout=subprocess.PIPE, text=True, cwd=REPO, env=ENV,
            ))
        for si, proc in enumerate(fleets):
            out, _ = proc.communicate(timeout=180)
            assert proc.returncode == 0, out[-500:]
            status = json.loads(out.strip().splitlines()[-1])
            assert status["done"] and status["errors"] == 0
            for d in by_shard[si]:
                assert status["texts"][d] == expected[d], (si, d)
    finally:
        for proc in fleets:
            if proc.poll() is None:
                proc.kill()
        dep.stop()


def test_packaging_artifacts_agree():
    """pyproject + Dockerfile + compose reference one buildable image:
    every compose `python -m` module imports, console scripts resolve,
    and the Dockerfile builds the image name compose runs."""
    import importlib

    compose = open(os.path.join(REPO, "deploy", "compose.yaml")).read()
    dockerfile = open(os.path.join(REPO, "Dockerfile")).read()
    pyproject = open(os.path.join(REPO, "pyproject.toml")).read()

    images = set(re.findall(r"image:\s*(\S+)", compose))
    assert images == {"fluidframework-tpu:latest"}
    assert "fluidframework-tpu" in pyproject

    for mod in set(re.findall(r'"python",\s*"-m",\s*\n?\s*"([\w.]+)"', compose)):
        importlib.import_module(mod)

    # Console entry points resolve to real callables.
    for ep in re.findall(r'fftpu-\w+ = "([\w.]+):(\w+)"', pyproject):
        mod, fn = ep
        assert callable(getattr(importlib.import_module(mod), fn)), ep

    # The Dockerfile copies everything its build steps touch.
    for needed in ("pyproject.toml", "fluidframework_tpu", "native"):
        assert re.search(rf"COPY .*{needed}", dockerfile), needed
    assert "pip install" in dockerfile


def test_launcher_supervise_restarts_crashed_shard():
    """The compose `restart: unless-stopped` analog: kill a shard process;
    the supervisor restarts it and the endpoint keeps serving."""
    import socket
    import time

    dep = launch({"shards": [{"name": "s0"}]}, supervise=True)
    try:
        port = dep.shards[0].port
        dep.shards[0].proc.kill()
        deadline = time.time() + 30
        ok = False
        while time.time() < deadline:
            try:
                s = socket.create_connection(("127.0.0.1", dep.shards[0].port), timeout=2)
                s.close()
                ok = True
                break
            except OSError:
                time.sleep(0.3)
        assert ok, "shard did not come back after kill"
        assert dep.shards[0].port == port  # stable endpoint
    finally:
        dep.stop()


def test_launcher_restart_budget_detects_crash_loop():
    """A shard that keeps dying must trip the restart budget: the
    supervisor backs off between respawns, then stops respawning and marks
    the shard crash-looped in the manifest — never an unconditional
    immediate relaunch loop hammering the same ports forever."""
    import time

    dep = launch({
        "shards": [{"name": "s0"}],
        "restartBudget": 2,
        "crashWindowS": 120.0,
        "restartBackoffS": 0.05,
        "maxRestartBackoffS": 0.2,
    }, supervise=True)
    try:
        deadline = time.time() + 90
        while time.time() < deadline:
            with dep._lock:
                looped = dep.shards[0].crash_looped
                proc = dep.shards[0].proc
            if looped:
                break
            if proc is not None and proc.poll() is None:
                proc.kill()  # the "crash", repeatedly
            time.sleep(0.1)
        m = dep.manifest()["shards"][0]
        assert m["crashLooped"] is True, "budget never tripped"
        assert m["pid"] is None
        # Respawns stopped AT the budget (initial launch is not a crash).
        assert m["restarts"] <= 2
        time.sleep(1.0)  # and it STAYS down
        with dep._lock:
            s = dep.shards[0]
            assert s.proc is None or s.proc.poll() is not None
            assert s.restarts == m["restarts"]  # no further respawns
    finally:
        dep.stop()


def test_launcher_heartbeat_and_promote_revives_crash_looped_shard():
    """The supervisor's fast-recovery surface (ISSUE 12): it stamps a
    liveness heartbeat file (the beacon a standby controller watches),
    and ``promote`` revives a shard the restart budget gave up on —
    fresh budget window, same spawn machinery, same ports."""
    import tempfile
    import time

    from fluidframework_tpu.server.failover import read_heartbeat

    hb_path = os.path.join(tempfile.mkdtemp(), "launcher-heartbeat.json")
    dep = launch({
        "shards": [{"name": "s0"}],
        "restartBudget": 1,
        "crashWindowS": 120.0,
        "restartBackoffS": 0.05,
        "maxRestartBackoffS": 0.1,
        "heartbeatFile": hb_path,
        "heartbeatEveryS": 0.1,
    }, supervise=True)
    try:
        deadline = time.time() + 30
        while time.time() < deadline and not os.path.exists(hb_path):
            time.sleep(0.1)
        rec, fresh = read_heartbeat(hb_path, stale_after_s=10.0)
        assert fresh and rec["shards"][0]["name"] == "s0"

        # Crash past the budget -> crashLooped, supervisor stands down.
        deadline = time.time() + 90
        while time.time() < deadline:
            with dep._lock:
                looped = dep.shards[0].crash_looped
                proc = dep.shards[0].proc
            if looped:
                break
            if proc is not None and proc.poll() is None:
                proc.kill()
            time.sleep(0.1)
        assert dep.manifest()["shards"][0]["crashLooped"] is True
        assert dep.promote("nope") is False  # unknown shard

        # Promote: the shard comes back on its ports with a fresh budget.
        assert dep.promote("s0") is True
        m = dep.manifest()["shards"][0]
        assert m["pid"] is not None and m["crashLooped"] is False
        assert dep.promote("s0") is False  # alive: nothing to promote
        # The heartbeat keeps stamping the revived manifest.
        deadline = time.time() + 10
        while time.time() < deadline:
            rec, fresh = read_heartbeat(hb_path, stale_after_s=1.0)
            if fresh and rec["shards"][0]["pid"] == m["pid"]:
                break
            time.sleep(0.1)
        assert fresh and rec["shards"][0]["pid"] == m["pid"]
    finally:
        dep.stop()
