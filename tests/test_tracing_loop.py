"""Every millisecond of the served loop has a name (ISSUE 23).

- ``fleet_main``'s loop, run as its own process with ``--trace``, leaves a
  flight recorder whose top-level spans (``pump``, ``step``, ``status``,
  ``idle``) do not overlap and cover the loop, with the children the serving
  path promises, on the cohort path, the fleet-wide path and a docs mesh;
- the recorder is written on SIGTERM too;
- a recorded span is a profiler annotation with the same name and labels, and
  nothing is annotated without a recorder;
- the merge-tree kernel's branches are named in the lowered step;
- ``CompileStats`` counts tracing and lowering.
"""

from __future__ import annotations

import functools
import json
import os
import re
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from fluidframework_tpu.dds.shared_string import SharedString
from fluidframework_tpu.native.ingest_native import available
from fluidframework_tpu.observability import flight_recorder as fr
from fluidframework_tpu.server.netserver import NetworkServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

TOP = ("pump", "step", "status", "idle")


@pytest.fixture
def server():
    srv = NetworkServer().start()
    yield srv
    srv.stop()


def _join(server, doc_ids) -> dict[str, SharedString]:
    out = {}
    with server.lock:
        for doc_id in doc_ids:
            doc = server.service.document(doc_id)
            c = SharedString(client_id=f"{doc_id}-w")
            doc.connect(c.client_id, c.process)
            doc.process_all()
            out[doc_id] = c
    return out


def _edit(server, writers, doc_ids, text: str) -> int:
    n = 0
    with server.lock:
        for doc_id in doc_ids:
            c = writers[doc_id]
            c.insert_text(0, text)
            doc = server.service.document(doc_id)
            for m in c.take_outbox():
                doc.submit(m)
                n += 1
            doc.process_all()
    return n


class _Fleet:
    """``fleet_main`` as its own process, with its JSON lines."""

    LAUNCH = ("-m", "fluidframework_tpu.server.fleet_main")

    def __init__(self, server, doc_ids, trace_path, extra=()):
        self.proc = subprocess.Popen(
            [sys.executable, *self.LAUNCH,
             "--port", str(server.port), "--docs", ",".join(doc_ids),
             "--capacity", "64", "--text-capacity", "512",
             "--ops-per-step", "4", "--megastep-k", "1",
             "--status-every", "0.02",
             "--trace", str(trace_path), *extra],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        )

    def wait_line(self, key: str, at_least=True, timeout: float = 240.0):
        """The next JSON line whose ``key`` is at least ``at_least``."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            line = self.proc.stdout.readline()
            if not line:
                break
            obj = json.loads(line)
            if obj.get(key, 0) >= at_least:
                return obj
        raise AssertionError(
            f"no {key!r} line; stderr: {self.proc.stderr.read()[-800:]}")

    def finish(self, timeout: float = 120.0) -> int:
        try:
            self.proc.communicate(timeout=timeout)
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.communicate()
        return self.proc.returncode


def _children(spans, parent):
    _n, p0, p1, _a = parent
    return [s for s in spans if s is not parent and p0 <= s[1] and s[2] <= p1]


@pytest.mark.skipif(not available(), reason="native ingest unavailable")
@pytest.mark.parametrize("path,busy_docs,extra", [
    ("cohort", 1, ()),
    ("full", 8, ()),
    ("mesh", 8, ("--mesh", "2")),
])
def test_served_loop_is_spanned(server, tmp_path, path, busy_docs, extra):
    import traces

    doc_ids = [f"t{i}" for i in range(8)]
    writers = _join(server, doc_ids)
    rounds = 12
    trace_path = tmp_path / "flight.json"
    fleet = _Fleet(server, doc_ids, trace_path,
                   ("--exit-after-rows", str(rounds * busy_docs), *extra))
    try:
        fleet.wait_line("ready")
        # The first round compiles the step program; the loop is judged on
        # what follows.
        _edit(server, writers, doc_ids[:busy_docs], "ab")
        fleet.wait_line("rows", at_least=busy_docs)
        for _ in range(rounds - 1):
            time.sleep(0.06)                       # idle stretches between
            _edit(server, writers, doc_ids[:busy_docs], "ab")
        done = fleet.wait_line("done")
        assert done["errors"] == 0
        assert fleet.finish() == 0
    finally:
        fleet.finish(timeout=5)

    with open(trace_path) as f:
        raw = json.load(f)["traceEvents"]
    main_tid = next(e["tid"] for e in raw if e["name"] == "step")
    spans = [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6,
              e.get("args") or {})
             for e in raw if e["ph"] == "X" and e["tid"] == main_tid]
    top = sorted((s for s in spans if s[0] in TOP), key=lambda s: s[1])
    assert {s[0] for s in top} == set(TOP)
    # Top-level spans follow one another and never overlap.
    for a, b in zip(top, top[1:]):
        assert b[1] >= a[2] - 1e-9, (a, b)
    # ... and together they cover the loop: from the end of the first step
    # (its dispatch compiles) to the end of the last one.
    steps = [s for s in top if s[0] == "step"]
    assert len(steps) >= 3
    t0, t1 = steps[0][2], steps[-1][2]
    covered, _merged = traces.union(
        (max(s[1], t0), min(s[2], t1)) for s in top
        if s[2] > t0 and s[1] < t1)
    assert covered / (t1 - t0) >= 0.98, covered / (t1 - t0)

    # A step's children, by the path its dispatch took.  (On one device a
    # pump can catch one or two documents of a wide burst: a cohort step.)
    want = {"pack", "upload", "dispatch", "readback", "recover",
            "housekeeping"}
    worked = [s for s in steps if s[3].get("slices")]
    kinds = set()
    for step in worked:
        kids = _children(spans, step)
        kind = next(k[3]["kind"] for k in kids if k[0] == "dispatch")
        kinds.add(kind)
        extra_kids = {"gather", "scatter"} if kind == "cohort" else set()
        assert want | extra_kids <= {k[0] for k in kids}, (step, kids)
        assert {"docs", "slices", "dispatches"} <= set(step[3])
        for k in kids:
            if k[0] == "pack":
                assert k[3]["kind"] == kind
            if k[0] == "dispatch" and path == "mesh":
                assert k[3]["shards"] == 2
    assert ("cohort" if path == "cohort" else "full") in kinds, kinds
    if path == "mesh":
        assert kinds == {"full"}
    for pump in (s for s in top if s[0] == "pump"):
        assert {"ready", "bytes", "staged"} <= set(pump[3])
        names = {k[0] for k in _children(spans, pump)}
        if pump[3]["staged"]:
            assert "ingest" in names
    assert any(s[0] == "pump.select" for s in spans)
    for status in (s for s in top if s[0] == "status"):
        assert {"status.errors", "status.health", "status.emit"} <= {
            k[0] for k in _children(spans, status)}
    # A pump that found nothing while an idle span was open left no event:
    # an idle stretch is ONE span however long it lasts.
    for idle in (s for s in top if s[0] == "idle"):
        assert not _children(spans, idle), idle


@pytest.mark.skipif(not available(), reason="native ingest unavailable")
def test_flight_recorder_is_written_on_sigterm(server, tmp_path):
    doc_ids = ["k0", "k1"]
    writers = _join(server, doc_ids)
    trace_path = tmp_path / "flight.json"
    fleet = _Fleet(server, doc_ids, trace_path)
    try:
        fleet.wait_line("ready")
        _edit(server, writers, doc_ids, "xy")
        time.sleep(0.5)
        fleet.proc.send_signal(signal.SIGTERM)
        tail = fleet.wait_line("events", timeout=60)
        assert fleet.finish() == 0
    finally:
        fleet.finish(timeout=5)
    assert tail["trace"] == str(trace_path) and tail["events"] > 0
    with open(trace_path) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert {"pump", "step"} <= names


# ------------------------------------------------- spans as annotations
def _profile(tmp_path, body):
    """Run ``body`` under a CPU profiler session with the Python tracer off
    (as benchmark/fleet_child.py sets it); returns the xplane's path."""
    import traces

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = traces.find_xplane(str(tmp_path))
    assert path is not None
    return path


def _host_events(path):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(ev.name, dict(ev.stats), ev.duration_ns)
                        for ev in line.events]
    return out


def test_recorded_span_is_a_profiler_annotation(tmp_path):
    import host_plane

    rec = fr.install(fr.FlightRecorder(64))
    try:
        def body():
            with fr.span("pump") as sp:
                with fr.span("readback", kind="error_vector"):
                    time.sleep(0.002)
                sp.set(ready=3, bytes=77, staged=5)

        path = _profile(tmp_path, body)
    finally:
        fr.uninstall()
    by_name = {n: (st, d) for n, st, d in _host_events(path)}
    assert by_name["pump"][0] == {"ready": 3, "bytes": 77, "staged": 5}
    assert by_name["readback"][0] == {"kind": "error_vector"}
    # The flight recorder holds the same two spans, same labels.
    got = {e.name: e for e in rec.events()}
    assert got["pump"].args == {"ready": 3, "bytes": 77, "staged": 5}
    assert abs(got["readback"].dur_ns - by_name["readback"][1]) < 1e6
    # The benchmark's adapter finds them on the serving thread's line.
    host = host_plane.reduce_file(path)["host"]
    assert len(host["spans"]["pump"]) == 1
    assert len(host["spans"]["readback"]) == 1
    start, dur = host["spans"]["readback"][0]
    assert abs(dur - by_name["readback"][1]) < 1e4
    p_start, p_dur = host["spans"]["pump"][0]
    assert p_start <= start and start + dur <= p_start + p_dur


def test_no_recorder_no_span_and_no_annotation(tmp_path):
    assert fr.recorder() is None
    assert fr.span("pump", ready=1) is fr._NULL_SPAN
    fr._NULL_SPAN.set(ready=2)   # labels at the end cost nothing either

    def body():
        with fr.span("pump", ready=1):
            time.sleep(0.001)

    path = _profile(tmp_path, body)
    assert "pump" not in {n for n, _st, _d in _host_events(path)}


# ------------------------------------------------------- kernel scopes
@functools.lru_cache(maxsize=1)
def _lowered_step_text() -> str:
    from fluidframework_tpu.models import doc_batch_engine as dbe
    from fluidframework_tpu.ops import mergetree_kernel as mk

    proto = mk.init_state(16, 2, 2, 64, 2)
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (2,) + x.shape), proto)
    ops = jnp.zeros((2, 2, mk.OP_FIELDS), jnp.int32)
    payloads = jnp.zeros((2, 2, 4), jnp.int32)
    return dbe._fleet_step.lower(state, ops, payloads).as_text(
        debug_info=True)


def test_every_apply_op_branch_is_scoped_in_the_lowered_step():
    # (since PR 25 the "branches" are the kind-specific parts of one body)
    from fluidframework_tpu.models import doc_batch_engine as dbe
    from fluidframework_tpu.ops import mergetree_kernel as mk

    text = _lowered_step_text()
    assert mk.BRANCH_SCOPES == (
        "insert", "remove", "annotate", "ack", "obliterate")
    # MLIR carries the scope path as the name of each op's location (inside
    # the scan body's closed call relative to it); XLA joins the parts into
    # the instruction's op_name.
    # What every row runs whatever its kind is under its own scope, with the
    # helpers nested as before; the kinds keep theirs for what only they do.
    assert mk.SHARED_SCOPE == "shared"
    for path in (*mk.BRANCH_SCOPES, "shared",
                 "shared/ensure_boundary/open_slot", "shared/mark_range"):
        assert re.search(rf'loc\("(?:[^"]*/)?{path}(?:/[^"]*)?"', text), path
    # The insert's slot is the second of the cuts' one rewrite: the kind
    # has no pass over the columns of its own.
    assert not re.search(r'loc\("(?:[^"]*/)?insert/open_slot', text)
    # No kind's scope encloses the shared phase: a trace reads it as no
    # kind's time, not as the first kind's that happens to call a helper.
    for kind in mk.BRANCH_SCOPES:
        assert not re.search(rf'loc\("(?:[^"]*/)?{kind}/ensure_boundary', text)
    # The gather is a named program, not jit__lambda.
    assert dbe._gather_cohort_jit.__name__ == "_gather_cohort_jit"


def test_what_the_cache_keys_on_does_not_name_the_checkouts_directory():
    """``compile_cache.enable()`` (conftest calls it) keys the persistent
    cache on the programs' metadata so that a renamed scope is not served
    an executable with the old names; the same source must then lower to
    the same metadata from any directory and under any caller: file names
    relative to the checkout, one frame a location."""
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    text = _lowered_step_text()
    files = set(re.findall(r'loc\("([^"]+\.py)"', text))
    assert "fluidframework_tpu/ops/mergetree_kernel.py" in files
    assert not [f for f in files if os.path.isabs(f)], files
    assert REPO not in text
    assert "callsite(" not in text       # no caller's frame in a location


def test_compact_is_scoped():
    from fluidframework_tpu.ops import mergetree_kernel as mk

    proto = mk.init_state(16, 2, 2, 64, 2)
    text = jax.jit(mk.compact).lower(proto).as_text(debug_info=True)
    assert re.search(r'loc\("(?:[^"]*/)?compact(?:/[^"]*)?"', text)


# ------------------------------------------------------- compile stats
def test_compile_stats_count_tracing_and_lowering():
    from fluidframework_tpu.utils.compile_cache import CompileStats

    stats = CompileStats().install()

    @jax.jit
    def fresh(x):
        return jnp.cos(x) * 3 + 1

    before = stats.snapshot()
    fresh(jnp.ones((5, 7))).block_until_ready()
    first = stats.snapshot()
    assert first["traces"] > before["traces"]
    assert first["trace_seconds"] > before["trace_seconds"]
    assert first["lower_seconds"] > before["lower_seconds"]
    fresh(jnp.ones((5, 7))).block_until_ready()
    second = stats.snapshot()
    assert second["traces"] == first["traces"]
    assert second["lower_seconds"] == first["lower_seconds"]
