"""The per-layer readers ISSUE 23 adds, each on a synthetic ``ctx`` with a
known answer, and None where there is nothing to read (as at a parent commit
that has no such span, scope or counter)."""

from __future__ import annotations

import importlib
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import host_plane  # noqa: E402


def _reader(name: str):
    return importlib.import_module("layer_metrics." + name)


def _ctx(flight=None, **more) -> dict:
    return {"w0": 100.0, "w1": 110.0, "traced": {"flight": flight or []},
            "parsed": [], **more}


# ------------------------------------------------------------ span shares
@pytest.mark.parametrize("metric,span", [
    ("status_busy_share", "status"),
    ("pack_busy_share", "pack"),
    ("pump_select_share", "pump.select"),
    ("gather_busy_share", "gather"),
    ("scatter_busy_share", "scatter"),
    ("recover_busy_share", "recover"),
])
def test_span_share_readers(metric, span):
    flight = [
        (span, 99.5, 100.5, {}),       # clipped to the window: 0.5 s
        (span, 104.0, 105.0, {}),      # 1.0 s
        ("readback", 101.0, 109.0, {}),
    ]
    assert _reader(metric).read(_ctx(flight)) == pytest.approx(15.0)
    assert _reader(metric).read(_ctx([("readback", 101.0, 102.0, {})])) is None
    assert _reader(metric).read(_ctx()) is None


def test_loop_named_share_is_the_union_over_the_loops_lifetime():
    mod = _reader("loop_named_share")
    flight = [
        ("pump", 100.0, 101.0, {}), ("ingest", 100.2, 100.4, {}),
        ("step", 101.0, 104.0, {}), ("readback", 102.0, 104.0, {}),
        ("status", 104.5, 105.0, {}),          # 0.5 s of the loop unnamed
        ("idle", 105.0, 108.0, {}),
    ]
    # The loop ran 100..108 inside the window 100..110; 7.5 s are named.
    assert mod.read(_ctx(flight)) == pytest.approx(100.0 * 7.5 / 8.0)
    assert mod.read(_ctx([("readback", 101.0, 102.0, {})])) is None


# --------------------------------------------------- device idle, by span
def _host_plane(spans=None, gaps=(), kernel_ns=None, own_ns=None):
    return {
        "host": {"line": "python3", "spans": spans} if spans else None,
        "device": {"planes": ["/device:TPU:0"], "first_ns": 0,
                   "last_ns": 1000, "gaps": [list(g) for g in gaps],
                   "step_executions": 1.0, "kernel_ns": kernel_ns or {},
                   "kernel_own_scope_ns": own_ns,
                   "unscoped_top": []},
    }


@pytest.mark.parametrize("metric,want", [
    ("device_idle_in_status_share", 8.0),    # 150..230 of the gap 100..300
    ("device_idle_in_pump_share", 7.0),      # 230..300, and 700..710 is busy
])
def test_device_idle_inside_a_known_annotation(metric, want):
    spans = {"status": [[150, 80]], "pump": [[230, 100], [700, 10]],
             "readback": [[400, 100]]}
    ctx = _ctx(host_plane=_host_plane(spans, gaps=[(100, 300), (800, 850)]))
    assert _reader(metric).read(ctx) == pytest.approx(want)
    # No annotations (the parent commit), or no profile at all.
    assert _reader(metric).read(
        _ctx(host_plane=_host_plane(None, gaps=[(100, 300)]))) is None
    assert _reader(metric).read(_ctx(host_plane=None)) is None


def test_overlap_of_gaps_and_spans():
    assert host_plane.overlap_s(
        [(0, 10), (20, 30)], [(5, 25), (28, 40)]) == 5 + 5 + 2
    assert host_plane.overlap_s([(0, 10)], []) == 0


# ---------------------------------------------------------- kernel scopes
def test_scope_of_takes_the_outermost_kernel_scope():
    base = "jit(_fleet_step)/vmap()/cond/branch_0_fun/while/body/closed_call"
    assert host_plane.scope_of(
        base + "/insert/ensure_boundary/open_slot/select_n:") == "insert"
    assert host_plane.scope_of(
        base + "/obliterate/cond/remove") == "obliterate"
    assert host_plane.scope_of(base + "/select_n:") == "unscoped"
    assert host_plane.scope_of(None) == "unscoped"


def test_step_self_times_count_whole_step_executions_only():
    # Modules: a step the trace's start cut, a gather, a whole step
    # (100..200), a scatter, a step the end cut.
    mods = [("jit__fleet_step", 0, 10), ("jit__gather_cohort_jit", 20, 10),
            ("jit__fleet_step", 100, 100),
            ("jit__scatter_cohort_jit", 210, 5), ("jit__fleet_step", 300, 50)]
    ops = [(1, 0, 10),                       # in the cut step: not counted
           (3, 100, 100),                    # a while: self 100 - 70 = 30
           (1, 110, 40), (2, 150, 20), (4, 170, 10),
           (2, 210, 5),                      # the scatter: not a step
           (1, 300, 50)]
    self_ns, n = host_plane.step_self_times(ops, mods)
    assert n == 1
    assert self_ns == {3: 30, 1: 40, 2: 20, 4: 10}
    assert host_plane.step_self_times(ops, mods[:2]) == ({}, 0)


def test_a_fusion_takes_the_scopes_of_what_was_fused_into_it():
    base = "jit(_fleet_step)/vmap()/while/body/closed_call"
    program = {
        "instructions": {
            "fusion.7": ("fusion", "", [11]),
            "fusion.8": ("fusion", base + "/ack/select_n", [12]),
            "copy.3": ("copy", "", []),
        },
        "computations": {
            11: [("parameter", ""), ("constant", ""),
                 ("compare", base + "/insert/open_slot/ge"),
                 ("select", base + "/insert/open_slot/select_n"),
                 ("add", base + "/remove/mark_range/add"),
                 ("select", base + "/select_n"),
                 ("tuple", "")],
            12: [("select", base + "/insert/select_n")],
        },
    }
    scopes = host_plane.instruction_scopes
    assert scopes(program, "fusion.7", None) == {
        "insert": 0.5, "remove": 0.25, "unscoped": 0.25}
    # An op_name of its own wins, on the event or in the program.
    assert scopes(program, "fusion.7", base + "/obliterate/x:") == {
        "obliterate": 1.0}
    assert scopes(program, "fusion.8", None) == {"ack": 1.0}
    assert scopes(program, "copy.3", None) == {"unscoped": 1.0}
    assert scopes(program, "nowhere.1", None) == {"unscoped": 1.0}
    assert scopes(None, "fusion.7", base + "/select_n:") == {"unscoped": 1.0}
    assert host_plane.instruction_name(
        "%fusion.7 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop") == "fusion.7"


@pytest.mark.parametrize("scope,want", [
    ("insert", 50.0), ("remove", 20.0), ("annotate", 0.0),
    ("obliterate", 0.0), ("ack", 0.0), ("unscoped", 30.0),
])
def test_kernel_share_readers_add_to_100(scope, want):
    ns = {"unscoped": 30, "insert": 50, "remove": 20}
    mod = _reader(f"kernel_{scope}_share")
    assert mod.read(_ctx(host_plane=_host_plane(kernel_ns=ns))) == want
    # A program with no scope anywhere has nothing to read: not "100%
    # unscoped".
    assert mod.read(_ctx(host_plane=_host_plane(
        kernel_ns={"unscoped": 70}))) is None
    assert mod.read(_ctx(host_plane=None)) is None


def test_kernel_own_scope_share_is_what_no_fusion_split_attributed():
    ns = {"unscoped": 30, "insert": 50, "remove": 20}
    mod = _reader("kernel_own_scope_share")
    assert mod.read(_ctx(host_plane=_host_plane(
        kernel_ns=ns, own_ns=45))) == 45.0
    # Nothing scoped at all (the parent commit), an adapter result from
    # before the field, no profile.
    assert mod.read(_ctx(host_plane=_host_plane(
        kernel_ns={"unscoped": 70}, own_ns=0))) is None
    assert mod.read(_ctx(host_plane=_host_plane(kernel_ns=ns))) is None
    assert mod.read(_ctx(host_plane=None)) is None


def _step_planes(program_stats, hlo=None, tf_op=None):
    """A device plane with three executions of one step program (one whole)
    holding one fusion, and the metadata plane's entry for the program."""
    op_stats = {"program_id": 9}
    if tf_op:
        op_stats["tf_op"] = tf_op
    device = {
        "name": "/device:TPU:0",
        "metadata": {1: ("%fusion.7 = s32[8]{0} fusion()", op_stats),
                     2: ("jit__fleet_step(9)", {})},
        "lines": [
            {"name": "XLA Ops",
             "events": [(1, 0, 10), (1, 100, 80), (1, 300, 10)]},
            {"name": "XLA Modules",
             "events": [(2, 0, 10), (2, 100, 100), (2, 300, 10)]},
        ],
    }
    meta = {"name": "/host:metadata", "lines": [],
            "metadata": {9: ("jit__fleet_step(9)", program_stats)}}
    return [device, meta]


def test_reduce_planes_splits_a_fusion_and_counts_own_scopes(monkeypatch):
    base = "jit(_fleet_step)/vmap()/while/body"
    program = {
        "instructions": {"fusion.7": ("fusion", "", [11]),
                         "add.1": ("add", base + "/insert/add", [])},
        "computations": {11: [("add", base + "/insert/add"),
                              ("select", base + "/select_n")]},
    }
    monkeypatch.setattr(host_plane, "hlo_program", lambda buf: program)
    dev = host_plane.reduce_planes(
        _step_planes({"Hlo Proto": b"x"}))["device"]
    assert dev["step_executions"] == 1 and dev["step_programs_with_hlo"] == 1
    assert dev["kernel_ns"] == {"insert": 40.0, "unscoped": 40.0}
    assert dev["kernel_own_scope_ns"] == 0.0
    # The event's own tf_op names the scope: nothing is split.
    dev = host_plane.reduce_planes(_step_planes(
        {"Hlo Proto": b"x"}, tf_op=base + "/remove/mark_range/x:"))["device"]
    assert dev["kernel_ns"] == {"remove": 80.0}
    assert dev["kernel_own_scope_ns"] == 80.0


def test_a_step_program_without_hlo_fails_the_run_loudly(monkeypatch):
    from traces import BenchFailure

    # The profile holds the program but not its HloProto (or the stat's
    # name moved): not "100% unscoped".
    with pytest.raises(BenchFailure, match="have no 'Hlo Proto'"):
        host_plane.reduce_planes(_step_planes({}))
    # An op that names its own scope needs no HLO.
    dev = host_plane.reduce_planes(
        _step_planes({}, tf_op="jit(f)/ack/x:"))["device"]
    assert dev["kernel_ns"] == {"ack": 80.0}
    # A decoded program that cannot be what XLA wrote.
    for broken, why in [
        ({"instructions": {}, "computations": {}}, "no instruction"),
        ({"instructions": {"fusion.7": ("fusion", "", [11])},
          "computations": {11: [("add", "")]}}, "has an op_name"),
        ({"instructions": {"fusion.7": ("fusion", "jit(f)/x", [])},
          "computations": {}}, "no fused computation"),
    ]:
        monkeypatch.setattr(host_plane, "hlo_program", lambda buf: broken)
        with pytest.raises(BenchFailure, match=why):
            host_plane.reduce_planes(_step_planes({"Hlo Proto": b"x"}))


# ---------------------------------------------------------- clock pairing
def test_clock_pairing_error_is_the_offset_the_stop_stamp_missed():
    mod = _reader("clock_pairing_error_ms")
    # Flight recorder: a readback every 60 ms or so, perf_counter seconds.
    starts = [50.0, 50.061, 50.119, 50.183, 50.240, 50.302, 50.359]
    flight = [("readback", s, s + 0.04, {}) for s in starts]
    # The profiler saw the 3rd..6th, on its own clock (ns): perf = prof/1e9
    # + 49.0.  The last device event ended at prof 1.35e9, i.e. perf 50.35,
    # but stop_trace was stamped 4 ms later: the pairing is 4 ms off.
    notes = [[round((s - 49.0) * 1e9), 40_000_000] for s in starts[2:6]]
    hp = _host_plane({"readback": notes, "pump": [[0, 1]]})
    hp["device"]["last_ns"] = 1_350_000_000
    ctx = _ctx(flight, host_plane=hp)
    ctx["traced"]["clock"] = {"stop_perf_ns": 50_354_000_000}
    assert mod.read(ctx) == pytest.approx(4.0, abs=1e-3)
    ctx["traced"]["clock"] = {"stop_perf_ns": 50_350_000_000}
    assert mod.read(ctx) == pytest.approx(0.0, abs=1e-3)
    assert mod.read(_ctx(flight, host_plane=_host_plane(None))) is None


def test_align_finds_the_run_by_its_intervals():
    whole = [0.0, 1.0, 2.5, 3.0, 5.0, 5.5, 8.0]
    assert host_plane.align([102.5, 103.0, 105.0], whole) == 2
    assert host_plane.align([5.4], whole) == 5
    assert host_plane.align([], whole) is None


# ------------------------------------------------------ status-line counters
def _line(traces=0, trace_s=0.0, lower_s=0.0, old=False):
    compile_ = {"requests": 1, "cache_hits": 1}
    if not old:
        compile_.update(traces=traces, trace_seconds=trace_s,
                        lower_seconds=lower_s)
    return {"compile": compile_, "health": {}}


def test_setup_trace_lower_s_reads_the_windows_first_line():
    mod = _reader("setup_trace_lower_s")
    ctx = _ctx(parsed=[(99.5, _line(10, 1.0, 2.0)),
                       (100.5, _line(40, 100.5, 40.25)),
                       (109.0, _line(40, 100.5, 40.25))])
    assert mod.read(ctx) == 140.75
    assert mod.read(_ctx(parsed=[(100.5, _line(old=True))])) is None
    assert mod.read(_ctx()) is None


def test_traces_in_window_counts_up_to_the_done_line():
    mod = _reader("traces_in_window")
    ctx = _ctx(parsed=[(100.5, _line(40)), (109.0, _line(40))],
               final=_line(43))
    assert mod.read(ctx) == 3
    ctx["final"] = None
    assert mod.read(ctx) == 0
    assert mod.read(_ctx(parsed=[(100.5, _line(old=True)),
                                 (101.0, _line(old=True))])) is None


# ------------------------------------------------- the xplane wire reader
def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _f(no: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def test_read_xspace_decodes_events_and_metadata_stats(tmp_path):
    stat_meta = [_f(5, _f(1, 7) + _f(2, _f(1, 7) + _f(2, "tf_op"))),
                 _f(5, _f(1, 8) + _f(2, _f(1, 8) + _f(2, "jit(f)/remove/x:")))]
    # Event metadata 1 carries tf_op as a string, 2 as a reference to a
    # stat's name, 3 has none.
    ev_meta = [
        _f(4, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "%fusion.1 = s32[4] fusion()")
                             + _f(5, _f(1, 7) + _f(5, "jit(f)/insert/y:")))),
        _f(4, _f(1, 2) + _f(2, _f(1, 2) + _f(2, "%fusion.2 = s32[4] fusion()")
                             + _f(5, _f(1, 7) + _f(7, 8)))),
        _f(4, _f(1, 3) + _f(2, _f(1, 3) + _f(2, "jit__fleet_step(9)"))),
    ]
    ops_line = _f(3, _f(2, "XLA Ops") + _f(3, 1000)
                  + _f(4, _f(1, 1) + _f(2, 5_000_000) + _f(3, 2_000_000))
                  + _f(4, _f(1, 2) + _f(2, 9_000_000) + _f(3, 1_000_000)))
    skipped = _f(3, _f(2, "Steps") + _f(4, _f(1, 3)))
    plane = _f(1, _f(2, "/device:TPU:0") + ops_line + skipped
               + b"".join(ev_meta) + b"".join(stat_meta))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(plane + _f(4, "host"))
    (got,) = host_plane.read_xspace(
        str(path), lambda plane, line: line == "XLA Ops")
    assert got["name"] == "/device:TPU:0"
    assert got["lines"] == [{"name": "XLA Ops", "events": [
        (1, 1000 + 5000, 2000), (2, 1000 + 9000, 1000)]}]
    assert got["metadata"][1] == (
        "%fusion.1 = s32[4] fusion()", {"tf_op": "jit(f)/insert/y:"})
    assert got["metadata"][2][1] == {"tf_op": "jit(f)/remove/x:"}
    assert got["metadata"][3] == ("jit__fleet_step(9)", {})
    assert host_plane._i64((1 << 64) - 5) == -5


def test_hlo_program_decodes_instructions_and_fused_computations():
    def instr(name, opcode, op_name="", called=()):
        body = _f(1, name) + _f(2, opcode)
        if op_name:
            body += _f(7, _f(1, "ignored") + _f(2, op_name))
        for c in called:
            body += _f(38, c)
        return _f(2, body)

    fused = _f(3, _f(1, "fused_computation.7") + _f(5, 11)
               + instr("p0", "parameter")
               + instr("ge.1", "compare", "jit(f)/insert/ge"))
    packed = _f(1, "fusion.9") + _f(2, "fusion") + (
        _varint(38 << 3 | 2) + bytes([2, 11, 12]))
    entry = _f(3, _f(1, "main") + _f(5, 1)
               + instr("fusion.7", "fusion", called=(11,)) + _f(2, packed))
    proto = _f(1, _f(1, "jit__fleet_step") + fused + entry) + _f(3, "buffers")
    got = host_plane.hlo_program(memoryview(proto))
    assert got["instructions"]["fusion.7"] == ("fusion", "", [11])
    assert got["instructions"]["fusion.9"] == ("fusion", "", [11, 12])
    assert got["instructions"]["ge.1"] == ("compare", "jit(f)/insert/ge", [])
    assert got["computations"][11] == [
        ("parameter", ""), ("compare", "jit(f)/insert/ge")]
