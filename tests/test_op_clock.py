"""The op's own clock (ISSUE 38): the sequencer's wire stamp -> received ->
applied, inside the program.

- the stamp probe (``wire_stamp``): the field order of ``to_json`` it leans
  on, contents that hold the key themselves, the oldest line of a feed;
- the clock's arithmetic (``OpClock``): row weights, three means that add up,
  the pending bound, a moved offset;
- both engines' feed path: a join-only feed records nothing, a feed without
  a stamp counts ``unstamped_rows``;
- the six readers, each on a synthetic ``ctx`` with a known answer, and None
  without ``op_clock`` (the parent commit's status lines);
- the served loop on the CPU (string cohort path, fleet-wide path, a docs
  mesh, the tree family): every status line carries ``op_clock`` and the
  program's own ``applied``, ``lag.stamps_of`` accepts that stream with no
  wrapper installed, and with ``fleet_child.install_stamps`` installed over
  it the two logs agree.
"""

from __future__ import annotations

import importlib
import json
import os
import random
import sys
import threading
import time

import pytest

from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine
from fluidframework_tpu.native.ingest_native import available
from fluidframework_tpu.observability import OpClock
from fluidframework_tpu.observability.op_clock import STAGES, wire_stamp
from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage
from fluidframework_tpu.utils.telemetry import Histogram

from test_tracing_loop import _Fleet, _edit, _join, server  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import lag  # noqa: E402

native = pytest.mark.skipif(not available(), reason="native ingest unavailable")


def _msg(seq=1, stamp=1727000000.5, contents=None, mtype=MessageType.OP,
         metadata=None) -> SequencedMessage:
    return SequencedMessage(
        client_id="w0", client_seq=seq, ref_seq=seq - 1, seq=seq, min_seq=0,
        type=mtype, contents=contents, metadata=metadata, timestamp=stamp,
        short_client=0)


def _insert(text: str, pos: int = 0) -> dict:
    return {"type": 0, "pos1": pos, "seg": text}


JOIN = SequencedMessage(
    client_id="__service__", client_seq=0, ref_seq=0, seq=0, min_seq=0,
    type=MessageType.JOIN, contents={"clientId": "w0", "short": 0},
    timestamp=1726999999.25)


# ------------------------------------------------------------ the stamp probe
def test_to_json_writes_the_stamp_after_contents_and_metadata():
    keys = list(json.loads(_msg(contents=_insert("ab")).to_json()))
    assert keys[-2:] == ["timestamp", "shortClient"]
    assert keys.index("contents") < keys.index("metadata") < keys.index(
        "timestamp")


@pytest.mark.parametrize("case,contents,metadata", [
    ("plain", _insert("ab"), None),
    ("inserted_text_holds_the_key", _insert(',"timestamp":7.5,'), None),
    ("inserted_text_is_a_whole_line", _insert(
        '{"a":1,"timestamp":3.25,"shortClient":1}\n'), None),
    ("nested_contents_hold_the_key", {
        "type": "edit", "changes": [{"timestamp": 9.75, "x": {
            "timestamp": 1.5}}], "timestamp": 2.5}, None),
    ("metadata_holds_the_key", _insert("ab"), {"timestamp": 4.25,
                                               "batch": True}),
])
def test_probe_reads_the_top_level_stamp(case, contents, metadata):
    m = _msg(contents=contents, metadata=metadata)
    assert wire_stamp(m.wire_line()) == 1727000000.5, case


def test_probe_takes_the_oldest_line_of_a_feed():
    feed = b"".join(
        _msg(seq=i, stamp=1727000000.0 + i, contents=_insert("ab")).wire_line()
        for i in (1, 2, 3))
    assert wire_stamp(feed) == 1727000001.0
    # A feed that opens with a join takes the join's stamp: still its oldest.
    assert wire_stamp(JOIN.wire_line() + feed) == 1726999999.25


@pytest.mark.parametrize("feed", [
    b"", b"\n", b'{"t":"resync","boot":true}\n',
    b'{"a":1,"timestamp":"soon","shortClient":1}\n',
    b'{"a":1,"timestamp":}\n',
])
def test_probe_finds_no_stamp(feed):
    assert wire_stamp(feed) == 0.0


def test_probe_reads_a_stamp_that_ends_its_line():
    assert wire_stamp(b'{"a":1,"timestamp":12.5}\n') == 12.5
    assert wire_stamp(b'{"a":1,"timestamp":12.5}') == 12.5


# ----------------------------------------------------- the clock's arithmetic
class _Clocks:
    """A monotonic clock and a wall clock ``offset`` ahead of it, both moved
    by hand."""

    def __init__(self, offset: float = 1.7e9) -> None:
        self.t, self.offset = 100.0, offset

    def mono(self) -> float:
        return self.t

    def wall(self) -> float:
        return self.t + self.offset


def _clock(**kw) -> tuple[OpClock, _Clocks]:
    c = _Clocks()
    return OpClock(clock=c.mono, wall=c.wall, **kw), c


def test_histogram_record_takes_a_weight():
    a, b = Histogram(), Histogram()
    a.record(0.004, 3)
    a.record(0.050)
    for v in (0.004, 0.004, 0.004, 0.050):
        b.record(v)
    assert a.to_wire() == b.to_wire()
    assert a.count == 4 and a.sum == pytest.approx(0.062)


def test_rows_weigh_the_samples_and_the_three_means_add_up():
    clock, c = _clock()
    # Sequenced at 100.000 and 100.010 (wall), received 30 and 5 ms later,
    # applied together at 100.050.
    clock.feed(c.wall() + 0.000, 100.030, 3, doc=0)
    clock.feed(c.wall() + 0.010, 100.015, 1, doc=1)
    assert clock.rows == 0                         # nothing resolved yet
    c.t = 100.050
    clock.resolve()
    s2r, r2a, s2a = (getattr(clock, s) for s in STAGES)
    assert clock.rows == s2r.count == r2a.count == s2a.count == 4
    assert s2r.sum == pytest.approx(3 * 0.030 + 0.005)
    assert r2a.sum == pytest.approx(3 * 0.020 + 0.035)
    assert s2a.sum == pytest.approx(3 * 0.050 + 0.040)
    assert s2r.sum / 4 + r2a.sum / 4 == pytest.approx(s2a.sum / 4)
    # The weighted median is the three-row feed's.
    assert s2a.percentile(0.5) == pytest.approx(0.050, rel=0.2)
    clock.resolve()                                # nothing pending: no-op
    assert clock.rows == 4


def test_a_stamp_ahead_of_the_clock_is_clamped_once():
    clock, c = _clock()
    clock.feed(c.wall() + 0.002, 100.0, 2)         # "received before sequenced"
    c.t = 100.010
    clock.resolve()
    assert clock.sequenced_to_received.sum == 0.0
    assert clock.sequenced_to_applied.sum == pytest.approx(
        clock.received_to_applied.sum)


def test_unstamped_and_empty_feeds():
    clock, c = _clock()
    clock.feed(0.0, 100.0, 5)
    clock.feed(c.wall(), 100.0, 0)                 # a join-only feed: no rows
    clock.feed_lines(b'{"t":"resync"}\n', 100.0, 2)
    c.t = 100.5
    clock.resolve()
    assert clock.unstamped_rows == 7 and clock.rows == 0
    assert clock.sequenced_to_applied.count == 0
    assert clock.take_wire_age() is None


def test_the_pending_bound_counts_dropped_rows():
    clock, c = _clock()
    for _ in range(OpClock.PENDING_MAX):
        clock.feed(c.wall(), 100.0, 1)
    clock.feed(c.wall(), 100.0, 7)
    clock.feed(c.wall(), 100.0, 2)
    assert clock.dropped_rows == 9
    c.t = 100.1
    clock.resolve()
    assert clock.rows == OpClock.PENDING_MAX
    clock.feed(c.wall(), 100.1, 7)                 # room again
    clock.resolve()
    assert clock.rows == OpClock.PENDING_MAX + 7 and clock.dropped_rows == 9


def test_a_moved_offset_counts_a_clock_step():
    clock, c = _clock()
    assert clock.offset == pytest.approx(c.offset)
    c.t += 10.0
    assert clock.status()["clock_steps"] == 0      # both clocks moved on
    c.offset += 0.0005                             # slewing: under 1 ms
    assert clock.status()["clock_steps"] == 0
    c.offset += 0.25                               # the wall clock stepped
    st = clock.status()
    assert st["clock_steps"] == 1
    assert clock.offset == pytest.approx(c.offset)
    # From here on a stamp is converted with the new offset.
    clock.feed(c.wall(), c.t + 0.004, 1)
    c.t += 0.010
    clock.resolve()
    assert clock.sequenced_to_received.sum == pytest.approx(0.004)


def test_status_is_lossless_and_cumulative():
    clock, c = _clock()
    zero = clock.status()
    assert set(zero) == {*STAGES, "rows", "unstamped_rows", "dropped_rows",
                         "clock_steps"}
    assert all(zero[s]["count"] == 0 and zero[s]["buckets"] == {}
               for s in STAGES)
    clock.feed(c.wall(), 100.002, 4)
    c.t = 100.020
    clock.resolve()
    st = json.loads(json.dumps(clock.status()))
    back = Histogram.from_wire(st["sequenced_to_applied"])
    assert back.count == 4 and back.sum == pytest.approx(0.080)
    assert st["rows"] == 4


def test_wire_age_is_the_oldest_stamps_at_its_receipt():
    clock, c = _clock()
    clock.feed(c.wall() - 0.010, 100.001, 1)       # 11 ms old when received
    clock.feed(c.wall() - 0.040, 100.002, 1)       # the oldest: 42 ms
    clock.feed(c.wall() - 0.001, 100.003, 1)
    assert clock.take_wire_age() == pytest.approx(0.042)
    assert clock.take_wire_age() is None           # taken: the next pump's


def test_per_shard_histograms_follow_shard_of():
    c = _Clocks()
    clock = OpClock(2, lambda d: d % 2, clock=c.mono, wall=c.wall)
    clock.feed(c.wall(), 100.0, 3, doc=0)
    clock.feed(c.wall(), 100.0, 1, doc=5)
    c.t = 100.01
    clock.resolve()
    assert [h.count for h in clock.shard_latency] == [3, 1]
    assert {"op_latency", *STAGES, "op_latency_shard0",
            "op_latency_shard1"} == set(clock.histograms())
    assert clock.histograms()["op_latency"] is clock.sequenced_to_applied


# ------------------------------------------------------ the engines' feed path
def _string_engine(**kw) -> DocBatchEngine:
    return DocBatchEngine(
        2, max_segments=64, text_capacity=512, max_insert_len=8,
        ops_per_step=4, use_mesh=False, recovery="off", **kw)


def test_latency_sample_every_is_gone():
    import inspect

    assert "latency_sample_every" not in inspect.signature(
        DocBatchEngine.__init__).parameters
    eng = _string_engine()
    for gone in ("_lat_sample", "_lat_flush", "_lat_pending", "_lat_tick",
                 "op_latency", "latency_sample_every"):
        assert not hasattr(eng, gone), gone


@native
def test_string_feed_is_one_sample_weighted_by_its_rows():
    eng = _string_engine()
    now = time.time()
    # A join-only feed stages no rows and records nothing.
    assert eng.ingest_lines(0, JOIN.wire_line()) == 0
    assert eng.op_clock._pending == [] and eng.op_clock.unstamped_rows == 0
    feed = b"".join(
        _msg(seq=i, stamp=now - 0.5 + i, contents=_insert("ab")).wire_line()
        for i in (1, 2, 3))
    assert eng.ingest_lines(0, feed) == 3
    (pending,) = eng.op_clock._pending
    assert pending[2:] == (3, 0)
    # The oldest line's stamp, on the engine's clock: ~0.5 s ago.
    assert eng.op_clock.now() - pending[0] == pytest.approx(-0.5, abs=0.05)
    eng.step()
    s2a = eng.op_clock.sequenced_to_applied
    assert eng.op_clock.rows == s2a.count == 3
    assert eng.latency_histograms()["op_latency"] is s2a
    h = eng.health()
    assert h["latency_samples"] == 3 and h["latency_p50_ms"] >= 0


@native
def test_string_feed_without_a_stamp_counts_unstamped_rows():
    eng = _string_engine()
    eng.ingest_lines(0, JOIN.wire_line())
    feed = _msg(seq=1, stamp=0.0, contents=_insert("ab")).wire_line()
    assert eng.ingest_lines(0, feed) == 1
    eng.step()
    assert eng.op_clock.unstamped_rows == 1 and eng.op_clock.rows == 0
    assert "latency_p50_ms" not in eng.health()    # never the receipt time
    assert eng.text(0) == "ab"


def test_per_message_ingest_feeds_the_same_clock():
    eng = _string_engine()
    eng.ingest(0, JOIN)
    eng.ingest(0, _msg(seq=1, stamp=time.time(), contents=_insert("ab")))
    eng.ingest_batch([0], [_msg(seq=2, stamp=0.0, contents=_insert("cd"))])
    eng.step()
    assert eng.op_clock.rows == 1 and eng.op_clock.unstamped_rows == 1


@pytest.mark.parametrize("native_wire", [True, False])
def test_tree_feed_is_one_sample_on_either_decode(native_wire):
    import plants.shared_tree as shared_tree

    w = shared_tree.TreeWriter("w0")
    rng = random.Random(3)
    lines = []
    for seq in (1, 2):
        w.tree.submit_change(shared_tree.make_insert(
            [], "", 0, shared_tree._leaves(rng, 2)))
        (m,) = w.take_outbox()
        lines.append(SequencedMessage(
            client_id="w0", client_seq=m.client_seq, ref_seq=seq - 1, seq=seq,
            min_seq=0, type=MessageType.OP, contents=m.contents,
            timestamp=time.time() - 0.2, short_client=0))
        w.process(lines[-1])
    eng = TreeBatchEngine(1, capacity=64, ops_per_step=4,
                          native_wire=native_wire)
    assert eng.ingest_lines(0, JOIN.wire_line()) == 0
    assert eng.op_clock._pending == []
    rows = eng.ingest_lines(0, b"".join(m.wire_line() for m in lines))
    assert rows == 2
    ((_t_seq, _t_recv, n, doc),) = eng.op_clock._pending
    assert (n, doc) == (2, 0)
    eng.step()
    assert eng.op_clock.rows == 2
    hists = eng.latency_histograms()
    assert hists["op_latency"].count == 2 and "recovery_time" in hists
    # Sequenced 0.2 s before it was fed; the first step compiles.
    assert 0.2 <= hists["op_latency"].sum / 2 < 120.0
    assert eng.health()["latency_samples"] == 2
    # The per-message path: one row a message, unstamped where it has none.
    eng.ingest(0, SequencedMessage(
        client_id="w0", client_seq=9, ref_seq=2, seq=3, min_seq=0,
        type=MessageType.JOIN, contents=None))
    assert eng.op_clock.unstamped_rows == 0


# ------------------------------------------------------------- the six readers
def _reader(name: str):
    return importlib.import_module("layer_metrics." + name)


def _wire(samples) -> dict:
    h = Histogram()
    for v, n in samples:
        h.record(v, n)
    return h.to_wire()


def _line(rows, s2r, r2a, **counters) -> dict:
    """A status line whose ``op_clock`` holds the given (value, rows)
    samples; ``sequenced_to_applied`` is their sum feed by feed."""
    s2a = [(a + b, n) for (a, n), (b, _n) in zip(s2r, r2a)]
    clock = {"sequenced_to_received": _wire(s2r),
             "received_to_applied": _wire(r2a),
             "sequenced_to_applied": _wire(s2a),
             "rows": sum(n for _v, n in s2r), "unstamped_rows": 0,
             "dropped_rows": 0, "clock_steps": 0, **counters}
    return {"rows": rows, "health": {}, "op_clock": json.loads(
        json.dumps(clock))}


def _ctx(lines, **more) -> dict:
    return {"w0": 100.0, "w1": 110.0, "parsed": lines, "groups": [],
            "status": [], "traced": {"breakdown": {}}, **more}


BEFORE = ([(0.300, 10)], [(0.200, 10)])            # warm-up: a slow start
# Inside the window: 60 rows at 40 + 10 ms, 40 rows at 70 + 20 ms.
WINDOW = ([(0.300, 10), (0.040, 60), (0.070, 40)],
          [(0.200, 10), (0.010, 60), (0.020, 40)])


def _two_lines(**last_counters):
    return [(99.0, _line(3, [(0.5, 3)], [(0.5, 3)])),         # outside
            (101.0, _line(10, *BEFORE)),
            (105.0, _line(50, [(0.300, 10), (0.040, 40)],
                          [(0.200, 10), (0.010, 40)])),
            (109.0, _line(110, *WINDOW, **last_counters)),
            (111.0, _line(200, [(9.0, 200)], [(9.0, 200)]))]  # outside


@pytest.mark.parametrize("metric,want", [
    # Interpolated inside the bucket that holds the quantile, 19% wide.
    ("sequenced_to_received_ms_p50", 40.0),
    ("received_to_applied_ms_p50", 10.0),
    ("sequenced_to_applied_ms_p50", 50.0),
    ("sequenced_to_applied_ms_p95", 90.0),
])
def test_stage_readers_take_the_windows_delta(metric, want):
    mod = _reader(metric)
    got = mod.read(_ctx(_two_lines()))
    assert want / 2 ** 0.25 <= got <= want * 2 ** 0.25, got
    # No op_clock in the lines (the parent commit): nothing to read.
    bare = [(t, {"rows": s["rows"], "health": {}}) for t, s in _two_lines()]
    assert mod.read(_ctx(bare)) is None
    assert mod.read(_ctx([])) is None
    # One line inside the window, or nothing resolved between the two.
    assert mod.read(_ctx(_two_lines()[:2])) is None
    same = _two_lines()
    assert mod.read(_ctx([same[1], (102.0, same[1][1])])) is None


def test_window_means_add_up_and_percentile_interpolates():
    oc = _reader("sequenced_to_applied_ms_p50")
    ctx = _ctx(_two_lines())
    means = {s: oc.mean_ms(oc.stage_delta(ctx, s)) for s in oc.STAGES}
    assert means["sequenced_to_received"] == pytest.approx(
        (60 * 40 + 40 * 70) / 100)
    assert means["received_to_applied"] == pytest.approx(
        (60 * 10 + 40 * 20) / 100)
    assert means["sequenced_to_applied"] == pytest.approx(
        means["sequenced_to_received"] + means["received_to_applied"])
    # One bucket holding 10 rows between 10 and 20 ms: linear inside it.
    delta = {"base": 0.010, "growth": 2.0, "count": 10, "sum": 0.15,
             "buckets": {1: 10}}
    assert oc.percentile_ms(delta, 0.5) == pytest.approx(15.0)
    assert oc.percentile_ms(delta, 1.0) == pytest.approx(20.0)
    assert oc.percentile_ms(None, 0.5) is None


def test_coverage_share():
    mod = _reader("op_clock_coverage_share")
    assert mod.read(_ctx(_two_lines())) == pytest.approx(100.0)
    # Five of the window's hundred rows left out as unstamped.
    short = _two_lines()
    short[3][1]["op_clock"]["rows"] -= 5
    short[3][1]["op_clock"]["unstamped_rows"] = 5
    assert mod.read(_ctx(short)) == pytest.approx(95.0)
    # A stepped wall clock inside the window: no reading.
    assert mod.read(_ctx(_two_lines(clock_steps=1))) is None
    bare = [(t, {"rows": s["rows"], "health": {}}) for t, s in _two_lines()]
    assert mod.read(_ctx(bare)) is None


def test_lag_before_the_sequencer_is_the_lag_less_the_clocks_mean():
    mod = _reader("lag_before_sequencer_ms_mean")
    # Flushes as run.py keeps them: (due, sent_at, ops sent so far, ops,
    # doc).  60 ops due at 100.90 and 40 at 104.90, proved applied by the
    # stamps at 100.98 and 105.02: lags 80 and 120 ms, mean 96.
    groups = [(100.90, 100.91, 70, 60, 0), (104.90, 104.92, 110, 40, 1)]
    status = [(100.5, 10), (100.98, 70), (105.02, 110)]
    ctx = _ctx(_two_lines(), groups=groups, status=status)
    # The clock's mean over the same window: 0.6 * 50 + 0.4 * 90 = 66 ms.
    assert mod.read(ctx) == pytest.approx(96.0 - 66.0)
    win = ctx["traced"]["breakdown"]["op_clock_window"]
    assert win["lag_ms_mean"] == pytest.approx(96.0)
    assert win["sequenced_to_received_ms_mean"] + win[
        "received_to_applied_ms_mean"] == pytest.approx(
            win["sequenced_to_applied_ms_mean"])
    assert (win["rows"], win["unstamped_rows"], win["dropped_rows"],
            win["clock_steps"]) == (100, 0, 0, 0)
    # An op no stamp covers: no reading.
    assert mod.read(_ctx(_two_lines(), groups=groups,
                         status=status[:2])) is None
    bare = [(t, {"rows": s["rows"], "health": {}}) for t, s in _two_lines()]
    assert mod.read(_ctx(bare, groups=groups, status=status)) is None
    assert mod.read(_ctx(_two_lines())) is None    # no flush in the window


def test_benchmark_json_lists_the_six_for_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = bench["per_layer"][-6:]
    assert [m["name"] for m in mine] == [
        "sequenced_to_received_ms_p50", "received_to_applied_ms_p50",
        "sequenced_to_applied_ms_p50", "sequenced_to_applied_ms_p95",
        "lag_before_sequencer_ms_mean", "op_clock_coverage_share"]
    for m in mine:
        assert "workloads" not in m and m["source"] == "program_counter"
        mod = _reader(m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])


# ------------------------------------------------------------ the served loop
def _tree_join(server, doc_ids):
    import plants.shared_tree as shared_tree

    out = {}
    with server.lock:
        for doc_id in doc_ids:
            doc = server.service.document(doc_id)
            w = shared_tree.TreeWriter(f"{doc_id}-w")
            doc.connect(w.client_id, w.process)
            doc.process_all()
            out[doc_id] = w
    return out


def _tree_edit(server, writers, doc_ids, rng) -> int:
    import plants.shared_tree as shared_tree

    n = 0
    with server.lock:
        for doc_id in doc_ids:
            w = writers[doc_id]
            w.tree.submit_change(shared_tree.make_insert(
                [], "", 0, shared_tree._leaves(rng, 2)))
            doc = server.service.document(doc_id)
            for m in w.take_outbox():
                doc.submit(m)
                n += 1
            doc.process_all()
    return n


# ``fleet_main.main`` under the benchmark's wrapper, as ``fleet_child.py``
# installs it, with the program's own stamps kept beside the wrapper's.
_WRAPPED = """
import sys
sys.path.insert(0, {bench!r})
import fleet_child
from fluidframework_tpu.server import fleet_consumer, fleet_main
snapshot = fleet_main.status_snapshot
def keep(*args, **kwargs):
    snap = snapshot(*args, **kwargs)
    snap["program_applied"] = snap["applied"]
    snap["program_applied_dropped"] = snap["applied_dropped"]
    return snap
fleet_main.status_snapshot = keep
fleet_child.install_stamps(
    fleet_main, fleet_consumer.FleetConsumer, fleet_child.StampLog())
sys.exit(fleet_main.main(sys.argv[1:]))
"""


class _WrappedFleet(_Fleet):
    LAUNCH = ("-c", _WRAPPED.format(bench=os.path.join(REPO, "benchmark")))


def _status_lines(fleet):
    """Every JSON line up to the ``done`` line, with its arrival time."""
    out = []
    while True:
        line = fleet.proc.stdout.readline()
        assert line, fleet.proc.stderr.read()[-800:]
        obj = json.loads(line)
        out.append((time.perf_counter(), obj))
        if obj.get("done"):
            return out


@native
@pytest.mark.parametrize("path,busy_docs,extra,wrapped", [
    ("cohort", 1, (), False),
    ("full", 8, (), False),
    ("mesh", 8, ("--mesh", "2"), False),
    ("tree", 2, ("--family", "tree"), False),
    ("cohort", 1, (), True),
    ("tree", 2, ("--family", "tree"), True),
])
def test_served_loop_carries_the_clock_and_stamps_its_steps(
        server, tmp_path, path, busy_docs, extra, wrapped):
    doc_ids = [f"t{i}" for i in range(8)]
    tree = path == "tree"
    rng = random.Random(5)
    writers = (_tree_join if tree else _join)(server, doc_ids)
    rounds = 10
    cls = _WrappedFleet if wrapped else _Fleet
    fleet = cls(server, doc_ids, tmp_path / "flight.json",
                ("--exit-after-rows", str(rounds * busy_docs), *extra))
    busy = doc_ids[:busy_docs]
    try:
        fleet.wait_line("ready")

        def traffic():
            for _ in range(rounds):
                if tree:
                    _tree_edit(server, writers, busy, rng)
                else:
                    _edit(server, writers, busy, "ab")
                time.sleep(0.06)

        sender = threading.Thread(target=traffic, daemon=True)
        sender.start()
        lines = _status_lines(fleet)
        sender.join(timeout=30)
        assert fleet.finish() == 0
    finally:
        fleet.finish(timeout=5)

    status = [(t, s) for t, s in lines if "rows" in s]
    assert status and status[-1][1].get("done")
    done = status[-1][1]
    assert done["errors"] == 0 and done["rows"] == rounds * busy_docs
    # Every status line, from the first to the done line, carries the clock
    # and the program's own stamps, zeros included.
    for _t, s in status:
        assert set(s["op_clock"]) == {
            *STAGES, "rows", "unstamped_rows", "dropped_rows", "clock_steps"}
        assert isinstance(s["applied"], list) and s["applied_dropped"] == 0
        assert all(len(a) == 3 for a in s["applied"])
    clock = done["op_clock"]
    # Every applied row is on the clock, none unstamped, none dropped.
    assert clock["rows"] == done["rows"]
    assert (clock["unstamped_rows"], clock["dropped_rows"],
            clock["clock_steps"]) == (0, 0, 0)
    s2r, r2a, s2a = (clock[s] for s in STAGES)
    assert s2r["count"] == r2a["count"] == s2a["count"] == done["rows"]
    assert s2r["sum"] + r2a["sum"] == pytest.approx(s2a["sum"], abs=1e-6)
    # Sequencer and fleet share this machine's wall clock: an op is received
    # after it was sequenced, and applied well inside the run.
    assert 0 < s2a["min"] and s2a["max"] < 60.0
    # ``lag.stamps_of`` accepts the program's stream as it stands.
    key = "program_applied" if wrapped else "applied"
    stamps = lag.stamps_of([
        (t, s["rows"], s[key], s[key + "_dropped"]) for t, s in status])
    assert stamps and stamps[-1][1] == done["rows"]
    assert all(seen <= t for t, _r, seen in stamps)
    if wrapped:
        # The wrapper's values replaced the program's in the line, and the
        # two logs agree: same steps, same rows, t_applied within 1 ms;
        # the program saw a feed no later than the wrapper, and earlier
        # where it had read the feed while the step before was in flight.
        theirs = lag.stamps_of([
            (t, s["rows"], s["applied"], s["applied_dropped"])
            for t, s in status])
        assert [r for _t, r, _s in theirs] == [r for _t, r, _s in stamps]
        for (t_a, _r, seen_a), (t_b, _r2, seen_b) in zip(theirs, stamps):
            assert 0 <= t_a - t_b < 1e-3, (t_a, t_b)
            assert seen_b - seen_a < 1e-3
    else:
        # The flight recorder's pump spans carry the wire's age.
        with open(tmp_path / "flight.json") as f:
            pumps = [e for e in json.load(f)["traceEvents"]
                     if e["name"] == "pump" and e.get("args", {}).get("staged")]
        assert pumps and all(
            0 <= p["args"]["wire_age_ms"] < 60e3 for p in pumps)
