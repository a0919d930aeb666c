"""The consumer reads its sockets while a step is in flight (PR 43).

``FleetConsumer.step`` hands the engine ``_read_ahead``; the engine calls it
once between the step's last dispatch and the readback that waits for it,
with the dispatch's error latch.  Until the device has produced the latch the
consumer sleeps in one ``select`` (a helper thread turns the latch into a
byte on a socket pair) and reads what the sockets deliver.  What it reads is
KEPT and handed to the engine by the next ``pump``: only bytes move early.
A CPU step is over before the seam is reached, so these tests put a latch of
their own in the seam (``_device_busy_until``: the "device" is done when the
test's condition holds) and hold the consumer to what the engine, the stamps
and the summary loop may see of it.
"""

from __future__ import annotations

import contextlib
import random
import select
import threading
import time

import pytest

from fluidframework_tpu.dds.shared_string import SharedString
from fluidframework_tpu.loadgen.coordinator import oracle_text
from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.native.ingest_native import available
from fluidframework_tpu.server.fleet_consumer import FleetConsumer
from fluidframework_tpu.server.netserver import NetworkServer

from test_fleet_consumer import _flush, _writers

pytestmark = pytest.mark.skipif(
    not available(), reason="native ingest encoder unavailable"
)


@pytest.fixture
def server():
    srv = NetworkServer().start()
    yield srv
    srv.stop()


def _engine(n_docs=2, **kw):
    kw.setdefault("recovery", "grow")   # a readback to wait for: a seam
    return DocBatchEngine(n_docs, max_segments=256, text_capacity=4096,
                          max_insert_len=8, ops_per_step=8, use_mesh=False,
                          **kw)


def _readable(fc, idx, timeout=10.0) -> None:
    """Block until document ``idx``'s socket holds bytes (read nothing)."""
    r, _w, _x = select.select([fc._socks[idx]], [], [], timeout)
    assert r, f"socket {idx} never became readable"
    time.sleep(0.05)    # the writer thread's frame is one send: all of it


class _Latch:
    """Stands in for a step's error latch: produced once ``done()`` holds
    (or after ``timeout`` seconds, so that a failing test ends)."""

    def __init__(self, done, timeout):
        self.done, self.deadline = done, time.monotonic() + timeout

    def is_ready(self) -> bool:
        return False

    def block_until_ready(self) -> None:
        while not self.done() and time.monotonic() < self.deadline:
            time.sleep(0.001)


@contextlib.contextmanager
def _device_busy_until(fc, done, timeout=10.0):
    """Every step of ``fc`` inside the block finds its device busy in the
    seam until ``done()`` holds."""
    seam = fc._read_ahead
    fc._read_ahead = lambda _latch: seam(_Latch(done, timeout))
    try:
        yield
    finally:
        del fc._read_ahead


def _record_ingests(eng) -> list:
    """Every ``ingest_lines`` call, in order: ``(doc index, bytes)``."""
    calls = []
    ingest = eng.ingest_lines

    def ingest_(idx, data):            # the benchmark's control wraps it so
        calls.append((idx, data))
        return ingest(idx, data)

    eng.ingest_lines = ingest_
    return calls


def _caught_up(fc) -> None:
    """Drain the catch-up (the writers' joins) with pumps alone: no step, so
    nothing is read ahead and the counters stay at 0."""
    quiet = 0
    while quiet < 2:
        fc.pump(0.1)
        quiet = 0 if fc.last_ready else quiet + 1
    assert not fc._kept and fc.rows_staged == 0


def test_kept_feeds_are_ingested_once_in_order_before_the_pumps_own(server):
    ws = {d: _writers(server, d, 1) for d in ("k0", "k1")}
    eng = _engine()
    fc = FleetConsumer("127.0.0.1", server.port, eng, ["k0", "k1"])
    try:
        _caught_up(fc)
        joins = fc.bytes_consumed
        calls = _record_ingests(eng)
        ws["k0"][0].insert_text(0, "first")
        rows = _flush(server, "k0", ws["k0"])
        _readable(fc, 0)
        with _device_busy_until(fc, lambda: fc._kept):
            fc.step()                  # nothing staged: the seam reads k0
        assert [i for i, _f, _t in fc._kept] == [0]
        assert not calls and fc.rows_staged == 0 and not eng._busy
        assert fc.bytes_consumed == joins
        assert fc.health()["reads_in_flight"] == 1
        t_read = fc._kept[0][2]
        ws["k1"][0].insert_text(0, "second")
        rows += _flush(server, "k1", ws["k1"])
        ws["k0"][0].insert_text(5, "third")
        rows += _flush(server, "k0", ws["k0"])
        _readable(fc, 0)
        _readable(fc, 1)
        assert fc.pump() == rows
        # The kept feed first, then this pump's own; every line once.
        assert calls[0][0] == 0 and b"first" in calls[0][1]
        assert sorted(i for i, _d in calls[1:]) == [0, 1]
        assert sum(d.count(b'"first"') for _i, d in calls) == 1
        assert sum(d.count(b'"third"') for _i, d in calls) == 1
        assert not fc._kept and fc._t_seen == t_read
        h = fc.health()
        assert h["bytes_read_in_flight"] == len(calls[0][1])
        assert h["bytes_consumed"] - joins == sum(len(d) for _i, d in calls)
        fc.step()
        assert eng.texts() == ["firstthird", "second"]
    finally:
        fc.close()


def test_a_stamp_proves_the_rows_its_step_applied_and_no_more(server):
    (w,) = _writers(server, "s0", 1)
    eng = _engine(1)
    fc = FleetConsumer("127.0.0.1", server.port, eng, ["s0"])
    try:
        _caught_up(fc)
        fc.take_applied()
        received = []
        feed = eng.op_clock.feed
        eng.op_clock.feed = lambda stamp, t, rows, doc=-1: (
            received.append(t), feed(stamp, t, rows, doc))[1]
        w.insert_text(0, "ab")
        rows_a = _flush(server, "s0", [w])
        _readable(fc, 0)
        assert fc.pump() == rows_a
        w.insert_text(2, "cd")
        rows_b = _flush(server, "s0", [w])
        _readable(fc, 0)
        with _device_busy_until(fc, lambda: fc._kept):
            fc.step()                  # applies A; the seam reads B
        (first,) = fc.take_applied()
        assert first[2] == rows_a == fc.rows_staged
        assert eng.text(0) == "ab" and not eng._busy and len(fc._kept) == 1
        assert eng.op_clock.rows == rows_a
        t_read = fc._kept[0][2]
        assert first[0] <= t_read <= first[1]   # read inside the first step
        assert fc.pump() == rows_b
        fc.step()
        (second,) = fc.take_applied()
        assert second[2] == rows_a + rows_b
        # Seen when its bytes were read, a step before they were ingested;
        # the op clock's ``received`` is that moment too.
        assert second[0] == t_read
        assert received[1] == t_read and received[0] < t_read
        assert eng.text(0) == "abcd"
    finally:
        fc.close()


def test_an_ack_read_ahead_compacts_after_the_rows_read_before_it(server):
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        UnsequencedMessage,
    )

    (w,) = _writers(server, "a0", 1)
    eng = _engine(1, recovery="grow")
    handed = []
    compact = eng.compact
    eng.compact = lambda docs=None: (handed.append(list(docs)),
                                     compact(docs))[1]
    fc = FleetConsumer("127.0.0.1", server.port, eng, ["a0"])
    try:
        _caught_up(fc)
        w.insert_text(0, "hello")
        w.remove_range(0, 2)
        rows = _flush(server, "a0", [w])
        with server.lock:
            doc = server.service.document("a0")
            doc.connect("summarizer", lambda m: None)
            doc.process_all()
            handle = doc.upload_summary({"type": "tree", "entries": {}})
            doc.submit(UnsequencedMessage(
                client_id="summarizer", client_seq=1,
                ref_seq=doc.sequencer.seq, type=MessageType.SUMMARIZE,
                contents={"handle": handle, "refSeq": doc.sequencer.seq},
            ))
            doc.process_all()
        def acked():                   # the ack's frame follows the ops'
            return any(b'"type":"summaryAck"' in f for _i, f, _t in fc._kept)

        with _device_busy_until(fc, acked):
            fc.step()                  # the seam reads ops AND ack
        assert acked()
        h = eng.health()
        assert not handed and not eng.compact_due and not fc.acks_unstepped
        assert h["acks_seen"] == 0 and h["compact_dispatches"] == 0
        assert fc.pump() == rows
        assert handed == [[0]] and eng.compact_due == {0} and fc.acks_unstepped
        assert eng.health()["compact_dispatches"] == 0   # not in the pump
        fc.step()
        h = eng.health()
        assert (h["acks_seen"], h["compact_dispatches"]) == (1, 1)
        assert eng.text(0) == w.text == "llo"
        assert eng.evictable_left() == 0
    finally:
        fc.close()


def test_a_paused_socket_is_not_read_ahead(server):
    (w,) = _writers(server, "p0", 1)
    eng = _engine(1, overload_high_watermark=4, overload_low_watermark=1)
    fc = FleetConsumer("127.0.0.1", server.port, eng, ["p0"])
    try:
        _caught_up(fc)
        for i in range(6):
            w.insert_text(i, "x")
        rows = _flush(server, "p0", [w])
        _readable(fc, 0)
        assert fc.pump() == rows and fc.paused_socks == {0}
        w.insert_text(0, "y")
        rows += _flush(server, "p0", [w])
        _readable(fc, 0)
        with _device_busy_until(fc, lambda: False, timeout=0.3):
            fc.step()                  # the socket is parked: not read
        assert not fc._kept and fc.health()["reads_in_flight"] == 0
        assert fc.paused_socks == {0}
        assert fc.pump() == 1 and not fc.paused_socks   # re-armed, then read
        fc.step()
        assert eng.text(0) == w.text
    finally:
        fc.close()


def test_a_socket_that_closes_in_the_seam_is_dead_and_its_bytes_are_kept():
    """A shard that sends its last lines and dies while a step is in flight
    (modeled as in ``test_fleet_consumer``: a minimal shard that closes)."""
    import json as _json
    import socket as _socket

    from fluidframework_tpu.server.local_service import LocalService

    svc = LocalService()
    doc = svc.document("c0")
    w = SharedString(client_id="c0-w0")
    doc.connect(w.client_id, w.process)
    doc.process_all()
    w.insert_text(0, "last words")
    for m in w.take_outbox():
        doc.submit(m)
    doc.process_all()
    lines = "".join(m.to_json() + "\n" for m in doc.sequencer.log).encode()

    lsock = _socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    go = threading.Event()

    def serve():
        conn, _ = lsock.accept()
        conn.recv(4096)  # the consume request
        conn.sendall(
            (_json.dumps({"t": "consuming", "doc": "c0"}) + "\n").encode())
        go.wait(10)
        conn.sendall(lines)
        conn.close()     # the shard dies

    threading.Thread(target=serve, daemon=True).start()
    eng = _engine(1)
    fc = FleetConsumer("127.0.0.1", lsock.getsockname()[1], eng, ["c0"])
    try:
        assert fc.pump(0.05) == 0 and not fc.dead_socks
        go.set()
        with _device_busy_until(fc, lambda: fc.dead_socks):
            fc.step()                  # the seam reads the bytes and the EOF
        assert fc.dead_socks == {0} and fc.health()["dead_socks"] == 1
        assert len(fc._kept) == 1 and fc.rows_staged == 0
        # Every socket is dead; the pump still hands on what was read.
        assert fc.pump() == 2 and fc.last_ready == 1
        # A consumer with a dead socket is on its way out: no seam.
        with _device_busy_until(fc, lambda: pytest.fail("read ahead")):
            fc.step()
        assert eng.text(0) == "last words"
        assert fc.pump() == 0 and fc.dead_socks == {0}
    finally:
        fc.close()
        lsock.close()


def test_a_boot_marker_in_a_kept_feed_resyncs_as_today(tmp_path):
    from fluidframework_tpu.server.netserver import ServicePlane
    from fluidframework_tpu.server.ordered_log import CheckpointStore

    from test_fanout import _force_boot_marker

    plane = ServicePlane(historian_port=0).start()
    fc = None
    try:
        with plane.nexus.lock:
            doc = plane.service.document("d0")
            w = SharedString(client_id="d0-w0")
            doc.connect(w.client_id, w.process)
            doc.process_all()

        def flush():
            n = 0
            with plane.nexus.lock:
                d = plane.service.document("d0")
                for m in w.take_outbox():
                    d.submit(m)
                    n += 1
                d.process_all()
            return n

        def mk_engine(recovery="off"):
            return DocBatchEngine(
                1, max_segments=4096, text_capacity=1 << 16,
                max_insert_len=8, ops_per_step=8, use_mesh=False,
                recovery=recovery, doc_keys=["d0"],
            )

        w.insert_text(0, "hello ")
        rows = flush()
        eng = mk_engine("grow")
        fc = FleetConsumer(
            "127.0.0.1", plane.nexus.port, eng, ["d0"],
            historian=("127.0.0.1", plane.historian.port),
        )
        fc.run_for(rows)
        for _ in range(4):
            w.insert_text(0, "gap-")
            flush()
        _readable(fc, 0)
        fc.pump()
        fc.step()
        oracle = mk_engine()
        with plane.nexus.lock:
            log_msgs = list(plane.service.document("d0").sequencer.log)
        for m in log_msgs:
            oracle.ingest(0, m)
        oracle.step()
        oracle.checkpoint_store = CheckpointStore(str(tmp_path / "ck"))
        oracle.maybe_checkpoint(force=True)
        rec = oracle.checkpoint_store.load("d0")
        snap_seq = oracle.hosts[0].last_seq
        with plane.nexus.lock:
            plane.service.document("d0").save_snapshot(snap_seq, rec)

        _force_boot_marker(plane, "d0")
        _readable(fc, 0)
        old_sock = fc._socks[0]
        with _device_busy_until(fc, lambda: fc._kept):
            fc.step()                  # the seam reads the marker
        assert len(fc._kept) == 1 and fc.boot_resyncs == 0
        assert fc._socks[0] is old_sock
        # Bytes that follow the marker on the OLD socket are read by the
        # next pump before the kept feed is ingested: dropped, as today.
        w.insert_text(0, "late-")
        flush()
        _readable(fc, 0)
        calls = _record_ingests(eng)
        fc.pump()
        assert fc.boot_resyncs == 1 and fc._socks[0] is not old_sock
        assert not any(b"late-" in d for _i, d in calls)
        assert not fc.dead_socks and fc._tails[0] == b""
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            fc.pump(wait_s=0.05)
            fc.step()
            if eng.text(0) == w.text:
                break
        assert eng.text(0) == w.text
        assert not eng.errors().any()
        assert fc.health()["boot_resync_failures"] == 0
    finally:
        if fc is not None:
            fc.close()
        plane.stop()


def test_the_tree_engines_step_takes_the_seam():
    from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine

    from test_tree_batch_engine import drive_tree_docs

    svc, expected = drive_tree_docs(2, seed=4, steps=3)
    eng = TreeBatchEngine(2)
    for d in range(2):
        for msg in svc.document(f"doc{d}").sequencer.log:
            eng.ingest(d, msg)
    seen = []
    # Called once after the dispatches (nothing is busy any more) with the
    # latch the readback will wait for, before the step has returned.
    eng.step(in_flight=lambda latch: seen.append(
        (bool(eng._busy), latch is eng.state.error)))
    assert seen == [(False, True)]
    for d in range(2):
        assert eng.values(d) == expected[d]
    eng.step(lambda _latch: seen.append("idle"))
    assert seen[-1] == "idle" and len(seen) == 2


def test_texts_equal_the_oracle_with_the_seam_forced_on_every_step(server):
    """A live fleet whose every step finds bytes in its seam: the reads move
    a step early and nothing else does."""
    rng = random.Random(43)
    n_docs = 4
    fleets = [(f"f{i}", _writers(server, f"f{i}", 2)) for i in range(n_docs)]
    total = [0]

    def edit_round():
        for doc_id, writers in fleets:
            for c in writers:
                n = len(c.text)
                if rng.random() < 0.7 or n < 4:
                    c.insert_text(rng.randint(0, n), "".join(
                        rng.choice("abcdef")
                        for _ in range(rng.randint(1, 6))))
                else:
                    p = rng.randint(0, n - 2)
                    c.remove_range(p, p + 1)
            total[0] += _flush(server, doc_id, writers)

    eng = _engine(n_docs, recovery="grow")
    fc = FleetConsumer("127.0.0.1", server.port, eng,
                       [d for d, _ in fleets])
    try:
        _caught_up(fc)
        joins = fc.bytes_consumed
        rounds = 12
        for _ in range(rounds):
            edit_round()
            for i in range(n_docs):
                _readable(fc, i)
            with _device_busy_until(
                    fc, lambda: len({i for i, _f, _t in fc._kept}) == n_docs):
                fc.step()              # applies the round before; reads this
            assert not eng._busy
            fc.pump(0)
        fc.step()
        assert fc.rows_staged == total[0]
        assert fc.health()["reads_in_flight"] >= rounds   # wake-ups
        assert fc.health()["bytes_read_in_flight"] == fc.bytes_consumed - joins
        stamps = fc.take_applied()
        assert [s[2] for s in stamps] == sorted({s[2] for s in stamps})
        assert all(seen <= applied for seen, applied, _r in stamps)
        for i, (doc_id, writers) in enumerate(fleets):
            with server.lock:
                log = list(server.service.document(doc_id).sequencer.log)
            assert eng.text(i) == oracle_text(log) == writers[0].text
        assert not eng.errors().any()
    finally:
        fc.close()


def test_the_seam_runs_between_the_last_dispatch_and_the_readback():
    """``DocBatchEngine.step(in_flight)``: once, after every queue is
    drained into a dispatch, with the latch ``recover()`` then reads back;
    not at all where nothing is read back."""
    from test_doc_batch_engine import drive_docs

    svc, expected = drive_docs(4, seed=9)
    eng = DocBatchEngine(4, max_segments=256, text_capacity=4096,
                         max_insert_len=8, ops_per_step=4, use_mesh=False)
    order = []
    recover = eng.recover
    eng.recover = lambda: (order.append("recover"), recover())[1]
    for d in range(4):
        for msg in svc.document(f"doc{d}").sequencer.log:
            eng.ingest(d, msg)
    eng.step(lambda latch: order.append(
        ("seam", bool(eng._busy), latch is eng.state.error)))
    assert order == [("seam", False, True), "recover"]
    assert [eng.text(d) for d in range(4)] == [expected[d] for d in range(4)]
    n = len(order)
    eng.step()                          # no seam given: none called
    assert order[n:] == ["recover"]
    eng.recovery = "off"                # no readback: nothing waits
    eng.step(lambda _latch: pytest.fail("a seam with nothing to wait for"))


# ----------------------------------------------------------- the one wait
def _one_doc(server, doc_id):
    (w,) = _writers(server, doc_id, 1)
    fc = FleetConsumer("127.0.0.1", server.port, _engine(1), [doc_id])
    _caught_up(fc)
    return w, fc


def _wake_pending(fc) -> bool:
    return any(key.data == -1 for key, _ev in fc._sel.select(0))


def test_a_seam_whose_device_is_done_watches_and_reads_nothing(server):
    w, fc = _one_doc(server, "w0")
    try:
        w.insert_text(0, "x")
        rows = _flush(server, "w0", [w])
        _readable(fc, 0)
        latch = fc.engine.state.error
        latch.block_until_ready()
        fc._waker.watch = lambda _latch: pytest.fail("nothing to wait for")
        fc._read_ahead(latch)
        assert not fc._kept and fc.health()["reads_in_flight"] == 0
        assert fc.pump() == rows       # the pump's own read, as ever
    finally:
        fc.close()


def test_the_seam_sleeps_until_the_device_is_done_and_takes_the_byte(server):
    _w, fc = _one_doc(server, "w1")
    try:
        t0 = time.monotonic()
        with _device_busy_until(fc, lambda: False, timeout=0.3):
            fc.step()                  # no bytes: woken by the device alone
        assert 0.3 <= time.monotonic() - t0 < 5.0
        assert not fc._kept and fc.health()["reads_in_flight"] == 0
        assert not _wake_pending(fc)
        assert fc.pump(0) == 0 and fc.last_ready == 0
    finally:
        fc.close()


def test_a_device_error_ends_the_seam_and_the_next_one_still_waits(server):
    w, fc = _one_doc(server, "w2")

    class Failed:
        is_ready = staticmethod(lambda: False)

        @staticmethod
        def block_until_ready():
            raise RuntimeError("the device's, for the readback to raise")

    try:
        fc._read_ahead(Failed)         # returns: the waker told all the same
        assert not _wake_pending(fc) and fc._waker._thread.is_alive()
        w.insert_text(0, "y")
        _flush(server, "w2", [w])
        with _device_busy_until(fc, lambda: fc._kept):
            fc.step()
        assert len(fc._kept) == 1
    finally:
        fc.close()


def test_a_raise_in_the_seam_still_takes_the_wakers_byte(server):
    w, fc = _one_doc(server, "w3")
    try:
        w.insert_text(0, "z")
        rows = _flush(server, "w3", [w])
        _readable(fc, 0)

        def interrupted(_ready):
            raise KeyboardInterrupt     # SIGTERM's, from fleet_main

        fc._read = interrupted
        t_end = time.monotonic() + 0.2
        with pytest.raises(KeyboardInterrupt):
            fc._read_ahead(_Latch(lambda: time.monotonic() > t_end, 10.0))
        del fc._read
        assert time.monotonic() >= t_end    # waited for the device's byte
        assert not _wake_pending(fc)
        assert fc.pump() == rows and fc.last_ready == 1
    finally:
        fc.close()


def test_close_stops_the_waker(server):
    _w, fc = _one_doc(server, "w4")
    thread = fc._waker._thread
    assert thread.is_alive() and thread.daemon
    fc.close()
    assert not thread.is_alive() and fc._waker is None
    fc.close()                          # twice is fine


@pytest.mark.parametrize("family", ["string", "tree"])
def test_a_feed_staged_inside_received_at_was_received_then(family):
    """``received`` of a feed is what its reader says (the consumer: when it
    read the bytes), for the block and no longer; else the moment it is
    staged."""
    if family == "string":
        from test_doc_batch_engine import drive_docs

        svc, _expected = drive_docs(1, seed=3)
        eng = _engine(1)
    else:
        from fluidframework_tpu.models.tree_batch_engine import (
            TreeBatchEngine,
        )

        from test_tree_batch_engine import drive_tree_docs

        svc, _expected = drive_tree_docs(1, seed=3, steps=3)
        eng = TreeBatchEngine(1)
    log = svc.document("doc0").sequencer.log
    half = len(log) // 2
    feeds = ["".join(m.to_json() + "\n" for m in part).encode()
             for part in (log[:half], log[half:])]
    received = []
    feed = eng.op_clock.feed
    eng.op_clock.feed = lambda stamp, t, rows, doc=-1: (
        received.append(t), feed(stamp, t, rows, doc))[1]
    t_read = eng.op_clock.now() - 5.0
    before = eng.op_clock.now()
    rows = eng.ingest_lines(0, feeds[0])
    with eng.op_clock.received_at(t_read):
        rows += eng.ingest_lines(0, feeds[1])
    assert rows > 0 and received[-1] == t_read
    assert all(before <= t <= eng.op_clock.now() for t in received[:-1])
    with pytest.raises(ValueError), eng.op_clock.received_at(t_read):
        raise ValueError                # a raise leaves nothing set
    assert eng.op_clock.received() >= before
