"""Read fan-out plane (ISSUE 13): encode-once delta frames, bounded
drop-and-resync byte-identity, the snapshot-boot historian tier's HTTP
caching contract, and sequencer-free at-most-once presence."""

from __future__ import annotations

import gc
import http.client
import json
import socket
import sys
import threading
import time

import pytest

from fluidframework_tpu.fanout import (
    FLAVOR_ENVELOPE,
    FLAVOR_WIRE,
    RESYNC_BOOT_MARKER,
    FanoutPlane,
    FanoutWriter,
    HistorianTier,
)
from fluidframework_tpu.protocol.messages import (
    UnsequencedMessage,
    wire_encode_count,
)
from fluidframework_tpu.server.sequencer import Sequencer


def _mint(n_ops: int, client: str = "w0", text: str = "x") -> list:
    """Sequenced messages via a real sequencer: join + n_ops ops."""
    seqr = Sequencer()
    out = [seqr.join(client)]
    for i in range(n_ops):
        out.append(seqr.ticket(UnsequencedMessage(
            client_id=client, client_seq=i + 1, ref_seq=out[-1].seq,
            contents={"i": i, "text": text * (i % 5 + 1)},
        )))
    return out


def _oracle(msgs) -> bytes:
    return b"".join(m.wire_line() for m in msgs)


# --------------------------------------------------------------------------
# Delta frames: encode-once, shared bytes
# --------------------------------------------------------------------------

def test_broadcaster_frames_encode_once_shared():
    """N frame subscribers + the firehose oracle share ONE encode per
    message — one frame per (doc, pump), the same object for everyone."""
    from fluidframework_tpu.server.ordered_log import Topic
    from fluidframework_tpu.server.lambdas import BroadcasterLambda

    deltas = Topic("deltas", 1)
    bc = BroadcasterLambda(deltas, 0)
    got: list[list] = [[] for _ in range(8)]
    for i in range(8):
        bc.subscribe_frames("d", lambda fr, i=i: got[i].append(fr))
    msgs = _mint(24)
    before = wire_encode_count()
    for chunk in (msgs[:10], msgs[10:]):  # two pumps
        for m in chunk:
            deltas.produce("d", m)
        bc.pump()
    encodes = wire_encode_count() - before
    # <=1 encode per message however many subscribers (the oracle below
    # re-reads the SAME cached bytes: no further encodes).
    assert encodes == len(msgs)
    assert bc.frames_built == 2
    for sub in got:
        assert len(sub) == 2
        # every subscriber got the SAME frame objects
        assert sub[0] is got[0][0] and sub[1] is got[0][1]
    assert b"".join(fr.wire for fr in got[0]) == _oracle(msgs)
    assert wire_encode_count() - before == len(msgs)


def test_plane_publish_and_drain_byte_identity():
    """Wire + envelope subscribers over several pumps: every observed
    stream byte-identical to its flavor's oracle."""
    plane = FanoutPlane()
    msgs = _mint(40)
    plane.ensure_doc("d", last_seq=0)
    sinks = []
    for flavor in (FLAVOR_WIRE, FLAVOR_WIRE, FLAVOR_ENVELOPE):
        chunks: list[bytes] = []
        peer = plane.new_peer(sink=chunks.append)
        plane.attach("d", peer, flavor=flavor, last_seq=0)
        sinks.append((flavor, peer, chunks))
    for lo in range(0, len(msgs), 7):
        plane.publish("d", msgs[lo:lo + 7])
    for _flavor, peer, _chunks in sinks:
        plane.drain_virtual(peer)
    wire_oracle = _oracle(msgs)
    env_oracle = b"".join(m.op_envelope() for m in msgs)
    for flavor, _peer, chunks in sinks:
        want = wire_oracle if flavor == FLAVOR_WIRE else env_oracle
        assert b"".join(chunks) == want
    assert plane.stats()["frames_published"] == len(range(0, len(msgs), 7))
    assert plane.stats()["resyncs"] == 0


def test_slow_subscriber_drop_and_resync_byte_identity():
    """A subscriber that stops draining falls off the bounded ring; its
    resync rebuilds the missed range from the log — the full observed
    stream stays byte-identical to the firehose oracle, and the fast
    subscriber never noticed."""
    msgs = _mint(60)
    log = {m.seq: m for m in msgs}

    def resync_source(doc_id, from_seq):
        return [m for s, m in sorted(log.items()) if s > from_seq]

    plane = FanoutPlane(resync_source=resync_source, ring_frames=4)
    plane.ensure_doc("d", last_seq=0)
    fast_chunks: list[bytes] = []
    slow_chunks: list[bytes] = []
    fast = plane.new_peer(sink=fast_chunks.append)
    slow = plane.new_peer(sink=slow_chunks.append)
    plane.attach("d", fast, flavor=FLAVOR_WIRE, last_seq=0)
    plane.attach("d", slow, flavor=FLAVOR_WIRE, last_seq=0)
    for lo in range(0, 30, 3):
        plane.publish("d", msgs[lo:lo + 3])
        plane.drain_virtual(fast)  # fast keeps up pump by pump
    # slow drains only now: >4 frames published, the ring evicted some.
    plane.drain_virtual(slow)
    # tail pumps: both keep up again
    for lo in range(30, len(msgs), 3):
        plane.publish("d", msgs[lo:lo + 3])
        plane.drain_virtual(fast)
    plane.drain_virtual(slow)
    oracle = _oracle(msgs)
    assert b"".join(fast_chunks) == oracle
    assert b"".join(slow_chunks) == oracle
    stats = plane.stats()
    assert stats["frames_evicted"] > 0
    assert slow.resyncs >= 1 and fast.resyncs == 0
    assert stats["boot_resyncs"] == 0


def test_resync_without_retained_log_sends_boot_marker():
    """When the missed range is no longer retained, the subscriber gets
    the snapshot-boot marker instead of silently missing bytes, and the
    live stream resumes after it."""
    msgs = _mint(24)
    plane = FanoutPlane(resync_source=lambda d, s: None, ring_frames=2)
    plane.ensure_doc("d", last_seq=0)
    chunks: list[bytes] = []
    peer = plane.new_peer(sink=chunks.append)
    plane.attach("d", peer, flavor=FLAVOR_WIRE, last_seq=0)
    for lo in range(0, 20, 2):
        plane.publish("d", msgs[lo:lo + 2])
    plane.drain_virtual(peer)
    # Everything missed collapses into the marker: the subscriber must
    # snapshot-boot (historian tier) instead of receiving a gapped stream.
    assert chunks == [RESYNC_BOOT_MARKER]
    assert plane.stats()["boot_resyncs"] == 1
    # post-marker pumps stream normally again
    plane.publish("d", msgs[20:22])
    plane.publish("d", msgs[22:24])
    plane.drain_virtual(peer)
    assert b"".join(chunks[1:]) == _oracle(msgs[20:24])


# --------------------------------------------------------------------------
# Historian snapshot-boot tier
# --------------------------------------------------------------------------

@pytest.fixture
def historian_store():
    from fluidframework_tpu.server.gitstore import GitSnapshotStore

    store = GitSnapshotStore()
    store.save(10, {"root": {"a": "v1", "big": {"x": 1, "y": 2}}})
    store.save(20, {"root": {"a": "v2", "big": {"x": 1, "y": 2}}})
    tier = HistorianTier(lambda doc: store if doc == "doc" else None).start()
    yield tier, store
    tier.stop()


def _get(port: int, path: str, headers: dict | None = None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path, headers=headers or {})
    r = conn.getresponse()
    body = r.read()
    out = (r.status, dict(r.getheaders()), body)
    conn.close()
    return out


def test_historian_latest_etag_and_304(historian_store):
    tier, store = historian_store
    status, headers, body = _get(tier.port, "/doc/doc/snapshot")
    assert status == 200
    latest_sha = store.versions[-1][1]
    assert headers["ETag"] == f'"{latest_sha}"'
    assert headers["Cache-Control"] == "no-cache"
    payload = json.loads(body)
    assert payload["commit"] == latest_sha and payload["seq"] == 20
    assert payload["summary"]["root"]["a"] == "v2"
    # Conditional revalidation: one header round-trip, no body.
    status, headers, body = _get(
        tier.port, "/doc/doc/snapshot",
        headers={"If-None-Match": f'"{latest_sha}"'},
    )
    assert status == 304 and body == b""
    assert headers["ETag"] == f'"{latest_sha}"'
    # A stale ETag (older version) still gets the full new snapshot.
    old_sha = store.versions[0][1]
    status, _h, body = _get(
        tier.port, "/doc/doc/snapshot",
        headers={"If-None-Match": f'"{old_sha}"'},
    )
    assert status == 200 and json.loads(body)["commit"] == latest_sha
    stats = tier.stats()
    assert stats["not_modified_304"] == 1 and stats["cold_serves"] == 2


def test_historian_sha_addressed_immutable_and_versions(historian_store):
    tier, store = historian_store
    old_sha = store.versions[0][1]
    status, headers, body = _get(tier.port, f"/doc/doc/snapshot/{old_sha}")
    assert status == 200
    assert "immutable" in headers["Cache-Control"]
    assert json.loads(body)["summary"]["root"]["a"] == "v1"
    # sha-addressed conditional GET: 304 without touching the store
    status, _h, body = _get(
        tier.port, f"/doc/doc/snapshot/{old_sha}",
        headers={"If-None-Match": f'"{old_sha}"'},
    )
    assert status == 304 and body == b""
    status, _h, body = _get(tier.port, "/doc/doc/versions?max=5")
    ids = [v["id"] for v in json.loads(body)["versions"]]
    assert ids == [store.versions[1][1], store.versions[0][1]]
    status, _h, _b = _get(tier.port, "/doc/doc/snapshot/deadbeef")
    assert status == 404


def test_historian_partial_subtree_read_over_http(historian_store):
    tier, store = historian_store
    sha = store.versions[-1][1]
    status, headers, body = _get(
        tier.port, f"/doc/doc/path/{sha}?path=root/big"
    )
    assert status == 200
    assert json.loads(body)["value"] == {"x": 1, "y": 2}
    assert "immutable" in headers["Cache-Control"]
    status, _h, body = _get(tier.port, f"/doc/doc/path/{sha}?path=root/a")
    assert json.loads(body)["value"] == "v1" or json.loads(body)["value"] == "v2"
    status, _h, _b = _get(tier.port, f"/doc/doc/path/{sha}?path=root/nope")
    assert status == 404
    assert tier.stats()["path_reads"] == 2


def test_historian_serves_service_docs_without_touching_sequencer():
    """ServicePlane integration: boots come straight from the gitstore —
    unknown docs 404 (never instantiated), and reads leave the sequencer
    exactly where it was."""
    from fluidframework_tpu.server.netserver import ServicePlane

    plane = ServicePlane(historian_port=0).start()
    try:
        with plane.nexus.lock:
            doc = plane.service.document("hot")
            doc.save_snapshot(5, {"ch": {"v": 1}})
            seq_before = doc.sequencer.seq
        port = plane.historian.port
        status, headers, body = _get(port, "/doc/hot/snapshot")
        assert status == 200
        sha = json.loads(body)["commit"]
        status, _h, _b = _get(
            port, "/doc/hot/snapshot", headers={"If-None-Match": f'"{sha}"'}
        )
        assert status == 304
        status, _h, _b = _get(port, "/doc/never-created/snapshot")
        assert status == 404
        with plane.nexus.lock:
            assert plane.service.peek_document("never-created") is None
            assert doc.sequencer.seq == seq_before
    finally:
        plane.stop()


# --------------------------------------------------------------------------
# Presence plane
# --------------------------------------------------------------------------

def test_presence_at_most_once_bounded_drop_no_sequencer():
    """Signals encode once, deliver at most once per subscriber, drop past
    the per-peer bound, and never touch any ordering state."""
    plane = FanoutPlane(max_directs=4)
    plane.ensure_doc("d", last_seq=0)
    live_chunks: list[bytes] = []
    live = plane.new_peer(sink=live_chunks.append)
    stalled = plane.new_peer(sink=lambda b: None)
    plane.add_signal_peer("d", live)
    plane.add_signal_peer("d", stalled)
    before = wire_encode_count()
    for i in range(10):
        plane.publish_signal("d", "w0", {"cursor": i})
        plane.drain_virtual(live)  # live keeps up; stalled never drains
    assert wire_encode_count() == before  # signals never touch op encodes
    got = [json.loads(c) for c in live_chunks]
    assert [g["contents"]["cursor"] for g in got] == list(range(10))
    assert all(g["t"] == "signal" and g["clientId"] == "w0" for g in got)
    stats = plane.stats()
    # stalled peer: bound 4, ten published -> six shed, at most once each
    assert stats["signal_drops"] == 6 and stalled.signal_drops == 6
    assert stats["signals_published"] == 10
    assert stats["frames_published"] == 0  # nowhere near the ordering path


def test_stalled_signal_subscriber_does_not_stall_ticketing():
    """ISSUE 13 satellite regression: a signal subscriber that never reads
    must not stall op ticketing.  Pre-fanout, submit_signal wrote every
    subscriber's socket synchronously under the service lock — one full
    kernel buffer wedged the whole ordering plane."""
    from fluidframework_tpu.server.netserver import ServicePlane

    plane = ServicePlane().start()
    stalled = writer = None
    try:
        # Stalled subscriber: connects with signals, then never reads.
        stalled = socket.create_connection(("127.0.0.1", plane.nexus.port))
        stalled.sendall(json.dumps({
            "t": "connect", "doc": "d", "client": "lurker",
            "mode": "read", "signals": True,
        }).encode() + b"\n")
        sf = stalled.makefile("rb")
        while b'"joined"' not in sf.readline():
            pass  # connect fully processed; from here the lurker stalls
        # Tight per-peer signal bound so the storm sheds visibly (kernel
        # buffers on loopback can otherwise swallow megabytes).
        with plane.nexus.lock:
            plane.nexus.fanout.max_directs = 64
        # Writer client on its own socket.
        writer = socket.create_connection(("127.0.0.1", plane.nexus.port))
        writer.sendall(json.dumps({
            "t": "connect", "doc": "d", "client": "w0", "mode": "write",
        }).encode() + b"\n")
        wf = writer.makefile("rb")
        while b'"joined"' not in wf.readline():
            pass
        # Saturate far past any kernel buffer: ~32MB of signal payload the
        # stalled peer never drains.  Old code would block mid-loop.
        blob = "s" * 65536
        t0 = time.monotonic()
        for i in range(500):
            writer.sendall(json.dumps(
                {"t": "signal", "content": {"i": i, "blob": blob}}
            ).encode() + b"\n")
        # Ticketing stays live: an op submitted and sync-echoed promptly.
        writer.sendall(json.dumps({
            "t": "submit",
            "msg": {"clientId": "w0", "clientSequenceNumber": 1,
                    "referenceSequenceNumber": 1, "type": "op",
                    "contents": {"probe": True}},
        }).encode() + b"\n")
        writer.sendall(b'{"t": "sync", "n": 7}\n')
        writer.settimeout(30)
        deadline = time.monotonic() + 30
        synced = False
        while time.monotonic() < deadline:
            line = wf.readline()
            if not line:
                break
            if b'"sync"' in line and b'"n": 7' in line:
                synced = True
                break
        elapsed = time.monotonic() - t0
        assert synced, "ticketing wedged behind the stalled signal subscriber"
        assert elapsed < 30
        stats = plane.http.service_stats()["fanout"]
        # the stalled peer's bounded queue shed most of the storm
        assert stats["signal_drops"] > 0
        with plane.nexus.lock:
            doc = plane.service.peek_document("d")
            # signals never sequenced: log = lurker-less quorum traffic only
            types = [m.type for m in doc.sequencer.log]
            assert "signal" not in types
    finally:
        for s in (stalled, writer):
            if s is not None:
                s.close()
        plane.stop()


# --------------------------------------------------------------------------
# Wire integration: consumers + clients share frames over real TCP
# --------------------------------------------------------------------------

def _read_lines_until(sock_file, n_payload_lines: int, deadline_s: float = 30):
    out = []
    end = time.monotonic() + deadline_s
    while len(out) < n_payload_lines and time.monotonic() < end:
        line = sock_file.readline()
        if not line:
            break
        out.append(line)
    return out


def test_firehose_and_clients_share_one_encode_over_tcp():
    """One connect client + two firehose consumers on one doc: per pump,
    every sequenced message is wire-encoded exactly once, and each
    consumer's byte stream equals the log's cached encoding."""
    from fluidframework_tpu.server.netserver import ServicePlane

    plane = ServicePlane().start()
    socks = []
    try:
        consumers = []
        for _ in range(2):
            c = socket.create_connection(("127.0.0.1", plane.nexus.port))
            socks.append(c)
            c.sendall(b'{"t": "consume", "doc": "d"}\n')
            f = c.makefile("rb")
            assert b"consuming" in f.readline()
            consumers.append(f)
        w = socket.create_connection(("127.0.0.1", plane.nexus.port))
        socks.append(w)
        w.sendall(json.dumps({
            "t": "connect", "doc": "d", "client": "w0", "mode": "write",
        }).encode() + b"\n")
        wf = w.makefile("rb")
        while b'"joined"' not in wf.readline():
            pass
        # Quiesce the join broadcast (its one encode included) before
        # snapshotting the counter: the sync echo orders after the frame.
        w.sendall(b'{"t": "sync", "n": 0}\n')
        while True:
            line = wf.readline()
            if not line or b'"sync"' in line:
                break
        before = wire_encode_count()
        n_ops = 16
        for i in range(n_ops):
            w.sendall(json.dumps({
                "t": "submit",
                "msg": {"clientId": "w0", "clientSequenceNumber": i + 1,
                        "referenceSequenceNumber": 1, "type": "op",
                        "contents": {"i": i}},
            }).encode() + b"\n")
        w.sendall(b'{"t": "sync", "n": 1}\n')
        while True:
            line = wf.readline()
            if not line or b'"sync"' in line:
                break
        # join already encoded pre-`before`; the 16 ops encode once each
        # though three subscribers (2 wire + 1 envelope) observed them.
        assert wire_encode_count() - before == n_ops
        with plane.nexus.lock:
            doc = plane.service.peek_document("d")
            oracle = b"".join(m.wire_line() for m in doc.sequencer.log)
        for f in consumers:
            lines = _read_lines_until(f, len(oracle.splitlines()))
            assert b"".join(lines) == oracle
    finally:
        for s in socks:
            s.close()
        plane.stop()


def test_pipelined_sync_disconnect_still_echoes():
    """A client may pipeline sync + disconnect in one write: the sync echo
    (its deterministic quiescence marker) must reach the wire before the
    server tears the session down — queued-writer delivery included."""
    from fluidframework_tpu.server.netserver import ServicePlane

    plane = ServicePlane().start()
    s = None
    try:
        s = socket.create_connection(("127.0.0.1", plane.nexus.port))
        s.sendall(json.dumps({
            "t": "connect", "doc": "d", "client": "w0", "mode": "write",
        }).encode() + b"\n")
        f = s.makefile("rb")
        while b'"joined"' not in f.readline():
            pass
        s.sendall(b'{"t": "sync", "n": 9}\n{"t": "disconnect"}\n')
        s.settimeout(15)
        saw_sync = False
        while True:
            line = f.readline()
            if not line:
                break  # server closed after the goodbye
            if b'"sync"' in line and b'"n": 9' in line:
                saw_sync = True
        assert saw_sync, "sync echo lost on pipelined disconnect"
    finally:
        if s is not None:
            s.close()
        plane.stop()


def test_backlogged_consumer_resyncs_over_tcp_byte_identical():
    """A consumer that stops reading while the ring is tiny gets dropped
    to catch-up and resynced from the log — the bytes it finally reads are
    still exactly the firehose oracle."""
    from fluidframework_tpu.server.netserver import ServicePlane

    plane = ServicePlane().start()
    socks = []
    try:
        with plane.nexus.lock:
            plane.nexus.fanout.ring_frames = 4  # force eviction quickly
        c = socket.create_connection(("127.0.0.1", plane.nexus.port))
        socks.append(c)
        c.sendall(b'{"t": "consume", "doc": "d"}\n')
        cf = c.makefile("rb")
        assert b"consuming" in cf.readline()
        w = socket.create_connection(("127.0.0.1", plane.nexus.port))
        socks.append(w)
        w.sendall(json.dumps({
            "t": "connect", "doc": "d", "client": "w0", "mode": "write",
        }).encode() + b"\n")
        wf = w.makefile("rb")
        while b'"joined"' not in wf.readline():
            pass
        # Big payloads + no reads on the consumer: kernel buffers fill,
        # frames fall off the 4-deep ring.
        blob = "y" * 32768
        n_ops = 96
        for i in range(n_ops):
            w.sendall(json.dumps({
                "t": "submit",
                "msg": {"clientId": "w0", "clientSequenceNumber": i + 1,
                        "referenceSequenceNumber": 1, "type": "op",
                        "contents": {"i": i, "blob": blob}},
            }).encode() + b"\n")
        w.sendall(b'{"t": "sync", "n": 2}\n')
        while True:
            line = wf.readline()
            if not line or b'"sync"' in line:
                break
        with plane.nexus.lock:
            oracle = b"".join(
                m.wire_line()
                for m in plane.service.peek_document("d").sequencer.log
            )
        got = b""
        c.settimeout(10)
        end = time.monotonic() + 60
        while len(got) < len(oracle) and time.monotonic() < end:
            try:
                data = c.recv(1 << 20)
            except socket.timeout:
                break
            if not data:
                break
            got += data
        assert got == oracle
    finally:
        for s in socks:
            s.close()
        plane.stop()


# --------------------------------------------------------------------------
# Client boot-marker handling (PR 14): FleetConsumer snapshot-boot resync
# --------------------------------------------------------------------------

def _force_boot_marker(plane, doc_id: str):
    """Drive the REAL resync path into its boot branch for every socket
    subscriber of ``doc_id``: the retained window is declared compacted
    away (resync source empty) and each peer's floor is dropped below it —
    exactly the state a long-stalled consumer wakes up to.  The eviction
    mechanics themselves are covered by the server-side tests
    (test_resync_without_retained_log_sends_boot_marker and the backlogged
    TCP test); this helper makes the CLIENT contract testable without
    megabytes of filler traffic."""
    fanout = plane.nexus.fanout
    with plane.nexus.lock:
        fanout._resync_source = lambda _d, _s: None
        peers = [p for p in fanout._docs[doc_id].subs if p.is_socket]
    with fanout._lock:
        for p in peers:
            p.sub.last_seq = -1
    for p in peers:
        fanout.resync(p)  # no locks held: the resync-source contract
    plane.nexus.fanout_writer.wake(peers)
    return peers


def test_fleet_consumer_boot_marker_snapshot_resync_over_tcp(tmp_path):
    """End-to-end over real TCP: a FleetConsumer whose firehose fell off
    the retained log receives ``{"t":"resync","boot":true}``, fetches the
    latest historian snapshot over HTTP, adopts it into the engine, and
    re-consumes from its seq — the device doc converges byte-identically
    with the writers despite the gap (ops the ring skipped)."""
    from fluidframework_tpu.dds.shared_string import SharedString
    from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
    from fluidframework_tpu.native.ingest_native import available
    from fluidframework_tpu.server.fleet_consumer import FleetConsumer
    from fluidframework_tpu.server.netserver import ServicePlane
    from fluidframework_tpu.server.ordered_log import CheckpointStore

    if not available():
        pytest.skip("native ingest encoder unavailable")

    plane = ServicePlane(historian_port=0).start()
    fc = None
    try:
        with plane.nexus.lock:
            doc = plane.service.document("d0")
            writers = []
            for w in range(2):
                c = SharedString(client_id=f"d0-w{w}")
                doc.connect(c.client_id, c.process)
                writers.append(c)
            doc.process_all()
        a, b = writers

        def flush():
            n = 0
            with plane.nexus.lock:
                d = plane.service.document("d0")
                for c in writers:
                    for m in c.take_outbox():
                        d.submit(m)
                        n += 1
                d.process_all()
            return n

        a.insert_text(0, "hello ")
        rows = flush()
        b.insert_text(6, "world")
        rows += flush()

        def mk_engine():
            return DocBatchEngine(
                1, max_segments=4096, text_capacity=1 << 16,
                max_insert_len=8, ops_per_step=8, use_mesh=False,
                recovery="off", doc_keys=["d0"],
            )

        eng = mk_engine()
        fc = FleetConsumer(
            "127.0.0.1", plane.nexus.port, eng, ["d0"],
            historian=("127.0.0.1", plane.historian.port),
        )
        fc.run_for(rows)
        assert eng.text(0) == a.text

        # The consumer stalls while writers keep editing: these ops form
        # the range the ring will have evicted by the time it wakes.
        for _ in range(6):
            a.insert_text(0, "gap-")
            flush()

        # An acked summary covering the WHOLE log so far reaches the
        # historian (the scribe's job in production) — built here by an
        # oracle engine replaying the sequencer log.
        oracle = mk_engine()
        with plane.nexus.lock:
            log_msgs = list(plane.service.document("d0").sequencer.log)
        # Object-path replay: the record must carry the quorum table the
        # adopted consumer resumes with (native-mode quorum lives in C++).
        for m in log_msgs:
            oracle.ingest(0, m)
        oracle.step()
        oracle.checkpoint_store = CheckpointStore(str(tmp_path / "ck"))
        oracle.maybe_checkpoint(force=True)
        rec = oracle.checkpoint_store.load("d0")
        assert rec is not None and rec["engine"] == "doc_batch"
        snap_seq = oracle.hosts[0].last_seq
        assert snap_seq > eng.hosts[0].last_seq  # a real gap to adopt over
        with plane.nexus.lock:
            plane.service.document("d0").save_snapshot(snap_seq, rec)

        _force_boot_marker(plane, "d0")

        deadline = time.monotonic() + 30
        while fc.boot_resyncs == 0 and time.monotonic() < deadline:
            fc.pump(wait_s=0.05)
            fc.step()
            assert not fc.dead_socks, "boot resync failed (doc marked dead)"
        assert fc.boot_resyncs == 1
        assert eng.counters.get("boot_snapshots_adopted") == 1
        assert eng.hosts[0].last_seq >= snap_seq

        # Post-resync the stream is live again: new edits converge.
        a.insert_text(0, "post-")
        flush()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            fc.pump(wait_s=0.05)
            fc.step()
            if eng.text(0) == a.text:
                break
        assert eng.text(0) == a.text == b.text
        assert not eng.errors().any()
        assert fc.health()["boot_resyncs"] == 1
        assert fc.health()["boot_resync_failures"] == 0
    finally:
        if fc is not None:
            fc.close()
        plane.stop()


def test_fleet_consumer_boot_resync_refused_below_floor(tmp_path):
    """Refusal half of the boot-resync contract: when the only historian
    snapshot sits at/below the doc's applied floor, adoption is REFUSED —
    re-subscribing from the engine's own floor would just draw another
    boot marker (an infinite resync loop that looks healthy) — and the
    doc falls to the supervisor restart path: ``boot_resync_failures``
    counts, ``dead_socks`` carries the doc, and the engine's served state
    is untouched."""
    from fluidframework_tpu.dds.shared_string import SharedString
    from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
    from fluidframework_tpu.native.ingest_native import available
    from fluidframework_tpu.server.fleet_consumer import FleetConsumer
    from fluidframework_tpu.server.netserver import ServicePlane
    from fluidframework_tpu.server.ordered_log import CheckpointStore

    if not available():
        pytest.skip("native ingest encoder unavailable")

    plane = ServicePlane(historian_port=0).start()
    fc = None
    try:
        with plane.nexus.lock:
            doc = plane.service.document("d0")
            a = SharedString(client_id="d0-w0")
            doc.connect(a.client_id, a.process)
            doc.process_all()

        def flush():
            n = 0
            with plane.nexus.lock:
                d = plane.service.document("d0")
                for m in a.take_outbox():
                    d.submit(m)
                    n += 1
                d.process_all()
            return n

        a.insert_text(0, "hello world")
        rows = flush()

        def mk_engine():
            return DocBatchEngine(
                1, max_segments=4096, text_capacity=1 << 16,
                max_insert_len=8, ops_per_step=8, use_mesh=False,
                recovery="off", doc_keys=["d0"],
            )

        eng = mk_engine()
        fc = FleetConsumer(
            "127.0.0.1", plane.nexus.port, eng, ["d0"],
            historian=("127.0.0.1", plane.historian.port),
        )
        fc.run_for(rows)
        assert eng.text(0) == a.text
        text_before = eng.text(0)

        # A perfectly well-formed snapshot record, but stamped at/below
        # the doc's applied floor (the historian's seq stamp is the
        # authoritative one): stale — nothing for the consumer to adopt.
        oracle = mk_engine()
        with plane.nexus.lock:
            log_msgs = list(plane.service.document("d0").sequencer.log)
        for m in log_msgs:
            oracle.ingest(0, m)
        oracle.step()
        oracle.checkpoint_store = CheckpointStore(str(tmp_path / "ck"))
        oracle.maybe_checkpoint(force=True)
        rec = oracle.checkpoint_store.load("d0")
        assert rec is not None
        snap_seq = eng.hosts[0].last_seq  # == the floor: refused
        with plane.nexus.lock:
            plane.service.document("d0").save_snapshot(snap_seq, rec)

        _force_boot_marker(plane, "d0")

        deadline = time.monotonic() + 30
        while not fc.dead_socks and time.monotonic() < deadline:
            fc.pump(wait_s=0.05)
            fc.step()
        assert 0 in fc.dead_socks, "doc should fall to the supervisor path"
        assert fc.boot_resyncs == 0
        assert fc.boot_resync_failures == 1
        assert fc.health()["boot_resync_failures"] == 1
        assert eng.counters.get("boot_snapshots_stale") == 1
        assert not eng.counters.get("boot_snapshots_adopted")
        # The refusal never touched the served doc.
        assert eng.text(0) == text_before
        assert not eng.errors().any()
    finally:
        if fc is not None:
            fc.close()
        plane.stop()


def test_delta_connection_surfaces_boot_marker():
    """Driver side of the contract: NetworkDeltaConnection hands the boot
    marker to the host's boot listener (the container reload hook) instead
    of silently dropping the line."""
    from fluidframework_tpu.driver.network_driver import (
        NetworkDocumentServiceFactory,
    )
    from fluidframework_tpu.server.netserver import ServicePlane

    plane = ServicePlane().start()
    try:
        booted = []
        factory = NetworkDocumentServiceFactory(
            "127.0.0.1", plane.nexus.port, plane.http.port
        )
        svc = factory.create_document_service("d0")
        conn = svc.connect_to_delta_stream(
            "c0", lambda _m: None, boot_listener=lambda: booted.append(1)
        )
        try:
            _force_boot_marker(plane, "d0")
            deadline = time.monotonic() + 10
            while not booted and time.monotonic() < deadline:
                conn.pump(block_s=0.05)
            assert booted and conn.boot_resyncs == 1
        finally:
            conn.disconnect()
    finally:
        plane.stop()


def test_quiet_firehose_consumers_hold_no_thread():
    """A firehose consumer says nothing after its handshake, so the front
    parks its socket with one watch thread instead of keeping a handler
    thread per DOCUMENT of a device fleet (a sandboxed host kills a
    process near 4,096 threads; config 3 is 10,000 documents).  The
    stream still flows, a consumer that speaks again is served again, and
    a consumer that goes away is dropped."""
    import threading
    import time

    from fluidframework_tpu.dds.shared_string import SharedString
    from fluidframework_tpu.server.netserver import NetworkServer

    srv = NetworkServer().start()
    socks = []
    try:
        before = threading.active_count()
        files = []
        for i in range(48):
            c = socket.create_connection(("127.0.0.1", srv.port))
            socks.append(c)
            c.sendall(json.dumps({"t": "consume", "doc": f"d{i}"}).encode()
                      + b"\n")
            f = c.makefile("rb")
            assert b"consuming" in f.readline()
            files.append(f)

        def settle(cond):
            deadline = time.monotonic() + 10
            while not cond() and time.monotonic() < deadline:
                time.sleep(0.01)
            return cond()

        assert settle(lambda: threading.active_count() <= before + 1), (
            f"{threading.active_count() - before} threads for 48 consumers"
        )
        assert srv.fanout.stats()["peers"] == 48

        with srv.lock:  # the stream still flows to a parked socket
            doc = srv.service.document("d0")
            w = SharedString(client_id="w")
            doc.connect(w.client_id, w.process)
            doc.process_all()
        w.insert_text(0, "parked")
        with srv.lock:
            for m in w.take_outbox():
                doc.submit(m)
            doc.process_all()
        while b'"seg":"parked"' not in files[0].readline():
            pass

        socks[1].sendall(b'{"t": "sync", "n": 7}\n')  # it speaks again
        assert json.loads(files[1].readline()) == {"t": "sync", "n": 7}
        assert settle(lambda: threading.active_count() <= before + 1)

        socks[2].close()                               # it goes away
        files[2].close()
        assert settle(lambda: srv.fanout.stats()["peers"] == 47)
    finally:
        for s in socks:
            s.close()
        srv.stop()


# --------------------------------------------------------------------------
# Writer tier: the wake protocol (no wake is ever slept on)
# --------------------------------------------------------------------------


class _HookedLock:
    """``FanoutWriter._lock`` with a one-shot callback the moment the writer
    thread releases it: the first release of a pass is the swap that took
    ``_pending``."""

    def __init__(self, lock, owner, on_release):
        self._lock, self._owner, self._on_release = lock, owner, on_release

    def __enter__(self):
        self._lock.acquire()

    def __exit__(self, *exc):
        self._lock.release()
        if threading.current_thread() is self._owner:
            self._on_release()


class _HookedWakeSock:
    """``FanoutWriter._wake_r`` with a one-shot callback the moment a drain
    finds the wake channel empty (``recv`` would block)."""

    def __init__(self, sock, on_drained):
        self._sock, self._on_drained = sock, on_drained

    def recv(self, n):
        try:
            return self._sock.recv(n)
        except BlockingIOError:
            self._on_drained()
            raise

    def __getattr__(self, name):
        return getattr(self._sock, name)


def _writer_plane(n_peers: int, **plane_kw):
    """A plane with its writer tier and ``n_peers`` socketpair subscribers,
    peer i on document ``d{i}``.  Returns (plane, writer, peers, readers)."""
    plane = FanoutPlane(**plane_kw)
    writer = FanoutWriter(plane)
    plane.set_writer(writer)
    peers, readers = [], []
    for i in range(n_peers):
        a, b = socket.socketpair()
        a.setblocking(False)
        peer = plane.new_peer(sock=a)
        plane.attach(f"d{i}", peer, FLAVOR_WIRE)
        peers.append(peer)
        readers.append(b)
    return plane, writer, peers, readers


def _close_writer_plane(writer, peers, readers):
    writer.stop()
    for peer in peers:
        peer.sock.close()
    for r in readers:
        r.close()


def _recv_by(sock, n: int, deadline: float) -> bytes:
    """Up to ``n`` bytes from ``sock`` by ``deadline`` (perf_counter)."""
    got = b""
    while len(got) < n:
        left = deadline - time.perf_counter()
        if left <= 0:
            break
        sock.settimeout(left)
        try:
            chunk = sock.recv(n - len(got))
        except TimeoutError:
            break
        if not chunk:
            break
        got += chunk
    return got


def test_writer_stats_carry_the_wake_counters_from_the_first_call():
    plane, writer, peers, readers = _writer_plane(1)
    try:
        stats = writer.stats()
        assert stats["passes"] == 0
        assert stats["wake_to_pass_ms_sum"] == 0.0
        assert stats["wake_to_pass_ms_max"] == 0.0
        msgs = _mint(2)
        plane.publish("d0", msgs)
        want = _oracle(msgs)
        assert _recv_by(readers[0], len(want),
                             time.perf_counter() + 5) == want
        stats = writer.stats()
        assert stats["passes"] >= 1
        assert 0.0 < stats["wake_to_pass_ms_max"] <= stats["wake_to_pass_ms_sum"]
        # /status carries the writer's stats verbatim (netserver.HttpFront).
        assert {"sends", "send_bytes", "partial_sends", "dead_peers"} <= set(stats)
    finally:
        _close_writer_plane(writer, peers, readers)


@pytest.mark.parametrize(
    "point", ["in_select", "after_swap", "after_drain", "in_service"]
)
def test_writer_never_sleeps_on_a_wake(point):
    """A publish to peer B lands, from a second thread, at a forced point of
    the pass that a publish to peer A started: while the writer sits in
    ``select``, right after it swapped ``_pending`` out, right after it
    drained the wake channel, and while it services A.  B's bytes must
    reach its socket at once (the 1 s ``select`` timeout is the safety
    net, not the mechanism).  A loop that swaps first and drains after
    loses the ``after_swap`` wake: the drain eats B's byte while B sits in
    the new ``_pending``."""
    plane, writer, peers, readers = _writer_plane(2)
    msgs_a, msgs_b = _mint(3, client="a"), _mint(3, client="b")
    injected_at = []
    fired = threading.Event()

    def inject():
        if fired.is_set():  # one shot: the pass that A's publish started
            return
        fired.set()

        def publish_b():
            injected_at.append(time.perf_counter())
            plane.publish("d1", msgs_b)

        t = threading.Thread(target=publish_b)
        t.start()
        t.join(5)
        assert not t.is_alive()

    try:
        if point == "in_select":
            time.sleep(0.05)  # the writer is parked in select by now
            inject()
        else:
            if point == "after_swap":
                writer._lock = _HookedLock(writer._lock, writer._thread, inject)
            elif point == "after_drain":
                writer._wake_r = _HookedWakeSock(writer._wake_r, inject)
            else:
                claim = plane.claim

                def hooked_claim(peer, max_bytes=None):
                    out = claim(peer, max_bytes)
                    inject()
                    return out

                plane.claim = hooked_claim
            plane.publish("d0", msgs_a)
            assert fired.wait(5), f"the pass never reached {point}"
            want_a = _oracle(msgs_a)
            assert _recv_by(readers[0], len(want_a),
                                 time.perf_counter() + 5) == want_a
        want_b = _oracle(msgs_b)
        got = _recv_by(readers[1], len(want_b), injected_at[0] + 0.2)
        waited_ms = (time.perf_counter() - injected_at[0]) * 1e3
        assert got == want_b, (
            f"{len(got)} of {len(want_b)} bytes {waited_ms:.0f} ms after a "
            f"wake at {point}: the writer slept on work it was told about"
        )
        assert writer.stats()["wake_to_pass_ms_max"] < 200
    finally:
        _close_writer_plane(writer, peers, readers)


def _tick_bursts_under_contention():
    """One run of the stress below: (latest publish -> receipt in seconds,
    the writer's stats).  Delivery and the forgotten peer are asserted here,
    in every run; the caller judges the times."""
    n_peers, n_pub, n_bursts, per_burst, tick_s = 64, 4, 100, 5, 0.05
    plane, writer, peers, readers = _writer_plane(n_peers)
    minted = [_mint(40, client=f"w{i}") for i in range(n_peers)]
    sent_at = [[] for _ in range(n_peers)]   # per document, in publish order
    recv_at = [[] for _ in range(n_peers)]
    lines = [[] for _ in range(n_peers)]
    mid_run = threading.Event()
    failures = []

    # A peer whose subscriber never reads: it parks on writability.
    pa, pb = socket.socketpair()
    pa.setblocking(False)
    pa.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    parked = plane.new_peer(sock=pa)
    plane.attach("parked", parked, FLAVOR_WIRE)
    plane.publish("parked", _mint(40, client="p", text="x" * 4096))
    deadline = time.monotonic() + 5
    while parked not in writer._registered and time.monotonic() < deadline:
        time.sleep(0.005)
    assert parked in writer._registered, "the full socket never parked"

    # Publisher k owns documents k, k + n_pub, ...: one order a document.
    schedule = [
        [k + n_pub * (i % (n_peers // n_pub))
         for i in range(n_bursts * per_burst)]
        for k in range(n_pub)
    ]
    expect = [schedule[d % n_pub].count(d) for d in range(n_peers)]
    assert sum(expect) == 2000

    def publisher(k: int, start: float):
        spins = 0
        try:
            for burst in range(n_bursts):
                due = start + burst * tick_s
                while time.perf_counter() < due:
                    spins += 1  # hold the GIL: the contention under test
                if k == 0 and burst == n_bursts // 2:
                    mid_run.set()
                for d in schedule[k][burst * per_burst:(burst + 1) * per_burst]:
                    msg = minted[d][len(sent_at[d])]
                    sent_at[d].append(time.perf_counter())
                    plane.publish(f"d{d}", [msg])
        except Exception as e:  # surfaced by the main thread
            failures.append(e)

    def reader(d: int):
        readers[d].settimeout(10)
        f = readers[d].makefile("rb")
        try:
            for _ in range(expect[d]):
                lines[d].append(f.readline())
                recv_at[d].append(time.perf_counter())
        except Exception as e:
            failures.append(e)

    try:
        start = time.perf_counter() + 0.1
        threads = [threading.Thread(target=reader, args=(d,))
                   for d in range(n_peers)]
        threads += [threading.Thread(target=publisher, args=(k, start))
                    for k in range(n_pub)]
        for t in threads:
            t.start()
        assert mid_run.wait(30)
        plane.remove_peer(parked)  # -> writer.forget, in mid-burst
        deadline = time.monotonic() + 2
        while parked in writer._registered and time.monotonic() < deadline:
            time.sleep(0.002)
        assert parked not in writer._registered, "a forgotten peer stayed parked"
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        stats = writer.stats()
        _close_writer_plane(writer, peers, readers)
        pa.close()
        pb.close()
    assert not failures, failures
    worst = 0.0
    for d in range(n_peers):
        assert len(sent_at[d]) == expect[d]
        assert b"".join(lines[d]) == _oracle(minted[d][:expect[d]]), f"d{d}"
        worst = max(worst, max(r - s for s, r in zip(sent_at[d], recv_at[d])))
    return worst, stats


def test_writer_keeps_up_with_ticks_under_publisher_contention():
    """Four publisher threads that never leave the interpreter (they spin
    bytecode between bursts, as a load generator's tick thread does) hand
    the writer 2,000 frames for 64 socket peers in bursts 50 ms apart, a
    reader thread a peer.  Every frame arrives once and in order and a
    parked peer forgotten in mid-burst leaves the selector, in every run;
    no frame reaches its socket, and no wake waits for its pass, longer
    than a quarter of a second (a lost wake waits for the next burst, and
    after the last burst for the 1 s safety net).  The times are a shared
    box's: a run over the limit is run again, twice at most."""
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(2e-5)  # more interleavings, cheaper GIL hand-offs
    # The collector stops every thread while it walks whatever the tests
    # before this one left on the heap (100-300 ms a pass with JAX loaded):
    # set that aside, as benchmark/run.py does around its window.
    gc.collect()
    gc.freeze()
    try:
        for _attempt in range(3):
            worst, stats = _tick_bursts_under_contention()
            if worst < 0.25 and stats["wake_to_pass_ms_max"] < 250:
                break
    finally:
        gc.unfreeze()
        sys.setswitchinterval(old_interval)
    assert worst < 0.25, f"a frame reached its socket {worst * 1e3:.0f} ms late"
    assert stats["wake_to_pass_ms_max"] < 250, stats
