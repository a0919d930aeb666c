"""Wire-bytes -> device through the product stack (VERDICT r3 weak #4).

Writers edit through the normal sequenced path; a FleetConsumer subscribes
to the netserver firehose over REAL TCP sockets and feeds the raw bytes into
a DocBatchEngine via the C++ encoder — no per-op Python on the data plane.
The device fleet must reproduce every writer's converged text exactly.
"""

from __future__ import annotations

import random
import threading

import pytest

from fluidframework_tpu.dds.shared_string import SharedString
from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.native.ingest_native import available
from fluidframework_tpu.server.fleet_consumer import FleetConsumer
from fluidframework_tpu.server.netserver import NetworkServer

pytestmark = pytest.mark.skipif(
    not available(), reason="native ingest encoder unavailable"
)


@pytest.fixture
def server():
    srv = NetworkServer().start()
    yield srv
    srv.stop()


def _writers(server, doc_id: str, n: int) -> list[SharedString]:
    with server.lock:
        doc = server.service.document(doc_id)
        out = []
        for w in range(n):
            c = SharedString(client_id=f"{doc_id}-w{w}")
            doc.connect(c.client_id, c.process)
            out.append(c)
        doc.process_all()
    return out


def _flush(server, doc_id: str, writers) -> int:
    """Submit outboxes; returns op messages sequenced."""
    n = 0
    with server.lock:
        doc = server.service.document(doc_id)
        for c in writers:
            for m in c.take_outbox():
                doc.submit(m)
                n += 1
        doc.process_all()
    return n


def test_wire_to_device_single_doc(server):
    writers = _writers(server, "d0", 2)
    a, b = writers
    a.insert_text(0, "hello")
    rows = _flush(server, "d0", writers)
    b.insert_text(5, " world")
    a.annotate_range(0, 5, 3, 7)
    rows += _flush(server, "d0", writers)
    a.remove_range(0, 1)
    rows += _flush(server, "d0", writers)

    eng = DocBatchEngine(1, max_segments=256, text_capacity=4096,
                         max_insert_len=8, ops_per_step=8, use_mesh=False,
                         recovery="off")
    fc = FleetConsumer("127.0.0.1", server.port, eng, ["d0"])
    try:
        fc.run_for(rows)
        assert eng.text(0) == a.text == "ello world"
        assert not eng.errors().any()
        # The data plane really was the native path.
        assert eng.hosts[0].mode == "native"
        assert fc.bytes_consumed > 0
    finally:
        fc.close()


def test_wire_to_device_fleet_with_live_tail(server):
    """Multi-doc fleet: catch-up history + live ops arriving while the
    consumer is attached, randomized edits, all docs converge."""
    rng = random.Random(3)
    n_docs = 4
    fleets = [(f"d{i}", _writers(server, f"d{i}", 2)) for i in range(n_docs)]
    rows = [0] * n_docs

    def edit_round():
        for i, (doc_id, writers) in enumerate(fleets):
            for c in writers:
                n = len(c.text)
                if rng.random() < 0.7 or n < 4:
                    c.insert_text(rng.randint(0, n), "".join(
                        rng.choice("abcdef") for _ in range(rng.randint(1, 6))
                    ))
                else:
                    p = rng.randint(0, n - 2)
                    c.remove_range(p, p + 1)
            rows[i] += _flush(server, doc_id, writers)

    for _ in range(4):
        edit_round()  # pre-attach history (exercises firehose catch-up)

    eng = DocBatchEngine(n_docs, max_segments=512, text_capacity=8192,
                         max_insert_len=8, ops_per_step=8, use_mesh=False,
                         recovery="off")
    fc = FleetConsumer("127.0.0.1", server.port, eng,
                       [d for d, _ in fleets])
    try:
        # Live tail lands while attached — from another thread, like a real
        # front-end serving concurrent writers.
        t = threading.Thread(target=lambda: [edit_round() for _ in range(3)])
        t.start()
        t.join()
        # Inserts of len<=8 are single rows; removes are single rows.
        fc.run_for(sum(rows))
        for i, (_doc_id, writers) in enumerate(fleets):
            assert eng.text(i) == writers[0].text, f"doc {i} diverged"
        assert not eng.errors().any()
    finally:
        fc.close()


def test_a_summary_ack_hands_over_its_own_document_only(server):
    """Two documents' feeds in one pump, one of them with a summary ack:
    only that document is handed to the engine, and nothing is compacted
    inside the pump (PR 35)."""
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        UnsequencedMessage,
    )

    ws = {d: _writers(server, d, 1) for d in ("a0", "a1")}
    rows = 0
    for d, (w,) in ws.items():
        w.insert_text(0, "hello")
        rows += _flush(server, d, [w])
    with server.lock:
        doc = server.service.document("a1")
        doc.connect("summarizer", lambda m: None)
        doc.process_all()
        handle = doc.upload_summary({"type": "tree", "entries": {}})
        doc.submit(UnsequencedMessage(
            client_id="summarizer", client_seq=1, ref_seq=doc.sequencer.seq,
            type=MessageType.SUMMARIZE,
            contents={"handle": handle, "refSeq": doc.sequencer.seq},
        ))
        doc.process_all()
    eng = DocBatchEngine(8, max_segments=256, text_capacity=4096,
                         max_insert_len=8, ops_per_step=8, use_mesh=False)
    handed = []
    compact = eng.compact
    eng.compact = lambda docs=None: (handed.append(list(docs)),
                                     compact(docs))[1]
    fc = FleetConsumer("127.0.0.1", server.port, eng, ["a0", "a1"])
    try:
        # Catch-up: everything is in the sockets; pump until both are read.
        while fc.rows_staged < rows or not handed:
            fc.pump(0.05)
        h = eng.health()
        assert handed == [[1]] and eng.compact_due == {1}
        assert h["acks_seen"] == 1 and h["msn_compactions"] == 1
        assert h["compact_dispatches"] == 0 and fc.acks_unstepped
        assert fc.acks_by_doc == [0, 1]
        fc.step()
        h = eng.health()
        assert (h["compact_dispatches"], h["compacted_docs"],
                h["compacted_lanes"]) == (1, 1, 1)
        assert not eng.compact_due and not fc.acks_unstepped
        assert eng.texts()[:2] == ["hello", "hello"]
    finally:
        fc.close()


def test_fleet_main_entry_cross_process(server):
    """The deployable fleet entry (deploy/compose.yaml fleet tier): spawn
    fleet_main as its OWN process against the TCP front; it consumes,
    applies on device, reports status JSON, and exits at the row bound."""
    import json
    import os
    import subprocess
    import sys

    writers = _writers(server, "dm", 2)
    a, _b = writers
    a.insert_text(0, "compose")
    rows = _flush(server, "dm", writers)

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "fluidframework_tpu.server.fleet_main",
         "--port", str(server.port), "--docs", "dm",
         "--exit-after-rows", str(rows)],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=repo_root,
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    # The readiness line says where the engine state lives, as JAX
    # reported it in the fleet process.
    ready = next(ln for ln in lines if ln.get("ready"))
    assert ready["platform"] == "cpu" and ready["device_count"] >= 1
    assert sum(ready["resident_bytes_per_device"].values()) > 0
    status = lines[-1]
    assert status["done"] and status["errors"] == 0
    assert status["texts"]["dm"] == "compose"
    assert status["health"]["ingest_plane"] == "native"


def test_fleet_main_refuses_mesh_wider_than_devices(server):
    """``--mesh N`` on fewer than N devices is an error, never a silently
    narrower fleet (one CPU device here; XLA_FLAGS stripped)."""
    import os
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "fluidframework_tpu.server.fleet_main",
         "--port", str(server.port), "--docs", "dm", "--mesh", "4"],
        capture_output=True, text=True, timeout=180, env=env, cwd=repo_root,
    )
    assert out.returncode != 0
    assert "--mesh 4 needs 4 devices; JAX sees 1 (cpu)" in out.stderr


def test_fleet_consumer_boots_from_scribe_summary(server, tmp_path):
    """Boot-from-summary through the REAL wire path: a scribe summarizes
    and acks the doc's sequenced prefix; a cold FleetConsumer seeds its
    engine from the acked commit, consumes the firehose from offset 0, and
    converges byte-identically — replaying only the post-ack tail."""
    from fluidframework_tpu.server.ordered_log import Topic
    from fluidframework_tpu.server.scribe import (
        ScribeConfig,
        ScribeLambda,
        SummaryRecordStore,
    )

    writers = _writers(server, "db", 2)
    a, b = writers
    a.insert_text(0, "hello scribe")
    _flush(server, "db", writers)
    b.remove_range(0, 6)
    _flush(server, "db", writers)

    # The scribe consumes the same total order (here: mirrored from the
    # doc's sequencer log into an op topic) and acks the prefix.
    topic = Topic("deltas", 1)
    with server.lock:
        for m in server.service.document("db").sequencer.log:
            topic.produce("db", m)
    scribe = ScribeLambda(topic, str(tmp_path / "scribe"),
                          config=ScribeConfig(max_ops=1))
    scribe.pump()
    acked_seq = scribe.refs["db"]["seq"]
    assert scribe.health()["summaries_written"] >= 1

    # Post-ack tail lands after the summary was acked.
    a.insert_text(len(a.text), "!")
    tail_rows = _flush(server, "db", writers)

    eng = DocBatchEngine(1, max_segments=256, text_capacity=4096,
                         max_insert_len=16, ops_per_step=8, use_mesh=False,
                         doc_keys=["db"])
    fc = FleetConsumer("127.0.0.1", server.port, eng, ["db"],
                       boot_store=SummaryRecordStore.from_scribe(scribe))
    try:
        assert fc.booted_docs == [0]
        assert eng.text(0) == "scribe"  # summary state alone, pre-catch-up
        fc.run_for(tail_rows)  # catch-up replays all; only the tail stages
        assert eng.text(0) == a.text == "scribe!"
        h = fc.health()
        assert h["checkpointed_ops_skipped"] > 0, "prefix not skipped"
        assert h["boot_replay_len"] == tail_rows
        assert h["booted_docs"] == 1
        assert eng.hosts[0].base_seq == acked_seq
        assert not eng.errors().any()
    finally:
        fc.close()
        scribe.close()


def test_fleet_consumer_reports_dead_sockets_on_shard_close():
    """The shard closing the firehose must surface as dead_socks (the
    supervisor-restart signal), never as a silent healthy-looking idle.
    Modeled with a minimal shard that closes right after the handshake —
    the socket state a dying shard PROCESS leaves behind."""
    import json as _json
    import socket as _socket

    lsock = _socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]

    def serve():
        conn, _ = lsock.accept()
        conn.recv(4096)  # the consume request
        conn.sendall(
            (_json.dumps({"t": "consuming", "doc": "dx"}) + "\n").encode()
        )
        conn.close()  # shard dies

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    eng = DocBatchEngine(1, max_segments=64, text_capacity=512,
                         max_insert_len=8, ops_per_step=4, use_mesh=False,
                         recovery="off")
    fc = FleetConsumer("127.0.0.1", port, eng, ["dx"])
    try:
        assert not fc.dead_socks
        for _ in range(100):
            fc.pump()
            if fc.dead_socks:
                break
        assert fc.dead_socks == {0}
    finally:
        fc.close()
        lsock.close()


def test_wire_to_device_mesh_served_fleet(server):
    """The production mesh path end to end: wire bytes off the firehose,
    native decode, placement-packed staging, shard_map megastep dispatch
    over the 8 virtual devices — every doc converges and the per-shard
    health surface is live (the ``fleet_main --mesh`` serving loop)."""
    from fluidframework_tpu.parallel.mesh import doc_mesh

    n_docs = 8
    fleets = [(f"m{i}", _writers(server, f"m{i}", 2)) for i in range(n_docs)]
    rows = [0] * n_docs
    rng = random.Random(11)
    for _ in range(3):
        for i, (doc_id, writers) in enumerate(fleets):
            for c in writers:
                n = len(c.text)
                if rng.random() < 0.7 or n < 4:
                    c.insert_text(rng.randint(0, n), "".join(
                        rng.choice("abcdef") for _ in range(rng.randint(1, 6))
                    ))
                else:
                    p = rng.randint(0, n - 2)
                    c.remove_range(p, p + 1)
            rows[i] += _flush(server, doc_id, writers)

    eng = DocBatchEngine(n_docs, max_segments=512, text_capacity=8192,
                         max_insert_len=8, ops_per_step=8, megastep_k=4,
                         mesh=doc_mesh(), spare_slots=8)
    fc = FleetConsumer("127.0.0.1", server.port,
                       eng, [doc_id for doc_id, _ in fleets])
    try:
        fc.run_for(sum(rows))
        for i, (doc_id, writers) in enumerate(fleets):
            assert eng.text(i) == writers[0].text, f"{doc_id} diverged"
        h = fc.health()
        assert h["n_shards"] == 8 and len(h["shard_ops"]) == 8
        assert h["megastep_dispatches"] >= 1
        # Live migration composes with the consumer: move a doc and keep
        # serving (placement is host-side; the socket set is untouched).
        src = eng.shard_of(0)
        dst = (src + 1) % eng.n_shards
        assert eng.migrate_doc(0, dst) and eng.shard_of(0) == dst
        for i, (doc_id, writers) in enumerate(fleets):
            writers[0].insert_text(0, "Z")
            rows[i] += _flush(server, doc_id, writers)
        fc.run_for(sum(rows))
        for i, (doc_id, writers) in enumerate(fleets):
            assert eng.text(i) == writers[0].text, f"{doc_id} post-move"
    finally:
        fc.close()
