"""Columnar ingest fast path (ISSUE 5): batch-vs-per-message byte identity.

The contract under test: ``ingest_batch`` (vectorized wire decode straight
into the per-doc RowQueues) and the translation plan cache
(``TreeBatchEngine(plan_cache=True)``) are pure performance paths — every
observable byte (device state, texts/values, retained recovery logs,
quarantine routing) must be identical to the per-message walk they replace.
"""

from __future__ import annotations

import random

import jax
import numpy as np

from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine
from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage
from fluidframework_tpu.server.fleet_main import status_snapshot

from test_doc_batch_engine import drive_docs
from test_tree_batch_engine import drive_tree_docs


# ------------------------------------------------------------------ helpers

def _join(client: str, short: int) -> SequencedMessage:
    return SequencedMessage(
        seq=0, min_seq=0, ref_seq=0, client_id=client, client_seq=0,
        type=MessageType.JOIN, contents={"clientId": client, "short": short},
    )


def _op(seq: int, contents: dict, client: str = "w0") -> SequencedMessage:
    return SequencedMessage(
        seq=seq, min_seq=0, ref_seq=0, client_id=client, client_seq=seq,
        type=MessageType.OP, contents=contents,
    )


def _mk(n_docs: int, **kw) -> DocBatchEngine:
    kw.setdefault("max_insert_len", 8)
    kw.setdefault("ops_per_step", 4)
    return DocBatchEngine(
        n_docs, max_segments=256, text_capacity=4096, use_mesh=False, **kw
    )


def _interleaved(svc, n_docs):
    """Round-robin merge of the per-doc sequenced logs: the delivery order a
    multi-doc pump produces, so one ingest_batch call carries a mixed-doc,
    mixed-kind wire batch."""
    logs = [list(svc.document(f"doc{d}").sequencer.log) for d in range(n_docs)]
    out = []
    while any(logs):
        for d in range(n_docs):
            if logs[d]:
                out.append((d, logs[d].pop(0)))
    return out


def _assert_states_identical(a, b, n_docs):
    for d in range(n_docs):
        assert a.text(d) == b.text(d), f"doc {d} text diverged"
    la, lb = jax.tree.leaves(a.state), jax.tree.leaves(b.state)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        assert np.array_equal(np.asarray(xa), np.asarray(xb)), (
            "device state diverged between batch and per-message ingest"
        )
    for d in range(n_docs):
        qa, pa = a.hosts[d].queue.pending()
        qb, pb = b.hosts[d].queue.pending()
        assert np.array_equal(qa, qb) and np.array_equal(pa, pb), (
            f"doc {d} pending rows diverged"
        )


# ------------------------------------------- string engine: batch identity

def test_batch_matches_per_message_fuzz():
    """Random multi-client sessions (inserts, removes, annotates, plain and
    sided obliterates) through real client wire messages: the columnar
    batch path must be byte-identical to the per-message walk — device
    state, texts, and pending queues — for whole-trace batches AND for
    arbitrary mid-stream batch boundaries."""
    for seed in (0, 1):
        n_docs = 6
        svc, expected = drive_docs(n_docs, seed)
        feed = _interleaved(svc, n_docs)

        ref = _mk(n_docs)
        for d, m in feed:
            ref.ingest(d, m)
        ref.step()
        assert not ref.errors().any()

        # One whole-trace batch.
        whole = _mk(n_docs)
        staged = whole.ingest_batch(
            [d for d, _ in feed], [m for _, m in feed]
        )
        whole.step()
        assert staged > 0
        assert whole.health()["ingest_batch_rows"] == staged
        _assert_states_identical(ref, whole, n_docs)

        # Chunked batches (odd size so boundaries land mid-doc-stream).
        chunked = _mk(n_docs)
        for i in range(0, len(feed), 7):
            part = feed[i : i + 7]
            chunked.ingest_batch([d for d, _ in part], [m for _, m in part])
        chunked.step()
        _assert_states_identical(ref, chunked, n_docs)

        for d in range(n_docs):
            assert whole.text(d) == expected[d], f"doc {d} vs oracle"


def test_batch_multichunk_inserts_match():
    """Inserts longer than max_insert_len split into multiple op rows with
    back-to-front chunk emission; the vectorized encoder must reproduce
    the exact row stream."""
    rng = random.Random(3)
    n_docs = 3
    feed = []
    lengths = [0] * n_docs
    seqs = [0] * n_docs
    for _ in range(40):
        d = rng.randrange(n_docs)
        seqs[d] += 1
        if lengths[d] >= 4 and rng.random() < 0.3:
            p = rng.randrange(lengths[d] - 1)
            feed.append((d, _op(seqs[d], {"type": 1, "pos1": p, "pos2": p + 1})))
            lengths[d] -= 1
        else:
            text = "".join(
                rng.choice("xyzw") for _ in range(rng.randint(1, 21))
            )  # up to 3 chunks at L=8
            p = rng.randrange(lengths[d] + 1)
            feed.append((d, _op(seqs[d], {"type": 0, "pos1": p, "seg": text})))
            lengths[d] += len(text)

    ref, batch = _mk(n_docs), _mk(n_docs)
    for eng in (ref, batch):
        for d in range(n_docs):
            eng.ingest(d, _join("w0", 0))
    for d, m in feed:
        ref.ingest(d, m)
    batch.ingest_batch([d for d, _ in feed], [m for _, m in feed])
    _assert_states_identical(ref, batch, n_docs)  # pre-step: raw rows equal
    ref.step()
    batch.step()
    assert not ref.errors().any()
    _assert_states_identical(ref, batch, n_docs)


def test_midbatch_malformed_quarantines_only_offending_doc():
    """A decode failure in the middle of a batch quarantines exactly the
    offending doc: its earlier rows ride the retained log into the
    validated replay (no double-apply, poison dropped), its later messages
    fall back to the oracle path, and every other doc's rows land."""
    n_docs = 3
    feed: list[tuple[int, SequencedMessage]] = []
    for d in range(n_docs):
        for s in range(1, 5):
            feed.append((d, _op(s, {"type": 0, "pos1": 0, "seg": "ab"})))
    # Splice poison for doc 1 mid-batch (unknown client -> KeyError), then
    # a post-poison message for doc 1 that must route through the oracle.
    feed.insert(8, (1, _op(5, {"type": 0, "pos1": 0, "seg": "XX"},
                           client="ghost")))
    feed.append((1, _op(6, {"type": 0, "pos1": 0, "seg": "cd"})))

    eng = _mk(n_docs)
    for d in range(n_docs):
        eng.ingest(d, _join("w0", 0))
    eng.ingest_batch([d for d, _ in feed], [m for _, m in feed])
    eng.step()

    assert 1 in eng.quarantine and 0 not in eng.quarantine
    assert 2 not in eng.quarantine
    h = eng.health()
    assert h["quarantines"] == 1
    assert h["poison_ops_dropped"] >= 1
    assert h["ingest_batch_rows"] > 0
    # The post-quarantine message fell back to the per-message path.
    assert h["ingest_fallback_msgs"] >= 1
    # Healthy docs: all four inserts landed.
    assert eng.text(0) == eng.text(2) == "ab" * 4
    # Quarantined doc: everything except the poison op applied exactly once
    # (its earlier batch rows were dropped from the scatter and replayed
    # from the retained log instead; the later message went oracle-side).
    assert eng.text(1) == "cd" + "ab" * 4


def test_midbatch_malformed_scalar_quarantines_like_per_message():
    """A structurally-valid op carrying a non-int scalar (string annotate
    value) must quarantine its doc inside the batch walk — exactly like
    the per-message path — never escape to the whole-batch numpy scatter
    and take every doc's rows down with it."""
    eng = _mk(2)
    for d in range(2):
        eng.ingest(d, _join("w0", 0))
    feed = [
        (0, _op(1, {"type": 0, "pos1": 0, "seg": "aa"})),
        (1, _op(1, {"type": 0, "pos1": 0, "seg": "bb"})),
        (0, _op(2, {"type": 2, "pos1": 0, "pos2": 2, "props": {1: "bold"}})),
        (1, _op(2, {"type": 0, "pos1": 0, "seg": "cc"})),
    ]
    eng.ingest_batch([d for d, _ in feed], [m for _, m in feed])
    eng.step()
    assert 0 in eng.quarantine and 1 not in eng.quarantine
    assert eng.text(1) == "ccbb"  # healthy doc's rows all landed
    # The validated replay applied the insert (and the annotate, which the
    # reference oracle accepts with a string value) exactly once.
    assert eng.text(0) == "aa"
    assert eng.health()["quarantines"] == 1


def test_midbatch_malformed_scalar_keeps_collectors_aligned():
    """A coercion failure must leave the columnar collectors untouched for
    the failing message: if bookkeeping (row ids, chunk counts) were
    appended before the scalars coerced, the whole-batch scatter would
    crash with a shape mismatch instead of quarantining one doc."""
    eng = _mk(2)
    for d in range(2):
        eng.ingest(d, _join("w0", 0))
    feed = [
        (0, _op(1, {"type": 0, "pos1": 0, "seg": "hello"})),
        (0, _op(2, {"type": 0, "pos1": {"x": 1}, "seg": "world"})),
        (1, _op(1, {"type": 0, "pos1": 0, "seg": "goodbye"})),
        (1, _op(2, {"type": 2, "pos1": 0, "pos2": 2, "props": {1: 5}})),
    ]
    eng.ingest_batch([d for d, _ in feed], [m for _, m in feed])
    eng.step()
    assert 0 in eng.quarantine and 1 not in eng.quarantine
    assert eng.text(1) == "goodbye"
    assert eng.text(0) == "hello"  # replay: everything but the poison op


def test_out_of_int32_scalar_fails_loud_like_per_message():
    """Per-message ingest raises OverflowError on out-of-int32 scalars
    (np.array refuses); the batch path must do the same at collection
    time — never wrap silently through its int64 staging columns, and
    never lose the batch's earlier rows to a scatter-time crash."""
    import pytest

    for contents in (
        {"type": 0, "pos1": 2**40, "seg": "xx"},  # insert pos
        {"type": 2, "pos1": 0, "pos2": 2, "props": {1: 2**40}},  # annotate
    ):
        ref, batch = _mk(2), _mk(2)
        for eng in (ref, batch):
            for d in range(2):
                eng.ingest(d, _join("w0", 0))
        feed = [
            (0, _op(1, {"type": 0, "pos1": 0, "seg": "ok"})),
            (1, _op(1, contents)),
        ]
        for d, m in feed[:1]:
            ref.ingest(d, m)
        with pytest.raises(OverflowError):
            ref.ingest(*feed[1])
        with pytest.raises(OverflowError):
            batch.ingest_batch([d for d, _ in feed], [m for _, m in feed])
        ref.step()
        batch.step()
        # Earlier rows landed identically on both paths; no silent wrap.
        _assert_states_identical(ref, batch, 2)
        assert batch.text(0) == "ok"


def test_batch_subscriber_raise_is_crash_equivalent():
    """A raising batch subscriber surfaces the raise (loud failure) and the
    pump's records stay consumed — crash-equivalent, NO offset rewind:
    the subscriber may have landed a prefix of the batch, and engines
    carry no seq dedupe above the checkpoint floor, so a rewind would
    double-apply that prefix.  Durable recovery owns redelivery.  The
    stream is not wedged: later messages flow normally."""
    import pytest

    from fluidframework_tpu.server.lambdas import BroadcasterLambda
    from fluidframework_tpu.server.ordered_log import Topic

    topic = Topic("deltas", 1)
    bl = BroadcasterLambda(topic, 0)
    seen: list[list] = []
    fail = [True]

    def flaky(msgs):
        if fail[0]:
            raise NotImplementedError("unsupported wire form")
        seen.append(msgs)

    bl.subscribe_batch("a", flaky)
    msgs = [_op(s, {"type": 0, "pos1": 0, "seg": "x"}) for s in (1, 2)]
    for m in msgs:
        topic.produce("a", m)
    with pytest.raises(NotImplementedError):
        bl.pump()
    fail[0] = False
    assert bl.pump() == 0  # consumed, not redelivered (no double-apply)
    late = _op(3, {"type": 0, "pos1": 0, "seg": "y"})
    topic.produce("a", late)
    assert bl.pump() == 1 and seen == [[late]]  # stream continues


def test_recovery_log_equivalence():
    """Under recovery="grow" both ingest paths must retain the SAME replay
    log (same messages, same order) — the log is the recovery source of
    truth, so a batch-path divergence would corrupt every later replay."""
    n_docs = 4
    svc, _expected = drive_docs(n_docs, seed=2)
    feed = _interleaved(svc, n_docs)

    ref, batch = _mk(n_docs), _mk(n_docs)
    for d, m in feed:
        ref.ingest(d, m)
    batch.ingest_batch([d for d, _ in feed], [m for _, m in feed])
    for d in range(n_docs):
        la = [(m.seq, m.client_id, m.type) for m in ref.hosts[d].log]
        lb = [(m.seq, m.client_id, m.type) for m in batch.hosts[d].log]
        assert la == lb, f"doc {d} recovery logs diverged"
    ref.step()
    batch.step()
    _assert_states_identical(ref, batch, n_docs)


def test_counters_surface_in_health_and_fleet_status():
    n_docs = 2
    svc, _ = drive_docs(n_docs, seed=4, rounds=2)
    feed = _interleaved(svc, n_docs)
    eng = _mk(n_docs)
    eng.ingest_batch([d for d, _ in feed], [m for _, m in feed])
    eng.step()
    h = eng.health()
    assert h["ingest_batch_rows"] > 0
    assert "ingest_fallback_msgs" in h  # JOINs walked the per-message path
    snap = status_snapshot(eng, [f"doc{d}" for d in range(n_docs)], rows=7)
    assert snap["health"]["ingest_batch_rows"] == h["ingest_batch_rows"]
    assert snap["rows"] == 7


# ------------------------------------------- tree engine: plan-cache identity

def test_tree_plan_cache_byte_identity():
    """The translation plan cache must be invisible: random tree sessions
    (inserts, removes, sets, moves, transactions) through plan_cache=True
    vs the legacy per-row emit produce byte-identical device state — and
    the cache actually hits in steady state."""
    for seed in (0, 3):
        n_docs = 4
        svc, expected = drive_tree_docs(n_docs, seed=seed)
        engines = []
        for cached in (False, True):
            eng = TreeBatchEngine(n_docs, plan_cache=cached)
            for d in range(n_docs):
                for msg in svc.document(f"doc{d}").sequencer.log:
                    eng.ingest(d, msg)
            eng.step()
            assert not eng.errors().any()
            engines.append(eng)
        legacy, cached = engines
        for d in range(n_docs):
            assert cached.values(d) == legacy.values(d) == expected[d], d
        la, lb = jax.tree.leaves(legacy.state), jax.tree.leaves(cached.state)
        for xa, xb in zip(la, lb):
            assert np.array_equal(np.asarray(xa), np.asarray(xb)), (
                f"seed {seed}: tree device state diverged under plan cache"
            )
        h = cached.health()
        assert h["translation_plan_hits"] > 0
        assert 0.0 < h["translation_plan_hit_rate"] <= 1.0
        assert legacy.health().get("translation_plan_hits", 0) == 0


def test_summary_ack_carries_msn():
    """mint_service stamps summary acks with the ack-derived MSN, bounded
    by the live collab window, and the floor survives checkpoint/restore
    (Python sequencer and the native shim agree)."""
    from fluidframework_tpu.protocol.messages import UnsequencedMessage
    from fluidframework_tpu.server.sequencer import Sequencer

    def drive(s):
        s.join("c1")
        for i in range(1, 5):
            s.ticket(UnsequencedMessage(
                client_id="c1", client_seq=i, ref_seq=s.seq,
                contents={"type": 0, "pos1": 0, "seg": "x"},
            ))
        return s.mint_service(
            MessageType.SUMMARY_ACK,
            {"handle": "h", "refSeq": 3, "summarySeq": 5},
        )

    s = Sequencer()
    ack = drive(s)
    assert ack.contents["msn"] == min(3, s.min_seq)
    assert s.ack_msn == min(3, s.min_seq)
    restored = Sequencer.restore(s.checkpoint())
    assert restored.ack_msn == s.ack_msn  # floor survives restart

    from fluidframework_tpu.native import NativeSequencer, native_available

    if native_available():
        nat = NativeSequencer()
        nack = drive(nat)
        assert nack.contents["msn"] == ack.contents["msn"]


def test_broadcaster_batch_delivery():
    """BroadcasterLambda.subscribe_batch hands each pump's decoded messages
    for a doc as ONE list (the columnar-ingest seam) while per-message
    subscribers and offset tracking behave exactly as before."""
    from fluidframework_tpu.server.lambdas import BroadcasterLambda
    from fluidframework_tpu.server.ordered_log import Topic

    topic = Topic("deltas", 1)
    bl = BroadcasterLambda(topic, 0)
    per_msg, batches = [], []
    bl.subscribe("a", per_msg.append)
    bl.subscribe_batch("a", batches.append)
    msgs = [_op(s, {"type": 0, "pos1": 0, "seg": "x"}) for s in (1, 2, 3)]
    for m in msgs:
        topic.produce("a", m)
    other = _op(1, {"type": 0, "pos1": 0, "seg": "y"})
    topic.produce("b", other)  # no batch subscriber: must not batch
    assert bl.pump() == 4
    assert per_msg == msgs
    assert batches == [msgs]  # one list per pump, order preserved
    assert bl.pump() == 0 and batches == [msgs]  # offset advanced
    topic.produce("a", other)
    assert bl.pump() == 1
    assert batches == [msgs, [other]]


# ------------------------------------------------- scribe-driven MSN zamboni

def test_msn_compaction_rides_summary_ack():
    """Scribe-driven MSN (ROADMAP): a summaryAck in the firehose feed — not
    a timer — triggers ``engine.compact()`` in the fleet consumer, and the
    ``msn_compactions`` counter surfaces through health() and the fleet
    status snapshot."""
    from fluidframework_tpu.dds.shared_string import SharedString
    from fluidframework_tpu.protocol.messages import UnsequencedMessage
    from fluidframework_tpu.server.fleet_consumer import FleetConsumer
    from fluidframework_tpu.server.netserver import NetworkServer

    srv = NetworkServer().start()
    fc = None
    try:
        with srv.lock:
            doc = srv.service.document("d0")
            w = SharedString(client_id="w0")
            doc.connect(w.client_id, w.process)
            doc.process_all()
        w.insert_text(0, "hello")
        rows = 0
        with srv.lock:
            for m in w.take_outbox():
                doc.submit(m)
                rows += 1
            doc.process_all()
        eng = _mk(1)
        fc = FleetConsumer("127.0.0.1", srv.port, eng, ["d0"])
        fc.run_for(rows)
        assert eng.health().get("msn_compactions", 0) == 0

        # The scribe's voice: a summarize op whose ack carries the MSN.
        with srv.lock:
            handle = doc.upload_summary({"type": "tree", "entries": {}})
            doc.connect("scriber", lambda m: None)
            doc.process_all()
            doc.submit(UnsequencedMessage(
                client_id="scriber", client_seq=1,
                ref_seq=doc.sequencer.seq, type=MessageType.SUMMARIZE,
                contents={"handle": handle, "refSeq": doc.sequencer.seq},
            ))
            doc.process_all()
        for _ in range(200):
            fc.pump(0.02)
            if eng.health().get("msn_compactions", 0):
                break
        h = eng.health()
        assert h["msn_compactions"] >= 1, "ack did not trigger zamboni"
        snap = status_snapshot(eng, ["d0"])
        assert snap["health"]["msn_compactions"] == h["msn_compactions"]
        assert eng.text(0) == "hello"  # compaction is invisible
    finally:
        if fc is not None:
            fc.close()
        srv.stop()


def test_compact_applies_staged_rows_before_shrinking_the_window():
    """A consumer that fell behind reads ops AND the summary ack that
    follows them in one pass: the ack's MSN is newer than the ref-seq of
    the rows still staged.  ``compact()`` must apply those rows before
    zamboni runs — evicting first drops the tombstone a concurrent insert
    still resolves its position against, and the insert lands in the wrong
    place with no error bit (found by the on-chip smoke, where one fleet
    step takes seconds and every ack rides in behind a backlog)."""
    from fluidframework_tpu.dds.shared_string import SharedString
    from fluidframework_tpu.loadgen.coordinator import oracle_text
    from fluidframework_tpu.server.local_service import LocalService

    doc = LocalService().document("d")
    a, b = SharedString(client_id="a"), SharedString(client_id="b")
    for c in (a, b):
        doc.connect(c.client_id, c.process)
    doc.process_all()

    def flush(*clients):
        for c in clients:
            for m in c.take_outbox():
                doc.submit(m)
        doc.process_all()

    a.insert_text(0, "abcdefgh")
    flush(a)
    b.remove_range(2, 4)     # sequenced first ...
    a.insert_text(5, "Z")    # ... concurrent: a has not seen the remove
    flush(b, a)
    for _ in range(2):       # both ref-seqs (so the MSN) pass the remove
        a.insert_text(0, "1")
        b.insert_text(0, "2")
        flush(a, b)
    log = doc.sequencer.log
    assert doc.sequencer.min_seq > 4 and a.text == b.text == oracle_text(log)
    cut = 1 + next(
        i for i, m in enumerate(log)
        if m.type == MessageType.OP and m.contents["type"] == 1
    )
    eng = _mk(1)
    eng.ingest_lines(0, b"".join(m.wire_line() for m in log[:cut]))
    eng.step()               # the remove is applied; its tombstone is live
    eng.ingest_lines(0, b"".join(m.wire_line() for m in log[cut:]))
    eng.compact()            # what FleetConsumer.pump does on an ack
    eng.step()
    assert eng.text(0) == a.text


def test_tree_ingest_batch_wrapper_matches():
    n_docs = 3
    svc, expected = drive_tree_docs(n_docs, seed=1, steps=15)
    feed = _interleaved(svc, n_docs)
    eng = TreeBatchEngine(n_docs)
    eng.ingest_batch([d for d, _ in feed], [m for _, m in feed])
    eng.step()
    for d in range(n_docs):
        assert eng.values(d) == expected[d], d
