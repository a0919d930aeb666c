"""Compaction of the documents whose summary was acked (PR 35).

``DocBatchEngine.compact(docs)`` marks documents due and ``step`` compacts
them after it has applied what was staged, through the cohort compaction
program.  What zamboni may do is held to the path that existed before it:
``_fleet_compact`` (``vmap(mk.compact)`` over the whole fleet at each host's
``min_seq``) for the state, ``loadgen.coordinator.oracle_text`` of the
sequencer log for the text.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.dds.shared_string import SharedString
from fluidframework_tpu.loadgen.coordinator import oracle_text
from fluidframework_tpu.models import doc_batch_engine as dbe
from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.native.ingest_native import available
from fluidframework_tpu.observability import flight_recorder as fr
from fluidframework_tpu.ops import mergetree_kernel as mk
from fluidframework_tpu.parallel import mesh as pm
from fluidframework_tpu.protocol.messages import (
    MessageType,
    UnsequencedMessage,
)
from fluidframework_tpu.server.fleet_consumer import FleetConsumer
from fluidframework_tpu.server.fleet_main import status_snapshot
from fluidframework_tpu.server.local_service import LocalService
from fluidframework_tpu.server.netserver import NetworkServer

GEOM = dict(max_segments=128, text_capacity=2048, max_insert_len=8,
            ops_per_step=8, use_mesh=False)


# ------------------------------------------------------------------ traffic
def _edit(rng: random.Random, c: SharedString, obliterate: bool) -> None:
    """One edit, one device row: the benchmark plant's mix."""
    n = len(c.text)
    r = rng.random()
    if n < 12 or r < 0.45:
        c.insert_text(rng.randint(0, n), "".join(
            rng.choice("abcdefghij") for _ in range(rng.randint(1, 8))))
    elif obliterate and r < 0.6:
        p = rng.randint(1, n - 6)
        c.obliterate_range_sided((p, True), (p + rng.randint(1, 3), False))
    elif r < 0.8:
        p = rng.randint(0, n - 3)
        c.remove_range(p, p + rng.randint(1, 2))
    else:
        p = rng.randint(0, n - 4)
        c.annotate_range(p, p + rng.randint(1, 3), rng.choice((1, 2, 3)),
                         rng.randint(1, 99))


def _writers(doc, doc_id: str, n: int = 2) -> list[SharedString]:
    out = []
    for w in range(n):
        c = SharedString(client_id=f"{doc_id}-w{w}")
        doc.connect(c.client_id, c.process)
        out.append(c)
    doc.process_all()
    return out


def _flush(doc, writers) -> int:
    """Submit every outbox, THEN deliver: the round's ops are concurrent."""
    n = 0
    for c in writers:
        for m in c.take_outbox():
            doc.submit(m)
            n += 1
    doc.process_all()
    return n


def _summarize(doc, state: dict) -> None:
    """The summarizer's op and the scribe's ack, as the benchmark's plant
    and chip_smoke send them; none of it is an op row."""
    if "seq" not in state:
        doc.connect("summarizer", lambda m: None)
        doc.process_all()
        state["seq"] = 0
    state["seq"] += 1
    at = doc.sequencer.seq
    handle = doc.upload_summary({"type": "tree", "entries": {}})
    doc.submit(UnsequencedMessage(
        client_id="summarizer", client_seq=state["seq"], ref_seq=at,
        type=MessageType.SUMMARIZE, contents={"handle": handle, "refSeq": at},
    ))
    doc.process_all()


def _wire(log) -> bytes:
    return b"".join(m.wire_line() for m in log)


# --------------------------------------------------------- 1. row identity
N_DOCS = 64


@pytest.fixture(scope="module")
def history():
    """64 documents after a seeded history of concurrent rounds, applied and
    not yet compacted: the engine, a host copy of its state, and what
    ``_fleet_compact`` makes of that state at the hosts' floors.  Documents
    0-15 end on an obliterate above their floor (live after zamboni),
    32-63 never obliterate."""
    rng = random.Random(35)
    svc = LocalService()
    eng = DocBatchEngine(N_DOCS, **GEOM, ob_slots=16)
    for d in range(N_DOCS):
        doc = svc.document(f"d{d}")
        ws = _writers(doc, f"d{d}")
        for _round in range(6):
            for c in ws:
                for _ in range(rng.randint(1, 3)):
                    _edit(rng, c, obliterate=d < 32)
            _flush(doc, ws)
        if d < 16:
            n = len(ws[0].text)
            ws[0].obliterate_range_sided((1, True), (min(3, n - 1), False))
            _flush(doc, ws)
        assert ws[0].text == ws[1].text == oracle_text(doc.sequencer.log)
        eng.ingest_lines(d, _wire(doc.sequencer.log))
    eng.step()
    assert not eng.errors().any() and not eng.overflow
    before = jax.tree.map(np.asarray, eng.state)
    mins = np.zeros((eng.capacity,), np.int32)
    for d, h in enumerate(eng.hosts):
        mins[eng._slot[d]] = h.min_seq
    assert (mins[:N_DOCS] > 0).all()
    want = jax.tree.map(np.asarray, dbe._fleet_compact(
        jax.tree.map(jnp.array, before), jnp.asarray(mins)))
    assert (want.nseg < before.nseg).sum() > N_DOCS // 2   # zamboni has work
    live = [d for d in range(N_DOCS)
            if (want.ob_key[eng._slot[d]] >= 0).any()]
    none = [d for d in range(N_DOCS)
            if not (want.ob_key[eng._slot[d]] >= 0).any()]
    assert set(range(16)) <= set(live) and set(range(32, 64)) <= set(none)
    return eng, before, want, live, none


@pytest.mark.parametrize("with_live_obliterate", [True, False])
@pytest.mark.parametrize("n", [1, 2, 3, 8])
def test_cohort_rows_equal_fleet_compact_and_the_rest_is_untouched(
        history, n, with_live_obliterate):
    eng, before, want, live, none = history
    rng = random.Random(n * 2 + with_live_obliterate)
    if with_live_obliterate:
        docs = rng.sample(live, 1) + rng.sample(none, n - 1)
    else:
        docs = rng.sample(none, n)
    eng.state = jax.tree.map(jnp.array, before)
    eng._compact_built.clear()       # the engine is shared by the cases
    lanes0 = eng.counters.get("compacted_lanes")
    eng.compact(docs)
    eng.step()
    assert eng.counters.get("compacted_lanes") - lanes0 == (
        DocBatchEngine._cohort_lanes(n))
    got = jax.tree.map(np.asarray, eng.state)
    rows = np.zeros((eng.capacity,), bool)
    rows[eng._slot[docs]] = True
    for name, g, w, b in zip(mk.DocState._fields, got, want, before):
        for gi, wi, bi in zip(*(jax.tree.leaves(x) for x in (g, w, b))):
            assert gi.dtype == wi.dtype and gi.shape == wi.shape, name
            assert gi[rows].tobytes() == wi[rows].tobytes(), name
            assert gi[~rows].tobytes() == bi[~rows].tobytes(), name


# --------------------------------------------------- 2. served equivalence
@pytest.mark.skipif(not available(), reason="native ingest unavailable")
def test_served_per_document_equals_fleet_wide_equals_oracle():
    """Two engines behind FleetConsumers on the same firehose: one compacts
    the acked documents in its step, one is forced fleet-wide inside the
    pump (``docs=None``, the behaviour before PR 35)."""
    rng = random.Random(7)
    srv = NetworkServer().start()
    ids = [f"e{i}" for i in range(4)]
    engs = [DocBatchEngine(len(ids), **GEOM) for _ in range(2)]
    engs[1].compact = lambda docs=None: DocBatchEngine.compact(engs[1])
    fcs = []
    try:
        with srv.lock:
            docs = {i: srv.service.document(i) for i in ids}
            ws = {i: _writers(docs[i], i) for i in ids}
        fcs = [FleetConsumer("127.0.0.1", srv.port, e, ids) for e in engs]
        rows, acks, summ = 0, 0, {i: {} for i in ids}

        def settle():
            for fc in fcs:
                fc.run_for(rows)
                while fc.engine.counters.get("acks_seen") < acks:
                    fc.pump(0.05)
                fc.step()
            with srv.lock:
                want = [oracle_text(docs[i].sequencer.log) for i in ids]
            for fc in fcs:
                assert fc.engine.texts() == want
                assert not fc.engine.errors().any()

        for rnd in range(12):
            for i in ids:
                with srv.lock:
                    for c in ws[i]:
                        for _ in range(rng.randint(1, 3)):
                            _edit(rng, c, obliterate=True)
                    rows += _flush(docs[i], ws[i])
                    if rng.random() < 0.4:
                        _summarize(docs[i], summ[i])
                        acks += 1
                        # Ops that follow the ack on the same firehose.
                        _edit(rng, ws[i][0], obliterate=False)
                        rows += _flush(docs[i], ws[i])
                settle()
        assert acks >= 8
        for e in engs:
            h = e.health()
            assert h["acks_seen"] == acks
            assert not (h["overflow_docs"] or h["oracle_docs"]
                        or h["quarantined_docs"])
        per_doc, fleet = (e.health() for e in engs)
        assert per_doc["compacted_lanes"] < fleet["compacted_lanes"]
        assert fleet["compacted_lanes"] == (
            fleet["compact_dispatches"] * engs[1].capacity)
    finally:
        for fc in fcs:
            fc.close()
        srv.stop()


# ------------------------------- 2b. inserts at an obliterate's edge
def _edge_round(a, b, doc, at: int, edge: str) -> None:
    """``b`` obliterates [at, at + 3] with both sides inward and ``a``, which
    has not seen it, inserts at one ``edge`` of that window: sequenced after
    the obliterate, concurrent with it."""
    b.obliterate_range_sided((at, True), (at + 3, False))
    where = {"before_start": at, "inside_at_start": at + 1,
             "inside_at_end": at + 3, "after_end": at + 4}[edge]
    a.insert_text(where, "XY")
    _flush(doc, [b, a])


@pytest.mark.parametrize("floor", ["record_live", "record_expired"])
@pytest.mark.parametrize("path", ["cohort", "fleet_wide"])
def test_inserts_at_an_obliterates_edge_across_a_summary_ack(path, floor):
    """The acks cell's sequence at engine level: an insert at an obliterate's
    edge, a summary ack (``compact(docs)``: the record expires or stays, the
    gate may close, evicted segments move every index), then a second
    obliterate and a second insert at an edge of the same document.  No
    document leaves the batch, none latches, every text is the oracle's."""
    edges = ["before_start", "inside_at_start", "inside_at_end", "after_end"]
    # A cohort is at most a quarter of the fleet; the documents beside it
    # never hold a record, so the cohort's gate is its own.
    n_docs, busy = (16, 4) if path == "cohort" else (4, 4)
    svc = LocalService()
    eng = DocBatchEngine(n_docs, **GEOM)
    docs, ws, summ = [], [], []
    for d in range(busy):
        doc = svc.document(f"x{d}")
        docs.append(doc)
        ws.append(_writers(doc, f"x{d}"))
        summ.append({})
        ws[d][0].insert_text(0, "abcdefghijklmnopqrst")
        _flush(doc, ws[d])
    fed = [0] * busy

    def feed():
        for d, doc in enumerate(docs):
            log = doc.sequencer.log
            eng.ingest_lines(d, _wire(log[fed[d]:]))
            fed[d] = len(log)
        eng.step()
        for d, doc in enumerate(docs):
            a, b = ws[d]
            assert a.text == b.text == oracle_text(doc.sequencer.log)
            assert eng.text(d) == a.text, (d, edges[d])
        h = eng.health()
        assert not eng.errors().any()
        assert not (h["overflow_docs"] or h["oracle_docs"]
                    or h["quarantined_docs"])

    for d, doc in enumerate(docs):
        _edge_round(*ws[d], doc, 4, edges[d])
    feed()
    assert (np.asarray(eng.state.ob_key) >= 0).any()
    # Inside the window the insert was swallowed on arrival, outside it not.
    assert ["XY" in ws[d][0].text for d in range(busy)] == [
        True, False, False, True]
    for d, doc in enumerate(docs):
        a, b = ws[d]
        if floor == "record_expired":
            # Both writers move on, so the MSN passes the obliterate.
            for i in range(3):
                (a, b)[i % 2].insert_text(0, "12"[i % 2])
                _flush(doc, [a, b])
        _summarize(doc, summ[d])
    for d, doc in enumerate(docs):
        eng.ingest_lines(d, _wire(doc.sequencer.log[fed[d]:]))
        fed[d] = len(doc.sequencer.log)
    eng.compact(range(busy))     # what FleetConsumer.pump does on the acks
    eng.step()
    assert eng.counters.get("compacted_docs") == busy
    live = (np.asarray(eng.state.ob_key) >= 0).any()
    assert live == (floor == "record_live")
    for d, doc in enumerate(docs):
        # The second window overlaps the first one's far edge.
        at = len(ws[d][0].text) - 14
        _edge_round(*ws[d], doc, at, edges[(d + 1) % 4])
        _edge_round(*ws[d], doc, 2, edges[(d + 2) % 4])
    feed()
    if path == "cohort":
        assert eng._built and not eng._full_built
    else:
        assert eng._full_built and not eng._built


# ----------------------------------------------------------------- 3. order
def _tombstone_history(tail: int):
    """A remove, an insert concurrent with it that resolves its position
    against the tombstone, enough rounds for the MSN to pass the remove,
    then ``tail`` more ops: the log, where the remove ends, the text."""
    doc = LocalService().document("d")
    a, b = _writers(doc, "d")
    a.insert_text(0, "abcdefgh")
    _flush(doc, [a])
    b.remove_range(2, 4)     # sequenced first ...
    a.insert_text(5, "Z")    # ... concurrent: a has not seen the remove
    _flush(doc, [b, a])
    for i in range(max(2, tail)):
        (a, b)[i % 2].insert_text(0, "12"[i % 2])
        _flush(doc, [a, b])
    log = doc.sequencer.log
    assert doc.sequencer.min_seq > 4 and a.text == b.text == oracle_text(log)
    cut = 1 + next(i for i, m in enumerate(log)
                   if m.type == MessageType.OP and m.contents["type"] == 1)
    return log, cut, a.text


@pytest.mark.parametrize("depth,k", [(1, 1), (33, 2)])
def test_rows_read_with_the_ack_are_applied_before_zamboni(depth, k):
    """One pass reads ops and the ack that follows them (a consumer that
    fell behind): the host's floor is then past the ref-seq of rows still
    queued, and they must be applied first."""
    log, cut, text = _tombstone_history(depth)
    eng = DocBatchEngine(4, **{**GEOM, "ops_per_step": 32}, megastep_k=2)
    eng.ingest_lines(0, _wire(log[:cut]))
    eng.step()               # the remove is applied; its tombstone is live
    staged = eng.ingest_lines(0, _wire(log[cut:]))
    assert staged >= depth and eng.hosts[0].min_seq > 4
    eng.compact([0])         # what FleetConsumer.pump does on an ack
    assert eng.compact_due == {0} and not eng.counters.get("compacted_docs")
    slices = eng.step()
    assert slices == k and eng.counters.get("compacted_docs") == 1
    assert not eng.compact_due and not eng.errors().any()
    assert eng.text(0) == text


def test_zamboni_under_queued_rows_is_caught():
    """The regression planted: the same feed, compacted at the host's floor
    while its rows are still queued, puts the concurrent insert in the wrong
    place with no error latched; the guard in the engine is what stops it."""
    log, cut, text = _tombstone_history(1)
    eng = DocBatchEngine(4, **GEOM)
    eng.ingest_lines(0, _wire(log[:cut]))
    eng.step()
    eng.ingest_lines(0, _wire(log[cut:]))
    eng.compact([0])
    eng._compact_due_docs()          # as step() would, were it to run first
    assert eng.compact_due == {0}    # ... and it waits: the queue is not empty
    eng._queue_depth = lambda d: 0   # the guard taken out
    eng._compact_due_docs()
    eng.step()
    assert not eng.errors().any() and eng.text(0) != text


# --------------------------------------------------- 4. cost follows the acks
def _quiet_engine(n: int = 64) -> DocBatchEngine:
    return DocBatchEngine(n, **GEOM)


def test_each_ack_costs_its_own_lanes_and_never_the_fleet():
    eng = _quiet_engine()
    groups = [[30, 31, 32, 33, 34], [1, 2, 4], [5, 6], [3], [9], [20]]
    for docs in groups:              # one pump's acked documents each
        eng.compact(docs)
        eng.step()
    h = eng.health()
    assert h["compact_dispatches"] == len(groups)
    assert h["compacted_docs"] == sum(len(g) for g in groups)
    assert h["compacted_lanes"] == 8 + 4 + 2 + 1 + 1 + 1 < eng.capacity
    assert eng._compact_built == {1, 2, 4, 8}


def test_more_than_a_quarter_of_the_fleet_goes_fleet_wide():
    eng = _quiet_engine()
    eng.compact(range(eng.capacity // 4))
    eng.step()
    assert eng.health()["compacted_lanes"] == eng.capacity // 4
    eng.compact(range(eng.capacity // 4 + 1))
    eng.step()
    h = eng.health()
    assert h["compacted_lanes"] == eng.capacity // 4 + eng.capacity
    assert h["compact_dispatches"] == 2 and not eng.compact_due


def test_no_compaction_shape_is_built_while_a_built_one_can_serve():
    eng = _quiet_engine()
    for docs in ([1, 2], [0]):       # a set-up that built 2 lanes and 1
        eng.compact(docs)
        eng.step()
    built = dbe._compact_cohort._cache_size()
    lanes0, n0 = (eng.counters.get(k)
                  for k in ("compacted_lanes", "compact_dispatches"))
    eng.compact([10, 11, 12, 13, 14])    # 8 lanes were never built
    eng.step()
    assert dbe._compact_cohort._cache_size() == built
    assert eng._compact_built == {1, 2}
    assert eng.counters.get("compact_dispatches") - n0 == 3      # 2 + 2 + 1
    assert eng.counters.get("compacted_lanes") - lanes0 == 5
    assert eng.counters.get("compacted_docs") == 3 + 5
    eng.compact([20, 21, 22])            # 4 lanes were never built: 2 + 1
    eng.step()
    assert dbe._compact_cohort._cache_size() == built
    assert eng.counters.get("compact_dispatches") - n0 == 5


# ----------------------------------------------------- 5. obliterates expire
def _obliterate_stream(acks: bool):
    """24 sided obliterates on one document, every 6 followed by a round
    from both writers (so the MSN passes them) and, with ``acks``, a
    summary; fed to an engine the way the consumer feeds it."""
    doc = LocalService().document("d")
    a, b = _writers(doc, "d")
    for i in range(12):
        a.insert_text(0, "abcdefgh")
    _flush(doc, [a])
    eng = DocBatchEngine(1, **GEOM)
    fed, summ = 0, {}
    most = 0
    for i in range(24):
        a.obliterate_range_sided((1 + i % 5, True), (3 + i % 5, False))
        _flush(doc, [a])
        if i % 6 == 5:
            b.insert_text(0, "x")
            _flush(doc, [b])
            a.insert_text(0, "y")
            _flush(doc, [a])
            if acks:
                _summarize(doc, summ)
        feed = _wire(doc.sequencer.log[fed:])
        fed = len(doc.sequencer.log)
        eng.ingest_lines(0, feed)
        if b'"type":"summaryAck"' in feed:
            eng.compact([0])
        eng.step()
        if 0 not in eng.overflow:
            most = max(most, int((np.asarray(eng.state.ob_key[0]) >= 0).sum()))
    assert a.text == b.text == oracle_text(doc.sequencer.log) == eng.text(0)
    return eng, most


def test_obliterate_records_expire_with_the_acks():
    eng, most = _obliterate_stream(acks=True)
    h = eng.health()
    assert h["overflow_docs"] == 0 and not eng.errors().any()
    # Read after each step: the sixth record of a group is freed in the
    # step that applied it, by the ack that came with it.
    assert h["compacted_docs"] == 4 and most == 5
    assert eng.evictable_left() == 0
    assert eng.device_min_seqs()[0] == eng.hosts[0].min_seq > 0


def test_without_acks_the_ninth_obliterate_leaves_the_device():
    eng, most = _obliterate_stream(acks=False)
    assert eng.health()["overflow_docs"] == 1 and most == 8


# ------------------------------------------------------ 6. lanes and a mesh
def test_an_acked_document_in_an_overflow_lane_compacts_its_lane_alone():
    doc = LocalService().document("d")
    a, b = _writers(doc, "d")
    for _ in range(10):
        a.insert_text(0, "ab")
    _flush(doc, [a])
    eng = DocBatchEngine(2, max_segments=4, max_insert_len=8, ops_per_step=4,
                         use_mesh=False)
    eng.ingest_lines(0, _wire(doc.sequencer.log))
    eng.step()
    assert 0 in eng.overflow
    fed = len(doc.sequencer.log)
    a.remove_range(0, 4)
    _flush(doc, [a])
    for c in (b, a):                 # the MSN passes the remove
        c.insert_text(0, "z")
        _flush(doc, [c])
    eng.ingest_lines(0, _wire(doc.sequencer.log[fed:]))
    eng.step()
    nseg = int(eng.overflow[0].state.nseg)
    batch = jax.tree.map(np.asarray, eng.state)
    eng.compact([0])
    eng.step()
    lane = eng.overflow[0].state
    assert int(lane.nseg) < nseg
    assert int(lane.min_seq) == eng.hosts[0].min_seq > 0
    assert eng.device_min_seqs() == [eng.hosts[0].min_seq, 0]
    assert eng.text(0) == a.text and eng.evictable_left() == 0
    assert eng.counters.get("compact_dispatches") == 0      # no batch program
    for got, was in zip(jax.tree.leaves(eng.state), jax.tree.leaves(batch)):
        assert np.asarray(got).tobytes() == was.tobytes()


def test_an_acked_document_in_a_segment_lane_compacts_its_lane_alone():
    eng = DocBatchEngine(4, max_segments=256, text_capacity=4096,
                         max_insert_len=8, ops_per_step=8, seg_shards=4)
    doc = LocalService().document("d")
    a, b = _writers(doc, "d")
    for _ in range(6):
        a.insert_text(0, "abcd")
    _flush(doc, [a])
    for m in doc.sequencer.log:
        eng.ingest(0, m)
    eng.step()
    assert eng.enable_segment_sharding(0)
    fed = len(doc.sequencer.log)
    a.remove_range(0, 6)
    _flush(doc, [a])
    for c in (b, a):
        c.insert_text(0, "z")
        _flush(doc, [c])
    for m in doc.sequencer.log[fed:]:
        eng.ingest(0, m)
    eng.step()
    version = eng.seg_lanes[0].version
    nseg = int(eng.doc_state(0).nseg)
    eng.compact([0])
    eng.step()
    assert eng.seg_lanes[0].version == version + 1
    assert int(eng.doc_state(0).nseg) < nseg
    assert eng.text(0) == a.text
    assert eng.counters.get("compact_dispatches") == 0


def test_under_a_mesh_acked_documents_take_the_fleet_wide_program():
    """4 virtual devices: ``compact(docs)`` is the mesh's fleet-wide
    program, and the acked rows equal the unmeshed engine's."""
    rng = random.Random(11)
    svc = LocalService()
    mesh = pm.doc_mesh(jax.devices()[:4])
    meshed = DocBatchEngine(8, **{**GEOM, "use_mesh": True}, mesh=mesh)
    plain = DocBatchEngine(8, **GEOM)
    for d in range(8):
        doc = svc.document(f"m{d}")
        ws = _writers(doc, f"m{d}")
        for _round in range(5):
            for c in ws:
                _edit(rng, c, obliterate=True)
                _edit(rng, c, obliterate=True)
            _flush(doc, ws)
        for eng in (meshed, plain):
            eng.ingest_lines(d, _wire(doc.sequencer.log))
    acked = [1, 6]
    for eng in (meshed, plain):
        eng.step()
        eng.compact(acked)
        eng.step()
        assert not eng.compact_due and not eng.errors().any()
    assert meshed.counters.get("compacted_lanes") == meshed.capacity
    assert plain.counters.get("compacted_lanes") == 2
    for d in acked:
        for got, want in zip(jax.tree.leaves(meshed.doc_state(d)),
                             jax.tree.leaves(plain.doc_state(d))):
            assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    for d in range(8):
        assert meshed.text(d) == plain.text(d) == oracle_text(
            svc.document(f"m{d}").sequencer.log)


# --------------------------------------------------------------- 8. tracing
def test_compact_is_a_span_of_the_step_and_counted_in_every_status_line():
    eng = _quiet_engine(8)
    snap = status_snapshot(eng, [str(d) for d in range(8)])
    for key in ("acks_seen", "compact_dispatches", "compacted_docs",
                "compacted_lanes"):
        assert snap["health"][key] == 0, key
    rec = fr.install(fr.FlightRecorder(256))
    try:
        with fr.span("pump"):
            eng.compact([2, 5])
        with fr.span("step"):
            eng.step()
    finally:
        fr.uninstall()
    ev = {e.name: e for e in rec.events()}
    compact, step, pump = ev["compact"], ev["step"], ev["pump"]
    assert compact.args == {"kind": "cohort", "docs": 2, "lanes": 2}
    assert step.ts_ns <= compact.ts_ns
    assert compact.ts_ns + compact.dur_ns <= step.ts_ns + step.dur_ns
    assert not pump.ts_ns <= compact.ts_ns <= pump.ts_ns + pump.dur_ns
    snap = status_snapshot(eng, [str(d) for d in range(8)])
    assert snap["health"]["compacted_lanes"] == 2
    assert snap["health"]["compacted_docs"] == 2
    assert snap["health"]["compact_dispatches"] == 1
