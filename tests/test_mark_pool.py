"""Pooled columnar mark store (PR 14): byte-identity vs the object oracle.

The pooled fold (dds/tree/mark_pool.py + EditManager(mark_pool=...)) must
be BYTE-identical to the object-mark fold it replaces: same summaries,
same recorded fold stages, same trunk commits, same device rows — across
rebase windows, undo-redo, mixed field kinds, moves (the pooled
fallback-to-oracle path), and constraints.  The native tree wire decoder
must be row-identical to the Python decode, with malformed-op isolation.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from fluidframework_tpu.dds.tree.changeset import (
    Commit,
    apply_commit,
    clone_commit,
    commit_from_json,
    commit_to_json,
    invert_commit,
    make_insert,
    make_move,
    make_optional_edit,
    make_optional_set,
    make_remove,
    make_set_value,
    node_exists_constraint,
)
from fluidframework_tpu.dds.tree.editmanager import EditManager
from fluidframework_tpu.dds.tree.forest import Forest, Node
from fluidframework_tpu.dds.tree.mark_pool import (
    MarkPool,
    pool_commit_from_json,
)
from fluidframework_tpu.dds.tree.schema import leaf
from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage


# ---------------------------------------------------------------------------
# Fuzz stream generator: W writers, ref-seq lag, mixed edit kinds
# ---------------------------------------------------------------------------


def _rand_leaf(rng):
    if rng.random() < 0.35:
        n = int(rng.integers(2, 8))
        alpha = "abcdefΔЖ"  # non-ASCII exercises codec + native
        return leaf("".join(alpha[int(c)] for c in rng.integers(0, 8, n)))
    return leaf(int(rng.integers(1000)))


def _fuzz_edits(seed: int, rounds: int = 6, writers: int = 3,
                with_moves: bool = True, with_optional: bool = True,
                with_undo: bool = True, with_constraints: bool = True):
    """Yield (writer, ref_seq, seq, min_seq, Commit) — one doc's sequenced
    stream with genuine concurrency, valid by construction: positional
    edits stay inside each writer's OWN subtree (owner-exclusive sizes are
    exact), the SHARED subtree takes only position-0 inserts and sets
    (always valid under any interleaving), undo-redo inverts the writer's
    own recent pure-insert commits (invertible without apply enrichment),
    and constraints ride commits occasionally (voiding is a legal
    outcome)."""
    rng = np.random.default_rng(seed)
    seq = 0
    out = []
    # Seed tree: writer subtrees + one shared subtree, each with kids.
    for w in range(writers + 1):
        seq += 1
        out.append((0, seq - 1, seq, max(0, seq - 2), Commit([
            make_insert([], "", w, [Node(type="obj", fields={
                "kids": [leaf(0)], })]),
        ])))
    sizes = [1] * (writers + 1)  # exact for owner-exclusive subtrees
    meta_set = [False] * writers
    # Last own-subtree insert, undoable only while it is the writer's most
    # recent structural edit there (its positions stay locally valid).
    undoable: list[Commit | None] = [None] * writers

    for _round in range(rounds):
        ref = seq
        for w in range(writers):
            for _k in range(4):
                seq += 1
                r = rng.random()
                if rng.random() < 0.4:
                    # Shared subtree: genuinely conflicting concurrent
                    # inserts/sets at position 0.
                    if rng.random() < 0.6:
                        c = Commit([make_insert(
                            [("", writers)], "kids", 0, [_rand_leaf(rng)],
                        )])
                    else:
                        c = Commit([make_set_value(
                            [("", writers), ("kids", 0)],
                            _rand_leaf(rng).value,
                        )])
                elif with_undo and r < 0.12 and undoable[w] is not None:
                    # Undo (and sometimes redo): invert the writer's own
                    # latest pure-insert commit — Insert inverts to Remove
                    # with repair data, no apply enrichment needed; a
                    # second invert redoes it.
                    c = invert_commit(clone_commit(undoable[w]))
                    sizes[w] -= 1
                    if rng.random() < 0.5:
                        c = invert_commit(clone_commit(c))
                        sizes[w] += 1
                    undoable[w] = None
                elif with_optional and r < 0.32:
                    if meta_set[w] and rng.random() < 0.4:
                        from fluidframework_tpu.dds.tree.changeset import (
                            NodeChange,
                        )

                        c = Commit([make_optional_edit(
                            [("", w)], "meta",
                            NodeChange(value=(int(rng.integers(50)),)),
                        )])
                    else:
                        content = (
                            _rand_leaf(rng) if rng.random() < 0.8 else None
                        )
                        meta_set[w] = content is not None
                        c = Commit([make_optional_set(
                            [("", w)], "meta", content,
                        )])
                elif with_moves and r < 0.40 and sizes[w] >= 3:
                    a = int(rng.integers(sizes[w] - 1))
                    c = Commit([make_move(
                        [("", w)], "kids", a, 1,
                        int(rng.integers(sizes[w] + 1)),
                    )])
                    undoable[w] = None  # positions shifted: undo stale
                elif r < 0.55 and sizes[w] > 1:
                    c = Commit([make_remove(
                        [("", w)], "kids",
                        int(rng.integers(sizes[w] - 1)), 1,
                    )])
                    sizes[w] -= 1
                    undoable[w] = None
                elif r < 0.72:
                    c = Commit([make_set_value(
                        [("", w), ("kids", int(rng.integers(sizes[w]))),
                         ], _rand_leaf(rng).value,
                    )])
                else:
                    c = Commit([make_insert(
                        [("", w)], "kids",
                        int(rng.integers(sizes[w] + 1)), [_rand_leaf(rng)],
                    )])
                    sizes[w] += 1
                    undoable[w] = clone_commit(c)
                if with_constraints and rng.random() < 0.05:
                    c = Commit(list(c), [node_exists_constraint([("", w)])])
                out.append((w, ref, seq, max(0, ref - 1), c))
    return out


def _run_manager(edits, mark_pool):
    """Fold one stream through an EditManager; returns (summaries json,
    stage json, trunk json list, forest json)."""
    em = EditManager(mark_pool=MarkPool() if mark_pool else None)
    forest = Forest()
    trunk_json = []
    pool = em.pool
    for w, ref, seq, min_seq, commit in edits:
        wire = commit_to_json(clone_commit(commit))
        if mark_pool:
            change = pool_commit_from_json(pool, wire)
        else:
            change = commit_from_json(wire)
        ret = em.add_sequenced(
            client_id=f"w{w}", revision=(w, seq), change=change,
            ref_seq=ref, seq=seq,
        )
        trunk_json.append(json.dumps(commit_to_json(clone_commit(ret))))
        apply_commit(forest.root, ret)  # enrichment, like the engine
        em.advance_min_seq(min_seq)
    stages = {
        cid: [
            [[tseq, commit_to_json(cm)] for tseq, cm in st]
            for st in br.stages
        ]
        for cid, br in em.peers.items()
    }
    return (
        json.dumps(em.summarize(), sort_keys=True),
        json.dumps(stages, sort_keys=True),
        trunk_json,
        json.dumps(forest.to_json(), sort_keys=True),
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pooled_fold_byte_identity(seed):
    """Summaries, recorded fold stages, every trunk commit, and the
    applied forest are byte-identical pooled vs object-oracle — mixed
    field kinds, moves, undo, constraints, ref-seq windows included."""
    edits = _fuzz_edits(seed)
    s1, st1, t1, f1 = _run_manager(edits, mark_pool=True)
    s0, st0, t0, f0 = _run_manager(edits, mark_pool=False)
    assert t1 == t0, "trunk commit divergence"
    assert st1 == st0, "recorded fold-stage divergence"
    assert s1 == s0, "summary divergence"
    assert f1 == f0, "applied forest divergence"


def test_pooled_fold_identity_through_summary_reload():
    """Cut a summary mid-stream, load it into FRESH managers (pooled and
    object), continue the stream: the post-load scratch/bridge paths stay
    byte-identical too."""
    edits = _fuzz_edits(7, rounds=5)
    cut = len(edits) * 2 // 3

    def run(mark_pool):
        em = EditManager(mark_pool=MarkPool() if mark_pool else None)
        pool = em.pool
        for w, ref, seq, min_seq, commit in edits[:cut]:
            wire = commit_to_json(clone_commit(commit))
            change = (
                pool_commit_from_json(pool, wire) if mark_pool
                else commit_from_json(wire)
            )
            em.add_sequenced(f"w{w}", (w, seq), change, ref, seq)
            em.advance_min_seq(min_seq)
        snap = em.summarize()
        em2 = EditManager(mark_pool=MarkPool() if mark_pool else None)
        em2.load(json.loads(json.dumps(snap)))
        pool2 = em2.pool
        rets = []
        for w, ref, seq, min_seq, commit in edits[cut:]:
            wire = commit_to_json(clone_commit(commit))
            change = (
                pool_commit_from_json(pool2, wire) if mark_pool
                else commit_from_json(wire)
            )
            rets.append(json.dumps(commit_to_json(em2.add_sequenced(
                f"w{w}", (w, seq), change, ref, seq
            ))))
            em2.advance_min_seq(min_seq)
        return json.dumps(snap, sort_keys=True), rets, json.dumps(
            em2.summarize(), sort_keys=True
        )

    snap1, rets1, final1 = run(True)
    snap0, rets0, final0 = run(False)
    assert snap1 == snap0
    assert rets1 == rets0
    assert final1 == final0


def test_pool_blocks_recycle_as_windows_evict():
    """MSN eviction frees stream spans; dead blocks return to the free
    list and later windows reuse them (the mark_pool_hit_rate claim)."""
    pool = MarkPool(block_size=16)  # tiny blocks: rotation is observable
    em = EditManager(mark_pool=pool)
    seq = 0
    for w in range(2):
        seq += 1
        em.add_sequenced(f"w{w}", (w, seq), commit_from_json(commit_to_json(
            Commit([make_insert([], "", w, [Node(type="obj", fields={
                "kids": [leaf(0)]})])])
        )), seq - 1, seq)
    import gc

    for r in range(120):
        ref = seq
        for w in range(2):
            seq += 1
            em.add_sequenced(
                f"w{w}", (w, seq),
                pool_commit_from_json(pool, commit_to_json(Commit([
                    make_insert([("", w)], "kids", 0, [leaf(r)]),
                ]))),
                ref, seq,
            )
        em.advance_min_seq(seq - 2)
    gc.collect()
    assert pool.blocks_recycled > 0
    assert pool.reuse_hits > 0
    assert 0.0 <= pool.occupancy() <= 1.0


# ---------------------------------------------------------------------------
# Engine-level identity (device rows + summaries through TreeBatchEngine)
# ---------------------------------------------------------------------------


def _engine_msgs(seed):
    edits = _fuzz_edits(seed, rounds=4, with_optional=False,
                        with_undo=False, with_constraints=False)
    msgs = []
    for w, ref, seq, min_seq, commit in edits:
        msgs.append(SequencedMessage(
            client_id=f"w{w}", client_seq=seq, ref_seq=ref, seq=seq,
            min_seq=min_seq, type=MessageType.OP,
            contents={"type": "edit", "sid": f"s{w}", "rev": seq,
                      "changes": commit_to_json(clone_commit(commit))},
        ))
    return msgs


@pytest.mark.parametrize("seed", [0, 3])
def test_engine_pooled_vs_oracle_device_identity(seed):
    from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine

    msgs = _engine_msgs(seed)

    def run(mark_pool):
        eng = TreeBatchEngine(2, capacity=4096, ops_per_step=16,
                              pool_capacity=32768, mark_pool=mark_pool)
        for m in msgs:
            eng.ingest(0, m)
            eng.ingest(1, m)
        sums = [json.dumps(eng.hosts[d].em.summarize(), sort_keys=True)
                for d in range(2)]
        eng.step()
        trees = [json.dumps(eng.tree_json(d), sort_keys=True)
                 for d in range(2)]
        return eng, sums, trees

    e1, s1, t1 = run(True)
    e0, s0, t0 = run(False)
    assert s1 == s0 and t1 == t0
    assert bool(e1.fallbacks) == bool(e0.fallbacks)
    h = e1.health()
    assert h["mark_pool_hit_rate"] > 0
    assert 0.0 <= h["pool_occupancy"] <= 1.0


# ---------------------------------------------------------------------------
# Native tree wire decode: row identity + malformed isolation
# ---------------------------------------------------------------------------


def _native_available():
    from fluidframework_tpu.native.ingest_native import available

    return available()


@pytest.mark.parametrize("seed", [0, 5])
def test_native_tree_decode_row_identity(seed):
    """Native column assembly produces byte-identical pooled commits (and
    envelopes) to the Python decode, across mixed kinds incl. moves,
    detached repair data, unicode strings, and constraint (dict-form)
    commits routed through the opaque path."""
    if not _native_available():
        pytest.skip("native tree decoder unavailable")
    from fluidframework_tpu.dds.tree.mark_pool import pool_commit_from_native
    from fluidframework_tpu.native.ingest_native import (
        TREE_ST_EDITS,
        TREE_ST_OPAQUE,
        tree_decode,
    )

    edits = _fuzz_edits(seed, rounds=3)
    msgs = []
    for w, ref, seq, min_seq, commit in edits:
        msgs.append(SequencedMessage(
            client_id=f"w{w}", client_seq=seq, ref_seq=ref, seq=seq,
            min_seq=min_seq, type=MessageType.OP,
            contents={"type": "edit", "sid": f"s{w}", "rev": seq,
                      "changes": commit_to_json(clone_commit(commit))},
        ))
    data = b"".join((m.to_json() + "\n").encode() for m in msgs)
    tables = tree_decode(data)
    assert tables is not None
    msgs_t, chgs, flds, marks, spans = (t.tolist() for t in tables)
    assert len(msgs_t) == len(msgs)
    pool = MarkPool()
    n_edits = n_opaque = 0
    for m_row, msg in zip(msgs_t, msgs):
        assert m_row[0] == msg.seq and m_row[1] == msg.ref_seq
        assert m_row[2] == msg.min_seq
        assert data[m_row[4]: m_row[4] + m_row[5]].decode() == msg.client_id
        wire_changes = msg.contents["changes"]
        if m_row[10] == TREE_ST_OPAQUE:
            # Constraint commits (dict wire form) route through the
            # opaque span: Python re-parses the same bytes.
            n_opaque += 1
            contents = json.loads(data[m_row[11]: m_row[11] + m_row[12]])
            assert contents == msg.contents
            continue
        assert m_row[10] == TREE_ST_EDITS
        n_edits += 1
        native = pool_commit_from_native(
            pool, data, m_row, chgs, flds, marks, spans
        )
        oracle = pool_commit_from_json(pool, wire_changes)
        assert commit_to_json(native) == commit_to_json(oracle)
        assert commit_to_json(native) == wire_changes
    assert n_edits > 0 and n_opaque > 0  # both routes exercised


def test_native_decode_malformed_line_isolation():
    """A malformed op mid-feed: earlier lines land, the error surfaces
    through the Python path's semantics, and OTHER docs are untouched."""
    from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine

    good = SequencedMessage(
        client_id="w0", client_seq=1, ref_seq=0, seq=1, min_seq=0,
        type=MessageType.OP,
        contents={"type": "edit", "sid": "s0", "rev": 1,
                  "changes": commit_to_json(Commit([
                      make_insert([], "", 0, [leaf(1)]),
                  ]))},
    )
    bad = b'{"sequenceNumber": 2, "type": "op", "clientId": "w0", '\
          b'"contents": {"type": "edit", "sid": "s0", "rev": 2, '\
          b'"changes": [{"f": {"": [["??", 1]]}}]}}\n'
    eng = TreeBatchEngine(2, capacity=1024, ops_per_step=8,
                          pool_capacity=8192)
    # Other doc, clean feed: lands fine.
    n = eng.ingest_lines(1, (good.to_json() + "\n").encode())
    assert n > 0
    feed = (good.to_json() + "\n").encode() + bad
    with pytest.raises((ValueError, KeyError, TypeError)):
        eng.ingest_lines(0, feed)
    # The good prefix landed before the malformed line raised.
    assert eng.hosts[0].total_commits == 1
    assert eng.hosts[1].total_commits == 1
    eng.step()
    assert eng.values(1) == [1]


def test_engine_lines_native_vs_python_identical():
    from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine

    msgs = _engine_msgs(1)
    wire = b"".join((m.to_json() + "\n").encode() for m in msgs)

    def run(native):
        eng = TreeBatchEngine(1, capacity=4096, ops_per_step=16,
                              pool_capacity=32768, native_wire=native)
        eng.ingest_lines(0, wire)
        summary = json.dumps(eng.hosts[0].em.summarize(), sort_keys=True)
        q = eng.hosts[0].queue
        rows = json.dumps(q.ops[q.head: q.tail].tolist())
        return eng, summary, rows

    e_nat, s_nat, r_nat = run(True)
    e_py, s_py, r_py = run(False)
    assert s_nat == s_py and r_nat == r_py
    if _native_available():
        assert e_nat.health().get("tree_native_batches", 0) == 1


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(3, 9))
def test_pooled_fold_byte_identity_sweep(seed):
    """Deeper multi-seed sweep (slow lane): larger windows, more writers."""
    edits = _fuzz_edits(seed, rounds=9, writers=4)
    s1, st1, t1, f1 = _run_manager(edits, mark_pool=True)
    s0, st0, t0, f0 = _run_manager(edits, mark_pool=False)
    assert (t1, st1, s1, f1) == (t0, st0, s0, f0)


def test_host_fold_subphase_spans_recorded():
    """The flight recorder sees the host fold's sub-phases as their own
    phase_shares rows (mark_alloc / rebase / translate; compose appears
    once the trunk-log fold threshold is crossed) — the reproducible form
    of the 'Mark.__init__ was ~30% of host time' claim."""
    from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine
    from fluidframework_tpu.observability import flight_recorder as fr

    rec = fr.install(fr.FlightRecorder(capacity=1 << 14))
    try:
        eng = TreeBatchEngine(1, capacity=2048, ops_per_step=16,
                              pool_capacity=16384)
        for m in _engine_msgs(2):
            eng.ingest(0, m)
        shares = fr.phase_shares(rec.events())
    finally:
        fr.uninstall()
    for phase in ("host_fold_mark_alloc", "host_fold_rebase",
                  "host_fold_translate"):
        assert phase in shares, shares


def test_mixed_sequence_family_rebase_and_compose_interop():
    """A pooled span meeting an OBJECT mark list for the same field (mixed
    producers) rebases/composes through the shared mark-list view instead
    of crashing or silently dropping the edit — and matches the pure
    object-mode outcome byte for byte."""
    from fluidframework_tpu.dds.tree.changeset import (
        Insert,
        NodeChange,
        Skip,
        compose_node_change,
        rebase_node_change,
    )
    from fluidframework_tpu.dds.tree.field_kinds import field_change_to_json
    from fluidframework_tpu.dds.tree.mark_pool import pool_marks

    pool = MarkPool()
    a_marks = [Skip(1), Insert([leaf(7)])]
    b_marks = [Insert([leaf(9)])]
    for pooled_side in ("a", "b"):
        a_fc = pool_marks(pool, a_marks) if pooled_side == "a" else list(a_marks)
        b_fc = list(b_marks) if pooled_side == "a" else pool_marks(pool, b_marks)
        mixed = rebase_node_change(
            NodeChange(fields={"f": a_fc}), NodeChange(fields={"f": b_fc}),
            True,
        )
        oracle = rebase_node_change(
            NodeChange(fields={"f": list(a_marks)}),
            NodeChange(fields={"f": list(b_marks)}), True,
        )
        assert field_change_to_json(mixed.fields["f"]) \
            == field_change_to_json(oracle.fields["f"])
    # compose: pooled x object list must route through compose_marks
    composed = compose_node_change(
        NodeChange(fields={"f": pool_marks(pool, [Skip(2)])}),
        NodeChange(fields={"f": [Skip(1), Insert([leaf(3)])]}),
    )
    oracle_c = compose_node_change(
        NodeChange(fields={"f": [Skip(2)]}),
        NodeChange(fields={"f": [Skip(1), Insert([leaf(3)])]}),
    )
    assert field_change_to_json(composed.fields["f"]) \
        == field_change_to_json(oracle_c.fields["f"])


def test_adopt_boot_snapshot_rejects_unusable_record():
    """An engine-mismatched snapshot record fails LOUDLY instead of
    returning a stale floor (which would loop the consumer forever)."""
    from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine

    eng = DocBatchEngine(1, max_segments=64, text_capacity=512,
                         max_insert_len=8, ops_per_step=8, use_mesh=False,
                         recovery="off", doc_keys=["d0"])
    with pytest.raises(ValueError, match="not adoptable"):
        eng.adopt_boot_snapshot(0, {"engine": "tree_batch", "seq": 5})
    assert eng.counters.get("boot_snapshots_adopted") == 0
