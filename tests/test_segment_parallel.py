"""Segment-parallel hot-doc serving (the 2-D docs x segs mesh path).

The contract under test: a seg-sharded replay (ops.mergetree_kernel.
apply_megastep_seg under shard_map over the segs axis) produces a final
DocState BYTE-IDENTICAL to the single-lane kernel on the same trace — the
single-lane path is the oracle (``canonical_doc`` compares every live
array, text pool, stamps, uids, and the obliterate window table).  Engine
tests cover the serving integration: mid-stream promotion, rebalance,
demotion, health gauges, and the fleet-status 2-D placement surface.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.ops import mergetree_kernel as mk
from fluidframework_tpu.parallel import mesh as pm
from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage

SEG_SHARDS = 4
# Growth from empty lands every append on the LAST shard until a rebalance
# re-blocks, so per-shard capacity (S_TOTAL / SEG_SHARDS) must hold the
# whole smoke trace's segments.
S_TOTAL = 512
TEXT_CAP = 8192
# min_seq never advances in these traces, so obliterate windows accumulate
# for the whole run: the table must hold every one the fuzz issues.
OB_SLOTS = 16
PAD_OPS = 112  # fixed trace length (NOOP-padded) -> one compile for all seeds


@pytest.fixture(scope="module")
def mesh():
    return pm.docs_segs_mesh(jax.devices(), seg_shards=SEG_SHARDS)


def four_writer_trace(seed: int, n_rounds: int = 8, max_insert_len: int = 8):
    """Multi-writer rounds with REAL ref_seq lag: inserts (some multi-chunk:
    text longer than max_insert_len), removes, annotates, and sided
    obliterates of each writer's own content — the op soup the tentpole's
    byte-identity acceptance names.  Positions are valid in each op's OWN
    perspective (writers only remove/obliterate what they inserted)."""
    rng = np.random.default_rng(seed)
    rows = []
    length = 0
    seq = 0
    writers = 4
    for _r in range(n_rounds):
        ref = seq
        base = length
        own = [0] * writers
        last_ins = [(0, 0)] * writers
        for w in range(writers):
            for _ in range(2):
                own_len = base + own[w]
                kind = rng.integers(0, 5)
                seq += 1
                if kind in (0, 1) or own_len < 4:
                    tlen = int(rng.integers(1, 20))
                    pos = int(rng.integers(0, own_len + 1))
                    text = "".join(
                        chr(97 + rng.integers(0, 26)) for _ in range(tlen)
                    )
                    rows.extend(
                        mk.encode_insert(pos, text, seq, w, ref, max_insert_len)
                    )
                    last_ins[w] = (pos, tlen)
                    own[w] += tlen
                elif kind == 2:
                    p, ln = last_ins[w]
                    p2 = min(p + max(1, ln // 2), own_len)
                    rows.append((
                        np.array(
                            [mk.OpKind.REMOVE, seq, w, ref, p, p2, 0, 0],
                            np.int32,
                        ),
                        np.zeros(max_insert_len, np.int32),
                    ))
                    own[w] -= p2 - p
                    last_ins[w] = (p, 0)
                elif kind == 3:
                    p = int(rng.integers(0, own_len - 1))
                    p2 = int(rng.integers(p + 1, own_len + 1))
                    rows.append((
                        np.array(
                            [mk.OpKind.ANNOTATE, seq, w, ref, p, p2,
                             int(rng.integers(0, 2)), int(rng.integers(1, 100))],
                            np.int32,
                        ),
                        np.zeros(max_insert_len, np.int32),
                    ))
                else:
                    p, ln = last_ins[w]
                    if ln >= 2:
                        rows.append((
                            mk.encode_obliterate(
                                p, mk.SIDE_BEFORE, p + ln - 1, mk.SIDE_AFTER,
                                seq, w, ref,
                            ),
                            np.zeros(max_insert_len, np.int32),
                        ))
                        own[w] -= ln
                        last_ins[w] = (p, 0)
                    else:
                        rows.append((
                            np.array(
                                [mk.OpKind.NOOP, seq, w, ref, 0, 0, 0, 0],
                                np.int32,
                            ),
                            np.zeros(max_insert_len, np.int32),
                        ))
        length = base + sum(own)
    ops = np.stack([o for o, _ in rows])
    payloads = np.stack([p for _, p in rows])
    assert len(ops) <= PAD_OPS, "bump PAD_OPS"
    pad = PAD_OPS - len(ops)  # NOOP padding: one compile for every seed
    ops = np.concatenate([ops, np.zeros((pad, mk.OP_FIELDS), np.int32)])
    payloads = np.concatenate(
        [payloads, np.zeros((pad, payloads.shape[1]), np.int32)]
    )
    return ops, payloads


def run_single_lane(ops, payloads):
    state = mk.init_state(
        max_segments=S_TOTAL, remove_slots=4, prop_slots=4,
        text_capacity=TEXT_CAP, ob_slots=OB_SLOTS,
    )
    return jax.jit(mk.apply_ops)(state, jnp.asarray(ops), jnp.asarray(payloads))


def run_seg(mesh, ops, payloads, rebalance_at: int | None = None):
    """The same trace through the segment-parallel megastep, optionally
    re-blocking mid-stream (rebalance must be unobservable)."""
    n = mesh.shape["segs"]
    state = mk.init_state(
        max_segments=S_TOTAL, remove_slots=4, prop_slots=4,
        text_capacity=TEXT_CAP, ob_slots=OB_SLOTS,
    )
    blocked = mk.seg_shard_state(state, n, s_local=S_TOTAL // n)
    specs = pm.seg_state_specs(blocked)
    prog = pm.mesh_seg_program(mk.apply_megastep_seg, mesh, specs)
    dev = pm.shard_seg_state(blocked, mesh)
    spans = (
        [(0, len(ops))]
        if rebalance_at is None
        else [(0, rebalance_at), (rebalance_at, len(ops))]
    )
    for i, (a, b) in enumerate(spans):
        if i:
            dev = pm.shard_seg_state(
                mk.seg_rebalance_state(jax.tree.map(np.asarray, dev)), mesh
            )
        dev = prog(dev, jnp.asarray(ops[a:b][None]), jnp.asarray(payloads[a:b][None]))
    return dev


def assert_byte_identical(single_out, seg_out):
    gathered = mk.seg_gather_state(seg_out, max_segments=S_TOTAL)
    a = mk.canonical_doc(single_out)
    b = mk.canonical_doc(gathered)
    bad = [k for k in a if not np.array_equal(a[k], b[k])]
    assert not bad, f"seg path diverged from single-lane oracle in {bad}"


@pytest.mark.parametrize("seed", [0])
def test_seg_replay_byte_identity_smoke(mesh, seed):
    """Tier-1 smoke: a short 4-writer trace (multi-chunk inserts,
    obliterates, annotates, removes) replayed segment-parallel is
    byte-identical to the single-lane oracle — text pool, stamps, uids,
    remove slots, props, and the obliterate window table included."""
    ops, payloads = four_writer_trace(seed)
    single_out = run_single_lane(ops, payloads)
    assert int(single_out.error) == 0, "trace must not overflow the oracle"
    seg_out = run_seg(mesh, ops, payloads)
    assert int(np.asarray(seg_out.error)) == 0
    assert_byte_identical(single_out, seg_out)


def test_seg_rebalance_midstream_unobservable(mesh):
    """Re-blocking the shard layout between two halves of the trace must
    not change a single byte of the final state."""
    ops, payloads = four_writer_trace(2)
    single_out = run_single_lane(ops, payloads)
    seg_out = run_seg(mesh, ops, payloads, rebalance_at=PAD_OPS // 2)
    assert_byte_identical(single_out, seg_out)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 3, 4, 5, 6, 7, 8])
def test_seg_fuzz_sweep(mesh, seed):
    """6-seed fuzz: byte identity with AND without a mid-stream rebalance
    (rebalance point varies by seed)."""
    ops, payloads = four_writer_trace(seed, n_rounds=8)
    single_out = run_single_lane(ops, payloads)
    assert int(single_out.error) == 0
    assert_byte_identical(single_out, run_seg(mesh, ops, payloads))
    # Rebalance point varies by seed but quantizes to a quarter boundary
    # (each distinct span length is one more compiled program shape).
    cut = (PAD_OPS // 4) * (1 + seed % 3)
    assert_byte_identical(
        single_out, run_seg(mesh, ops, payloads, rebalance_at=cut)
    )


# ---------------------------------------------------------------- engine

def _join(eng, d, writers=1):
    for w in range(writers):
        eng.ingest(d, SequencedMessage(
            seq=0, min_seq=0, ref_seq=0, client_id=f"w{w}", client_seq=0,
            type=MessageType.JOIN, contents={"clientId": f"w{w}", "short": w},
        ))


def drive_engine_rounds(eng, oracles, lengths, seqs, rng, rounds):
    from fluidframework_tpu.dds.mergetree_ref import RefMergeTree  # noqa: F401

    n = len(oracles)
    for r in range(rounds):
        idxs, msgs = [], []
        for d in range(n):
            pos = int(rng.integers(0, lengths[d] + 1))
            seqs[d] += 1
            msgs.append(SequencedMessage(
                seq=seqs[d], min_seq=0, ref_seq=seqs[d] - 1, client_id="w0",
                client_seq=r, type=MessageType.OP,
                contents={"type": 0, "pos1": pos, "seg": "abcd"},
            ))
            idxs.append(d)
            oracles[d].apply_insert(pos, "abcd", seqs[d], 0, seqs[d] - 1)
            lengths[d] += 4
        eng.ingest_batch(idxs, msgs)
        eng.step()


def test_engine_segment_lane_lifecycle():
    """Promote mid-stream -> serve segment-parallel -> compact -> rebalance
    -> demote back into the batch row, converging with per-doc oracles at
    every stage; the health surface carries the 2-D gauges."""
    from fluidframework_tpu.dds.mergetree_ref import RefMergeTree

    rng = np.random.default_rng(7)
    eng = DocBatchEngine(
        4, max_segments=256, text_capacity=8192, max_insert_len=8,
        ops_per_step=8, seg_shards=SEG_SHARDS, megastep_k=4,
    )
    assert eng.seg_shards == SEG_SHARDS
    oracles = {d: RefMergeTree() for d in range(4)}
    lengths = [0] * 4
    seqs = [0] * 4
    for d in range(4):
        _join(eng, d)
    drive_engine_rounds(eng, oracles, lengths, seqs, rng, 4)
    assert eng.enable_segment_sharding(0)
    assert eng.segment_sharded() == {"0": SEG_SHARDS}
    assert not eng.enable_segment_sharding(0)  # already sharded
    drive_engine_rounds(eng, oracles, lengths, seqs, rng, 8)
    eng.compact()
    for d in range(4):
        assert eng.text(d) == oracles[d].visible_text(), f"doc {d} diverged"
    health = eng.health()
    assert health["segment_shards"] == SEG_SHARDS
    assert health["segment_sharded_docs"] == 1
    assert health["seg_promotions"] == 1
    assert len(health["seg_occupancy"]) == SEG_SHARDS
    assert sum(health["seg_occupancy"]) > 0
    # Re-block and keep serving: unobservable.
    assert eng.rebalance_segments(0)
    assert eng.health()["seg_rebalances"] == 1
    drive_engine_rounds(eng, oracles, lengths, seqs, rng, 2)
    for d in range(4):
        assert eng.text(d) == oracles[d].visible_text()
    # The watchdog cross-checks seg-lane docs against the oracle replay.
    assert eng.watchdog(sample=4) == []
    # Demote back into the reserved batch slot and keep serving.
    assert eng.disable_segment_sharding(0)
    assert eng.segment_sharded() == {}
    drive_engine_rounds(eng, oracles, lengths, seqs, rng, 2)
    for d in range(4):
        assert eng.text(d) == oracles[d].visible_text()
    assert not eng.errors().any()


def test_engine_hot_doc_auto_promotes():
    """rebalance_hot_shards promotes a doc whose own queue IS the hotspot
    (the case placement migration skips) when a segs axis is available."""
    eng = DocBatchEngine(
        4, max_segments=256, text_capacity=8192, max_insert_len=8,
        ops_per_step=8, seg_shards=SEG_SHARDS,
    )
    for d in range(4):
        _join(eng, d)
    # One viral doc: deep queue on doc 0, trickle elsewhere.
    idxs, msgs = [], []
    seq = 0
    for i in range(64):
        seq += 1
        idxs.append(0)
        msgs.append(SequencedMessage(
            seq=seq, min_seq=0, ref_seq=seq - 1, client_id="w0", client_seq=i,
            type=MessageType.OP, contents={"type": 0, "pos1": 0, "seg": "ab"},
        ))
    eng.ingest_batch(idxs, msgs)
    moves = eng.rebalance_hot_shards(factor=2.0)
    assert 0 in eng.seg_lanes, "hot doc should have promoted to the seg path"
    assert any(d == 0 and dst == -1 for d, _s, dst in moves)
    eng.step()
    assert eng.text(0) == "ab" * 64
    assert not eng.errors().any()


def test_seg_lane_doc_refuses_migration_loudly():
    """A segment-sharded doc's serving state lives outside its fleet slot:
    migrate_doc must refuse LOUDLY (PlacementError from the shared plane)
    before any handoff — never silently strand the lane.  Demoting back
    onto the batch path clears the refusal."""
    from fluidframework_tpu.models.placement import PlacementError

    eng = DocBatchEngine(
        4, max_segments=256, text_capacity=8192, max_insert_len=8,
        ops_per_step=8, seg_shards=SEG_SHARDS,
    )
    _join(eng, 0)
    assert eng.enable_segment_sharding(0)
    with pytest.raises(PlacementError, match="segment"):
        eng.migrate_doc(0, 0)
    assert eng.disable_segment_sharding(0)
    # Back on the batch path: no more refusal (same-shard move is just a
    # quiet no-op, not an error).
    assert eng.migrate_doc(0, 0) is False


def test_engine_fleet_status_surfaces_2d_placement():
    from fluidframework_tpu.server.fleet_main import status_snapshot

    eng = DocBatchEngine(
        2, max_segments=128, text_capacity=4096, max_insert_len=8,
        ops_per_step=8, seg_shards=SEG_SHARDS,
    )
    _join(eng, 0)
    assert eng.enable_segment_sharding(0)
    snap = status_snapshot(eng, ["doc0", "doc1"])
    assert snap["segmentSharded"] == {"0": SEG_SHARDS}
    assert snap["health"]["segment_sharded_docs"] == 1


def test_tree_engine_rebalance_makes_real_move():
    """TreeBatchEngine.rebalance_hot_shards: detects a hot shard and
    live-migrates one of its docs to a cold shard with free slots — the
    same shared-plane skeleton the string engine rides (was: a counted
    no-op parity gap with the string fleet)."""
    from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine

    eng = TreeBatchEngine(32, mesh=pm.doc_mesh(), spare_slots=8)
    if eng.n_shards <= 1:
        return
    # Pile queued rows onto every doc of shard 0 via the raw queues
    # (detection reads queue depth only); depths stay at the fleet mean
    # so the docs remain placement candidates, not hot-doc promotions.
    shard0 = [d for d in range(eng.n_docs) if eng.shard_of(d) == 0]
    for d in shard0:
        q = eng.hosts[d].queue
        q.extend_block(
            np.zeros((12, q.ops.shape[1]), np.int32),
            np.zeros((12, q.payloads.shape[1]), np.int32),
        )
    moves = eng.rebalance_hot_shards()
    assert len(moves) == 1
    d, src, dst = moves[0]
    assert src == 0 and dst != 0 and d in shard0
    assert eng.shard_of(d) == dst
    assert eng.counters.get("doc_migrations") == 1
    assert eng.counters.get("hot_shard_rebalances") == 1
    # The old counted-degradation counters are gone for good.
    assert not [k for k in eng.health() if k.endswith("_unsupported")]


def test_mesh_seg_program_defaults_donation_off():
    """Regression pin for the persistent-cache aliasing bug seen on jax
    0.4.37: a DONATED ``mesh_seg_program`` executable reloaded from the
    persistent XLA compile cache returned permuted/garbage outputs
    whenever the obliterate branch ran (two-process repro — the
    byte-identity fuzz caught it live; see the note in
    ``parallel/mesh.py``, which also records the jax 0.9.0 retry).

    Donation must stay OFF by default until the upstream bug is fixed.
    A well-meaning "re-enable donation" PR now trips THIS named test and
    the ``mesh-safety`` pass's ``mesh-donate-replicated-out`` rule
    (layers.json declares mesh_seg_program replicated-out), instead of a
    flaky byte-identity fuzz three suites away."""
    import inspect

    sig = inspect.signature(pm.mesh_seg_program.__wrapped__)
    assert sig.parameters["donate"].default is False, (
        "mesh_seg_program must default donate=False: donated "
        "replicated-output executables corrupted on persistent-cache "
        "reload (seen on jax 0.4.37). Turning it on is its own change "
        "— see parallel/mesh.py."
    )
