"""The mesh-served fleet against its single-device twin.

``parallel.mesh`` wraps the per-document fleet programs in ``shard_map``
over a ``docs`` mesh; the engines import it directly.  There is ONE
implementation of the merge-tree step (``ops/mergetree_kernel``), held by
its references (``tests/test_apply_op_body.py``'s sequential composition,
``dds/mergetree_ref.py``, the lax oracle below).  What this file pins is
that sharding the document axis changes nothing:

- **program conformance**: the ``shard_map`` fleet programs (megastep and
  compaction) against the same programs jitted on one device, over seeded
  multi-writer traces spanning the full op palette (inserts incl.
  multi-chunk/tie-break and splits, removes, annotates, sided obliterates
  with insert-time swallow, acks of pending stamps, zamboni compaction),
  compared on the FULL raw state columns, padding remnants included, plus
  the per-doc error latch (capacity/poison bits must latch identically);
- **engine equivalence**: a mesh-served engine and a single-device engine
  fed the same stream through the real ingest -> staging -> megastep ->
  recover path agree (string: texts, annotations, fleet digest; tree:
  forest JSON);
- **checkpoints cross the line**: a checkpoint written by a mesh-served
  engine restores on a single-device engine and the reverse, for both
  engines.

Tier-1 runs a short sweep; ``-m slow`` runs the 6-seed deep sweep.
"""

from __future__ import annotations

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.models.doc_batch_engine import (
    DocBatchEngine,
    _fleet_compact_body,
    _fleet_digest,
)
from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine
from fluidframework_tpu.ops import mergetree_kernel as mk
from fluidframework_tpu.parallel import mesh as pm
from fluidframework_tpu.protocol.stamps import LOCAL_BASE
from fluidframework_tpu.server.ordered_log import CheckpointStore

from test_engine_checkpoint import _join
from test_megastep import _schedule
from test_tree_batch_engine import drive_tree_docs


# ----------------------------------------------------------- trace maker

def make_trace(seed, D, K, B, L, n_rings, chunky=True):
    """Seeded multi-writer [K, D, B] op rings across the full palette:
    inserts (some deliberately out of range), multi-chunk same-stamp
    inserts (tie-break path), removes, annotates (incl. out-of-range
    prop slots), sided obliterates, pending local inserts + later acks.
    Positions are approximate on purpose — poison ops latch error bits,
    and the latch itself is part of the conformance surface."""
    rng = np.random.default_rng(seed)
    lengths = [0] * D
    seqs = [0] * D
    local = [0] * D
    rings = []
    for _ in range(n_rings):
        ops = np.zeros((K, D, B, 8), np.int32)
        pays = np.zeros((K, D, B, L), np.int32)
        for k in range(K):
            for d in range(D):
                b = 0
                while b < B:
                    roll = rng.random()
                    seqs[d] += 1
                    key = seqs[d]
                    client = int(rng.integers(0, 4))
                    ref = max(0, seqs[d] - int(rng.integers(1, 6)))
                    ln = lengths[d]
                    if chunky and roll < 0.15 and b + 3 <= B:
                        pos = int(rng.integers(0, ln + 1))
                        for _c in range(3):
                            tl = int(rng.integers(1, L + 1))
                            ops[k, d, b] = [1, key, client, ref, pos, 0, tl, 0]
                            pays[k, d, b, :tl] = rng.integers(65, 91, tl)
                            lengths[d] += tl
                            b += 1
                        continue
                    if roll < 0.4 or ln < 4:
                        tl = int(rng.integers(1, L + 1))
                        pos = int(rng.integers(0, ln + 2))
                        ops[k, d, b] = [1, key, client, ref, pos, 0, tl, 0]
                        pays[k, d, b, :tl] = rng.integers(65, 91, tl)
                        lengths[d] += tl
                    elif roll < 0.55:
                        p1 = int(rng.integers(0, ln))
                        p2 = int(rng.integers(p1, ln + 1))
                        ops[k, d, b] = [2, key, client, ref, p1, p2, 0, 0]
                    elif roll < 0.68:
                        p1 = int(rng.integers(0, ln))
                        p2 = int(rng.integers(p1, ln + 1))
                        ops[k, d, b] = [
                            3, key, client, ref, p1, p2,
                            int(rng.integers(0, 5)), int(rng.integers(1, 100)),
                        ]
                    elif roll < 0.82:
                        p1 = int(rng.integers(0, max(1, ln)))
                        p2 = int(rng.integers(p1, max(p1 + 1, ln)))
                        ops[k, d, b] = [
                            5, key, client, ref, p1, p2,
                            int(rng.integers(0, 2)), int(rng.integers(0, 2)),
                        ]
                    elif roll < 0.92:
                        local[d] += 1
                        ops[k, d, b] = [
                            1, LOCAL_BASE + local[d], -2, ref,
                            int(rng.integers(0, ln + 1)), 0, 2, 0,
                        ]
                        pays[k, d, b, :2] = [97, 98]
                        lengths[d] += 2
                    else:
                        ls = (
                            int(rng.integers(1, local[d] + 1))
                            if local[d] else 0
                        )
                        ops[k, d, b] = [
                            4, key, int(rng.integers(0, 4)),
                            int(rng.integers(0, seqs[d] + 1)), 0, 0, ls, key,
                        ]
                    b += 1
        rings.append((ops, pays))
    return rings, seqs


def _assert_leaves_equal(a, b, tag):
    """Full-array byte identity — stricter than canonical_doc (shift
    remnants in padding slots must match too)."""
    for name in mk.DocState._fields:
        xs, ys = getattr(a, name), getattr(b, name)
        xs = xs if isinstance(xs, tuple) else (xs,)
        ys = ys if isinstance(ys, tuple) else (ys,)
        for j, (x, y) in enumerate(zip(xs, ys)):
            assert np.array_equal(np.asarray(x), np.asarray(y)), (
                f"{tag}: field {name}[{j}] diverged"
            )


def _run_conformance(seed, D=8, K=3, B=8, L=6, S=32, T=256, n_rings=4):
    """Replay one trace through the shard_map fleet programs and through
    the single-device lax oracle; byte-compare after every ring AND
    after every compact."""
    proto = mk.init_state(S, 3, 2, T, 4)
    fleet = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (D,) + x.shape), proto
    )
    mesh = pm.doc_mesh()
    da = pm.fleet_doc_axes(mesh)
    s_mesh = pm.shard_fleet_state(fleet, mesh)
    specs = pm.fleet_state_specs(s_mesh, da)
    mega = pm.mesh_fleet_program(
        mk.apply_megastep, mesh, specs,
        arg_specs=(pm.P(None, da), pm.P(None, da)),
    )
    compact = pm.mesh_fleet_program(
        _fleet_compact_body, mesh, specs, arg_specs=(pm.P(da),),
    )
    oracle_mega = jax.jit(mk.apply_megastep)
    oracle_compact = jax.jit(_fleet_compact_body)

    rings, seqs = make_trace(seed, D, K, B, L, n_rings)
    s_oracle = fleet
    for i, (ops, pays) in enumerate(rings):
        s_mesh = mega(s_mesh, jnp.asarray(ops), jnp.asarray(pays))
        s_oracle = oracle_mega(s_oracle, jnp.asarray(ops), jnp.asarray(pays))
        _assert_leaves_equal(s_oracle, s_mesh, f"seed {seed} ring {i}")
        mins = np.array(
            [max(0, s - 7 - i) for s in seqs], np.int32
        )
        s_mesh = compact(s_mesh, jnp.asarray(mins))
        s_oracle = oracle_compact(s_oracle, jnp.asarray(mins))
        _assert_leaves_equal(
            s_oracle, s_mesh, f"seed {seed} ring {i} post-compact"
        )
    # The error latch is part of the identity surface — and the trace
    # must actually have latched something, or the latch leg proved
    # nothing.
    assert int(pm.error_count(s_mesh.error)) == int(
        np.count_nonzero(np.asarray(s_oracle.error))
    )
    return np.asarray(s_oracle.error)


# --------------------------------------------------- program conformance

@pytest.mark.parametrize("seed", [0, 1])
def test_megastep_conformance_short(seed):
    errs = _run_conformance(seed)
    assert errs.any(), "trace never latched an error bit (weak trace)"


@pytest.mark.slow
@pytest.mark.parametrize("seed", [2, 3, 4, 5, 6, 7])
def test_megastep_conformance_deep(seed):
    _run_conformance(seed, D=8, K=4, B=12, L=8, S=64, T=1024, n_rings=8)


# ---------------------------------------------------- engine equivalence

D = 8


def _string_engine(use_mesh, **kw):
    return DocBatchEngine(
        D, remove_slots=4, max_insert_len=8, ops_per_step=4,
        use_mesh=use_mesh, megastep_k=4, max_segments=128,
        text_capacity=1024, **kw,
    )


def _feed_string(eng, sched, step_every):
    for d in range(D):
        eng.ingest(d, _join("w0", 0))
    for i, (d, msg) in enumerate(sched):
        eng.ingest(d, msg)
        if (i + 1) % step_every == 0:
            eng.step()
    eng.step()
    assert not eng.errors().any()
    return eng


def _string_view(eng):
    digest = np.asarray(_fleet_digest(eng.state))[eng._slot[:D]]
    return (
        [eng.text(d) for d in range(D)],
        [eng.annotations(d) for d in range(D)],
        digest.tobytes(),
    )


def _tree_engine(meshed, **kw):
    return TreeBatchEngine(D, mesh=pm.doc_mesh() if meshed else None, **kw)


def _feed_tree(eng, logs, upto=None):
    for d in range(D):
        for msg in logs[d][:upto]:
            eng.ingest(d, msg)
    eng.step()
    return eng


def _tree_logs(seed, steps):
    svc, expected = drive_tree_docs(D, seed=seed, steps=steps)
    logs = [list(svc.document(f"doc{d}").sequencer.log) for d in range(D)]
    return logs, [expected[d] for d in range(D)]


def test_string_engine_mesh_served_matches_single_device():
    """The whole serving path — ingest, staging ring, megastep dispatch,
    error readback — under the mesh, vs the same schedule on one device."""
    sched = _schedule(D, 16, seed=11, obliterate=True)
    meshed = _feed_string(_string_engine(True), sched, 17)
    assert len(meshed.state.seg_len.sharding.device_set) == jax.device_count()
    single = _feed_string(_string_engine(False), sched, 17)
    assert single.mesh is None
    assert _string_view(meshed) == _string_view(single)


def test_tree_engine_mesh_served_matches_single_device():
    logs, expected = _tree_logs(seed=5, steps=20)
    meshed = _feed_tree(_tree_engine(True), logs)
    assert len(meshed.state.error.sharding.device_set) == jax.device_count()
    single = _feed_tree(_tree_engine(False), logs)
    got = [meshed.tree_json(d) for d in range(D)]
    assert got == [single.tree_json(d) for d in range(D)]
    assert [meshed.values(d) for d in range(D)] == expected


# ------------------------------------------- checkpoints cross the line

WRITER_IS_MESHED = [
    pytest.param(True, id="mesh-to-single"),
    pytest.param(False, id="single-to-mesh"),
]


@pytest.mark.parametrize("writer_meshed", WRITER_IS_MESHED)
def test_string_checkpoint_crosses_mesh_line(writer_meshed):
    """A checkpoint holds a document, not a placement: written under one
    serving path it restores byte for byte under the other, and replaying
    the full stream there stays idempotent."""
    sched = _schedule(D, 10, seed=12)
    tmp = tempfile.mkdtemp()
    writer = _feed_string(
        _string_engine(
            writer_meshed, checkpoint_store=CheckpointStore(tmp),
            checkpoint_every=3,
        ),
        sched, 5,
    )
    writer.maybe_checkpoint(force=True)
    expected = _string_view(writer)[:2]
    del writer

    reader = _string_engine(
        not writer_meshed, checkpoint_store=CheckpointStore(tmp)
    )
    assert (reader.mesh is None) == writer_meshed
    assert sorted(reader.restore_from_checkpoints()) == list(range(D))
    assert _string_view(reader)[:2] == expected
    _feed_string(reader, sched, len(sched) + 1)
    assert _string_view(reader)[:2] == expected


@pytest.mark.parametrize("writer_meshed", WRITER_IS_MESHED)
def test_tree_checkpoint_crosses_mesh_line(writer_meshed):
    """Written at half the stream under one path, restored under the
    other, then fed the whole stream: the restored half is not applied
    twice and the rest lands on it."""
    logs, expected = _tree_logs(seed=4, steps=16)
    tmp = tempfile.mkdtemp()
    half = min(len(log) for log in logs) // 2
    writer = _feed_tree(
        _tree_engine(
            writer_meshed, checkpoint_store=CheckpointStore(tmp),
            checkpoint_every=8,
        ),
        logs, half,
    )
    writer.maybe_checkpoint(force=True)
    at_half = [writer.tree_json(d) for d in range(D)]
    del writer

    reader = _tree_engine(
        not writer_meshed, checkpoint_store=CheckpointStore(tmp)
    )
    assert (reader.mesh is None) == writer_meshed
    assert reader.restore_from_checkpoints() == list(range(D))
    reader.step()  # apply the re-materialization rows
    assert [reader.tree_json(d) for d in range(D)] == at_half
    _feed_tree(reader, logs)
    assert [reader.values(d) for d in range(D)] == expected
