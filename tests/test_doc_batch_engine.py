"""DocBatchEngine: batched multi-doc application matches per-doc oracles,
and the doc axis shards over the 8-device CPU mesh."""

import random

import numpy as np
import pytest

from fluidframework_tpu.dds.shared_string import SharedString
from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.server.local_service import LocalService

from test_mergetree_oracle import draw_op, issue_op, pump


def drive_docs(n_docs, seed, rounds=4, clients_per_doc=2):
    """Run independent multi-client sessions for n_docs documents; return the
    service (with full op logs) and converged oracle texts."""
    rng = random.Random(seed)
    svc = LocalService()
    all_clients = {}
    for d in range(n_docs):
        doc = svc.document(f"doc{d}")
        clients = []
        for i in range(clients_per_doc):
            c = SharedString(client_id=f"d{d}c{i}")
            doc.connect(c.client_id, c.process)
            clients.append(c)
        doc.process_all()
        all_clients[d] = clients
    for _round in range(rounds):
        for d in range(n_docs):
            doc = svc.document(f"doc{d}")
            for c in all_clients[d]:
                for _ in range(rng.randint(0, 2)):
                    issue_op(c, draw_op(rng, len(c.text)))
                if rng.random() < 0.7:
                    for m in c.take_outbox():
                        doc.submit(m)
            doc.process_some(rng.randint(0, doc.pending_count))
    for d in range(n_docs):
        pump(svc.document(f"doc{d}"), all_clients[d])
    texts = {d: all_clients[d][0].text for d in range(n_docs)}
    return svc, texts


@pytest.mark.parametrize("seed", [0, 1])
def test_engine_matches_oracle_fleet(seed):
    n_docs = 8
    svc, expected = drive_docs(n_docs, seed)
    eng = DocBatchEngine(
        n_docs, max_segments=256, text_capacity=4096, max_insert_len=8,
        ops_per_step=4,
    )
    for d in range(n_docs):
        for msg in svc.document(f"doc{d}").sequencer.log:
            eng.ingest(d, msg)
    eng.step()
    assert not eng.errors().any()
    for d in range(n_docs):
        assert eng.text(d) == expected[d], f"doc {d} diverged"
    # Zamboni across the fleet must not change any visible text.
    eng.compact()
    for d in range(n_docs):
        assert eng.text(d) == expected[d], f"doc {d} changed by compaction"


def test_engine_state_is_sharded_over_mesh():
    import jax

    eng = DocBatchEngine(16, max_segments=64, text_capacity=512)
    n_dev = len(jax.devices())
    assert n_dev == 8, "conftest should force 8 virtual CPU devices"
    # The doc axis must actually be partitioned across devices.
    sharding = eng.state.seg_len.sharding
    assert len(sharding.device_set) == n_dev
    # Stepping a sharded batch works and keeps sharding.
    svc, expected = drive_docs(16, seed=2, rounds=2)
    for d in range(16):
        for msg in svc.document(f"doc{d}").sequencer.log:
            eng.ingest(d, msg)
    eng.step()
    assert len(eng.state.seg_len.sharding.device_set) == n_dev
    for d in range(16):
        assert eng.text(d) == expected[d]


def test_zipf_bucketing_cuts_full_fleet_steps():
    """Straggler mitigation (SURVEY §7 doc-packing): with Zipf-skewed
    per-doc op counts, one hot doc no longer forces fleet-wide steps —
    the tail runs in small gathered cohorts, and the result is identical
    to the unbucketed engine."""
    rng = random.Random(5)
    n_docs = 16
    svc = LocalService()
    clients = {}
    # Zipf-ish skew: doc 0 gets ~40 ops, the rest 1-3.
    for d in range(n_docs):
        doc = svc.document(f"doc{d}")
        c = SharedString(client_id=f"d{d}")
        doc.connect(c.client_id, c.process)
        doc.process_all()
        clients[d] = c
        n_ops = 40 if d == 0 else rng.randint(1, 3)
        for _ in range(n_ops):
            n = len(c.text)
            if n > 6 and rng.random() < 0.3:
                p = rng.randrange(n - 2)
                c.remove_range(p, p + 1)
            else:
                c.insert_text(rng.randint(0, n), "abcd")
        for m in c.take_outbox():
            doc.submit(m)
        doc.process_all()

    def run(bucketing):
        eng = DocBatchEngine(
            n_docs, max_segments=256, text_capacity=4096, max_insert_len=8,
            ops_per_step=4, use_mesh=False, recovery="off",
        )
        eng.bucketing = bucketing
        for d in range(n_docs):
            for msg in svc.document(f"doc{d}").sequencer.log:
                eng.ingest(d, msg)
        eng.step()
        assert not eng.errors().any()
        return eng

    flat = run(False)
    bucketed = run(True)
    for d in range(n_docs):
        assert bucketed.text(d) == flat.text(d) == clients[d].text, d
    # The hot doc's ~40 ops need ~10 B=4 passes; unbucketed takes them all
    # fleet-wide, bucketed collapses to a couple of full steps + small
    # cohorts.
    assert flat.full_steps >= 8
    assert bucketed.full_steps <= 2, bucketed.full_steps
    assert bucketed.cohort_steps >= 6
    assert bucketed.cohort_lanes <= bucketed.cohort_steps * 4, (
        "cohorts must stay far below fleet width"
    )


def test_a_cold_cohort_shape_is_not_built_where_one_fleet_wide_slice_does():
    """Once the fleet-wide program is built, a small busy set whose cohort
    shape was never dispatched and whose queues fit one slice is stepped
    fleet-wide: serving pays no first dispatch it can avoid.  A fleet that
    never went wide builds its cohorts as it meets them, and so does
    warmup()."""
    n_docs = 16
    svc, expected = drive_docs(n_docs, seed=3)
    logs = [list(svc.document(f"doc{d}").sequencer.log) for d in range(n_docs)]

    def engine(ops_per_step=64):
        return DocBatchEngine(
            n_docs, max_segments=256, text_capacity=4096, max_insert_len=8,
            ops_per_step=ops_per_step, use_mesh=False, megastep_k=1,
        )

    def feed(eng, d, msgs):
        for msg in msgs:
            eng.ingest(d, msg)
        return d in eng._busy

    def taken(eng):
        return (
            eng.full_steps, eng.cohort_steps,
            eng.health().get("cohort_cold_fallbacks", 0),
        )

    # Narrow from the start: cohorts only.
    eng = engine()
    assert feed(eng, 0, logs[0])
    eng.step()
    assert taken(eng) == (0, 1, 0)

    # A backlog deeper than one slice builds its cohort: dense fleet-wide
    # slices would cost more than the build.
    eng = engine(ops_per_step=2)
    eng._full_built = True
    assert feed(eng, 0, logs[0]) and len(eng.hosts[0].queue) > 2
    assert not eng._cold_and_shallow([0])
    eng.step()
    assert eng.full_steps == 0 and eng.cohort_steps >= 2
    assert eng.text(0) == expected[0]

    # Wide, then loops of one busy document each.
    eng = engine()
    cut = [len(log) // 2 for log in logs]
    probe = engine()
    for d in range(n_docs):
        feed(probe, d, logs[d][:cut[d]])
    probe.step()
    tails = [d for d in range(n_docs) if feed(probe, d, logs[d][cut[d]:])]
    first, second, third = tails[:3]
    assert sum(feed(eng, d, logs[d][:cut[d]]) for d in range(n_docs)) > 4
    eng.step()
    assert taken(eng) == (1, 0, 0)
    assert feed(eng, first, logs[first][cut[first]:])
    eng.step()
    assert taken(eng) == (2, 0, 1)
    assert feed(eng, second, logs[second][cut[second]:])
    eng.step()
    assert taken(eng) == (3, 0, 2)
    before = [eng.text(d) for d in range(n_docs)]
    eng.warmup()
    assert [eng.text(d) for d in range(n_docs)] == before
    assert feed(eng, third, logs[third][cut[third]:])
    eng.step()
    assert taken(eng) == (3, 1, 2)
    for d in range(n_docs):
        if d not in (first, second, third):
            feed(eng, d, logs[d][cut[d]:])
    eng.step()
    assert not eng.errors().any()
    for d in range(n_docs):
        assert eng.text(d) == expected[d]
