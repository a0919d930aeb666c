"""Megastep pipeline: K-invariance fuzz + dispatch-amortization counters.

The tentpole contract of the scan-fused megastep dispatch
(ops/mergetree_kernel.apply_megastep / ops/tree_kernel.apply_nested_megastep
behind models/doc_batch_engine / models/tree_batch_engine):

- **K-invariance**: an identical op schedule applied with megastep_k=1
  (today's per-slice dispatch, preserved exactly) and megastep_k=8 produces
  BYTE-IDENTICAL device states and digests for both engine families —
  including obliterate ops (the per-slice ob gate hoisted to the scan
  carry), overflow-latch recovery into grow lanes, quarantine/readmit
  interleaving, and tree fallback routing.
- **Counters**: ``steps_per_dispatch`` / ``megastep_k`` /
  ``staging_overlap_packs`` surface through ``health()`` and the fleet
  status line (``fleet_main.status_snapshot``), and a megastep engine
  actually amortizes (steps_per_dispatch > 1 on deep queues).

Tier-1 sizes here; the larger sweep runs under ``-m slow``.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine, _fleet_digest
from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine
from fluidframework_tpu.server.fleet_main import status_snapshot

from test_engine_checkpoint import _ins, _join, _op, _rm


# ------------------------------------------------------------------ schedule

def _schedule(
    n_docs: int,
    rounds: int,
    seed: int = 0,
    obliterate: bool = False,
    poison: tuple | None = None,
    big: tuple | None = None,
):
    """Deterministic single-writer schedule (valid in its own perspective):
    inserts/removes, optional plain obliterates, one optional poison op
    (out-of-range insert -> quarantine) and one optional capacity-buster
    (long insert -> overflow latch + grow-lane recovery)."""
    rng = np.random.default_rng(seed)
    lengths = [0] * n_docs
    seqs = [0] * n_docs
    out: list[tuple[int, object]] = []
    for r in range(rounds):
        for d in range(n_docs):
            if poison == (d, r):
                seqs[d] += 1
                out.append((d, _ins(seqs[d], 10**6, "XX")))
            seqs[d] += 1
            roll = rng.random()
            if big == (d, r):
                p = lengths[d] // 2
                out.append((d, _ins(seqs[d], p, "Z" * 40)))
                lengths[d] += 40
            elif obliterate and lengths[d] >= 6 and roll < 0.15:
                p1 = int(rng.integers(0, lengths[d] - 2))
                p2 = int(rng.integers(p1 + 1, lengths[d] + 1))
                out.append((d, _op(seqs[d], {"type": 4, "pos1": p1, "pos2": p2})))
                lengths[d] -= p2 - p1
            elif lengths[d] >= 4 and roll < 0.4:
                p = int(rng.integers(0, lengths[d] - 1))
                out.append((d, _rm(seqs[d], p, p + 1)))
                lengths[d] -= 1
            else:
                p = int(rng.integers(0, lengths[d] + 1))
                out.append((d, _ins(seqs[d], p, "ab")))
                lengths[d] += 2
    return out


def _run_doc_engine(megastep_k, schedule, n_docs, step_every=41, **kw):
    kw.setdefault("max_segments", 128)
    kw.setdefault("text_capacity", 1024)
    eng = DocBatchEngine(
        n_docs, remove_slots=4, max_insert_len=8, ops_per_step=4,
        use_mesh=False, megastep_k=megastep_k, **kw,
    )
    for d in range(n_docs):
        eng.ingest(d, _join("w0", 0))
    for i, (d, msg) in enumerate(schedule):
        eng.ingest(d, msg)
        if (i + 1) % step_every == 0:
            eng.step()
    eng.step()
    return eng


def _assert_identical(a: DocBatchEngine, b: DocBatchEngine) -> None:
    """Byte-identical device states + digests + views + lane routing."""
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
    assert (
        np.asarray(_fleet_digest(a.state)).tobytes()
        == np.asarray(_fleet_digest(b.state)).tobytes()
    )
    assert sorted(a.overflow) == sorted(b.overflow)
    for d in a.overflow:
        assert a.overflow[d].geometry == b.overflow[d].geometry
        for x, y in zip(
            jax.tree.leaves(a.overflow[d].state),
            jax.tree.leaves(b.overflow[d].state),
        ):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
    assert sorted(a.quarantine) == sorted(b.quarantine)
    assert sorted(a.oracles) == sorted(b.oracles)
    for d in range(a.n_docs):
        assert a.text(d) == b.text(d), f"doc {d}"
        assert a.annotations(d) == b.annotations(d), f"doc {d}"
    assert not a.errors().any() and not b.errors().any()


# ------------------------------------------------------- string K-invariance

@pytest.mark.parametrize("seed", [0, 1])
def test_k_invariance_doc_engine(seed):
    sched = _schedule(6, 24, seed=seed)
    a = _run_doc_engine(1, sched, 6)
    b = _run_doc_engine(8, sched, 6)
    # The megastep engine must actually have fused slices (otherwise this
    # test proves nothing).
    assert b.health()["steps_per_dispatch"] > 1.0
    _assert_identical(a, b)


def test_k_invariance_with_obliterates():
    # n_docs=6 matches the plain-invariance tests so the module-level jit
    # cache serves every (geometry, K) program already compiled there.
    sched = _schedule(6, 24, seed=2, obliterate=True)
    assert any(m.contents.get("type") == 4 for _d, m in sched)
    a = _run_doc_engine(1, sched, 6)
    b = _run_doc_engine(8, sched, 6)
    assert b.health()["steps_per_dispatch"] > 1.0
    _assert_identical(a, b)


def test_k_invariance_overflow_latch_recovery():
    """A capacity-busting insert latches ERR_* on device and recovers into
    a grow lane at the same observation point (megastep granularity) for
    K=1 and K=8 — states, lanes, and grown geometries all byte-identical."""
    # Geometry chosen so ONLY the capacity-buster overflows (text, not
    # segments) and one doubling fits the replay — exactly one grow-lane
    # geometry to compile, keeping the test tier-1-cheap.
    sched = _schedule(4, 20, seed=3, big=(1, 4))
    kw = dict(max_segments=64, text_capacity=48)
    a = _run_doc_engine(1, sched, 4, **kw)
    b = _run_doc_engine(8, sched, 4, **kw)
    assert a.overflow or a.oracles, "schedule must actually overflow"
    _assert_identical(a, b)
    assert a.health()["capacity_recoveries"] == b.health()["capacity_recoveries"]


def test_k_invariance_quarantine_readmit_interleaving():
    """A poison op quarantines its doc mid-schedule; backoff readmission
    packs the oracle state back into the batch while traffic continues —
    identical under K=1 and K=8 (readmit cadence counts step() calls,
    which are K-invariant)."""
    sched = _schedule(6, 24, seed=4, poison=(2, 5))
    kw = dict(readmit_after_steps=2)
    a = _run_doc_engine(1, sched, 6, step_every=5, **kw)
    b = _run_doc_engine(8, sched, 6, step_every=5, **kw)
    ha, hb = a.health(), b.health()
    assert ha["quarantines"] == hb["quarantines"] >= 1
    assert ha.get("readmissions", 0) == hb.get("readmissions", 0) >= 1
    _assert_identical(a, b)


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(6))
def test_k_invariance_sweep(seed):
    """Larger fuzz sweep: more docs/rounds, obliterates + poison + overflow
    in one schedule, several K values."""
    sched = _schedule(
        12, 48, seed=seed, obliterate=True, poison=(3, 9), big=(5, 7)
    )
    kw = dict(max_segments=32, text_capacity=256, readmit_after_steps=3)
    ref = _run_doc_engine(1, sched, 12, step_every=11, **kw)
    for k in (2, 4, 8):
        eng = _run_doc_engine(k, sched, 12, step_every=11, **kw)
        _assert_identical(ref, eng)


# --------------------------------------------------------- tree K-invariance

def _run_tree_engine(megastep_k, svc, n_docs, step_every=9, **kw):
    kw.setdefault("capacity", 512)
    kw.setdefault("pool_capacity", 2048)
    eng = TreeBatchEngine(
        n_docs, ops_per_step=4, megastep_k=megastep_k, **kw,
    )
    i = 0
    for d in range(n_docs):
        for msg in svc.document(f"doc{d}").sequencer.log:
            eng.ingest(d, msg)
            i += 1
            if i % step_every == 0:
                eng.step()
    eng.step()
    return eng


def _assert_tree_identical(a: TreeBatchEngine, b: TreeBatchEngine) -> None:
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
    assert sorted(a.fallbacks) == sorted(b.fallbacks)
    for d in range(a.n_docs):
        assert a.tree_json(d) == b.tree_json(d), f"doc {d}"


@pytest.mark.parametrize("nested_prob", [0.0, 1.0])
def test_k_invariance_tree_engine(nested_prob):
    """Tree-family K-invariance, with (nested_prob=1.0) and without
    nested-field edits in the mix (both ride the columnar device path)."""
    from test_tree_batch_engine import drive_tree_docs

    svc, expected = drive_tree_docs(4, seed=7, steps=24, nested_prob=nested_prob)
    a = _run_tree_engine(1, svc, 4)
    b = _run_tree_engine(8, svc, 4)
    assert b.health()["steps_per_dispatch"] > 1.0
    _assert_tree_identical(a, b)
    for d in range(4):
        assert a.values(d) == b.values(d) == expected[d]


def test_k_invariance_tree_fallback_routing():
    """A wide leaf (wider than one payload row) routes its doc to the host
    fallback at the same megastep-granularity observation point for K=1
    and K=8, while a sibling doc stays columnar — membership, values, and
    device state all identical."""
    from test_tree_batch_engine import drive_tree_docs
    from fluidframework_tpu.dds.channels import default_registry
    from fluidframework_tpu.dds.tree.changeset import make_insert
    from fluidframework_tpu.dds.tree.schema import leaf
    from fluidframework_tpu.runtime import ContainerRuntime

    svc, expected = drive_tree_docs(2, seed=11, steps=16)
    doc = svc.document("doc0")
    rt = ContainerRuntime(default_registry(), container_id="wide")
    rt.create_datastore("root").create_channel("sharedTree", "t")
    rt.connect(doc, "wide")
    doc.process_all()
    t = rt.datastore("root").get_channel("t")
    t.submit_change(make_insert([], "", 0, [leaf("x" * 100)]))
    t.submit_change(make_insert([], "", 1, [leaf(7)]))
    rt.flush()
    doc.process_all()
    a = _run_tree_engine(1, svc, 2)
    b = _run_tree_engine(8, svc, 2)
    assert 0 in b.fallbacks, "wide leaf must route doc 0 to fallback"
    assert 1 not in b.fallbacks
    _assert_tree_identical(a, b)
    assert a.values(1) == b.values(1) == expected[1]


# ----------------------------------------------------------------- counters

def test_megastep_counters_in_health_and_fleet_status():
    """CI smoke (ISSUE 4 satellite): the megastep pipeline surfaces
    ``steps_per_dispatch`` / ``megastep_k`` / ``staging_overlap_packs``
    through engine health AND the fleet status line, and a deep queue
    actually amortizes dispatches (steps_per_dispatch > 1)."""
    sched = _schedule(6, 16, seed=5)
    # megastep_k=2 reuses the K=2 program the invariance tests compiled.
    eng = _run_doc_engine(2, sched, 6, step_every=10**9)  # one deep drain
    h = eng.health()
    assert h["megastep_k"] == 2
    assert h["steps_per_dispatch"] > 1.0
    assert h["megastep_slices"] > h["megastep_dispatches"] >= 1
    assert "staging_overlap_packs" in h
    status = status_snapshot(eng, [str(d) for d in range(6)], rows=7)
    assert status["rows"] == 7
    for key in ("steps_per_dispatch", "megastep_k", "staging_overlap_packs"):
        assert key in status["health"], key
    # K=1 reports the degenerate ratio (1.0) — the exact legacy path.
    legacy = _run_doc_engine(1, _schedule(6, 4, seed=6), 6)
    assert legacy.health()["steps_per_dispatch"] == 1.0
    # Tree engine surfaces the same counter family.
    th = TreeBatchEngine(2, megastep_k=4).health()
    assert th["megastep_k"] == 4 and "steps_per_dispatch" in th
    assert "staging_aliased_swaps" in h and "staging_aliased_swaps" in th


# ------------------------------------------------------------ staging ring

@pytest.mark.parametrize("meshed", [False, True], ids=["one_device", "mesh"])
def test_staging_ring_never_reuses_memory_an_upload_aliases(meshed):
    """On a zero-copy backend (the CPU, for a host buffer that happens to be
    64-byte aligned) an uploaded array reads the staging buffer for as long
    as it lives, so the ring must hand that memory over instead of refilling
    it.  Under a mesh the upload is sharded, a sharded array has no pointer
    of its own, and each shard of a K = 1 upload is a contiguous slice of
    the buffer: the probe has to look shard by shard (it did not, and
    ``test_engine_matches_oracle_fleet`` lost ops whenever numpy's allocator
    handed out such a buffer and the machine was busy: a pending dispatch
    read a buffer that was being refilled)."""
    import jax
    import numpy as np

    from fluidframework_tpu.models.staging import StagingRing
    from fluidframework_tpu.parallel.mesh import doc_mesh

    def aligned_zeros(shape):
        n = int(np.prod(shape)) * 4
        raw = np.zeros(n + 64, np.uint8)
        start = (-raw.ctypes.data) % 64
        return raw[start:start + n].view(np.int32).reshape(shape)

    rows = 2 * len(jax.devices())
    ring = StagingRing(4, rows, 4, 8, 8, depth=1,
                       mesh=doc_mesh() if meshed else None)
    buf = ring._bufs[0]
    buf.ops, buf.payloads = (aligned_zeros(buf.ops.shape),
                             aligned_zeros(buf.payloads.shape))
    ops, pays = ring.acquire(1, rows)
    ops[...] = 7
    dev = ring.upload(ops, pays)
    assert all(
        ops.ctypes.data <= s.data.unsafe_buffer_pointer()
        < ops.ctypes.data + ops.nbytes
        for s in dev[0].addressable_shards), "this backend copies: no case"
    ops2, _pays2 = ring.acquire(1, rows)          # same slot: depth is 1
    ops2[...] = 9
    assert ring.aliased_swaps == 1
    assert np.asarray(dev[0]).min() == 7, "the upload's memory was refilled"
