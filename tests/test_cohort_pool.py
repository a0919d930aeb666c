"""The cohort trio leaves the text pool where it is (PR 37).

``DocBatchEngine._cohort_trio`` gathers and scatters every leaf of the busy
documents but ``text``; its step (``mk.apply_cohort_ops`` /
``apply_cohort_megastep``) takes the fleet's pool donated beside the gathered
rows and writes the strips its inserts append at the cohort's rows.  What it
may do is held to the path that owns the whole pool: the fleet-wide step
(``dbe._fleet_step``) over the same ops, NOOPs everywhere else.  The state
after the trio equals that step's leaf for leaf, the whole pool and every
padding slot included, and no row outside the cohort changes a bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.models import doc_batch_engine as dbe
from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.ops import mergetree_kernel as mk
from fluidframework_tpu.ops.pallas_kernels import LANES, SUBLANES, text_strip_width

K = mk.OpKind
B, L, T = 8, 8, 1024
GEOM = dict(max_segments=64, text_capacity=T, max_insert_len=L,
            ops_per_step=B, use_mesh=False, ob_slots=8, megastep_k=2)


class Rows:
    """Seeded op rows from one writer's own perspective (always valid): the
    visible length and the sequence number of every document are tracked on
    the host, as the microbenchmarks of PERF.md section 5 do."""

    def __init__(self, n_docs: int, seed):
        self.n = n_docs
        self.rng = np.random.default_rng(seed)
        self.length = np.zeros(n_docs, np.int64)
        self.seq = np.zeros(n_docs, np.int64)

    def slice(self, docs, depth: int, obliterate=()):
        """One [D, B] slice: each of ``docs`` holds 1..depth rows in a prefix
        of the slots (``depth`` itself for the first), the rest NOOPs; the
        documents ``obliterate`` start with a sided obliterate."""
        rng, n = self.rng, self.n
        ops = np.zeros((n, B, mk.OP_FIELDS), np.int32)
        pays = np.zeros((n, B, L), np.int32)
        depths = np.zeros(n, np.int64)
        depths[list(docs)] = rng.integers(1, depth + 1, len(docs))
        depths[list(docs)[0]] = depth
        ob_docs = np.zeros(n, bool)
        ob_docs[list(obliterate)] = True
        for i in range(depth):
            on = depths > i
            self.seq[on] += 1
            k = np.where(self.length < 6, K.INSERT, rng.choice(
                [K.INSERT, K.INSERT, K.REMOVE, K.ANNOTATE], n))
            if i == 0:
                k = np.where(ob_docs & (self.length >= 6), K.OBLITERATE, k)
            ins = on & (k == K.INSERT)
            ln = rng.integers(1, L + 1, n)
            pos = (rng.random(n) * (self.length + 1)).astype(np.int64)
            p1 = (rng.random(n) * np.maximum(self.length - 2, 1)).astype(np.int64)
            p2 = p1 + rng.integers(1, 3, n)
            row = np.zeros((n, mk.OP_FIELDS), np.int64)
            row[:, 0], row[:, 1], row[:, 2], row[:, 3] = k, self.seq, 1, self.seq - 1
            row[:, 4] = np.where(k == K.INSERT, pos, p1)
            row[:, 5] = np.where(k == K.INSERT, 0, p2)
            row[:, 6] = np.where(k == K.INSERT, ln,
                                 np.where(k == K.ANNOTATE, self.seq % 4, 0))
            row[:, 7] = np.where(k == K.ANNOTATE, self.seq, 0)
            ob = k == K.OBLITERATE
            row[ob, 5] = p2[ob] - 1
            row[ob, 6], row[ob, 7] = mk.SIDE_BEFORE, mk.SIDE_AFTER
            ops[on, i] = row[on]
            chars = rng.integers(97, 123, (n, L))
            chars[np.arange(L)[None, :] >= ln[:, None]] = 0
            pays[ins, i] = chars[ins]
            self.length[ins] += ln[ins]
            gone = on & ((k == K.REMOVE) | ob)
            self.length[gone] -= (p2 - p1)[gone]
        return ops, pays


def _fleet(n_docs: int, seed):
    """An engine of ``n_docs`` documents after two fleet-wide slices of
    seeded rows: every document holds text, each at a ``text_end`` of its
    own.  Returns the engine, its rows' generator and a host copy of the
    state."""
    eng = DocBatchEngine(n_docs, **GEOM)
    assert eng.capacity == n_docs
    rows = Rows(n_docs, seed)
    for _ in range(2):
        ops, pays = rows.slice(range(n_docs), 3)
        eng.state = dbe._fleet_step(eng.state, jnp.asarray(ops), jnp.asarray(pays))
    before = jax.tree.map(np.asarray, eng.state)
    assert not before.error.any()
    assert len(set(before.text_end.tolist())) > n_docs // 8
    return eng, rows, before


def _device(host_state):
    return jax.tree.map(jnp.array, host_state)


def _run(eng, before, busy, slices):
    """The trio over ``busy`` (padded as ``_cohort_step`` pads) against the
    fleet-wide step over the same [D, B] ``slices``.  Returns (got, want)."""
    want = _device(before)
    for ops, pays in slices:
        want = dbe._fleet_step(want, jnp.asarray(ops), jnp.asarray(pays))
    lanes = eng._cohort_lanes(len(busy))
    idx = np.full((lanes,), busy[-1], np.int32)
    idx[: len(busy)] = busy
    valid = np.zeros((lanes,), bool)
    valid[: len(busy)] = True
    stage = eng._staging()
    ops, pays = stage.acquire(len(slices), lanes)
    for j, (o, p) in enumerate(slices):
        ops[j, : len(busy)], pays[j, : len(busy)] = o[busy], p[busy]
        stage.mark(j, np.arange(len(busy)))
    eng.state = _device(before)
    eng._built.clear()
    eng._cohort_trio(idx, valid, ops, pays)
    assert (lanes, len(slices)) in eng._built
    return (jax.tree.map(np.asarray, eng.state),
            jax.tree.map(np.asarray, want))


def _same(got, want, before, busy):
    for name, g, w, b in zip(
            mk.DocState._fields, got, want, before, strict=True):
        for gl, wl, bl in zip(jax.tree.leaves(g), jax.tree.leaves(w),
                              jax.tree.leaves(b), strict=True):
            assert gl.shape == wl.shape and gl.dtype == wl.dtype, name
            np.testing.assert_array_equal(gl, wl, err_msg=name)
            rest = np.ones(gl.shape[0], bool)
            rest[busy] = False
            np.testing.assert_array_equal(gl[rest], bl[rest], err_msg=name)


N_DOCS = 96     # twelve tile rows: the kernel (interpreted here) writes


@pytest.fixture(scope="module")
def fleet():
    return _fleet(N_DOCS, 37)


def _spread(rng, n, must=()):
    docs = set(must)
    while len(docs) < n:
        docs.add(int(rng.integers(0, N_DOCS)))
    return sorted(docs)


@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
@pytest.mark.parametrize("gate", [False, True], ids=["no_obliterate", "obliterate"])
@pytest.mark.parametrize("n_busy,must", [
    (1, ()), (3, ()), (8, (16, 19)), (33, (40, 41, 47, 48, 63, 64)), (64, ()),
], ids=["one", "three_one_pad", "eight_two_in_a_tile_row",
        "thirty_three_adjacent_tile_rows_31_pads", "sixty_four"])
def test_trio_equals_the_fleet_wide_step(fleet, n_busy, must, gate, k):
    """1, 3, 8, 33 and 64 busy documents (1, 4, 8, 64, 64 lanes: pad lanes
    repeat the last busy row), documents that share an (8, 128) tile row
    and documents in adjacent tile rows, one slice and a K = 2 megastep, a
    batch whose gate an OBLITERATE opens and one without."""
    eng, rows, before = fleet
    rng = np.random.default_rng([n_busy, gate, k])
    busy = _spread(rng, n_busy, must)
    if must:
        tiles = [d // SUBLANES for d in must]
        assert len(set(tiles)) < len(tiles)              # a shared tile row
    gen = Rows(N_DOCS, [n_busy, gate, k, 1])
    gen.length, gen.seq = rows.length.copy(), rows.seq.copy()
    slices = [gen.slice(busy, 3, obliterate=busy[:2] if gate and j == 0 else ())
              for j in range(k)]
    assert (slices[0][0][:, :, 0] == K.OBLITERATE).any() == gate
    got, want = _run(eng, before, busy, slices)
    _same(got, want, before, busy)
    assert not got.error.any()
    assert (got.text_end[busy] > before.text_end[busy]).any()
    assert not np.array_equal(got.text, before.text)
    assert (got.ob_key >= 0).any() == gate


def test_text_overflow_latches_the_same_bits_and_pool(fleet):
    """An insert past the pool's end: the same error bit, the same pool and
    the same ``text_end`` as the fleet-wide step's."""
    eng, rows, before = fleet
    busy = [5, 6, 50]
    full = before._replace(text_end=before.text_end.copy())
    full.text_end[6] = T - 3
    gen = Rows(N_DOCS, 6)
    gen.length, gen.seq = rows.length.copy(), rows.seq.copy()
    ops, pays = gen.slice(busy, 2)
    ops[6, 0] = [K.INSERT, gen.seq[6] + 1, 1, gen.seq[6], 0, 0, 7, 0]
    ops[6, 1] = 0
    pays[6, 0] = np.arange(1, L + 1)
    got, want = _run(eng, full, busy, [(ops, pays)])
    _same(got, want, full, busy)
    assert got.error[6] & mk.ERR_TEXT_OVERFLOW
    assert not got.error[[5, 50]].any()


def test_a_row_past_its_strip_takes_a_second_pass(fleet):
    """A hand-made insert whose ``text_len`` exceeds L moves the document's
    next start past the strip: ``_write_text`` loops once more, for that
    lane alone, and the pool is the fleet-wide step's."""
    eng, rows, before = fleet
    busy = [8, 9, 30, 77]
    at = int(before.text_end[9])
    strip = text_strip_width(T, B * L)
    assert at + 2 * strip < T
    gen = Rows(N_DOCS, 9)
    gen.length, gen.seq = rows.length.copy(), rows.seq.copy()
    ops, pays = gen.slice(busy, 2)
    s = gen.seq[9]
    ops[9] = 0
    pays[9] = 0
    for i, n in enumerate((3, strip + 40, 5)):
        ops[9, i] = [K.INSERT, s + 1 + i, 1, s + i, 0, 0, n, 0]
        pays[9, i] = 100 * (i + 1) + np.arange(L)
    got, want = _run(eng, before, busy, [(ops, pays)])
    _same(got, want, before, busy)
    assert got.text_end[9] == at + 3 + strip + 40 + 5
    third = at + 3 + strip + 40
    assert third // LANES * LANES >= at // LANES * LANES + strip  # past it
    assert got.text[9, third] == 300 and before.text[9, third] == 0


@pytest.mark.parametrize("n_docs", [21, 4], ids=["pool_not_whole_tiles", "under_a_tile_row"])
def test_trio_on_a_pool_that_is_not_whole_tiles(n_docs):
    """A fleet whose pool is not whole (8, 128) tile rows takes plain
    updates, one lane after the other: the same state."""
    eng, rows, before = _fleet(n_docs, n_docs)
    busy = [0, 2, 3] if n_docs == 4 else [1, 7, 8, 9, 20]
    got, want = _run(eng, before, busy, [rows.slice(busy, 3)])
    _same(got, want, before, busy)
    assert not np.array_equal(got.text, before.text)


def test_engine_steps_a_zipf_tail_like_an_engine_without_cohorts():
    """``step()`` end to end: a few busy documents of a fleet go through
    ``_cohort_step`` (pads, K chosen from the queues), and the state, the
    whole pool included, is that of an engine that steps fleet-wide."""
    from fluidframework_tpu.dds.shared_string import SharedString
    from fluidframework_tpu.server.local_service import LocalService

    svc = LocalService()
    a, b = DocBatchEngine(32, **GEOM), DocBatchEngine(32, **GEOM)
    b.bucketing = False
    rng = np.random.default_rng(7)
    texts = {}
    for d in (3, 4, 11, 12, 30):
        doc = svc.document(f"d{d}")
        c = SharedString(client_id=f"w{d}")
        doc.connect(c.client_id, c.process)
        doc.process_all()
        for _ in range(int(rng.integers(2, 14))):
            c.insert_text(int(rng.integers(0, len(c.text) + 1)), "abcdefgh"[: int(rng.integers(1, 9))])
        for m in c.take_outbox():
            doc.submit(m)
        doc.process_all()
        wire = b"".join(m.wire_line() for m in doc.sequencer.log)
        for eng in (a, b):
            eng.ingest_lines(d, wire)
        texts[d] = c.text
    a.step()
    b.step()
    assert a.cohort_steps and not a.full_steps
    assert b.full_steps and not b.cohort_steps
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert {d: a.text(d) for d in texts} == texts
