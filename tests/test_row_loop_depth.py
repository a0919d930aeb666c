"""The merge-tree row loop ends at the deepest queue of its batch.

``mk.apply_fleet_ops`` (and ``mk.apply_ops`` for one document) loops over
the first ``rows`` of its B row slots, where ``rows`` is data: 1 + the last
slot that holds an op in ANY document of the batch (``mk.row_count``).  The
loop is outside the ``vmap`` over documents, its body the vmapped row.
Until PR 31 a ``lax.scan`` under the ``vmap`` ran all B slots whatever they
held; that dense scan is kept HERE as the reference.

(a) Identity: every leaf of the state, padding slots and the error latch
included, equals the dense scan's: for depths 0 to B, with an interior NOOP,
for every kind and a row that latches an overflow, and through every program
that runs the loop (``_fleet_step``, ``apply_megastep`` with slices of
different depth, ``_lane_apply_jit``, the ``shard_map``-wrapped megastep with
shards of different depth).

(b) The bound stays a scalar: the ``while`` of the step tests one predicate
for every document and runs ``rows`` times, not B; a batched bound (what
``vmap`` of the one-document ``apply_ops`` makes of a per-document count)
fails the same check.  The row body is traced once per obliterate trace.

(c) The engine counts how often the data trip count engages:
``row_slots_scanned`` / ``row_slots_dense`` in ``health()``, per cohort,
fleet-wide and lane dispatch.
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np
import pytest

from fluidframework_tpu.models import doc_batch_engine as dbe
from fluidframework_tpu.ops import mergetree_kernel as mk
from fluidframework_tpu.parallel import mesh as pm
from fluidframework_tpu.protocol.stamps import LOCAL_BASE

from test_engine_checkpoint import _ins, _join

S, R, P, T, OB, L, B = 48, 2, 2, 256, 4, 4, 32
D = 4
K = mk.OpKind


# ------------------------------------------------- the dense reference
def _scatter_write_text(text, writes, payloads):
    """``mk._write_text`` as it was until the strip write: ONE per-element
    scatter of a document's [B * L] payload elements, an element that a later
    row writes too sent past the end (dropped), so no index twice.  Kept
    HERE as the reference: every program below is compared with the old
    write, pool padding included, not with itself."""
    n_rows, width = payloads.shape
    cap = text.shape[0]
    tpos = jnp.arange(width, dtype=jnp.int32)
    pos = writes.start[:, None] + tpos[None, :]
    live = tpos[None, :] < writes.count[:, None]
    later = jnp.arange(n_rows)[:, None] < jnp.arange(n_rows)[None, :]
    lo = writes.start[None, None, :]
    hit = (lo <= pos[:, :, None]) & (pos[:, :, None] < lo + writes.count[None, None, :])
    covered = jnp.any(hit & later[:, None, :], axis=-1)
    dst = jnp.where(live & ~covered, pos, cap)
    return text.at[dst.reshape(-1)].set(payloads.reshape(-1), mode="drop")


def _dense_apply_ops(s, ops, payloads, ob_flag):
    """``apply_ops`` as it was: ``lax.scan`` of the row body over all B, then
    the one scatter."""

    def scan_spec(st, flag):
        cap = st.text.shape[0]

        def step(carry, xs):
            return mk._apply_row(carry, xs[0], xs[1], flag, cap)

        out, writes = jax.lax.scan(
            step, st._replace(text=jnp.zeros((0,), jnp.int32)),
            (ops, payloads))
        return out._replace(text=_scatter_write_text(st.text, writes, payloads))

    return jax.lax.cond(
        ob_flag, lambda st: scan_spec(st, True),
        lambda st: scan_spec(st, False), s)


def _ob_flag(state, ops):
    return jnp.any(state.ob_key >= 0) | jnp.any(ops[..., 0] == K.OBLITERATE)


@jax.jit
def _dense_fleet_step(state, ops, payloads):
    return jax.vmap(_dense_apply_ops, in_axes=(0, 0, 0, None))(
        state, ops, payloads, _ob_flag(state, ops))


def _fleet(n_docs=D):
    proto = mk.init_state(S, R, P, T, OB)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_docs,) + x.shape), proto)


def _assert_same(got, want):
    names, leaves, _ = pm.named_leaves(got)
    for name, x, y in zip(names, leaves, jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name


# ------------------------------------------------------ building rows
class _Rows:
    """Sequenced rows for ``n_docs`` documents, valid in one writer's own
    perspective; ``slice(depths)`` packs a [n_docs, B] batch the way
    ``_drain_into`` does: document d's next ``depths[d]`` rows in a prefix
    of its slots."""

    def __init__(self, n_docs=D, seed=0):
        self.rng = np.random.default_rng(seed)
        self.n_docs = n_docs
        self.length = [0] * n_docs
        self.seq = [0] * n_docs

    def row(self, d, kind=None):
        self.seq[d] += 1
        seq, n = self.seq[d], self.length[d]
        if kind is None:
            kind = K.INSERT if n < 6 else int(
                self.rng.choice([K.INSERT, K.REMOVE, K.ANNOTATE]))
        pay = np.zeros((L,), np.int32)
        if kind == K.INSERT:
            ln = int(self.rng.integers(1, L + 1))
            pos = int(self.rng.integers(0, n + 1))
            pay[:ln] = self.rng.integers(97, 123, ln)
            self.length[d] += ln
            return [kind, seq, 1, seq - 1, pos, 0, ln, 0], pay
        p1 = int(self.rng.integers(0, n - 2))
        p2 = p1 + int(self.rng.integers(1, 3))
        if kind == K.REMOVE:
            self.length[d] -= p2 - p1
            return [kind, seq, 1, seq - 1, p1, p2, 0, 0], pay
        if kind == K.ANNOTATE:
            return [kind, seq, 1, seq - 1, p1, p2, seq % P, seq], pay
        assert kind == K.OBLITERATE
        # Sided form of the plain range [p1, p2).
        self.length[d] -= p2 - p1
        return [kind, seq, 1, seq - 1, p1, p2 - 1,
                mk.SIDE_BEFORE, mk.SIDE_AFTER], pay

    def slice(self, depths, kind=None, hole=None):
        ops = np.zeros((self.n_docs, B, mk.OP_FIELDS), np.int32)
        pays = np.zeros((self.n_docs, B, L), np.int32)
        for d, depth in enumerate(depths):
            slots = [i for i in range(depth + (hole is not None))
                     if i != hole][:depth]
            for i in slots:
                ops[d, i], pays[d, i] = self.row(d, kind)
        return jnp.asarray(ops), jnp.asarray(pays)


def _seeded(rows):
    """A fleet with some content: two dense slices of 8 rows a document."""
    state = _fleet(rows.n_docs)
    for _ in range(2):
        state = _dense_fleet_step(state, *rows.slice([8] * rows.n_docs))
    return state


def _copy(state):
    # The step programs donate their state.
    return jax.tree.map(jnp.copy, state)


# ------------------------------------------------------------ identity
@pytest.mark.parametrize("depth", [0, 1, 2, 5, 31, 32])
def test_depths_equal_the_dense_scan(depth):
    rows = _Rows(seed=depth)
    state = _seeded(rows)
    # Document 0 holds the deepest queue; the others hold less.
    depths = [depth] + [int(rows.rng.integers(0, depth + 1))
                        for _ in range(D - 1)]
    ops, pays = rows.slice(depths)
    assert int(mk.row_count(ops)) == depth
    want = _dense_fleet_step(_copy(state), ops, pays)
    _assert_same(dbe._fleet_step(state, ops, pays), want)


def test_interior_noop_runs_as_a_row():
    rows = _Rows(seed=7)
    state = _seeded(rows)
    # Slot 1 of every document is empty, slots 0, 2, 3 hold ops: only the
    # common tail is cut.
    ops, pays = rows.slice([3, 3, 2, 1], hole=1)
    assert int(mk.row_count(ops)) == 4
    want = _dense_fleet_step(_copy(state), ops, pays)
    _assert_same(dbe._fleet_step(state, ops, pays), want)


def _pending_then_ack(rows, d):
    """A local pending insert (key LOCAL_BASE + 1) and its ack as seq."""
    rows.seq[d] += 1
    seq = rows.seq[d]
    pay = np.zeros((L,), np.int32)
    pay[:2] = (120, 121)
    rows.length[d] += 2
    pending = [K.INSERT, LOCAL_BASE + 1, 1, seq - 1, 0, 0, 2, 0]
    ack = [K.ACK, 0, -1, -1, 0, 0, 1, seq]
    return (pending, pay), (ack, np.zeros((L,), np.int32))


@pytest.mark.parametrize(
    "kind", ["insert", "remove", "annotate", "ack", "obliterate",
             "seg_overflow"])
def test_kinds_equal_the_dense_scan(kind):
    rows = _Rows(seed=11)
    state = _seeded(rows)
    if kind == "ack":
        ops = np.zeros((D, B, mk.OP_FIELDS), np.int32)
        pays = np.zeros((D, B, L), np.int32)
        for d in range(D):
            (ops[d, 0], pays[d, 0]), (ops[d, 1], pays[d, 1]) = (
                _pending_then_ack(rows, d))
        ops, pays = jnp.asarray(ops), jnp.asarray(pays)
    elif kind == "seg_overflow":
        # Document 0 inserts until its 48 segment slots are full and past.
        ops, pays = rows.slice([B, 3, 2, 1], kind=K.INSERT)
        state = dbe._fleet_step(state, ops, pays)
        ops, pays = rows.slice([B, 3, 2, 1], kind=K.INSERT)
    else:
        ops, pays = rows.slice([3, 2, 1, 0], kind=getattr(K, kind.upper()))
    want = _dense_fleet_step(_copy(state), ops, pays)
    got = dbe._fleet_step(state, ops, pays)
    _assert_same(got, want)
    errors = np.asarray(got.error)
    if kind == "seg_overflow":
        assert errors[0] & mk.ERR_SEG_OVERFLOW and not errors[1:].any()
    else:
        assert not errors.any()
    if kind == "obliterate":
        assert (np.asarray(got.ob_key) >= 0).any()
        # The table is nonempty now: the next batch runs the ob trace too.
        ops, pays = rows.slice([2, 0, 1, 0])
        want = _dense_fleet_step(_copy(got), ops, pays)
        _assert_same(dbe._fleet_step(got, ops, pays), want)


def _megastep_case(n_docs=8):
    rows = _Rows(n_docs=n_docs, seed=3)
    state = _seeded(rows)
    # Two slices of different depth; within each, the first half of the
    # documents (one shard of two) is deeper than the second.
    half = n_docs // 2
    first = rows.slice(([5, 3, 4, 1] * half)[:half] + ([2, 0, 1, 2] * half)[:half])
    second = rows.slice(([1, 0, 1, 1] * half)[:half] + [0] * half)
    ops = jnp.stack([first[0], second[0]])
    pays = jnp.stack([first[1], second[1]])
    want = _copy(state)
    for k in range(2):
        want = _dense_fleet_step(want, ops[k], pays[k])
    return state, ops, pays, want


# The text write takes whole tile rows of eight documents through its kernel
# and the documents after them by plain updates: ``_tile_rows`` programs hold
# both (20 documents; 12 a shard), the others the plain updates alone.
@pytest.mark.parametrize("program", [
    "fleet_step", "fleet_step_tile_rows", "megastep", "megastep_tile_rows",
    "lane", "mesh", "mesh_tile_rows"])
def test_programs_equal_the_dense_scan(program):
    n_docs = {"fleet_step": D, "fleet_step_tile_rows": 20, "megastep": 8,
              "megastep_tile_rows": 20, "lane": 1, "mesh": 8,
              "mesh_tile_rows": 24}[program]
    if program.startswith("fleet_step"):
        rows = _Rows(n_docs=n_docs, seed=5)
        state = _seeded(rows)
        # Seven consecutive batches on one carried state.
        want = _copy(state)
        for depth in (0, 1, 2, 3, 4, 6, 32):
            ops, pays = rows.slice(
                [depth] + [int(rows.rng.integers(0, depth + 1))
                           for _ in range(n_docs - 1)])
            state = dbe._fleet_step(state, ops, pays)
            want = _dense_fleet_step(want, ops, pays)
            _assert_same(state, want)
    elif program.startswith("megastep"):
        state, ops, pays, want = _megastep_case(n_docs)
        assert [int(mk.row_count(o)) for o in ops] == [5, 1]
        _assert_same(dbe._fleet_megastep(state, ops, pays), want)
    elif program == "lane":
        # One document, the row not vmapped: the trip count is its own.
        rows = _Rows(n_docs=n_docs, seed=9)
        state = jax.tree.map(lambda x: x[0], _seeded(rows))
        ops, pays = rows.slice([5])
        want = jax.jit(_dense_apply_ops)(
            state, ops[0], pays[0], _ob_flag(state, ops[0]))
        _assert_same(dbe._lane_apply_jit(state, ops[0], pays[0]), want)
    else:
        state, ops, pays, want = _megastep_case(n_docs)
        mesh = pm.doc_mesh(jax.devices()[:2])
        # Each shard holds half of the documents: their counts differ in
        # both slices, and each runs its own.
        half = n_docs // 2
        assert [[int(mk.row_count(o[:half])), int(mk.row_count(o[half:]))]
                for o in ops] == [[5, 2], [1, 0]]
        specs = pm.fleet_state_specs(state)
        program = pm.mesh_fleet_program(mk.apply_megastep, mesh, specs)
        state = pm.shard_fleet_state(state, mesh)
        ring = pm.NamedSharding(mesh, pm.op_spec(4))
        got = program(
            state, jax.device_put(ops, ring), jax.device_put(pays, ring))
        _assert_same(got, want)


# ------------------------------------------------ the bound is a scalar
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _row_loops(step, *args):
    """The ``while`` equations of ``step``'s jaxpr whose body carries a
    [D, S] segment column: the row loops (one per obliterate trace)."""
    every = _eqns(jax.make_jaxpr(step)(*args).jaxpr)
    loops = [e for e in every if e.primitive.name == "while"
             and any(tuple(v.aval.shape) == (D, S)
                     for v in e.params["body_jaxpr"].jaxpr.invars)]
    assert len(loops) == 2, len(loops)
    return loops


def _predicate_is_unbatched(loop):
    cond = loop.params["cond_jaxpr"].jaxpr
    return all(v.aval.shape == ()
               for e in _eqns(cond) for v in (*e.invars, *e.outvars)
               if isinstance(v, jex_core.Var))


def test_trip_count_reaches_the_loop_unbatched():
    state = _fleet()
    ops = jnp.zeros((D, B, mk.OP_FIELDS), jnp.int32)
    pays = jnp.zeros((D, B, L), jnp.int32)
    # dbe._fleet_step's own function, without its jit and donation.
    for loop in _row_loops(dbe._fleet_step.__wrapped__, state, ops, pays):
        assert _predicate_is_unbatched(loop)

    # The control: ``vmap`` of the one-document program takes the count per
    # document, it reaches the loop batched, and the same check says so.
    def batched(state, ops, pays):
        return jax.vmap(mk.apply_ops, in_axes=(0, 0, 0, None))(
            state, ops, pays, _ob_flag(state, ops))

    for loop in _row_loops(batched, state, ops, pays):
        assert not _predicate_is_unbatched(loop)


def _count_row_bodies(monkeypatch, traced, ran):
    """``mk._apply_row`` counting its traces and, on the device, its runs."""
    body = mk._apply_row

    def counted(s, op, payload, flag, cap):
        traced.append(flag)
        jax.debug.callback(lambda: ran.append(1))
        return body(s, op, payload, flag, cap)

    monkeypatch.setattr(mk, "_apply_row", counted)


@pytest.mark.parametrize("depth", [0, 3, 32])
def test_row_body_runs_rows_times_not_B(depth, monkeypatch):
    traced, ran = [], []
    _count_row_bodies(monkeypatch, traced, ran)
    rows = _Rows(seed=depth)
    ops, pays = rows.slice([depth, 0, depth // 2, 0])
    # A function of this test's own: jit's cache would hand back a trace
    # that counts into another case's list.
    step = jax.jit(lambda *args: mk.apply_fleet_ops(*args))
    jax.block_until_ready(step(_fleet(), ops, pays))
    jax.effects_barrier()
    assert len(ran) == depth


@pytest.mark.parametrize("program", ["fleet_step", "megastep", "lane"])
def test_row_body_is_traced_once_per_obliterate_trace(program, monkeypatch):
    """The loop is outside the ``vmap``, so a program shape costs two traces
    of the row (with and without the obliterate parts) however many
    documents and slices it has: ``vmap`` of a ``while`` would batch the
    body again, and the row is most of a first dispatch's seconds."""
    traced, ran = [], []
    _count_row_bodies(monkeypatch, traced, ran)
    state = _fleet()
    ops = jnp.zeros((D, B, mk.OP_FIELDS), jnp.int32)
    pays = jnp.zeros((D, B, L), jnp.int32)
    # Functions of this test's own: a cached trace would count nothing.
    if program == "fleet_step":
        jaxpr = jax.make_jaxpr(lambda *a: mk.apply_fleet_ops(*a))(
            state, ops, pays)
    elif program == "megastep":
        jaxpr = jax.make_jaxpr(lambda *a: mk.apply_megastep(*a))(
            state, jnp.stack([ops, ops]), jnp.stack([pays, pays]))
    else:
        jaxpr = jax.make_jaxpr(lambda *a: mk.apply_ops(*a))(
            jax.tree.map(lambda x: x[0], state), ops[0], pays[0])
    assert sorted(traced) == [False, True]
    # ... and the program holds the row twice, not once per batching pass.
    rows = [e for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name in ("jit", "pjit")
            and e.params["name"] == "row"]
    assert len(rows) == 2


# ------------------------------------------------ the engine's counters
def _engine(n_docs, **kw):
    eng = dbe.DocBatchEngine(
        n_docs, max_segments=64, text_capacity=512, remove_slots=2,
        prop_slots=2, max_insert_len=4, ops_per_step=8, use_mesh=False, **kw)
    for d in range(n_docs):
        eng.ingest(d, _join("w0", 0))
    return eng


def _feed(eng, d, n, seqs):
    for _ in range(n):
        seqs[d] = seqs.get(d, 0) + 1
        eng.ingest(d, _ins(seqs[d], 0, "ab"))


def _slots(eng):
    h = eng.health()
    return h["row_slots_scanned"], h["row_slots_dense"]


@pytest.mark.parametrize("path", ["cohort", "full", "megastep", "lane"])
def test_health_counts_row_slots(path):
    seqs = {}
    if path == "cohort":
        eng = _engine(16, megastep_k=1)
        assert _slots(eng) == (0, 0)
        # 3 of 16 documents busy (<= 16 // 4): one cohort slice, deepest 5.
        for d, n in ((1, 5), (4, 2), (9, 1)):
            _feed(eng, d, n, seqs)
        eng.step()
        assert (eng.cohort_steps, eng.full_steps) == (1, 0)
        assert _slots(eng) == (5, 8)
        # A queue deeper than ops_per_step: slices of 8, then 3.
        _feed(eng, 2, 11, seqs)
        eng.step()
        assert eng.cohort_steps == 3
        assert _slots(eng) == (5 + 8 + 3, 3 * 8)
    elif path == "full":
        eng = _engine(8, megastep_k=1)
        # 5 of 8 busy (> 8 // 4): one fleet-wide slice, deepest 3.
        for d in range(5):
            _feed(eng, d, 1 + d % 3, seqs)
        eng.step()
        assert (eng.cohort_steps, eng.full_steps) == (0, 1)
        assert _slots(eng) == (3, 8)
    elif path == "megastep":
        eng = _engine(8, megastep_k=2)
        # Every document 8 + 2 deep: one K = 2 dispatch, slices 8 and 2.
        for d in range(8):
            _feed(eng, d, 10, seqs)
        eng.step()
        assert eng.full_steps == 2
        assert eng.health()["megastep_dispatches"] == 1
        assert _slots(eng) == (8 + 2, 2 * 8)
    else:
        eng = _engine(4, megastep_k=1)
        # One document outgrows its 64 segments and moves to a grow lane;
        # its later ops are dispatched alone, one chunk of at most 8 each.
        _feed(eng, 0, 40, seqs)
        eng.step()
        for i in range(30):
            seqs[0] += 1
            eng.ingest(0, _ins(seqs[0], 2 * i + 1, "ab"))
        eng.step()
        assert 0 in eng.overflow
        before = _slots(eng)
        _feed(eng, 0, 11, seqs)
        eng.step()
        assert _slots(eng) == (before[0] + 8 + 3, before[1] + 2 * 8)
    assert not eng.errors().any()


# ------------------------------------------------- the benchmark's reader
def _status(t, scanned=None, dense=None):
    health = {"cohort_steps": 1}
    if dense is not None:
        health.update(row_slots_scanned=scanned, row_slots_dense=dense)
    return t, {"rows": 0, "health": health}


@pytest.mark.parametrize("lines,want", [
    # The window's first and last lines: (21 - 5) of (320 - 64) row slots.
    ([_status(99.0, 1, 32), _status(100.5, 5, 64), _status(105.0, 9, 160),
      _status(109.5, 21, 320), _status(111.0, 99, 640)], 6.25),
    # Every step filled its slots.
    ([_status(101.0, 32, 32), _status(102.0, 96, 96)], 100.0),
    # A program that counts neither (the parent commit): absent, no error.
    ([_status(101.0), _status(102.0)], None),
    # Nothing dispatched inside the window, one line, no line.
    ([_status(101.0, 5, 64), _status(102.0, 5, 64)], None),
    ([_status(101.0, 5, 64)], None),
    ([], None),
], ids=["window_delta", "dense", "parent", "idle", "one_line", "no_line"])
def test_row_slots_scanned_share_reader(lines, want):
    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    reader = importlib.import_module("layer_metrics.row_slots_scanned_share")
    got = reader.read({"w0": 100.0, "w1": 110.0, "parsed": lines})
    assert got == (pytest.approx(want) if want is not None else None)
