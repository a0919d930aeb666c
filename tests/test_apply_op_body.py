"""The merge-tree op body (``mk.apply_op``): one straight-line program per
row, held to the host oracle and to its own structure.

(a) Row streams recorded from real multi-client farms (every row a replica's
kernel backend applied: remote ops, local pending ops, their acks, sided
obliterates), with illegal rows spliced in (positions out of range, inserts
that overflow the text pool, invalid obliterates), are replayed through the
batched kernel — ``apply_fleet_ops`` slice by slice, and K > 1 through
``apply_megastep`` — and through ``dds/mergetree_ref.RefMergeTree``.  Every
leaf's live content must agree: segment boundaries, text, stamps, remove
sets, props, obliterate records, and the error latch bit for bit.  (The
padding slots hold shift remnants and are held by no independent reference;
(c) compares them for the cuts.)

(b) The jaxpr of the fleet's row loop keeps the shape the body was written
for: at most two cumulative sums and ONE rewrite of each per-segment column
a row, no select over the text pool (which the loop does not even carry: one
strip a document, written after the loop and after the obliterate gate, is
the pool's only write, and no scatter touches it), no ``cond``/``switch`` on
a batched predicate.  The strip write itself (``mk._write_text``) is held to
the rows' writes applied one after another.

(c) A row's two boundary cuts, planned from one geometry and opened in one
pass (``mk._plan_cuts``, ``mk._open_slots``), against the one-cut split
applied twice, each time on a geometry of its own, which is what the body did
before: the reference is kept here.

(d) The row whose insert rides the second slot of that pass, against the row
as it ran before: the cuts, then a geometry of the document they leave, the
boundary walk and the obliterate rule on it, and a second shift of every
column for the insert's slot (``_reference_row``, kept here with its
``_open_slot``).  Every leaf after every row, ``error`` and the padding
included.
"""

import functools
import random

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np
import pytest

from fluidframework_tpu.dds.kernel_backend import (
    KernelMergeTree,
    pull_obliterates,
    pull_segments,
)
from fluidframework_tpu.dds.mergetree_ref import RefMergeTree
from fluidframework_tpu.dds.shared_string import SharedString
from fluidframework_tpu.models import doc_batch_engine as dbe
from fluidframework_tpu.ops import mergetree_kernel as mk
from fluidframework_tpu.ops import pallas_kernels as pk
from fluidframework_tpu.ops.pallas_kernels import text_strip_width
from fluidframework_tpu.server.local_service import LocalDocument

from test_mergetree_oracle import draw_op, issue_op, pump

S, R, P, T, OB, L, B = 192, 4, 4, 512, 16, 8, 8
K = mk.OpKind


class _Recording(KernelMergeTree):
    """A kernel backend that keeps every row it applied.  The collab window
    never moves (no compaction), so the rows alone rebuild the state."""

    def __init__(self):
        super().__init__(max_segments=S, remove_slots=R, prop_slots=P,
                         text_capacity=T, max_insert_len=L, ob_slots=OB)
        self.rows = []

    def _step(self, op, payload=None):
        p = self._empty_payload if payload is None else payload
        self.rows.append((np.array(op, np.int32), np.array(p, np.int32)))
        super()._step(op, payload)

    def update_min_seq(self, min_seq):
        pass


def _farm(seed, kinds):
    """Rows of every replica of one seeded farm that issues only ``kinds``
    (acks and the rows of remote ops come with them)."""
    rng = random.Random(seed)
    doc = LocalDocument("d")
    clients = [SharedString(client_id=f"c{i}", backend=_Recording())
               for i in range(3)]
    for c in clients:
        doc.connect(c.client_id, c.process)
    doc.process_all()
    for _round in range(6):
        for c in clients:
            for _ in range(rng.randint(0, 2)):
                op = draw_op(rng, len(c.text))
                while op[0] not in kinds:
                    op = draw_op(rng, len(c.text))
                issue_op(c, op)
            if rng.random() < 0.7:
                for m in c.take_outbox():
                    doc.submit(m)
        doc.process_some(rng.randint(0, doc.pending_count))
    pump(doc, clients)
    assert len({c.text for c in clients}) == 1
    for c in clients:
        assert c.backend.check_errors() == 0
    return [c.backend.rows for c in clients]


def _row(kind, key, client, ref, pos1=0, pos2=0, a=0, b=0):
    return (np.array([kind, key, client, ref, pos1, pos2, a, b], np.int32),
            np.zeros((L,), np.int32))


def _oracle_with_illegal_rows(rows, rng, flavour):
    """Replay ``rows`` through the host oracle, splicing in rows the stream
    itself never carries.  Returns (the rows to feed the kernel, the oracle,
    the error bits the kernel has to latch).  ``flavour`` 0 splices only rows
    that must latch nothing, 1 only pool overflows, 2 everything."""
    tree = RefMergeTree()
    out, bits, seq = [], 0, 0
    # The text pool's fill, as the kernel counts it (every applied insert).
    pool = 0

    def noise():
        # Fields a kind does not read hold what would trip another kind's
        # check: nothing may latch, nothing may change.
        total = tree.visible_length(seq, 0)
        pick = rng.randrange(4)
        if pick == 0:
            out.append(_row(K.NOOP, seq, 0, seq, total + 9, total + 9, T + 1, 7))
        elif pick == 1:
            out.append(_row(-3, seq, 0, seq, total + 9, 0, T + 1, 0))
        elif pick == 2:
            # An ack of a local seq nobody holds, as large as a text length
            # the pool could not take.
            out.append(_row(K.ACK, 0, -1, -1, total + 9, total + 9, T + 1, seq))
        else:
            # An empty remove still cuts its boundary.
            p = rng.randint(0, total)
            out.append(_row(K.REMOVE, seq, 0, seq, p, p, T + 1, -5))
            tree.apply_remove(p, p, seq, 0, seq)

    def overflow():
        # An insert the pool cannot take: the boundary is still cut.
        nonlocal bits
        pos = rng.randint(0, tree.visible_length(seq, 0))
        out.append(_row(K.INSERT, seq, 0, seq, pos, a=T - pool + 1))
        tree._split_at(pos, seq, 0)
        bits |= mk.ERR_TEXT_OVERFLOW

    def out_of_range():
        nonlocal bits
        total = tree.visible_length(seq, 0)
        pick = rng.randrange(3)
        if pick == 0:
            # Insert past the end: rejected whole.
            out.append(_row(K.INSERT, seq, 0, seq, total + rng.randint(1, 3),
                            a=2))
        elif pick == 1 or total < 2:
            # An obliterate whose end character does not exist.
            out.append(_row(K.OBLITERATE, seq, 0, seq, rng.randint(0, total),
                            total + rng.randint(0, 2),
                            rng.randint(0, 1), rng.randint(0, 1)))
        else:
            # Inverted places on characters that do exist.
            out.append(_row(K.OBLITERATE, seq, 0, seq, 1, 0, 0, 1))
        bits |= mk.ERR_POS_RANGE

    splices = ([noise], [noise, overflow],
               [noise, overflow, out_of_range])[flavour]
    for op, payload in rows:
        if rng.random() < 0.25:
            rng.choice(splices)()
        kind, key, client, ref, pos1, pos2, a, b = (int(v) for v in op)
        if kind == K.INSERT:
            text = "".join(chr(c) for c in payload[:a])
            tree.apply_insert(pos1, text, key, client, ref)
            pool += a
        elif kind == K.REMOVE:
            tree.apply_remove(pos1, pos2, key, client, ref)
        elif kind == K.ANNOTATE:
            tree.apply_annotate(pos1, pos2, a, b, key, client, ref)
        elif kind == K.OBLITERATE:
            tree.apply_obliterate(pos1, a, pos2, b, key, client, ref)
        elif kind == K.ACK:
            tree.ack(a, b, client if client >= 0 else None,
                     ref if ref >= 0 else None)
            seq = max(seq, b)
        if key < mk.LOCAL_BASE and kind != K.ACK:
            seq = max(seq, key)
        out.append((op, payload))
    if flavour == 2:
        # Last row: a remove that runs past the end marks what exists.
        total = tree.visible_length(seq, 0)
        p1 = rng.randint(0, total)
        out.append(_row(K.REMOVE, seq + 1, 0, seq, p1, total + 2))
        if p1 < total:
            tree.apply_remove(p1, total, seq + 1, 0, seq)
        bits |= mk.ERR_POS_RANGE
    return out, tree, bits


def _oracle_content(tree):
    index = {id(s): i for i, s in enumerate(tree.segments)}
    segs = [
        (s.text, s.ins_key, s.ins_client, sorted(s.removes),
         {p: tuple(v) for p, v in s.props.items()},
         -1 if s.ob_preceding is None else s.ob_preceding.key)
        for s in tree.segments
    ]
    obs = [
        (o.key, o.client, index[id(o.start_seg)], o.start_side,
         index[id(o.end_seg)], o.end_side, o.ref_seq)
        for o in tree.obliterates
    ]
    return segs, obs


def _kernel_content(state):
    pulled = pull_segments(state, with_text=True)
    index = {s.uid: i for i, s in enumerate(pulled)}
    segs = [
        (s.text, s.ins_key, s.ins_client, s.removes, s.props, s.obpre)
        for s in pulled
    ]
    obs = [
        (o.key, o.client, index[o.start_uid], o.start_side,
         index[o.end_uid], o.end_side, o.ref_seq)
        for o in pull_obliterates(state)
    ]
    return segs, obs


def _fleet(n_docs):
    proto = mk.init_state(S, R, P, T, OB)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_docs,) + x.shape), proto)




def _run_batched(streams, k):
    """Apply one stream per document, B rows a slice, NOOP-padded, through
    the engine's own step programs: ``_fleet_step`` slice by slice, or ``k``
    slices a dispatch through ``_fleet_megastep`` when k > 1."""
    n_docs = len(streams)
    n_slices = max(-(-len(s) // B) for s in streams)
    n_slices = -(-n_slices // k) * k
    ops = np.zeros((n_slices, n_docs, B, mk.OP_FIELDS), np.int32)
    pays = np.zeros((n_slices, n_docs, B, L), np.int32)
    for d, stream in enumerate(streams):
        for i, (op, payload) in enumerate(stream):
            ops[i // B, d, i % B] = op
            pays[i // B, d, i % B] = payload
    state = _fleet(n_docs)
    if k == 1:
        for i in range(n_slices):
            state = dbe._fleet_step(state, jnp.asarray(ops[i]),
                                    jnp.asarray(pays[i]))
    else:
        for i in range(0, n_slices, k):
            state = dbe._fleet_megastep(state, jnp.asarray(ops[i:i + k]),
                                        jnp.asarray(pays[i:i + k]))
    return state


KIND_SETS = {
    "insert": ("insert",),
    "remove": ("insert", "remove"),
    "annotate": ("insert", "annotate"),
    "obliterate": ("insert", "obliterate", "obliterate_sided"),
    "all": ("insert", "remove", "annotate", "obliterate", "obliterate_sided"),
}


@functools.lru_cache(maxsize=None)
def _case(name):
    """(kernel rows, oracle, expected error bits) per document of a case.
    ``mixed`` lays the replicas of every other case side by side, so each
    scan step runs different kinds on different rows of the batch."""
    if name == "mixed":
        return [doc for other in KIND_SETS for doc in _case(other)]
    seed = 4100 + sorted(KIND_SETS).index(name)
    rng = random.Random(seed)
    return [_oracle_with_illegal_rows(rows, rng, flavour)
            for flavour, rows in enumerate(_farm(seed, KIND_SETS[name]))]


@pytest.mark.parametrize("k", [1, 3], ids=["vmap", "megastep_k3"])
@pytest.mark.parametrize("case", [*KIND_SETS, "mixed"])
def test_op_body_matches_host_oracle(case, k):
    docs = _case(case)
    state = _run_batched([rows for rows, _tree, _bits in docs], k)
    kinds = {int(op[0]) for rows, _t, _b in docs for op, _p in rows}
    assert {K.INSERT, K.ACK} <= kinds
    if case in ("all", "mixed"):
        assert kinds >= {K.NOOP, K.INSERT, K.REMOVE, K.ANNOTATE, K.ACK,
                         K.OBLITERATE}
    assert {bits for _r, _t, bits in docs} >= {
        0, mk.ERR_TEXT_OVERFLOW, mk.ERR_TEXT_OVERFLOW | mk.ERR_POS_RANGE}
    for d, (_rows, tree, bits) in enumerate(docs):
        one = jax.tree.map(lambda x, d=d: np.asarray(x[d]), state)
        assert int(one.error) == bits, (case, d, int(one.error), bits)
        assert _kernel_content(one) == _oracle_content(tree), (case, d)


# ------------------------------------------------- the body's structure
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


def _step_program(flag, n_docs=3):
    """(every equation of ``apply_fleet_ops``, those of its row loop's
    body, the loop itself, documents in the batch).  The loop is a ``while``
    since its trip count became data (``mk.row_count``), and the vmapped row
    one ``jit`` equation (``row``) of its body: tests/test_row_loop_depth.py
    holds both."""
    fleet = _fleet(n_docs)
    ops = jnp.zeros((n_docs, B, mk.OP_FIELDS), jnp.int32)
    pays = jnp.zeros((n_docs, B, L), jnp.int32)
    step = functools.partial(mk.apply_fleet_ops, ob_flag=flag)
    every = list(_eqns(jax.make_jaxpr(step)(fleet, ops, pays).jaxpr))
    # The text write has loops of its own; the row loop is the one whose
    # body holds the staged row.
    loops = [e for e in every if e.primitive.name == "while"
             and any(b.primitive.name in ("jit", "pjit")
                     and b.params["name"] == "row"
                     for b in e.params["body_jaxpr"].jaxpr.eqns)]
    assert len(loops) == 1
    return (every, list(_eqns(loops[0].params["body_jaxpr"].jaxpr)),
            loops[0], n_docs)


def _shifted_versions(loop, n_docs):
    """Per [n_docs, S] column the row loop carries: how many versions of it
    the row body reads shifted along the segment axis (a ``concatenate`` of
    ``slice``s of one array, which is how a slot is opened).  One version is
    one rewrite of the column, however many slots the rewrite opens."""
    (row,) = [e for e in loop.params["body_jaxpr"].jaxpr.eqns
              if e.primitive.name in ("jit", "pjit")
              and e.params["name"] == "row"]
    body = row.params["jaxpr"].jaxpr
    col = (n_docs, S)

    def is_col(v):
        return tuple(v.aval.shape) == col and v.aval.dtype == jnp.int32

    counts = []
    for col_in in body.invars:
        if not is_col(col_in):
            continue
        # Every later value of the column: what a select, or a ``jnp.where``,
        # makes of it, and its shifted self.
        lineage, slice_of, shifted = {col_in}, {}, set()
        for e in body.eqns:
            ins = [v for v in e.invars
                   if isinstance(v, jex_core.Var) and v in lineage]
            name, out = e.primitive.name, e.outvars[0]
            if name == "slice" and ins:
                slice_of[out] = ins[0]
            elif name == "concatenate" and is_col(out):
                sources = {slice_of[v] for v in e.invars if v in slice_of}
                if sources:
                    assert len(sources) == 1, e
                    shifted |= sources
                    lineage.add(out)
            elif ins and is_col(out) and (
                    name == "select_n"
                    or (name in ("jit", "pjit")
                        and e.params["name"] == "_where")):
                lineage.add(out)
        counts.append(len(shifted))
    return counts


@pytest.mark.parametrize("flag", [False, True], ids=["no_ob", "ob"])
@pytest.mark.parametrize(
    "guard", ["cumsums", "slot_passes", "text_pool", "branches"])
def test_vmapped_scan_body_structure(guard, flag):
    every, body, scan, n_docs = _step_program(flag)
    names = [e.primitive.name for e in body]
    if guard == "cumsums":
        # One geometry both splits are planned from and one after them.
        assert names.count("cumsum") <= 2, names.count("cumsum")
        assert names.count("cumsum") >= 1
    elif guard == "slot_passes":
        # Every per-segment column is rewritten once a row, for both
        # boundary cuts and the insert.  (The ``concatenate``s do not tell:
        # a two-slot pass has two a column.)
        assert _shifted_versions(scan, n_docs) == [1] * (6 + 2 * R + 2 * P)
    elif guard == "text_pool":
        pool = (n_docs, T)

        def touches(e):
            return any(tuple(getattr(v.aval, "shape", ())) == pool
                       for v in (*e.invars, *e.outvars))

        # Nothing selects between pools, anywhere in the program ...
        assert not [e for e in every
                    if e.primitive.name == "select_n" and touches(e)]
        # ... the row loop neither carries nor touches the pool ...
        assert not touches(scan)
        assert not [e for e in body if touches(e)]
        # ... and nothing scatters into it (``test_pool_write_is_one_strip``
        # holds what does write it).
        assert not [e for e in every
                    if e.primitive.name.startswith("scatter") and touches(e)]
    else:
        # A switch on the row's kind is a ``cond`` in a jaxpr, and one on a
        # batched predicate would have been turned into selects by vmap: the
        # program has neither to begin with.
        assert "cond" not in [e.primitive.name for e in every]
        assert "switch" not in names


# ------------------------------------------- the two-cut plan of a row
CS, CR, CP, COB = 16, 2, 2, 8       # a small document: S - 1 and S are near
REF_SEQ, CLIENT = 10, 1
INT_MIN, INT_MAX = -2**31, 2**31 - 1


def _open_slot(s, k, do, new):
    """Conditionally (``do``) shift all per-segment arrays right at ``k`` and
    write the new segment's values there: the pass of its own that a cut
    took until (c), and an insert until (d).  Capacity overflow sets error."""
    overflow = do & (s.nseg >= s.seg_len.shape[0])
    do = do & ~overflow
    cols = jax.tree.map(
        lambda arr, newval: jnp.where(do, mk._shift_right(arr, k, newval), arr),
        mk._columns(s), new)
    return s._replace(
        **cols._asdict(),
        nseg=s.nseg + do.astype(jnp.int32),
        error=s.error | jnp.where(overflow, mk.ERR_SEG_OVERFLOW, 0))


def _one_cut_reference(s, geom, pos, gate):
    """``_ensure_boundary`` as the row body ran it until the two cuts were
    planned together (one cut; the caller takes a geometry before each)."""
    vis, vlen, excl = geom
    mid = vis & (excl < pos) & (pos < excl + vlen)
    k = mk._first_true(mid, jnp.asarray(0, jnp.int32))
    do = gate & jnp.any(mid)
    off = pos - excl[k]
    old_uid = s.seg_uid[k]
    right_uid = s.uid_next
    right = mk._NewSeg(
        seg_start=s.seg_start[k] + off,
        seg_len=s.seg_len[k] - off,
        ins_key=s.ins_key[k],
        ins_client=s.ins_client[k],
        seg_uid=right_uid,
        seg_obpre=s.seg_obpre[k],
        rem_keys=tuple(a[k] for a in s.rem_keys),
        rem_clients=tuple(a[k] for a in s.rem_clients),
        prop_keys=tuple(a[k] for a in s.prop_keys),
        prop_vals=tuple(a[k] for a in s.prop_vals),
    )
    s2 = _open_slot(s, k + 1, do, right)
    at_k = jnp.arange(s2.seg_len.shape[0], dtype=jnp.int32) == k
    moved_start = (do & (s2.ob_start_uid == old_uid)
                   & (s2.ob_start_side == mk.SIDE_AFTER))
    moved_end = (do & (s2.ob_end_uid == old_uid)
                 & (s2.ob_end_side == mk.SIDE_AFTER))
    return s2._replace(
        seg_len=jnp.where(do & at_k, off, s2.seg_len),
        uid_next=s2.uid_next + do.astype(jnp.int32),
        ob_start_uid=jnp.where(moved_start, right_uid, s2.ob_start_uid),
        ob_end_uid=jnp.where(moved_end, right_uid, s2.ob_end_uid),
    )


def _cuts_in_turn(s, cut1, gate1, cut2, gate2):
    s = _one_cut_reference(s, mk._geometry(s, REF_SEQ, CLIENT), cut1, gate1)
    return _one_cut_reference(
        s, mk._geometry(s, REF_SEQ, CLIENT), cut2, gate2)


def _cuts_planned(s, cut1, gate1, cut2, gate2):
    cuts = mk._plan_cuts(
        s, mk._geometry(s, REF_SEQ, CLIENT), cut1, gate1, cut2, gate2)
    # No insert: whatever segment it names stays out.
    no_insert = mk._Insert(
        jnp.int32(3), jnp.bool_(False),
        jax.tree.map(lambda arr: arr[0] + 7, mk._columns(s)))
    return mk._open_slots(s, *cuts, no_insert)


_CUT_PROGRAMS = {
    "alone": (jax.jit(_cuts_in_turn), jax.jit(_cuts_planned)),
    "vmap": (jax.jit(jax.vmap(_cuts_in_turn)), jax.jit(jax.vmap(_cuts_planned))),
}

# Segment 4 takes cut 1 (length 8, cut at 3: room on both sides); 1 lies
# before it and 7 after it; 2 and 5 are invisible from (REF_SEQ, CLIENT), by
# a later insert and by an acked remove, so an index is not a position.
_LENS = [3, 6, 4, 2, 8, 5, 1, 7, 3, 2, 4, 3, 5, 2, 6, 4]
_K_BEFORE, _K_CUT1, _K_AFTER, _OFF1 = 1, 4, 7, 3


def _cut_doc(rng, nseg):
    """One document of ``nseg`` live segments (the padding behind them holds
    noise, as shifts leave it) whose obliterate table anchors both sides of
    both kinds on the segments the cuts split."""
    d = {f: [np.array(a) for a in v] if isinstance(v, tuple) else np.array(v)
         for f, v in mk.init_state(CS, CR, CP, 64, COB)._asdict().items()}
    d["nseg"] = np.int32(nseg)
    d["seg_len"] = np.array(_LENS, np.int32)
    d["seg_start"] = (np.cumsum(_LENS) - _LENS).astype(np.int32)
    d["ins_key"] = rng.integers(1, REF_SEQ + 1, CS).astype(np.int32)
    d["ins_client"] = rng.integers(2, 5, CS).astype(np.int32)
    d["ins_key"][2], d["ins_client"][3] = REF_SEQ + 5, CLIENT
    d["ins_key"][3] = mk.LOCAL_BASE + 1          # the client's own pending
    d["seg_uid"] = (100 + rng.permutation(CS)).astype(np.int32)
    d["seg_obpre"] = rng.integers(-1, 9, CS).astype(np.int32)
    d["rem_keys"][0][5], d["rem_clients"][0][5] = 4, 3
    # A remove the perspective has not seen: still visible.
    d["rem_keys"][0][_K_AFTER], d["rem_clients"][0][_K_AFTER] = REF_SEQ + 2, 3
    for p in range(CP):
        d["prop_keys"][p] = rng.integers(-1, 9, CS).astype(np.int32)
        d["prop_vals"][p] = rng.integers(0, 99, CS).astype(np.int32)
    d["uid_next"] = np.int32(200)
    uid = d["seg_uid"]
    anchors = [(_K_CUT1, mk.SIDE_AFTER, _K_CUT1, mk.SIDE_AFTER),
               (_K_CUT1, mk.SIDE_BEFORE, _K_CUT1, mk.SIDE_BEFORE),
               (_K_BEFORE, mk.SIDE_AFTER, _K_CUT1, mk.SIDE_AFTER),
               (_K_CUT1, mk.SIDE_BEFORE, _K_AFTER, mk.SIDE_AFTER),
               (_K_BEFORE, mk.SIDE_BEFORE, _K_AFTER, mk.SIDE_BEFORE)]
    for i, (ks, ss, ke, se) in enumerate(anchors):
        d["ob_key"][i], d["ob_client"][i] = 3 + i, 2
        d["ob_start_uid"][i], d["ob_start_side"][i] = uid[ks], ss
        d["ob_end_uid"][i], d["ob_end_side"][i] = uid[ke], se
    d["error"] = np.int32(rng.choice([0, mk.ERR_POS_RANGE]))
    for f in mk._NewSeg._fields:
        # What the padding holds is carried along bit for bit, too.
        for a in (d[f] if isinstance(d[f], list) else [d[f]]):
            a[nseg:] = rng.integers(-9, 99, CS - nseg)
    return mk.DocState(**{
        f: tuple(v) if isinstance(v, list) else v for f, v in d.items()})


def _visible_excl(doc):
    vis, _vlen, excl = mk._geometry(doc, REF_SEQ, CLIENT)
    return np.asarray(vis), np.asarray(excl)


def _cut2_at(place, doc):
    """Cut 2 of the case ``place``, where cut 1 is ``_OFF1`` into segment
    ``_K_CUT1``."""
    vis, excl = _visible_excl(doc)
    assert vis[[_K_BEFORE, _K_CUT1, _K_AFTER]].all() and not vis[[2, 5]].any()
    total = int((np.asarray(doc.seg_len) * vis).sum())
    cut1 = int(excl[_K_CUT1]) + _OFF1
    return cut1, {
        "before": int(excl[_K_BEFORE]) + 2,
        "left_half": cut1 - 1,
        "equal": cut1,
        "right_half": cut1 + 2,
        "right_half_last_char": cut1 + _LENS[_K_CUT1] - _OFF1 - 1,
        "after": int(excl[_K_AFTER]) + 4,
        "on_a_boundary": int(excl[_K_AFTER]),
        "on_cut1s_segment_end": int(excl[_K_CUT1]) + _LENS[_K_CUT1],
        "zero": 0,
        "negative": -3,
        "at_the_end": total,
        "past_the_end": total + 2,
        "int_max": INT_MAX,
        "int_min": INT_MIN,
    }[place]


CUT2_PLACES = ["before", "left_half", "equal", "right_half",
               "right_half_last_char", "after", "on_a_boundary",
               "on_cut1s_segment_end", "zero", "negative", "at_the_end",
               "past_the_end", "int_max", "int_min"]
# nseg: room for both splits; for one (the second overflows); for none (the
# first overflows, and the second meets the trimmed document).
FILLS = {"room": CS - 3, "last_slot": CS - 1, "full": CS}
GATES = {"both": (True, True), "first_off": (False, True),
         "second_off": (True, False), "none": (False, False)}


def _assert_states_equal(got, want, note):
    for name, g, w in zip(mk.DocState._fields, got, want):
        for i, (ga, wa) in enumerate(zip(*(
                (x if isinstance(x, tuple) else (x,)) for x in (g, w)))):
            assert np.array_equal(np.asarray(ga), np.asarray(wa)), (
                note, name, i, np.asarray(ga), np.asarray(wa))


def _run_cuts(batching, docs, cuts):
    """Both programs over ``docs`` (one, or a batch with a row of ``cuts``
    each): (planned, in turn)."""
    in_turn, planned = _CUT_PROGRAMS[batching]
    args = [jnp.asarray(c) for c in zip(*cuts)]
    if batching == "alone":
        (doc,), args = docs, [a[0] for a in args]
    else:
        doc = _stack(docs)
    return planned(doc, *args), in_turn(doc, *args)


@pytest.mark.parametrize("batching", ["alone", "vmap"])
@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("gates", GATES)
@pytest.mark.parametrize("place", CUT2_PLACES)
def test_two_cuts_planned_equal_two_cuts_in_turn(place, gates, fill, batching):
    rng = np.random.default_rng(CUT2_PLACES.index(place))
    doc = _cut_doc(rng, FILLS[fill])
    cut1, cut2 = _cut2_at(place, doc)
    g1, g2 = GATES[gates]
    docs, cuts = [doc], [(cut1, g1, cut2, g2)]
    if batching == "vmap":
        # Other rows of the batch cut elsewhere, under other gates, in
        # documents of every fill, and in the other order.
        for i, other in enumerate(CUT2_PLACES[:6]):
            d = _cut_doc(rng, list(FILLS.values())[i % 3])
            c1, c2 = _cut2_at(other, d)
            docs.append(d)
            cuts.append((c2, i % 4 != 1, c1, i % 4 != 2))
    got, want = _run_cuts(batching, docs, cuts)
    _assert_states_equal(got, want, (place, gates, fill))
    # The case is the case it says it is.
    first = jax.tree.map(lambda x: np.asarray(x)[0], want) \
        if batching == "vmap" else jax.tree.map(np.asarray, want)
    splits = int(first.nseg) - FILLS[fill]
    inside = ("before", "left_half", "right_half", "right_half_last_char",
              "after") + (() if g1 else ("equal",))
    asked = int(g1) + int(g2 and place in inside)
    if fill == "full" and g1:
        # Cut 1 overflowed: its right half is gone, and cut 2 met what
        # followed it that much lower.
        asked = int(first.uid_next) - 200
        assert asked >= 1
    assert splits == min(asked, CS - FILLS[fill])
    assert int(first.uid_next) == 200 + asked
    assert bool(int(first.error) & mk.ERR_SEG_OVERFLOW) == (asked > splits)


@pytest.mark.parametrize("cut1_place", ["after", "negative", "past_the_end",
                                        "on_a_boundary", "int_max"])
@pytest.mark.parametrize("fill", FILLS)
def test_two_cuts_with_cut1_elsewhere(cut1_place, fill):
    """Cut 2 below cut 1 (the order illegal rows produce), and a cut 1 that
    splits nothing before a cut 2 that does."""
    doc = _cut_doc(np.random.default_rng(7), FILLS[fill])
    cut2, cut1 = _cut2_at(cut1_place, doc)
    got, want = _run_cuts("alone", [doc], [(cut1, True, cut2, True)])
    _assert_states_equal(got, want, (cut1_place, fill))
    assert int(want.uid_next) == 200 + 1 + (cut1_place == "after")


@pytest.mark.parametrize("seed", range(6))
def test_two_cuts_random(seed):
    """Cuts drawn anywhere around a document, every fill and gate."""
    rng = np.random.default_rng(900 + seed)
    docs, cuts = [], []
    for _ in range(64):
        doc = _cut_doc(rng, int(rng.choice([3, 9, CS - 2, CS - 1, CS])))
        vis, _excl = _visible_excl(doc)
        total = int((np.asarray(doc.seg_len) * vis).sum())
        docs.append(doc)
        cuts.append((int(rng.integers(-2, total + 3)), bool(rng.random() < 0.8),
                     int(rng.integers(-2, total + 3)), bool(rng.random() < 0.8)))
    got, want = _run_cuts("vmap", docs, cuts)
    _assert_states_equal(got, want, seed)
    assert len({int(n) for n in np.asarray(want.nseg)}) > 3


# ------------------------------- the insert in the cuts' rewrite of a row
def _reference_row(s, op, payload, flag, text_capacity):
    """``mk._apply_row`` as it ran until the insert's slot joined the cuts'
    rewrite: both cuts (each a one-cut split on a geometry of its own, which
    (c) holds equal to the planned pair), a geometry of the document they
    leave, on which the insert finds its index and its obliterates, every
    kind's writes, and last a shift of every column for the insert's slot."""
    I32 = jnp.int32
    kind, key, client, ref_seq = op[0], op[1], op[2], op[3]
    pos1, pos2, a, b = op[4], op[5], op[6], op[7]
    is_insert, is_remove = kind == K.INSERT, kind == K.REMOVE
    is_annotate, is_ack = kind == K.ANNOTATE, kind == K.ACK
    is_range = is_remove | is_annotate
    is_ob = (kind >= K.OBLITERATE) if flag else False

    geom = mk._geometry(s, ref_seq, client)
    total = jnp.sum(geom[1])
    cut1, cut2 = pos1, pos2
    do_cut1, do_cut2 = is_insert | is_range, is_range
    if flag:
        start_pos, end_pos = pos1 + a, pos2 + b
        valid = ((0 <= pos1) & (pos1 <= pos2) & (pos2 < total)
                 & (start_pos <= end_pos))
        ob_ok = is_ob & valid
        cut1 = jnp.where(is_ob, start_pos, cut1)
        cut2 = jnp.where(is_ob, end_pos, cut2)
        do_cut1, do_cut2 = do_cut1 | ob_ok, do_cut2 | ob_ok
    s = _one_cut_reference(s, geom, cut1, do_cut1)
    s = _one_cut_reference(s, mk._geometry(s, ref_seq, client), cut2, do_cut2)
    vis, vlen, excl = mk._geometry(s, ref_seq, client)
    alive = mk._alive(s)
    in_range = vis & (excl >= pos1) & (excl + vlen <= pos2) & (vlen > 0)
    error = s.error | jnp.where(is_range & (pos2 > total), mk.ERR_POS_RANGE, 0)

    text_len = a
    stop = alive & (excl >= pos1) & ((vlen > 0) | mk._tiebreak(s, key))
    k = mk._first_true(stop, s.nseg)
    text_over = is_insert & (s.text_end + text_len > text_capacity)
    fits = is_insert & ~text_over
    write = mk._TextWrite(
        s.text_end, jnp.where(fits, jnp.clip(text_len, 0, payload.shape[0]), 0))
    new_rem_k, new_rem_c, obpre, swallow_over = (
        mk._obliterate_swallow(
            s, mk._ob_anchor_indices(s), k, key, client, ref_seq)
        if flag else mk._no_obliterate_swallow(s))
    n_props = len(s.prop_keys)
    new = mk._NewSeg(
        seg_start=s.text_end, seg_len=text_len, ins_key=key,
        ins_client=client, seg_uid=s.uid_next, seg_obpre=obpre,
        rem_keys=new_rem_k, rem_clients=new_rem_c,
        prop_keys=tuple(jnp.full((), -1, I32) for _ in range(n_props)),
        prop_vals=tuple(jnp.zeros((), I32) for _ in range(n_props)))
    ok = fits & (pos1 <= total)
    error = (error
             | jnp.where(text_over, mk.ERR_TEXT_OVERFLOW, 0)
             | jnp.where(is_insert & (pos1 > total), mk.ERR_POS_RANGE, 0)
             | jnp.where(ok & swallow_over, mk.ERR_REM_OVERFLOW, 0))

    stamp = in_range & is_remove
    if flag:
        cont_s = vis & (excl <= pos1) & (pos1 < excl + vlen)
        cont_e = vis & (excl <= pos2) & (pos2 < excl + vlen)
        s_idx = mk._first_true(cont_s, s.nseg)
        e_idx = mk._first_true(cont_e, s.nseg)
        lo = s_idx + (a == mk.SIDE_AFTER).astype(I32)
        hi = e_idx - (b == mk.SIDE_BEFORE).astype(I32)
        idx = jnp.arange(s.seg_len.shape[0], dtype=I32)
        visit, skip = mk._obliterate_visit(s, vis, key, client, ref_seq)
        stamp = stamp | (
            ob_ok & alive & (idx >= lo) & (idx <= hi) & visit & ~skip)
        free = s.ob_key < 0
        has_free = jnp.any(free)
        at_slot = (ob_ok & has_free) & (
            jnp.arange(s.ob_key.shape[0], dtype=I32)
            == mk._first_true(free, jnp.asarray(0, I32)))
        put = lambda arr, val: jnp.where(at_slot, val, arr)
        s = s._replace(
            ob_key=put(s.ob_key, key), ob_client=put(s.ob_client, client),
            ob_start_uid=put(s.ob_start_uid, s.seg_uid[s_idx]),
            ob_end_uid=put(s.ob_end_uid, s.seg_uid[e_idx]),
            ob_start_side=put(s.ob_start_side, a),
            ob_end_side=put(s.ob_end_side, b),
            ob_ref_seq=put(s.ob_ref_seq, ref_seq))
        error = (error
                 | jnp.where(is_ob & ~valid, mk.ERR_POS_RANGE, 0)
                 | jnp.where(ob_ok & ~has_free, mk.ERR_OB_OVERFLOW, 0))

    rem_keys, rem_clients, stamp_over = mk._splice_remove_stamp(
        s, stamp, key, client)
    s = s._replace(rem_keys=rem_keys, rem_clients=rem_clients)
    error = error | jnp.where(stamp_over, mk.ERR_REM_OVERFLOW, 0)
    s = mk._annotate_marked(s, in_range & is_annotate, op)
    s = mk._restamp_acked(s, op, is_ack)
    s = _open_slot(s._replace(error=error), k, ok, new)
    return s._replace(
        text_end=s.text_end + jnp.where(ok, text_len, 0),
        uid_next=s.uid_next + ok.astype(I32)), write


CT, CL = 64, 4                      # the small document's pool and payload
OP_KEY = REF_SEQ + 1


@functools.lru_cache(maxsize=None)
def _row_programs(flag):
    """(the row, the reference row) over a batch of documents."""
    return tuple(
        jax.jit(jax.vmap(functools.partial(
            row, flag=flag, text_capacity=CT)))
        for row in (mk._apply_row, _reference_row))


def _stack(docs):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *docs)


def _unstack(state):
    n_docs = state.nseg.shape[0]
    return [jax.tree.map(lambda x, d=d: x[d], state) for d in range(n_docs)]


def _rows_agree(docs, ops, flag, note, between=None):
    """Each document through its ROWS of ``ops`` ([D, rows, 8]), by the row
    and by the reference row, each side carrying its own state (and both
    through ``between`` after every row but the last): every leaf equal after
    every row.  Returns the states after each row."""
    new_row, ref_row = _row_programs(flag)
    got = want = _stack(docs)
    ops = np.asarray(ops, np.int32)
    pays = np.arange(1, 1 + CL, dtype=np.int32) + 10 * np.arange(
        len(docs), dtype=np.int32)[:, None]
    after = []
    for i in range(ops.shape[1]):
        got, got_w = new_row(got, jnp.asarray(ops[:, i]), jnp.asarray(pays))
        want, want_w = ref_row(want, jnp.asarray(ops[:, i]), jnp.asarray(pays))
        _assert_states_equal(got, want, (note, "row", i))
        for g, w in zip(got_w, want_w):
            assert np.array_equal(np.asarray(g), np.asarray(w)), (note, i)
        after.append(jax.tree.map(np.asarray, want))
        if between is not None and i + 1 < ops.shape[1]:
            got, want = between(got), between(want)
            _assert_states_equal(got, want, (note, "between", i))
    return after


# Where an insert lands in ``_cut_doc``'s document, seen from (REF_SEQ,
# CLIENT): segments 1, 4 and 7 are visible and anchor obliterates; 2 and 5
# are not visible (a later insert, an acked remove); 3 is the client's own
# pending insert.
def _insert_at(place, doc):
    vis, excl = _visible_excl(doc)
    total = int((np.asarray(doc.seg_len) * vis).sum())
    return {
        "splits_4": int(excl[_K_CUT1]) + _OFF1,
        "splits_1": int(excl[_K_BEFORE]) + 2,
        "splits_7": int(excl[_K_AFTER]) + 4,
        "splits_7_last_char": int(excl[_K_AFTER]) + _LENS[_K_AFTER] - 1,
        "boundary_before_1": int(excl[_K_BEFORE]),
        "boundary_after_1_before_invisible_2": int(excl[2]),
        "boundary_before_4": int(excl[_K_CUT1]),
        "boundary_after_4_before_removed_5": int(excl[5]),
        "boundary_before_7": int(excl[_K_AFTER]),
        "boundary_after_7": int(excl[_K_AFTER]) + _LENS[_K_AFTER],
        "zero": 0,
        "negative": -2,
        "at_the_end": total,
        "past_the_end": total + 1,
    }[place]


INSERT_PLACES = [
    "splits_4", "splits_1", "splits_7", "splits_7_last_char",
    "boundary_before_1", "boundary_after_1_before_invisible_2",
    "boundary_before_4", "boundary_after_4_before_removed_5",
    "boundary_before_7", "boundary_after_7", "zero", "negative",
    "at_the_end", "past_the_end"]
# nseg: room for the split and the insert; for the split alone (the insert is
# refused); for neither (the split is refused, its left half trimmed all the
# same, and so is the insert).
INSERT_FILLS = {"room": CS - 4, "two_slots": CS - 2, "last_slot": CS - 1,
                "full": CS}
# The obliterates the insert meets: ``_cut_doc``'s five windows (both sides
# of both kinds on segments 1, 4 and 7) under keys the row's perspective has
# seen, has not seen (concurrent: they swallow), has not seen and outnumber
# the R remove slots, or pending at the row's own client.
OB_TABLES = {
    "seen": ([3, 4, 5, 6, 7], [2, 2, 2, 2, 2]),
    "concurrent": ([REF_SEQ + 1, 4, REF_SEQ + 3, 6, 7], [2, 2, 3, 2, 2]),
    "concurrent_over_R": ([REF_SEQ + 1, REF_SEQ + 2, REF_SEQ + 3,
                           REF_SEQ + 4, REF_SEQ + 5], [2, 3, 4, 2, 3]),
    "own_pending": ([mk.LOCAL_BASE + 2, REF_SEQ + 2, 5, mk.LOCAL_BASE + 4, 7],
                    [CLIENT, 2, 2, CLIENT, 3]),
    "others_pending": ([mk.LOCAL_BASE + 2, REF_SEQ + 2, 5, 6, REF_SEQ + 4],
                       [3, 2, 2, 2, CLIENT]),
    # A sixth window, from After segment 4 to After segment 7: where the cut
    # of segment 4 finds no room its start anchor is on no segment, and the
    # R stamps of the two windows around it fit.
    "start_after_the_holder": ([3, 4, 5, REF_SEQ + 1, REF_SEQ + 2, REF_SEQ + 3],
                               [2, 2, 2, 2, 3, 4]),
}


def _with(doc, **fields):
    """``doc`` with whole leaves replaced, or single elements ({index:
    value}) of a leaf."""
    out = {}
    for name, v in fields.items():
        if isinstance(v, dict):
            arr = np.array(getattr(doc, name))
            for i, x in v.items():
                arr[i] = x
            v = arr
        out[name] = v
    return doc._replace(**out)


def _insert_doc(rng, nseg, table=None, text_end=20):
    """``_cut_doc`` with the obliterate table of a gate that is on (``table``
    of ``OB_TABLES``) or off (None: empty)."""
    doc = _cut_doc(rng, nseg)
    ob_key, ob_client = np.full(COB, -1, np.int32), np.full(COB, -1, np.int32)
    if table is not None:
        keys, clients = OB_TABLES[table]
        ob_key[:len(keys)], ob_client[:len(keys)] = keys, clients
    return _with(
        doc, ob_key=ob_key, ob_client=ob_client,
        ob_start_uid={5: doc.seg_uid[_K_CUT1]}, ob_end_uid={5: doc.seg_uid[_K_AFTER]},
        ob_start_side={5: mk.SIDE_AFTER}, ob_end_side={5: mk.SIDE_AFTER},
        text_end=np.int32(text_end), error=np.int32(0))


def _insert_op(pos, key=OP_KEY, client=CLIENT, ref=REF_SEQ, text_len=3):
    return [K.INSERT, key, client, ref, pos, 0, text_len, 0]


@pytest.mark.parametrize("table", [None, *OB_TABLES])
@pytest.mark.parametrize("fill", INSERT_FILLS)
@pytest.mark.parametrize("place", INSERT_PLACES)
def test_insert_in_the_cuts_rewrite_equals_a_slot_of_its_own(place, fill, table):
    """One insert, then a second one at each place around it (so that it
    meets the halves, the new segment and the anchors the first one moved),
    in documents of every fill, under every obliterate table and with the
    gate off."""
    rng = np.random.default_rng(INSERT_PLACES.index(place))
    nseg = INSERT_FILLS[fill]
    docs, ops = [], []
    for second in INSERT_PLACES:
        doc = _insert_doc(rng, nseg, table)
        first_at = _insert_at(place, doc)
        second_at = _insert_at(second, doc)
        # The second row's places are the first document's: past the first
        # insert they lie its length higher.
        second_at += 3 if second_at > first_at else 0
        docs.append(doc)
        ops.append([_insert_op(first_at),
                    _insert_op(second_at, key=OP_KEY + 1, ref=OP_KEY)])
    after = _rows_agree(docs, ops, table is not None, (place, fill, table))
    # The case is the case it says it is: what the first row did.
    first = jax.tree.map(lambda x: x[0], after[0])
    splits = place.startswith("splits")
    in_range = place != "past_the_end"
    want_uids = splits + in_range
    assert int(first.uid_next) == 200 + want_uids
    assert int(first.nseg) == min(nseg + want_uids, CS)
    assert int(first.text_end) == 20 + 3 * in_range
    bits = int(first.error)
    assert bool(bits & mk.ERR_SEG_OVERFLOW) == (nseg + want_uids > CS)
    assert bool(bits & mk.ERR_POS_RANGE) == (not in_range)
    if table == "concurrent_over_R" and place == "splits_4":
        # Inside more windows than there are remove slots, whether or not
        # the segment lands; in a full document the right half is gone, and
        # the After-side anchors that followed it bound no window.
        assert bool(bits & mk.ERR_REM_OVERFLOW) == (fill != "full")
    if table == "concurrent" and place == "splits_4" and fill == "room":
        # Swallowed on arrival, between the halves of the anchor's segment:
        # inside the window that ends After the right half (key REF_SEQ + 3),
        # outside the one that starts After it (key REF_SEQ + 1).
        k = _K_CUT1 + 1
        assert int(first.seg_uid[k]) == 201 and int(first.seg_len[k]) == 3
        assert int(first.rem_keys[0][k]) == REF_SEQ + 3
        assert int(first.rem_keys[1][k]) == mk.NO_REMOVE
        assert int(first.seg_obpre[k]) == REF_SEQ + 3
    if table in (None, "seen"):
        landed = np.asarray(first.seg_uid[:int(first.nseg)]) == 200 + splits
        if landed.any():
            assert int(first.rem_keys[0][np.argmax(landed)]) == mk.NO_REMOVE


def test_walk_behind_a_cut_that_found_no_room():
    """A full document whose prefix sums fall (a negative length, which no
    encoder makes): the cut of segment 4 finds no room, what follows it lies
    the lost half's length lower, and segment 7 is then BELOW the insert's
    position and no stop.  The insert is refused either way; where the walk
    ends decides which windows it would have met, and so the remove-slot
    latch."""
    doc = _insert_doc(np.random.default_rng(5), CS)
    three = slice(0, 3)
    doc = _with(
        doc, seg_len={6: -4},
        # Neither the removed segment 5 nor segment 6 wins the tie-break
        # against key 4.
        ins_key={5: 9, 6: 8}, ins_client={6: 2},
        # Three concurrent windows from Before segment 7 to After segment 9.
        ob_key={three: [REF_SEQ + 1, REF_SEQ + 2, REF_SEQ + 3]},
        ob_client={three: [2, 3, 4]},
        ob_start_uid={three: doc.seg_uid[7]}, ob_end_uid={three: doc.seg_uid[9]},
        ob_start_side={three: mk.SIDE_BEFORE}, ob_end_side={three: mk.SIDE_AFTER})
    vis, excl = _visible_excl(doc)
    assert vis[[4, 6, 7, 8, 9]].all() and not vis[5]
    at = int(excl[_K_CUT1]) + _OFF1
    assert excl[7] >= at > excl[7] - (_LENS[_K_CUT1] - _OFF1)
    (after,) = _rows_agree([doc], [[_insert_op(at, key=4)]], True, "no room")
    # Past segment 7 the walk is inside all three windows: one stamp too many.
    assert int(after.error[0]) == mk.ERR_SEG_OVERFLOW | mk.ERR_REM_OVERFLOW
    assert int(after.nseg[0]) == CS and int(after.uid_next[0]) == 202


TIEBREAK_KEYS = {
    # Against segment 5's acked remove (key 4) and the acked inserts around
    # it: an older key loses the insert clause and wins by the remove.
    "older_than_the_remove": 2,
    "the_removes_own_key": 4,
    "newer": OP_KEY,
    "same_key_as_a_neighbour": None,       # drawn from the document
    "pending": mk.LOCAL_BASE + 7,
}


@pytest.mark.parametrize("key", TIEBREAK_KEYS)
@pytest.mark.parametrize("gate", [False, True], ids=["no_ob", "ob"])
def test_insert_tiebreak_reads_the_document_before_the_cut(key, gate):
    """The boundary walk against segments that are not visible: removed
    ones, later inserts, the same key (a grouped batch), from an acked and a
    pending op, at every boundary and split of the document."""
    rng = np.random.default_rng(len(key))
    docs, ops = [], []
    for place in INSERT_PLACES:
        doc = _insert_doc(rng, CS - 4, "concurrent" if gate else None)
        op_key = TIEBREAK_KEYS[key]
        if op_key is None:
            op_key = int(doc.ins_key[5])
        # Another client's op, so that segment 3 (this client's pending
        # insert) is no longer visible either.
        docs.append(doc)
        ops.append([_insert_op(_insert_at(place, doc), key=op_key, client=4),
                    _insert_op(_insert_at(place, doc), key=op_key, client=4)])
    after = _rows_agree(docs, ops, gate, (key, gate))
    landed = [int(np.argmax(np.asarray(after[0].seg_uid[d]) >= 200))
              for d in range(len(docs))]
    assert len(set(landed)) > 5


@pytest.mark.parametrize("gate", [False, True], ids=["no_ob", "ob"])
@pytest.mark.parametrize("fill", INSERT_FILLS)
def test_insert_that_overflows_the_pool_still_cuts(fill, gate):
    """``text_over``: the boundary is cut, a uid spent on the right half,
    nothing lands and the pool's end stays.  Lengths no encoder makes land
    the same segment in both bodies: one the payload cannot hold, and a
    negative one, after which the second row walks prefix sums that fall."""
    rng = np.random.default_rng(3)
    docs, ops = [], []
    for text_len, text_end in [(5, CT - 4), (CT + 1, 0), (1, CT), (0, CT),
                               (-1, CT - 2), (CL + 3, 10)]:
        for place in ("splits_4", "boundary_before_7"):
            doc = _insert_doc(rng, INSERT_FILLS[fill],
                              "concurrent" if gate else None, text_end)
            docs.append(doc)
            ops.append([_insert_op(_insert_at(place, doc), text_len=text_len),
                        _insert_op(_insert_at(place, doc), key=OP_KEY + 1,
                                   ref=OP_KEY)])
    after = _rows_agree(docs, ops, gate, fill)
    bits = np.asarray(after[0].error)
    assert (bits[:6] & mk.ERR_TEXT_OVERFLOW).all()
    assert not (bits[6:] & mk.ERR_TEXT_OVERFLOW).any()
    assert (np.asarray(after[0].text_end)[:2] == CT - 4).all()


def _compact_to(min_seq, flag):
    """A summary ack's zamboni over a batch of documents."""
    return jax.jit(jax.vmap(
        lambda s: mk.compact(mk.set_min_seq(s, min_seq), flag)))


@pytest.mark.parametrize("min_seq", [REF_SEQ, OP_KEY + 1, OP_KEY + 4])
@pytest.mark.parametrize("fill", ["room", "two_slots", "full"])
@pytest.mark.parametrize("table", ["concurrent", "concurrent_over_R",
                                   "others_pending"])
def test_inserts_at_an_obliterates_edge_across_a_compaction(table, fill, min_seq):
    """The acks cell's sequence, which nothing held before: an insert at an
    obliterate's edge, a summary ack's ``set_min_seq`` + ``compact`` (records
    expire, the gate may close, evicted segments move every index), and a
    second insert at each edge of the same document, ``error`` bit for bit.
    The gate stays on in both bodies, as a fleet's does while any document
    holds a record."""
    rng = np.random.default_rng(min_seq)
    docs, ops = [], []
    for first in ("splits_4", "boundary_before_4", "splits_7",
                  "boundary_after_1_before_invisible_2"):
        for second in ("splits_1", "boundary_before_4", "splits_4",
                       "boundary_after_4_before_removed_5", "splits_7",
                       "boundary_after_7"):
            doc = _insert_doc(rng, INSERT_FILLS[fill], table)
            first_at, second_at = (_insert_at(p, doc) for p in (first, second))
            second_at += 3 if second_at > first_at else 0
            docs.append(doc)
            ops.append([
                _insert_op(first_at, client=4),
                _insert_op(second_at, key=OP_KEY + 5, client=4, ref=OP_KEY),
                _insert_op(second_at + 1, key=OP_KEY + 6, client=2,
                           ref=OP_KEY + 5)])
    after = _rows_agree(docs, ops, True, (table, fill, min_seq),
                        between=_compact_to(min_seq, True))
    # The acked records at or under the floor expired.
    left = sum(k >= mk.LOCAL_BASE or k > min_seq for k in OB_TABLES[table][0])
    assert ((np.asarray(after[-1].ob_key) >= 0).sum(axis=1) == left).all()


def _random_rows(rng, docs, flag, step):
    """One row a document, of every kind, at positions around its visible
    length from the row's own perspective."""
    ops = np.zeros((len(docs), 8), np.int32)
    seq = OP_KEY + step
    for d, doc in enumerate(docs):
        u = rng.random()
        kind = (K.INSERT if u < 0.5 else K.REMOVE if u < 0.62
                else K.ANNOTATE if u < 0.7 else K.ACK if u < 0.78
                else (K.OBLITERATE if flag else K.REMOVE) if u < 0.95
                else K.NOOP)
        client, ref = int(rng.integers(0, 4)), int(seq - rng.integers(0, 4))
        key = (int(mk.LOCAL_BASE + rng.integers(1, 4))
               if rng.random() < 0.2 else seq)
        _vis, vlen, _excl = mk._geometry(doc, ref, client)
        total = int(np.asarray(vlen).sum())
        p1 = int(rng.integers(-1, total + 2))
        p2 = int(rng.integers(p1, total + 2))
        a = b = 0
        if kind == K.INSERT:
            a = int(rng.integers(0, CL + 1)) if rng.random() < 0.95 else CT
        elif kind == K.ANNOTATE:
            a, b = int(rng.integers(0, CP)), int(rng.integers(0, 50))
        elif kind == K.ACK:
            a, b = int(rng.integers(1, 4)), seq
            client = client if rng.random() < 0.5 else -1
            ref = ref if rng.random() < 0.5 else -1
        elif kind == K.OBLITERATE:
            a, b = int(rng.integers(0, 2)), int(rng.integers(0, 2))
            if total > 0 and rng.random() < 0.9:
                p1 = int(rng.integers(0, total))
                p2 = int(rng.integers(p1, total))
        ops[d] = [kind, key, client, ref, p1, p2, a, b]
    return ops


@pytest.mark.parametrize("gate", [False, True], ids=["no_ob", "ob"])
@pytest.mark.parametrize("seed", range(4))
def test_rows_of_every_kind_equal_the_reference_row(seed, gate):
    """Streams of rows of every kind over documents that fill up, overflow
    their remove slots, their obliterate table and their pool, with a
    compaction now and then: every leaf after every row."""
    rng = np.random.default_rng(7000 + seed)
    new_row, ref_row = _row_programs(gate)
    n_docs = 24
    state = _stack([
        _insert_doc(rng, int(rng.choice([3, 9, CS - 2, CS - 1, CS])),
                    rng.choice(list(OB_TABLES)) if gate else None)
        for _ in range(n_docs)])
    seen = set()
    for step in range(40):
        ops = jnp.asarray(_random_rows(rng, _unstack(state), gate, step))
        pays = jnp.asarray(rng.integers(1, 99, (n_docs, CL)).astype(np.int32))
        got, got_w = new_row(state, ops, pays)
        want, want_w = ref_row(state, ops, pays)
        _assert_states_equal(got, want, (seed, gate, step))
        assert all(np.array_equal(np.asarray(g), np.asarray(w))
                   for g, w in zip(got_w, want_w))
        seen |= set(np.asarray(want.error).tolist())
        # The latch is cleared on most rows, so that later rows latch anew.
        state = want._replace(error=jnp.zeros_like(want.error)) \
            if step % 3 else want
        if step % 8 == 7:
            state = _compact_to(OP_KEY + step - 2, gate)(state)
    assert any(b & mk.ERR_SEG_OVERFLOW for b in seen)
    assert any(b & mk.ERR_POS_RANGE for b in seen)


def _drive(program, state, ops, pays):
    """``ops`` [n, D, B, 8] through ``program`` of ``_STEP_PROGRAMS``."""
    n_docs = ops.shape[1]
    if program == "fleet":
        step = jax.jit(mk.apply_fleet_ops)
        for o, p in zip(ops, pays):
            state = step(state, o, p)
    elif program == "megastep_k2":
        step = jax.jit(mk.apply_megastep)
        for i in range(0, ops.shape[0], 2):
            state = step(state, ops[i:i + 2], pays[i:i + 2])
    else:
        # A cohort of every other document of a fleet twice as large, its
        # pool left in the fleet's.
        rows = jnp.arange(n_docs, dtype=jnp.int32) * 2
        pool = jnp.zeros((2 * n_docs, CT), jnp.int32).at[rows].set(state.text)
        sub = state._replace(text=jnp.zeros((n_docs, 0), jnp.int32))
        step = jax.jit(mk.apply_cohort_ops)
        for o, p in zip(ops, pays):
            pool, sub = step(pool, sub, rows, o, p)
        state = sub._replace(text=pool[rows])
    return state


@pytest.mark.parametrize("program", ["fleet", "megastep_k2", "cohort"])
def test_step_programs_equal_the_reference_row(program, monkeypatch):
    """``apply_fleet_ops``, ``apply_megastep`` (K = 2) and
    ``apply_cohort_ops`` over slices of rows of every kind (the gate opens
    with the first obliterate), against the same programs with the reference
    row for their body."""
    rng = np.random.default_rng(81)
    n_docs, n_slices, depth = 8, 4, 4
    state = _stack([
        _insert_doc(rng, fill, None) for fill in (1, 2, 3, 5, 9, CS - 2, CS, 4)])
    ops = np.zeros((n_slices, n_docs, depth, 8), np.int32)
    step_state = state
    row = _row_programs(True)[1]
    for i in range(n_slices):
        for j in range(depth):
            ops[i, :, j] = _random_rows(
                rng, _unstack(step_state), i > 0, i * depth + j)
            step_state, _w = row(step_state, jnp.asarray(ops[i, :, j]),
                                 jnp.zeros((n_docs, CL), jnp.int32))
    pays = rng.integers(1, 99, (n_slices, n_docs, depth, CL)).astype(np.int32)
    ops, pays = jnp.asarray(ops), jnp.asarray(pays)
    assert (ops[..., 0] == K.OBLITERATE).any()
    got = _drive(program, state, ops, pays)
    monkeypatch.setattr(mk, "_apply_row", _reference_row)
    want = _drive(program, state, ops, pays)
    _assert_states_equal(got, want, program)
    assert len({int(n) for n in np.asarray(want.nseg)}) > 2
    assert len({int(e) for e in np.asarray(want.error)}) > 2


# ------------------------------------------------ the pool's strip write
def _writes_in_order(text, starts, counts, payloads):
    """The reference: every document's rows write one after another; what
    falls past the pool's end is dropped."""
    want = np.array(text)
    cap = want.shape[1]
    for d in range(want.shape[0]):
        for st, c, row in zip(starts[d], counts[d], payloads[d]):
            for j in range(int(c)):
                if 0 <= st + j < cap:
                    want[d, st + j] = row[j]
    return want


def _chain(rng, n, width, cap, t0, commit=0.6, empty=0.3, jump=None):
    """Starts and counts of one document's batch, the way ``_apply_row``
    makes them: a row writes ``count`` elements at ``text_end`` (0 where the
    insert does not fit) and a committed row moves ``text_end`` on by its
    ``text_len``: ``jump(i)`` where the case gives one (a hand-made row whose
    ``text_len`` exceeds L), else its count.  A rejected row leaves
    ``text_end`` where it was: the next row writes over it."""
    starts, counts, at = [], [], t0
    for i in range(n):
        c = 0 if rng.random() < empty else int(rng.integers(1, width + 1))
        text_len = c if jump is None or c == 0 else max(c, jump(i))
        if at + text_len > cap:
            c = text_len = 0
        starts.append(at)
        counts.append(c)
        if rng.random() < commit:
            at += text_len
    return starts, counts


def _pool_case(case, rng, n_docs):
    """(rows, width, cap, starts [D, n], counts [D, n]) of a named case."""
    n, width, cap = 24, 8, 1024
    chain = functools.partial(_chain, rng, n, width, cap)
    if case == "random":
        docs = [chain(int(rng.integers(0, cap))) for _ in range(n_docs)]
    elif case == "clamped_at_pool_end":
        # t0 > T - B * L: the strip cannot start at the first write.
        docs = [chain(cap - int(rng.integers(1, n * width))) for _ in range(n_docs)]
    elif case == "window_wider_than_pool":
        n, width, cap = 24, 8, 96                      # B * L >= T
        docs = [_chain(rng, n, width, cap, int(rng.integers(0, cap)))
                for _ in range(n_docs)]
    elif case == "rejected_then_overwritten":
        docs = [chain(int(rng.integers(0, cap // 2)), commit=0.0, empty=0.0)
                for _ in range(n_docs)]
    elif case == "all_counts_zero":
        docs = [chain(int(rng.integers(0, cap)), empty=1.0) for _ in range(n_docs)]
    elif case == "text_len_over_L_inside_the_strip":
        docs = [chain(int(rng.integers(0, cap // 2)), commit=1.0,
                      jump=lambda i: width + 2 if i == 1 else 0)
                for _ in range(n_docs)]
    elif case == "text_len_over_L_past_the_strip":
        # Starts that jump by more than they wrote, and further than any
        # strip reaches: three times in one batch.
        cap = 4096
        docs = [_chain(rng, n, width, cap, int(rng.integers(0, 64)), commit=1.0,
                       empty=0.1, jump=lambda i: 700 if i in (2, 9, 17) else 0)
                for _ in range(n_docs)]
    else:
        assert case == "every_document_elsewhere"
        ends = rng.permutation(cap - n * width)[:n_docs]
        docs = [chain(int(t0)) for t0 in ends]
    starts, counts = (np.array(x, np.int32) for x in zip(*docs))
    return n, width, cap, starts, counts


@pytest.mark.parametrize("n_docs", [1, 5, 8, 21],
                         ids=["one_doc", "under_a_tile_row", "one_tile_row",
                              "tile_rows_and_a_tail"])
@pytest.mark.parametrize("case", [
    "random", "clamped_at_pool_end", "window_wider_than_pool",
    "rejected_then_overwritten", "all_counts_zero",
    "text_len_over_L_inside_the_strip", "text_len_over_L_past_the_strip",
    "every_document_elsewhere"])
def test_text_write_equals_the_writes_in_order(case, n_docs):
    """``_write_text`` against the rows' writes applied one after another,
    padding included: overlapping starts, empty rows and batches, strips
    clamped at the pool's end or as wide as the pool, starts beyond the
    strip, and every document at a ``text_end`` of its own (whole tile rows
    through the kernel, the rest by plain updates)."""
    rng = np.random.default_rng([len(case), n_docs])
    n, width, cap, starts, counts = _pool_case(case, rng, n_docs)
    payloads = rng.integers(1, 1000, (n_docs, n, width)).astype(np.int32)
    text = -rng.integers(1, 1000, (n_docs, cap)).astype(np.int32)
    want = _writes_in_order(text, starts, counts, payloads)
    write = functools.partial(mk._write_text, write=pk.write_text_strips)
    got = jax.jit(write)(
        jnp.asarray(text),
        mk._TextWrite(jnp.asarray(starts), jnp.asarray(counts)),
        jnp.asarray(payloads))
    assert np.array_equal(np.asarray(got), want)
    # The case is the case it says it is.
    live = counts > 0
    if case == "all_counts_zero":
        assert not live.any() and np.array_equal(want, text)
    else:
        assert live.any(axis=1).all() and not np.array_equal(want, text)
    first = np.where(live.any(axis=1),
                     np.where(live, starts, cap).min(axis=1), 0)
    reach = np.where(live, starts + counts, 0).max(axis=1) - first
    if case == "clamped_at_pool_end":
        assert (first > cap - n * width).all()
    if case == "text_len_over_L_past_the_strip":
        assert (reach > n * width + 256).all()
    elif case == "text_len_over_L_inside_the_strip":
        assert (np.diff(starts, axis=1) > width).any()
        assert (reach <= n * width).all()
    if case == "rejected_then_overwritten":
        assert (starts == starts[:, :1]).all()
    if case == "every_document_elsewhere":
        assert len(set(starts[:, 0].tolist())) == n_docs


def _pool_writers(n_docs):
    """The equations of ``apply_fleet_ops`` that give out a pool, loops and
    staged calls that merely pass it on left out."""
    every = _step_program(True, n_docs)[0]
    pool = (n_docs, T)
    is_pool = lambda v: tuple(getattr(v.aval, "shape", ())) == pool
    return every, is_pool, [
        e for e in every if any(is_pool(v) for v in e.outvars)
        and e.primitive.name not in ("while", "scan", "cond", "jit", "pjit")]


@pytest.mark.parametrize("n_docs", [3, 16, 21])
def test_pool_write_is_one_strip_a_document(n_docs):
    """The pool is written once a slice and by strips alone: whole tile rows
    of eight documents by ONE kernel call that takes the pool in and gives
    it out in place, each document after them by a
    ``dynamic_update_slice``; what goes in beside the pool is a strip a
    document, B * L elements rounded up to whole lanes plus one lane more
    (or the whole row where that is narrower), and nothing scatters into
    the pool, reshapes it or selects over it (the per-element scatter this
    replaces fails every one of these)."""
    every, is_pool, writers = _pool_writers(n_docs)
    strip = text_strip_width(T, B * L)
    assert strip == -(-B * L // 128) * 128 + 128 < T
    names = [e.primitive.name for e in writers]
    # (The documents after the last whole tile row share one update, in a
    # loop over them; with none the loop is empty.)
    assert names == (["pallas_call"] * (n_docs >= 8)
                     + ["dynamic_update_slice"]), names
    for e in writers:
        (operand,) = [v for v in e.invars if is_pool(v)]
        assert e.outvars[0].aval.shape == operand.aval.shape
        beside = [v.aval.shape for v in e.invars
                  if getattr(v.aval, "shape", ()) and not is_pool(v)]
        if e.primitive.name == "pallas_call":
            assert dict(e.params["input_output_aliases"]) == {
                e.invars.index(operand): 0}
            assert sorted(beside) == sorted(
                [(n_docs,), (n_docs, strip), (n_docs, strip)])
        else:
            assert beside == [(1, strip)]
    for e in every:
        if any(is_pool(v) for v in e.invars):
            assert not e.primitive.name.startswith("scatter"), e
            assert e.primitive.name not in ("reshape", "select_n", "gather"), e


def test_apply_op_traced_flag_is_one_scalar_cond():
    """Unbatched callers (host lanes, ``dds/kernel_backend``) pass no flag:
    the obliterate gate is then one ``lax.cond`` on a scalar around the
    body, never a switch on the kind."""
    proto = mk.init_state(16, 2, 2, 64, 2)
    op = jnp.zeros((mk.OP_FIELDS,), jnp.int32)
    jaxpr = jax.make_jaxpr(mk.apply_op)(proto, op, jnp.zeros((4,), jnp.int32))
    conds = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert len(conds[0].params["branches"]) == 2
    assert conds[0].invars[0].aval.shape == ()
