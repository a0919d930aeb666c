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
for: at most two cumulative sums and two rewrites of each per-segment column
a row, no select over the text pool (which the loop does not even carry: one
strip a document, written after the loop and after the obliterate gate, is
the pool's only write, and no scatter touches it), no ``cond``/``switch`` on
a batched predicate.  The strip write itself (``mk._write_text``) is held to
the rows' writes applied one after another.

(c) A row's two boundary cuts, planned from one geometry and opened in one
pass (``mk._ensure_boundaries``), against the one-cut split applied twice,
each time on a geometry of its own, which is what the body did before: the
reference is kept here.
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
        # Every per-segment column is rewritten twice a row: once for both
        # boundary cuts, once for the insert.  (The ``concatenate``s do not
        # tell: a two-slot pass has two a column.)
        assert _shifted_versions(scan, n_docs) == [2] * (6 + 2 * R + 2 * P)
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
    s2 = mk._open_slot(s, k + 1, do, right)
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
    return mk._ensure_boundaries(
        s, mk._geometry(s, REF_SEQ, CLIENT), cut1, gate1, cut2, gate2)


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
        doc = jax.tree.map(lambda *xs: jnp.stack(xs), *docs)
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
