"""Columnar merge-tree kernel: sequenced-op application as tensor ops.

This is the TPU-native replacement for the reference's merge-tree apply path
(merge-tree/src/client.ts Client.applyMsg -> mergeTree.ts insertSegments /
markRangeRemoved / annotateRange + blockUpdatePathLengths).  The reference
maintains a B-tree of segments with per-block PartialSequenceLengths so CPU
position resolution is O(log n); here the segment store is a flat SoA of
int32 arrays and every position query is a perspective-masked prefix sum —
O(S) work but fully data-parallel on the VPU, and `vmap`-able over a
document axis so one device step applies ops for thousands of docs.

Semantics are bit-identical to ``dds/mergetree_ref.py`` (the oracle), which
itself mirrors the reference:

- visibility = hasOccurred(insert) && !any(hasOccurred(remove_r))
- insert boundary tie-break = reference breakTie (mergeTree.ts:1811)
- overlapping removes kept in R slots per segment (reference seg.removes)
- annotate per-(segment, prop) LWW by stamp key
- ack rewrites pending stamp keys (localSeq -> seq) in place

Design notes (TPU):

- All state is int32, and every per-segment array is 1-D over the segment
  axis ([S], so [D, S] after vmap).  The R remove slots and P prop slots are
  tuples of such arrays rather than [S,R]/[R,S] matrices: trailing dims of
  2-8 get lane-padded to 128 on TPU (16-64x physical blowup), and XLA's
  layout assignment can pick the small axis as minor even for [R,S].  Tuples
  of 2-D-after-vmap leaves make every layout trivially optimal.
- Within one document, ops are inherently sequential (each op's position
  depends on prior ops); a `lax.fori_loop` applies an op batch row slot by
  row slot and ends at the deepest queue of the batch (``row_count``: the
  trip count is data), not at the batch's width.  The document axis supplies
  the parallelism: the row is `vmap`ped INSIDE the loop (``apply_fleet_ops``;
  one trip count for every document), the fleet sharded by `shard_map`.
- Mutation = masked gather/select: inserting a segment shifts the suffix of
  every per-segment array by one slot (a vectorized O(S) move, not a
  data-dependent loop).
- The text pool is append-only and no row reads it, so the row loop does not
  carry it: the rows hand out (start, count) pairs, and after the loop each
  document's writes go in as ONE strip around its ``text_end``, read, merged
  and written back in place (``_write_text``; a Pallas kernel over whole tile
  rows of documents, ops/pallas_kernels.py).  The write costs D strips of
  B * L elements, not the pool: a scatter into [D, T] has XLA relay the whole
  pool out to one axis and back.  A cohort (a few busy documents of a fleet)
  is stepped without its pool rows: ``apply_cohort_ops`` takes the fleet's
  pool beside the cohort's other leaves and writes the strips at their rows.
- Capacity overflow (segments, text pool, remove slots) sets an error bit
  instead of trapping; the host inspects error flags and reacts (grow +
  re-replay, or route the doc to the host oracle).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..protocol.stamps import ALL_ACKED, LOCAL_BASE, NO_REMOVE

I32 = jnp.int32

# Error flag bits.
ERR_SEG_OVERFLOW = 1
ERR_TEXT_OVERFLOW = 2
ERR_REM_OVERFLOW = 4
ERR_POS_RANGE = 8
ERR_OB_OVERFLOW = 16

# Error lanes (host recovery policy dispatch): capacity bits are recoverable
# by growing the implicated axis and replaying; anything else (today only
# ERR_POS_RANGE alone) means the op stream itself is malformed — growing
# cannot fix it, the document must leave the device batch (quarantine).
ERR_CAPACITY_MASK = (
    ERR_SEG_OVERFLOW | ERR_TEXT_OVERFLOW | ERR_REM_OVERFLOW | ERR_OB_OVERFLOW
)


def is_capacity_error(bits: int) -> bool:
    """True iff the latched bits are recoverable by growth + replay.
    ERR_POS_RANGE *alongside* a capacity bit is usually a cascade (an op
    referencing content a capacity overflow dropped), which replay at
    grown capacity resolves — so any capacity bit keeps the doc on the
    grow lane."""
    return bits != 0 and (bits & ERR_CAPACITY_MASK) != 0


def is_poison_error(bits: int) -> bool:
    """True iff the bits indicate a malformed op stream (quarantine lane)."""
    return bits != 0 and (bits & ERR_CAPACITY_MASK) == 0

# Obliterate endpoint sides (ref sequencePlace.ts Side; mergetree_ref.py).
SIDE_BEFORE = 0
SIDE_AFTER = 1


class OpKind:
    NOOP = 0
    INSERT = 1
    REMOVE = 2
    ANNOTATE = 3
    ACK = 4
    OBLITERATE = 5  # always sided: plain {pos1,pos2} encodes as (pos1,B)..(pos2-1,A)


# Op row layout (int32[OP_FIELDS]):
#   0 kind | 1 key | 2 client | 3 ref_seq | 4 pos1 | 5 pos2 | 6 a | 7 b
# a/b meaning per kind: INSERT a=text_len, REMOVE -, ANNOTATE a=prop_slot
# b=value, ACK a=local_seq b=seq, OBLITERATE a=side1 b=side2 (pos1/pos2 are
# the endpoint CHARACTER positions, already in sided form).
OP_FIELDS = 8


class DocState(NamedTuple):
    """SoA replica state for one document (or [D, ...] for a doc batch)."""

    text: jnp.ndarray         # int32[T] codepoint pool (append-only)
    text_end: jnp.ndarray     # int32 scalar
    nseg: jnp.ndarray         # int32 scalar: live segment count
    seg_start: jnp.ndarray    # int32[S] offset into text pool
    seg_len: jnp.ndarray      # int32[S]
    ins_key: jnp.ndarray      # int32[S] insert stamp key
    ins_client: jnp.ndarray   # int32[S] insert short client id
    seg_uid: jnp.ndarray      # int32[S] stable identity (obliterate anchors)
    seg_obpre: jnp.ndarray    # int32[S] newest concurrent ob key at insert (-1)
    rem_keys: tuple           # R x int32[S] remove stamp keys (NO_REMOVE empty)
    rem_clients: tuple        # R x int32[S]
    prop_keys: tuple          # P x int32[S] LWW stamp key per prop (-1 unset)
    prop_vals: tuple          # P x int32[S]
    uid_next: jnp.ndarray     # int32 scalar
    # Obliterate window table (ref MergeTree.obliterates): OB slots, key=-1
    # free.  Anchors reference segments by uid; sides follow mergetree_ref.
    ob_key: jnp.ndarray       # int32[OB]
    ob_client: jnp.ndarray    # int32[OB]
    ob_start_uid: jnp.ndarray  # int32[OB]
    ob_end_uid: jnp.ndarray    # int32[OB]
    ob_start_side: jnp.ndarray  # int32[OB]
    ob_end_side: jnp.ndarray    # int32[OB]
    ob_ref_seq: jnp.ndarray     # int32[OB] refSeq the obliterate was issued at
    min_seq: jnp.ndarray      # int32 scalar (collab-window floor)
    error: jnp.ndarray        # int32 scalar bitmask


def init_state(
    max_segments: int = 512,
    remove_slots: int = 4,
    prop_slots: int = 4,
    text_capacity: int = 8192,
    ob_slots: int = 8,
) -> DocState:
    S, R, P, T, OB = max_segments, remove_slots, prop_slots, text_capacity, ob_slots
    return DocState(
        text=jnp.zeros((T,), I32),
        text_end=jnp.zeros((), I32),
        nseg=jnp.zeros((), I32),
        seg_start=jnp.zeros((S,), I32),
        seg_len=jnp.zeros((S,), I32),
        ins_key=jnp.zeros((S,), I32),
        ins_client=jnp.full((S,), -1, I32),
        seg_uid=jnp.full((S,), -1, I32),
        seg_obpre=jnp.full((S,), -1, I32),
        rem_keys=tuple(jnp.full((S,), NO_REMOVE, I32) for _ in range(R)),
        rem_clients=tuple(jnp.full((S,), -1, I32) for _ in range(R)),
        prop_keys=tuple(jnp.full((S,), -1, I32) for _ in range(P)),
        prop_vals=tuple(jnp.zeros((S,), I32) for _ in range(P)),
        uid_next=jnp.zeros((), I32),
        ob_key=jnp.full((OB,), -1, I32),
        ob_client=jnp.full((OB,), -1, I32),
        ob_start_uid=jnp.full((OB,), -1, I32),
        ob_end_uid=jnp.full((OB,), -1, I32),
        ob_start_side=jnp.zeros((OB,), I32),
        ob_end_side=jnp.zeros((OB,), I32),
        ob_ref_seq=jnp.full((OB,), -1, I32),
        min_seq=jnp.zeros((), I32),
        error=jnp.zeros((), I32),
    )


def make_noop(op_fields: int = OP_FIELDS) -> np.ndarray:
    return np.zeros((op_fields,), np.int32)


def encode_insert(
    pos: int,
    text: str,
    op_key: int,
    op_client: int,
    ref_seq: int,
    max_insert_len: int,
) -> list[tuple[np.ndarray, np.ndarray]]:
    """Encode one insert as (op_row, payload) pairs, chunking long text.

    Chunks share the op's stamp and are emitted BACK-TO-FRONT, all at
    ``pos``: with the >=-tiebreak each later-emitted chunk lands immediately
    before the previously placed one, whether that one is alive or was
    swallowed by a concurrent obliterate — so the final order is the
    original text order, equivalent to the reference's single unbounded
    segment.  This is THE insert encoding; every ingest path must use it so
    chunk placement can never diverge between host adapters.
    """
    out: list[tuple[np.ndarray, np.ndarray]] = []
    for i in reversed(range(0, len(text), max_insert_len)):
        chunk = text[i : i + max_insert_len]
        payload = np.zeros((max_insert_len,), np.int32)
        payload[: len(chunk)] = [ord(ch) for ch in chunk]
        op = np.array(
            [OpKind.INSERT, op_key, op_client, ref_seq, pos, 0, len(chunk), 0],
            np.int32,
        )
        out.append((op, payload))
    return out


def encode_obliterate(
    pos1: int,
    side1: int,
    pos2: int,
    side2: int,
    op_key: int,
    op_client: int,
    ref_seq: int,
) -> np.ndarray:
    """Encode a sided obliterate op row.  The plain wire form {pos1, pos2}
    encodes as ``encode_obliterate(pos1, SIDE_BEFORE, pos2-1, SIDE_AFTER)``."""
    return np.array(
        [OpKind.OBLITERATE, op_key, op_client, ref_seq, pos1, pos2, side1, side2],
        np.int32,
    )


def encode_insert_batch(
    pos: np.ndarray,
    texts: list[str],
    op_keys: np.ndarray,
    op_clients: np.ndarray,
    ref_seqs: np.ndarray,
    max_insert_len: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized ``encode_insert`` over N wire inserts at once.

    Returns ``(ops[M, OP_FIELDS], payloads[M, L], owner[M])`` where M is
    the total chunk-row count and ``owner[i]`` is the input index each row
    came from.  Row-for-row identical to mapping ``encode_insert`` over
    the inputs — including the back-to-front chunk emission order for
    texts longer than one payload row (see ``encode_insert``: this IS the
    insert encoding; chunk placement must never diverge between paths) —
    but the whole batch costs two array builds and one codepoint scatter
    instead of per-op numpy allocations and per-char Python loops.
    """
    n = len(texts)
    L = max_insert_len
    lens = np.fromiter((len(t) for t in texts), np.int64, n)
    nchunks = -(-lens // L)  # empty text -> 0 rows, matching encode_insert
    m = int(nchunks.sum())
    ops = np.zeros((m, OP_FIELDS), np.int32)
    payloads = np.zeros((m, L), np.int32)
    owner = np.repeat(np.arange(n), nchunks)
    if m == 0:
        return ops, payloads, owner
    # Chunk index within each message, in EMISSION order (back-to-front):
    # row k of message i covers text[(nchunks[i]-1-k)*L :].
    row0 = np.concatenate(([0], np.cumsum(nchunks)[:-1]))
    local = np.arange(m) - np.repeat(row0, nchunks)
    chunk_idx = np.repeat(nchunks, nchunks) - 1 - local
    chunk_start = chunk_idx * L
    chunk_len = np.minimum(L, np.repeat(lens, nchunks) - chunk_start)
    ops[:, 0] = OpKind.INSERT
    ops[:, 1] = np.repeat(np.asarray(op_keys, np.int64), nchunks)
    ops[:, 2] = np.repeat(np.asarray(op_clients, np.int64), nchunks)
    ops[:, 3] = np.repeat(np.asarray(ref_seqs, np.int64), nchunks)
    ops[:, 4] = np.repeat(np.asarray(pos, np.int64), nchunks)
    ops[:, 6] = chunk_len
    # One utf-32 decode covers every codepoint in the batch; each chunk
    # row is a scatter from the flat pool.
    codes = np.frombuffer(
        "".join(texts).encode("utf-32-le"), dtype=np.uint32
    ).astype(np.int32)
    text_off = np.concatenate(([0], np.cumsum(lens)[:-1]))
    src_base = np.repeat(text_off, nchunks) + chunk_start
    row = np.repeat(np.arange(m), chunk_len)
    within = np.arange(int(chunk_len.sum())) - np.repeat(
        np.concatenate(([0], np.cumsum(chunk_len)[:-1])), chunk_len
    )
    payloads[row, within] = codes[np.repeat(src_base, chunk_len) + within]
    return ops, payloads, owner


def encode_obliterate_batch(
    pos1: np.ndarray,
    side1: np.ndarray,
    pos2: np.ndarray,
    side2: np.ndarray,
    op_keys: np.ndarray,
    op_clients: np.ndarray,
    ref_seqs: np.ndarray,
) -> np.ndarray:
    """Vectorized ``encode_obliterate``: N sided obliterates -> ops[N, 8]."""
    n = len(op_keys)
    ops = np.empty((n, OP_FIELDS), np.int32)
    ops[:, 0] = OpKind.OBLITERATE
    ops[:, 1] = op_keys
    ops[:, 2] = op_clients
    ops[:, 3] = ref_seqs
    ops[:, 4] = pos1
    ops[:, 5] = pos2
    ops[:, 6] = side1
    ops[:, 7] = side2
    return ops


def _any_tree(masks) -> jnp.ndarray:
    return functools.reduce(jnp.logical_or, masks)


def _min_tree(arrays) -> jnp.ndarray:
    return functools.reduce(jnp.minimum, arrays)


# --------------------------------------------------------------------------
# Visibility / geometry primitives
# --------------------------------------------------------------------------

def _alive(s: DocState) -> jnp.ndarray:
    return jnp.arange(s.seg_len.shape[0], dtype=I32) < s.nseg


def _visible(s: DocState, ref_seq, client) -> jnp.ndarray:
    """Perspective mask over segments (ref perspective.ts isSegmentPresent)."""
    ins_occ = (s.ins_key <= ref_seq) | (s.ins_client == client)
    rem_occ = _any_tree(
        [(k <= ref_seq) | (c == client) for k, c in zip(s.rem_keys, s.rem_clients)]
    )
    return _alive(s) & ins_occ & ~rem_occ


def _vis_lengths(s: DocState, vis: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    vlen = jnp.where(vis, s.seg_len, 0)
    excl = jnp.cumsum(vlen) - vlen  # exclusive prefix
    return vlen, excl


def _first_true(mask: jnp.ndarray, default: jnp.ndarray) -> jnp.ndarray:
    idx = jnp.argmax(mask)
    return jnp.where(jnp.any(mask), idx.astype(I32), default)


def _shift_right(arr, k, newval):
    """arr with a slot opened at k: [0..k-1] keep, [k]=newval, [k+1..] shifted."""
    idx = jnp.arange(arr.shape[0], dtype=I32)
    # The neighbour to the left, as a static shift: indexing with idx - 1
    # lowers to a gather per column, which the TPU runs far slower.
    prev = jnp.concatenate([arr[:1], arr[:-1]])
    return jnp.where(idx < k, arr, jnp.where(idx == k, newval, prev))


class _NewSeg(NamedTuple):
    seg_start: jnp.ndarray
    seg_len: jnp.ndarray
    ins_key: jnp.ndarray
    ins_client: jnp.ndarray
    seg_uid: jnp.ndarray
    seg_obpre: jnp.ndarray
    rem_keys: tuple
    rem_clients: tuple
    prop_keys: tuple
    prop_vals: tuple


def _columns(s: DocState) -> _NewSeg:
    """The per-segment columns of ``s``, under the names a new segment's
    values go by."""
    return _NewSeg(*(getattr(s, name) for name in _NewSeg._fields))


def _geometry(s: DocState, ref_seq, client):
    """(vis, vlen, excl) of ``s`` from one perspective: the visibility mask,
    the visible lengths and their exclusive prefix sum."""
    vis = _visible(s, ref_seq, client)
    vlen, excl = _vis_lengths(s, vis)
    return vis, vlen, excl


class _Cut(NamedTuple):
    """One boundary cut of a row, as ``_plan_cuts`` plans it."""

    k: jnp.ndarray        # index of the segment that holds the cut (see there)
    do: jnp.ndarray       # the cut is made: left half trimmed, a uid spent
    split: jnp.ndarray    # ... and there is room for the right half's slot
    off: jnp.ndarray      # the left half's length
    src_uid: jnp.ndarray  # the holder's uid
    right: _NewSeg        # the right half; ``right.seg_uid`` is the uid spent


class _Insert(NamedTuple):
    """The segment an insert row adds, as ``_apply_row`` plans it."""

    k: jnp.ndarray   # where it lands, in the index space cut 1 leaves
    ok: jnp.ndarray  # the row is an insert that is applied
    new: _NewSeg


@jax.named_scope("ensure_boundary")
def _plan_cuts(s: DocState, geom, cut1, gate1, cut2, gate2) -> tuple[_Cut, _Cut]:
    """Under each cut's gate, plan the split of the segment that holds it
    strictly inside, if any: cut 1, then cut 2 in the document cut 1 leaves.
    ``geom`` is ``_geometry`` of ``s`` from the op's perspective, and both
    cuts are planned from it; ``_open_slots`` carries them out.  ``k`` of
    cut 1 indexes ``s``, ``k`` of cut 2 the document with cut 1's slot open.

    Mirrors the reference's split-on-walk (ensureIntervalBoundary /
    insertingWalk split path): after the cuts, each falls on a segment
    boundary of the perspective-visible sequence.

    A split that finds the document full (``nseg == S``) latches
    ERR_SEG_OVERFLOW and opens no slot, but its left half is trimmed, its
    uid spent and its anchors moved all the same (tests/test_apply_op_body's
    one-cut split holds that; the host grows the document and replays).
    """
    vis, vlen, excl = geom
    S = s.seg_len.shape[0]
    cols = _columns(s)

    def holder(pos):
        mid = vis & (excl < pos) & (pos < excl + vlen)
        return _first_true(mid, jnp.asarray(0, I32)), jnp.any(mid)

    def at(k):
        return jax.tree.map(lambda arr: arr[k], cols)

    def right_half(src: _NewSeg, off, uid) -> _NewSeg:
        return src._replace(
            seg_start=src.seg_start + off, seg_len=src.seg_len - off, seg_uid=uid
        )

    k1, found1 = holder(cut1)  # default index unused when ~do1
    do1 = gate1 & found1
    split1 = do1 & (s.nseg < S)
    src1 = at(k1)
    off1 = cut1 - excl[k1]
    uid1 = s.uid_next
    right1 = right_half(src1, off1, uid1)

    # A split moves no visible length and both halves keep the source's
    # visibility, so the document cut 1 leaves has the geometry of ``geom``
    # with segment k1 in two parts, and cut 2's holder is found in the index
    # space of ``geom``.  Where cut 1 overflowed its right half is gone: what
    # follows k1 then lies that half's length lower (a cut past the end
    # stays past it or wraps below zero: nothing holds it either way).
    lost1 = do1 & ~split1
    c2g = cut2 + jnp.where(lost1 & (cut2 > cut1), right1.seg_len, 0)
    k2g, found2 = holder(c2g)
    do2 = gate2 & found2 & ~(do1 & (cut2 == cut1))  # cut 1 made it a boundary
    same = do1 & (k2g == k1)
    in_right = same & (cut2 > cut1)
    src2 = at(k2g)
    src2 = src2._replace(
        seg_start=src2.seg_start + jnp.where(in_right, off1, 0),
        seg_len=jnp.where(
            same, jnp.where(in_right, right1.seg_len, off1), src2.seg_len
        ),
        seg_uid=jnp.where(in_right, uid1, src2.seg_uid),
    )
    off2 = jnp.where(in_right, cut2 - cut1, c2g - excl[k2g])
    # Index of cut 2's holder once slot 1 is open.
    k2 = k2g + (split1 & (k2g > k1)).astype(I32) + in_right.astype(I32)
    split2 = do2 & (s.nseg + split1.astype(I32) < S)
    uid2 = uid1 + do1.astype(I32)
    return (
        _Cut(k1, do1, split1, off1, src1.seg_uid, right1),
        _Cut(k2, do2, split2, off2, src2.seg_uid, right_half(src2, off2, uid2)),
    )


@jax.named_scope("ensure_boundary")
def _open_slots(s: DocState, c1: _Cut, c2: _Cut, ins: _Insert) -> DocState:
    """Carry out a row's planned cuts and its insert in ONE rewrite of the
    per-segment columns, which opens up to two slots: cut 1's right half,
    and cut 2's right half or the inserted segment.  The kinds exclude one
    another: a row that inserts (``ins.ok``) cuts at its position only, so
    its second slot is free.  The state that comes out is that of cut 1,
    then cut 2, then the insert, each a shift of its own, padding included.

    Obliterate anchors on a split segment follow the half holding their
    endpoint char: Before sides keep the left half's uid, After sides move
    to the right half.  An insert that finds the document full latches
    ERR_SEG_OVERFLOW and opens no slot, but spends its uid.
    """
    S = s.seg_len.shape[0]
    idx = jnp.arange(S, dtype=I32)
    k1, k2 = c1.k, c2.k
    split1 = c1.split
    lands = ins.ok & (s.nseg + split1.astype(I32) < S)
    split2 = c2.split | lands
    v2 = jax.tree.map(lambda n, r: jnp.where(ins.ok, n, r), ins.new, c2.right)

    with jax.named_scope("open_slot"):
        # ``shift_right(shift_right(arr, k1 + 1, v1), q2, v2)``, each under
        # its gate, element by element: slot 2 ends up at q2, slot 1 at q1
        # (one higher where slot 2 opened at or below it), and every other
        # element comes from as many indices lower as slots opened below it.
        q2 = jnp.where(ins.ok, ins.k, k2 + 1)
        q1 = k1 + 1 + (split2 & (k1 + 1 >= q2)).astype(I32)
        at1, past1 = split1 & (idx == q1), split1 & (idx > q1)
        at2, past2 = split2 & (idx == q2), split2 & (idx > q2)
        by1, by2 = past1 ^ past2, past1 & past2

        def rewrite(arr, v1, v2):
            # Static shifts: indexing with idx - 1 lowers to a gather per
            # column, which the TPU runs far slower.
            prev1 = jnp.concatenate([arr[:1], arr[:-1]])
            prev2 = jnp.concatenate([arr[:2], arr[:-2]])
            moved = jnp.where(by2, prev2, jnp.where(by1, prev1, arr))
            return jnp.where(at2, v2, jnp.where(at1, v1, moved))

        out = jax.tree.map(rewrite, _columns(s), c1.right, v2)
        # Trim the left halves in the same write (masked, not ``.at[k].set``:
        # one element per document is a scatter).  Slot 2 may have opened
        # below cut 1's left half; cut 2's own trim comes last.
        t1 = k1 + (split2 & (k1 >= q2)).astype(I32)
        seg_len = jnp.where(
            c2.do & (idx == k2),
            c2.off,
            jnp.where(c1.do & (idx == t1), c1.off, out.seg_len),
        )

    def anchored(uids, sides):
        for cut in (c1, c2):
            moved = cut.do & (sides == SIDE_AFTER) & (uids == cut.src_uid)
            uids = jnp.where(moved, cut.right.seg_uid, uids)
        return uids

    over = (c1.do & ~split1) | (c2.do & ~c2.split) | (ins.ok & ~lands)
    return s._replace(
        **out._replace(seg_len=seg_len)._asdict(),
        nseg=s.nseg + split1.astype(I32) + split2.astype(I32),
        # Cut 2's uid follows cut 1's, and so does the inserted segment's.
        uid_next=c2.right.seg_uid + (c2.do | ins.ok).astype(I32),
        ob_start_uid=anchored(s.ob_start_uid, s.ob_start_side),
        ob_end_uid=anchored(s.ob_end_uid, s.ob_end_side),
        error=s.error | jnp.where(over, ERR_SEG_OVERFLOW, 0),
    )


# --------------------------------------------------------------------------
# The parts of the op body
# --------------------------------------------------------------------------

def _tiebreak(s: DocState, op_key) -> jnp.ndarray:
    """Reference breakTie (mergeTree.ts:1811) as a per-segment mask.

    Equal keys (>=) win the tie — grouped-batch ops share a sequence number
    and the issuer placed the later op's segment in front by localSeq (see
    mergetree_ref._tiebreak); same-stamp insert CHUNKS rely on this too
    (encode_insert emits them back-to-front at one position)."""
    rem0 = _min_tree(s.rem_keys)  # removes[0] = earliest remove stamp
    rem_clause = (rem0 < LOCAL_BASE) & (rem0 > op_key)
    return (op_key >= s.ins_key) | rem_clause


def _ob_anchor_indices(s: DocState) -> tuple[jnp.ndarray, ...]:
    """Per obliterate slot: segment indices of its start/end anchor uids
    ([OB] each) plus found masks.  OB is small (<=8), so the [OB, S]
    comparison matrix is cheap."""
    alive = _alive(s)
    m_start = (s.ob_start_uid[:, None] == s.seg_uid[None, :]) & alive[None, :]
    m_end = (s.ob_end_uid[:, None] == s.seg_uid[None, :]) & alive[None, :]
    s_idx = jnp.argmax(m_start, axis=1).astype(I32)
    e_idx = jnp.argmax(m_end, axis=1).astype(I32)
    return s_idx, m_start.any(axis=1), e_idx, m_end.any(axis=1)


def _obliterate_new_segment(s: DocState, c1: _Cut, k, key, client, ref_seq):
    """The insert-time obliterate rule (ref mergeTree.ts blockInsert
    :1647-1745): decide whether the segment about to land at index ``k`` of
    the document cut ``c1`` leaves of ``s`` is swallowed by concurrent
    obliterates, and with which remove stamps.

    Returns (rem_keys, rem_clients, obpre, overflow): the new segment's
    remove slots (sorted ascending, NO_REMOVE padded), its
    obliteratePrecedingInsertion stamp key (-1 none), and whether the
    candidate stamps overflowed the R slots."""
    s_idx, s_found, e_idx, e_found = _ob_anchor_indices(s)

    def after_cut(i, found, uids, sides):
        # An After-side anchor on the split segment follows the right half
        # (``_open_slots``), which exists only where its slot opened.  Every
        # other anchor stays on its segment; one behind the holder lies one
        # higher behind the slot, and at or past ``k`` (at most the right
        # half's index) either way, so its index serves as it is.
        moved = c1.do & (sides == SIDE_AFTER) & (uids == c1.src_uid)
        return jnp.where(moved, c1.k + 1, i), jnp.where(moved, c1.split, found)

    anchors = (
        *after_cut(s_idx, s_found, s.ob_start_uid, s.ob_start_side),
        *after_cut(e_idx, e_found, s.ob_end_uid, s.ob_end_side),
    )
    return _obliterate_swallow(s, anchors, k, key, client, ref_seq)


def _obliterate_swallow(s: DocState, anchors, k, key, client, ref_seq):
    """Swallow analysis shared by the single-lane and segment-parallel
    inserts: ``anchors`` carries the (start idx, found, end idx, found)
    tuple in whatever index space ``k`` lives in (absolute for the single
    lane, global for the sharded layout).  Everything here reads only the
    replicated obliterate window table, so the sharded path can run it
    identically on every shard."""
    R = len(s.rem_keys)
    OB = s.ob_key.shape[0]
    used = s.ob_key >= 0
    s_idx, s_found, e_idx, e_found = anchors
    # New segment lands at k: inside the anchor window iff strictly after
    # the start anchor and at/before the end anchor (pre-insert indices).
    inside = used & s_found & e_found & (s_idx < k) & (e_idx >= k)
    concurrent = inside & (s.ob_key > ref_seq)
    others = concurrent & (s.ob_client != client)
    any_conc = jnp.any(concurrent)
    conc_keys = jnp.where(concurrent, s.ob_key, -1)
    newest_i = jnp.argmax(conc_keys)
    newest_key = conc_keys[newest_i]
    newest_client = s.ob_client[newest_i]
    acked_conc = concurrent & (s.ob_key < LOCAL_BASE)
    any_acked = jnp.any(acked_conc)
    na_keys = jnp.where(acked_conc, s.ob_key, -1)
    na_i = jnp.argmax(na_keys)
    na_key = na_keys[na_i]
    na_client = s.ob_client[na_i]
    unacked_conc = concurrent & (s.ob_key >= LOCAL_BASE)
    ou_keys = jnp.where(unacked_conc, s.ob_key, NO_REMOVE)
    ou_i = jnp.argmin(ou_keys)
    mark = jnp.any(others) & any_conc & (newest_client != client)
    include_acked = ~any_acked | (na_key == newest_key) | (na_client != client)
    is_oldest_unacked = unacked_conc & (jnp.arange(OB, dtype=I32) == ou_i)
    cand = mark & ((others & acked_conc & include_acked) | is_oldest_unacked)
    # Extract the R smallest candidate stamps into sorted remove slots.
    ckeys = jnp.where(cand, s.ob_key, NO_REMOVE)
    rem_k, rem_c = [], []
    for _ in range(R):
        i = jnp.argmin(ckeys)
        kk = ckeys[i]
        rem_k.append(kk)
        rem_c.append(jnp.where(kk < NO_REMOVE, s.ob_client[i], -1))
        ckeys = jnp.where(jnp.arange(OB, dtype=I32) == i, NO_REMOVE, ckeys)
    overflow = jnp.any(ckeys < NO_REMOVE)
    obpre = jnp.where(any_conc, newest_key, -1)
    return tuple(rem_k), tuple(rem_c), obpre, overflow


def _no_obliterate_swallow(s: DocState):
    """Cheap branch of the insert-time obliterate rule: empty ob table means
    the new segment is never swallowed."""
    R = len(s.rem_keys)
    no = jnp.full((), NO_REMOVE, I32)
    neg = jnp.full((), -1, I32)
    return (
        tuple(no for _ in range(R)),
        tuple(neg for _ in range(R)),
        neg,
        jnp.zeros((), bool),
    )


def _splice_remove_stamp(s: DocState, mark, key, client):
    """Place a remove stamp into the first free slot of every marked
    segment; returns (rem_keys, rem_clients, overflow)."""
    rem_keys = list(s.rem_keys)
    rem_clients = list(s.rem_clients)
    placed = jnp.zeros_like(mark)
    for r in range(len(rem_keys)):
        sel = mark & (rem_keys[r] == NO_REMOVE) & ~placed
        rem_keys[r] = jnp.where(sel, key, rem_keys[r])
        rem_clients[r] = jnp.where(sel, client, rem_clients[r])
        placed = placed | sel
    return tuple(rem_keys), tuple(rem_clients), jnp.any(mark & ~placed)


def _annotate_marked(s: DocState, mark, op) -> DocState:
    """The annotate LWW write against an already-computed mark mask
    (shared by the single-lane and segment-parallel paths)."""
    key, prop_slot, value = op[1], op[6], op[7]
    prop_keys = list(s.prop_keys)
    prop_vals = list(s.prop_vals)
    for p in range(len(prop_keys)):
        # LWW by stamp key: pending local writes outrank acked remotes.
        # Ties (>=) go to the later-applied op (grouped-batch shared seqs).
        win = (prop_slot == p) & mark & (key >= prop_keys[p])
        prop_keys[p] = jnp.where(win, key, prop_keys[p])
        prop_vals[p] = jnp.where(win, value, prop_vals[p])
    return s._replace(prop_keys=tuple(prop_keys), prop_vals=tuple(prop_vals))


def _obliterate_visit(s: DocState, vis, key, client, ref_seq):
    """The obliterate marking visit rule (ref nodeMap mergeTree.ts:2990-3001
    + markRemoved splice, walking RemoteObliteratePerspective for remote
    ops), shared by the single-lane and segment-parallel paths (purely
    element-wise over the segment axis): a REMOTE obliterate visits — and
    splices into — every window segment except those dead in both views:
    acked-removed AND invisible at the op's refSeq AND not a local pending
    insert.  A LOCAL obliterate marks exactly the segments visible to the
    op's (local) perspective.  Returns (visit, skip) masks."""
    rem_min = _min_tree(s.rem_keys)
    has_acked_rem = rem_min < LOCAL_BASE
    is_local_ins = s.ins_key >= LOCAL_BASE
    # Concurrent-inserted segments are spliced even when acked-removed (the
    # obliterater's replica swallowed them at insert time), unless an older
    # remove stamp from the same client already covers them (then the extra
    # stamp would be unobservable and the issuer never added it).
    ins_conc = ~((s.ins_key <= ref_seq) | (s.ins_client == client))
    # The issuer swallowed a concurrent insert at INSERT time by appending
    # its OLDEST covering pending obliterate; our stamp already exists there
    # iff some same-client stamp came from an obliterate pending at the
    # issuer when the insert arrived: ins_seq < k <= key (== key is an
    # earlier op of the same grouped batch, sharing our sequence number).
    same_client_stamp = _any_tree(
        [
            (c == client) & (k > s.ins_key) & (k <= key)
            for k, c in zip(s.rem_keys, s.rem_clients)
        ]
    )
    visit = jnp.where(
        key >= LOCAL_BASE,
        vis,
        ~has_acked_rem | vis | is_local_ins | (ins_conc & ~same_client_stamp),
    )
    # Last-obliterater-wins: never mark a local pending insert whose newest
    # preceding obliterate is an (even newer) local pending one.
    skip = (s.ins_key >= LOCAL_BASE) & (s.seg_obpre >= LOCAL_BASE) & (key < LOCAL_BASE)
    return visit, skip


def _restamp_acked(s: DocState, op, gate) -> DocState:
    """Under ``gate``, convert pending stamps (localSeq) to the acked seq;
    optionally re-stamp the client id (op[2] >= 0) and the obliterate's
    recorded refSeq (op[3] >= 0) — channel-hosted replicas stamp local
    pending ops with a sentinel client and learn their short id / wire
    refSeq only at ack (mirrors mergetree_ref.RefMergeTree.ack)."""
    local_seq, seq = op[6], op[7]
    new_client, new_ref = op[2], op[3]
    local_key = LOCAL_BASE + local_seq

    def hit(keys):
        return gate & (keys == local_key)

    ins_hit = hit(s.ins_key)
    ob_hit = hit(s.ob_key)
    rw_c = new_client >= 0
    return s._replace(
        ins_key=jnp.where(ins_hit, seq, s.ins_key),
        ins_client=jnp.where(ins_hit & rw_c, new_client, s.ins_client),
        rem_keys=tuple(jnp.where(hit(a), seq, a) for a in s.rem_keys),
        rem_clients=tuple(
            jnp.where(hit(k) & rw_c, new_client, c)
            for k, c in zip(s.rem_keys, s.rem_clients)
        ),
        prop_keys=tuple(jnp.where(hit(a), seq, a) for a in s.prop_keys),
        ob_key=jnp.where(ob_hit, seq, s.ob_key),
        ob_client=jnp.where(ob_hit & rw_c, new_client, s.ob_client),
        ob_ref_seq=jnp.where(ob_hit & (new_ref >= 0), new_ref, s.ob_ref_seq),
        seg_obpre=jnp.where(hit(s.seg_obpre), seq, s.seg_obpre),
    )


def _do_ack(s: DocState, op, payload) -> DocState:
    return _restamp_acked(s, op, True)


# One ``jax.named_scope`` per op kind, in ``OpKind`` order after NOOP.  The
# scope is the first component of every instruction's ``op_name`` that a
# kind's own part of the op body lowers to, and the device trace groups a
# step's time by it (benchmark/host_plane.py; the ``kernel_*_share`` metrics).
# What every row runs whatever its kind (the perspective's geometry, the two
# boundary splits, the range mask) is under ``SHARED_SCOPE``, which names no
# kind; the row loop's carry has no scope at all.  The persistent compile cache
# has to key on this metadata (utils/compile_cache.py), or a cached executable
# keeps its old names.
BRANCH_SCOPES = ("insert", "remove", "annotate", "ack", "obliterate")
SHARED_SCOPE = "shared"


def _scoped_branches(*fns) -> list:
    """``lax.switch`` branches by ``OpKind`` for ``apply_op_seg``: NOOP, then
    ``fns`` each under its scope of ``BRANCH_SCOPES``."""
    return [lambda s, op, p: s] + [
        jax.named_scope(name)(fn)
        for name, fn in zip(BRANCH_SCOPES, fns, strict=True)
    ]


class _TextWrite(NamedTuple):
    """What one row writes to the text pool: ``count`` payload elements from
    ``start`` on (count 0: nothing)."""

    start: jnp.ndarray
    count: jnp.ndarray


def _unwritten(writes: _TextWrite, done):
    """[D, B]: the rows that still have something to write, ``done`` [D] the
    slots ``_write_text`` is through with."""
    slot = jnp.arange(writes.count.shape[1], dtype=I32)
    return (slot >= done[:, None]) & (writes.count > 0)


def _strip_pass(capacity: int, writes: _TextWrite, payloads, done):
    """One pass of ``_write_text``: per document, the strip around its first
    row not written yet (slot >= ``done``), with every following row written
    into it in order for as long as the rows lie inside it.  Returns the
    strips as ``write_text_strips`` takes them (``starts`` [D], ``new`` and
    ``mask`` [D, strip], for a pool ``capacity`` wide) and the new ``done``.

    The strip's contents are composed row by row over the live prefix of the
    slots (a later row over an earlier one: the last writer wins, as when the
    rows write one after another), [D, strip] of work a row; the caller's
    writer then reads, merges and writes back each document's strip in
    place."""
    # Imported where it is traced: Pallas costs a second to import, and most
    # processes that import this module never trace a step.
    from . import pallas_kernels as pk

    n_docs, cap = writes.count.shape[0], capacity
    n_rows, width = payloads.shape[1:]
    strip = pk.text_strip_width(cap, min(n_rows * width, cap))
    slot = jnp.arange(n_rows, dtype=I32)
    todo = _unwritten(writes, done)
    lead = jnp.min(jnp.where(todo, slot, n_rows), axis=1)
    first = jnp.min(jnp.where(todo, writes.start, cap), axis=1)
    at = jnp.clip(first // pk.LANES * pk.LANES, 0, cap - strip)
    off = writes.start - at[:, None]
    outside = todo & ((off < 0) | (off + writes.count > strip))
    # Every pass takes its leading row (what of it lies outside the pool is
    # dropped, as an index past the end was), so B passes end any batch.
    stop = jnp.min(jnp.where(outside & (slot > lead[:, None]), slot, n_rows), axis=1)
    count = jnp.where(todo & (slot < stop[:, None]), writes.count, 0)
    rows = jnp.max(jnp.where(jnp.any(count > 0, axis=0), slot + 1, 0), initial=0)
    lane = jnp.arange(strip, dtype=I32)

    def compose(i, carry):
        new, mask = carry
        pick = lambda x: jax.lax.dynamic_index_in_dim(x, i, 1, keepdims=False)
        rel = lane - pick(off)[:, None]                             # [D, strip]
        n, pay = pick(count)[:, None], pick(payloads)
        for j in range(width):
            new = jnp.where((rel == j) & (j < n), pay[:, j, None], new)
        return new, mask | ((rel >= 0) & (rel < n))

    new, mask = jax.lax.fori_loop(
        jnp.min(lead), rows, compose,
        (jnp.zeros((n_docs, strip), I32), jnp.zeros((n_docs, strip), bool)),
    )
    return (at, new, mask.astype(I32)), stop


def _write_text(text, writes: _TextWrite, payloads, write):
    """Apply the text writes of a batch of rows to the pools of D documents:
    starts and counts [D, B], payloads [D, B, L].  The result is the rows'
    writes applied one after another, element for element.  ``write(text,
    starts, new, mask)`` puts a pass's strips into the pool: ``text`` is
    [D, T] and document d owns row d of it (``pallas_kernels
    .write_text_strips``: a fleet-wide step, a mesh shard, a host lane), or
    it is the pool of a larger fleet of which the D documents, a cohort, own
    a few rows anywhere (``write_text_strips_at`` bound to those rows).

    The pool is append-only and nothing in the op body reads it, so the row
    loop does not carry it, and a row writes at its document's ``text_end``,
    which only moves forward and by at most L a row (``encode_insert``,
    ``encode_insert_batch`` and native/ingest.cpp all chunk an insert to L,
    and no other path makes a device row).  So all writes of one document in
    one batch fall into one strip of B * L elements, and the write costs D
    strips, not the pool: a per-element scatter into [D, T] cost the TPU
    three passes over the whole pool (relaid to one axis, scattered, relaid
    back), a fifth of a shallow fleet-wide step.  A hand-made row whose
    ``text_len`` exceeds L moves the next starts past the strip; such rows
    take a further pass each, so the loop below runs once for every batch an
    engine can stage, and not at all for a batch that writes nothing."""
    def strip_pass(carry):
        strips, done = _strip_pass(text.shape[1], writes, payloads, carry[1])
        return write(carry[0], *strips), done

    text, _ = jax.lax.while_loop(
        lambda carry: jnp.any(_unwritten(writes, carry[1])),
        strip_pass,
        (text, jnp.zeros(writes.count.shape[:1], I32)),
    )
    return text


def _apply_row(s: DocState, op, payload, flag: bool, text_capacity: int):
    """The op body: one straight-line program for every kind.  Returns the
    new state and the row's ``_TextWrite``; ``s.text`` is never looked at
    (``apply_ops`` keeps the pool out of the loop's carry).

    Under ``vmap`` a ``lax.switch`` on the row's kind runs every branch for
    every row and selects between whole ``DocState``s, text pool included.
    All kinds read the document from the same perspective (``ref_seq``,
    ``client`` of the row), so here the shared work runs once (one geometry,
    from which both gated boundary splits and the insert are planned and
    their slots opened in ONE rewrite of the segment columns, and one
    geometry after it) and the kinds differ only in the masks their writes
    go under.  ``flag`` is the Python bool of ``apply_op``: with False the
    obliterate parts trace to nothing.
    """
    kind, key, client, ref_seq = op[0], op[1], op[2], op[3]
    pos1, pos2, a, b = op[4], op[5], op[6], op[7]
    is_insert = kind == OpKind.INSERT
    is_remove = kind == OpKind.REMOVE
    is_annotate = kind == OpKind.ANNOTATE
    is_ack = kind == OpKind.ACK
    is_range = is_remove | is_annotate
    # Out-of-range kinds keep what ``lax.switch`` made of them by clamping
    # (``apply_op_seg`` still does; no test feeds one): below NOOP nothing,
    # above OBLITERATE an obliterate.
    is_ob = (kind >= OpKind.OBLITERATE) if flag else False

    with jax.named_scope(SHARED_SCOPE):
        geom = _geometry(s, ref_seq, client)
        vis, vlen, excl = geom
        # A split moves no visible length: this total serves every range
        # check below.
        total = jnp.sum(vlen)
        # The row's two boundaries: insert (pos1, none), remove/annotate
        # (pos1, pos2), a valid obliterate its sided endpoints, else none.
        cut1, cut2 = pos1, pos2
        do_cut1, do_cut2 = is_insert | is_range, is_range
        if flag:
            start_pos, end_pos = pos1 + a, pos2 + b
            valid = (
                (0 <= pos1) & (pos1 <= pos2) & (pos2 < total)
                & (start_pos <= end_pos)
            )
            ob_ok = is_ob & valid
            cut1 = jnp.where(is_ob, start_pos, cut1)
            cut2 = jnp.where(is_ob, end_pos, cut2)
            do_cut1, do_cut2 = do_cut1 | ob_ok, do_cut2 | ob_ok
        c1, c2 = _plan_cuts(s, geom, cut1, do_cut1, cut2, do_cut2)

    with jax.named_scope("insert"):
        text_len = a
        # Boundary walk: insert before the first segment at/after pos that
        # is visible or wins the tie-break; else append at nseg.  The walk is
        # over the document cut 1 leaves, read off the geometry before it: a
        # split puts its right half, which starts at pos1, behind the holder,
        # and a cut that found no room leaves what follows the holder that
        # half's length lower.
        behind = jnp.arange(excl.shape[0], dtype=I32) > c1.k
        lost = c1.do & ~c1.split
        excl1 = excl - jnp.where(lost & behind, c1.right.seg_len, 0)
        stop = _alive(s) & (excl1 >= pos1) & ((vlen > 0) | _tiebreak(s, key))
        k = _first_true(stop, s.nseg)
        k = jnp.where(c1.split, jnp.minimum(k, c1.k + 1), k)
        # The payload goes into the text pool at ``text_end`` whenever it
        # fits, whether or not the position is in range.  Only this write
        # ever touches the pool (``_write_text``): a row that is no insert,
        # or overflows it, writes nothing, so no select over the pool
        # exists on any path.
        text_over = is_insert & (s.text_end + text_len > text_capacity)
        fits = is_insert & ~text_over
        write = _TextWrite(
            s.text_end,
            jnp.where(fits, jnp.clip(text_len, 0, payload.shape[0]), 0),
        )
        # The [OB,S] swallow analysis only traces when an obliterate can
        # exist (apply_ops hoists the runtime branch to whole-loop level).
        new_rem_k, new_rem_c, obpre, swallow_over = (
            _obliterate_new_segment(s, c1, k, key, client, ref_seq)
            if flag
            else _no_obliterate_swallow(s)
        )
        P = len(s.prop_keys)
        zero = jnp.zeros((), I32)
        new = _NewSeg(
            seg_start=s.text_end,
            seg_len=text_len,
            ins_key=key,
            ins_client=client,
            seg_uid=s.uid_next + c1.do.astype(I32),
            seg_obpre=obpre,
            rem_keys=new_rem_k,
            rem_clients=new_rem_c,
            prop_keys=tuple(jnp.full((), -1, I32) for _ in range(P)),
            prop_vals=tuple(zero for _ in range(P)),
        )
        ok = fits & (pos1 <= total)
        text_end = s.text_end + jnp.where(ok, text_len, 0)
        insert_error = (
            jnp.where(text_over, ERR_TEXT_OVERFLOW, 0)
            | jnp.where(is_insert & (pos1 > total), ERR_POS_RANGE, 0)
            | jnp.where(ok & swallow_over, ERR_REM_OVERFLOW, 0)
        )

    with jax.named_scope(SHARED_SCOPE):
        s = _open_slots(s, c1, c2, _Insert(k, ok, new))
        # On an insert row nothing below writes: every mask is off.
        vis, vlen, excl = _geometry(s, ref_seq, client)
        alive = _alive(s)
        with jax.named_scope("mark_range"):
            in_range = (
                vis & (excl >= pos1) & (excl + vlen <= pos2) & (vlen > 0)
            )
        error = (
            s.error
            | insert_error
            | jnp.where(is_range & (pos2 > total), ERR_POS_RANGE, 0)
        )

    stamp = in_range & is_remove
    if flag:
        with jax.named_scope("obliterate"):
            # Sided obliterate (ref mergeTree.ts obliterateRangeSided:2083):
            # mark every not-yet-removed segment in the anchor window —
            # concurrent inserts included — and record the obliterate for
            # insert-time swallowing.  pos1/pos2 are the endpoint CHARACTER
            # positions in the op's perspective, a/b the sides.  Anchor
            # segments: the visible segments containing the endpoint chars.
            cont_s = vis & (excl <= pos1) & (pos1 < excl + vlen)
            cont_e = vis & (excl <= pos2) & (pos2 < excl + vlen)
            s_idx = _first_true(cont_s, s.nseg)
            e_idx = _first_true(cont_e, s.nseg)
            lo = s_idx + (a == SIDE_AFTER).astype(I32)
            hi = e_idx - (b == SIDE_BEFORE).astype(I32)
            idx = jnp.arange(s.seg_len.shape[0], dtype=I32)
            visit, skip = _obliterate_visit(s, vis, key, client, ref_seq)
            window = ob_ok & alive & (idx >= lo) & (idx <= hi) & visit & ~skip
            stamp = stamp | window
            # Record in the obliterate window table.
            free = s.ob_key < 0
            slot = _first_true(free, jnp.asarray(0, I32))
            has_free = jnp.any(free)
            rec = ob_ok & has_free

            at_slot = rec & (jnp.arange(s.ob_key.shape[0], dtype=I32) == slot)

            def put(arr, val):
                return jnp.where(at_slot, val, arr)

            s = s._replace(
                ob_key=put(s.ob_key, key),
                ob_client=put(s.ob_client, client),
                ob_start_uid=put(s.ob_start_uid, s.seg_uid[s_idx]),
                ob_end_uid=put(s.ob_end_uid, s.seg_uid[e_idx]),
                ob_start_side=put(s.ob_start_side, a),
                ob_end_side=put(s.ob_end_side, b),
                ob_ref_seq=put(s.ob_ref_seq, ref_seq),
            )
            error = (
                error
                | jnp.where(is_ob & ~valid, ERR_POS_RANGE, 0)
                | jnp.where(ob_ok & ~has_free, ERR_OB_OVERFLOW, 0)
            )

    with jax.named_scope("remove"):
        # One splice serves remove's range and obliterate's window (segments
        # covered by earlier removes already occupy lower slots).
        rem_keys, rem_clients, stamp_over = _splice_remove_stamp(
            s, stamp, key, client
        )
        s = s._replace(rem_keys=rem_keys, rem_clients=rem_clients)
        error = error | jnp.where(stamp_over, ERR_REM_OVERFLOW, 0)
    with jax.named_scope("annotate"):
        s = _annotate_marked(s, in_range & is_annotate, op)
    with jax.named_scope("ack"):
        s = _restamp_acked(s, op, is_ack)
    return s._replace(error=error, text_end=text_end), write


def row_count(ops: jnp.ndarray) -> jnp.ndarray:
    """The row loop's trip count for a batch of op rows ([..., B, OP_FIELDS],
    any leading document axes): 1 + the index of the last slot whose kind is
    not NOOP in ANY document, 0 where every slot is a NOOP.  The engines
    pack a queue into a prefix of the slots, so this is the deepest queue of
    the batch; an interior NOOP still runs as a row."""
    live = ops[..., 0] != OpKind.NOOP
    live = jnp.any(live.reshape(-1, live.shape[-1]), axis=0)
    slots = jnp.arange(1, live.shape[0] + 1, dtype=I32)
    return jnp.max(jnp.where(live, slots, 0), initial=0)


def _ob_gate(s: DocState, ops: jnp.ndarray) -> jnp.ndarray:
    """The obliterate gate of a batch: any table nonempty | any op in the
    batch is an OBLITERATE (one scalar whatever the leading axes)."""
    return jnp.any(s.ob_key >= 0) | jnp.any(ops[..., 0] == OpKind.OBLITERATE)


def _row_loop(s: DocState, ops, payloads, ob_flag, over_docs: bool, capacity: int):
    """The row loop of ``apply_ops`` (one document) and, with ``over_docs``,
    of ``apply_fleet_ops`` and ``apply_cohort_ops`` (every leaf, ``ops`` and
    ``payloads`` lead with the document axis): a ``lax.fori_loop`` over the
    first ``row_count(ops)`` of the B row slots.  The loop is OUTSIDE the
    ``vmap`` over documents and its body is the vmapped row: one trip count
    for the whole batch, and the row is traced once (``vmap`` of a ``while``
    batches its body twice, and a batched bound would make it select over the
    whole carried state every iteration).

    The text pool is no part of it: the loop carries every other leaf and
    collects the rows' ``_TextWrite``s, and returns the state with an empty
    ``text`` beside them.  Where the pool lives is the caller's: ``s.text``
    of ``apply_ops`` and ``apply_fleet_ops`` (a lane's document, the fleet's
    or a shard's every row), the fleet's pool beside the cohort's rows in
    ``apply_cohort_ops`` (there ``s.text`` is empty already).  Its width is
    ``capacity``, and it is written once, after the loop and after the
    obliterate gate's ``cond`` (``_write_text``)."""
    lift = jax.vmap if over_docs else (lambda f: f)
    B, T = ops.shape[-2], capacity
    slot = jnp.arange(B, dtype=I32)
    rows = row_count(ops)

    def loop_spec(st: DocState, flag: bool):
        # Staged before it is vmapped, so that a row's scopes reach the
        # instructions' ``op_name`` as written (``.../insert/...``, not
        # ``vmap(insert)``): the device trace groups by them.
        @jax.jit
        def row(st, op, payload):
            return _apply_row(st, op, payload, flag, T)

        def step(i, carry):
            st, writes = carry
            op = jax.lax.dynamic_index_in_dim(ops, i, -2, keepdims=False)
            payload = jax.lax.dynamic_index_in_dim(payloads, i, -2, keepdims=False)
            st, write = lift(row)(st, op, payload)
            # The row's text write goes to slot i of the [B] starts and
            # counts as a masked write (a single-element dynamic update is
            # a scatter over documents).  Slots the loop never reaches keep
            # count 0: they write nothing.
            return st, jax.tree.map(
                lambda w, arr: jnp.where(slot == i, w[..., None], arr),
                write, writes,
            )

        none = jnp.zeros(ops.shape[:-1], I32)
        return jax.lax.fori_loop(0, rows, step, (st, _TextWrite(none, none)))

    # The pool stays outside the loop and outside the gate's ``cond``: the
    # rows' writes do not depend on the flag, and go in after both.
    bare = s._replace(text=jnp.zeros(s.text.shape[:-1] + (0,), I32))
    if isinstance(ob_flag, bool):
        return loop_spec(bare, ob_flag)
    # Hoist the runtime branch to WHOLE-LOOP level: one cond per batch
    # instead of two per op, so the common no-obliterate path is a single
    # fully-fused loop body (conds inside a loop break XLA fusion and
    # were costing ~2x on obliterate-free workloads).
    return jax.lax.cond(
        ob_flag,
        lambda st: loop_spec(st, True),
        lambda st: loop_spec(st, False),
        bare,
    )


def apply_op(
    s: DocState, op: jnp.ndarray, payload: jnp.ndarray, ob_flag=None
) -> DocState:
    """Apply one op row (+ its text payload row) to one document.

    ``ob_flag`` gates the obliterate machinery off the hot path: it must be
    True whenever the ob table may be nonempty or this op may be an
    OBLITERATE (default: computed from this document and this op).  A PYTHON
    bool specializes the trace outright; a traced flag picks between the two
    traces with one ``lax.cond`` at whole-loop level (``_row_loop``), so the
    op body stays one program with no interior cond.  Both the gate and the
    row loop's trip count are scalars of everything the call is given: for a
    batch of documents call ``apply_fleet_ops``, never ``vmap`` of this (a
    batched predicate degrades the cond to select-of-both, a batched bound
    the loop to run-to-the-maximum with a select over the whole state per
    iteration).  Here B = 1: the row runs unless it is a NOOP.
    """
    return apply_ops(s, op[None], payload[None], ob_flag)


def apply_ops(
    s: DocState, ops: jnp.ndarray, payloads: jnp.ndarray, ob_flag=None
) -> DocState:
    """Apply a batch of ops to ONE document, in order: a ``lax.fori_loop``
    over the first ``row_count(ops)`` of the B row slots, ``_apply_row`` its
    body.  A batch costs its depth, not its width: a NOOP row costs what any
    row costs, and the engines fill a prefix of the slots.

    ops: int32[B, OP_FIELDS]; payloads: int32[B, MAX_INSERT_LEN].
    This is the per-document sequential spine (the overflow and quarantine
    lanes, replay); the parallelism over documents is ``apply_fleet_ops``,
    not ``vmap`` of this (see ``apply_op``).
    """
    from . import pallas_kernels as pk

    if ob_flag is None:
        ob_flag = _ob_gate(s, ops)
    out, writes = _row_loop(
        s, ops, payloads, ob_flag, over_docs=False, capacity=s.text.shape[-1]
    )
    one = lambda x: x[None]
    text = _write_text(
        one(s.text), jax.tree.map(one, writes), one(payloads),
        pk.write_text_strips,
    )
    return out._replace(text=text[0])


def apply_fleet_ops(
    s: DocState, ops: jnp.ndarray, payloads: jnp.ndarray, ob_flag=None
) -> DocState:
    """Apply one [D, B] slice of ops to a [D, ...] batch of documents: the
    same row loop with the row vmapped over the document axis INSIDE it.

    The step ends at the deepest queue of the batch: the trip count
    (``row_count``) and the obliterate gate (``ob_flag``, default: any
    document's table nonempty | any op of the slice an OBLITERATE) are
    scalars of the whole slice, taken outside the ``vmap``, so the loop is a
    ``while`` on one predicate for every document.  A slice that fills all B
    slots of some document runs B iterations; interior NOOPs (a document
    with fewer ops than the deepest) run as rows.  Bit-identical, padding
    slots and the error latch included, to applying each document's ops with
    ``apply_ops`` under the same gate.

    ops: int32[D, B, OP_FIELDS]; payloads: int32[D, B, L].
    """
    from . import pallas_kernels as pk

    if ob_flag is None:
        ob_flag = _ob_gate(s, ops)
    out, writes = _row_loop(
        s, ops, payloads, ob_flag, over_docs=True, capacity=s.text.shape[-1]
    )
    text = _write_text(s.text, writes, payloads, pk.write_text_strips)
    return out._replace(text=text)


def apply_megastep(
    s: DocState, ops: jnp.ndarray, payloads: jnp.ndarray
) -> DocState:
    """Apply a [K, D, B] op ring to a [D, ...] document batch in ONE fused
    program: ``lax.scan`` over the K slice axis, ``apply_fleet_ops`` its
    body.

    This is the megastep dispatch amortizer: where the per-slice path pays
    one jit dispatch + one host->device upload per [D, B] slice, a megastep
    pays them once per K slices — error bits latch into the carried state
    on device and are read back once per megastep, never per slice.

    Semantics are bit-identical to K sequential ``apply_fleet_ops``
    dispatches: each slice's obliterate gate is the same whole-batch scalar
    the per-slice dispatch computes, re-evaluated per slice from the CARRIED
    state, and each slice's row loop takes its own trip count: a shallow
    last slice costs its own depth.  Wrapped in ``shard_map``
    (parallel.mesh) both scalars are a shard's own, taken from its slice of
    the documents: the row loop holds no collective, so shards may run
    different counts.

    ops: int32[K, D, B, OP_FIELDS]; payloads: int32[K, D, B, L].
    """

    def body(st: DocState, xs):
        return apply_fleet_ops(st, *xs), None

    out, _ = jax.lax.scan(body, s, (ops, payloads))
    return out


def apply_cohort_ops(
    pool: jnp.ndarray, s: DocState, rows: jnp.ndarray, ops: jnp.ndarray,
    payloads: jnp.ndarray,
) -> tuple[jnp.ndarray, DocState]:
    """``apply_fleet_ops`` for a cohort whose text stays in its fleet's pool:
    one [C, B] slice of ops applied to the C documents at the rows ``rows``
    of a fleet.  ``s`` holds those rows of every leaf but the pool (``text``
    [C, 0]); ``pool`` is the fleet's [D, T], of which the step writes the
    strips its inserts append, in place, at the rows ``rows``, and nothing
    else.  Returns ``(pool, s)``.

    The gate and the trip count are the cohort's own, as a fleet-wide step
    over these C documents alone would take them, and the result is that
    step's, bit for bit: the same ``s``, and in ``pool`` the same rows.  A
    lane whose rows are all NOOPs writes nothing and may repeat another
    lane's row (the engine pads a cohort so).

    rows: int32[C]; ops: int32[C, B, OP_FIELDS]; payloads: int32[C, B, L].
    """
    from . import pallas_kernels as pk

    out, writes = _row_loop(
        s, ops, payloads, _ob_gate(s, ops), over_docs=True,
        capacity=pool.shape[-1],
    )
    at_rows = lambda pool, *strips: pk.write_text_strips_at(pool, rows, *strips)
    return _write_text(pool, writes, payloads, at_rows), out


def apply_cohort_megastep(
    pool: jnp.ndarray, s: DocState, rows: jnp.ndarray, ops: jnp.ndarray,
    payloads: jnp.ndarray,
) -> tuple[jnp.ndarray, DocState]:
    """``apply_megastep`` for a cohort (``apply_cohort_ops``): a [K, C, B]
    op ring in one program, the fleet's pool carried through the ``scan``
    beside the cohort's rows.  Bit-identical to K ``apply_cohort_ops``."""

    def body(carry, xs):
        return apply_cohort_ops(*carry, rows, *xs), None

    out, _ = jax.lax.scan(body, (pool, s), (ops, payloads))
    return out


# --------------------------------------------------------------------------
# Segment-parallel apply (the docs x segs serving path)
# --------------------------------------------------------------------------
#
# One viral document serializes a whole lane: the [S] per-segment arrays are
# the per-op cost, and a hot doc's S is the largest on the box.  The
# segment-parallel variant block-shards those arrays over a named mesh axis
# (default "segs") — shard k owns the k-th contiguous run of the GLOBAL
# segment order, per-shard live counts vary (``nseg`` becomes int32[n_shards],
# one live count per shard), and the global order is the concatenation of the
# per-shard live prefixes.  The text pool, every scalar, and the obliterate
# window table stay REPLICATED, so stamp/uid/text values are bit-identical to
# the single-lane kernel and a gather of the live prefixes reproduces the
# single-lane state exactly (the byte-identity fuzz contract; the single-lane
# path is the oracle).
#
# Per op, the collective structure is the two-hop scheme of
# parallel/long_doc.py ("Parallel Batch-Dynamic Trees via Change
# Propagation" / "Data Structures for Mergeable Trees", PAPERS.md):
#
#   hop 1: all_gather of per-shard visible totals (and live counts) turns
#          local prefix sums into global coordinates,
#   local: masked prefix-sum / containment search inside the shard,
#   hop 2: pmin/psum combines per-shard one-hot candidates into the global
#          insert index / anchor index / owner decision.
#
# Mutations are OWNER-LOCAL: exactly one shard owns the op's landing
# segment, and the O(S_local) suffix shift of ``_open_slot_seg`` runs under a
# real ``lax.cond`` on that shard only — legal here because a segment lane
# is a single-document program (no vmap to degrade the cond to a select).
# Range ops (remove/annotate/obliterate) are purely-local mask updates once
# the global prefix is known.  Inserts land shard-local; the layout re-blocks
# only at rebalance points (``seg_rebalance_state`` below, reusing the
# compaction gather's fill conventions).
#
# These functions use named-axis collectives and MUST run inside a
# ``shard_map`` over the segment axis (parallel.mesh.mesh_seg_program).

SEG_AXIS = "segs"

# Route the shard-local containment searches through the blocked Pallas
# kernel (ops/pallas_kernels.py) instead of the jnp membership mask.  The
# jnp/lax form is the oracle; the Pallas form streams the segment axis
# through VMEM on TPU (a long doc's shard still holds 100k+ segments).
# Trace-time flag: set it before the first segment-lane dispatch compiles.
SEG_RESOLVE_PALLAS = False


def _seg_prefix(s: DocState, vis, axis: str):
    """Hop 1: (vlen, excl_global, total, char_off) — one all_gather of
    per-shard visible totals turns the local exclusive prefix into global
    perspective-visible coordinates."""
    vlen = jnp.where(vis, s.seg_len, 0)
    totals = jax.lax.all_gather(jnp.sum(vlen), axis)  # [n_shards]
    my = jax.lax.axis_index(axis)
    char_off = jnp.sum(jnp.where(jnp.arange(totals.shape[0]) < my, totals, 0))
    excl = jnp.cumsum(vlen) - vlen + char_off
    return vlen, excl, jnp.sum(totals), char_off


def _seg_index_base(s: DocState, axis: str):
    """Hop 1b: (idx_off, nseg_total, counts) — the global segment-index
    base of this shard (global order = concatenation of per-shard live
    prefixes) from one all_gather of the live counts."""
    counts = jax.lax.all_gather(s.nseg, axis)  # [n_shards]
    my = jax.lax.axis_index(axis)
    idx_off = jnp.sum(jnp.where(jnp.arange(counts.shape[0]) < my, counts, 0))
    return idx_off, jnp.sum(counts), counts


def _seg_first_true(mask, idx_off, default, axis: str):
    """Hop 2: global index of the first set bit across shards (pmin of the
    per-shard one-hot candidates), else ``default``.  ``mask`` must only be
    set inside the shard's live prefix."""
    has = jnp.any(mask)
    big = jnp.asarray(2**31 - 1, I32)
    cand = jnp.where(has, idx_off + jnp.argmax(mask).astype(I32), big)
    best = jax.lax.pmin(cand, axis)
    return jnp.where(best == big, default, best)


def _seg_contains(vlen, q_local, strict: bool):
    """Shard-local containment search: (local index, hit) of the visible
    segment containing the local-coordinate query (``strict`` excludes
    boundary hits — the split predicate).  Behind ``SEG_RESOLVE_PALLAS``
    the blocked Pallas kernel is the fused inner loop; the jnp form is the
    oracle and the non-TPU fallback."""
    if SEG_RESOLVE_PALLAS:
        from .pallas_kernels import resolve_positions_blocked

        idx, off, hit = resolve_positions_blocked(vlen, q_local[None])
        idx, off, hit = idx[0], off[0], hit[0] != 0
        if strict:
            hit = hit & (off > 0)
        return idx.astype(I32), hit
    prefix = jnp.cumsum(vlen) - vlen
    if strict:
        inside = (prefix < q_local) & (q_local < prefix + vlen)
    else:
        inside = (vlen > 0) & (prefix <= q_local) & (q_local < prefix + vlen)
    return jnp.argmax(inside).astype(I32), jnp.any(inside)


def _open_slot_seg(s: DocState, k, do, new: _NewSeg, axis: str) -> DocState:
    """Open a slot at ``k`` on its owner: conditionally (``do``) shift all
    per-segment arrays right at ``k`` and write the new segment's values
    there.  ``do`` is a SHARD-LOCAL scalar (exactly one shard owns the
    insert), so the O(S_local) suffix shift runs under a
    real branch on the owning shard only — the non-owners skip the heavy
    gather/select entirely.  Shard capacity overflow latches
    ERR_SEG_OVERFLOW globally (psum), exactly like the single-lane latch;
    host recovery re-blocks or re-provisions."""
    S = s.seg_len.shape[0]
    overflow = do & (s.nseg >= S)
    do = do & ~overflow
    R, Pn = len(s.rem_keys), len(s.prop_keys)
    flat = (
        s.seg_start, s.seg_len, s.ins_key, s.ins_client, s.seg_uid,
        s.seg_obpre, *s.rem_keys, *s.rem_clients, *s.prop_keys, *s.prop_vals,
    )
    vals = (
        new.seg_start, new.seg_len, new.ins_key, new.ins_client, new.seg_uid,
        new.seg_obpre, *new.rem_keys, *new.rem_clients, *new.prop_keys,
        *new.prop_vals,
    )
    shifted = jax.lax.cond(
        do,
        lambda t: tuple(_shift_right(a, k, v) for a, v in zip(t, vals)),
        lambda t: t,
        flat,
    )
    err = jax.lax.psum(jnp.where(overflow, ERR_SEG_OVERFLOW, 0), axis)
    return s._replace(
        seg_start=shifted[0], seg_len=shifted[1], ins_key=shifted[2],
        ins_client=shifted[3], seg_uid=shifted[4], seg_obpre=shifted[5],
        rem_keys=tuple(shifted[6 : 6 + R]),
        rem_clients=tuple(shifted[6 + R : 6 + 2 * R]),
        prop_keys=tuple(shifted[6 + 2 * R : 6 + 2 * R + Pn]),
        prop_vals=tuple(shifted[6 + 2 * R + Pn :]),
        nseg=s.nseg + do.astype(I32),
        error=s.error | err,
    )


def _ensure_boundary_seg(s: DocState, pos, ref_seq, client, axis: str) -> DocState:
    """One cut of ``_plan_cuts`` / ``_open_slots``, distributed: the
    containing segment (if any) is strictly inside exactly one shard; that
    shard splits locally.
    The split uid allocation and obliterate anchor side-moves replay
    identically on every shard from the replicated uid_next / ob table plus
    one psum broadcast of the split segment's old uid."""
    vis = _visible(s, ref_seq, client)
    vlen, excl, _total, char_off = _seg_prefix(s, vis, axis)
    k, hit = _seg_contains(vlen, pos - char_off, strict=True)
    do = jax.lax.psum(hit.astype(I32), axis) > 0
    off = pos - excl[k]
    old_uid = jax.lax.psum(jnp.where(hit, s.seg_uid[k], 0), axis)
    right_uid = s.uid_next
    right = _NewSeg(
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
    s2 = _open_slot_seg(s, k + 1, hit, right, axis)
    # Trim the left half (owner only; pre-overflow ``hit``/``do`` exactly as
    # the single-lane path uses its pre-overflow ``do``).
    new_len = jnp.where(hit, off, s2.seg_len[k])
    moved_start = do & (s2.ob_start_uid == old_uid) & (s2.ob_start_side == SIDE_AFTER)
    moved_end = do & (s2.ob_end_uid == old_uid) & (s2.ob_end_side == SIDE_AFTER)
    return s2._replace(
        seg_len=s2.seg_len.at[k].set(new_len),
        uid_next=s2.uid_next + do.astype(I32),
        ob_start_uid=jnp.where(moved_start, right_uid, s2.ob_start_uid),
        ob_end_uid=jnp.where(moved_end, right_uid, s2.ob_end_uid),
    )


def _ob_anchor_indices_seg(s: DocState, idx_off, axis: str):
    """``_ob_anchor_indices`` in global coordinates: local uid matches (uids
    are globally unique, so at most one shard hits per anchor), one psum
    pair combines the per-shard one-hots."""
    alive = _alive(s)
    m_start = (s.ob_start_uid[:, None] == s.seg_uid[None, :]) & alive[None, :]
    m_end = (s.ob_end_uid[:, None] == s.seg_uid[None, :]) & alive[None, :]
    ls = jnp.argmax(m_start, axis=1).astype(I32)
    le = jnp.argmax(m_end, axis=1).astype(I32)
    fs = m_start.any(axis=1)
    fe = m_end.any(axis=1)
    s_idx = jax.lax.psum(jnp.where(fs, idx_off + ls, 0), axis)
    e_idx = jax.lax.psum(jnp.where(fe, idx_off + le, 0), axis)
    s_found = jax.lax.psum(fs.astype(I32), axis) > 0
    e_found = jax.lax.psum(fe.astype(I32), axis) > 0
    return s_idx, s_found, e_idx, e_found


def _do_insert_seg(s: DocState, op, payload, ob_flag: bool, axis: str) -> DocState:
    pos, key, client, ref_seq = op[4], op[1], op[2], op[3]
    text_len = op[6]
    s = _ensure_boundary_seg(s, pos, ref_seq, client, axis)
    vis = _visible(s, ref_seq, client)
    vlen, excl, total, _off = _seg_prefix(s, vis, axis)
    idx_off, nseg_total, counts = _seg_index_base(s, axis)
    # Boundary walk in global coordinates: the stop mask is local, the
    # first stop across shards comes from one pmin (hop 2).
    stop = _alive(s) & (excl >= pos) & ((vlen > 0) | _tiebreak(s, key))
    k_g = _seg_first_true(stop, idx_off, nseg_total, axis)
    my = jax.lax.axis_index(axis)
    append = k_g >= nseg_total
    # Appends land on the LAST shard (any other placement would interleave
    # the new segment before a later shard's run and break global order).
    is_owner = jnp.where(
        append,
        my == counts.shape[0] - 1,
        (idx_off <= k_g) & (k_g < idx_off + s.nseg),
    )
    k_local = jnp.where(append, s.nseg, k_g - idx_off).astype(I32)

    # Payload lands in the REPLICATED text pool: every shard appends the
    # same bytes at the same (replicated) text_end, so seg_start values are
    # global offsets bit-identical to the single-lane pool.
    T = s.text.shape[0]
    tpos = jnp.arange(payload.shape[0], dtype=I32)
    text_over = s.text_end + text_len > T
    dst = jnp.where((tpos < text_len) & ~text_over, s.text_end + tpos, T)
    text = s.text.at[dst].set(payload, mode="drop")

    if ob_flag:
        anchors = _ob_anchor_indices_seg(s, idx_off, axis)
        new_rem_k, new_rem_c, obpre, rem_over = _obliterate_swallow(
            s, anchors, k_g, key, client, ref_seq
        )
    else:
        new_rem_k, new_rem_c, obpre, rem_over = _no_obliterate_swallow(s)
    Pn = len(s.prop_keys)
    zero = jnp.zeros((), I32)
    new = _NewSeg(
        seg_start=s.text_end,
        seg_len=text_len,
        ins_key=key,
        ins_client=client,
        seg_uid=s.uid_next,
        seg_obpre=obpre,
        rem_keys=new_rem_k,
        rem_clients=new_rem_c,
        prop_keys=tuple(jnp.full((), -1, I32) for _ in range(Pn)),
        prop_vals=tuple(zero for _ in range(Pn)),
    )
    ok = ~text_over & (pos <= total)
    s = _open_slot_seg(s, k_local, ok & is_owner, new, axis)
    return s._replace(
        text=jnp.where(text_over, s.text, text),
        text_end=s.text_end + jnp.where(ok, text_len, 0),
        uid_next=s.uid_next + ok.astype(I32),
        error=s.error
        | jnp.where(text_over, ERR_TEXT_OVERFLOW, 0)
        | jnp.where(pos > total, ERR_POS_RANGE, 0)
        | jnp.where(ok & rem_over, ERR_REM_OVERFLOW, 0),
    )


def _mark_range_seg(s: DocState, op, axis: str):
    """The distributed range mark: split at both boundaries, then the
    in-range mask is a purely-local comparison against the global prefix."""
    pos1, pos2, client, ref_seq = op[4], op[5], op[2], op[3]
    s = _ensure_boundary_seg(s, pos1, ref_seq, client, axis)
    s = _ensure_boundary_seg(s, pos2, ref_seq, client, axis)
    vis = _visible(s, ref_seq, client)
    vlen, excl, total, _off = _seg_prefix(s, vis, axis)
    mark = vis & (excl >= pos1) & (excl + vlen <= pos2) & (vlen > 0)
    s = s._replace(error=s.error | jnp.where(pos2 > total, ERR_POS_RANGE, 0))
    return s, mark


def _do_remove_seg(s: DocState, op, payload, axis: str) -> DocState:
    key, client = op[1], op[2]
    s, mark = _mark_range_seg(s, op, axis)
    rem_keys, rem_clients, over_l = _splice_remove_stamp(s, mark, key, client)
    overflow = jax.lax.psum(over_l.astype(I32), axis) > 0
    return s._replace(
        rem_keys=rem_keys,
        rem_clients=rem_clients,
        error=s.error | jnp.where(overflow, ERR_REM_OVERFLOW, 0),
    )


def _do_annotate_seg(s: DocState, op, payload, axis: str) -> DocState:
    s, mark = _mark_range_seg(s, op, axis)
    return _annotate_marked(s, mark, op)


def _do_obliterate_seg(s: DocState, op, payload, axis: str) -> DocState:
    """The distributed obliterate: anchors resolve with the two hops,
    the visit/skip masks and the remove-stamp splice are local, and the
    obliterate window record replays identically on every shard from the
    psum-broadcast anchor uids."""
    key, client, ref_seq = op[1], op[2], op[3]
    pos1, pos2, side1, side2 = op[4], op[5], op[6], op[7]
    start_pos = pos1 + side1
    end_pos = pos2 + side2
    vis = _visible(s, ref_seq, client)
    _vlen, _excl, total, _off = _seg_prefix(s, vis, axis)
    valid = (0 <= pos1) & (pos1 <= pos2) & (pos2 < total) & (start_pos <= end_pos)
    s = _ensure_boundary_seg(s, jnp.where(valid, start_pos, 0), ref_seq, client, axis)
    s = _ensure_boundary_seg(s, jnp.where(valid, end_pos, 0), ref_seq, client, axis)
    vis = _visible(s, ref_seq, client)
    vlen, _excl2, _t2, char_off = _seg_prefix(s, vis, axis)
    idx_off, nseg_total, _counts = _seg_index_base(s, axis)
    ks, hs = _seg_contains(vlen, pos1 - char_off, strict=False)
    ke, he = _seg_contains(vlen, pos2 - char_off, strict=False)
    s_found = jax.lax.psum(hs.astype(I32), axis) > 0
    e_found = jax.lax.psum(he.astype(I32), axis) > 0
    s_idx = jnp.where(
        s_found, jax.lax.psum(jnp.where(hs, idx_off + ks, 0), axis), nseg_total
    )
    e_idx = jnp.where(
        e_found, jax.lax.psum(jnp.where(he, idx_off + ke, 0), axis), nseg_total
    )
    start_uid = jax.lax.psum(jnp.where(hs, s.seg_uid[ks], 0), axis)
    end_uid = jax.lax.psum(jnp.where(he, s.seg_uid[ke], 0), axis)
    lo = s_idx + (side1 == SIDE_AFTER).astype(I32)
    hi = e_idx - (side2 == SIDE_BEFORE).astype(I32)
    # Global index of local slot j inside the live prefix is idx_off + j
    # (dead slots are gated by the alive mask below).
    gidx = idx_off + jnp.arange(s.seg_len.shape[0], dtype=I32)
    visit, skip = _obliterate_visit(s, vis, key, client, ref_seq)
    mark = valid & _alive(s) & (gidx >= lo) & (gidx <= hi) & visit & ~skip
    rem_keys, rem_clients, over_l = _splice_remove_stamp(s, mark, key, client)
    rem_over = jax.lax.psum(over_l.astype(I32), axis) > 0
    free = s.ob_key < 0
    slot = _first_true(free, jnp.asarray(0, I32))
    has_free = jnp.any(free)
    rec = valid & has_free

    def put(arr, val):
        return arr.at[slot].set(jnp.where(rec, val, arr[slot]))

    return s._replace(
        rem_keys=rem_keys,
        rem_clients=rem_clients,
        ob_key=put(s.ob_key, key),
        ob_client=put(s.ob_client, client),
        ob_start_uid=put(s.ob_start_uid, start_uid),
        ob_end_uid=put(s.ob_end_uid, end_uid),
        ob_start_side=put(s.ob_start_side, side1),
        ob_end_side=put(s.ob_end_side, side2),
        ob_ref_seq=put(s.ob_ref_seq, ref_seq),
        error=s.error
        | jnp.where(~valid, ERR_POS_RANGE, 0)
        | jnp.where(valid & ~has_free, ERR_OB_OVERFLOW, 0)
        | jnp.where(rem_over, ERR_REM_OVERFLOW, 0),
    )


def apply_op_seg(
    s: DocState, op: jnp.ndarray, payload: jnp.ndarray, ob_flag: bool,
    axis: str = SEG_AXIS,
) -> DocState:
    """Segment-parallel ``apply_op``.  ``ob_flag`` must be a PYTHON bool
    (the scan level hoists the runtime gate — see ``apply_ops_seg``); ACK is
    the single-lane branch verbatim (purely element-wise over local arrays
    plus replicated ob-table rewrites)."""
    kind = op[0]
    branches = _scoped_branches(
        lambda s, op, p: _do_insert_seg(s, op, p, ob_flag, axis),
        lambda s, op, p: _do_remove_seg(s, op, p, axis),
        lambda s, op, p: _do_annotate_seg(s, op, p, axis),
        _do_ack,
        (lambda s, op, p: _do_obliterate_seg(s, op, p, axis))
        if ob_flag
        else (lambda s, op, p: s),
    )
    return jax.lax.switch(kind, branches, s, op, payload)


def apply_ops_seg(
    s: DocState, ops: jnp.ndarray, payloads: jnp.ndarray, ob_flag=None,
    axis: str = SEG_AXIS,
) -> DocState:
    """Segment-parallel ``apply_ops``: one op batch for ONE document, in
    order, per-segment work sharded over ``axis``.  The runtime obliterate
    gate hoists to whole-scan level exactly like ``apply_ops`` (the flag is
    replicated, so every shard takes the same branch and the collectives
    inside stay matched)."""
    if ob_flag is None:
        ob_flag = jnp.any(s.ob_key >= 0) | jnp.any(ops[:, 0] == OpKind.OBLITERATE)

    def scan_spec(st: DocState, flag: bool) -> DocState:
        def step(carry, xs):
            op, payload = xs
            return apply_op_seg(carry, op, payload, flag, axis), None

        out, _ = jax.lax.scan(step, st, (ops, payloads))
        return out

    if isinstance(ob_flag, bool):
        return scan_spec(s, ob_flag)
    return jax.lax.cond(
        ob_flag,
        lambda st: scan_spec(st, True),
        lambda st: scan_spec(st, False),
        s,
    )


def apply_megastep_seg(
    s: DocState, ops: jnp.ndarray, payloads: jnp.ndarray, axis: str = SEG_AXIS
) -> DocState:
    """Segment-parallel megastep: apply a [K, B] op ring to ONE seg-sharded
    document in one fused program (lax.scan over the K slice axis, per-slice
    obliterate gate carried on device — the single-doc analog of
    ``apply_megastep``).

    This is a ``shard_map`` BODY over the segment axis
    (parallel.mesh.mesh_seg_program dispatches it): ``s`` arrives as the
    local shard view of a seg-sharded state — per-segment arrays [S_local],
    ``nseg`` boxed as int32[1] (this shard's live count), text/scalars/ob
    table replicated — and ops/payloads arrive replicated.
    """
    s = s._replace(nseg=s.nseg[0])

    def body(st: DocState, xs):
        o, p = xs
        flag = jnp.any(st.ob_key >= 0) | jnp.any(o[..., 0] == OpKind.OBLITERATE)
        st = apply_ops_seg(st, o, p, flag, axis)
        return st, None

    out, _ = jax.lax.scan(body, s, (ops, payloads))
    return out._replace(nseg=out.nseg[None])


def compact_seg(
    s: DocState, min_seq: jnp.ndarray, axis: str = SEG_AXIS
) -> DocState:
    """Zamboni on the seg-sharded layout (``shard_map`` body, like
    ``apply_megastep_seg``): ``set_min_seq`` is replicated arithmetic and
    eviction is a purely shard-local stable compaction — order is preserved
    within each shard, so the global concatenation order is preserved."""
    s = s._replace(nseg=s.nseg[0])
    out = compact(set_min_seq(s, min_seq))
    return out._replace(nseg=out.nseg[None])


# ----------------------------------------------------- host-side seg packing

# Dead-slot fill per per-segment field, shared by ``seg_shard_state`` and
# ``seg_gather_state`` (tuple-typed fields fill every element array).
# These MUST match the compaction gather's fills (``compact``'s dead-slot
# conventions) for gather-after-shard to be the identity the byte-identity
# fuzz asserts.
_SEG_FILL = {
    "seg_start": 0, "seg_len": 0, "ins_key": 0, "ins_client": -1,
    "seg_uid": -1, "seg_obpre": -1,
    "rem_keys": NO_REMOVE, "rem_clients": -1,
    "prop_keys": -1, "prop_vals": 0,
}


def _seg_repack(state: DocState, pack) -> dict:
    """Apply ``pack(arr, fill)`` to every per-segment field of ``state``
    per ``_SEG_FILL`` — the one place the fill conventions are spelled."""
    out = {}
    for f, fill in _SEG_FILL.items():
        v = getattr(state, f)
        out[f] = (
            tuple(pack(a, fill) for a in v)
            if isinstance(v, tuple) else pack(v, fill)
        )
    return out


def seg_shard_state(
    state: DocState,
    n_shards: int,
    s_local: int | None = None,
    text_capacity: int | None = None,
) -> DocState:
    """Host-side re-block of a single-doc DocState into the seg-sharded
    layout: the live segments split into ``n_shards`` balanced contiguous
    runs, per-segment arrays become [n_shards * s_local] (block-shard over
    the segment axis), ``nseg`` becomes int32[n_shards] per-shard live
    counts, and the text pool / scalars / obliterate table replicate
    verbatim (text offsets stay GLOBAL, so ``seg_gather_state`` round-trips
    byte-identically).  ``text_capacity`` optionally grows the replicated
    pool for a hot doc.  Leaves are numpy; the caller device_puts them with
    ``parallel.mesh.shard_seg_state``."""
    state = jax.tree.map(np.asarray, state)
    nseg = int(state.nseg)
    S_old = state.seg_len.shape[0]
    if s_local is None:
        s_local = -(-S_old // n_shards)
    base, extra = divmod(nseg, n_shards)
    counts = [base + (1 if i < extra else 0) for i in range(n_shards)]
    if max(counts) > s_local:
        raise ValueError(
            f"{nseg} live segments do not block into {n_shards} shards of "
            f"{s_local} slots"
        )
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))

    def blk(arr: np.ndarray, fill: int) -> np.ndarray:
        out = np.full((n_shards * s_local,), fill, np.int32)
        for i in range(n_shards):
            out[i * s_local : i * s_local + counts[i]] = arr[
                starts[i] : starts[i] + counts[i]
            ]
        return out

    T_old = state.text.shape[0]
    T = text_capacity if text_capacity is not None else T_old
    if T < int(state.text_end):
        raise ValueError(f"text_capacity {T} < text_end {int(state.text_end)}")
    text = np.zeros((T,), np.int32)
    keep = min(T, T_old)
    text[:keep] = state.text[:keep]
    return state._replace(
        text=text,
        nseg=np.asarray(counts, np.int32),
        **_seg_repack(state, blk),
    )


def seg_gather_state(state: DocState, max_segments: int | None = None) -> DocState:
    """Inverse of ``seg_shard_state`` (the compaction gather's fill
    conventions): concatenate the per-shard live prefixes back into one
    single-doc DocState in global segment order.  Because the text pool,
    stamps, and uids are replicated/global, the result is byte-identical
    to what the single-lane kernel would have produced — this is both the
    rebalance gather and the byte-identity fuzz surface."""
    state = jax.tree.map(np.asarray, state)
    counts = state.nseg.astype(np.int64)
    n_shards = int(counts.shape[0])
    s_local = state.seg_len.shape[0] // n_shards
    total = int(counts.sum())
    S = max_segments if max_segments is not None else state.seg_len.shape[0]
    if total > S:
        raise ValueError(f"{total} live segments exceed capacity {S}")

    def gat(arr: np.ndarray, fill: int) -> np.ndarray:
        out = np.full((S,), fill, np.int32)
        w = 0
        for i in range(n_shards):
            c = int(counts[i])
            out[w : w + c] = arr[i * s_local : i * s_local + c]
            w += c
        return out

    return state._replace(
        nseg=np.asarray(total, np.int32),
        **_seg_repack(state, gat),
    )


def seg_rebalance_state(
    state: DocState, s_local: int | None = None, text_capacity: int | None = None
) -> DocState:
    """Re-block a seg-sharded state so every shard holds an even share of
    the live segments again (inserts land shard-local between rebalance
    points, so runs skew over time).  Gather + re-shard, both order- and
    byte-preserving."""
    n_shards = int(np.asarray(state.nseg).shape[0])
    if s_local is None:
        s_local = np.asarray(state.seg_len).shape[0] // n_shards
    return seg_shard_state(
        seg_gather_state(state), n_shards, s_local, text_capacity
    )


def seg_occupancy(state: DocState) -> np.ndarray:
    """Per-shard live segment counts (the occupancy gauge)."""
    return np.asarray(state.nseg).astype(np.int64)


def canonical_doc(state: DocState) -> dict:
    """The live content of a SINGLE-DOC state as plain numpy — padding
    slots excluded (they hold shift remnants) — the byte-identity
    comparison surface for the segment-parallel fuzz.  Seg-sharded states
    gather first (``seg_gather_state``)."""
    state = jax.tree.map(np.asarray, state)
    n = int(state.nseg)
    te = int(state.text_end)
    out = {
        "text": state.text[:te].copy(),
        "text_end": te,
        "nseg": n,
        "uid_next": int(state.uid_next),
        "min_seq": int(state.min_seq),
        "error": int(state.error),
        "ob_key": state.ob_key.copy(),
        "ob_client": state.ob_client.copy(),
        "ob_start_uid": state.ob_start_uid.copy(),
        "ob_end_uid": state.ob_end_uid.copy(),
        "ob_start_side": state.ob_start_side.copy(),
        "ob_end_side": state.ob_end_side.copy(),
        "ob_ref_seq": state.ob_ref_seq.copy(),
    }
    for name in (
        "seg_start", "seg_len", "ins_key", "ins_client", "seg_uid", "seg_obpre"
    ):
        out[name] = getattr(state, name)[:n].copy()
    for name in ("rem_keys", "rem_clients", "prop_keys", "prop_vals"):
        for i, a in enumerate(getattr(state, name)):
            out[f"{name}{i}"] = a[:n].copy()
    return out


# --------------------------------------------------------------------------
# Compaction (zamboni)
# --------------------------------------------------------------------------

def _anchored_mask(s: DocState) -> jnp.ndarray:
    """Segments anchoring a live obliterate ([OB,S] uid match)."""
    used = s.ob_key >= 0
    return (
        (
            (s.seg_uid[None, :] == s.ob_start_uid[:, None])
            | (s.seg_uid[None, :] == s.ob_end_uid[:, None])
        )
        & used[:, None]
    ).any(axis=0)


def _gather_keep(s: DocState, keep: jnp.ndarray) -> DocState:
    """Stable-compact the per-segment arrays down to the kept ones."""
    order = jnp.argsort(~keep, stable=True)
    n_keep = jnp.sum(keep).astype(I32)
    idx = jnp.arange(keep.shape[0], dtype=I32)

    def g(arr, fill):
        return jnp.where(idx < n_keep, arr[order], fill)

    return s._replace(
        seg_start=g(s.seg_start, 0),
        seg_len=g(s.seg_len, 0),
        ins_key=g(s.ins_key, 0),
        ins_client=g(s.ins_client, -1),
        seg_uid=g(s.seg_uid, -1),
        seg_obpre=g(s.seg_obpre, -1),
        rem_keys=tuple(g(a, NO_REMOVE) for a in s.rem_keys),
        rem_clients=tuple(g(a, -1) for a in s.rem_clients),
        prop_keys=tuple(g(a, -1) for a in s.prop_keys),
        prop_vals=tuple(g(a, 0) for a in s.prop_vals),
        nseg=n_keep,
    )


def _dead_mask(s: DocState) -> jnp.ndarray:
    """Live segments whose winning remove is acked at or below min_seq."""
    rem0 = _min_tree(s.rem_keys)
    return _alive(s) & (rem0 < LOCAL_BASE) & (rem0 <= s.min_seq)


def evictable_count(s: DocState) -> jnp.ndarray:
    """How many segments ``compact`` would drop right now (0 right after a
    compaction at the document's ``min_seq``)."""
    return jnp.sum(_dead_mask(s) & ~_anchored_mask(s)).astype(I32)


@jax.named_scope("compact")
def compact(s: DocState, ob_flag=None) -> DocState:
    """Evict segments whose winning remove is acked at or below min_seq.

    Reference zamboni.ts:33 — such segments are invisible to every legal
    perspective (refSeq >= minSeq), so dropping them is unobservable.
    Segments anchoring a live obliterate stay resident (their index position
    defines the obliterate's window for concurrent inserts).  ``ob_flag``
    gates the [OB,S] anchor-retention matrix (scalar; see apply_op).
    """
    if ob_flag is None:
        ob_flag = jnp.any(s.ob_key >= 0)
    alive = _alive(s)
    dead = _dead_mask(s)
    if isinstance(ob_flag, bool):
        anchored = _anchored_mask(s) if ob_flag else jnp.zeros_like(alive)
    else:
        anchored = jax.lax.cond(
            ob_flag, _anchored_mask, lambda s: jnp.zeros_like(alive), s
        )
    return _gather_keep(s, alive & ~(dead & ~anchored))


@jax.jit
def drop_squashed(s: DocState) -> DocState:
    """Drop squashed segments: pending insert later covered by a pending
    remove — under squash resubmission the pair cancels and the segment
    never materializes remotely (ref reSubmitCore(squash), channel.ts:160;
    mergetree_ref.RefMergeTree._squashed).  Obliterate anchors stay."""
    alive = _alive(s)
    pend_ins = s.ins_key >= LOCAL_BASE
    pend_rem = _any_tree(
        [(k >= LOCAL_BASE) & (k < NO_REMOVE) for k in s.rem_keys]
    )
    squashed = alive & pend_ins & pend_rem
    return _gather_keep(s, alive & ~(squashed & ~_anchored_mask(s)))


@jax.jit
def strip_stamp(s: DocState, key) -> DocState:
    """Erase every trace of the stamp ``key``: remove-slot stamps revert to
    NO_REMOVE and the matching obliterate record (if any) is freed.  Used
    when a pending op is retired without resubmission (its target content
    vanished during reconnect regeneration)."""
    hits = [k == key for k in s.rem_keys]
    ob_hit = s.ob_key == key
    return s._replace(
        rem_keys=tuple(
            jnp.where(h, NO_REMOVE, k) for h, k in zip(hits, s.rem_keys)
        ),
        rem_clients=tuple(
            jnp.where(h, -1, c) for h, c in zip(hits, s.rem_clients)
        ),
        ob_key=jnp.where(ob_hit, -1, s.ob_key),
    )


@jax.jit
def restamp(
    s: DocState,
    mask: jnp.ndarray,
    old_key,
    new_key,
    new_client,
    do_ins,
    do_rem,
    do_prop,
    do_ob,
) -> DocState:
    """Selectively rewrite stamp keys ``old_key`` -> ``new_key`` on the
    segments selected by ``mask`` ([S] bool), per stamp class (insert /
    remove / prop / obliterate-record).  ``new_client`` < 0 keeps clients.
    This is the device half of reconnect regeneration: the host plans the
    re-minted ops (kernel_backend.regenerate_pending) and re-stamps exactly
    the segments of each plan so every re-minted op acks independently
    (ref client.ts regeneratePendingOp mints new segment groups)."""
    rw_c = new_client >= 0
    ins_hit = do_ins & mask & (s.ins_key == old_key)
    rem_hits = [do_rem & mask & (k == old_key) for k in s.rem_keys]
    ob_hit = do_ob & (s.ob_key == old_key)
    return s._replace(
        ins_key=jnp.where(ins_hit, new_key, s.ins_key),
        ins_client=jnp.where(ins_hit & rw_c, new_client, s.ins_client),
        rem_keys=tuple(
            jnp.where(h, new_key, k) for h, k in zip(rem_hits, s.rem_keys)
        ),
        rem_clients=tuple(
            jnp.where(h & rw_c, new_client, c)
            for h, c in zip(rem_hits, s.rem_clients)
        ),
        prop_keys=tuple(
            jnp.where(do_prop & mask & (k == old_key), new_key, k)
            for k in s.prop_keys
        ),
        ob_key=jnp.where(ob_hit, new_key, s.ob_key),
        ob_client=jnp.where(ob_hit & rw_c, new_client, s.ob_client),
        # ob_preceding references follow the record's stamp rewrite (the
        # oracle mutates the shared Obliterate object in place).
        seg_obpre=jnp.where(
            do_ob & (s.seg_obpre == old_key), new_key, s.seg_obpre
        ),
    )


def set_min_seq(s: DocState, min_seq) -> DocState:
    """Advance the collab-window floor and release obliterates below it
    (ref Obliterates.setMinSeq)."""
    new_min = jnp.maximum(s.min_seq, jnp.asarray(min_seq, I32))
    expired = (s.ob_key >= 0) & (s.ob_key < LOCAL_BASE) & (s.ob_key <= new_min)
    return s._replace(
        min_seq=new_min,
        ob_key=jnp.where(expired, -1, s.ob_key),
    )


# --------------------------------------------------------------------------
# Host-side views (pull arrays off device; numpy)
# --------------------------------------------------------------------------

def _host_vis(s: DocState, ref_seq: int, view_client: int):
    nseg = int(s.nseg)
    ins_key = np.asarray(s.ins_key)[:nseg]
    ins_client = np.asarray(s.ins_client)[:nseg]
    rem_keys = np.stack([np.asarray(a)[:nseg] for a in s.rem_keys])
    rem_clients = np.stack([np.asarray(a)[:nseg] for a in s.rem_clients])
    ins_occ = (ins_key <= ref_seq) | (ins_client == view_client)
    # Padding slots (NO_REMOVE / client -1) must never match: a pure
    # observer legitimately views as client -1.
    rem_valid = rem_keys != NO_REMOVE
    rem_occ = (
        rem_valid & ((rem_keys <= ref_seq) | (rem_clients == view_client))
    ).any(axis=0)
    return nseg, ins_occ & ~rem_occ


def visible_text(
    s: DocState, ref_seq: int = ALL_ACKED, view_client: int = -3,
    raw: bool = False,
) -> str:
    """Materialize the perspective-visible text on the host.  Marker
    codepoints (the reserved U+E000..U+F8FF plane, dds/markers.py) are
    filtered here — markers hold positions but contribute no text, the
    reference's getText/getLength split.  ``raw=True`` keeps them so
    string indices equal positions."""
    from ..protocol.marker_plane import MARKER_CP_BASE, MARKER_CP_END

    nseg, vis = _host_vis(s, ref_seq, view_client)
    text = np.asarray(s.text)
    start = np.asarray(s.seg_start)[:nseg]
    length = np.asarray(s.seg_len)[:nseg]
    parts = [
        "".join(
            chr(c)
            for c in text[start[i] : start[i] + length[i]]
            if raw or not MARKER_CP_BASE <= c < MARKER_CP_END
        )
        for i in range(nseg)
        if vis[i]
    ]
    return "".join(parts)


def visible_length(s: DocState, ref_seq: int = ALL_ACKED, view_client: int = -3) -> int:
    """Perspective-visible character count without materializing the text
    (sum of visible segment lengths)."""
    nseg, vis = _host_vis(s, ref_seq, view_client)
    length = np.asarray(s.seg_len)[:nseg]
    return int(length[vis[:nseg]].sum()) if nseg else 0


def annotations(
    s: DocState, ref_seq: int = ALL_ACKED, view_client: int = -3
) -> list[dict[int, int]]:
    """Per visible character: {prop_slot: value} (differential-test view)."""
    nseg, vis = _host_vis(s, ref_seq, view_client)
    length = np.asarray(s.seg_len)[:nseg]
    prop_keys = np.stack([np.asarray(a)[:nseg] for a in s.prop_keys])
    prop_vals = np.stack([np.asarray(a)[:nseg] for a in s.prop_vals])
    out: list[dict[int, int]] = []
    for i in range(nseg):
        if not vis[i]:
            continue
        props = {
            p: int(prop_vals[p, i])
            for p in range(prop_keys.shape[0])
            if prop_keys[p, i] >= 0
        }
        out.extend(props for _ in range(length[i]))
    return out
