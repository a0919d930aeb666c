"""Columnar SharedMatrix kernel: permutation vectors + batched cell writes.

Reference parity: packages/dds/matrix/src/matrix.ts processMessagesCore
(position->handle resolution through the permutation merge-trees under the
op's perspective, then LWW or FWW cell conflict — shouldSetCellBasedOnFWW,
matrix.ts:987).

Re-uses the merge-tree kernel for the row/col permutation vectors: the
"text pool" stores handle ids instead of codepoints, and handle allocation
is deterministic-by-sequencing (a row-insert op applied at seq S allocates
the next ``count`` handles from the replica's counter — identical on every
replica because ops apply in total order).

Cell state is dense [HR, HC] int32 (values host-interned), with last-write
(seq, client) for the FWW rule.  This is the sequenced-replica path (the
DocBatchEngine analog for matrices); client-side pending overlay lives in
``dds/shared_matrix.py``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import mergetree_kernel as mk

I32 = jnp.int32

ERR_HANDLE_RANGE = 16


class MatrixOpKind:
    NOOP = 0
    INSERT_ROWS = 1
    INSERT_COLS = 2
    REMOVE_ROWS = 3
    REMOVE_COLS = 4
    SET_CELL = 5


# Op row layout (int32[8]):
#   0 kind | 1 seq | 2 client | 3 ref_seq | 4 pos1 | 5 pos2/count | 6 a | 7 b
# SET_CELL: pos1=row pos2=col a=value b=fww_flag
# INSERT_*: pos1=pos  pos2=count
# REMOVE_*: pos1=pos  pos2=count
MATRIX_OP_FIELDS = 8


class MatrixState(NamedTuple):
    rows: mk.DocState
    cols: mk.DocState
    next_row_handle: jnp.ndarray  # int32 scalar
    next_col_handle: jnp.ndarray  # int32 scalar
    cell_val: jnp.ndarray         # int32[HR, HC]
    cell_present: jnp.ndarray     # int32[HR, HC]
    cell_seq: jnp.ndarray         # int32[HR, HC] last write seq (0 = none)
    cell_client: jnp.ndarray      # int32[HR, HC] last write short client
    fww: jnp.ndarray              # int32 scalar 0/1
    error: jnp.ndarray            # int32 scalar


def init_state(
    max_rows: int = 256,
    max_cols: int = 256,
    max_segments: int = 128,
    remove_slots: int = 4,
) -> MatrixState:
    return MatrixState(
        rows=mk.init_state(max_segments, remove_slots, 1, max_rows),
        cols=mk.init_state(max_segments, remove_slots, 1, max_cols),
        next_row_handle=jnp.zeros((), I32),
        next_col_handle=jnp.zeros((), I32),
        cell_val=jnp.zeros((max_rows, max_cols), I32),
        cell_present=jnp.zeros((max_rows, max_cols), I32),
        cell_seq=jnp.zeros((max_rows, max_cols), I32),
        cell_client=jnp.full((max_rows, max_cols), -1, I32),
        fww=jnp.zeros((), I32),
        error=jnp.zeros((), I32),
    )


def _resolve_handle(perm: mk.DocState, pos, ref_seq, client):
    """Position -> handle under the op's perspective (ref adjustPosition)."""
    vis = mk._visible(perm, ref_seq, client)
    vlen, excl = mk._vis_lengths(perm, vis)
    inside = vis & (excl <= pos) & (pos < excl + vlen)
    k = mk._first_true(inside, jnp.asarray(0, I32))
    found = jnp.any(inside)
    off = pos - excl[k]
    handle = perm.text[perm.seg_start[k] + off]
    return jnp.where(found, handle, -1), found


def _perm_insert(perm: mk.DocState, next_handle, op):
    """Insert ``count`` handles at pos: a merge-tree insert whose payload is
    the next handle ids (capacity = the text pool, entries = handles)."""
    count = op[5]
    T = perm.text.shape[0]
    payload = next_handle + jnp.arange(T, dtype=I32)  # first `count` used
    ins_op = jnp.stack(
        [jnp.asarray(mk.OpKind.INSERT, I32), op[1], op[2], op[3], op[4],
         jnp.zeros((), I32), count, jnp.zeros((), I32)]
    )
    # Permutation vectors never carry obliterates: ob machinery stays off.
    new_perm = mk.apply_op(perm, ins_op, payload, False)
    return new_perm, next_handle + count


def _perm_remove(perm: mk.DocState, op):
    rem_op = jnp.stack(
        [jnp.asarray(mk.OpKind.REMOVE, I32), op[1], op[2], op[3], op[4],
         op[4] + op[5], jnp.zeros((), I32), jnp.zeros((), I32)]
    )
    return mk.apply_op(perm, rem_op, jnp.zeros((1,), I32), False)


def apply_op(s: MatrixState, op: jnp.ndarray) -> MatrixState:
    kind = op[0]

    def do_insert_rows(s, op):
        rows, nh = _perm_insert(s.rows, s.next_row_handle, op)
        over = nh > s.cell_val.shape[0]
        return s._replace(
            rows=rows, next_row_handle=nh,
            error=s.error | jnp.where(over, ERR_HANDLE_RANGE, 0),
        )

    def do_insert_cols(s, op):
        cols, nh = _perm_insert(s.cols, s.next_col_handle, op)
        over = nh > s.cell_val.shape[1]
        return s._replace(
            cols=cols, next_col_handle=nh,
            error=s.error | jnp.where(over, ERR_HANDLE_RANGE, 0),
        )

    def do_remove_rows(s, op):
        return s._replace(rows=_perm_remove(s.rows, op))

    def do_remove_cols(s, op):
        return s._replace(cols=_perm_remove(s.cols, op))

    def do_set_cell(s, op):
        seq, client, ref_seq = op[1], op[2], op[3]
        value, fww_flag = op[6], op[7]
        fww = jnp.maximum(s.fww, fww_flag)
        rh, rfound = _resolve_handle(s.rows, op[4], ref_seq, client)
        ch, cfound = _resolve_handle(s.cols, op[5], ref_seq, client)
        ok = rfound & cfound
        # FWW: first write, same client, or ref_seq >= last write's seq.
        last_seq = s.cell_seq[rh, ch]
        last_client = s.cell_client[rh, ch]
        should = jnp.where(
            fww > 0,
            (last_seq == 0) | (last_client == client) | (ref_seq >= last_seq),
            True,
        )
        write = ok & should
        rh_c = jnp.maximum(rh, 0)
        ch_c = jnp.maximum(ch, 0)
        upd = lambda arr, v: arr.at[rh_c, ch_c].set(jnp.where(write, v, arr[rh_c, ch_c]))
        return s._replace(
            cell_val=upd(s.cell_val, value),
            cell_present=upd(s.cell_present, 1),
            cell_seq=upd(s.cell_seq, seq),
            cell_client=upd(s.cell_client, client),
            fww=fww,
            error=s.error | jnp.where(~ok, ERR_HANDLE_RANGE, 0),
        )

    branches = [
        lambda s, op: s,
        do_insert_rows,
        do_insert_cols,
        do_remove_rows,
        do_remove_cols,
        do_set_cell,
    ]
    return jax.lax.switch(kind, branches, s, op)


def apply_ops(s: MatrixState, ops: jnp.ndarray) -> MatrixState:
    """Apply a [B, 8] batch of sequenced matrix ops in order."""

    def step(carry, op):
        return apply_op(carry, op), None

    out, _ = jax.lax.scan(step, s, ops)
    return out


apply_ops_fleet = jax.vmap(apply_ops)


# --------------------------------------------------------------------------
# Host views
# --------------------------------------------------------------------------

def visible_handles(perm: mk.DocState, ref_seq: int = None, view_client: int = -3):
    from ..protocol.stamps import ALL_ACKED

    ref = ALL_ACKED if ref_seq is None else ref_seq
    nseg, vis = mk._host_vis(perm, ref, view_client)
    text = np.asarray(perm.text)
    start = np.asarray(perm.seg_start)[:nseg]
    length = np.asarray(perm.seg_len)[:nseg]
    out = []
    for i in range(nseg):
        if vis[i]:
            out.extend(int(h) for h in text[start[i] : start[i] + length[i]])
    return out


def to_grid(s: MatrixState):
    """Materialized consensus grid (None for unset cells)."""
    rows = visible_handles(s.rows)
    cols = visible_handles(s.cols)
    val = np.asarray(s.cell_val)
    present = np.asarray(s.cell_present)
    return [
        [int(val[rh, ch]) if present[rh, ch] else None for ch in cols]
        for rh in rows
    ]


# --------------------------------------------------------------------------
# Summary-record codecs (the DDS-level checkpoint format matrix fleets were
# missing — same record shape as the string/tree engines: a JSON summary a
# cold consumer can boot from, replaying only the post-summary tail)
# --------------------------------------------------------------------------

def _perm_to_json(perm: mk.DocState) -> dict:
    """Exact dump of a permutation merge-tree (full arrays: seg layout,
    stamps, uids, remove slots — a restored perm must resolve every future
    position identically, including tiebreak/perspective state the
    canonical summary walk would normalize away)."""
    out = {}
    for name, arr in perm._asdict().items():
        if isinstance(arr, tuple):
            out[name] = [np.asarray(a).tolist() for a in arr]
        else:
            out[name] = np.asarray(arr).tolist()
    return out


def _perm_from_json(d: dict) -> mk.DocState:
    kw = {}
    for name, val in d.items():
        if name in ("rem_keys", "rem_clients", "prop_keys", "prop_vals"):
            kw[name] = tuple(jnp.asarray(v, I32) for v in val)
        else:
            kw[name] = jnp.asarray(val, I32)
    return mk.DocState(**kw)


def state_to_summary(s: MatrixState) -> dict:
    """MatrixState -> summary JSON: exact perm dumps + the sparse touched
    cell set + handle counters.  ``summary_to_state`` reproduces the state
    arrays bit-for-bit (given the same geometry)."""
    val = np.asarray(s.cell_val)
    present = np.asarray(s.cell_present)
    seq = np.asarray(s.cell_seq)
    client = np.asarray(s.cell_client)
    touched = np.nonzero((present != 0) | (seq != 0) | (client != -1) | (val != 0))
    return {
        "shape": [int(val.shape[0]), int(val.shape[1])],
        "rows": _perm_to_json(s.rows),
        "cols": _perm_to_json(s.cols),
        "next_row_handle": int(s.next_row_handle),
        "next_col_handle": int(s.next_col_handle),
        "cells": [
            [int(r), int(c), int(val[r, c]), int(present[r, c]),
             int(seq[r, c]), int(client[r, c])]
            for r, c in zip(*touched)
        ],
        "fww": int(s.fww),
    }


def summary_to_state(summary: dict) -> MatrixState:
    """Summary JSON -> a MatrixState identical to the one summarized."""
    HR, HC = summary["shape"]
    cell_val = np.zeros((HR, HC), np.int32)
    cell_present = np.zeros((HR, HC), np.int32)
    cell_seq = np.zeros((HR, HC), np.int32)
    cell_client = np.full((HR, HC), -1, np.int32)
    for r, c, v, pres, sq, cl in summary["cells"]:
        if not (0 <= r < HR and 0 <= c < HC):
            raise ValueError(f"summary cell ({r},{c}) outside shape {HR}x{HC}")
        cell_val[r, c], cell_present[r, c] = v, pres
        cell_seq[r, c], cell_client[r, c] = sq, cl
    return MatrixState(
        rows=_perm_from_json(summary["rows"]),
        cols=_perm_from_json(summary["cols"]),
        next_row_handle=jnp.asarray(summary["next_row_handle"], I32),
        next_col_handle=jnp.asarray(summary["next_col_handle"], I32),
        cell_val=jnp.asarray(cell_val),
        cell_present=jnp.asarray(cell_present),
        cell_seq=jnp.asarray(cell_seq),
        cell_client=jnp.asarray(cell_client),
        fww=jnp.asarray(summary["fww"], I32),
        error=jnp.zeros((), I32),
    )
