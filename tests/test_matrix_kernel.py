"""Differential: matrix TPU kernel vs host SharedMatrix oracle."""

import json
import os
import random
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fluidframework_tpu.dds.shared_matrix import SharedMatrix
from fluidframework_tpu.ops import matrix_kernel as mxk
from fluidframework_tpu.server.local_service import LocalDocument

from test_shared_matrix import make_matrices, pump

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

import matrix_reference  # noqa: E402


_FLEET_STEP = jax.jit(mxk.apply_matrix_fleet)


def _one_table_fleet_step(state, ops):
    """``ops`` through the fleet step (one body a row) as a fleet of one
    table, in slices of 8 rows padded with NOOPs."""
    fleet = jax.tree.map(lambda x: x[None], state)
    for i in range(0, ops.shape[0], 8):
        rows = np.zeros((1, 8, mxk.MATRIX_OP_FIELDS), np.int32)
        rows[0, : len(ops[i : i + 8])] = ops[i : i + 8]
        fleet, _wrote = _FLEET_STEP(fleet, jnp.asarray(rows))
    return jax.tree.map(lambda x: x[0], fleet)


STEPS = {"apply_ops": mxk.apply_ops, "fleet_step": _one_table_fleet_step}


def replay_through_kernel(doc: LocalDocument, value_intern, step="apply_ops"):
    """Encode the sequenced op log into kernel ops and apply in one batch."""
    quorum = {}
    ops = []
    for msg in doc.sequencer.log:
        if msg.type == "join":
            quorum[msg.contents["clientId"]] = msg.contents["short"]
            continue
        if msg.type != "op":
            continue
        c = msg.contents
        client = quorum[msg.client_id]
        kindmap = {
            "insertRows": mxk.MatrixOpKind.INSERT_ROWS,
            "insertCols": mxk.MatrixOpKind.INSERT_COLS,
            "removeRows": mxk.MatrixOpKind.REMOVE_ROWS,
            "removeCols": mxk.MatrixOpKind.REMOVE_COLS,
        }
        if c["type"] in kindmap:
            ops.append(
                [kindmap[c["type"]], msg.seq, client, msg.ref_seq,
                 c["pos"], c["count"], 0, 0]
            )
        elif c["type"] == "set":
            ops.append(
                [mxk.MatrixOpKind.SET_CELL, msg.seq, client, msg.ref_seq,
                 c["row"], c["col"], value_intern(c["value"]),
                 1 if c.get("fwwMode") else 0]
            )
    state = mxk.init_state(max_rows=64, max_cols=64, max_segments=128)
    if ops:
        state = STEPS[step](state, jnp.asarray(np.array(ops, np.int32)))
    return state


@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("seed", range(6))
def test_matrix_kernel_matches_oracle(seed, step):
    rng = random.Random(seed)
    doc = LocalDocument("d")
    ms = make_matrices(doc, rng.randint(2, 3))
    for _round in range(rng.randint(3, 6)):
        for m in ms:
            for _ in range(rng.randint(0, 3)):
                r = rng.random()
                nrows = len(m.rows.handles(2**30 - 1, m.short_client))
                ncols = len(m.cols.handles(2**30 - 1, m.short_client))
                if r < 0.3 or nrows == 0:
                    m.insert_rows(rng.randint(0, nrows), rng.randint(1, 2))
                elif r < 0.5 or ncols == 0:
                    m.insert_cols(rng.randint(0, ncols), rng.randint(1, 2))
                elif r < 0.58 and nrows > 1:
                    m.remove_rows(rng.randint(0, nrows - 1), 1)
                elif r < 0.64 and ncols > 1:
                    m.remove_cols(rng.randint(0, ncols - 1), 1)
                elif ncols > 0 and nrows > 0:
                    m.set_cell(
                        rng.randint(0, nrows - 1), rng.randint(0, ncols - 1),
                        rng.randint(1, 999),
                    )
            if rng.random() < 0.7:
                for msg in m.take_outbox():
                    doc.submit(msg)
        doc.process_some(rng.randint(0, doc.pending_count))
    pump(doc, ms)

    state = replay_through_kernel(doc, value_intern=lambda v: int(v), step=step)
    assert int(state.error) == 0
    kernel_grid = mxk.to_grid(state)
    oracle_grid = ms[0].to_grid()
    # Handles differ between implementations only if allocation order
    # diverged; grids must be identical cell-for-cell.
    assert kernel_grid == oracle_grid, f"seed {seed} diverged"


@pytest.mark.parametrize("step", sorted(STEPS))
def test_fww_kernel_semantics(step):
    doc = LocalDocument("d")
    a, b = make_matrices(doc, 2)
    a.insert_rows(0, 1)
    a.insert_cols(0, 1)
    pump(doc, [a, b])
    a.switch_to_fww()
    b.switch_to_fww()
    a.set_cell(0, 0, 7)
    b.set_cell(0, 0, 8)  # concurrent loser under FWW
    pump(doc, [a, b])
    state = replay_through_kernel(doc, value_intern=lambda v: int(v), step=step)
    assert mxk.to_grid(state) == a.to_grid() == [[7]]


def _perm_entries(perm):
    """A permutation vector handle by handle, in document order: (handle,
    insert seq, remove seqs)."""
    n = int(perm.nseg)
    text = np.asarray(perm.text)
    rem = [np.asarray(r)[:n] for r in perm.rem_keys]
    out = []
    for i in range(n):
        start, length = int(perm.seg_start[i]), int(perm.seg_len[i])
        removes = sorted(int(r[i]) for r in rem if r[i] != mxk.mk.NO_REMOVE)
        out += [(int(h), int(perm.ins_key[i]), removes)
                for h in text[start:start + length]]
    return out


def _reference_entries(perm):
    return [(e.handle, e.ins_seq, sorted(s for s, _c in e.removes))
            for e in perm.entries]


@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("vector", ["rows", "cols"])
def test_inserts_at_a_split_and_on_a_boundary_match_the_plain_reference(
        vector, step):
    """Row and column inserts that land inside a run of handles inserted
    together (the run's segment is split and the new one goes between its
    halves, in the same rewrite of the vector's columns), on the boundary
    between two runs, at both ends, and concurrently at one place, against
    the benchmark's plain reference: every handle of the vector with its
    stamps, in order, and the grid."""
    doc = LocalDocument("d")
    a, b = make_matrices(doc, 2)
    ins = {"rows": SharedMatrix.insert_rows, "cols": SharedMatrix.insert_cols}
    rem = {"rows": SharedMatrix.remove_rows, "cols": SharedMatrix.remove_cols}
    other = "cols" if vector == "rows" else "rows"
    ins[other](a, 0, 2)
    ins[vector](a, 0, 6)            # one run: handles 0-5
    pump(doc, [a, b])
    ins[vector](a, 3, 2)            # splits the run
    pump(doc, [a, b])
    ins[vector](b, 3, 1)            # on the boundary the split left
    ins[vector](a, 5, 1)            # concurrent: the other boundary
    pump(doc, [a, b])
    ins[vector](a, 1, 1)            # splits the left half again
    ins[vector](b, 1, 2)            # ... and so does b, at the same place
    pump(doc, [a, b])
    rem[vector](b, 2, 3)            # a remove whose ends split two runs
    ins[vector](a, 4, 1)            # concurrent, inside the removed range
    pump(doc, [a, b])
    ins[vector](a, 0, 1)            # both ends
    ins[vector](b, b.row_count if vector == "rows" else b.col_count, 1)
    pump(doc, [a, b])
    a.set_cell(1, 1, 42)
    pump(doc, [a, b])

    state = replay_through_kernel(doc, value_intern=int, step=step)
    assert int(state.error) == 0
    table = matrix_reference.replay(
        json.loads(m.wire_line()) for m in doc.sequencer.log)
    for name in ("rows", "cols"):
        got = _perm_entries(getattr(state, name))
        assert got == _reference_entries(getattr(table, name)), name
    assert len(_perm_entries(getattr(state, vector))) == 16
    # Splits happened: more segments than inserts of the vector.
    assert int(getattr(state, vector).nseg) > 9
    assert mxk.to_grid(state) == table.grid() == a.to_grid()
