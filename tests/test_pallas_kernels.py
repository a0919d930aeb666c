"""Pallas long-document position resolution, differentially against the
jnp oracle, and the text pool's strip write against numpy (interpreter mode —
tests run on the CPU mesh)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fluidframework_tpu.ops.pallas_kernels import (
    LANES,
    STRIP_ROW_LANES,
    STRIP_TILE_ROWS,
    SUBLANES,
    resolve_positions_blocked,
    resolve_positions_pallas,
    resolve_positions_reference,
    text_strip_width,
    write_text_strips,
    write_text_strips_at,
)


def random_case(rng, n_segs, n_queries, max_len=9, vis_p=0.7):
    lens = rng.integers(0, max_len, size=n_segs).astype(np.int32)
    lens = np.where(rng.random(n_segs) < vis_p, lens, 0).astype(np.int32)
    total = int(lens.sum())
    qs = rng.integers(0, max(total, 1) + 3, size=n_queries).astype(np.int32)
    return lens, qs


@pytest.mark.parametrize("n_segs", [1, 7, 128, 1024, 1500, 4096])
def test_pallas_resolve_matches_reference(n_segs):
    rng = np.random.default_rng(n_segs)
    for trial in range(4):
        lens, qs = random_case(rng, n_segs, n_queries=37)
        ri, ro, rh = resolve_positions_reference(lens, qs)
        pi, po, ph = resolve_positions_pallas(lens, qs, interpret=True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(pi))
        np.testing.assert_array_equal(np.asarray(ro), np.asarray(po))
        np.testing.assert_array_equal(np.asarray(rh), np.asarray(ph))


def test_pallas_resolve_misses_are_zero():
    lens = np.asarray([3, 0, 2], np.int32)  # total visible = 5
    qs = np.asarray([0, 2, 3, 4, 5, 99], np.int32)
    pi, po, ph = resolve_positions_pallas(lens, qs, interpret=True)
    ri, ro, rh = resolve_positions_reference(lens, qs)
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(ph), np.asarray(rh))
    # In-range queries land in the right segment with the right offset
    # (queries 0,2 in segment 0; 3,4 in segment 2; 5 is one past the end).
    assert list(np.asarray(pi))[:4] == [0, 0, 2, 2]
    assert list(np.asarray(po))[:4] == [0, 2, 0, 1]
    # Misses (q >= total) report (0, 0).
    assert int(pi[4]) == 0 and int(po[4]) == 0 and int(ph[4]) == 0
    assert int(pi[5]) == 0 and int(po[5]) == 0 and int(ph[5]) == 0
    assert list(np.asarray(ph))[:4] == [1, 1, 1, 1]


def test_pallas_resolve_all_invisible():
    lens = np.zeros(256, np.int32)
    qs = np.asarray([0, 1, 2], np.int32)
    pi, po, ph = resolve_positions_pallas(lens, qs, interpret=True)
    assert not np.asarray(pi).any() and not np.asarray(po).any()
    assert not np.asarray(ph).any()


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_resolve_triple_parity_fuzz(seed):
    """The three entries — ``resolve_positions_pallas`` (interpret),
    ``resolve_positions_blocked`` (the backend-dispatching entry the
    segment-parallel kernel calls behind its flag), and
    ``resolve_positions_reference`` (the oracle) — agree on random
    perspectives, out-of-range and NEGATIVE query positions included
    (the seg path queries local coordinates that go negative for earlier
    shards' positions)."""
    rng = np.random.default_rng(seed)
    for _trial in range(6):
        # Sizes draw from a fixed palette: resolve_positions_* jit-compile
        # per (S, Q) signature, so free-random sizes would turn the fuzz
        # into a compile benchmark.
        n_segs = int(rng.choice([1, 65, 517, 899]))
        lens, qs = random_case(rng, n_segs, n_queries=41)
        # Mix in out-of-range high and negative queries deliberately.
        extra = np.asarray(
            [-1, -7, int(lens.sum()), int(lens.sum()) + 5], np.int32
        )
        qs = np.concatenate([qs, extra])
        ri, ro, rh = resolve_positions_reference(lens, qs)
        bi, bo, bh = resolve_positions_blocked(lens, qs)
        pi, po, ph = resolve_positions_pallas(lens, qs, interpret=True)
        for got_i, got_o, got_h in ((bi, bo, bh), (pi, po, ph)):
            np.testing.assert_array_equal(np.asarray(ri), np.asarray(got_i))
            np.testing.assert_array_equal(np.asarray(ro), np.asarray(got_o))
            np.testing.assert_array_equal(
                np.asarray(rh).astype(np.int32),
                np.asarray(got_h).astype(np.int32),
            )
        # Misses never report a hit; hits land inside their segment.
        hits = np.asarray(rh).astype(bool)
        if hits.any():
            gi = np.asarray(ri)[hits]
            off = np.asarray(ro)[hits]
            assert (off >= 0).all() and (off < lens[gi]).all()
        assert not np.asarray(rh)[np.asarray(qs) < 0].any()


def test_blocked_is_reference_off_tpu():
    """On non-TPU backends the blocked entry must BE the jnp oracle (the
    CPU test mesh semantics the segment-parallel flag relies on)."""
    rng = np.random.default_rng(0)
    lens, qs = random_case(rng, 333, 17)
    bi, bo, bh = resolve_positions_blocked(lens, qs)
    ri, ro, rh = resolve_positions_reference(lens, qs)
    np.testing.assert_array_equal(np.asarray(bi), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(bo), np.asarray(ro))
    np.testing.assert_array_equal(np.asarray(bh), np.asarray(rh))


# ------------------------------------------------- the pool's strip write
@pytest.mark.parametrize("capacity,window", [(1024, 256), (512, 64), (96, 256),
                                             (200, 64)])
@pytest.mark.parametrize("n_docs", [
    1, 7, SUBLANES, 3 * SUBLANES + 5, STRIP_TILE_ROWS * SUBLANES + 19])
def test_strip_write_merges_one_strip_a_document(n_docs, capacity, window):
    """``write_text_strips`` == numpy, element for element: batches under a
    tile row (plain updates only), whole tile rows (the kernel, interpreted
    here), a second, ragged grid step, and a tail after it; strips of whole
    lanes at starts all over the row, the last lane included, rows that are
    their own strip (narrower than the window), and pools not made of lanes
    (plain updates for every document: Mosaic slices whole tiles)."""
    rng = np.random.default_rng([n_docs, capacity, window])
    width = text_strip_width(capacity, window)
    if capacity % LANES == 0 and capacity > window + 2 * LANES:
        assert width % LANES == 0 and window + LANES <= width < capacity
    else:
        assert width == capacity
    starts = (rng.integers(0, (capacity - width) // LANES + 1, n_docs)
              * LANES).astype(np.int32)
    starts[-1] = capacity - width
    pool = rng.integers(-1000, 0, (n_docs, capacity)).astype(np.int32)
    new = rng.integers(1, 1000, (n_docs, width)).astype(np.int32)
    mask = (rng.random((n_docs, width)) < 0.3).astype(np.int32)
    mask[0] = 0
    want = pool.copy()
    for d in range(n_docs):
        strip = want[d, starts[d]:starts[d] + width]
        strip[mask[d] != 0] = new[d][mask[d] != 0]
    got = jax.jit(write_text_strips)(
        jnp.asarray(pool), jnp.asarray(starts), jnp.asarray(new),
        jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert not np.array_equal(want, pool) or n_docs == 1


@pytest.mark.parametrize("n_docs,capacity,window", [
    (64, 1024, 256), (24, 512, 64), (8, 96, 256), (16, 200, 64),
    (21, 1024, 256)],
    ids=["strips", "narrow_strips", "whole_rows", "pool_not_of_lanes",
         "pool_not_of_tile_rows"])
@pytest.mark.parametrize("lanes", [1, 3, SUBLANES, 33, 300])
def test_strip_write_at_rows_merges_one_strip_a_lane(n_docs, capacity, window,
                                                     lanes):
    """``write_text_strips_at`` == numpy, element for element: rows in any
    order and all over the pool (neighbours in a tile row among them), pad
    lanes that repeat the last live row with nothing in their mask, a lane
    count past one grid step (300 > ``STRIP_ROW_LANES``), strips of whole
    lanes, rows that are their own strip, and the pools the kernel does not
    take (not made of whole lanes, or not of whole tile rows: plain updates
    one lane after the other).  Rows no lane names keep every bit."""
    assert STRIP_ROW_LANES < 300
    rng = np.random.default_rng([n_docs, capacity, window, lanes])
    width = text_strip_width(capacity, window)
    live = max(1, min(lanes, n_docs) - (lanes > 2) * min(lanes, n_docs) // 4)
    rows = rng.permutation(n_docs)[:live]
    if live >= 2:
        rows[1] = rows[0] ^ 1                       # one tile row, two lanes
        rows = np.array(list(dict.fromkeys(rows.tolist())))
        live = len(rows)
    rows = np.concatenate(
        [rows, np.full(lanes - live, rows[-1])]).astype(np.int32)
    starts = (rng.integers(0, (capacity - width) // LANES + 1, lanes)
              * LANES).astype(np.int32)
    starts[0] = capacity - width
    pool = rng.integers(-1000, 0, (n_docs, capacity)).astype(np.int32)
    new = rng.integers(1, 1000, (lanes, width)).astype(np.int32)
    mask = (rng.random((lanes, width)) < 0.3).astype(np.int32)
    mask[live:] = 0
    if live > 2:
        mask[2] = 0                                 # a live lane with no write
    want = pool.copy()
    for j in range(lanes):
        strip = want[rows[j], starts[j]:starts[j] + width]
        strip[mask[j] != 0] = new[j][mask[j] != 0]
    got = jax.jit(write_text_strips_at)(
        jnp.asarray(pool), jnp.asarray(rows), jnp.asarray(starts),
        jnp.asarray(new), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert not np.array_equal(want, pool)
    untouched = np.setdiff1d(np.arange(n_docs), rows)
    np.testing.assert_array_equal(np.asarray(got)[untouched], pool[untouched])
