"""Pallas TPU kernels: the long-document position search and the step's
text-pool write (``write_text_strips`` for a fleet-wide step, which owns every
row of the pool in order; ``write_text_strips_at`` for a cohort step, which
owns a few rows anywhere; both at the end of the file).

Position resolution over a long document asks: for each query position q
(perspective-visible coordinates), which segment contains q and at what
offset? The jnp form materializes an [Q, S] membership matrix
(parallel/long_doc.py _resolve) — fine for fleet docs (S ~ 2k), but a
long-document shard holds 100k+ segments and [Q, S] becomes an HBM-sized
intermediate. The Pallas kernel streams the segment axis through VMEM in
blocks, keeping the working set at [Q, BLOCK] and writing each query's hit
exactly once — the classic memory-bound fusion the guide's "grid over the
long axis, accumulate into a replicated output block" pattern covers.

``resolve_positions_blocked`` is the public entry: the Pallas kernel on a
TPU, the jnp form on backends Mosaic does not target (tests run the kernel
in interpret mode as well, differentially against the jnp form;
``chip_smoke.py`` compiles it on the chip against the same reference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32 = jnp.int32

BLOCK = 1024  # segment-axis VMEM block (8 sublanes x 128 lanes, int32)


def _resolve_kernel(pos_ref, prefix_ref, lens_ref, idx_ref, off_ref, hit_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        idx_ref[:] = jnp.zeros_like(idx_ref)
        off_ref[:] = jnp.zeros_like(off_ref)
        hit_ref[:] = jnp.zeros_like(hit_ref)

    # Load as [1, N] rows and reshape explicitly: fancy-indexing with
    # newaxis lowers to a gather Mosaic rejects.
    prefix = prefix_ref[:].reshape(1, -1)   # [1, BLOCK]
    lens = lens_ref[:].reshape(1, -1)       # [1, BLOCK]
    pos = pos_ref[:].reshape(-1, 1)         # [Q, 1]
    delta = pos - prefix                    # [Q, BLOCK]
    inside = (delta >= 0) & (delta < lens)
    # Exactly one segment contains each in-range query, so masked maxes
    # extract its local index and offset without any dynamic gather
    # (Mosaic-lowerable, unlike prefix[local]).
    cols = jax.lax.broadcasted_iota(I32, inside.shape, 1)
    local = jnp.max(jnp.where(inside, cols, -1), axis=1).reshape(1, -1)
    off_local = jnp.max(jnp.where(inside, delta, 0), axis=1).reshape(1, -1)
    hit = local >= 0
    base = (b * BLOCK).astype(I32)
    idx_ref[:] = jnp.where(hit, base + local, idx_ref[:])
    off_ref[:] = jnp.where(hit, off_local, off_ref[:])
    hit_ref[:] = jnp.where(hit, jnp.ones_like(hit_ref), hit_ref[:])


def _pad_to(x: jnp.ndarray, n: int, fill) -> jnp.ndarray:
    return jnp.pad(x, (0, n - x.shape[0]), constant_values=fill)


@functools.partial(jax.jit, static_argnames=("interpret",))
def resolve_positions_pallas(
    lens: jnp.ndarray,       # int32[S] visible lengths (0 = invisible)
    positions: jnp.ndarray,  # int32[Q] query positions
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(segment index, offset, hit) per query; (0, 0, 0) for out-of-range
    queries. Streams the segment axis in VMEM blocks instead of
    materializing [Q, S]."""
    S = lens.shape[0]
    Q = positions.shape[0]
    S_pad = -(-S // BLOCK) * BLOCK
    Q_pad = max(-(-Q // 128) * 128, 128)
    prefix = jnp.cumsum(lens) - lens
    # Padded tail segments get length 0 at prefix "total": never a hit.
    lens_p = _pad_to(lens.astype(I32), S_pad, 0)
    prefix_p = _pad_to(prefix.astype(I32), S_pad, 2**31 - 1)
    pos_p = _pad_to(positions.astype(I32), Q_pad, -1)

    grid = (S_pad // BLOCK,)
    idx, off, hit = pl.pallas_call(
        _resolve_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q_pad), lambda b: (0, 0)),
            pl.BlockSpec((1, BLOCK), lambda b: (0, b)),
            pl.BlockSpec((1, BLOCK), lambda b: (0, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q_pad), lambda b: (0, 0)),
            pl.BlockSpec((1, Q_pad), lambda b: (0, 0)),
            pl.BlockSpec((1, Q_pad), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Q_pad), I32),
            jax.ShapeDtypeStruct((1, Q_pad), I32),
            jax.ShapeDtypeStruct((1, Q_pad), I32),
        ],
        interpret=interpret,
    )(pos_p[None, :], prefix_p[None, :], lens_p[None, :])
    return idx[0, :Q], off[0, :Q], hit[0, :Q]


def resolve_positions_reference(
    lens: jnp.ndarray, positions: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The jnp [Q, S] form (long_doc._resolve's local computation) — the
    fallback and the differential oracle for the Pallas kernel."""
    prefix = jnp.cumsum(lens) - lens
    q = positions[:, None]
    inside = (q >= prefix[None, :]) & (q < (prefix + lens)[None, :])
    local = jnp.argmax(inside, axis=1).astype(I32)
    hit = jnp.any(inside, axis=1)
    idx = jnp.where(hit, local, 0)
    off = jnp.where(hit, positions - prefix[local], 0)
    return idx.astype(I32), off.astype(I32), hit.astype(I32)


def resolve_positions_blocked(
    lens: jnp.ndarray, positions: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Backend-dispatching entry: the Pallas kernel on TPU (O(Q*BLOCK) VMEM
    instead of an [Q, S] HBM intermediate), the jnp form elsewhere (CPU
    test meshes; Mosaic has no CPU target).  On a v5e the kernel compiles
    and matches the jnp form at 256 queries x 262,144 segments, but was
    not faster there: 2.4-2.6 ms against 1.6-2.1 ms per call, input upload
    included (chip run, PR 21 — PERF.md)."""
    if jax.default_backend() == "tpu":
        return resolve_positions_pallas(lens, positions)
    return resolve_positions_reference(lens, positions)


# ----------------------------------------------- the text pool's strip write
LANES, SUBLANES = 128, 8    # the (8, 128) tile of an int32 array in HBM
STRIP_TILE_ROWS = 64        # tile rows (of 8 documents) per grid step, at most
STRIP_VMEM_BYTES = 8 << 20  # ... and as many as fit this much VMEM
STRIP_ROW_LANES = 256       # cohort lanes per grid step, at most (same fit)


def text_strip_width(capacity: int, window: int) -> int:
    """Width of the lane-aligned strip that holds any ``window`` consecutive
    elements of a ``capacity``-wide pool row: the window rounded up to whole
    lanes plus one more, or the whole row where that is no narrower (or the
    row is not made of whole lanes)."""
    width = -(-window // LANES) * LANES + LANES
    return width if capacity % LANES == 0 and width < capacity else capacity


def _strip_kernel(start_ref, new_ref, mask_ref, pool_in, pool, buf, sem, *,
                  tile_rows: int):
    """One grid step: up to ``STRIP_TILE_ROWS`` tile rows of the pool.  A DMA
    moves whole (8, 128) tiles, so a document's strip travels with the seven
    other rows of its tile row, and the eight documents of a tile row take
    their turns one after another (turn r: row r of every tile row of the
    block, all their copies in flight together)."""
    del pool_in  # the same buffer as ``pool`` (input_output_aliases)
    block, _, width = buf.shape
    base = pl.program_id(0) * block
    n = jnp.minimum(block, tile_rows - base)

    def each(fn):
        jax.lax.fori_loop(0, n, lambda t, c: (fn(t), c)[1], 0)

    def copy(t, r, back: bool):
        first = pl.multiple_of((base + t) * SUBLANES, SUBLANES)
        hbm = pool.at[pl.ds(first, SUBLANES)]
        if width < pool.shape[1]:    # else the strip is the row (start 0)
            at = pl.multiple_of(start_ref[(base + t) * SUBLANES + r], LANES)
            hbm = hbm.at[:, pl.ds(at, width)]
        if back:
            return pltpu.make_async_copy(buf.at[t], hbm, sem.at[1])
        return pltpu.make_async_copy(hbm, buf.at[t], sem.at[0])

    row = jax.lax.broadcasted_iota(I32, buf.shape, 1)

    def turn(r, carry):
        each(lambda t: copy(t, r, False).start())
        each(lambda t: copy(t, r, False).wait())
        take = (row == r) & (mask_ref[...].reshape(buf.shape) != 0)
        buf[...] = jnp.where(take, new_ref[...].reshape(buf.shape), buf[...])
        each(lambda t: copy(t, r, True).start())
        each(lambda t: copy(t, r, True).wait())
        return carry

    # A loop, not eight copies of the body: every step program traces and
    # lowers this kernel on its first dispatch.
    jax.lax.fori_loop(0, SUBLANES, turn, 0)


def _merge_strip(pool, row, start, lane, new, mask):
    """``pool[row, start + i] = new[lane, i]`` wherever ``mask[lane, i] != 0``:
    one strip by a plain slice and update, for the documents no kernel call
    takes."""
    at = (row, start)
    old = jax.lax.dynamic_slice(pool, at, (1, new.shape[1]))
    strip = jnp.where(mask[lane] != 0, new[lane], old[0])
    return jax.lax.dynamic_update_slice(pool, strip[None], at)


def write_text_strips(pool, starts, new, mask):
    """``pool[d, starts[d] + j] = new[d, j]`` wherever ``mask[d, j] != 0``,
    for every document d and j < width: one strip a document, read, merged
    and written back in place (the pool is aliased in and out), so the cost
    follows D x width and not the pool.  This is the write of a step that owns
    the whole pool, document d at row d (the fleet-wide step, a mesh shard, a
    host lane's one document); a cohort, whose documents are a few rows of
    a pool it does not carry, writes through ``write_text_strips_at``.

    pool: int32[D, T]; starts: int32[D], each a multiple of ``LANES`` where
    width is (``text_strip_width``) and at most T - width; new, mask:
    int32[D, width].  Whole tile rows of eight documents go through the
    Pallas kernel (Mosaic on a TPU, the interpreter elsewhere: the program is
    the same); the D % 8 documents after them, and so a batch of fewer than
    eight, take a plain ``dynamic_update_slice`` each, as every document of
    a pool that is not made of whole lanes does (Mosaic slices whole tiles).
    """
    n_docs, capacity = pool.shape
    width = new.shape[1]
    tile_rows = n_docs // SUBLANES if capacity % LANES == 0 else 0
    if tile_rows:
        # A grid step holds its strips once in scratch and the new values
        # and the mask twice each (the pipeline's two buffers).
        fit = STRIP_VMEM_BYTES // (5 * SUBLANES * width * 4)
        block = max(1, min(STRIP_TILE_ROWS, fit, tile_rows))
        docs = block * SUBLANES
        pool = pl.pallas_call(
            functools.partial(_strip_kernel, tile_rows=tile_rows),
            out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(pl.cdiv(tile_rows, block),),
                in_specs=[
                    pl.BlockSpec((docs, width), lambda i, starts: (i, 0)),
                    pl.BlockSpec((docs, width), lambda i, starts: (i, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[
                    pltpu.VMEM((block, SUBLANES, width), I32),
                    pltpu.SemaphoreType.DMA((2,)),
                ],
            ),
            input_output_aliases={3: 0},
            interpret=jax.default_backend() != "tpu",
            name="text_strip_write",
        )(starts, new, mask, pool)

    return jax.lax.fori_loop(
        tile_rows * SUBLANES, n_docs,
        lambda d, pool: _merge_strip(pool, d, starts[d], d, new, mask), pool)


def _strip_rows_kernel(row_ref, start_ref, turn_ref, new_ref, mask_ref,
                       pool_in, pool, buf, sem, *, lanes: int):
    """One grid step: up to ``STRIP_ROW_LANES`` lanes of a cohort, each with
    a pool row of its own.  A DMA moves whole (8, 128) tiles, so a lane's
    strip travels with the seven other rows of its tile row, and two lanes
    whose rows share a tile row may not have it in flight at once: as in
    ``_strip_kernel`` the lanes take eight turns, a lane at row ``r`` the
    turn ``r % 8`` (two rows of one tile row never share it), and every read
    of a turn has landed before a strip of it is merged and sent back.  A
    lane that writes nothing has turn -1 and moves nothing."""
    del pool_in  # the same buffer as ``pool`` (input_output_aliases)
    block, _, width = buf.shape
    base = pl.program_id(0) * block
    n = jnp.minimum(block, lanes - base)
    place = jax.lax.broadcasted_iota(I32, buf.shape[1:], 0)

    def copy(t, back: bool):
        first = row_ref[base + t] // SUBLANES * SUBLANES
        hbm = pool.at[pl.ds(pl.multiple_of(first, SUBLANES), SUBLANES)]
        if width < pool.shape[1]:    # else the strip is the row (start 0)
            at = pl.multiple_of(start_ref[base + t], LANES)
            hbm = hbm.at[:, pl.ds(at, width)]
        if back:
            return pltpu.make_async_copy(buf.at[t], hbm, sem.at[1])
        return pltpu.make_async_copy(hbm, buf.at[t], sem.at[0])

    def turn(r, carry):
        def each(fn):
            def lane(t, c):
                pl.when(turn_ref[base + t] == r)(lambda: fn(t))
                return c

            jax.lax.fori_loop(0, n, lane, 0)

        def merge(t):
            take = (place == r) & (mask_ref[pl.ds(t, 1), :] != 0)
            buf[t] = jnp.where(take, new_ref[pl.ds(t, 1), :], buf[t])

        each(lambda t: copy(t, False).start())
        each(lambda t: copy(t, False).wait())
        each(merge)
        each(lambda t: copy(t, True).start())
        each(lambda t: copy(t, True).wait())
        return carry

    jax.lax.fori_loop(0, SUBLANES, turn, 0)


def write_text_strips_at(pool, rows, starts, new, mask):
    """``pool[rows[j], starts[j] + i] = new[j, i]`` wherever ``mask[j, i] !=
    0``, for every lane j and i < width: ``write_text_strips`` for a cohort,
    whose lanes' documents lie at the rows ``rows`` of a pool that stays
    where it is (aliased in and out): the cost follows lanes x width, and
    neither the pool's rows nor its columns.

    pool: int32[D, T]; rows: int32[lanes], each in [0, D); starts:
    int32[lanes], as ``write_text_strips`` wants them; new, mask:
    int32[lanes, width].  The lanes that write anything (a nonzero mask)
    name different rows; the others may repeat a row (a cohort's pad lanes
    do) and touch nothing.  A pool of whole (8, 128) tiles goes through the
    Pallas kernel (Mosaic on a TPU, the interpreter elsewhere); any other
    takes a plain ``dynamic_update_slice`` a lane, one after the other.
    """
    n_docs, capacity = pool.shape
    lanes, width = new.shape
    if n_docs % SUBLANES or capacity % LANES:
        return jax.lax.fori_loop(
            0, lanes,
            lambda j, pool: _merge_strip(pool, rows[j], starts[j], j, new, mask),
            pool)

    turn = jnp.where(jnp.any(mask != 0, axis=1), rows % SUBLANES, -1)
    # A grid step holds its lanes' tile rows once in scratch and their new
    # values and masks twice each (the pipeline's two buffers).
    fit = STRIP_VMEM_BYTES // ((SUBLANES + 4) * width * 4)
    block = max(SUBLANES, min(STRIP_ROW_LANES, fit) // SUBLANES * SUBLANES)
    block = min(block, lanes)   # (a cohort of one step is one whole block)
    return pl.pallas_call(
        functools.partial(_strip_rows_kernel, lanes=lanes),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(lanes, block),),
            in_specs=[
                pl.BlockSpec((block, width), lambda i, *_: (i, 0)),
                pl.BlockSpec((block, width), lambda i, *_: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((block, SUBLANES, width), I32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        input_output_aliases={5: 0},
        interpret=jax.default_backend() != "tpu",
        name="text_strip_write_rows",
    )(rows, starts, turn, new, mask, pool)
