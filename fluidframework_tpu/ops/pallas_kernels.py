"""Pallas TPU kernels for the hot long-document primitives.

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
