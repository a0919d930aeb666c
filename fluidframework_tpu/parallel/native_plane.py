"""Native CPU dispatch plane: the second backend behind models/dispatch.

Satisfies the duck-typed plane surface of ``parallel.mesh`` (the default
jax/XLA plane) but routes the two fleet hot-path programs — the
merge-tree megastep and the zamboni compact — through the C++ row loops
of ``native/megastep.cpp`` instead of jit(shard_map) dispatches.  On the
CPU-degraded tail (no accelerator; XLA CPU dispatch is ~99% of the
pipeline per OBS_r07) this is the difference between ~10^2 and ~10^5
replay ops/s on the same box.

Design points:

* **Mesh machinery is delegated**, not faked: ``doc_mesh`` /
  ``shard_docs`` / ``shard_fleet_state`` come straight from
  ``parallel.mesh``, so ``StagingRing.upload``'s NamedSharding
  device_puts and the engines' state broadcast work unchanged.  A
  1-process CPU mesh is a perfectly good Mesh.
* **State stays jax-typed at the seam**: each native dispatch copies the
  int32 columns to writable numpy (the same arrays
  ``summary_to_state_host`` builds), mutates them in place in C++, and
  returns ``jnp.asarray``-wrapped leaves — so engine code that does
  ``.at[slot].set`` on leaves keeps working and checkpoints/scribe folds
  are backend-invariant by construction.
* **Byte identity is the contract**, enforced against the lax oracle by
  tests/test_dispatch_backends.py (full arrays incl. padding remnants,
  plus the per-doc error latch).
* **Seg lanes raise loudly**: the native plane has no segment-parallel
  programs; ``mesh_seg_program``/``seg_state_specs``/``shard_seg_state``
  raise NotImplementedError and ``DocBatchEngine`` maps that to its
  counted fallback (``seg_plane_unsupported``) — no silent degradation.
* **The .so never builds under a lock**: ``megastep_native.warm()`` runs
  only from ``mesh_fleet_program`` (engine construction); serving
  dispatches use the non-building accessors.

Importing this module registers it as THE dispatch plane (last-wins, see
``models.dispatch.register_dispatch_plane``); select it per process with
``FFTPU_DISPATCH_PLANE=fluidframework_tpu.parallel.native_plane``.
Callers flipping planes inside one process (tests, bench) must
re-register the plane they want afterwards.
"""

from __future__ import annotations

import sys as _sys

import jax
import jax.numpy as jnp
import numpy as np

from ..models.dispatch import register_dispatch_plane as _register
from ..native import megastep_native
from ..ops import mergetree_kernel as mk
from . import mesh as _mesh

# ----------------------------------------------------- delegated surface
P = _mesh.P
SEG_AXIS = _mesh.SEG_AXIS
doc_mesh = _mesh.doc_mesh
docs_segs_mesh = _mesh.docs_segs_mesh
fleet_doc_axes = _mesh.fleet_doc_axes
shard_docs = _mesh.shard_docs
replicate = _mesh.replicate
fleet_state_specs = _mesh.fleet_state_specs
shard_fleet_state = _mesh.shard_fleet_state


def available() -> bool:
    """True iff the native megastep library is built (building it if g++
    is present — call at startup, not under a serving lock)."""
    return megastep_native.warm()


# ------------------------------------------------------- fleet programs

def _wrap(state):
    """numpy-backed DocState -> jax-typed leaves (zero/one copy on CPU):
    the engines' ``.at[slot].set`` sites and digests need jnp arrays."""
    return jax.tree.map(jnp.asarray, state)


def _native_megastep(state, ops, payloads):
    return _wrap(megastep_native.megastep(state, ops, payloads))


def _native_compact(state, min_seqs):
    return _wrap(megastep_native.fleet_compact(state, min_seqs))


# The native programs never compile: a constant executable-cache size lets
# the engines' RecompileWatchdog register them like any jitted program.
_native_megastep._cache_size = _native_compact._cache_size = lambda: 0


def mesh_fleet_program(step_fn, mesh, state_specs, arg_specs=None,
                       donate=True):
    """The plane's program factory.  The two fleet hot-path bodies map to
    their native twins; anything else (tree-fleet programs, digests)
    delegates to the jax plane — full correctness, just not native-fast.

    ``warm()`` runs HERE, at program-build time (engine construction,
    outside any serving lock): per the PR 15 split the returned callables
    only ever touch the prebuilt library."""
    if step_fn is mk.apply_megastep:
        if not megastep_native.warm():
            raise RuntimeError(
                "native dispatch plane: libtpumegastep.so unavailable "
                "(g++ build failed?) — use the default jax plane"
            )
        return _native_megastep
    if getattr(step_fn, "__name__", "") == "_fleet_compact_body":
        if not megastep_native.warm():
            raise RuntimeError(
                "native dispatch plane: libtpumegastep.so unavailable "
                "(g++ build failed?) — use the default jax plane"
            )
        return _native_compact
    if arg_specs is None:
        return _mesh.mesh_fleet_program(
            step_fn, mesh, state_specs, donate=donate
        )
    return _mesh.mesh_fleet_program(
        step_fn, mesh, state_specs, arg_specs=arg_specs, donate=donate
    )


def error_count(error) -> int:
    """Host-side error latch count (the jax plane jits a device sum; one
    numpy reduction is the native equivalent)."""
    return int(np.count_nonzero(np.asarray(error)))


# ------------------------------------------------- seg lanes: loud N/A

_SEG_MSG = (
    "native dispatch plane: segment-parallel lanes are not implemented "
    "(docs-sharded serving only); DocBatchEngine falls back to the "
    "doc-sharded path and counts seg_plane_unsupported"
)


def seg_state_specs(*args, **kwargs):
    raise NotImplementedError(_SEG_MSG)


def shard_seg_state(*args, **kwargs):
    raise NotImplementedError(_SEG_MSG)


def mesh_seg_program(*args, **kwargs):
    raise NotImplementedError(_SEG_MSG)


# Self-register (last-wins): importing this module selects the native
# plane for engines constructed afterwards.
_register(_sys.modules[__name__])
