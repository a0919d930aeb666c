"""ctypes binding for the native merge-tree megastep (native/megastep.cpp).

The C++ loops apply a [K, D, B] op ring — and the zamboni compact — in
place over the SAME int32 state columns the lax kernel carries, byte
identical to ``ops.mergetree_kernel.apply_megastep`` /
``_fleet_compact_body`` (the conformance contract is enforced by
tests/test_dispatch_backends.py against the lax oracle).  The dispatch
plane built on top lives in ``parallel/native_plane.py``.

Build: ``native/libtpumegastep.so`` compiles with g++ whenever the
source's content hash differs from the one recorded beside the library
(``native/_build.py``) — but ONLY through ``warm()``/``available()``,
which the plane calls at
program-build time (engine construction).  The serving-path entry points
(``loaded``, ``megastep``, ``fleet_compact``) never spawn the compiler:
they can run under the engines' ``ckpt_lock``, where a g++ run would
stall every ingest contender for seconds (fftpu-check
``blocking-under-lock``)."""

from __future__ import annotations

import ctypes
from pathlib import Path

import numpy as np

from ._build import NativeBuildError, ensure_built, is_current

_REPO_ROOT = Path(__file__).resolve().parents[2]
_SRC = _REPO_ROOT / "native" / "megastep.cpp"
_LIB = _REPO_ROOT / "native" / "libtpumegastep.so"

OP_FIELDS = 8
ABI_VERSION = 1

_lib_cache: list = []
_warmed: list = []

_I32P = ctypes.POINTER(ctypes.c_int32)
_I64P = ctypes.POINTER(ctypes.c_int64)

# Column table order — must match megastep.cpp's header comment.
_SCALAR_COLS = ("text_end", "nseg", "uid_next", "min_seq", "error")
_COL_ORDER = (
    "text", "text_end", "nseg", "seg_start", "seg_len", "ins_key",
    "ins_client", "seg_uid", "seg_obpre", "rem_keys", "rem_clients",
    "prop_keys", "prop_vals", "uid_next", "ob_key", "ob_client",
    "ob_start_uid", "ob_end_uid", "ob_start_side", "ob_end_side",
    "ob_ref_seq", "min_seq", "error",
)


def warm() -> bool:
    """Build (when the recorded source hash differs) and load the library,
    eagerly and idempotently.  This is the ONLY entry that runs g++: the
    native plane calls it while building its fleet programs (engine
    ``__init__``, outside any serving lock) — the hot-path accessors
    below only ever LOAD a current library (same warm/loaded split as
    ``ingest_native``, the PR 15 blocking-under-lock fix).  False means
    the build failed; the plane raises on it."""
    if _warmed:
        return bool(_lib_cache) and _lib_cache[0] is not None
    _warmed.append(True)
    try:
        ensure_built(_SRC, _LIB)
    except NativeBuildError:
        _lib_cache[:] = [None]
        return False
    _lib_cache[:] = [_load()]
    return True


def _ensure_built() -> ctypes.CDLL | None:
    """Serving-path accessor: the cached library, loading a CURRENT .so
    on first touch — never compiling."""
    if _lib_cache:
        return _lib_cache[0]
    _lib_cache[:] = [_load() if is_current(_SRC, _LIB) else None]
    return _lib_cache[0]


def _load() -> ctypes.CDLL:
    lib = ctypes.CDLL(str(_LIB))
    lib.ms_abi_version.restype = ctypes.c_int32
    lib.ms_abi_version.argtypes = []
    lib.ms_megastep.restype = ctypes.c_int32
    lib.ms_megastep.argtypes = [_I64P, _I32P, _I32P, _I32P]
    lib.ms_compact.restype = ctypes.c_int32
    lib.ms_compact.argtypes = [_I64P, _I32P, _I32P]
    if lib.ms_abi_version() != ABI_VERSION:
        raise RuntimeError(
            f"{_LIB.name} reports ABI {lib.ms_abi_version()}, binding "
            f"expects {ABI_VERSION}: megastep.cpp and megastep_native.py "
            "disagree"
        )
    return lib


def available() -> bool:
    """Build-on-demand probe for host tools/tests (outside any serving
    lock).  Serving paths use ``loaded()`` instead."""
    return warm()


def loaded() -> bool:
    """Non-building availability probe (safe under the engines' locks)."""
    return _ensure_built() is not None


def state_columns(state) -> tuple[dict, list]:
    """Copy a [D, ...] DocState's leaves into writable, C-contiguous
    numpy columns (tuple fields stacked on a leading axis) plus the
    megastep's column pointer table.  Returns ``(cols, addrs)`` where
    ``cols`` maps field name -> array and ``addrs`` is the int64 pointer
    list in ``_COL_ORDER``."""
    cols: dict[str, np.ndarray] = {}
    for name in _COL_ORDER:
        v = getattr(state, name)
        if isinstance(v, tuple):
            arr = np.ascontiguousarray(
                np.stack([np.asarray(a) for a in v]).astype(
                    np.int32, copy=False
                )
            )
        else:
            # Always a fresh buffer: the caller's leaves (jax arrays or
            # an oracle's numpy state) must never be mutated in place.
            arr = np.array(np.asarray(v), dtype=np.int32, order="C")
        cols[name] = arr
    addrs = [cols[name].ctypes.data for name in _COL_ORDER]
    return cols, addrs


def _dims(state, extra: tuple = ()) -> np.ndarray:
    D = int(np.asarray(state.text_end).shape[0])
    T = int(np.asarray(state.text).shape[-1])
    S = int(np.asarray(state.seg_len).shape[-1])
    R = len(state.rem_keys)
    P = len(state.prop_keys)
    OB = int(np.asarray(state.ob_key).shape[-1])
    return np.array((D, T, S, R, P, OB) + extra, np.int32)


def unpack_columns(state, cols: dict):
    """Rebuild a DocState from mutated columns (stacked tuple fields are
    re-split into per-slot views — zero copy)."""
    kw = {}
    for name in _COL_ORDER:
        arr = cols[name]
        if isinstance(getattr(state, name), tuple):
            kw[name] = tuple(arr[i] for i in range(arr.shape[0]))
        else:
            kw[name] = arr
    return state._replace(**kw)


def megastep(state, ops: np.ndarray, payloads: np.ndarray):
    """Apply a [K, D, B, 8] op ring (+ [K, D, B, L] payloads) to a
    [D, ...] DocState via the native loops; returns the stepped state as
    plain numpy-backed columns.  Raises RuntimeError when the prebuilt
    library is unavailable (callers guard with ``loaded()``/``warm()``)."""
    lib = _ensure_built()
    if lib is None:
        raise RuntimeError("native megastep library unavailable")
    ops = np.ascontiguousarray(np.asarray(ops, dtype=np.int32))
    payloads = np.ascontiguousarray(np.asarray(payloads, dtype=np.int32))
    K, D, B, L = (
        ops.shape[0], ops.shape[1], ops.shape[2], payloads.shape[-1]
    )
    cols, addrs = state_columns(state)
    addr_arr = np.array(addrs, np.int64)
    dims = _dims(state, (K, B, L))
    rc = lib.ms_megastep(
        addr_arr.ctypes.data_as(_I64P),
        dims.ctypes.data_as(_I32P),
        ops.ctypes.data_as(_I32P),
        payloads.ctypes.data_as(_I32P),
    )
    if rc != 0:
        raise RuntimeError(f"native megastep failed (rc={rc}): dims {dims}")
    return unpack_columns(state, cols)


def fleet_compact(state, min_seqs: np.ndarray):
    """set_min_seq + zamboni compact for every doc (the native twin of
    models.doc_batch_engine._fleet_compact_body)."""
    lib = _ensure_built()
    if lib is None:
        raise RuntimeError("native megastep library unavailable")
    min_seqs = np.ascontiguousarray(np.asarray(min_seqs, dtype=np.int32))
    cols, addrs = state_columns(state)
    addr_arr = np.array(addrs, np.int64)
    dims = _dims(state)
    rc = lib.ms_compact(
        addr_arr.ctypes.data_as(_I64P),
        dims.ctypes.data_as(_I32P),
        min_seqs.ctypes.data_as(_I32P),
    )
    if rc != 0:
        raise RuntimeError(f"native compact failed (rc={rc}): dims {dims}")
    return unpack_columns(state, cols)
