"""ctypes binding for the native wire-ingest encoder (native/ingest.cpp).

One ``NativeIngestEncoder`` per document: JSON-lines sequenced messages in,
kernel op-row tensors out — the whole decode+encode path (JSON parse,
quorum lookup, insert chunking, property interning) runs in C++, replacing
the per-op Python that bounds the fleet's ingest rate.  Differentially
tested against the Python path in tests/test_native_ingest.py.

Build: ``native/libtpuingest.so`` compiles with g++ whenever the source's
content hash differs from the one recorded beside the library
(``native/_build.py``; no pip/pybind11 dependencies) — but ONLY through
``warm()``/``available()``, which the engines call at construction time.
The serving-path accessors (``loaded``, ``tree_decode``,
``NativeIngestEncoder``) never spawn the compiler: they run under the
engines' ``ckpt_lock``, where a g++ run would stall every ingest
contender for seconds (fftpu-check ``blocking-under-lock``).  A library
that is not current is never loaded: the callers take the Python decode
and ``health()['ingest_plane']`` says so.
"""

from __future__ import annotations

import ctypes
from pathlib import Path

import numpy as np

from ._build import NativeBuildError, ensure_built, is_current

_REPO_ROOT = Path(__file__).resolve().parents[2]
_SRC = _REPO_ROOT / "native" / "ingest.cpp"
_LIB = _REPO_ROOT / "native" / "libtpuingest.so"

OP_FIELDS = 8

_lib_cache: list = []
_warmed: list = []
_build_error: list[str] = []


def warm() -> bool:
    """Build (when the recorded source hash differs) and load the library,
    eagerly and idempotently.  This is the ONLY entry that runs g++: call
    it at process/engine startup, never from a serving path — the lazy
    rebuild used to be reachable under the engines' ``ckpt_lock``, and a
    multi-second compiler run under the serving lock convoys every ingest
    (fftpu-check blocking-under-lock: subprocess under ckpt_lock).  The
    engines warm in ``__init__``; the hot-path accessors below only ever
    LOAD a current library.  False means the build failed
    (``build_error()`` has the compiler's words); serving entry points
    treat that as fatal."""
    if _warmed:
        return bool(_lib_cache) and _lib_cache[0] is not None
    _warmed.append(True)
    try:
        ensure_built(_SRC, _LIB)
    except NativeBuildError as e:
        _build_error[:] = [str(e)]
        _lib_cache[:] = [None]
        return False
    _lib_cache[:] = [_load()]
    return True


def build_error() -> str | None:
    """The compiler failure the last ``warm()`` hit, if any."""
    return _build_error[0] if _build_error else None


def _ensure_built() -> ctypes.CDLL | None:
    """Serving-path accessor: the cached library, loading a CURRENT .so
    on first touch — never compiling.  Returns None when the library is
    missing or was built from other source bytes (the callers fall back
    to the Python decode paths); ``warm()`` upgrades a None verdict after
    building."""
    if _lib_cache:
        return _lib_cache[0]
    _lib_cache[:] = [_load() if is_current(_SRC, _LIB) else None]
    return _lib_cache[0]


def _load() -> ctypes.CDLL:
    lib = ctypes.CDLL(str(_LIB))
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.ing_create.restype = ctypes.c_void_p
    lib.ing_create.argtypes = [ctypes.c_int32, ctypes.c_int32]
    lib.ing_destroy.argtypes = [ctypes.c_void_p]
    lib.ing_min_seq.restype = ctypes.c_int64
    lib.ing_min_seq.argtypes = [ctypes.c_void_p]
    lib.ing_last_error.restype = ctypes.c_char_p
    lib.ing_last_error.argtypes = [ctypes.c_void_p]
    lib.ing_encode.restype = ctypes.c_int32
    lib.ing_encode.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
        i32p, i32p, ctypes.c_int32,
    ]
    lib.ing_prop_table.restype = ctypes.c_int32
    lib.ing_prop_table.argtypes = [
        ctypes.c_void_p, i64p, i32p, ctypes.c_int32,
    ]
    lib.ing_tree_decode.restype = ctypes.c_int32
    lib.ing_tree_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        i64p, ctypes.c_int32, i32p, ctypes.c_int32,
        i32p, ctypes.c_int32, i32p, ctypes.c_int32,
        i64p, ctypes.c_int32, i32p, i32p,
    ]
    return lib


def available() -> bool:
    """Build-on-demand probe for host tools/tests (outside any serving
    lock).  Serving paths use the non-building accessors instead."""
    return warm()


def loaded() -> bool:
    """Non-building availability probe for serving paths (safe under the
    engines' locks): True iff a current library is loaded/loadable."""
    return _ensure_built() is not None


def fed_plane(native_chunks: int, python_chunks: int, next_native: bool) -> str:
    """The engines' ``health()['ingest_plane']``: which decoder actually
    fed ``ingest_lines`` so far — ``native`` (this library only),
    ``python`` (per-message decode only) or ``mixed``.  Before any feed it
    names the plane the next chunk would take (``next_native``)."""
    if native_chunks and python_chunks:
        return "mixed"
    if native_chunks or python_chunks:
        return "native" if native_chunks else "python"
    return "native" if next_native else "python"


class NativeIngestEncoder:
    """Per-document native wire decoder (quorum + prop tables live in C++)."""

    def __init__(self, max_insert_len: int = 64, prop_slots: int = 4) -> None:
        lib = _ensure_built()
        if lib is None:
            raise RuntimeError("native ingest encoder unavailable (g++ build failed)")
        self._lib = lib
        self.max_insert_len = max_insert_len
        self._h = lib.ing_create(max_insert_len, prop_slots)

    def __del__(self) -> None:
        if getattr(self, "_h", None):
            self._lib.ing_destroy(self._h)
            self._h = None

    @property
    def min_seq(self) -> int:
        return int(self._lib.ing_min_seq(self._h))

    def prop_table(self) -> dict[int, int]:
        """The C++ property interning table as ``{prop_id: kernel slot}``.

        Checkpoint fidelity (ROADMAP): the engine folds this into its host
        table before summarizing a native-mode doc, so checkpoints carry
        the documents' REAL annotation property ids — a restored doc's
        annotations round-trip instead of surfacing private slot numbers."""
        cap = 16
        while True:
            props = np.empty((cap,), np.int64)
            slots = np.empty((cap,), np.int32)
            n = self._lib.ing_prop_table(
                self._h,
                props.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                slots.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                cap,
            )
            if n < cap:
                return {int(props[i]): int(slots[i]) for i in range(n)}
            cap *= 2

    def encode(self, data: bytes, max_rows: int = 0) -> tuple[np.ndarray, np.ndarray]:
        """Newline-separated JSON messages -> (ops[M, 8], payloads[M, L])."""
        if max_rows <= 0:
            # Every line yields at most a handful of rows; newline count is a
            # safe starting capacity, doubled on overflow.
            max_rows = max(16, 2 * (data.count(b"\n") + 1))
        while True:
            # np.empty is safe: the encoder writes every field of each row
            # it returns (payload rows are memset before use).
            ops = np.empty((max_rows, OP_FIELDS), np.int32)
            payloads = np.empty((max_rows, self.max_insert_len), np.int32)
            n = self._lib.ing_encode(
                self._h, data, len(data),
                ops.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                payloads.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                max_rows,
            )
            if n == -1:
                raise ValueError(
                    f"native ingest: {self._lib.ing_last_error(self._h).decode()}"
                )
            if n < -1:  # capacity exhausted mid-stream: grow and re-run
                max_rows *= 2
                continue
            return ops[:n], payloads[:n]


# ---------------------------------------------------------------------------
# Tree wire decode
# ---------------------------------------------------------------------------

# Row widths (mirror native/ingest.cpp ing_tree_decode).
_TREE_MSG_FIELDS = 14
_TREE_CHG_FIELDS = 3
_TREE_FLD_FIELDS = 4
_TREE_MARK_FIELDS = 5

TREE_ST_EDITS, TREE_ST_SKIP, TREE_ST_OPAQUE = 0, 1, 2


def tree_decode(data: bytes):
    """Decode newline-separated sequenced tree messages into mark-pool
    columns (stateless; the whole-feed grow-and-retry contract of
    ``NativeIngestEncoder.encode``).

    Returns ``(msgs, chgs, flds, marks, spans)`` numpy tables — see the
    C header comment for layouts — or ``None`` when no current library is
    loaded.  Raises ``ValueError`` on a malformed line (message index
    included), matching the Python path's ownership of error semantics."""
    lib = _ensure_built()
    if lib is None:
        return None
    n_lines = data.count(b"\n") + 1
    m_msgs = max(16, n_lines)
    m_chgs = m_flds = max(32, 2 * n_lines)
    m_marks = m_spans = max(64, 8 * n_lines)
    while True:
        msgs = np.empty((m_msgs, _TREE_MSG_FIELDS), np.int64)
        chgs = np.empty((m_chgs, _TREE_CHG_FIELDS), np.int32)
        flds = np.empty((m_flds, _TREE_FLD_FIELDS), np.int32)
        marks = np.empty((m_marks, _TREE_MARK_FIELDS), np.int32)
        spans = np.empty((m_spans, 2), np.int64)
        counts = np.zeros((5,), np.int32)
        err_line = np.zeros((1,), np.int32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        n = lib.ing_tree_decode(
            data, len(data),
            msgs.ctypes.data_as(i64p), m_msgs,
            chgs.ctypes.data_as(i32p), m_chgs,
            flds.ctypes.data_as(i32p), m_flds,
            marks.ctypes.data_as(i32p), m_marks,
            spans.ctypes.data_as(i64p), m_spans,
            counts.ctypes.data_as(i32p),
            err_line.ctypes.data_as(i32p),
        )
        if n == -1:
            raise ValueError(
                f"native tree decode: malformed message at line "
                f"{int(err_line[0])}"
            )
        if n == -2:  # some table filled: double everything, re-run
            m_msgs *= 2
            m_chgs *= 2
            m_flds *= 2
            m_marks *= 2
            m_spans *= 2
            continue
        return (
            msgs[: counts[0]], chgs[: counts[1]], flds[: counts[2]],
            marks[: counts[3]], spans[: counts[4]],
        )
