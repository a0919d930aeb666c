"""ctypes binding for the native deli sequencer (native/sequencer.cpp).

Drop-in replacement for ``server.sequencer.Sequencer``: same public
surface (join/leave/ticket/mint_service/clients membership, checkpoint/
restore, seq/min_seq/log — ``clients()`` maps client id to short id rather
than full ClientEntry objects) and bit-identical sequencing decisions —
enforced by the differential suite in tests/test_native_sequencer.py. The integer state machine runs in C++;
message-object construction stays in Python (it is not the hot part).

Build: ``native/libtpusequencer.so`` is compiled on demand with g++
whenever the source's content hash differs from the one recorded beside the
library (``native/_build.py``; no pip/pybind11 dependencies).
"""

from __future__ import annotations

import ctypes
import time
from pathlib import Path

from ..protocol.messages import MessageType, Nack, SequencedMessage, UnsequencedMessage
from ._build import NativeBuildError, ensure_built

_REPO_ROOT = Path(__file__).resolve().parents[2]
_SRC = _REPO_ROOT / "native" / "sequencer.cpp"
_LIB = _REPO_ROOT / "native" / "libtpusequencer.so"

_NACK_REASONS = {
    1: "client not joined",
    2: "refSeq below MSN",
    3: "refSeq from the future",
    4: "clientSeq out of order",
}


def _ensure_built() -> ctypes.CDLL | None:
    try:
        ensure_built(_SRC, _LIB)
    except NativeBuildError:
        return None  # native_available() is False; the Python twin serves
    lib = ctypes.CDLL(str(_LIB))
    lib.seq_create.restype = ctypes.c_void_p
    lib.seq_create.argtypes = [ctypes.c_int64]
    lib.seq_destroy.argtypes = [ctypes.c_void_p]
    lib.seq_current.restype = ctypes.c_int64
    lib.seq_current.argtypes = [ctypes.c_void_p]
    lib.seq_min.restype = ctypes.c_int64
    lib.seq_min.argtypes = [ctypes.c_void_p]
    lib.seq_client_count.restype = ctypes.c_int32
    lib.seq_client_count.argtypes = [ctypes.c_void_p]
    lib.seq_join.restype = ctypes.c_int32
    lib.seq_join.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.seq_leave.restype = ctypes.c_int32
    lib.seq_leave.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.seq_ticket.restype = ctypes.c_int32
    lib.seq_ticket.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.seq_mint_service.restype = ctypes.c_int64
    lib.seq_mint_service.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
    lib.seq_checkpoint.restype = ctypes.c_int64
    lib.seq_checkpoint.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64
    ]
    lib.seq_restore.restype = ctypes.c_void_p
    lib.seq_restore.argtypes = [ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
    return lib


_lib = _ensure_built()


def native_available() -> bool:
    return _lib is not None


class NativeSequencer:
    """C++-backed sequencer with the Python Sequencer's surface."""

    def __init__(self, starting_seq: int = 0, _handle=None) -> None:
        if _lib is None:
            raise RuntimeError("native sequencer library unavailable")
        self._h = _handle if _handle is not None else _lib.seq_create(starting_seq)
        self.log: list[SequencedMessage] = []
        self._members: dict[str, int] = {}  # client id -> short id

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h and _lib is not None:
            _lib.seq_destroy(h)
            self._h = None

    # ------------------------------------------------------------------ admin
    @property
    def seq(self) -> int:
        return _lib.seq_current(self._h)

    @property
    def min_seq(self) -> int:
        return _lib.seq_min(self._h)

    def clients(self) -> dict[str, int]:
        """client id -> short id for currently joined clients."""
        assert len(self._members) == _lib.seq_client_count(self._h)
        return dict(self._members)

    def __contains__(self, client_id: str) -> bool:
        return client_id in self._members

    # ------------------------------------------------------------------ joins
    def join(self, client_id: str) -> SequencedMessage:
        out_seq = ctypes.c_int64()
        out_min = ctypes.c_int64()
        short = _lib.seq_join(self._h, client_id.encode(), ctypes.byref(out_seq), ctypes.byref(out_min))
        if short < 0:
            raise ValueError(f"duplicate join: {client_id}")
        self._members[client_id] = short
        msg = SequencedMessage(
            client_id=client_id,
            client_seq=0,
            ref_seq=out_seq.value - 1,
            seq=out_seq.value,
            min_seq=out_min.value,
            type=MessageType.JOIN,
            contents={"clientId": client_id, "short": short},
            metadata=None,
            timestamp=time.time(),
            short_client=short,
        )
        self.log.append(msg)
        return msg

    def leave(self, client_id: str) -> SequencedMessage:
        out_seq = ctypes.c_int64()
        out_min = ctypes.c_int64()
        out_cseq = ctypes.c_int64()
        out_rseq = ctypes.c_int64()
        short = _lib.seq_leave(
            self._h, client_id.encode(), ctypes.byref(out_seq), ctypes.byref(out_min),
            ctypes.byref(out_cseq), ctypes.byref(out_rseq),
        )
        if short < 0:
            raise ValueError(f"leave of unjoined client: {client_id}")
        self._members.pop(client_id, None)
        msg = SequencedMessage(
            client_id=client_id,
            client_seq=out_cseq.value,
            ref_seq=out_rseq.value,
            seq=out_seq.value,
            min_seq=out_min.value,
            type=MessageType.LEAVE,
            contents={"clientId": client_id},
            metadata=None,
            timestamp=time.time(),
            short_client=short,
        )
        self.log.append(msg)
        return msg

    # ----------------------------------------------------------------- ticket
    def ticket(self, msg: UnsequencedMessage) -> SequencedMessage | Nack:
        out_seq = ctypes.c_int64()
        out_min = ctypes.c_int64()
        out_short = ctypes.c_int32()
        rc = _lib.seq_ticket(
            self._h, msg.client_id.encode(), msg.client_seq, msg.ref_seq,
            ctypes.byref(out_seq), ctypes.byref(out_min), ctypes.byref(out_short),
        )
        if rc != 0:
            return Nack(msg.client_id, msg.client_seq, _NACK_REASONS[rc])
        out = SequencedMessage(
            client_id=msg.client_id,
            client_seq=msg.client_seq,
            ref_seq=msg.ref_seq,
            seq=out_seq.value,
            min_seq=out_min.value,
            type=msg.type,
            contents=msg.contents,
            metadata=msg.metadata,
            timestamp=time.time(),
            short_client=out_short.value,
        )
        self.log.append(out)
        return out

    def mint_service(self, mtype: str, contents) -> SequencedMessage:
        out_min = ctypes.c_int64()
        seq = _lib.seq_mint_service(self._h, ctypes.byref(out_min))
        # Scribe-driven MSN plumbing (mirror Sequencer.mint_service): a
        # summary ack carries the ack-derived compaction floor.  The floor
        # itself is Python-side state — the C++ core predates acks and its
        # checkpoint format must stay stable — so a restore conservatively
        # restarts the floor at 0 (compaction lags, never overruns).
        if mtype == MessageType.SUMMARY_ACK and isinstance(contents, dict):
            ref = contents.get("refSeq")
            if isinstance(ref, int):
                self._ack_floor = max(getattr(self, "_ack_floor", 0), ref)
            contents.setdefault(
                "msn", min(getattr(self, "_ack_floor", 0), out_min.value)
            )
        out = SequencedMessage(
            client_id="__service__",
            client_seq=0,
            ref_seq=seq - 1,
            seq=seq,
            min_seq=out_min.value,
            type=mtype,
            contents=contents,
            metadata=None,
            timestamp=time.time(),
            short_client=-1,
        )
        self.log.append(out)
        return out

    # ------------------------------------------------------------- checkpoint
    def checkpoint_bytes(self) -> bytes:
        n = _lib.seq_checkpoint(self._h, None, 0)
        buf = (ctypes.c_uint8 * n)()
        _lib.seq_checkpoint(self._h, buf, n)
        return bytes(buf)

    @staticmethod
    def restore_bytes(data: bytes) -> "NativeSequencer":
        buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
        h = _lib.seq_restore(buf, len(data))
        if not h:
            raise ValueError("truncated or corrupt sequencer checkpoint")
        out = NativeSequencer(_handle=h)
        out._members = _parse_checkpoint_members(data)
        return out


def _parse_checkpoint_members(data: bytes) -> dict[str, int]:
    """Read the client table from the flat checkpoint layout (see
    seq_checkpoint in native/sequencer.cpp)."""
    import struct

    off = 8 + 8 + 4  # seq, min_seq, next_short
    (n,) = struct.unpack_from("<i", data, off)
    off += 4
    members: dict[str, int] = {}
    for _ in range(n):
        short, _cseq, _rseq, slen = struct.unpack_from("<iqqi", data, off)
        off += 4 + 8 + 8 + 4
        name = data[off : off + slen].decode()
        off += slen
        members[name] = short
    return members
