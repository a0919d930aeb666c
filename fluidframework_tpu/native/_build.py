"""Build-on-demand for the ``native/*.cpp`` libraries, keyed by content.

A library is current when the SHA-256 of its source equals the digest
recorded beside it (``<lib>.sha256``, written after a successful build).
File times decide nothing: a checkout copied to another machine keeps no
meaningful mtimes, and a stale binary must never be what serves.  The
``.so`` and its stamp are build outputs (gitignored); a fresh checkout
compiles once, at ``warm()`` time.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
from pathlib import Path


class NativeBuildError(RuntimeError):
    """g++ is missing or rejected the source; carries the compiler output."""


def _stamp(lib: Path) -> Path:
    return lib.with_name(lib.name + ".sha256")


def source_digest(src: Path) -> str:
    return hashlib.sha256(src.read_bytes()).hexdigest()


def is_current(src: Path, lib: Path, digest: str | None = None) -> bool:
    """True iff ``lib`` was built from exactly the bytes ``src`` holds now
    (``digest``: their SHA-256, when the caller already has it)."""
    try:
        return lib.exists() and _stamp(lib).read_text().strip() == (
            digest or source_digest(src)
        )
    except OSError:
        return False


def ensure_built(src: Path, lib: Path) -> None:
    """Compile ``src`` into ``lib`` unless ``is_current``.  Concurrent
    builders are safe: each compiles to its own temporary and renames it
    into place (a loader never sees a half-written library), and the
    stamp lands only after the library it describes."""
    digest = source_digest(src)
    if is_current(src, lib, digest):
        return
    tmp = lib.with_name(f".{lib.name}.{os.getpid()}.tmp")
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
             "-o", str(tmp), str(src)],
            check=True, capture_output=True, text=True,
        )
        os.replace(tmp, lib)
    except FileNotFoundError as e:
        raise NativeBuildError(f"g++ not found building {src.name}") from e
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"g++ failed building {src.name}:\n{e.stderr[-2000:]}"
        ) from e
    finally:
        tmp.unlink(missing_ok=True)
    stamp_tmp = _stamp(lib).with_name(f".{lib.name}.{os.getpid()}.sha256")
    stamp_tmp.write_text(digest + "\n")
    os.replace(stamp_tmp, _stamp(lib))
