"""Room on CPython's frame stack for a call that traces and lowers programs.

CPython (3.11 and later) keeps a thread's Python frames in chunks of 16 KB.
A call whose frame does not fit the current chunk maps a new one, and the
chunk is unmapped again the moment that call returns: a call site that sits
at the end of a chunk pays an ``mmap``, a page fault and a ``munmap`` on
EVERY call it makes.  Where a chunk ends is decided by the sizes of all the
frames below, so one more frame (or one more local) anywhere between a
process's entry point and a ``jax.jit`` call decides whether the trace and
the lowering under it, hundreds of frames deep and tens of thousands of
calls at each depth, run at their own speed or at a fraction of it.  On this
repo's merge-tree step, here on a CPU: lowering 0.31 s and 615 page faults
at one depth of the caller, 0.90 s and 57,833 at the next.  On the TPU
machine's host, where a process that holds the chip has dozens of threads
for an unmap to interrupt, the 36 programs of a serving ladder lowered in
3.2 s from a chunk of their own, in 10.5 s from the depth the serving loop
had them at, and in 20.8 s from one frame deeper (PERF.md, PR 37).

``with_stack_room(fn, *args)`` is ``fn(*args)`` called from a frame that
claims 512 KB of frame stack for itself, which CPython answers with one
chunk of 1 MB: the frames of everything ``fn`` calls lie in the rest of that
chunk and cross no boundary.  The chunk is mapped once for the call and
unmapped when it returns (untouched pages cost nothing), so this is for the
call that is about to trace, not for a hot path.
"""

from __future__ import annotations

import types

STACK_ROOM_WORDS = 1 << 16  # of 8 bytes: the frame's own claim


def _call(fn, *args):
    return fn(*args)


with_stack_room = types.FunctionType(
    _call.__code__.replace(
        co_stacksize=STACK_ROOM_WORDS, co_name="with_stack_room"
    ),
    globals(),
    "with_stack_room",
)
with_stack_room.__doc__ = "``fn(*args)`` in a frame-stack chunk of its own."
