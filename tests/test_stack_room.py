"""``utils/stack_room.with_stack_room``: a call from a frame-stack chunk of
its own (what it is for: the module's docstring)."""

import resource
import sys

import pytest

from fluidframework_tpu.utils.stack_room import STACK_ROOM_WORDS, with_stack_room


def test_is_the_call():
    assert with_stack_room(divmod, 17, 5) == (3, 2)
    assert with_stack_room(lambda: None) is None
    with pytest.raises(ZeroDivisionError):
        with_stack_room(divmod, 1, 0)
    assert with_stack_room.__code__.co_stacksize == STACK_ROOM_WORDS


def _leaf():
    # thirty locals: the wider the frame, the more depths it straddles at
    a0 = a1 = a2 = a3 = a4 = a5 = a6 = a7 = a8 = a9 = None
    b0 = b1 = b2 = b3 = b4 = b5 = b6 = b7 = b8 = b9 = None
    c0 = c1 = c2 = c3 = c4 = c5 = c6 = c7 = c8 = c9 = None


def _calls(n):
    for _ in range(n):
        _leaf()


def _at_depth(depth, fn, *args):
    if depth:
        return _at_depth(depth - 1, fn, *args)
    return fn(*args)


def _faults(fn, *args):
    before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    fn(*args)
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before


@pytest.mark.skipif(
    sys.implementation.name != "cpython" or sys.version_info < (3, 11)
    or not sys.platform.startswith("linux"),
    reason="CPython's chunked frame stack, counted by Linux page faults",
)
def test_no_call_under_it_crosses_a_chunk():
    """At some depth of the caller every call of ``_leaf`` maps and unmaps
    a chunk (a page fault a call); under ``with_stack_room`` none does, at
    that depth or any other."""
    calls = 2000
    bare = [_faults(_at_depth, d, _calls, calls) for d in range(300)]
    worst = max(range(300), key=bare.__getitem__)
    if bare[worst] < calls // 2:
        pytest.skip("this interpreter keeps its frame-stack chunks")
    roomy = [
        _faults(_at_depth, d, with_stack_room, _calls, calls)
        for d in (0, worst - 1, worst, worst + 1)
    ]
    assert max(roomy) < calls // 20, (worst, bare[worst], roomy)
