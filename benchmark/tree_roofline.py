"""What a step of the tree fleet needs, from its shapes alone, and which
device programs are its steps.

The nested forest step is integer compare/select over columnar node arrays:
no matrix unit is involved, so its roofline is the memory one.  The bytes the
loop NEEDS are not the bytes the program moves: each document that got an
edit has its columns and its word pool read once and written once, and every
op row goes up once with its payload.  A fleet-wide step that scans every
document's 16,384 slots for each of 32 op slots moves far more; that is what
the share shows.  Nothing here reads the program: the sizes are the
configuration's ``geometry``, so the count is the same work whatever
implements the step.
"""

from __future__ import annotations

import bisect

import device_programs
import roofline

NESTED_OP_FIELDS = 22   # ops/tree_kernel.py: int32 columns of one op row
STATE_COLUMNS = 9       # NestedForestState: int32 columns over the node slots
STATE_SCALARS = 3       # pool_end, nrow, error
# jit_apply_nested_fleet (K = 1; jit_apply_nested_ops until PR 28) and
# jit_apply_nested_megastep (K > 1); the compaction is jit_compact_nested
# and is no step.
STEP_MARK = "apply_nested"


def doc_state_bytes(geometry: dict) -> int:
    return 4 * (STATE_COLUMNS * int(geometry["node_slots_per_doc"])
                + int(geometry["pool_words_per_doc"]) + STATE_SCALARS)


def op_row_bytes(geometry: dict) -> int:
    return 4 * (NESTED_OP_FIELDS + int(geometry["max_insert_len"]))


def step_bytes_needed(touched_docs: int, op_rows: int, geometry: dict) -> int:
    return (2 * touched_docs * doc_state_bytes(geometry)
            + op_rows * op_row_bytes(geometry))


def whole_steps(module_events) -> tuple[int, int]:
    """``(device ns, executions)`` of the tree step programs among
    ``(program, start_ns, dur_ns)`` of ONE device, the device's first and
    last event left out: the trace cuts whatever runs at its edges without
    saying so (``device_programs.classify``)."""
    ev = sorted(module_events, key=lambda e: e[1])
    whole = [d for name, _s, d in ev[1:-1] if STEP_MARK in name]
    return sum(whole), len(whole)


def bytes_needed_per_loop(ctx) -> float | None:
    """Mean bytes a loop of the fleet that touches the traced span needed:
    for each such loop (two consecutive step stamps), the documents that got
    an edit in it, in and out, and their op rows and payload up."""
    span = device_programs.traced_span(ctx)
    if span is None:
        return None
    geometry = ctx["spec"]["config"]["geometry"]
    ends = [g[2] for g in ctx["groups"]]
    needs = []
    status = ctx["status"]
    for (pt, pr), (t, r) in zip(status, status[1:]):
        if r <= pr or t < span[0] or pt > span[1]:
            continue
        lo, hi = bisect.bisect_right(ends, pr), bisect.bisect_right(ends, r)
        docs = {g[4] for g in ctx["groups"][lo:hi]}
        rows = sum(g[3] for g in ctx["groups"][lo:hi])
        needs.append(step_bytes_needed(len(docs), rows, geometry))
    return sum(needs) / len(needs) if needs else None


def roofline_share(ctx) -> float | None:
    """Percent: the least time the chip could take over a loop's bytes, over
    the mean device time of a whole step execution in the trace."""
    events = ctx["traced"].get("module_events")
    if not events:
        return None
    ns, n = whole_steps(events)
    need = bytes_needed_per_loop(ctx)
    if not n or need is None:
        return None
    return roofline.memory_roofline_share(
        need, ns / n / 1e9, ctx["ready"]["device_kind"],
        ctx["spec"]["cell"]["chips"])
