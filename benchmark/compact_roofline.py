"""What a compaction of some documents needs, from the configuration's
geometry and the lanes dispatched alone, and which device programs are the
cohort compaction.

Zamboni is integer compare/select and a stable compaction over the columnar
segment arrays of each acked document: no matrix unit is involved, so its
roofline is the memory one.  The bytes a dispatch NEEDS are its lanes'
per-segment columns and obliterate table, ``nseg`` and ``min_seq``, read once
and written once; the text pool is no part of it (zamboni does not touch it).
A sort and 22 element-wise gathers a lane move far more and wait on latency;
that is what the share shows.  Nothing here reads the program: the count is
the same work whatever implements the kernel.
"""

from __future__ import annotations

import device_programs
import roofline

# fleet_main runs DocBatchEngine at its defaults for these three (it has no
# flag for them); the configuration's ``geometry`` gives the rest.
REMOVE_SLOTS = 4
PROP_SLOTS = 4
OB_SLOTS = 8
SEGMENT_COLUMNS = 6 + 2 * REMOVE_SLOTS + 2 * PROP_SLOTS   # int32[S] each: 22
OB_COLUMNS = 7                                            # int32[OB] each
SCALARS = 2                                               # nseg, min_seq
# ``jit__compact_cohort``: the engine's cohort compaction.  The fleet-wide
# program is ``jit__fleet_compact_body`` and is not counted here.
MODULE_MARK = "compact_cohort"


def lane_bytes(geometry: dict) -> int:
    """Bytes of one document's state that zamboni reads or writes."""
    return 4 * (SEGMENT_COLUMNS * int(geometry["segments_per_doc"])
                + OB_COLUMNS * OB_SLOTS + SCALARS)


def dispatch_bytes_needed(lanes: float, geometry: dict) -> float:
    return 2.0 * lanes * lane_bytes(geometry)


def whole_executions(module_events) -> tuple[int, int]:
    """``(device ns, executions)`` of the cohort compaction among
    ``(program, start_ns, dur_ns)`` of ONE device, the device's first and
    last event left out: the trace cuts whatever runs at its edges without
    saying so (``device_programs.classify``)."""
    ev = sorted(module_events, key=lambda e: e[1])
    whole = [d for name, _s, d in ev[1:-1] if MODULE_MARK in name]
    return sum(whole), len(whole)


def mean_lanes_in_trace(ctx) -> float | None:
    """Mean lanes of the flight recorder's cohort ``compact`` spans inside
    the traced span: what the executions seen on the device carried."""
    span = device_programs.traced_span(ctx)
    if span is None:
        return None
    lanes = [a.get("lanes") for n, s0, _s1, a in ctx["traced"].get("flight", [])
             if n == "compact" and a.get("kind") == "cohort"
             and span[0] <= s0 <= span[1]]
    lanes = [x for x in lanes if x]
    return sum(lanes) / len(lanes) if lanes else None


def roofline_share(ctx) -> float | None:
    """Percent: the least time the chip could take over a dispatch's bytes,
    over the mean device time of a whole execution in the trace."""
    events = ctx["traced"].get("module_events")
    if not events:
        return None
    ns, n = whole_executions(events)
    lanes = mean_lanes_in_trace(ctx)
    if not n or lanes is None:
        return None
    need = dispatch_bytes_needed(lanes, ctx["spec"]["config"]["geometry"])
    return roofline.memory_roofline_share(
        need, ns / n / 1e9, ctx["ready"]["device_kind"],
        ctx["spec"]["cell"]["chips"])
