"""Which device program did the work: the fleet-wide step or the cohort trio.

Both paths dispatch the same jitted functions (``_fleet_step`` at K = 1,
``apply_megastep`` above) at different shapes, so a module's name does not say
which path ran it.  What does: a cohort step is bracketed on the device by the
gather before it and ``_scatter_cohort_jit`` after it.  Shared by the three
kernel readers under layer_metrics/.
"""

from __future__ import annotations

import bisect

STEP_MARKS = ("fleet_step", "megastep")
SCATTER_MARK = "scatter_cohort"


def is_step(program: str) -> bool:
    return any(m in program for m in STEP_MARKS)


def classify(module_events):
    """``module_events``: ``(program, start_ns, dur_ns)`` of ONE device.
    Returns {"fleet": {"ns", "executions"}, "cohort": {...}}: a step followed
    by the scatter is a cohort step, and the gather before it and the scatter
    after it are charged to the cohort path.

    The trace cuts whatever runs at its edges: the first event of a device
    can start before the trace did and the last is cut where it stops, and
    the event itself does not say so (a 4 s fleet step came back as 0.6 s).
    So an execution that holds the device's first or last event is not
    counted: only whole executions are."""
    ev = sorted(module_events, key=lambda e: e[1])
    edges = {0, len(ev) - 1}
    out = {"fleet": {"ns": 0, "executions": 0},
           "cohort": {"ns": 0, "executions": 0}}
    for i, (name, _s, d) in enumerate(ev):
        if not is_step(name):
            continue
        nxt = ev[i + 1][0] if i + 1 < len(ev) else ""
        if SCATTER_MARK in nxt:
            parts = [i, i + 1]
            if i and not is_step(ev[i - 1][0]) and (
                    SCATTER_MARK not in ev[i - 1][0]):
                parts.append(i - 1)           # the gather
            kind = "cohort"
        else:
            parts, kind = [i], "fleet"
        if edges.intersection(parts):
            continue
        out[kind]["ns"] += sum(ev[j][2] for j in parts)
        out[kind]["executions"] += 1
    return out


def traced_span(ctx):
    """The traced span in perf_counter seconds, or None."""
    t = ctx["traced"]
    if "clock" not in t or not t.get("window_s"):
        return None
    end = t["clock"]["stop_perf_ns"] / 1e9
    return end - t["window_s"], end


def split(ctx):
    """``classify`` plus the slices each path's executions carried: K of the
    flight recorder's dispatch spans of that kind inside the traced span
    (their mean times the executions seen on the device, so that a dispatch
    cut by the span's edge does not skew it)."""
    t = ctx["traced"]
    if not t.get("module_events"):
        return None
    out = classify(t["module_events"])
    span = traced_span(ctx)
    for kind, key in (("full", "fleet"), ("cohort", "cohort")):
        ks = [a.get("k", 1) for n, s0, _s1, a in t.get("flight", [])
              if n == "dispatch" and a.get("kind") == kind
              and span and span[0] <= s0 <= span[1]]
        mean_k = sum(ks) / len(ks) if ks else 1.0
        out[key]["slices"] = out[key]["executions"] * mean_k
        out[key]["mean_k"] = mean_k
    return out


def bytes_needed_per_loop(ctx):
    """Mean bytes a loop of the fleet that touches the traced span needed
    (roofline.py): for each such loop (two consecutive status lines), the
    documents that got an op in it, in and out, and the op rows up.  Per
    loop, because the device time it is held against is per whole execution
    (``classify``), and a loop is one dispatch."""
    import roofline

    span = traced_span(ctx)
    if span is None:
        return None
    doc_bytes = roofline.state_bytes_per_doc(
        ctx["ready"]["resident_bytes_per_device"], ctx["n_docs"])
    ends = [g[2] for g in ctx["groups"]]
    needs = []
    status = ctx["status"]
    for (pt, pr), (t, r) in zip(status, status[1:]):
        if r <= pr or t < span[0] or pt > span[1]:
            continue
        lo, hi = bisect.bisect_right(ends, pr), bisect.bisect_right(ends, r)
        docs = {g[4] for g in ctx["groups"][lo:hi]}
        rows = sum(g[3] for g in ctx["groups"][lo:hi])
        needs.append(roofline.step_bytes_needed(len(docs), rows, doc_bytes))
    return sum(needs) / len(needs) if needs else None
