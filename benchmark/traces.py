"""From traces and spans to numbers: the flight recorder's host spans, the
profiler's device trace, and the two laid over each other.

Pure functions first (checked by selftest.py on synthetic input and on a
small recorded trace), then ``reduce_run``, which finds a traced run's files.

Clocks.  Flight-recorder spans and every stamp the parent takes are
``perf_counter`` (CLOCK_MONOTONIC on Linux, one clock for all processes of a
machine).  The profiler's events carry their own nanosecond clock; the child
notes ``perf_counter_ns`` just before ``start_trace`` returns and just after
``stop_trace`` is called (``clock.json``), and the device events are laid
between those two stamps: the trace's first and last event are taken to be at
most the traced span apart, and the offset puts the trace's END at the stop
stamp (the profiler stops collecting at once; it starts lazily).
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import subprocess
import sys

from child import BenchFailure

HOST_SPANS = ("ingest", "upload", "dispatch", "readback")
IDLE_ELSE = "pump/status/sleep"
MIN_GAP_NS = 100_000          # device gaps under 0.1 ms are not reported


# ------------------------------------------------------------ pure functions
def union(intervals):
    """Merge ``(start, end)`` pairs; returns ``(covered, merged)``."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def gaps_between(merged, min_len):
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] - a[1] >= min_len]


def self_times(events):
    """``events``: ``(name, start, dur)`` on ONE line, where an event may
    enclose others (a ``while`` and the ops of its body).  Returns total
    self time per name: an event's duration minus what its direct children
    cover."""
    out: dict[str, int] = {}
    stack: list[list] = []   # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0) + max(done[2], 0)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, end, dur])
    for done in stack:
        out[done[0]] = out.get(done[0], 0) + max(done[2], 0)
    return out


def short_op(name: str) -> str:
    """``%fusion.7 = s32[6144,4096]{0,1:T(8,128)} fusion(...)`` ->
    ``fusion.7 s32[6144,4096]``: the instruction and its first result."""
    head, _, rest = name.partition(" = ")
    shape = rest.split(" ")[0].split("{")[0].strip("(),") if rest else ""
    return (head.lstrip("%") + " " + shape).strip()


def program_of(module_event_name: str) -> str:
    """``jit__fleet_step(123456789)`` -> ``jit__fleet_step``."""
    return module_event_name.split("(")[0]


def reduce_device(planes: dict, min_gap_ns: int = MIN_GAP_NS) -> dict:
    """``planes``: {device plane name: {line name: [(name, start_ns,
    dur_ns), ...]}} as the adapter hands them over.  Busy time is the union
    of the intervals on the ops line; programs come from the modules line."""
    devices = []
    modules: dict[str, dict] = {}
    ops: dict[str, int] = {}
    first = last = None
    gaps: list[tuple[int, int]] = []
    for pname in sorted(planes):
        lines = planes[pname]
        op_events = lines.get("XLA Ops", [])
        if not op_events:
            continue
        busy, merged = union((s, s + d) for _n, s, d in op_events)
        devices.append({"plane": pname, "busy_ns": busy,
                        "first_ns": merged[0][0], "last_ns": merged[-1][1],
                        "events": len(op_events)})
        first = merged[0][0] if first is None else min(first, merged[0][0])
        last = merged[-1][1] if last is None else max(last, merged[-1][1])
        if not gaps:   # idle gaps are labelled on the first device
            gaps = gaps_between(merged, min_gap_ns)
        for name, t in self_times(op_events).items():
            key = short_op(name)
            ops[key] = ops.get(key, 0) + t
        for name, _s, d in lines.get("XLA Modules", []):
            m = modules.setdefault(program_of(name), {"count": 0, "ns": 0})
            m["count"] += 1
            m["ns"] += d
    n = max(len(devices), 1)
    first_plane = devices[0]["plane"] if devices else None
    module_events = [
        [program_of(nm), s, d]
        for nm, s, d in planes.get(first_plane, {}).get("XLA Modules", [])
    ][:50000]
    for m in modules.values():   # mean over the devices that ran them
        m["count"] = m["count"] / n
        m["ns"] = m["ns"] / n
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    return {
        "devices": devices, "first_ns": first, "last_ns": last,
        "busy_ns": sum(d["busy_ns"] for d in devices) / n if devices else 0,
        "modules": modules, "module_events": module_events,
        "ops": [[k, v / n] for k, v in top[:40]],
        "ops_total_ns": sum(ops.values()) / n,
        # Share of the ops' self time in rematerialised clones and copies.
        "remat_ns": sum(v for k, v in ops.items() if "remat" in k) / n,
        "copy_ns": sum(v for k, v in ops.items()
                       if k.startswith(("copy", "slice-start", "reshape")))
        / n,
        "gaps": sorted(gaps, key=lambda g: g[0] - g[1])[:4000],
    }


def label_gaps(gaps, spans):
    """``gaps``: ``(start, end)`` seconds; ``spans``: ``(name, start, end)``
    seconds, host spans of the serving thread.  Each gap is split among the
    spans that overlap it; what no span covers is IDLE_ELSE.  Returns
    seconds of device idleness per label."""
    out: dict[str, float] = {}
    # The serving thread's spans follow one another, so sorted by start they
    # are sorted by end too.
    spans = sorted((s for s in spans if s[0] in HOST_SPANS),
                   key=lambda s: s[1])
    ends = [s[2] for s in spans]
    for g0, g1 in gaps:
        covered = 0.0
        i = bisect.bisect_right(ends, g0)
        while i < len(spans) and spans[i][1] < g1:
            name, s0, s1 = spans[i]
            ov = min(s1, g1) - max(s0, g0)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                covered += ov
            i += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            out[IDLE_ELSE] = out.get(IDLE_ELSE, 0.0) + rest
    return out


def load_flight(path: str):
    """The flight recorder's Chrome trace -> ``(name, start_s, end_s,
    args)`` in perf_counter seconds."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        raw = json.load(f)
    return [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6,
             e.get("args") or {})
            for e in raw["traceEvents"] if e["ph"] == "X"]


def busy_share(spans, name: str, t0: float, t1: float) -> float | None:
    """Percent of [t0, t1] inside spans called ``name`` (clipped)."""
    total, seen = 0.0, False
    for n, s0, s1, _a in spans:
        if n == name:
            seen = True
            total += max(0.0, min(s1, t1) - max(s0, t0))
    return 100.0 * total / (t1 - t0) if seen else None


def find_xplane(profile_dir: str) -> str | None:
    for base, _dirs, files in os.walk(profile_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    return None


# ------------------------------------------------------------------ one run
def reduce_run(ctx: dict) -> dict:
    """Everything a traced run's per-layer readers share."""
    out: dict = {}
    fp = ctx.get("flight_path")
    if fp and os.path.exists(fp):
        if ctx["tail"].get("dropped"):
            raise BenchFailure(
                f"flight recorder dropped {ctx['tail']['dropped']} events")
        out["flight"] = load_flight(fp)
        stats: dict = {}
        for n, s0, s1, a in out["flight"]:
            key = "/".join(str(x) for x in (
                n, a.get("kind", ""), a.get("lanes", ""), a.get("k", "")))
            st = stats.setdefault(key, [0, 0.0, 0.0])
            st[0] += 1
            st[1] += s1 - s0
            st[2] = max(st[2], s1 - s0)
        out["span_stats"] = stats
    pd = ctx.get("profile_dir")
    xplane = find_xplane(pd) if pd else None
    clock_path = os.path.join(pd, "clock.json") if pd else None
    if xplane and os.path.exists(clock_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__),
                                          "xplane_dump.py"), xplane],
            capture_output=True, text=True, env=env, timeout=900)
        if r.returncode != 0:
            raise RuntimeError(f"xplane_dump failed:\n{r.stderr[-2000:]}")
        dev = json.loads(r.stdout.strip().splitlines()[-1])
        with open(clock_path) as f:
            clock = json.load(f)
        out["clock"] = clock
        out["device_summary"] = {
            k: v for k, v in dev.items() if k not in ("gaps", "module_events")}
        out["module_events"] = dev["module_events"]
        if dev["devices"]:
            out["window_s"] = (dev["last_ns"] - dev["first_ns"]) / 1e9
            out["busy_s"] = dev["busy_ns"] / 1e9
            out["modules"] = dev["modules"]
            # Trace clock -> perf_counter seconds: END of the device
            # events at the stop stamp.
            off = clock["stop_perf_ns"] - dev["last_ns"]
            gaps_s = [((a + off) / 1e9, (b + off) / 1e9)
                      for a, b in dev["gaps"]]
            out["gaps"] = gaps_s
            spans = [(n, s0, s1) for n, s0, s1, _a in out.get("flight", [])]
            idle = label_gaps(gaps_s, spans)
            out["idle_by_host_span"] = idle
            out["breakdown"] = {
                "device_ops": [[n, ns / 1e9] for n, ns in dev["ops"][:10]],
                "idle_gaps": [[k, v] for k, v in sorted(
                    idle.items(), key=lambda kv: -kv[1])[:10]],
            }
    return out
