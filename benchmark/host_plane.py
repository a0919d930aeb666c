"""What the profiler itself recorded of the host's spans and of the kernel's
scopes (PR 23): the adapter for the ``device_idle_in_*_share``,
``clock_pairing_error_ms`` and ``kernel_*_share`` readers.

While a flight recorder is installed every span of the serving thread is also
a ``jax.profiler.TraceAnnotation`` (``observability/flight_recorder.py``), so a
traced run's ``.xplane.pb`` holds the host spans on the ``/host:CPU`` plane, on
the profiler's own clock, beside the device planes: a device idle gap and the
annotation it falls into need no pairing of clocks.  The merge-tree kernel's
``jax.named_scope`` names (``ops/mergetree_kernel.py``: ``BRANCH_SCOPES``) are
the ``op_name`` of every instruction; the TPU xplane carries it as the stat
``tf_op`` of the ``XLA Ops`` events' METADATA (found with a probe on the v5e,
PR 23), but only for instructions that kept their metadata: most fusions
of the TPU compiler carry none of their own (85-92% of a step's device time in
the first traced runs).  What every instruction of every fused computation
came from is in the program's ``HloProto``, which the xplane holds too (stat
``Hlo Proto`` of the program's entry on the ``/host:metadata`` plane): a
fusion's time is split among the scopes of the instructions fused into it, by
their count (``kernel_own_scope_share``: how much needed no such split).  An
op whose program has no ``HloProto`` there, or a program that decodes to
something XLA cannot have written, fails the run (``BenchFailure``) rather
than read as unscoped: the field numbers below are JAX 0.9's, and a moved one
must not pass for a result.  ``jax.profiler.ProfileData`` shows an event's own stats only,
neither its metadata's nor that plane's bytes, so this file reads the protobuf
wire format itself: the messages of ``xplane.proto`` and four of
``hlo.proto``, no import beyond the standard library.  That also keeps JAX out
of it, so it needs no process of its own and ``of(ctx)`` runs it in the
benchmark's parent, once per run.

    python benchmark/host_plane.py <file.xplane.pb>    # the reduction, JSON
"""

from __future__ import annotations

import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import device_programs  # noqa: E402
import traces  # noqa: E402

HOST_PLANE = "/host:CPU"
DEVICE_PLANE_PREFIX = "/device:TPU:"
# The serving thread's top-level spans (fleet_main's loop), and the children
# the readers ask for.
TOP_SPANS = ("pump", "step", "status", "idle")
HOST_SPANS = TOP_SPANS + ("pump.select", "dispatch", "readback")
# ops/mergetree_kernel.py BRANCH_SCOPES, as the metrics name them.  A copy:
# the program may rename a scope later, the yardstick then reads "unscoped".
KERNEL_SCOPES = ("insert", "remove", "annotate", "obliterate", "ack")
UNSCOPED = "unscoped"
SCOPE_STAT = "tf_op"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# Instructions of a fused computation that do no work of their own.
PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


# ------------------------------------------------- protobuf wire format
def _varint(buf, i: int) -> tuple[int, int]:
    v, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
            yield no, wt, v
            continue
        if wt == 2:
            ln, i = _varint(buf, i)
        elif wt == 1:
            ln = 8
        elif wt == 5:
            ln = 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield no, wt, buf[i:i + ln]
        i += ln


MASK64 = (1 << 64) - 1


def _i64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf):
    key, value = 0, b""
    for no, _wt, v in _fields(buf):
        if no == 1:
            key = _i64(v)
        elif no == 2:
            value = v
    return key, value


def _event_metadata(buf, stat_names):
    """XEventMetadata -> (name, {stat name: value}): strings, integers, and
    the bytes of the one stat that is a serialized message (HLO_STAT)."""
    name, stats = "", {}
    for no, _wt, v in _fields(buf):
        if no == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif no == 5:
            sid, sval = 0, None
            for sno, swt, sv in _fields(v):
                if sno == 1:
                    sid = _i64(sv)
                elif sno in (3, 4) and swt == 0:
                    sval = sv
                elif sno == 5:
                    sval = bytes(sv).decode("utf-8", "replace")
                elif sno == 6:
                    sval = sv
                elif sno == 7:
                    sval = stat_names.get(sv, "")
            key = stat_names.get(sid, "")
            if sval is not None and (
                    not isinstance(sval, memoryview) or key == HLO_STAT):
                stats[key] = sval
    return name, stats


def hlo_program(buf) -> dict:
    """A serialized ``HloProto`` -> ``{"instructions": {name: (opcode,
    op_name, called computation ids)}, "computations": {id: [(opcode,
    op_name)]}}``: where every instruction came from, fused ones too."""
    out = {"instructions": {}, "computations": {}}
    for no, _wt, module in _fields(buf):
        if no != 1:
            continue
        for mno, _mwt, comp in _fields(module):
            if mno != 3:
                continue
            cid, members = 0, []
            for cno, _cwt, v in _fields(comp):
                if cno == 5:
                    cid = _i64(v)
                elif cno == 2:
                    name = opcode = op_name = ""
                    called = []
                    for ino, iwt, iv in _fields(v):
                        if ino == 1:
                            name = bytes(iv).decode()
                        elif ino == 2:
                            opcode = bytes(iv).decode()
                        elif ino == 7:
                            for ono, _owt, ov in _fields(iv):
                                if ono == 2:
                                    op_name = bytes(ov).decode()
                        elif ino == 38:
                            if iwt == 0:
                                called.append(iv)
                            else:   # packed
                                i = 0
                                while i < len(iv):
                                    c, i = _varint(iv, i)
                                    called.append(c)
                    out["instructions"][name] = (opcode, op_name, called)
                    members.append((opcode, op_name))
            out["computations"][cid] = members
    return out


def check_program(name: str, program: dict) -> dict:
    """Raises where a decoded step program cannot be what XLA wrote: no
    instruction, none with an ``op_name`` (JAX gives every op one, scopes or
    not), or a fusion whose fused computation is not there.  The field
    numbers above are hlo.proto's of JAX 0.9; a moved one would otherwise
    read as "everything unscoped"."""
    instrs = program["instructions"]
    why = None
    if not instrs:
        why = "no instruction decoded"
    elif not any(op_name for _op, op_name, _c in instrs.values()):
        why = f"none of {len(instrs)} instructions has an op_name"
    else:
        for iname, (opcode, _n, called) in instrs.items():
            if opcode == "fusion" and not any(
                    c in program["computations"] for c in called):
                why = f"fusion {iname} has no fused computation"
                break
    if why:
        raise traces.BenchFailure(
            f"host_plane: HloProto of {name}: {why}; hlo.proto's layout "
            "moved (hlo_program's field numbers)")
    return program


def read_xspace(path: str, keep_line=lambda plane, line: True) -> list[dict]:
    """The planes of an ``.xplane.pb``: ``{"name", "metadata": {id: (name,
    stats)}, "lines": [{"name", "events": [(metadata id, start_ns,
    dur_ns)]}]}``.  Event times are the line's timestamp plus the event's
    offset: one axis for every plane of the file."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for no, _wt, pbuf in _fields(space):
        if no != 1:
            continue
        name, lines, meta_raw, stat_names = "", [], [], {}
        for pno, _pwt, v in _fields(pbuf):
            if pno == 2:
                name = bytes(v).decode()
            elif pno == 3:
                lines.append(v)
            elif pno == 4:
                meta_raw.append(v)
            elif pno == 5:
                sid, sbuf = _map_entry(v)
                for sno, _swt, sv in _fields(sbuf):
                    if sno == 2:
                        stat_names[sid] = bytes(sv).decode()
        plane = {"name": name, "lines": [], "metadata": {}}
        for raw in meta_raw:
            mid, mbuf = _map_entry(raw)
            plane["metadata"][mid] = _event_metadata(mbuf, stat_names)
        for lbuf in lines:
            lname, ts_ns, events = "", 0, []
            for lno, _lwt, v in _fields(lbuf):
                if lno == 2:
                    lname = bytes(v).decode()
                elif lno == 3:
                    ts_ns = _i64(v)
                elif lno == 4:
                    events.append(v)
            if not keep_line(name, lname):
                continue
            out = []
            for ebuf in events:
                mid = off_ps = dur_ps = 0
                for eno, _ewt, v in _fields(ebuf):
                    if eno == 1:
                        mid = _i64(v)
                    elif eno == 2:
                        off_ps = _i64(v)
                    elif eno == 3:
                        dur_ps = _i64(v)
                out.append((mid, ts_ns + off_ps // 1000, dur_ps // 1000))
            plane["lines"].append({"name": lname, "events": out})
        planes.append(plane)
    return planes


# ------------------------------------------------------------ pure functions
def scope_of(op_name: str | None) -> str:
    """The outermost kernel scope in an instruction's ``op_name``
    (``jit(_fleet_step)/vmap()/while/body/insert/open_slot/select_n``)."""
    for part in (op_name or "").split("/"):
        if part in KERNEL_SCOPES:
            return part
    return UNSCOPED


def overlap_s(gaps, spans) -> float:
    """Seconds (of whatever unit both are in) of ``gaps`` inside ``spans``;
    both lists of ``(start, end)``, each sorted and not overlapping."""
    total, j = 0, 0
    spans = sorted(spans)
    for g0, g1 in sorted(gaps):
        while j < len(spans) and spans[j][1] <= g0:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < g1:
            total += max(0, min(spans[k][1], g1) - max(spans[k][0], g0))
            k += 1
    return total


def align(part, whole):
    """``part``: starts of some consecutive events of ``whole`` seen on
    another clock that may be offset by a constant.  Returns the index in
    ``whole`` of ``part[0]``: where the intervals between the events agree
    best, nearest to no offset among equals; with fewer than two events,
    the nearest start."""
    n = len(part)
    if not n or len(whole) < n:
        return None
    best, at = None, None
    for j in range(len(whole) - n + 1):
        cost = sum(
            abs((part[i + 1] - part[i]) - (whole[j + i + 1] - whole[j + i]))
            for i in range(n - 1))
        key = (round(cost, 6), abs(whole[j] - part[0]))
        if best is None or key < best:
            best, at = key, j
    return at


def own_scope(program: dict | None, name: str, tf_op: str | None) -> str:
    """The kernel scope an instruction's OWN ``op_name`` names: the event's
    ``tf_op``, else the ``HloProto``'s entry for it; else unscoped."""
    own = scope_of(tf_op)
    if own != UNSCOPED or not program:
        return own
    return scope_of(program["instructions"].get(name, ("", "", ()))[1])


def instruction_scopes(program: dict | None, name: str,
                       tf_op: str | None) -> dict[str, float]:
    """``{scope: weight}`` (weights add to 1) of one instruction on the
    device: its own ``op_name`` where that names a kernel scope; for a
    fusion, the scopes of the instructions fused into it, by their count
    (a fusion of the TPU compiler rarely keeps an ``op_name`` of its own, and
    may mix two branches and the select that merges them); else unscoped."""
    own = own_scope(program, name, tf_op)
    if own != UNSCOPED or not program:
        return {own: 1.0}
    opcode, _op_name, called = program["instructions"].get(
        name, ("", "", ()))
    if opcode != "fusion":
        return {own: 1.0}
    counts: dict[str, int] = {}
    for cid in called:
        for iop, iname in program["computations"].get(cid, ()):
            if iop not in PLUMBING:
                sc = scope_of(iname)
                counts[sc] = counts.get(sc, 0) + 1
    total = sum(counts.values())
    return {k: v / total for k, v in counts.items()} if total else {own: 1.0}


def instruction_name(event_name: str) -> str:
    """``%fusion.7 = s32[8]{0} fusion(...)`` -> ``fusion.7``."""
    return event_name.partition(" = ")[0].lstrip("%")


def step_self_times(op_events, module_events):
    """Self time per op of the ops inside WHOLE executions of the step
    programs on one device.  ``op_events``/``module_events``: ``(key, start,
    dur)`` of the 'XLA Ops' and 'XLA Modules' lines; a module's key is its
    program name.  An execution that is the line's first or last module
    event may be cut by the trace and is left out.  Returns ``({op key:
    ns}, executions)``."""
    mods = sorted(module_events, key=lambda e: e[1])
    whole = [(s, s + d) for i, (name, s, d) in enumerate(mods)
             if device_programs.is_step(name) and 0 < i < len(mods) - 1]
    self_ns: dict = {}
    if whole:
        ops = sorted(op_events, key=lambda e: e[1])
        starts = [e[1] for e in ops]
        for s, e in whole:
            inside = ops[bisect.bisect_left(starts, s):
                         bisect.bisect_left(starts, e)]
            for key, ns in traces.self_times(inside).items():
                self_ns[key] = self_ns.get(key, 0) + ns
    return self_ns, len(whole)


def serving_thread(planes: list[dict]) -> dict | None:
    """The host plane's line with the most top-level annotations (the
    serving thread's): ``{"line", "spans": {name: [[start, dur]]}}``."""
    best = None
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            spans: dict[str, list] = {}
            for mid, start, dur in line["events"]:
                name = plane["metadata"].get(mid, ("", {}))[0]
                if name in HOST_SPANS:
                    spans.setdefault(name, []).append([start, dur])
            top = sum(len(spans.get(n, ())) for n in TOP_SPANS)
            if top and (best is None or top > best[0]):
                best = (top, line["name"], spans)
    return best and {"line": best[1], "spans": best[2]}


def reduce_planes(planes: list[dict]) -> dict:
    """Host spans of the serving thread, device idle gaps and the kernel
    split, all on the profiler's clock (nanoseconds)."""
    out: dict = {"host": serving_thread(planes), "device": None}
    # The step programs' HLO, by program id (the xplane names a program
    # ``jit__fleet_step(<id>)`` and stamps every op with ``program_id``).
    programs: dict[int, dict] = {}
    for plane in planes:
        if plane["name"] == METADATA_PLANE:
            for mid, (name, stats) in plane["metadata"].items():
                if HLO_STAT in stats and device_programs.is_step(name):
                    programs[mid & MASK64] = check_program(
                        name, hlo_program(stats[HLO_STAT]))
    devices = sorted((p for p in planes
                      if p["name"].startswith(DEVICE_PLANE_PREFIX)),
                     key=lambda p: p["name"])
    first = last = None
    gaps: list = []
    by_scope: dict[str, float] = {}
    unscoped: dict[str, float] = {}
    own_ns = 0.0
    no_hlo: dict[int, str] = {}
    executions = 0
    used = []
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get("XLA Ops", [])
        if not ops:
            continue
        used.append(plane["name"])
        _busy, merged = traces.union((s, s + d) for _m, s, d in ops)
        first = merged[0][0] if first is None else min(first, merged[0][0])
        last = merged[-1][1] if last is None else max(last, merged[-1][1])
        if len(used) == 1:     # idle gaps are read on the first device
            gaps = traces.gaps_between(merged, traces.MIN_GAP_NS)
        meta = plane["metadata"]
        mods = [(traces.program_of(meta.get(m, ("", {}))[0]), s, d)
                for m, s, d in lines.get("XLA Modules", [])]
        self_ns, n = step_self_times(ops, mods)
        executions += n
        for m, ns in self_ns.items():
            name, stats = meta.get(m, ("", {}))
            pid = stats.get("program_id", -1) & MASK64
            program = programs.get(pid)
            instr, tf_op = instruction_name(name), stats.get(SCOPE_STAT)
            if program is None and scope_of(tf_op) == UNSCOPED:
                # Nothing says where this op came from: not "unscoped".
                no_hlo.setdefault(pid, instr)
                continue
            if own_scope(program, instr, tf_op) != UNSCOPED:
                own_ns += ns
            for scope, w in instruction_scopes(program, instr, tf_op).items():
                by_scope[scope] = by_scope.get(scope, 0) + ns * w
                if scope == UNSCOPED:
                    key = traces.short_op(name)
                    unscoped[key] = unscoped.get(key, 0) + ns * w
    if no_hlo:
        raise traces.BenchFailure(
            f"host_plane: ops of {len(no_hlo)} step program(s) run in the "
            f"traced span have no '{HLO_STAT}' on {METADATA_PLANE} to read "
            f"their scopes from ({len(programs)} step programs have one), "
            "e.g. " + ", ".join(f"{i} of program {p}"
                                for p, i in sorted(no_hlo.items())[:3])
            + ": the xplane's or the HloProto's layout moved, or the "
            "profiler stopped keeping HLO")
    if used:
        n = len(used)
        out["device"] = {
            "planes": used, "first_ns": first, "last_ns": last,
            "gaps": [list(g) for g in gaps],
            "step_executions": executions / n,
            "step_programs_with_hlo": len(programs),
            "kernel_ns": {k: v / n for k, v in by_scope.items()},
            "kernel_own_scope_ns": own_ns / n,
            "unscoped_top": [[k, v / n] for k, v in sorted(
                unscoped.items(), key=lambda kv: -kv[1])[:12]],
        }
    return out


def reduce_file(path: str) -> dict:
    def keep(plane: str, line: str) -> bool:
        return plane == HOST_PLANE or (
            plane.startswith(DEVICE_PLANE_PREFIX)
            and line in ("XLA Ops", "XLA Modules"))

    return reduce_planes(read_xspace(path, keep))


# ------------------------------------------------------------------ one run
def of(ctx: dict) -> dict | None:
    """The reduction of this run's xplane, made once and kept in ``ctx``;
    None without a profile."""
    if "host_plane" not in ctx:
        pd = ctx.get("profile_dir")
        path = traces.find_xplane(pd) if pd else None
        ctx["host_plane"] = reduce_file(path) if path else None
    return ctx["host_plane"]


def device_idle_inside(ctx: dict, span: str) -> float | None:
    """Percent of the traced device span in which the first device sat idle
    while the serving thread was inside a ``span`` annotation."""
    hp = of(ctx)
    if not hp or not hp["host"] or not hp["device"]:
        return None
    spans = hp["host"]["spans"].get(span)
    dev = hp["device"]
    if not spans or dev["last_ns"] <= dev["first_ns"]:
        return None
    idle = overlap_s([tuple(g) for g in dev["gaps"]],
                     [(s, s + d) for s, d in spans])
    return 100.0 * idle / (dev["last_ns"] - dev["first_ns"])


def kernel_share(ctx: dict, scope: str) -> float | None:
    """Percent of the step programs' device self time (whole executions in
    the traced span) under ``scope``; None where no op carries a kernel
    scope at all (a program from before the scopes, or a stale executable)."""
    hp = of(ctx)
    if not hp or not hp["device"]:
        return None
    ns = hp["device"]["kernel_ns"]
    total = sum(ns.values())
    if not total or not any(ns.get(s) for s in KERNEL_SCOPES):
        return None
    return 100.0 * ns.get(scope, 0) / total


def kernel_own_scope_share(ctx: dict) -> float | None:
    """Percent of the same self time whose scope is the instruction's own
    ``op_name``; the rest of what is scoped was a fusion's time split by
    the count of what was fused into it."""
    hp = of(ctx)
    if not hp or not hp["device"] or kernel_share(ctx, UNSCOPED) is None:
        return None
    own = hp["device"].get("kernel_own_scope_ns")
    if own is None:
        return None
    return 100.0 * own / sum(hp["device"]["kernel_ns"].values())


def main(argv: list[str]) -> int:
    print(json.dumps(reduce_file(argv[0])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
