"""Where a tree step's device time goes, by the ``jax.named_scope`` names of
``ops/tree_kernel.py``'s op body (PR 28): the tree family's reading of a
traced run's ``.xplane.pb``, on ``host_plane.py``'s parser.

An instruction's scope is the path of kernel scopes in its ``op_name``
(``jit(apply_nested_fleet)/while/body/cond/branch_1_fun/vmap(remove)/
kill_descendants/select_n`` -> ``remove/kill_descendants``); a fusion that kept no ``op_name`` of its own
is split among the scopes of the instructions fused into it, by their count,
as ``host_plane.instruction_scopes`` does for the merge-tree kernel.  A step
program compiled before the scopes existed (a parent commit's) reads
``unscoped`` throughout, and a trace with no tree step reads nothing: neither
is an error here.

    python benchmark/tree_scopes.py <file.xplane.pb>    # the reduction, JSON
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import host_plane  # noqa: E402
import traces  # noqa: E402
import tree_roofline  # noqa: E402

# ops/tree_kernel.py NESTED_SCOPES and compact_nested's, as the trace names
# them.  A copy: the program may rename a scope, the yardstick then reads
# "unscoped".
TREE_SCOPES = ("resolve", "insert", "remove", "set_value", "move",
               "replace_field", "pool_write", "kill_descendants", "compact")
UNSCOPED = "unscoped"


_WRAPPED = re.compile(r"\w+\((\w*)\)")


def scope_of(op_name: str | None) -> str:
    """The path of kernel scopes in an ``op_name``.  A scope entered under a
    transform with nothing between them is written into the transform's
    name: ``jit(compact_nested)/vmap(compact)/add`` is in ``compact``."""
    parts = []
    for p in (op_name or "").split("/"):
        m = _WRAPPED.fullmatch(p)
        if m:
            p = m.group(1)
        if p in TREE_SCOPES:
            parts.append(p)
    return "/".join(parts) or UNSCOPED


def instruction_scopes(program: dict | None, name: str,
                       tf_op: str | None) -> dict[str, float]:
    """``{scope: weight}`` (weights add to 1) of one instruction."""
    own = scope_of(tf_op)
    if own != UNSCOPED or not program:
        return {own: 1.0}
    opcode, op_name, called = program["instructions"].get(name, ("", "", ()))
    own = scope_of(op_name)
    if own != UNSCOPED or opcode != "fusion":
        return {own: 1.0}
    counts: dict[str, int] = {}
    for cid in called:
        for iop, iname in program["computations"].get(cid, ()):
            if iop not in host_plane.PLUMBING:
                sc = scope_of(iname)
                counts[sc] = counts.get(sc, 0) + 1
    total = sum(counts.values())
    return {k: v / total for k, v in counts.items()} if total else {own: 1.0}


def reduce_planes(planes: list[dict]) -> dict | None:
    """``{"executions", "scope_ns": {scope: ns}}`` over whole executions of
    the tree step programs on the first device that ran any; None where the
    trace holds none."""
    programs: dict[int, dict] = {}
    for plane in planes:
        if plane["name"] == host_plane.METADATA_PLANE:
            for mid, (name, stats) in plane["metadata"].items():
                if host_plane.HLO_STAT in stats and (
                        tree_roofline.STEP_MARK in name):
                    programs[mid & host_plane.MASK64] = host_plane.hlo_program(
                        stats[host_plane.HLO_STAT])
    for plane in sorted((p for p in planes if p["name"].startswith(
            host_plane.DEVICE_PLANE_PREFIX)), key=lambda p: p["name"]):
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        meta = plane["metadata"]
        mods = sorted(
            ((traces.program_of(meta.get(m, ("", {}))[0]), s, d)
             for m, s, d in lines.get("XLA Modules", [])),
            key=lambda e: e[1])
        whole = [(s, s + d) for name, s, d in mods[1:-1]
                 if tree_roofline.STEP_MARK in name]
        if not whole:
            continue
        ops = sorted(lines.get("XLA Ops", []), key=lambda e: e[1])
        starts = [e[1] for e in ops]
        scope_ns: dict[str, float] = {}
        for s, e in whole:
            inside = ops[bisect.bisect_left(starts, s):
                         bisect.bisect_left(starts, e)]
            for m, ns in traces.self_times(inside).items():
                name, stats = meta.get(m, ("", {}))
                program = programs.get(
                    stats.get("program_id", -1) & host_plane.MASK64)
                for scope, w in instruction_scopes(
                        program, host_plane.instruction_name(name),
                        stats.get(host_plane.SCOPE_STAT)).items():
                    scope_ns[scope] = scope_ns.get(scope, 0.0) + ns * w
        return {"executions": len(whole), "scope_ns": scope_ns,
                "step_programs_with_hlo": len(programs)}
    return None


def reduce_file(path: str) -> dict | None:
    def keep(plane: str, line: str) -> bool:
        return plane.startswith(host_plane.DEVICE_PLANE_PREFIX) and (
            line in ("XLA Ops", "XLA Modules"))

    return reduce_planes(host_plane.read_xspace(path, keep))


def breakdown_of(ctx: dict) -> list | None:
    """``[[scope, device seconds]]`` of this run's traced tree steps, the
    largest first, made once and kept in ``ctx``; None without a profile or
    without a tree step in it."""
    if "tree_scopes" not in ctx:
        pd = ctx.get("profile_dir")
        path = traces.find_xplane(pd) if pd else None
        red = reduce_file(path) if path else None
        ctx["tree_scopes"] = red and sorted(
            ([k, v / 1e9] for k, v in red["scope_ns"].items()),
            key=lambda kv: -kv[1])
    return ctx["tree_scopes"]


def main(argv: list[str]) -> int:
    print(json.dumps(reduce_file(argv[0])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
