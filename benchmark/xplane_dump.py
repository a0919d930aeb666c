"""Adapter from the profiler's ``.xplane.pb`` to what ``traces.reduce_device``
takes.  The only file of the benchmark's parent side that imports JAX (for
``jax.profiler.ProfileData``), so it runs as a process of its own, on the
CPU, after the child has given the chip back.

    python benchmark/xplane_dump.py <file.xplane.pb>          # one JSON line
    python benchmark/xplane_dump.py --list <file.xplane.pb>   # look by hand
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

DEVICE_PLANE_PREFIX = "/device:TPU:"
LINES = ("XLA Ops", "XLA Modules")


def read_planes(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if line.name in LINES:
                lines.setdefault(line.name, []).extend(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
    return planes


def list_planes(path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns)


def main(argv: list[str]) -> int:
    if argv[0] == "--list":
        list_planes(argv[1])
        return 0
    import traces

    print(json.dumps(traces.reduce_device(read_planes(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
