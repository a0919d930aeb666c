"""The child that owns the chip: ``fleet_main.main(argv)`` unchanged.

    python benchmark/fleet_child.py [--profile-dir DIR --profile-seconds S] -- <fleet_main argv>

Two things are added around ``main`` and nothing inside it:

- after it returns, one more JSON line with the peak device memory
  (``fleet_main`` does not report it, and the result's ``device`` needs it);
- with ``--profile-dir`` (a traced run only), a side thread waits for the
  file ``DIR/go`` that the parent drops near the window's end, then runs
  ``jax.profiler.start_trace`` .. ``stop_trace`` for S seconds and writes
  ``DIR/clock.json`` with ``perf_counter_ns`` and the wall clock at both
  ends, so that flight-recorder spans (``perf_counter_ns``) can be laid over
  the device trace.  ``fleet_main`` has no profiler switch; when it gets one
  this wrapper can go (PERF.md, tracing list).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _profile_when_asked(profile_dir: str, seconds: float,
                        stop: threading.Event) -> None:
    import jax

    go = os.path.join(profile_dir, "go")
    while not os.path.exists(go):
        if stop.wait(0.05):
            return
    clock = {"start_perf_ns": time.perf_counter_ns(),
             "start_wall_ns": time.time_ns()}
    # The device planes are what the reductions read; the Python tracer
    # would slow the serving thread and is most of a trace's bytes.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(profile_dir, profiler_options=options)
    clock["started_perf_ns"] = time.perf_counter_ns()
    stop.wait(seconds)
    clock["stop_perf_ns"] = time.perf_counter_ns()
    clock["stop_wall_ns"] = time.time_ns()
    jax.profiler.stop_trace()
    clock["stopped_perf_ns"] = time.perf_counter_ns()
    with open(os.path.join(profile_dir, "clock.json.tmp"), "w") as f:
        json.dump(clock, f)
    os.replace(os.path.join(profile_dir, "clock.json.tmp"),
               os.path.join(profile_dir, "clock.json"))


def main(argv: list[str]) -> int:
    cut = argv.index("--")
    own, fleet_argv = argv[:cut], argv[cut + 1:]
    profile_dir, seconds = None, 5.0
    for i in range(0, len(own), 2):
        if own[i] == "--profile-dir":
            profile_dir = own[i + 1]
        elif own[i] == "--profile-seconds":
            seconds = float(own[i + 1])
        else:
            raise SystemExit(f"fleet_child: unknown option {own[i]}")
    sys.path.insert(0, ROOT)
    from fluidframework_tpu.server import fleet_main

    stop = threading.Event()
    prof = None
    if profile_dir is not None:
        prof = threading.Thread(
            target=_profile_when_asked, args=(profile_dir, seconds, stop),
            daemon=True)
        prof.start()
    try:
        rc = fleet_main.main(fleet_argv)
    finally:
        stop.set()
        if prof is not None:
            prof.join(timeout=280)
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [int(s.get("peak_bytes_in_use", 0)) for s in stats]
    print(json.dumps({"memory_peak_bytes": max(peaks),
                      "memory_peak_bytes_per_device": peaks,
                      "memory_stats_device0": stats[0]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
