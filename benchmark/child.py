"""The process half of a run, the same for every family: the child that owns
the chip, with every stdout line stamped on arrival (a status line's arrival
is the proof of the step stamps it carries, ``lag.py``), and the gated burst.

``Child`` and ``Gate`` are copies of ``chip_smoke.py``'s (PR 21, sound on the
chip): the program may change later, the yardstick may not.  The writers, the
edits and the comparison that decides ``correct`` belong to a family and live
under ``plants/``.

Nothing here imports JAX: the child is the only process that touches the chip.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import signal
import subprocess
import threading
import time

_ROWS = re.compile(r'^\{"rows": (\d+),')


class BenchFailure(Exception):
    """The run cannot produce a result; the message says why."""


class Child:
    """One child process that owns the chip.  A reader thread stamps every
    stdout line with ``time.perf_counter()`` on arrival and keeps it raw (a
    full pipe must never stall the fleet; parsing waits until someone asks).
    stderr goes to a file."""

    def __init__(self, name: str, cmd: list[str], env: dict, workdir: str,
                 cwd: str) -> None:
        self.name = name
        self.err_path = os.path.join(workdir, f"{name}.stderr")
        self._err = open(self.err_path, "w")
        self.t_spawn = time.perf_counter()
        self.proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=self._err, text=True,
            env=env, cwd=cwd,
        )
        self.lines: list[tuple[float, str]] = []
        self._cursor = 0
        self._eof = False
        self._cv = threading.Condition()
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            t = time.perf_counter()
            with self._cv:
                self.lines.append((t, line))
                self._cv.notify_all()
        with self._cv:
            self._eof = True
            self._cv.notify_all()

    def _next_line(self, deadline: float, what: str) -> tuple[float, str]:
        with self._cv:
            while self._cursor >= len(self.lines):
                if self._eof:
                    raise BenchFailure(
                        f"{self.name}: exited (code {self.proc.wait()}) "
                        f"while waiting for {what}\n{self.stderr_tail()}")
                left = deadline - time.perf_counter()
                if left <= 0:
                    raise BenchFailure(
                        f"{self.name}: timed out waiting for {what}")
                self._cv.wait(min(left, 1.0))
            self._cursor += 1
            return self.lines[self._cursor - 1]

    def next_json(self, deadline: float, what: str) -> tuple[float, dict]:
        """The next JSON object the child printed and when it arrived."""
        while True:
            t, line = self._next_line(deadline, what)
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict):
                return t, obj

    def wait_for(self, pred, deadline: float, what: str) -> tuple[float, dict]:
        while True:
            t, obj = self.next_json(deadline, what)
            if "error" in obj and "health" not in obj:
                raise BenchFailure(f"{self.name}: {obj}")
            if pred(obj):
                return t, obj

    def wait_rows(self, rows: int, deadline: float, what: str) -> None:
        """Block until a status line reports at least ``rows`` applied,
        reading only the line's first field (no JSON parse of health)."""
        while True:
            got = status_rows(self._next_line(deadline, what)[1])
            if got is not None and got >= rows:
                return

    def finish(self, deadline: float) -> int:
        try:
            return self.proc.wait(
                timeout=max(1.0, deadline - time.perf_counter()))
        except subprocess.TimeoutExpired:
            raise BenchFailure(f"{self.name}: did not exit") from None

    def stderr_tail(self, n: int = 3000) -> str:
        self._err.flush()
        with open(self.err_path) as f:
            return f.read()[-n:]

    def kill(self) -> None:
        if self.proc.poll() is None:
            with contextlib.suppress(OSError):
                os.kill(self.proc.pid, signal.SIGCONT)
            self.proc.kill()
            self.proc.wait()
        self._reader.join(timeout=10)
        self._err.close()


def status_rows(line: str) -> int | None:
    m = _ROWS.match(line)
    return int(m.group(1)) if m else None


class Gate:
    """Deliver one burst of traffic to the fleet ATOMICALLY (chip_smoke's
    trick).  The fleet picks its program from how many documents are busy in
    one pump (cohort buckets by power of two, fleet-wide above a quarter of
    the fleet) and how deep their queues are (megastep K).  So: wait until
    the fleet has APPLIED everything sent so far, stop the process the way a
    descheduled consumer stops, let the front hand the whole burst to the
    kernel's socket buffers, and continue it: the next pump sees every socket
    ready at once, and the burst reaches one chosen program."""

    def __init__(self, child: Child, plant) -> None:
        self.child, self.plant = child, plant

    def burst(self, what: str, doc_ids, send, deadline: float) -> None:
        self.child.wait_rows(self.plant.ops, deadline,
                             f"the previous burst to be applied before {what}")
        os.kill(self.child.proc.pid, signal.SIGSTOP)
        try:
            send()
            self.plant.drained(doc_ids, deadline)
        finally:
            os.kill(self.child.proc.pid, signal.SIGCONT)
