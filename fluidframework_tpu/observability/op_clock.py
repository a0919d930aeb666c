"""The op's own clock: sequenced -> received -> applied, inside the program.

The sequencer stamps every message with ``time.time()`` when it orders it
(``SequencedMessage.timestamp``), and the stamp rides every wire line.  The
served path reads it once per FEED (one document's complete lines from one
pump) with ``wire_stamp``: no JSON parse, the oldest line's top-level field.
``received`` is the moment the feed's bytes were read from the socket: the
moment it is staged, unless the reader says otherwise (the consumer reads
ahead while a step is in flight and ingests a step later, inside
``received_at``).
``OpClock`` keeps ``(t_sequenced, t_received, rows, doc)`` per feed until the
engine's sync boundary, where every pending feed is resolved at one
``t_applied`` into three mergeable histograms whose samples are weighted by
the feed's rows, so their means add up:

    sequenced_to_received + received_to_applied = sequenced_to_applied

The wire stamp is another process's wall clock.  It is converted ONCE, at
``feed``, to ``perf_counter`` (the clock the step stamps, the flight recorder
and, through the benchmark's ``clock.json``, the device trace share) with an
offset taken at start-up and re-taken at every status line; a re-take that
moved by more than a millisecond (the wall clock was stepped) counts in
``clock_steps``.  A feed that staged rows and carried no stamp counts its
rows in ``unstamped_rows`` and is left out of the histograms: there is no
falling back to the time of receipt.  Past ``PENDING_MAX`` unresolved feeds a
feed's rows count in ``dropped_rows``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable

from ..utils.telemetry import Histogram

_STAMP_KEY = b',"timestamp":'
STAGES = ("sequenced_to_received", "received_to_applied",
          "sequenced_to_applied")


def wire_stamp(feed: bytes) -> float:
    """The sequencer's stamp of ``feed``'s oldest (first) line, 0.0 where it
    has none.  ``SequencedMessage.to_json`` writes ``timestamp`` after
    ``contents`` and ``metadata``, and a quote inside a JSON string is
    escaped, so the LAST ``,"timestamp":`` of the line is the top-level one
    whatever the contents hold."""
    end = feed.find(b"\n")
    if end < 0:
        end = len(feed)
    at = feed.rfind(_STAMP_KEY, 0, end)
    if at < 0:
        return 0.0
    at += len(_STAMP_KEY)
    stop = feed.find(b",", at, end)
    try:
        return float(feed[at:stop if stop >= 0 else end].rstrip(b"}\r "))
    except ValueError:
        return 0.0


class OpClock:
    """Per-feed stage clock of one engine; see the module's docstring."""

    PENDING_MAX = 4096      # feeds a step-starved engine may leave unresolved
    STEP_S = 1e-3           # an offset that moved by more was a clock step

    def __init__(
        self,
        n_shards: int = 1,
        shard_of: Callable[[int], int] | None = None,
        clock: Callable[[], float] = time.perf_counter,
        wall: Callable[[], float] = time.time,
    ) -> None:
        self.now = clock
        self._wall = wall
        self._shard_of = shard_of if n_shards > 1 else None
        self.sequenced_to_received = Histogram()
        self.received_to_applied = Histogram()
        self.sequenced_to_applied = Histogram()
        self.shard_latency = [Histogram() for _ in range(n_shards)]
        self.rows = 0
        self.unstamped_rows = 0
        self.dropped_rows = 0
        self.clock_steps = 0
        self._pending: list[tuple[float, float, int, int]] = []
        # The oldest stamp fed since ``take_wire_age`` last asked, with the
        # moment its feed was received (the pump span's ``wire_age_ms``).
        self._oldest: tuple[float, float] | None = None
        self._read_at: float | None = None  # inside ``received_at`` only
        self.offset = self._take_offset()

    def _take_offset(self) -> float:
        """``wall - clock`` read back to back; the tightest of three pairs,
        so that a preemption between the two reads is not taken for a step
        of the wall clock."""
        pairs = []
        for _ in range(3):
            a = self.now()
            w = self._wall()
            b = self.now()
            pairs.append((b - a, w - 0.5 * (a + b)))
        return min(pairs)[1]

    # ----------------------------------------------------------------- feeds
    @contextlib.contextmanager
    def received_at(self, t_read: float):
        """Whatever is fed inside the block was read at ``t_read`` (this
        clock's axis), earlier than it is staged."""
        self._read_at = t_read
        try:
            yield
        finally:
            self._read_at = None

    def received(self) -> float:
        """``received`` of the feed being staged: now, or inside
        ``received_at`` what the reader said."""
        return self.now() if self._read_at is None else self._read_at

    def feed(self, stamp: float, t_received: float, rows: int,
             doc: int = -1) -> None:
        """One feed that staged ``rows`` rows for ``doc``: ``stamp`` is its
        oldest line's wire stamp (wall clock), ``t_received`` the moment it
        reached the engine (this clock)."""
        if rows <= 0:
            return
        if not stamp > 0.0:
            self.unstamped_rows += rows
            return
        if len(self._pending) >= self.PENDING_MAX:
            self.dropped_rows += rows
            return
        t_sequenced = stamp - self.offset
        self._pending.append((t_sequenced, t_received, rows, doc))
        if self._oldest is None or t_sequenced < self._oldest[0]:
            self._oldest = (t_sequenced, t_received)

    def feed_lines(self, data: bytes, t_received: float, rows: int,
                   doc: int = -1) -> None:
        """``feed`` with the stamp of ``data``'s oldest line."""
        self.feed(wire_stamp(data), t_received, rows, doc)

    def take_wire_age(self) -> float | None:
        """Age in seconds, when it was received, of the oldest stamp fed
        since the last call; None where no stamped feed came."""
        oldest, self._oldest = self._oldest, None
        return None if oldest is None else oldest[1] - oldest[0]

    def resolve(self) -> None:
        """The engine's sync boundary: everything fed so far is applied."""
        if not self._pending:
            return
        t_applied = self.now()
        for t_sequenced, t_received, rows, doc in self._pending:
            # One clamp, so that the three means still add up.
            s2r = max(0.0, t_received - t_sequenced)
            r2a = max(0.0, t_applied - t_received)
            self.sequenced_to_received.record(s2r, rows)
            self.received_to_applied.record(r2a, rows)
            self.sequenced_to_applied.record(s2r + r2a, rows)
            if self._shard_of is not None and doc >= 0:
                self.shard_latency[self._shard_of(doc)].record(
                    s2r + r2a, rows)
            self.rows += rows
        self._pending.clear()

    # --------------------------------------------------------------- readers
    def histograms(self) -> dict[str, Histogram]:
        """The live histograms (``/metrics``): ``op_latency`` (the series'
        old name: it is ``sequenced_to_applied``), the three stages, and one
        ``op_latency_shard<s>`` per shard under a mesh."""
        out = {"op_latency": self.sequenced_to_applied}
        out.update((name, getattr(self, name)) for name in STAGES)
        if self._shard_of is not None:
            for s, h in enumerate(self.shard_latency):
                out[f"op_latency_shard{s}"] = h
        return out

    def emit_gauges(self, counters) -> None:
        """``health()``'s latency gauges, answered from
        ``sequenced_to_applied``: rows resolved, ms percentiles, and the
        per-shard p99 under a mesh."""
        h = self.sequenced_to_applied
        counters.gauge("latency_samples", h.count)
        if h.count:
            counters.gauge(
                "latency_p50_ms", round(h.percentile(0.5) * 1e3, 3))
            counters.gauge(
                "latency_p99_ms", round(h.percentile(0.99) * 1e3, 3))
        if self._shard_of is not None:
            counters.gauge(
                "shard_latency_p99_ms",
                [round(h.percentile(0.99) * 1e3, 3) if h.count else 0.0
                 for h in self.shard_latency],
            )

    def status(self) -> dict[str, Any]:
        """The status line's ``op_clock``: cumulative since start, lossless
        (readers take window deltas of bucket counts, ``sum``, ``count``).
        A status line is also where the offset is re-taken."""
        offset = self._take_offset()
        if abs(offset - self.offset) > self.STEP_S:
            self.clock_steps += 1
        self.offset = offset
        out: dict[str, Any] = {
            name: getattr(self, name).to_wire() for name in STAGES}
        out.update(rows=self.rows, unstamped_rows=self.unstamped_rows,
                   dropped_rows=self.dropped_rows,
                   clock_steps=self.clock_steps)
        return out
