"""Fleet consumer: wire bytes -> native encoder -> device, end to end.

The production ingest path (VERDICT r3 weak #4): subscribes to the
netserver's firehose (``{"t": "consume"}`` — bare SequencedMessage JSON
lines, the deltas-topic consumer seam; ref deli consume path,
server/routerlicious/packages/lambdas/src/deli/lambda.ts:851) for a fleet of
documents and feeds the RAW BYTES into a ``DocBatchEngine`` through the C++
wire encoder (native/ingest.cpp).  The Python data plane touches bytes only
at chunk granularity — per-socket ``recv``, one ``rfind(b"\\n")`` to peel
the trailing partial line, one ``ingest_lines`` call; all JSON parsing,
quorum lookup, insert chunking, and op-row encoding run in C++, and op
application runs on device in the batched engine step.

With a megastep-enabled engine (``DocBatchEngine(megastep_k=K)``, the
``fleet_main --megastep-k`` flag) each ``step()`` fuses up to K staged op
slices into one donated device dispatch — ``health()`` surfaces the realized
amortization as ``steps_per_dispatch`` / ``megastep_k`` /
``staging_overlap_packs`` alongside the transport counters.

The serving thread reads the sockets while a step is in flight (ROADMAP S2):
``step()`` hands the engine ``_read_ahead``, which the engine calls once,
between the step's last dispatch and the readback of its error latch, with
that latch.  Where the thread used to sleep in the readback it now sleeps in
ONE ``select`` that wakes for bytes or for the device: a helper thread
(``_DeviceWaker``) blocks in the latch's ``block_until_ready`` and writes a
byte to a socket pair in the consumer's selector.  What the sockets deliver
meanwhile is read and KEPT; the next ``pump()`` hands it to the engine first,
in the order read.  Only bytes move early: nothing is staged, probed for an
ack or counted in ``rows_staged`` before that pump, so a step's stamp still
proves exactly the rows the step applied and an ack still compacts after the
rows read before it.  ``health()`` counts how often it engages:
``reads_in_flight`` and ``bytes_read_in_flight`` (engine health counters, so
in every status line) beside ``bytes_consumed``.

With a mesh-served engine (``fleet_main --mesh N``) the same dispatch is a
``shard_map`` program over an N-device docs mesh: staging packs by doc
placement, uploads carry the shard layout, and ``health()`` adds the
per-shard load surface (``shard_ops``/``shard_queue_depth``/``hot_shards``)
that drives live doc migration (``engine.rebalance_hot_shards``).
"""

from __future__ import annotations

import contextlib
import http.client
import json
import queue
import selectors
import socket
import threading
import time

from ..fanout.plane import RESYNC_BOOT_MARKER
from ..models.doc_batch_engine import DocBatchEngine
from ..observability.flight_recorder import span

_BOOT_MARKER = RESYNC_BOOT_MARKER.rstrip(b"\n")
APPLIED_CAPACITY = 512  # step stamps kept between two status lines
_WAKE = -1  # the waker's key in the selector, where a socket's is its doc index


class _DeviceWaker:
    """Sleeps in ``block_until_ready`` so that the serving thread need not:
    ``watch(latch)`` answers with one byte on ``sock`` when the device has
    produced ``latch``, whatever became of the computation (an error is the
    serving thread's own readback's to raise).  One thread for the
    consumer's life, one byte per ``watch``."""

    def __init__(self) -> None:
        self.sock, self._tell = socket.socketpair()
        self._latches: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, name="device-waker", daemon=True)
        self._thread.start()

    def watch(self, latch) -> None:
        self._latches.put(latch)

    def _run(self) -> None:
        while (latch := self._latches.get()) is not None:
            with contextlib.suppress(Exception):
                latch.block_until_ready()
            self._tell.send(b"\0")

    def close(self) -> None:
        self._latches.put(None)
        self._thread.join(5)
        self.sock.close()
        self._tell.close()


class FleetConsumer:
    """One firehose socket per document, feeding one batched engine."""

    def __init__(
        self,
        host: str,
        port: int,
        engine: DocBatchEngine,
        doc_ids: list[str],
        recv_bytes: int = 1 << 16,
        boot_store=None,
        historian: tuple[str, int] | None = None,
    ) -> None:
        if len(doc_ids) > engine.n_docs:
            raise ValueError(
                f"{len(doc_ids)} documents > engine capacity {engine.n_docs}"
            )
        self.engine = engine
        self.doc_ids = list(doc_ids)
        self._host = host
        self._port = port
        # Snapshot-boot tier address ((host, port) of the historian HTTP
        # front): the client half of the fan-out plane's
        # ``{"t":"resync","boot":true}`` contract — when a firehose falls
        # off the retained log, the consumer fetches the latest historian
        # snapshot, adopts it into the engine, and re-consumes from its
        # seq.  Without it a boot marker kills the doc's socket (the
        # supervisor restart path, the pre-PR-14 behavior).
        self._historian = historian
        self.boot_resyncs = 0
        self.boot_resync_failures = 0
        self.booted_docs: list[int] = []
        if boot_store is not None:
            # Boot-from-summary: seed the engine from the latest acked
            # scribe commits (or checkpoint records) BEFORE attaching, so
            # the firehose catch-up replay of the covered prefix is
            # skipped by seq floor and only the post-ack tail applies
            # (counted as boot_replay_len in engine health).
            self.booted_docs = engine.restore_from_checkpoints(
                store=boot_store
            )
        self._recv_bytes = recv_bytes
        self._socks: list[socket.socket] = []
        self._tails: list[bytes] = [b"" for _ in doc_ids]
        self.rows_staged = 0
        self.bytes_consumed = 0
        # Summary acks read so far, per document, and whether one was handed
        # to the engine since the last step (the serving loop then steps
        # even if the pump staged no row: the ack's compaction runs there).
        self.acks_by_doc = [0] * len(doc_ids)
        self.acks_unstepped = False
        # Doc indices whose firehose socket the SERVER closed (shard
        # restart/shutdown): the consumer is dead for those docs and its
        # supervisor should restart it.
        self.dead_socks: set[int] = set()
        # Credit-based flow control: docs over the engine's high ingest
        # watermark have their socket UNREGISTERED from the selector (no
        # reads, socket kept open) until the queue drains below the low
        # watermark — the backlog backs up into the kernel buffer and the
        # server's outbound queue, where admission control sees it and
        # starts shedding producers.  The engine's OverloadGate owns the
        # hysteresis; this set mirrors which sockets are parked.
        self.paused_socks: set[int] = set()
        self.pump_pauses = 0
        self.pump_resumes = 0
        # Sockets whose feeds the last pump handed on: those its select
        # found ready and those read ahead of it (0: it had nothing).
        self.last_ready = 0
        # Feeds ``_read_ahead`` read while a step was in flight, kept for
        # the next pump: ``(doc index, complete lines, time read)``.  They
        # are what arrived while the device ran ONE step (a compile happens
        # inside the dispatch call, before the seam), from sockets that flow
        # control has not parked; a fleet that exits holds them as it holds
        # the bytes still in its sockets, unread and under no durable floor
        # (that advances at ingest).
        self._kept: list[tuple[int, bytes, float]] = []
        self._waker: _DeviceWaker | None = None
        # How often that engages, among the engine's health counters (so in
        # every status line, from 0): wake-ups in the seam that found a
        # socket ready, and the bytes of ``bytes_consumed`` read there.
        engine.counters.bump("reads_in_flight", 0)
        engine.counters.bump("bytes_read_in_flight", 0)
        # The step stamps (D13): ``[t_seen, t_applied, rows_staged]`` per
        # step that advanced ``rows_staged``, on the flight recorder's clock
        # (``perf_counter``).  ``t_seen``: when the oldest feed the step
        # applied was read (this iteration's ``select`` reported work, or
        # the seam of the step before), or the iteration's start for a step
        # on paused partitions alone; ``t_applied``: ``eng.step()`` has
        # returned, everything staged is applied and the error latch read
        # back.  A status line takes them (``take_applied``); past
        # ``APPLIED_CAPACITY`` a stamp is counted in ``applied_dropped``.
        self.applied: list[list] = []
        self.applied_dropped = 0
        self._applied_rows = 0
        self._t_iter = time.perf_counter()
        self._t_seen: float | None = None
        self._sel = selectors.DefaultSelector()  # epoll: no FD_SETSIZE cap
        try:
            self._waker = _DeviceWaker()
            self._sel.register(self._waker.sock, selectors.EVENT_READ, _WAKE)
            for doc_id in doc_ids:
                s = self._subscribe(doc_id)
                self._socks.append(s)  # tracked immediately: any later
                self._sel.register(   # failure closes the whole set
                    s, selectors.EVENT_READ, len(self._socks) - 1
                )
        except BaseException:
            self.close()
            raise

    def _subscribe(self, doc_id: str, from_seq: int = 0) -> socket.socket:
        """Open one firehose subscription (handshake done, socket
        nonblocking); ``from_seq`` skips the already-covered prefix of the
        catch-up (the boot-resync re-consume floor)."""
        s = self._connect(self._host, self._port)
        try:
            req = {"t": "consume", "doc": doc_id}
            if from_seq:
                req["from"] = from_seq
            s.sendall((json.dumps(req) + "\n").encode())
            # Unbuffered ack read: a buffered reader would swallow
            # catch-up bytes already in flight behind the ack line.
            ack_buf = bytearray()
            while not ack_buf.endswith(b"\n"):
                ch = s.recv(1)
                if not ch:
                    raise RuntimeError(
                        "connection closed during consume handshake"
                    )
                ack_buf += ch
            ack = json.loads(ack_buf)
            if ack.get("t") != "consuming":
                raise RuntimeError(f"consume handshake failed: {ack}")
            s.setblocking(False)
            return s
        except BaseException:
            s.close()
            raise

    @staticmethod
    def _connect(host: str, port: int) -> socket.socket:
        """getaddrinfo-iterating connect (IPv6/multi-address hosts) with a
        deep receive buffer set BEFORE connect (so the TCP window scales):
        the producer can dump a whole backlog into the kernel in one go
        instead of 64KB ping-pong gated on the consumer's drain cadence."""
        err: Exception | None = None
        for family, kind, proto, _cn, addr in socket.getaddrinfo(
            host, port, type=socket.SOCK_STREAM
        ):
            s = socket.socket(family, kind, proto)
            try:
                s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                s.settimeout(30)
                s.connect(addr)
                return s
            except OSError as e:
                err = e
                s.close()
        raise err if err is not None else OSError(f"no addresses for {host}")

    # ------------------------------------------------------------ data plane
    def pump(self, wait_s: float = 0.02, idle=None) -> int:
        """Hand the engine every feed read since the last pump; returns op
        rows staged this pass.

        First the feeds ``_read_ahead`` kept while the last step was in
        flight, in the order read, then every socket READY now, drained
        once.  One ``select`` readiness wait covers the whole socket set —
        an idle socket costs nothing (the old per-socket recv-timeout walk
        stalled the drain up to 50ms per quiet socket per pass, which was
        most of the measured wire-ingest gap); a pump that already holds
        feeds does not wait at all.

        ``idle`` is the caller's open ``idle`` span (``fleet_main``), if it
        has one: the wait in ``select`` then belongs to that span, which
        ends here the moment there is a feed to hand on (``last_ready``
        tells the caller), and a pump that found nothing records no span at
        all, so an idle fleet leaves the flight recorder's ring alone."""
        self.last_ready = 0
        self._t_iter, self._t_seen = time.perf_counter(), None
        if self._kept:
            wait_s = 0
        elif len(self.dead_socks) == len(self._socks):
            return 0
        if idle is None:
            with span("pump") as sp:
                # Resume first: queues drained by step() between pumps may
                # have fallen below the low watermark — re-register those
                # sockets so this very select sees their backlog.
                self._apply_flow_control()
                with span("pump.select"):
                    ready = self._sel.select(wait_s)
                return self._drain_ready(ready, sp)
        self._apply_flow_control()
        ready = self._sel.select(wait_s)
        if not ready and not self._kept:
            return 0
        idle.__exit__(None, None, None)
        with span("pump") as sp:
            return self._drain_ready(ready, sp)

    def _drain_ready(self, ready, sp) -> int:
        """``pump``'s work once ``select`` has returned: read every ready
        socket, then ingest the kept feeds and this pump's own; labels the
        pump's span ``sp``."""
        kept, self._kept = self._kept, []
        if kept:
            # The oldest feed the next step applies was read in the seam.
            self._t_seen = kept[0][2]
            self.engine.counters.bump(
                "bytes_read_in_flight", sum(len(f) for _i, f, _t in kept))
        elif ready:
            self._t_seen = time.perf_counter()
        self.last_ready = len(ready) + len(kept)
        bytes_before = self.bytes_consumed
        staged = self._ingest(kept + self._read(ready))
        sp.set(ready=len(ready), bytes=self.bytes_consumed - bytes_before,
               staged=staged)
        # How old the oldest sequencer stamp this pump handed on was when
        # its feed was read: "the bytes came late" or "the pump came late".
        age = self.engine.op_clock.take_wire_age()
        if age is not None:
            sp.set(wire_age_ms=round(age * 1e3, 3))
        return staged

    def _read(self, ready) -> list[tuple[int, bytes, float]]:
        """``recv`` every ready socket until it would block, join with the
        socket's tail and cut at the last newline: ``(doc index, complete
        lines, time read)`` per socket that completed a line.  Marks the
        sockets the server closed; calls nothing of the engine's, so it may
        run while a step is in flight."""
        feeds = []
        for key, _events in ready:
            idx, sock = key.data, key.fileobj
            if idx in self.dead_socks:
                continue
            chunks: list[bytes] = []
            while True:
                try:
                    data = sock.recv(self._recv_bytes)
                except (BlockingIOError, TimeoutError, socket.timeout):
                    break
                except OSError:
                    self._mark_dead(idx, sock)
                    break
                if not data:  # orderly close: the shard went away
                    self._mark_dead(idx, sock)
                    break
                chunks.append(data)
            if not chunks:
                continue
            buf = self._tails[idx] + b"".join(chunks)
            cut = buf.rfind(b"\n")
            if cut < 0:
                self._tails[idx] = buf
                continue
            self._tails[idx] = buf[cut + 1 :]
            feeds.append((idx, buf[: cut + 1], time.perf_counter()))
        return feeds

    def _read_ahead(self, latch) -> None:
        """The engine's seam (``engine.step(in_flight)``): the step's last
        dispatch is in flight and ``latch`` is its error latch.  Until the
        device has produced it, sleep in ``select`` and read what the
        sockets deliver, keeping it for the next ``pump``; the waker's byte
        ends the wait, and is always taken before this returns."""
        if latch.is_ready():
            return
        self._waker.watch(latch)
        woken = False
        try:
            while not woken:
                ready = self._sel.select()
                socks = [kv for kv in ready if kv[0].data != _WAKE]
                woken = len(socks) < len(ready)
                if socks:
                    with span("pump.ahead", ready=len(socks)) as sp:
                        feeds = self._read(socks)
                        self._kept += feeds
                        self.engine.counters.bump("reads_in_flight")
                        sp.set(bytes=sum(len(f) for _i, f, _t in feeds))
        finally:
            self._waker.sock.recv(1)  # blocks only on the way out of a raise

    def _ingest(self, feeds) -> int:
        """Hand ``feeds`` (``_read``'s) to the engine in order: the ack
        probe, the boot marker, ``ingest_lines``, then flow control and the
        acked documents' compaction.  Returns the op rows staged."""
        staged = 0
        acked: list[int] = []
        resynced: set[int] = set()
        clock = self.engine.op_clock
        for idx, feed, t_read in feeds:
            if idx in resynced:
                # Read from the socket a boot marker has since replaced:
                # post-marker bytes, which the new subscription re-delivers.
                continue
            self.bytes_consumed += len(feed)
            # Scribe-driven MSN: a summary ack in the feed is the zamboni
            # TRIGGER for THIS document (a feed is one document's socket;
            # one substring probe per feed, anchored on the wire type field,
            # no extra parse).  The compaction floor itself is the host's
            # min_seq, refreshed by the ack message's own min_seq stamp
            # through ingest; the ack's contents["msn"] is the durable
            # ack-derived floor, carried on the wire for consumers that need
            # durability-bounded windows.
            n_acks = feed.count(b'"type":"summaryAck"')
            if n_acks:
                acked.append(idx)
                self.acks_by_doc[idx] += n_acks
            # The op clock's ``received`` is when the bytes were read.
            with clock.received_at(t_read):
                if _BOOT_MARKER in feed:
                    # Fan-out plane drop-to-catch-up, boot flavor: the missed
                    # range left the retained log — snapshot-boot instead of
                    # consuming a gapped stream (one substring probe per
                    # chunk, same idiom as the summaryAck trigger).
                    staged += self._handle_boot_marker(idx, feed)
                    resynced.add(idx)
                    continue
                staged += self.engine.ingest_lines(idx, feed)
        self.rows_staged += staged
        if staged:
            # Pause any doc this pass pushed over its high watermark BEFORE
            # the next select, so one hot doc stops accumulating host-side
            # the moment the megastep budget falls behind.
            self._apply_flow_control()
        if acked:
            # Compact collab windows on the ack, not on a timer, and only
            # the acked documents': the engine compacts them in the step
            # that has applied the rows read before the ack (never here,
            # inside the pump).
            self.engine.compact(acked)
            self.acks_unstepped = True
            self.engine.counters.bump("msn_compactions")
            self.engine.counters.bump("acks_seen", len(acked))
        return staged

    def _handle_boot_marker(self, idx: int, feed: bytes) -> int:
        """Consume the pre-marker prefix, then snapshot-boot: fetch the
        latest historian snapshot, adopt it into the engine, and
        re-subscribe the firehose from its seq.  Post-marker bytes are
        DISCARDED — the re-subscription's catch-up re-delivers everything
        past the adopted floor, so dropping them is what keeps the stream
        gapless."""
        head, _, _rest = feed.partition(_BOOT_MARKER)
        cut = head.rfind(b"\n")
        staged = 0
        if cut >= 0:
            staged += self.engine.ingest_lines(idx, head[: cut + 1])
        self._tails[idx] = b""
        self._boot_resync(idx)
        return staged

    def _boot_resync(self, idx: int) -> None:
        doc_id = self.doc_ids[idx]
        old = self._socks[idx]
        with contextlib.suppress(KeyError, ValueError):
            self._sel.unregister(old)
        with contextlib.suppress(OSError):
            old.close()
        try:
            if self._historian is None:
                raise RuntimeError(
                    "boot resync marker without a historian address"
                )
            # Short timeout: this fetch runs on the pump thread (boot
            # resyncs are rare, but a wedged historian must not stall the
            # whole fleet's drain for long — failure falls to the
            # supervisor restart path below).
            conn = http.client.HTTPConnection(*self._historian, timeout=5)
            try:
                conn.request("GET", f"/doc/{doc_id}/snapshot")
                resp = conn.getresponse()
                body = json.loads(resp.read() or b"{}")
            finally:
                conn.close()
            if resp.status != 200:
                raise RuntimeError(f"historian snapshot read: {body}")
            # The historian's seq stamp is authoritative (the snapshot's
            # commit seq), so it lands after the record's own keys.
            record = {**body["summary"], "doc": doc_id,
                      "seq": int(body["seq"])}
            result = self.engine.adopt_boot_snapshot(idx, record)
            if not result.adopted:
                # Refused below the doc's floor: the snapshot cannot help,
                # and the server already declared this consumer's range
                # gone — re-subscribing from the engine's own floor would
                # just draw another boot marker (an infinite resync loop
                # that looks healthy).  Fall to the supervisor path.
                raise RuntimeError(
                    f"boot snapshot seq {record['seq']} at or below doc "
                    f"floor {result.floor}: nothing to adopt"
                )
            sock = self._subscribe(doc_id, from_seq=result.floor)
        except (OSError, RuntimeError, ValueError, KeyError) as e:
            # No snapshot to boot from (or the re-subscribe died): the doc
            # is dead for this consumer, exactly like a server close — the
            # supervisor restart path owns it from here.
            self.boot_resync_failures += 1
            self.engine.counters.bump("boot_resync_failures")
            self.dead_socks.add(idx)
            if self.engine.counters.logger is not None:
                self.engine.counters.logger.error(
                    "boot_resync_failed", f"doc {doc_id}: {e}"
                )
            return
        self._socks[idx] = sock
        self._sel.register(sock, selectors.EVENT_READ, idx)
        self.paused_socks.discard(idx)
        self.boot_resyncs += 1
        self.engine.counters.bump("boot_resyncs_handled")

    def _apply_flow_control(self) -> None:
        """Advance the engine's watermark hysteresis and park/re-arm the
        affected firehose sockets (per-partition pause/resume).  A paused
        socket stays open — its unread broadcast accumulates in the kernel
        buffer and the shard's outbound queue, which is exactly the signal
        the front's admission control sheds producers on."""
        to_pause, to_resume = self.engine.update_overload()
        for d in to_pause:
            if d in self.dead_socks or d in self.paused_socks:
                continue
            self.paused_socks.add(d)
            self.pump_pauses += 1
            with contextlib.suppress(KeyError, ValueError):
                self._sel.unregister(self._socks[d])
        for d in to_resume:
            if d not in self.paused_socks:
                continue
            self.paused_socks.discard(d)
            if d in self.dead_socks:
                continue
            self.pump_resumes += 1
            self._sel.register(self._socks[d], selectors.EVENT_READ, d)

    def step(self) -> int:
        """Apply everything staged as one batched device step (the engine
        runs its own recovery, watchdog cadence, and checkpoint cadence
        inside ``step`` when configured), then compact the documents whose
        summary ack a pump handed over since the last step."""
        eng = self.engine
        self.acks_unstepped = False
        # A consumer with a dead socket is about to checkpoint and leave to
        # its supervisor: it reads nothing more that it would not apply.
        ahead = None if self.dead_socks else self._read_ahead
        with span("step", docs=len(eng._busy)) as sp:
            dispatches = eng.counters.get("megastep_dispatches")
            slices = eng.step(ahead)
            self._stamp_applied()
            sp.set(slices=slices, dispatches=eng.counters.get(
                "megastep_dispatches") - dispatches)
        return slices

    def _stamp_applied(self) -> None:
        """``eng.step()`` has just returned: stamp the step if it advanced
        ``rows_staged``."""
        now = time.perf_counter()
        if self.rows_staged <= self._applied_rows:
            return
        self._applied_rows = self.rows_staged
        if len(self.applied) >= APPLIED_CAPACITY:
            self.applied_dropped += 1
            return
        seen = self._t_seen if self._t_seen is not None else self._t_iter
        self.applied.append([seen, now, self.rows_staged])

    def take_applied(self) -> list[list]:
        """The stamps taken since the last call (a status line's)."""
        out, self.applied = self.applied, []
        return out

    def health(self) -> dict:
        """Engine health counters + this consumer's transport state."""
        out = self.engine.health()
        out.update(
            dead_socks=len(self.dead_socks),
            rows_staged=self.rows_staged,
            bytes_consumed=self.bytes_consumed,
            booted_docs=len(self.booted_docs),
            paused_docs=len(self.paused_socks),
            pump_pauses=self.pump_pauses,
            pump_resumes=self.pump_resumes,
            boot_resyncs=self.boot_resyncs,
            boot_resync_failures=self.boot_resync_failures,
        )
        return out

    def run_for(self, expected_rows: int, max_idle_pumps: int = 200) -> None:
        """Pump until ``expected_rows`` op rows staged (test/bench driver);
        raises if the stream stays idle for ``max_idle_pumps`` passes."""
        idle = 0
        while self.rows_staged < expected_rows:
            if self.paused_socks:
                # A doc hit its ingest watermark: drain the backlog on
                # device so the gate can re-arm its socket (the serving
                # loop's step() plays this role in production).
                self.step()
            if self.pump() == 0:
                idle += 1
                if idle >= max_idle_pumps:
                    raise TimeoutError(
                        f"firehose idle: {self.rows_staged}/{expected_rows} rows"
                    )
            else:
                idle = 0
        self.step()

    def _mark_dead(self, idx: int, sock: socket.socket) -> None:
        self.dead_socks.add(idx)
        # A paused (already-unregistered) socket can die too: suppress the
        # double-unregister, keep the dead mark.
        with contextlib.suppress(KeyError, ValueError):
            self._sel.unregister(sock)

    def close(self) -> None:
        for s in self._socks:
            with contextlib.suppress(OSError):
                s.close()
        self._socks = []
        with contextlib.suppress(OSError, AttributeError):
            self._sel.close()
        if self._waker is not None:
            self._waker.close()
            self._waker = None
