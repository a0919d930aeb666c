"""Host-side op staging for the megastep pipeline (doc/tree batch engines).

The per-slice dispatch path used to allocate a fresh ``np.zeros`` [D, B]
batch every device step and upload it synchronously with the dispatch.  The
megastep pipeline replaces that with a ring of PREALLOCATED [K, D, B]
staging buffers:

- **Reuse, not reallocation**: buffers are zeroed lazily — only the rows a
  previous megastep actually wrote are cleared before refill (tracked per
  slice), so idle lanes cost nothing and the allocator is out of the hot
  loop entirely.
- **Double buffering**: with ``depth=2`` the engine packs megastep N+1 into
  one buffer while the ``jax.device_put`` + dispatch of megastep N is still
  reading the other.  Before a buffer is reused, the ring blocks on the
  device arrays produced FROM IT (transfer completion only, two megasteps
  stale by then — in the steady state a no-op wait), so the host never
  mutates memory an in-flight upload may still be reading.  This is the
  only sync the pipeline takes between megasteps; full synchronization
  happens solely at the engine's recover()/watchdog/checkpoint boundaries.
- **Zero-copy backends**: on backends where host->device "transfer" is
  zero-copy (CPU jax: ``jnp.asarray(np_arr)`` ALIASES the numpy memory),
  the uploaded device array reads the staging buffer for as long as it
  lives — reuse would mutate the input of an asynchronously executing (or
  even future) dispatch.  ``acquire`` detects this by pointer probe and
  hands the memory over to the device arrays, swapping a fresh buffer
  into the ring slot (``aliased_swaps`` counts these).  That degrades the
  reuse win to exactly the seed's allocate-per-step behavior on CPU while
  keeping the DMA-backed reuse path on real accelerators.
"""

from __future__ import annotations

import numpy as np


class RowQueue:
    """Columnar per-document pending-op queue: one [N, F] op-row array and
    one [N, L] payload array with head/tail cursors, replacing the
    list-of-tiny-arrays queues that made the host feeder touch Python per
    op.  The batched ingest path lands whole wire batches with ONE slice
    copy per document (``extend_block``), and ``_drain_into`` consumes
    with one slice copy per document per slice (``take``) — the host cost
    of a message is amortized over its batch, not paid per op row.

    Growth doubles; a drained prefix is reclaimed by shifting the live
    window down whenever it would save a grow (amortized O(1) per row).
    ``take`` returns views valid until the next append/extend — callers
    copy out immediately (the staging buffers do).
    """

    __slots__ = ("ops", "payloads", "head", "tail")

    def __init__(self, op_fields: int, payload_len: int, capacity: int = 0) -> None:
        self.ops = np.empty((capacity, op_fields), np.int32)
        self.payloads = np.empty((capacity, payload_len), np.int32)
        self.head = 0
        self.tail = 0

    def __len__(self) -> int:
        return self.tail - self.head

    def __bool__(self) -> bool:
        return self.tail > self.head

    def __iter__(self):
        """Iterate pending op rows (diagnostics/tests; not a hot path)."""
        return iter(self.ops[self.head : self.tail])

    def _room(self, n: int) -> None:
        cap = self.ops.shape[0]
        if self.tail + n <= cap:
            return
        live = self.tail - self.head
        if live + n <= cap and self.head >= live + n:
            # Shifting beats growing: reclaim the drained prefix in place.
            self.ops[:live] = self.ops[self.head : self.tail]
            self.payloads[:live] = self.payloads[self.head : self.tail]
        else:
            new_cap = max(16, cap)
            while new_cap < live + n:
                new_cap *= 2
            ops = np.empty((new_cap, self.ops.shape[1]), np.int32)
            pay = np.empty((new_cap, self.payloads.shape[1]), np.int32)
            ops[:live] = self.ops[self.head : self.tail]
            pay[:live] = self.payloads[self.head : self.tail]
            self.ops, self.payloads = ops, pay
        self.head, self.tail = 0, live

    def append(self, op: np.ndarray, payload: np.ndarray) -> None:
        self._room(1)
        self.ops[self.tail] = op
        self.payloads[self.tail] = payload
        self.tail += 1

    def extend_rows(self, rows) -> None:
        """Per-message path: a small list of (op_row, payload_row) pairs."""
        n = len(rows)
        if not n:
            return
        self._room(n)
        t = self.tail
        for op, payload in rows:
            self.ops[t] = op
            self.payloads[t] = payload
            t += 1
        self.tail = t

    def extend_block(self, ops: np.ndarray, payloads: np.ndarray) -> None:
        """Batch path: land [M, F] / [M, L] row blocks as two slice copies."""
        m = ops.shape[0]
        if not m:
            return
        self._room(m)
        self.ops[self.tail : self.tail + m] = ops
        self.payloads[self.tail : self.tail + m] = payloads
        self.tail += m

    def take(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        """Dequeue ``n`` rows as views (copy out before the next append)."""
        h = self.head
        self.head = h + n
        return self.ops[h : h + n], self.payloads[h : h + n]

    def pending(self) -> tuple[np.ndarray, np.ndarray]:
        """Views of everything queued (watermark accounting, tests)."""
        return self.ops[self.head : self.tail], self.payloads[self.head : self.tail]

    def clear(self) -> None:
        self.head = self.tail = 0


class OverloadGate:
    """Per-doc ingest watermark hysteresis (credit-based flow control).

    Both batched engines expose their staged-op pressure through one of
    these: a doc whose RowQueue depth reaches ``high`` (sized in multiples
    of the megastep budget — what one K-slice dispatch can retire) enters
    the paused set, and leaves only when its depth drains to ``low`` — so
    a consumer pausing/resuming per-partition reads at the gate never
    flaps at the boundary.  ``update`` is O(busy + paused) per call and is
    meant to run once per pump, not per message.
    """

    __slots__ = ("high", "low", "paused", "events")

    def __init__(self, high: int, low: int) -> None:
        assert 0 < low < high, (low, high)
        self.high = high
        self.low = low
        self.paused: set[int] = set()
        self.events = 0  # pause transitions (the overload_events counter)

    def update(self, busy, depth_of) -> tuple[list[int], list[int]]:
        """-> (newly paused docs, newly resumed docs).  ``busy`` is the
        candidate set for NEW pauses (a doc over the high watermark is
        necessarily busy); ``depth_of(doc) -> int`` reads queue depth."""
        to_pause = [
            d for d in busy
            if d not in self.paused and depth_of(d) >= self.high
        ]
        for d in to_pause:
            self.paused.add(d)
        self.events += len(to_pause)
        to_resume = [d for d in self.paused if depth_of(d) <= self.low]
        for d in to_resume:
            self.paused.discard(d)
        return to_pause, to_resume

    def watermarks(self, megastep_budget: int) -> dict:
        """The flow-control contract numbers (ingest_watermarks surface
        shared by both engines)."""
        return {
            "megastep_budget": megastep_budget,
            "high": self.high,
            "low": self.low,
        }

    def emit_gauges(self, counters, megastep_budget: int,
                    queue_depth_max: int) -> None:
        """The engines' shared health() surface for graceful degradation:
        is any doc over its watermark, how many, how deep, and how many
        pause transitions the gate has taken over the run."""
        counters.gauge("megastep_budget", megastep_budget)
        counters.gauge("overload", int(bool(self.paused)))
        counters.gauge("overloaded_docs", len(self.paused))
        counters.gauge("overload_events", self.events)
        counters.gauge("queue_depth_max", queue_depth_max)


class _StageBuf:
    __slots__ = ("ops", "payloads", "dirty", "inflight")

    def __init__(self, shape_ops: tuple, shape_payloads: tuple) -> None:
        self.ops = np.zeros(shape_ops, np.int32)
        self.payloads = np.zeros(shape_payloads, np.int32)
        # [(slice k, row-index array)] written since the last reset.
        self.dirty: list[tuple[int, object]] = []
        # Device arrays last uploaded from this buffer (held so the memory
        # they were copied from is provably drained before reuse).
        self.inflight: tuple | None = None


class StagingRing:
    """A depth-N ring of reusable [K, D, B] op/payload staging buffers.

    Usage per megastep::

        ops, payloads = ring.acquire(k, rows)   # zeroed [k, rows, B, ...]
        ...fill slices, ring.mark(k, written_rows) per slice...
        dev = jnp.asarray(ops), jnp.asarray(payloads)
        ring.launched(*dev)                     # arms the reuse barrier

    ``acquire`` hands out views of the preallocated buffers; leading-axis
    views ([:k]) are contiguous, so the full-fleet upload path is
    zero-extra-copy.  Sub-row views ([:k, :rows]) are strided and copied by
    ``jnp.asarray`` (cohort steps — small by construction).
    """

    def __init__(
        self,
        k_max: int,
        rows: int,
        batch: int,
        op_fields: int,
        payload_len: int,
        depth: int = 2,
        mesh=None,
        doc_axis: str = "docs",
    ) -> None:
        # Mesh-aware upload: with a mesh, ``upload`` device_puts the
        # staging views with the SHARD layout (doc axis at dim -3), so each
        # chip receives exactly its placement-packed slice of the buffer
        # and the per-chip transfers overlap the previous dispatch
        # independently.  The engines pack doc rows by device slot, so the
        # buffer is contiguous per shard by construction.
        self._mesh = mesh
        self._doc_axis = doc_axis
        self.k_max = max(1, int(k_max))
        self._shape_ops = (self.k_max, rows, batch, op_fields)
        self._shape_payloads = (self.k_max, rows, batch, payload_len)
        self._bufs = [
            _StageBuf(self._shape_ops, self._shape_payloads)
            for _ in range(depth)
        ]
        self._i = 0
        self._cur: _StageBuf | None = None
        # Packs that overlapped an in-flight upload/dispatch (no blocking
        # wait was needed before reuse) — the double-buffer win counter.
        self.overlapped_packs = 0
        # Buffers surrendered to zero-copy device arrays (see module
        # docstring): each swap is one fresh allocation, the seed-parity
        # cost on backends without a real host->device transfer.
        self.aliased_swaps = 0

    def acquire(self, k: int, rows: int) -> tuple[np.ndarray, np.ndarray]:
        """A zeroed [k, rows, B, ...] staging view, safe to fill now."""
        slot = self._i
        buf = self._bufs[slot]
        self._i = (self._i + 1) % len(self._bufs)
        if buf.inflight is not None:
            import jax

            arrs = buf.inflight
            buf.inflight = None
            if self._aliased(buf, arrs):
                # The device arrays ALIAS this buffer's memory (zero-copy
                # backend): reuse would corrupt an in-flight dispatch's
                # input.  The arrays keep the old memory alive; the ring
                # slot gets fresh zeroed buffers.
                buf = self._bufs[slot] = _StageBuf(
                    self._shape_ops, self._shape_payloads
                )
                self.aliased_swaps += 1
            elif all(a.is_ready() for a in arrs):
                # The upload that read this buffer already drained: this
                # pack overlaps the previous megastep's device work.
                self.overlapped_packs += 1
            else:
                jax.block_until_ready(arrs)
        for kk, rr in buf.dirty:
            buf.ops[kk, rr] = 0
            buf.payloads[kk, rr] = 0
        buf.dirty.clear()
        self._cur = buf
        return buf.ops[:k, :rows], buf.payloads[:k, :rows]

    def mark(self, k: int, written_rows) -> None:
        """Record the rows slice ``k`` wrote (cleared on the next reuse)."""
        if len(written_rows):
            self._cur.dirty.append((k, np.asarray(written_rows)))

    def launched(self, *device_arrays) -> None:
        """Arm the reuse barrier with the arrays uploaded from the current
        buffer: the next acquire of this buffer waits for their transfers
        (not the consuming computation) before handing the memory back."""
        self._cur.inflight = device_arrays

    def upload(self, ops_view, payloads_view) -> tuple:
        """Upload the filled staging views and arm the reuse barrier in one
        call.  Under a mesh, [.., D, B, *] views (ndim >= 3) device_put
        with the shard layout — per-chip slices upload independently;
        lane-sized views ([B, *]) and mesh-less rings take the plain
        ``jnp.asarray`` path (zero-copy on CPU; the aliasing probe in
        ``acquire`` keeps reuse safe either way)."""
        import jax
        import jax.numpy as jnp

        from ..observability.flight_recorder import span

        nbytes = ops_view.nbytes + payloads_view.nbytes
        if self._mesh is not None and ops_view.ndim >= 3:
            from jax.sharding import NamedSharding, PartitionSpec

            spec = PartitionSpec(
                *([None] * (ops_view.ndim - 3)), self._doc_axis
            )
            sharding = NamedSharding(self._mesh, spec)
            # One span per shard-layout transfer: the device_put splits the
            # staging view per chip, so the span carries the shard count
            # and per-shard byte share for the trace.
            with span(
                "upload",
                shards=int(self._mesh.devices.size),
                bytes=nbytes,
                bytes_per_shard=nbytes // int(self._mesh.devices.size),
            ):
                dev = (
                    jax.device_put(ops_view, sharding),
                    jax.device_put(payloads_view, sharding),
                )
        else:
            with span("upload", shards=1, bytes=nbytes):
                dev = (jnp.asarray(ops_view), jnp.asarray(payloads_view))
        self.launched(*dev)
        return dev

    @staticmethod
    def _aliased(buf: _StageBuf, arrs) -> bool:
        """True when any uploaded device array points into the staging
        buffer's own memory (zero-copy backend; probe is best-effort —
        backends with real transfers either copy or lack the pointer)."""
        spans = [
            (buf.ops.ctypes.data, buf.ops.nbytes),
            (buf.payloads.ctypes.data, buf.payloads.nbytes),
        ]
        for a in arrs:
            # Shard by shard: a sharded array has no pointer of its own
            # (the probe raises), yet under a mesh each shard of a K = 1
            # upload is a contiguous slice of the staging buffer and
            # aliases it as a whole array would.
            for shard in getattr(a, "addressable_shards", ()):
                try:
                    p = int(shard.data.unsafe_buffer_pointer())
                except Exception:  # noqa: BLE001 — probe failure = assume no alias
                    continue
                if any(base <= p < base + n for base, n in spans):
                    return True
        return False


def upload_replicated(ops: np.ndarray, payloads: np.ndarray, mesh=None) -> tuple:
    """Replicated upload for SEGMENT-LANE op rings: a seg-sharded hot doc's
    [K, B] slices must reach every shard of the segment axis whole (each
    shard applies every op to its own segment block), so the device layout
    is replication — the other half of the 2-D docs x segs shard layout
    (``StagingRing.upload`` ships the doc-axis half).  Plain ``jnp.asarray``
    off-mesh."""
    import jax
    import jax.numpy as jnp

    from ..observability.flight_recorder import span

    nbytes = ops.nbytes + payloads.nbytes
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from ..ops.mergetree_kernel import SEG_AXIS

        rep = NamedSharding(mesh, PartitionSpec())
        # Label with the SEG width (not the full 2-D device count) so
        # upload(kind=seg) spans correlate with the dispatch spans'
        # seg_shards tag in the flight trace.
        seg_width = int(dict(mesh.shape).get(SEG_AXIS, mesh.devices.size))
        with span("upload", kind="seg", shards=seg_width, bytes=nbytes):
            return jax.device_put(ops, rep), jax.device_put(payloads, rep)
    with span("upload", kind="seg", shards=1, bytes=nbytes):
        return jnp.asarray(ops), jnp.asarray(payloads)
