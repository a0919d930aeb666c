"""DocBatchEngine: batched sequenced-op application across many documents.

The north-star configuration (BASELINE.json): thousands of SharedString
documents, each with its own totally-ordered op stream, applied in lockstep
device steps — ``vmap`` of the per-doc merge-tree kernel over a leading
document axis, sharded over a TPU mesh along ``docs``.

Host/device split (mirrors the reference's seam at
ContainerRuntime.processInboundMessages, containerRuntime.ts:3428 — where
contiguous ops are bunched before DDS apply; here the bunch becomes a
[D, B] tensor step):

- host: per-doc staging queues of sequenced messages, op encoding (stamp
  keys, positions, payload codepoints), quorum (clientId -> short id)
- device: ``step`` = vmap(scan(apply_op)) — applies up to B ops for each of
  D documents in one XLA program

This engine is the pure-replica path (no local pending ops): every op is a
remote sequenced apply, exactly the scenario of a server-side/materialized
replica fleet.  Client-side engines with pending/ack live in dds/.

Capacity overflow recovery (the kernel latches ERR_* bits instead of
trapping — mergetree_kernel.py): after every ``step`` the engine inspects
the fleet's error vector and recovers any flagged document, so no error bit
ever survives a run.  Recovery policy:

- ``"grow"`` (default): re-provision the document in an *overflow lane* — a
  single-doc DocState with the implicated capacity axes doubled — and
  replay its retained wire log from scratch (deterministic: the log is the
  total order).  Repeated overflows double again up to ``max_growths``,
  then fall through to the oracle.  Lanes keep applying on device (jit per
  geometry, cached), they just leave the lockstep batch.
- ``"oracle"``: replay the log through the host RefMergeTree and route all
  future ops there (the reference analog of a document leaving the fast
  path; SURVEY §7 capacity-management risk).

Fault isolation (this module's robustness contract):

- **Capacity errors** (ERR_SEG/TEXT/REM/OB_OVERFLOW) are recoverable:
  grow-and-replay into an overflow lane, or oracle routing (above).
- **Poison errors** — ERR_POS_RANGE with no capacity bit, a decode failure
  at ingest, or a divergence caught by the watchdog — mean the op stream
  (or the device state) is bad for THAT document only.  The doc is
  **quarantined**: evicted from the device batch into a host oracle lane
  rebuilt from its last checkpoint + retained tail, where every further op
  is validated before apply (malformed ops are dropped and counted, never
  applied).  The other documents in the batch never see a stall or a
  corrupt row.  A quarantined doc stays fully serviceable (reads + op
  application through the oracle) and can be re-admitted to the lockstep
  batch with ``readmit()`` once its replay is clean.
- **Checkpoints** bound recovery: with a ``checkpoint_store``
  (server/ordered_log.CheckpointStore) the engine periodically snapshots
  each doc's packed ``DocState`` as a summary record, truncates the
  retained wire log to ops after the checkpoint seq, and every recovery
  replay (grow lanes, quarantine, engine restart via
  ``restore_from_checkpoints``) starts from the checkpoint instead of op
  zero — replay work is bounded by ``checkpoint_every``, not history.
- A sampling **divergence watchdog** cross-checks device text against a
  host-oracle replay of checkpoint + tail every ``watchdog_every`` steps
  and quarantines on mismatch.  Health counters (quarantined_docs,
  checkpoint_age_seqs, recovery_replay_len, watchdog_mismatches, ...)
  surface through ``health()`` / utils.telemetry.HealthCounters.
"""

from __future__ import annotations

import functools
import json
import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..dds import kernel_backend as kb
from ..dds.mergetree_ref import RefMergeTree
from ..dds.shared_string import decode_obliterate_places
from ..observability import OpClock, RecompileWatchdog, instant, span
from ..ops import mergetree_kernel as mk
from ..parallel import mesh as pm
from . import placement
from ..protocol.messages import DeltaType, MessageType, SequencedMessage
from ..utils.stack_room import with_stack_room
from ..utils.telemetry import HealthCounters, Histogram, SampledTelemetryHelper
from .recovery import (
    RecoveryTracker,
    load_checkpoint_records,
    stale_due_docs,
    write_checkpoint_records,
)
from .staging import OverloadGate, RowQueue, StagingRing, upload_replicated


@dataclass
class _DocHost:
    """Host-side per-document bookkeeping."""

    # Columnar pending op rows (ops + payloads in one RowQueue): batch
    # ingest lands whole blocks, the drain consumes slice copies — no
    # per-op Python list traffic on either side.
    queue: RowQueue = None
    quorum: dict[str, int] = field(default_factory=dict)
    min_seq: int = 0
    # Property id -> kernel prop slot (interned per document).
    prop_slot: dict[int, int] = field(default_factory=dict)
    # Retained wire log (every OP message with seq > base_seq, in sequence
    # order): the replay source for recovery.  Bounded by checkpoints —
    # ops at or below ``base_seq`` live in ``base_summary`` instead.  Docs
    # fed through the native byte path retain raw lines instead (mode is
    # fixed per doc at first ingest).
    log: list[SequencedMessage] = field(default_factory=list)
    raw_log: list[bytes] = field(default_factory=list)
    native: object = None  # NativeIngestEncoder once the byte path is used
    mode: str | None = None  # "obj" | "native", fixed at first ingest
    # Checkpoint floor: the durable record covers ops up to ``base_seq``;
    # ``base_summary`` is its state (None = empty doc), the replay base.
    base_seq: int = 0
    base_summary: dict | None = None
    last_seq: int = 0  # highest OP seq ingested
    ops_since_ckpt: int = 0
    # Monotonic time the doc FIRST went dirty after its last durable
    # checkpoint (0.0 = clean): the bounded-staleness writer's seconds-
    # behind signal (recovery.BackgroundCheckpointWriter).
    dirty_since: float = 0.0
    # Set by restore_from_checkpoints: the doc consumes parsed messages
    # (seq dedupe needs per-message seqs the native encoder can't skip).
    restored: bool = False
    # Count applied ops as boot_replay_len only during the boot catch-up
    # phase — the first post-boot checkpoint ends it (live traffic after
    # that must not keep inflating a counter named "boot").
    boot_counting: bool = False


@dataclass
class _OverflowLane:
    """A document that outgrew the lockstep batch: own DocState, own queue."""

    state: mk.DocState
    geometry: dict[str, int]
    growths: int
    queue: RowQueue = None


@dataclass
class _SegmentLane:
    """A HOT document promoted to the segment-parallel serving path: its
    merge-tree segment arrays block-shard over the mesh's ``segs`` axis
    (per-segment work splits across shards; text/scalars/ob table
    replicate), served by the seg-parallel megastep
    (ops.mergetree_kernel.apply_megastep_seg) with the single-lane kernel
    as the byte-identity oracle.  Inserts land shard-local; the layout
    re-blocks at rebalance points (``rebalance_segments``)."""

    state: mk.DocState   # seg-sharded layout, device-resident
    n_shards: int
    s_local: int         # per-shard segment capacity
    queue: RowQueue = None
    rebalances: int = 0
    ops_since_rebalance: int = 0
    # Bumped at every state reassignment (dispatch/rebalance/compact): the
    # watchdog's host-side change mark — the slot-digest pre-filter cannot
    # vouch for a lane doc, and hot docs are the most expensive to replay.
    version: int = 0


def _i32(v) -> int:
    """Coerce one wire scalar for the batch walk with the per-message
    path's exact failure shape: ``np.array([...], np.int32)`` raises
    OverflowError on out-of-range ints, while the batch path's int64
    staging columns would silently WRAP on the int32 cast — so the range
    check must happen at collection time, loudly."""
    v = int(v)
    if not (-0x80000000 <= v <= 0x7FFFFFFF):
        raise OverflowError(f"op scalar {v} out of int32 range")
    return v


# Module-level jitted programs: every engine instance shares ONE compile
# cache keyed by input shapes (geometry x batch), instead of each instance
# recompiling identical programs through its own jit closures — engines are
# created per test / per restart, and the programs close over nothing
# instance-specific.

# One [D, B] slice: the row loop ends at the slice's deepest queue, the row
# vmapped over documents inside it (mk.apply_fleet_ops).
@functools.partial(jax.jit, donate_argnums=(0,))
def _fleet_step(state, ops, payloads):
    return mk.apply_fleet_ops(state, ops, payloads)


# Megastep dispatch: a [K, D, B] op ring applied as ONE donated program
# (lax.scan over slices, per-slice obliterate gate and row count taken
# on device — see mk.apply_megastep).  Amortizes the per-slice jit dispatch
# and host->device upload that starved the device at high fleet rates.
_fleet_megastep = functools.partial(jax.jit, donate_argnums=(0,))(
    mk.apply_megastep
)


def _fleet_compact_body(state, min_seqs):
    # Module-level body: shared by the single-device jit below and the
    # shard_map-wrapped mesh program (parallel.mesh.mesh_fleet_program
    # caches by function identity, so the body must be stable).
    state = jax.vmap(mk.set_min_seq)(state, min_seqs)
    flag = jnp.any(state.ob_key >= 0)
    return jax.vmap(mk.compact, in_axes=(0, None))(state, flag)


_fleet_compact = functools.partial(jax.jit, donate_argnums=(0,))(
    _fleet_compact_body
)


# What zamboni reads or writes of a document: the per-segment columns, the
# obliterate table, ``nseg`` and ``min_seq``.  The text pool, ``text_end``,
# ``uid_next`` and ``error`` are no operand of the cohort compaction.
_COMPACT_FIELDS = tuple(
    f for f in mk.DocState._fields
    if f not in ("text", "text_end", "uid_next", "error")
)


@functools.partial(jax.jit, donate_argnums=(0,))
def _compact_cohort(cols, idx, mins):
    """Zamboni over the rows ``idx`` of the fleet at the floors ``mins``:
    those rows' ``_COMPACT_FIELDS`` taken out, ``_fleet_compact_body`` on
    them, written back in place into the donated ``cols``.  Pad lanes repeat
    a real row (and its floor), so they write the same values twice.

    Rows move one at a time, a dynamic slice out and a dynamic-update-slice
    back in: a gather or a scatter relays the narrow obliterate columns to
    another layout first, a fleet-sized copy for a few rows' sake."""
    lanes = idx.shape[0]
    row = jax.lax.dynamic_index_in_dim
    put_row = jax.lax.dynamic_update_index_in_dim

    def take(lane, sub):
        return jax.tree.map(
            lambda s, x: put_row(s, row(x, idx[lane], 0, False), lane, 0),
            sub, cols,
        )

    sub = jax.lax.fori_loop(
        0, lanes, take,
        jax.tree.map(lambda x: jnp.zeros((lanes, *x.shape[1:]), x.dtype), cols),
    )
    none = jnp.zeros((lanes,), mk.I32)
    done = _fleet_compact_body(
        mk.DocState(
            text=jnp.zeros((lanes, 0), mk.I32), text_end=none, uid_next=none,
            error=none, **sub,
        ),
        mins,
    )
    done = {f: getattr(done, f) for f in _COMPACT_FIELDS}

    def put(lane, cols):
        return jax.tree.map(
            lambda x, s: put_row(x, row(s, lane, 0, False), idx[lane], 0),
            cols, done,
        )

    return jax.lax.fori_loop(0, lanes, put, cols)


@jax.jit
def _fleet_evictable(state):
    """Segments zamboni would still drop, summed over the batch."""
    return jnp.sum(jax.vmap(mk.evictable_count)(state))


_lane_apply_jit = jax.jit(mk.apply_ops)
_lane_compact_jit = jax.jit(lambda s, m: mk.compact(mk.set_min_seq(s, m)))


# The cohort path's three programs (``DocBatchEngine._cohort_trio``): the
# busy documents' rows gathered out of the fleet, stepped, and scattered
# back.  The text pool takes no part in the moving: it stays in the fleet's
# state, the gather and the scatter see a state without it (``text`` None),
# and the step writes the strips its inserts append straight into it, at
# the cohort's rows.  (A gather of 64 rows of the [6144, 65536] pool cost the
# TPU a copy of the whole pool: XLA slices a gather's operand to halves that
# fit its window first, 1.6 GB read and written for 16 MB of rows.)
@jax.jit
def _gather_cohort_jit(st, idx):
    """The rows ``idx`` of every leaf of ``st``, the fleet's state without
    its pool; the cohort's own ``text`` is empty ([lanes, 0])."""
    sub = jax.tree.map(lambda x: x[idx], st)
    return sub._replace(text=jnp.zeros((idx.shape[0], 0), mk.I32))


# One [lanes, B] slice (K = 1) or a [K, lanes, B] ring applied to a gathered
# cohort, the fleet's pool beside it and donated with it: ``_fleet_step``
# and ``_fleet_megastep`` for rows that lie anywhere in the pool.  Named so
# that the device trace reads them as step programs.
@functools.partial(jax.jit, donate_argnums=(0, 1))
def _cohort_fleet_step(pool, sub, idx, ops, payloads):
    return mk.apply_cohort_ops(pool, sub, idx, ops, payloads)


_cohort_megastep = functools.partial(jax.jit, donate_argnums=(0, 1))(
    mk.apply_cohort_megastep
)


@jax.jit
def _fleet_digest(state):
    """Cheap per-doc state digest computed ON DEVICE from the batched
    state: a position-weighted checksum of the text pool plus the segment
    layout scalars.  The divergence watchdog uses it as a pre-filter — a
    doc whose digest has not moved since its last verified check cannot
    have diverged SINCE then, so the expensive host-oracle replay is spent
    only on docs whose digest drifted."""
    U = jnp.uint32
    T = state.text.shape[-1]
    S = state.seg_len.shape[-1]
    wt = (jnp.arange(T, dtype=U) * U(2654435761) + U(0x9E3779B9))
    ws = (jnp.arange(S, dtype=U) * U(0x85EBCA6B) + U(0xC2B2AE35))
    dig = (state.text.astype(U) * wt).sum(axis=-1)
    dig += (state.seg_len.astype(U) * ws).sum(axis=-1)
    dig += (state.seg_start.astype(U) * (ws ^ U(0xA5A5A5A5))).sum(axis=-1)
    for rk in state.rem_keys:
        dig = dig * U(31) + (rk.astype(U) * ws).sum(axis=-1)
    dig = dig * U(31) + state.text_end.astype(U)
    dig = dig * U(31) + state.nseg.astype(U)
    return dig


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_cohort_jit(st, sub, idx, valid):
    """``sub``'s lanes written to the rows ``idx`` of the donated ``st``
    where ``valid`` (a pad lane goes nowhere).  Whole rows, the pool's
    included, for ``restore_scatter``; the cohort path hands in the state
    without its pool (``text`` None), and the cohort's empty ``text`` is
    left behind."""
    def put(x, s):
        safe = jnp.where(valid, idx, x.shape[0])
        return x.at[safe].set(s, mode="drop")

    if st.text is None:
        sub = sub._replace(text=None)
    return jax.tree.map(put, st, sub)


class DocBatchEngine:
    """A fleet of merge-tree replicas stepped as one batched device program."""

    def __init__(
        self,
        n_docs: int,
        max_segments: int = 512,
        remove_slots: int = 4,
        prop_slots: int = 4,
        text_capacity: int = 16384,
        max_insert_len: int = 64,
        ops_per_step: int = 16,
        ob_slots: int = 8,
        mesh=None,
        use_mesh: bool = True,
        recovery: str = "grow",
        max_growths: int = 4,
        checkpoint_store=None,
        checkpoint_every: int = 0,
        doc_keys: list[str] | None = None,
        watchdog_every: int = 0,
        watchdog_sample: int = 4,
        readmit_after_steps: int = 0,
        poison_budget: int = 0,
        megastep_k: int = 1,
        spare_slots: int = 0,
        telemetry=None,
        overload_high_watermark: int = 0,
        overload_low_watermark: int = 0,
        seg_shards: int = 0,
        seg_lane_segments: int = 0,
        seg_lane_text_capacity: int = 0,
        seg_rebalance_every: int = 0,
        max_seg_lanes: int = 4,
    ) -> None:
        assert recovery in ("grow", "oracle", "off")
        self.n_docs = n_docs
        self.max_insert_len = max_insert_len
        self.ops_per_step = ops_per_step
        # Megastep depth cap: up to K [D, B] op slices fuse into one
        # donated dispatch (adaptive per dispatch — see _select_k).  K=1
        # preserves the per-slice dispatch behavior exactly.
        self.megastep_k = max(1, megastep_k)
        # Ingest watermarks (credit-based flow control): the megastep
        # budget is what one fused dispatch retires per doc; a queue deeper
        # than ``overload_high`` watermarks the doc as overloaded (the
        # consumer pauses its partition) until it drains to
        # ``overload_low``.  Defaults: 8x / 1x the budget.
        budget = self.megastep_k * ops_per_step
        self.overload_gate = OverloadGate(
            high=overload_high_watermark or 8 * budget,
            low=overload_low_watermark or budget,
        )
        self.recovery = recovery
        self.max_growths = max_growths
        self.hosts = [
            _DocHost(queue=RowQueue(mk.OP_FIELDS, max_insert_len))
            for _ in range(n_docs)
        ]
        self.geometry = {
            "max_segments": max_segments,
            "remove_slots": remove_slots,
            "prop_slots": prop_slots,
            "text_capacity": text_capacity,
            "ob_slots": ob_slots,
        }
        # Recovery lanes (doc_idx -> lane / oracle replica).
        self.overflow: dict[int, _OverflowLane] = {}
        self.oracles: dict[int, RefMergeTree] = {}
        # Quarantine lane: docs whose op stream (or device state) proved
        # bad — served by a validated host oracle until readmission.
        self.quarantine: dict[int, RefMergeTree] = {}
        self.quarantine_reason: dict[int, str] = {}
        # Checkpoint / watchdog knobs (see module docstring).
        self.checkpoint_store = checkpoint_store
        self.checkpoint_every = checkpoint_every
        # Checkpoint-plane lock: the bounded-staleness background writer
        # (models/recovery.BackgroundCheckpointWriter) enters through
        # checkpoint_stale() on its own thread; step()/ingest*/
        # maybe_checkpoint/restore all take this, so a sweep only ever
        # sees the engine at an op boundary.  Re-entrant because step()
        # calls maybe_checkpoint under it.  Uncontended acquisition is
        # nanoseconds against ms-scale dispatches.
        self.ckpt_lock = threading.RLock()
        # Durable-write plane for checkpoint sweeps: saves happen outside
        # ckpt_lock (fsyncs must not stall serving), serialized here with
        # per-doc seq fencing so concurrent sweeps never write an older
        # record over a newer one.
        self._ckpt_io_lock = threading.Lock()
        self._ckpt_saved_seq: dict[int, int] = {}
        # Per-incident recovery clock (kill/restore -> first post-restore
        # op applied); gauges ride health(), the histogram rides
        # latency_histograms() into /metrics.
        self.recovery_tracker = RecoveryTracker()
        # Record-file mtimes last seen by a refresh trail: the standby's
        # poll skips unchanged records instead of re-reading and
        # re-parsing every checkpoint every poll_s.
        self._trail_mtime: dict[int, float] = {}
        self.doc_keys = list(doc_keys) if doc_keys is not None else [
            str(d) for d in range(n_docs)
        ]
        assert len(self.doc_keys) == n_docs
        # Warm the native ingest plane HERE, with no lock held: the byte
        # path's g++ rebuild (missing/stale .so) must never run lazily
        # under ckpt_lock — ingest_lines only probes the non-building
        # loaded() accessor (fftpu-check blocking-under-lock).
        from ..native import ingest_native as _ingest_native

        _ingest_native.warm()
        self.watchdog_every = watchdog_every
        self.watchdog_sample = watchdog_sample
        self._watchdog_cursor = 0
        self._steps_since_watchdog = 0
        # Watchdog pre-filter state: device digest at the last sweep, and
        # per doc the (digest, last_seq) pair recorded when it last PASSED
        # a check.  Skipping requires BOTH unchanged: the digest alone
        # cannot distinguish "no ops applied" from "ops silently dropped
        # by the kernel" — the exact divergence class the watchdog hunts.
        self._digests: np.ndarray | None = None
        self._verified_digest: dict[int, tuple[int, int]] = {}
        # Quarantine auto-readmission policy: with ``readmit_after_steps``
        # a quarantined doc is automatically re-tried after that many
        # engine steps, doubling per flap (exponential backoff).  A doc
        # that gets quarantined more than ``poison_budget`` times (0 = no
        # budget) is flapping — permanently oracle-routed instead of
        # bouncing in and out of the batch forever.
        self.readmit_after_steps = readmit_after_steps
        self.poison_budget = poison_budget
        self._step_count = 0
        self._flaps: dict[int, int] = {}
        self._readmit_due: dict[int, int] = {}
        # Current backoff interval per quarantined doc: doubles on every
        # flap AND on every failed readmission attempt (a doc whose state
        # outgrew the batch geometry must not re-pay the export/pack cost
        # at a fixed cadence forever).
        self._readmit_interval: dict[int, int] = {}
        # The row-slot pair is in every health line, 0 included: its
        # reader takes a window delta from the first line on.
        # So are the summary loop's four: documents acked, compaction
        # dispatches, documents compacted and lanes compacted (pow2 padding
        # included; a fleet-wide compaction adds ``capacity``).
        self.counters = HealthCounters(
            telemetry, row_slots_scanned=0, row_slots_dense=0,
            acks_seen=0, compact_dispatches=0, compacted_docs=0,
            compacted_lanes=0,
        )
        # Sampled hot-path timing through the reference's sampled-telemetry
        # shape (one event per N steps; flush_all drains the tail at
        # shutdown / status-snapshot time via ``flush_telemetry``).
        self.sampled = (
            SampledTelemetryHelper(telemetry, "engine_step", sample_every=64)
            if telemetry is not None
            else None
        )
        if use_mesh:
            if mesh is not None:
                self.mesh = mesh
            elif seg_shards > 1:
                # The 2-D docs x segs serving mesh: cold docs shard over
                # BOTH axes flattened (every device), hot docs carve the
                # segs axis via segment lanes.
                self.mesh = pm.docs_segs_mesh(seg_shards=seg_shards)
            else:
                self.mesh = pm.doc_mesh()
            n_shards = self.mesh.devices.size
            self.seg_shards = int(dict(self.mesh.shape).get(pm.SEG_AXIS, 1))
        else:
            self.mesh = None
            n_shards = 1
            self.seg_shards = 1
        # Segment-lane knobs (hot-doc opt-in; see _SegmentLane).
        self.seg_lanes: dict[int, _SegmentLane] = {}
        self.seg_lane_segments = seg_lane_segments
        self.seg_lane_text_capacity = seg_lane_text_capacity
        self.seg_rebalance_every = seg_rebalance_every
        self.max_seg_lanes = max_seg_lanes
        self.n_shards = n_shards
        # The op's own clock (observability/op_clock.py): sequencer stamp ->
        # received -> applied on the device, one sample a feed weighted by
        # its rows (a message on the per-message path).  Pending feeds
        # resolve at the step() sync boundary (recover()'s error readback
        # proves the dispatches that drained them retired).
        self.op_clock = OpClock(n_shards, self.shard_of)
        # Device-row placement rides the shared plane (models/placement.py):
        # doc -> slot indirection with per-shard spare-slot free pools, the
        # same plane the tree fleet rides.  ``_slot`` aliases the plane's
        # live array for hot-path staging packs.
        self.placement_plane = placement.PlacementPlane(
            n_docs, n_shards, spare_slots
        )
        self.capacity = self.placement_plane.capacity
        self.docs_per_shard = self.placement_plane.docs_per_shard
        self._slot = self.placement_plane.slots
        # Per-shard applied-op counters (host-side, no device readback):
        # accumulated at drain time, the hot-shard detection signal.
        self._shard_ops = np.zeros((n_shards,), np.int64)
        # The other side of a mesh's skew: per shard, the deepest take
        # among its documents, summed over the fleet-wide slices packed.
        # It is where that shard's own row loop ends (``mk.row_count`` of
        # its rows under ``shard_map``); never reset.
        self._shard_row_slots = np.zeros((n_shards,), np.int64)

        proto = mk.init_state(
            max_segments, remove_slots, prop_slots, text_capacity, ob_slots
        )
        self._proto = proto  # pristine row: retires vacated migration slots
        self.state = pm.init_fleet_state(proto, self.capacity, self.mesh)

        # Module-level jitted programs (shared compile cache across engine
        # instances; one executable per geometry/batch shape).
        self._step = _fleet_step
        self._megastep = _fleet_megastep
        self._compact = _fleet_compact
        self._seg_megastep = None
        self._seg_compact = None
        if self.mesh is not None:
            # shard_map-wrapped fleet programs: one donated dispatch steps
            # every shard with zero hot-path collectives; each shard's
            # obliterate gate is evaluated from its OWN docs, so one hot
            # obliterate shard no longer de-specializes the whole fleet.
            # Cached per (mesh, specs) — instances serving the same mesh
            # share compiles (parallel.mesh.mesh_fleet_program).  On a
            # docs x segs mesh the doc dim shards over BOTH axes flattened.
            da = pm.fleet_doc_axes(self.mesh)
            specs = pm.fleet_state_specs(self.state, da)
            self._state_specs = specs
            self._megastep = pm.mesh_fleet_program(
                mk.apply_megastep, self.mesh, specs,
                arg_specs=(pm.P(None, da), pm.P(None, da)),
            )
            self._compact = pm.mesh_fleet_program(
                _fleet_compact_body, self.mesh, specs,
                arg_specs=(pm.P(da),),
            )
            if self.seg_shards > 1:
                # Segment-lane programs: one donated dispatch applies a
                # [K, B] op ring to one seg-sharded hot doc, per-segment
                # work split over the segs axis (two collective hops
                # inside — mk.apply_megastep_seg).
                seg_specs = pm.seg_state_specs(self._proto)
                self._seg_megastep = pm.mesh_seg_program(
                    mk.apply_megastep_seg, self.mesh, seg_specs
                )
                self._seg_compact = pm.mesh_seg_program(
                    mk.compact_seg, self.mesh, seg_specs,
                    arg_specs=(pm.P(),),
                )
        self._lane_apply = _lane_apply_jit
        self._lane_compact = _lane_compact_jit
        # Recompile watchdog: executable-cache growth on any fleet program
        # after warmup = a megastep trace de-specialized mid-serve (counted
        # in health() as ``recompiles``; each emits an instant trace
        # event).  Polled once per step() — one int read per program.
        self.recompile_watchdog = RecompileWatchdog()
        for prog_name, prog in (
            ("fleet_step", self._step),
            ("fleet_megastep", self._megastep),
            ("fleet_compact", self._compact),
            ("compact_cohort", _compact_cohort),
            ("cohort_step", _cohort_fleet_step),
            ("cohort_megastep", _cohort_megastep),
            ("lane_apply", self._lane_apply),
        ):
            self.recompile_watchdog.register(prog_name, prog)
        if self._seg_megastep is not None:
            self.recompile_watchdog.register("seg_megastep", self._seg_megastep)
            self.recompile_watchdog.register("seg_compact", self._seg_compact)
        # Incremental busy set: doc indices whose host queue is nonempty,
        # maintained by ingest/drain/quarantine — step() never rescans the
        # whole host array (O(busy) per loop iteration, not O(capacity)).
        self._busy: set[int] = set()
        # Preallocated, double-buffered [K, D, B] staging (lazy: sized from
        # the megastep depth and fleet capacity on first use).
        self._stage: StagingRing | None = None
        # ---- Zipf straggler bucketing (SURVEY §7: doc-packing by op count)
        # Under skewed per-doc op counts one hot doc would force extra
        # FULL-fleet steps (every step scans B ops across all D lanes).
        # When few docs remain busy, gather just those docs' state rows
        # into a power-of-two cohort, step the small sub-fleet, and
        # masked-scatter the rows back — pad lanes route out of bounds
        # (mode="drop"), so duplicate writes never occur.  The text pool
        # does not travel: the cohort's step writes into the fleet's, at
        # the cohort's rows (_cohort_trio).  The jit caches one executable
        # per cohort size (log2(D) variants).
        # Single-chip optimization: under a mesh the doc axis is sharded
        # evenly and arbitrary-index gathers would cross shards.
        self.bucketing = self.mesh is None
        # No first dispatch while serving where a built program can do the
        # work: a busy set whose cohort shape (lanes, K) this engine has not
        # dispatched yet, and whose every queue fits one slice, is stepped by
        # the fleet-wide K = 1 program instead, once that one is built
        # (_step_fleet).  The row loop ends at the deepest queue, so a
        # shallow fleet-wide slice costs a fraction of a second; a shape's
        # first dispatch traces, lowers and loads for seconds and holds up
        # every document's ops meanwhile.  So the tail of a wide burst and a
        # busy set that hovers around the threshold reach no cohort program
        # they would have to build; warmup(), a ladder of bursts before the
        # fleet-wide program exists, and a backlog deeper than one slice
        # (there the dense fleet-wide slices would cost more than the build)
        # are what builds a cohort shape.
        self._built: set[tuple[int, int]] = set()  # cohort (lanes, K)
        self._full_built = False  # the fleet-wide K = 1 program
        self.full_steps = 0     # fleet-wide steps taken
        self.cohort_steps = 0   # bucketed steps taken
        self.cohort_lanes = 0   # sum of cohort sizes (work proxy)
        self._gather_cohort = _gather_cohort_jit
        self._scatter_cohort = _scatter_cohort_jit
        # Documents whose summary was acked (``compact(docs)``) and that
        # ``step`` has not compacted yet, and the lane counts of the cohort
        # compaction this engine has dispatched: like a step's shapes, a new
        # one is not built while a built one can do the work.
        self.compact_due: set[int] = set()
        self._compact_built: set[int] = set()

    # ------------------------------------------------------------------ ingest
    def ingest(self, doc_idx: int, msg: SequencedMessage) -> None:
        """Stage one sequenced message for a document (host-side decode).

        This is the engine's inbound seam: the equivalent of
        DeltaManager -> ContainerRuntime.process for one container, except
        application is deferred to the next batched device step.
        Serialized on ``ckpt_lock`` against the background checkpoint
        writer (a sweep never sees a half-staged message).
        """
        with self.ckpt_lock:
            return self._ingest_one(doc_idx, msg)

    def _ingest_one(self, doc_idx: int, msg: SequencedMessage) -> None:
        h = self.hosts[doc_idx]
        assert h.mode != "native" or self._in_lane(doc_idx), (
            f"doc {doc_idx} already fed through the native byte path; "
            "pick one ingest path per document"
        )
        if h.mode is None:
            h.mode = "obj"
        if msg.type == MessageType.JOIN:
            h.quorum[msg.contents["clientId"]] = msg.contents["short"]
            h.min_seq = max(h.min_seq, msg.min_seq)
            return
        if msg.type != MessageType.OP:
            h.min_seq = max(h.min_seq, msg.min_seq)
            return
        h.min_seq = max(h.min_seq, msg.min_seq)
        if h.base_seq and msg.seq <= h.base_seq:
            # Already folded into the durable checkpoint (a restarted
            # consumer replaying its topic from an older offset): skip —
            # restart must be idempotent, not double-apply.
            self.counters.bump("checkpointed_ops_skipped")
            return
        h.last_seq = max(h.last_seq, msg.seq)
        h.ops_since_ckpt += 1
        if not h.dirty_since:
            h.dirty_since = time.monotonic()
        self.op_clock.feed(msg.timestamp, self.op_clock.now(), 1, doc_idx)
        if h.boot_counting:
            # Post-summary tail actually replayed on a boot-from-checkpoint/
            # summary consumer (the skipped prefix counts separately above;
            # the first post-boot checkpoint ends the boot phase).
            self.counters.bump("boot_replay_len")
        if doc_idx in self.quarantine:
            # Quarantined docs stay serviceable: validated host-oracle
            # apply; malformed ops are dropped and counted, never applied.
            self._oracle_apply_validated(self.quarantine[doc_idx], h, msg)
            # Keep the tail log so checkpoints and readmission replay stay
            # bounded and auditable.
            if self.recovery != "off":
                h.log.append(msg)
            return
        if doc_idx in self.oracles:
            # Oracle-routed docs apply immediately and can never need
            # another replay — no point retaining their log further.  Same
            # validation gate as quarantine: a malformed op for this doc
            # drops (counted) instead of crashing the whole consumer.
            self._oracle_apply_validated(self.oracles[doc_idx], h, msg)
            return

        if self.recovery != "off":
            # Replay source for recovery, bounded by checkpoints: ops at or
            # below base_seq live in base_summary, this list is the tail.
            h.log.append(msg)
        try:
            rows = self._encode(h, msg)
        except NotImplementedError:
            # Legal-but-unsupported wire form: loud feature gap.  The op
            # was never applied — keep it out of the replay log so a
            # caller that survives the raise doesn't poison recovery.
            if h.log and h.log[-1] is msg:
                h.log.pop()
            h.ops_since_ckpt -= 1
            raise
        except (ValueError, KeyError, TypeError) as e:
            if self.recovery == "off":
                raise  # no retained log to rebuild from: surface it
            # Decode failure: the wire op is malformed for THIS doc only.
            # Quarantine it (checkpoint + validated tail replay, which
            # drops this op and counts it) so the rest of the batch keeps
            # stepping.
            self._quarantine_doc(doc_idx, f"decode: {e}")
            return
        if doc_idx in self.overflow:
            self.overflow[doc_idx].queue.extend_rows(rows)
            return
        if doc_idx in self.seg_lanes:
            self.seg_lanes[doc_idx].queue.extend_rows(rows)
            return
        h.queue.extend_rows(rows)
        if h.queue:
            self._busy.add(doc_idx)

    # -------------------------------------------------------- batched ingest
    def ingest_batch(self, doc_idxs, msgs) -> int:
        """Flight-recorded entry over ``_ingest_batch`` (the ``ingest``
        phase of a trace; a free no-op while no recorder is installed).
        Holds ``ckpt_lock`` so the background checkpoint writer only ever
        sweeps at a whole-batch boundary."""
        with self.ckpt_lock, span("ingest", msgs=len(doc_idxs)):
            return self._ingest_batch(doc_idxs, msgs)

    def _ingest_batch(self, doc_idxs, msgs) -> int:
        """Columnar ingest fast path: decode a whole wire batch into
        [N, OP_FIELDS] op rows + payload rows with vectorized numpy and
        land them in the per-doc RowQueues as block copies — Python is
        touched per *message* for routing/bookkeeping only; all op-row
        materialization is batched (mk.encode_insert_batch /
        encode_obliterate_batch / column stacks).

        Semantics are byte-identical to calling ``ingest`` per message:

        - JOINs, non-OP messages, quarantined/oracle/overflow docs, and
          native-mode docs take the per-message path row by row
          (``ingest_fallback_msgs`` counts them).
        - A decode error quarantines ONLY the offending doc, exactly as
          the per-message path does: its earlier batch rows are dropped
          from the scatter (they already rode the retained log into the
          quarantine replay) and its later messages route through the
          validated oracle.
        - Recovery logging, checkpoint-floor dedupe, and boot counting
          run per message in the routing walk, unchanged.

        Returns the op-row count landed through the batch path.
        """
        L = self.max_insert_len
        counters = self.counters
        total = 0
        doc_of: list[int] = []  # row id -> doc
        # Per-kind columnar collectors (row ids reserved in walk order so
        # the per-doc ordering of mixed-kind streams is preserved).
        i_start: list[int] = []
        i_nch: list[int] = []
        i_pos: list[int] = []
        i_txt: list[str] = []
        i_key: list[int] = []
        i_cli: list[int] = []
        i_ref: list[int] = []
        s_id: list[int] = []  # single-row ops: global row ids
        s_row: list[tuple[int, int, int, int, int, int, int, int]] = []
        o_id: list[int] = []  # obliterates (vectorized encoder columns)
        o_col: tuple[list[int], ...] = ([], [], [], [], [], [], [])
        pending_raise: BaseException | None = None
        for d, msg in zip(doc_idxs, msgs):
            h = self.hosts[d]
            if (
                msg.type != MessageType.OP
                or d in self.quarantine
                or d in self.oracles
                or d in self.overflow
                or d in self.seg_lanes
                or h.mode == "native"
            ):
                counters.bump("ingest_fallback_msgs")
                self.ingest(d, msg)
                continue
            if h.mode is None:
                h.mode = "obj"
            h.min_seq = max(h.min_seq, msg.min_seq)
            if h.base_seq and msg.seq <= h.base_seq:
                counters.bump("checkpointed_ops_skipped")
                continue
            h.last_seq = max(h.last_seq, msg.seq)
            h.ops_since_ckpt += 1
            if not h.dirty_since:
                h.dirty_since = time.monotonic()
            self.op_clock.feed(msg.timestamp, self.op_clock.now(), 1, d)
            if h.boot_counting:
                counters.bump("boot_replay_len")
            if self.recovery != "off":
                h.log.append(msg)
            try:
                c = msg.contents
                kind = c["type"]
                client = h.quorum[msg.client_id]
                if kind == DeltaType.INSERT:
                    seg = c["seg"]
                    if not isinstance(seg, str):
                        # Legal-but-unsupported wire form: loud feature
                        # gap, never applied — same unwinding as _encode.
                        if h.log and h.log[-1] is msg:
                            h.log.pop()
                        h.ops_since_ckpt -= 1
                        pending_raise = NotImplementedError(
                            "engine supports plain-text insert segs only; "
                            f"got {type(seg).__name__}"
                        )
                        break
                    # _i32 coercions throughout this walk are load-bearing
                    # AND must complete before ANY collector append: a
                    # malformed scalar (string value, dict pos) raises
                    # INSIDE this try — per-doc quarantine — an
                    # out-of-int32 scalar raises OverflowError (per-message
                    # parity: loud, never a silent int64->int32 wrap), and
                    # a partial append would misalign the columnar
                    # collectors and crash the whole-batch numpy scatter.
                    pos = _i32(c["pos1"])
                    nch = -(-len(seg) // L)
                    i_start.append(total)
                    i_nch.append(nch)
                    i_pos.append(pos)
                    i_txt.append(seg)
                    i_key.append(_i32(msg.seq))
                    i_cli.append(client)
                    i_ref.append(_i32(msg.ref_seq))
                    doc_of.extend([d] * nch)
                    total += nch
                elif kind == DeltaType.REMOVE:
                    row = (
                        mk.OpKind.REMOVE, _i32(msg.seq), client,
                        _i32(msg.ref_seq), _i32(c["pos1"]), _i32(c["pos2"]),
                        0, 0,
                    )
                    s_id.append(total)
                    s_row.append(row)
                    doc_of.append(d)
                    total += 1
                elif kind == DeltaType.ANNOTATE:
                    seq32, ref32 = _i32(msg.seq), _i32(msg.ref_seq)
                    p1, p2 = _i32(c["pos1"]), _i32(c["pos2"])
                    # All props coerce before any append, mirroring the
                    # per-message path where a mid-props failure lands
                    # NOTHING for the message.
                    prop_rows = [
                        (self._prop_slot_for(h, int(prop)), _i32(value))
                        for prop, value in c["props"].items()
                    ]
                    for slot, value in prop_rows:
                        s_id.append(total)
                        s_row.append((
                            mk.OpKind.ANNOTATE, seq32, client,
                            ref32, p1, p2, slot, value,
                        ))
                        doc_of.append(d)
                        total += 1
                elif kind in (DeltaType.OBLITERATE, DeltaType.OBLITERATE_SIDED):
                    places = decode_obliterate_places(c)
                    vals = tuple(
                        _i32(v)
                        for v in (*places, msg.seq, client, msg.ref_seq)
                    )
                    o_id.append(total)
                    for col, v in zip(o_col, vals):
                        col.append(v)
                    doc_of.append(d)
                    total += 1
                else:
                    raise ValueError(f"unsupported op type {kind}")
            except OverflowError as e:
                # Per-message parity: OverflowError is NOT a quarantine
                # class there (np.array raises it out of ingest with the
                # message's bookkeeping committed) — land the earlier
                # messages' rows, then surface it.
                pending_raise = e
                break
            except (ValueError, KeyError, TypeError) as e:
                if self.recovery == "off":
                    pending_raise = e
                    break
                # Decode failure: poison for THIS doc only — quarantine it
                # (its staged + batch rows ride the retained log into the
                # validated replay) and keep batching the rest.
                self._quarantine_doc(d, f"decode: {e}")
        staged = self._scatter_batch_rows(
            total, doc_of, i_start, i_nch, i_pos, i_txt, i_key, i_cli,
            i_ref, s_id, s_row, o_id, o_col,
        )
        if pending_raise is not None:
            raise pending_raise
        return staged

    def _scatter_batch_rows(
        self, total, doc_of, i_start, i_nch, i_pos, i_txt, i_key, i_cli,
        i_ref, s_id, s_row, o_id, o_col,
    ) -> int:
        """Materialize the collected batch rows (vectorized) and land them
        per doc as block copies; rows for docs that left the device path
        mid-batch are dropped (their ops already rode the log into the
        lane replay)."""
        if not total:
            return 0
        ops_all = np.zeros((total, mk.OP_FIELDS), np.int32)
        pay_all = np.zeros((total, self.max_insert_len), np.int32)
        if i_txt:
            ops_i, pay_i, _owner = mk.encode_insert_batch(
                np.asarray(i_pos, np.int64), i_txt,
                np.asarray(i_key, np.int64), np.asarray(i_cli, np.int64),
                np.asarray(i_ref, np.int64), self.max_insert_len,
            )
            nch = np.asarray(i_nch, np.int64)
            m = int(nch.sum())
            row0 = np.concatenate(([0], np.cumsum(nch)[:-1]))
            ids = np.repeat(np.asarray(i_start, np.int64), nch) + (
                np.arange(m) - np.repeat(row0, nch)
            )
            ops_all[ids] = ops_i
            pay_all[ids] = pay_i
        if s_row:
            ops_all[np.asarray(s_id, np.int64)] = np.asarray(s_row, np.int32)
        if o_id:
            ops_all[np.asarray(o_id, np.int64)] = mk.encode_obliterate_batch(
                *(np.asarray(col, np.int64) for col in o_col)
            )
        doc_arr = np.asarray(doc_of, np.int64)
        live = np.ones((total,), bool)
        for d in set(doc_of):
            if (
                d in self.quarantine or d in self.oracles
                or d in self.overflow or d in self.seg_lanes
            ):
                live[doc_arr == d] = False
        # Stable doc-sort: one extend_block per doc, original order kept.
        order = np.argsort(doc_arr, kind="stable")
        order = order[live[order]]
        staged = int(order.size)
        if not staged:
            return 0
        sorted_docs = doc_arr[order]
        cuts = np.flatnonzero(np.diff(sorted_docs)) + 1
        for seg in np.split(order, cuts):
            d = int(doc_arr[seg[0]])
            self.hosts[d].queue.extend_block(ops_all[seg], pay_all[seg])
            self._busy.add(d)
        self.counters.bump("ingest_batch_rows", staged)
        return staged

    def _make_lane(
        self, state: mk.DocState, geometry: dict[str, int], growths: int
    ) -> _OverflowLane:
        return _OverflowLane(
            state=state, geometry=geometry, growths=growths,
            queue=RowQueue(mk.OP_FIELDS, self.max_insert_len),
        )

    def _in_lane(self, doc_idx: int) -> bool:
        """True when the doc has left the lockstep batch (or was restored
        from a checkpoint): its ingest consumes parsed messages.  A live
        native-path doc that merely CHECKPOINTED is not in a lane — it
        stays on the C++ fast path."""
        return self._off_batch(doc_idx) or self.hosts[doc_idx].restored

    def ingest_lines(self, doc_idx: int, data: bytes) -> int:
        """Stage newline-separated wire JSON through the NATIVE encoder
        (native/ingest.cpp): the whole decode+encode runs in C++, so this is
        the production feed path for a server-side fleet consuming the
        broadcast stream.  Returns the number of op rows staged (op count
        applied, for oracle-routed docs).  Falls back to the Python path
        message by message when the native library is unavailable.  A
        healthy document stays on whichever path fed it first (the two
        paths intern property slots independently); recovery-lane routing
        normalizes a native doc onto the object path."""
        with self.ckpt_lock:
            return self._ingest_lines(doc_idx, data)

    def _ingest_lines(self, doc_idx: int, data: bytes) -> int:
        # loaded(), not available(): this runs under ckpt_lock, and the
        # building probe spawns g++ for a stale .so — warm() at __init__
        # already did any building with the lock free.
        from ..native.ingest_native import NativeIngestEncoder, loaded

        t_received = self.op_clock.received()
        h = self.hosts[doc_idx]
        if self._in_lane(doc_idx) or not loaded():
            # Lanes, checkpoint-restored docs, and the no-native fallback
            # consume parsed messages — decoded as one batch and fed
            # through the columnar fast path (ingest_batch routes lane
            # docs message by message itself, so semantics match).
            self._normalize_native(h)
            self.counters.bump("ingest_python_chunks")
            lane = self.overflow.get(doc_idx) or self.seg_lanes.get(doc_idx)
            before = len(lane.queue) if lane else len(h.queue)
            msgs = [
                SequencedMessage.from_json(line.decode())
                for line in data.split(b"\n")
                if line.strip()
            ]
            n_msgs = sum(m.type == MessageType.OP for m in msgs)
            self.ingest_batch([doc_idx] * len(msgs), msgs)
            if doc_idx in self.oracles or doc_idx in self.quarantine:
                return n_msgs
            lane = self.overflow.get(doc_idx) or self.seg_lanes.get(doc_idx)
            return (len(lane.queue) if lane else len(h.queue)) - before
        assert h.mode != "obj", (
            f"doc {doc_idx} already fed through the object path; "
            "pick one ingest path per document"
        )
        if h.native is None:
            h.native = NativeIngestEncoder(
                self.max_insert_len, self.geometry["prop_slots"]
            )
            h.mode = "native"
        self.counters.bump("ingest_native_chunks")
        with span("ingest", doc=doc_idx, bytes=len(data)):
            ops, payloads = h.native.encode(data)
            if self.recovery != "off":
                h.raw_log.append(data)
            # Native row output lands as one block copy per chunk — the doc
            # lane "gather" is a slice assignment, never a per-row Python
            # loop.
            h.queue.extend_block(ops, payloads)
        if len(ops):
            # The feed is the sample: its oldest line's sequencer stamp,
            # weighted by the rows it staged.
            self.op_clock.feed_lines(data, t_received, len(ops), doc_idx)
        if h.queue:
            self._busy.add(doc_idx)
        h.min_seq = max(h.min_seq, h.native.min_seq)
        h.ops_since_ckpt += len(ops)
        if len(ops) and not h.dirty_since:
            h.dirty_since = time.monotonic()
        if self.checkpoint_store is not None:
            # Checkpoints need the seq floor; one JSON parse of the chunk's
            # last line covers the whole chunk (lines are seq-ordered).
            tail_line = data.rstrip(b"\n").rsplit(b"\n", 1)[-1]
            if tail_line.strip():
                try:
                    h.last_seq = max(
                        h.last_seq,
                        int(json.loads(tail_line)["sequenceNumber"]),
                    )
                except (ValueError, KeyError):
                    pass
        return len(ops)

    def _normalize_native(self, h: _DocHost) -> None:
        """Move a native-path doc onto the object path: parse the retained
        raw lines into quorum + message log (PREPENDED — they precede
        anything the object path appended later) so recovery replay, oracle
        takeover, and further ingest share one consistent stream and one
        prop-slot interning order."""
        if not h.raw_log:
            if h.mode == "native":
                h.mode = "obj"
                h.native = None
            return
        prefix: list[SequencedMessage] = []
        for chunk in h.raw_log:
            for line in chunk.split(b"\n"):
                if line.strip():
                    m = SequencedMessage.from_json(line.decode())
                    if m.type == MessageType.JOIN:
                        h.quorum[m.contents["clientId"]] = m.contents["short"]
                    elif m.type == MessageType.OP and m.seq > h.base_seq:
                        prefix.append(m)
                        h.last_seq = max(h.last_seq, m.seq)
        h.raw_log.clear()
        h.log[:0] = prefix
        h.mode = "obj"
        h.native = None

    def _encode(
        self, h: _DocHost, msg: SequencedMessage
    ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Wire message -> kernel op rows (+payloads)."""
        c = msg.contents
        kind = c["type"]
        client = h.quorum[msg.client_id]
        empty = np.zeros((self.max_insert_len,), np.int32)
        if kind == DeltaType.INSERT:
            if not isinstance(c["seg"], str):
                # Marker/annotated dict specs and per-props-run spec LISTS
                # are legal channel-layer wire forms this engine cannot
                # encode yet.  They must fail LOUD (a feature gap), never
                # quarantine-drop as poison — silently dropping a legal op
                # would split-brain the fleet tier against every channel
                # replica that applied it.
                raise NotImplementedError(
                    "engine supports plain-text insert segs only; got "
                    f"{type(c['seg']).__name__}"
                )
            return mk.encode_insert(
                c["pos1"], c["seg"], msg.seq, client, msg.ref_seq,
                self.max_insert_len,
            )
        if kind == DeltaType.REMOVE:
            op = np.array(
                [mk.OpKind.REMOVE, msg.seq, client, msg.ref_seq,
                 c["pos1"], c["pos2"], 0, 0],
                np.int32,
            )
            return [(op, empty)]
        if kind == DeltaType.ANNOTATE:
            out = []
            for prop, value in c["props"].items():
                slot = self._prop_slot_for(h, int(prop))
                out.append(
                    (
                        np.array(
                            [mk.OpKind.ANNOTATE, msg.seq, client, msg.ref_seq,
                             c["pos1"], c["pos2"], slot, value],
                            np.int32,
                        ),
                        empty,
                    )
                )
            return out
        if kind in (DeltaType.OBLITERATE, DeltaType.OBLITERATE_SIDED):
            p1, s1, p2, s2 = decode_obliterate_places(c)
            return [
                (mk.encode_obliterate(p1, s1, p2, s2, msg.seq, client, msg.ref_seq),
                 empty)
            ]
        raise ValueError(f"unsupported op type {kind}")

    @staticmethod
    def _oracle_apply(tree: RefMergeTree, h: _DocHost, msg: SequencedMessage) -> None:
        """Apply one wire OP message to a host oracle replica (the pure
        remote path of SharedString._apply_remote)."""
        c = msg.contents
        kind = c["type"]
        client = h.quorum[msg.client_id]
        if kind == DeltaType.INSERT:
            tree.apply_insert(c["pos1"], c["seg"], msg.seq, client, msg.ref_seq)
        elif kind == DeltaType.REMOVE:
            tree.apply_remove(c["pos1"], c["pos2"], msg.seq, client, msg.ref_seq)
        elif kind == DeltaType.ANNOTATE:
            for prop, value in c["props"].items():
                tree.apply_annotate(
                    c["pos1"], c["pos2"], int(prop), value,
                    msg.seq, client, msg.ref_seq,
                )
        elif kind in (DeltaType.OBLITERATE, DeltaType.OBLITERATE_SIDED):
            p1, s1, p2, s2 = decode_obliterate_places(c)
            tree.apply_obliterate(p1, s1, p2, s2, msg.seq, client, msg.ref_seq)
        else:
            raise ValueError(f"unsupported op type {kind}")

    def _prop_slot_for(self, h: _DocHost, prop: int) -> int:
        """Intern a property id to a kernel prop slot (range-checked)."""
        if prop not in h.prop_slot:
            slot = len(h.prop_slot)
            if slot >= self.geometry["prop_slots"]:
                raise ValueError(
                    f"document exhausted its {self.geometry['prop_slots']} prop "
                    f"slots; raise prop_slots to accommodate prop id {prop}"
                )
            h.prop_slot[prop] = slot
        return h.prop_slot[prop]

    # ------------------------------------------------------------- op latency
    def latency_histograms(self) -> dict[str, Histogram]:
        """Mergeable latency histograms for the metrics plane: the op
        clock's (``op_latency`` = sequencer stamp -> applied on the device,
        its three stages, one per mesh shard) and the per-incident
        recovery-time histogram (kill/restore -> first post-restore op
        applied)."""
        return {
            **self.op_clock.histograms(),
            "recovery_time": self.recovery_tracker.histogram,
        }

    def flush_telemetry(self) -> None:
        """Drain residual sampled-telemetry buckets (status snapshot /
        shutdown hook): tail samples below ``sample_every`` must reach the
        sink before the process goes away."""
        if self.sampled is not None:
            self.sampled.flush_all()

    # ------------------------------------------------------------------- step
    def pending_ops(self) -> int:
        return (
            sum(len(h.queue) for h in self.hosts)
            + sum(len(l.queue) for l in self.overflow.values())
            + sum(len(l.queue) for l in self.seg_lanes.values())
        )

    # --------------------------------------------------------- flow control
    def update_overload(self) -> tuple[list[int], list[int]]:
        """Advance the ingest watermark hysteresis; -> (docs newly over the
        high watermark, docs drained back under the low watermark).  The
        consumer calls this once per pump and pauses/resumes per-partition
        reads on the deltas; the gate's paused set IS the engine's overload
        state (``health()['overload']``).  Lane docs (segment-sharded or
        overflow) queue on their lane, not the batch host, so the gate
        reads the combined depth — otherwise promotion (which empties
        ``h.queue`` into the lane) would instantly resume a paused hot doc
        and its lane queue would grow unboundedly."""
        return self.overload_gate.update(
            self._busy | set(self.seg_lanes) | set(self.overflow),
            self._queue_depth,
        )

    def _queue_depth(self, d: int) -> int:
        """Total staged-but-unapplied rows for doc ``d``: its batch host
        queue plus any seg/overflow lane queue (the flow-control signal)."""
        lane = self.seg_lanes.get(d) or self.overflow.get(d)
        return len(self.hosts[d].queue) + (
            len(lane.queue) if lane is not None else 0
        )

    def ingest_watermarks(self) -> dict:
        """The flow-control contract numbers: one megastep dispatch retires
        ``megastep_budget`` rows per doc; pause at ``high``, resume at
        ``low``."""
        return self.overload_gate.watermarks(
            self.megastep_k * self.ops_per_step
        )

    @property
    def overloaded(self) -> bool:
        return bool(self.overload_gate.paused)

    def _drain_into(
        self,
        docs: list[int],
        ops: np.ndarray,
        payloads: np.ndarray,
        rows: list[int] | None = None,
        slots: bool = False,
    ) -> tuple[list[int], int]:
        """Dequeue up to ops_per_step ops per listed doc into the padded
        arrays (``docs[j]`` fills row ``rows[j]``, default ``j``) — the
        ONE drain used by full-fleet, cohort, and megastep packing (their
        semantics must never diverge).  Vectorized: each doc moves as two
        slice copies (op rows + payload rows), never a per-op Python loop.
        The caller guarantees the target rows are zeroed
        (StagingRing.acquire); returns the rows written, so a reused buffer
        re-zeroes exactly those, and the deepest take: each queue fills a
        prefix of the slots, so that is where the slice's row loop ends
        (with ``slots``, each shard's own deepest take is counted too)."""
        B = self.ops_per_step
        written: list[int] = []
        deepest = 0
        shard_deepest = [0] * self.n_shards if slots else None
        for j, d in enumerate(docs):
            h = self.hosts[d]
            take = min(B, len(h.queue))
            if not take:
                continue
            r = j if rows is None else rows[j]
            src_ops, src_payloads = h.queue.take(take)
            ops[r, :take] = src_ops
            payloads[r, :take] = src_payloads
            if slots:
                # Row IS the device slot here (full-fleet packing): charge
                # the op count to its shard for hot-shard detection, and
                # keep the shard's deepest take.
                shard = r // self.docs_per_shard
                self._shard_ops[shard] += take
                if take > shard_deepest[shard]:
                    shard_deepest[shard] = take
            if not h.queue:
                self._busy.discard(d)
            written.append(r)
            deepest = max(deepest, take)
        if slots:
            self._shard_row_slots += shard_deepest
        return written, deepest

    def _count_row_slots(self, scanned: int, slices: int = 1) -> None:
        """How often the row loop's data trip count engages: the row slots
        ``slices`` dispatched slices loop over against the ``ops_per_step``
        each a dense loop would (``row_slots_scanned_share``)."""
        self.counters.bump("row_slots_scanned", scanned)
        self.counters.bump("row_slots_dense", slices * self.ops_per_step)

    def _staging(self) -> StagingRing:
        if self._stage is None:
            self._stage = StagingRing(
                self.megastep_k, self.capacity, self.ops_per_step,
                mk.OP_FIELDS, self.max_insert_len, mesh=self.mesh,
                doc_axis=(
                    pm.fleet_doc_axes(self.mesh)
                    if self.mesh is not None else "docs"
                ),
            )
        return self._stage

    @staticmethod
    def _pow2_floor(n: int) -> int:
        return 1 << (max(n, 1).bit_length() - 1)

    def _select_k(self, busy: list[int], cohort: bool) -> int:
        """Adaptive megastep depth from queue depths: how many B-op slices
        to fuse into the next dispatch.  Cohort-bucketing aware: a
        full-fleet megastep fuses only as many slices as the busy set
        stays ABOVE the cohort threshold (bounded by the (thresh+1)-th
        deepest queue), so a Zipf tail still collapses into small gathered
        cohorts exactly when it would have.  Quantized to powers of two
        (compile cache stays log2(K) deep, and an undershoot just means
        one more dispatch — never wasted all-NOOP slices)."""
        if self.megastep_k <= 1:
            return 1
        B = self.ops_per_step
        depths = np.array(
            [-(-len(self.hosts[d].queue) // B) for d in busy], np.int64
        )
        if cohort or not self.bucketing:
            need = int(depths.max())
        else:
            thresh = self.capacity // 4
            if len(depths) > thresh:
                # Slices until the busy set shrinks to cohort size: the
                # (thresh+1)-th deepest queue still has ops at slice k iff
                # its depth > k.
                need = int(np.partition(depths, -thresh - 1)[-thresh - 1])
            else:
                need = int(depths.max())
        return min(self.megastep_k, self._pow2_floor(need))

    def _full_step(self, busy: list[int], k_max: int | None = None) -> int:
        """One fleet-wide megastep: pack up to K [capacity, B] slices into
        the staging ring (slice k+1 packs while the upload/dispatch of the
        previous megastep is still in flight) and apply them as one
        donated program; returns the slices applied."""
        with span("pack", kind="full", docs=len(busy)):
            K = self._select_k(busy, cohort=False)
            if k_max is not None:
                K = min(K, k_max)
            stage = self._staging()
            ops, payloads = stage.acquire(K, self.capacity)
            # Pack by doc PLACEMENT: doc d's ops land in row slot(d), so
            # each shard's slice of the staging buffer holds exactly its
            # own docs and the shard-layout upload splits per chip with no
            # reshuffle.
            rows = [int(s) for s in self._slot[busy]]
            scanned = 0
            for k in range(K):
                written, deepest = self._drain_into(
                    busy, ops[k], payloads[k], rows=rows, slots=True
                )
                stage.mark(k, written)
                scanned += deepest
                if k + 1 < K:
                    pairs = [
                        (d, r) for d, r in zip(busy, rows) if d in self._busy
                    ]
                    busy = [d for d, _ in pairs]
                    rows = [r for _, r in pairs]
        if self.mesh is None and K == 1:
            dev_ops, dev_payloads = stage.upload(ops[0], payloads[0])
            with span("dispatch", kind="full", k=K, rows=scanned):
                self.state = self._step(self.state, dev_ops, dev_payloads)
            self._full_built = True
        else:
            # The mesh path always dispatches the [K, D, B] megastep
            # program (K=1 included — apply_megastep at K=1 is bit-
            # identical to one apply_ops dispatch): one donated shard_map
            # call steps every chip, zero hot-path collectives.
            dev_ops, dev_payloads = stage.upload(ops, payloads)
            with span(
                "dispatch", kind="full", k=K, rows=scanned,
                shards=self.n_shards,
            ):
                self.state = self._megastep(self.state, dev_ops, dev_payloads)
        self.full_steps += K
        self.counters.bump("megastep_dispatches")
        self.counters.bump("megastep_slices", K)
        self._count_row_slots(scanned, K)
        return K

    def step(self, in_flight=None) -> int:
        """Run device dispatches until all staged ops are applied; returns
        the number of batched SLICES applied (a K-slice megastep counts K,
        so the return value is K-invariant).  Busy-doc cohorts far below
        fleet size run bucketed (see __init__), so a Zipf-skewed tail
        stops costing full-fleet steps.  No host/device sync happens
        between megasteps — uploads and dispatches queue asynchronously;
        the pipeline synchronizes only at the recover()/watchdog/
        checkpoint boundaries below.  Afterwards, any latched overflow
        bits are recovered (grow-and-replay or oracle routing), so
        ``errors()`` is all-zero on return unless recovery is off.

        ``in_flight``, if given, is called once, between the step's last
        dispatch and the readback that waits for it, with the error latch
        of that dispatch: host work the caller can do until the latch
        ``is_ready()`` (the consumer reads its sockets there, asleep
        between arrivals).  It must not touch the engine: every queue is
        still empty when ``recover()`` runs.  Where nothing is read back
        (recovery off) nothing waits, and it is not called.

        Holds ``ckpt_lock`` end to end (the background checkpoint writer
        can only sweep between steps), and is the recovery clock's
        completion point: the first step that applies staged work after a
        restore closes the open incident (kill -> first post-restore op
        applied)."""
        with self.ckpt_lock:
            had_work = self._has_staged_rows()
            steps = self._step_fleet(in_flight)
            if had_work and self.recovery_tracker.active:
                self.recovery_tracker.complete()
        # Cadence checkpoints run AFTER the serving lock releases: the
        # record build retakes ckpt_lock briefly, but the durable fsyncs
        # land with it free — the serving thread no longer pays platter
        # time under the lock every ingest/step contender waits on
        # (fftpu-check blocking-under-lock: fsync under ckpt_lock).
        # Work staged by a racing ingest meanwhile is skipped by the
        # sweep's staged-but-unapplied guard, exactly as a background
        # sweep would skip it.
        with span("housekeeping", kind="checkpoint"):
            self.maybe_checkpoint()
        return steps

    def _has_staged_rows(self) -> bool:
        """Any op row ingested but not yet applied (batch or lanes)."""
        return bool(
            self._busy
            or any(ln.queue for ln in self.overflow.values())
            or any(ln.queue for ln in self.seg_lanes.values())
        )

    def _step_fleet(self, in_flight=None) -> int:
        """One loop of the serving thread, in order (ROADMAP S2): dispatch
        every slice the queues hold (nothing waits for the device), hand
        ``in_flight`` the last dispatch's error latch (the consumer reads
        its sockets until the device has caught up), then ``recover()``'s
        readback of that latch, recover what latched, compact the acked
        documents and resolve the op clock."""
        t0 = time.perf_counter() if self.sampled is not None else 0.0
        steps = 0
        while self._busy:
            busy = sorted(self._busy)
            if not (self.bucketing and len(busy) <= self.capacity // 4):
                steps += self._full_step(busy)
            elif self._cold_and_shallow(busy):
                self.counters.bump("cohort_cold_fallbacks")
                steps += self._full_step(busy, k_max=1)
            else:
                steps += self._cohort_step(busy)
        self._step_lanes()
        self._step_seg_lanes()
        self._step_count += 1
        if self.recovery != "off":
            if in_flight is not None:
                # Still the wait for the device, so still a ``readback``
                # span: the readback below finds the latch ready.
                with span("readback", kind="in_flight"):
                    in_flight(self.state.error)
            self.recover()
            self._steps_since_watchdog += 1
            if (
                self.watchdog_every
                and self._steps_since_watchdog >= self.watchdog_every
            ):
                self._steps_since_watchdog = 0
                self.watchdog()
            if self.readmit_after_steps:
                self._maybe_readmit()
        if self.compact_due:
            self._compact_due_docs()
        # Sync boundary housekeeping (host-side, O(programs + samples)):
        # resolve the op clock's pending feeds, poll for mid-serve
        # recompiles, and feed the sampled step timing when a telemetry
        # sink is attached.
        with span("housekeeping"):
            self.op_clock.resolve()
            self.recompile_watchdog.poll()
            if self.sampled is not None:
                self.sampled.record(time.perf_counter() - t0, "step")
        return steps

    @staticmethod
    def _cohort_lanes(n_busy: int) -> int:
        return max(1, 1 << (n_busy - 1).bit_length())  # pow2 ladder

    def _cold_and_shallow(self, busy: list[int]) -> bool:
        """The busy set's cohort program would be dispatched for the first
        time, the fleet-wide K = 1 program is built, and one slice of it
        clears every queue (see __init__)."""
        lanes = self._cohort_lanes(len(busy))
        if not self._full_built or (lanes, 1) in self._built:
            return False
        return max(len(self.hosts[d].queue) for d in busy) <= self.ops_per_step

    def _maybe_readmit(self) -> None:
        """Backoff-scheduled quarantine readmission (see __init__)."""
        for d, due_step in list(self._readmit_due.items()):
            if self._step_count < due_step or d not in self.quarantine:
                if d not in self.quarantine:
                    self._readmit_due.pop(d, None)
                continue
            if self.readmit(d):
                self.counters.bump("auto_readmissions")
            else:
                # State no longer fits the batch geometry: double the
                # backoff and retry later (the doc stays serviceable in
                # its quarantine lane).
                interval = min(
                    2 * self._readmit_interval.get(d, self.readmit_after_steps),
                    self.readmit_after_steps << 16,
                )
                self._readmit_interval[d] = interval
                self._readmit_due[d] = self._step_count + interval

    def _cohort_step(self, busy: list[int]) -> int:
        """One bucketed megastep over just the busy docs: gather the
        cohort's state rows once, apply up to K fused [Kc, B] slices, and
        masked-scatter the rows back — K > 1 amortizes the gather/scatter
        pair as well as the dispatch.  Every leaf moves but the text pool,
        which stays in ``self.state`` and is written in place by the step
        (``_cohort_trio``).  Returns the slices applied."""
        with span("pack", kind="cohort", docs=len(busy)):
            K = self._select_k(busy, cohort=True)
            Kc = self._cohort_lanes(len(busy))
            idx = np.full((Kc,), busy[-1], np.int32)  # gather pad: dup
            idx[: len(busy)] = busy
            valid = np.zeros((Kc,), bool)
            valid[: len(busy)] = True
            stage = self._staging()
            ops, payloads = stage.acquire(K, Kc)
            row_of = {d: j for j, d in enumerate(busy)}
            cur = busy
            scanned = 0
            for k in range(K):
                written, deepest = self._drain_into(
                    cur, ops[k], payloads[k], rows=[row_of[d] for d in cur]
                )
                stage.mark(k, written)
                scanned += deepest
                if k + 1 < K:
                    cur = [d for d in cur if d in self._busy]
        if (Kc, K) in self._built:
            self._cohort_trio(idx, valid, ops, payloads, scanned)
        else:
            # A size's first dispatch traces and lowers three programs under
            # this call: from a stretch of frame stack that no chunk boundary
            # crosses, or the depth of this frame decides what they cost
            # (utils/stack_room.py).
            with_stack_room(self._cohort_trio, idx, valid, ops, payloads, scanned)
        self.cohort_steps += K
        self.cohort_lanes += K * Kc
        self.counters.bump("megastep_dispatches")
        self.counters.bump("megastep_slices", K)
        self._count_row_slots(scanned, K)
        return K

    def _cohort_trio(self, idx, valid, ops, payloads, scanned: int = 0) -> None:
        """The cohort path's three dispatches over the fleet rows ``idx``
        ([lanes]; a pad lane repeats a row, carries NOOPs and is not
        ``valid``), for the staged ``ops`` / ``payloads`` [K, lanes, B, ...].

        Where the pool lives: in ``self.state.text`` throughout.  The gather
        and the scatter are given the state without it; the step takes it
        donated beside the gathered rows, writes its strips at ``idx`` and
        hands it back, and it is put back into ``self.state`` right after
        the dispatch."""
        K, lanes = ops.shape[:2]
        rows = jnp.asarray(idx)
        with span("gather", lanes=lanes):
            sub = self._gather_cohort(self.state._replace(text=None), rows)
        if K == 1:
            step = _cohort_fleet_step
            dev = self._staging().upload(ops[0], payloads[0])
        else:
            step = _cohort_megastep
            dev = self._staging().upload(ops, payloads)
        with span("dispatch", kind="cohort", k=K, lanes=lanes, rows=scanned):
            pool, sub = step(self.state.text, sub, rows, *dev)
            self.state = self.state._replace(text=pool)
        with span("scatter", lanes=lanes):
            rest = self._scatter_cohort(
                self.state._replace(text=None), sub, rows, jnp.asarray(valid)
            )
            self.state = rest._replace(text=pool)
        self._built.add((lanes, K))

    def _step_lanes(self) -> None:
        B = self.ops_per_step
        if not self.overflow:
            return
        stage = self._staging()
        for lane in self.overflow.values():
            while lane.queue:
                take = min(B, len(lane.queue))
                # One staged [B] chunk per dispatch through the shared
                # ring (row 0 of a 1-slice view): slice copies, no fresh
                # allocation, and the double buffer keeps the host from
                # mutating an upload still in flight.
                ops, payloads = stage.acquire(1, 1)
                src_ops, src_payloads = lane.queue.take(take)
                ops[0, 0, :take] = src_ops
                payloads[0, 0, :take] = src_payloads
                stage.mark(0, [0])
                dev_ops, dev_payloads = stage.upload(
                    ops[0, 0], payloads[0, 0]
                )
                with span("dispatch", kind="lane", rows=take):
                    lane.state = self._lane_apply(
                        lane.state, dev_ops, dev_payloads
                    )
                self._count_row_slots(take)

    # -------------------------------------------------------- segment lanes
    def _step_seg_lanes(self) -> None:
        """Drain every segment lane with [K, B] seg-parallel megastep
        dispatches, re-blocking any lane past its rebalance budget."""
        for d, lane in list(self.seg_lanes.items()):
            self._drain_seg_lane(d, lane)
            if (
                self.seg_rebalance_every
                and lane.ops_since_rebalance >= self.seg_rebalance_every
            ):
                self.rebalance_segments(d)

    def _drain_seg_lane(self, d: int, lane: _SegmentLane) -> None:
        """Apply ONE lane's staged ops as [K, B] seg megasteps: ops/
        payloads upload REPLICATED over the segs axis (each shard applies
        every op to its own segment block) and the dispatch spans carry
        the 2-D layout for the flight recorder.  The [K, B] buffers are
        fresh per dispatch — at K*B*(OP_FIELDS+L) int32 they are tiny next
        to the dispatch itself (phase_shares pins dispatch at ~99%), so
        the fleet ring's reuse machinery is not worth threading in here."""
        B = self.ops_per_step
        while lane.queue:
            need = -(-len(lane.queue) // B)
            K = min(self.megastep_k, self._pow2_floor(max(need, 1)))
            ops = np.zeros((K, B, mk.OP_FIELDS), np.int32)
            payloads = np.zeros((K, B, self.max_insert_len), np.int32)
            taken = 0
            for k in range(K):
                take = min(B, len(lane.queue))
                if not take:
                    break
                src_ops, src_payloads = lane.queue.take(take)
                ops[k, :take] = src_ops
                payloads[k, :take] = src_payloads
                taken += take
            dev_ops, dev_payloads = upload_replicated(ops, payloads, self.mesh)
            with span(
                "dispatch", kind="seg", k=K, doc=self.doc_keys[d],
                seg_shards=lane.n_shards,
            ):
                lane.state = self._seg_megastep(
                    lane.state, dev_ops, dev_payloads
                )
            lane.version += 1
            lane.ops_since_rebalance += taken
            self.counters.bump("megastep_dispatches")
            self.counters.bump("megastep_slices", K)

    def segment_sharded(self) -> dict[str, int]:
        """doc key -> segment shard count for every promoted hot doc: the
        2-D placement surface (fleet status / supervisors)."""
        return {
            self.doc_keys[d]: lane.n_shards
            for d, lane in self.seg_lanes.items()
        }

    def enable_segment_sharding(
        self, d: int, s_local: int = 0, text_capacity: int = 0
    ) -> bool:
        # ckpt_lock: promotion moves the doc's row into a seg lane the
        # background checkpoint sweep also reads — see migrate_doc.
        with self.ckpt_lock:
            return self._enable_segment_sharding_locked(
                d, s_local, text_capacity
            )

    def _enable_segment_sharding_locked(
        self, d: int, s_local: int = 0, text_capacity: int = 0
    ) -> bool:
        """Promote a hot doc onto the segment-parallel path: its device row
        re-blocks into the seg-sharded layout (``mk.seg_shard_state`` — live
        segments split into contiguous runs over the segs axis, text/
        scalars/ob table replicated) and future ops apply segment-parallel.
        The batch slot stays RESERVED (pristine) so placement/scribe
        alignment are untouched and demotion lands back in place.  Staged
        ops move to the lane queue — promotion is legal MID-STREAM.
        Returns False when seg serving is off, the doc is off the batch
        path, the lane budget is spent, or the state does not block."""
        if self.seg_shards <= 1 or self._seg_megastep is None:
            return False
        if not (0 <= d < self.n_docs):
            raise ValueError(f"no doc {d}")
        if (
            d in self.seg_lanes or d in self.overflow
            or d in self.oracles or d in self.quarantine
        ):
            return False
        if len(self.seg_lanes) >= self.max_seg_lanes:
            self.counters.bump("seg_promotions_skipped")
            return False
        slot = int(self._slot[d])
        row = jax.tree.map(lambda x: np.asarray(x[slot]), self.state)
        if int(row.error):
            return False  # recover first; never promote a latched row
        s_local = (
            s_local or self.seg_lane_segments or self.geometry["max_segments"]
        )
        tc = (
            text_capacity or self.seg_lane_text_capacity
            or self.geometry["text_capacity"]
        )
        try:
            blocked = mk.seg_shard_state(row, self.seg_shards, s_local, tc)
        except (ValueError, NotImplementedError):
            return False
        lane = _SegmentLane(
            state=pm.shard_seg_state(blocked, self.mesh),
            n_shards=self.seg_shards, s_local=s_local,
            queue=RowQueue(mk.OP_FIELDS, self.max_insert_len),
        )
        h = self.hosts[d]
        if h.queue:
            ops_p, payloads_p = h.queue.pending()
            lane.queue.extend_block(ops_p.copy(), payloads_p.copy())
            h.queue.clear()
        self._busy.discard(d)
        self.seg_lanes[d] = lane
        # Retire the batch row to the pristine proto (slot reserved).
        self.state = jax.tree.map(
            lambda x, s: x.at[slot].set(s), self.state, self._proto
        )
        self._verified_digest.pop(d, None)
        self.counters.bump("seg_promotions")
        instant(
            "seg_promote", doc=self.doc_keys[d], shards=self.seg_shards,
            s_local=s_local,
        )
        return True

    def disable_segment_sharding(self, d: int) -> bool:
        """Demote a segment-sharded doc back into its reserved batch row
        (the migrate_doc handoff: gather -> summary export -> re-pack at
        batch geometry).  Staged lane ops apply first so nothing is lost.
        Returns False when the gathered state no longer fits the batch
        geometry (the doc stays segment-sharded and serviceable)."""
        with self.ckpt_lock:  # mutates state/seg_lanes the sweep reads
            return self._disable_segment_sharding_locked(d)

    def _disable_segment_sharding_locked(self, d: int) -> bool:
        lane = self.seg_lanes.get(d)
        if lane is None:
            return False
        if lane.queue:
            self._drain_seg_lane(d, lane)
        host = jax.tree.map(np.asarray, lane.state)
        if int(host.error):
            return False  # recover() handles latched lanes
        gathered = mk.seg_gather_state(host)
        h = self.hosts[d]
        self._sync_native_props(h)
        summary = kb.state_to_summary(
            gathered, {v: k for k, v in h.prop_slot.items()}
        )
        try:
            row = kb.summary_to_state(
                summary, self.geometry,
                lambda p: self._prop_slot_for_geom(h, p, self.geometry),
            )
        except (ValueError, IndexError):
            return False
        slot = int(self._slot[d])
        self.state = jax.tree.map(
            lambda x, s: x.at[slot].set(s), self.state, row
        )
        del self.seg_lanes[d]
        self._verified_digest.pop(d, None)
        self.counters.bump("seg_demotions")
        instant("seg_demote", doc=self.doc_keys[d])
        return True

    def rebalance_segments(self, d: int) -> bool:
        """Re-block a segment lane so every shard holds an even share of
        the live segments again (inserts land shard-local between rebalance
        points, so runs skew toward the hot shard over time).  Gather +
        re-shard, byte- and order-preserving (``mk.seg_rebalance_state``,
        the compaction gather's fill conventions)."""
        with self.ckpt_lock:  # mutates lane state the sweep reads
            return self._rebalance_segments_locked(d)

    def _rebalance_segments_locked(self, d: int) -> bool:
        lane = self.seg_lanes.get(d)
        if lane is None:
            return False
        if int(np.asarray(lane.state.error)):
            # One scalar readback, not the tree-wide gather below: a
            # latched lane is re-tried every step while it waits for
            # recover() (or forever under recovery='off').
            return False
        with span(
            "seg_rebalance", doc=self.doc_keys[d], shards=lane.n_shards
        ):
            host = jax.tree.map(np.asarray, lane.state)
            blocked = mk.seg_rebalance_state(host, s_local=lane.s_local)
            lane.state = pm.shard_seg_state(blocked, self.mesh)
        lane.version += 1
        lane.rebalances += 1
        lane.ops_since_rebalance = 0
        self.counters.bump("seg_rebalances")
        instant("seg_rebalance", doc=self.doc_keys[d])
        return True

    def compact(self, docs=None) -> None:
        """Advance MSNs and run zamboni eviction: over the documents
        ``docs`` (those whose summary ack the consumer just read), or over
        the whole fleet.

        Either way the floor is each host's ``min_seq``, the MSN of the
        newest message ingested, and rows still staged are applied FIRST:
        that MSN may postdate the ref-seq of rows still queued (a consumer
        that fell behind reads ops and the summary ack that follows them in
        one pass); zamboni at that floor would evict tombstones those rows
        still resolve their positions against, and they would land in the
        wrong place with no error latched.

        With ``docs`` nothing is dispatched here: the documents are marked
        due and ``step`` compacts them after its slices have applied what
        was staged (``_compact_due_docs``), a due document with rows still
        queued waiting for the step that empties its queue.  Without, this
        call steps and then compacts every document and every lane."""
        if docs is not None:
            with self.ckpt_lock:
                self.compact_due.update(int(d) for d in docs)
            return
        if self._has_staged_rows():
            self.step()
        with span("compact", kind="fleet", docs=self.n_docs,
                  lanes=self.capacity):
            self._compact_batch_fleet_wide()
            self.compact_due.clear()
        for d in (*self.seg_lanes, *self.overflow, *self.oracles,
                  *self.quarantine):
            self._compact_lane(d)

    def _compact_batch_fleet_wide(self) -> None:
        """Every row of the batch state at its host's ``min_seq``: one
        program over ``capacity`` lanes (``shard_map`` under a mesh)."""
        mins = np.zeros((self.capacity,), np.int32)
        for d, h in enumerate(self.hosts):
            mins[self._slot[d]] = h.min_seq
        if self.mesh is not None:
            mins_dev = jax.device_put(mins, pm.shard_docs(self.mesh))
        else:
            mins_dev = jnp.asarray(mins)
        self.state = self._compact(self.state, mins_dev)
        self.counters.bump("compact_dispatches")
        self.counters.bump("compacted_docs", self.n_docs)
        self.counters.bump("compacted_lanes", self.capacity)

    def _compact_lane(self, d: int) -> None:
        """Zamboni for a document that left the batch: its lane alone."""
        floor = self.hosts[d].min_seq
        if d in self.seg_lanes:
            lane = self.seg_lanes[d]
            lane.state = self._seg_compact(
                lane.state, jnp.asarray(floor, jnp.int32)
            )
            lane.version += 1
        elif d in self.overflow:
            lane = self.overflow[d]
            lane.state = self._lane_compact(
                lane.state, jnp.asarray(floor, jnp.int32)
            )
        elif d in self.oracles:
            self.oracles[d].update_min_seq(floor)
        else:
            self.quarantine[d].update_min_seq(floor)

    def _compact_due_docs(self) -> None:
        """``step``'s last device work: zamboni for the due documents whose
        staged rows are all applied.  Batch documents go through the cohort
        program at ``_cohort_lanes`` of their number; more of them than the
        largest lane count this engine has dispatched go out as several
        dispatches of built sizes, so that serving builds no larger program
        than set-up did (a ladder builds its largest size first, ``warmup``
        builds 1 to 8).  Above ``capacity // 4`` of them, and under a mesh,
        it is the fleet-wide program.  A document in a lane compacts its
        lane alone."""
        due = sorted(d for d in self.compact_due if not self._queue_depth(d))
        lanes_of = [d for d in due if self._off_batch(d)]
        batch = [d for d in due if not self._off_batch(d)]
        fleet_wide = batch and (
            self.mesh is not None or len(batch) > self.capacity // 4
        )
        if fleet_wide and self._busy:
            # Every row is compacted at its host's floor: all of them wait.
            due, batch = lanes_of, []
        self.compact_due.difference_update(due)
        for d in lanes_of:
            with span("compact", kind="lane", docs=1, lanes=1):
                self._compact_lane(d)
        if not batch:
            return
        if fleet_wide:
            with span("compact", kind="fleet", docs=len(batch),
                      lanes=self.capacity):
                self._compact_batch_fleet_wide()
            return
        while batch:
            lanes = self._cohort_lanes(len(batch))
            lanes = min(lanes, max(self._compact_built, default=lanes))
            now, batch = batch[:lanes], batch[lanes:]
            with span("compact", kind="cohort", docs=len(now), lanes=lanes):
                idx = np.full((lanes,), self._slot[now[-1]], np.int32)
                idx[: len(now)] = self._slot[now]
                mins = np.full(
                    (lanes,), self.hosts[now[-1]].min_seq, np.int32
                )
                mins[: len(now)] = [self.hosts[d].min_seq for d in now]
                self._dispatch_compact_cohort(idx, mins)
            self.counters.bump("compact_dispatches")
            self.counters.bump("compacted_docs", len(now))
            self.counters.bump("compacted_lanes", lanes)

    def _dispatch_compact_cohort(self, idx: np.ndarray, mins: np.ndarray) -> None:
        """One cohort compaction over the batch rows ``idx`` (the numpy
        arrays ride the call: no upload of their own)."""
        cols = _compact_cohort(
            {f: getattr(self.state, f) for f in _COMPACT_FIELDS}, idx, mins
        )
        self.state = self.state._replace(**cols)
        self._compact_built.add(len(idx))

    def _off_batch(self, d: int) -> bool:
        """The document's replica is a lane's, not its batch row."""
        return (
            d in self.overflow or d in self.seg_lanes
            or d in self.oracles or d in self.quarantine
        )

    def evictable_left(self) -> int:
        """Segments zamboni would still drop at each replica's own
        ``min_seq``, over the batch and the overflow lanes: 0 when every
        compaction evicted what its floor allowed."""
        left = int(_fleet_evictable(self.state))
        for lane in self.overflow.values():
            left += int(mk.evictable_count(lane.state))
        return left

    def device_min_seqs(self) -> list[int]:
        """Each document's collab-window floor as its device replica holds
        it (a host lane's document: the floor its oracle was given)."""
        by_slot = np.asarray(self.state.min_seq)
        out = [int(by_slot[self._slot[d]]) for d in range(self.n_docs)]
        for d, lane in self.overflow.items():
            out[d] = int(lane.state.min_seq)
        for d in (*self.seg_lanes, *self.oracles, *self.quarantine):
            out[d] = int(self.hosts[d].min_seq)
        return out

    # --------------------------------------------------------------- recovery
    def recover(self) -> list[int]:
        """Inspect every error vector and recover flagged docs; returns the
        doc indices recovered this call.  Capacity bits grow-and-replay (or
        oracle-route); poison bits (ERR_POS_RANGE alone) quarantine."""
        recovered: list[int] = []
        batch_clean = False
        if self.mesh is not None:
            # Per-shard reduce instead of a cross-mesh [D] gather: each
            # shard partial-sums its own latch rows and the host reads ONE
            # scalar — the full error vector transfers only when it is
            # actually nonzero (recovery itself, off the hot path).  Lane
            # errors are per-lane scalars checked below, so an active seg
            # or overflow lane must not force the batch-state gather.
            with span("readback", kind="error_count"):
                batch_clean = int(pm.error_count(self.state.error)) == 0
        if not batch_clean:
            with span("readback", kind="error_vector"):
                err = np.asarray(self.state.error)
        # The host part: walk the vector, then the lanes' own latches.
        with span("recover"):
            if not batch_clean:
                for d in range(self.n_docs):
                    slot = int(self._slot[d])
                    if (
                        d not in self.overflow
                        and d not in self.oracles
                        and d not in self.quarantine
                        and err[slot]
                    ):
                        bits = int(err[slot])
                        if mk.is_capacity_error(bits):
                            self._recover_doc(d, bits, growths=0)
                        else:  # poison: ERR_POS_RANGE with no capacity bit
                            self._quarantine_doc(d, f"error bits {bits:#x}")
                        # Retire the batch slot: clear the latched bits so the
                        # slot never re-triggers (its queue is empty and future
                        # ops route to the lane).
                        self.state = self.state._replace(
                            error=self.state.error.at[slot].set(0)
                        )
                        recovered.append(d)
            for d, lane in list(self.overflow.items()):
                bits = int(lane.state.error)
                if bits:
                    if mk.is_capacity_error(bits):
                        self._recover_doc(d, bits, growths=lane.growths)
                    else:
                        self._quarantine_doc(d, f"error bits {bits:#x}")
                    recovered.append(d)
            for d, lane in list(self.seg_lanes.items()):
                bits = int(np.asarray(lane.state.error))
                if bits:
                    # A latched segment lane leaves the seg path entirely: the
                    # retained log replays into a standard overflow lane (grow)
                    # or quarantine — staged lane rows ride the log, so nothing
                    # is lost.  Re-promotion is the supervisor's call.
                    self.seg_lanes.pop(d)
                    if mk.is_capacity_error(bits):
                        self._recover_doc(d, bits, growths=0)
                    else:
                        self._quarantine_doc(
                            d, f"error bits {bits:#x} (seg lane)"
                        )
                    recovered.append(d)
        if recovered:
            # One structured health event per recovery action (no-op
            # without a telemetry logger).
            self.counters.emit(recovered_docs=len(recovered))
        return recovered

    def _recover_doc(self, d: int, bits: int, growths: int) -> None:
        # Recovery works on the parsed-message log: fold a native doc's raw
        # lines in first (ordering: they precede any object-path appends).
        self._normalize_native(self.hosts[d])
        h = self.hosts[d]
        geom = dict(
            self.overflow[d].geometry if d in self.overflow else self.geometry
        )
        while self.recovery == "grow" and growths < self.max_growths:
            growths += 1
            geom = self._grown_geometry(geom, bits)
            if h.base_summary is not None:
                # The replay base must fit before a single op applies.
                geom = self._fit_geometry(
                    geom, h.base_summary, len(h.prop_slot)
                )
            state = self._replay(h, geom)
            new_bits = int(state.error)
            if new_bits == 0:
                self.overflow[d] = self._make_lane(state, geom, growths)
                self.counters.bump("capacity_recoveries")
                return
            bits = new_bits
            if mk.is_poison_error(bits):
                # POS_RANGE that survives replay at grown capacity is not a
                # cascade: the op stream itself is malformed.  Isolate the
                # document instead of killing the fleet.
                self._quarantine_doc(
                    d, f"error bits {bits:#x} during replay at {geom}"
                )
                return
        # Growth exhausted (or policy is oracle): host replica takes over.
        self.overflow.pop(d, None)
        tree = self._oracle_from_base(h)
        for msg in h.log:
            self._oracle_apply(tree, h, msg)
        tree.update_min_seq(h.min_seq)
        self.oracles[d] = tree
        self.counters.bump("oracle_routes")

    @staticmethod
    def _grown_geometry(base: dict[str, int], bits: int) -> dict[str, int]:
        geom = dict(base)
        if bits & mk.ERR_SEG_OVERFLOW:
            geom["max_segments"] *= 2
        if bits & mk.ERR_TEXT_OVERFLOW:
            geom["text_capacity"] *= 2
        if bits & mk.ERR_REM_OVERFLOW:
            geom["remove_slots"] *= 2
        if bits & mk.ERR_OB_OVERFLOW:
            geom["ob_slots"] *= 2
        return geom

    @staticmethod
    def _fit_geometry(
        geom: dict[str, int], summary: dict, min_prop_slots: int = 0
    ) -> dict[str, int]:
        """Grow ``geom`` (doubling, preserving the pow2 ladder) until the
        checkpoint summary fits — a replay base must never itself overflow.
        ``min_prop_slots`` covers slots the doc's restored prop table
        already interned (slot indices, not just distinct summary props)."""
        geom = dict(geom)
        n_seg = len(summary["segments"])
        n_text = sum(len(e["text"]) for e in summary["segments"])
        n_rem = max(
            (len(e["removes"]) for e in summary["segments"]), default=0
        )
        n_ob = len(summary.get("obliterates", []))
        while geom["max_segments"] < n_seg:
            geom["max_segments"] *= 2
        while geom["text_capacity"] < n_text:
            geom["text_capacity"] *= 2
        while geom["remove_slots"] < n_rem:
            geom["remove_slots"] *= 2
        while geom["ob_slots"] < n_ob:
            geom["ob_slots"] *= 2
        while geom["prop_slots"] < min_prop_slots:
            geom["prop_slots"] *= 2
        return geom

    def _replay(self, h: _DocHost, geom: dict[str, int]) -> mk.DocState:
        """Re-apply the retained wire log on a state with ``geom`` — from
        the checkpoint base when one exists (bounded replay), from scratch
        otherwise."""
        if h.base_summary is not None:
            state = kb.summary_to_state(
                h.base_summary, geom, lambda p: self._prop_slot_for_geom(h, p, geom)
            )
        else:
            state = mk.init_state(
                geom["max_segments"], geom["remove_slots"], geom["prop_slots"],
                geom["text_capacity"], geom["ob_slots"],
            )
        B = self.ops_per_step
        rows: list[tuple[np.ndarray, np.ndarray]] = []
        for msg in h.log:
            rows.extend(self._encode(h, msg))
        self.counters.gauge("recovery_replay_len", len(h.log))
        for i in range(0, len(rows), B):
            chunk = rows[i : i + B]
            ops = np.zeros((B, mk.OP_FIELDS), np.int32)
            payloads = np.zeros((B, self.max_insert_len), np.int32)
            ops[: len(chunk)] = [op for op, _ in chunk]
            payloads[: len(chunk)] = [payload for _, payload in chunk]
            state = self._lane_apply(
                state, jnp.asarray(ops), jnp.asarray(payloads)
            )
        return state

    def _prop_slot_for_geom(self, h: _DocHost, prop: int, geom: dict) -> int:
        """Intern a checkpointed property id during a replay-base restore
        (same table as live encoding; range-checked against ``geom``)."""
        if prop not in h.prop_slot:
            slot = len(h.prop_slot)
            if slot >= geom["prop_slots"]:
                raise ValueError(
                    f"checkpoint needs more than {geom['prop_slots']} prop slots"
                )
            h.prop_slot[prop] = slot
        return h.prop_slot[prop]

    # ------------------------------------------------------------- quarantine
    def _oracle_from_base(self, h: _DocHost) -> RefMergeTree:
        """A host oracle seeded with the doc's checkpoint base (or empty)."""
        tree = RefMergeTree()
        if h.base_summary is not None:
            tree.import_summary(h.base_summary)
        return tree

    def _oracle_apply_validated(
        self, tree: RefMergeTree, h: _DocHost, msg: SequencedMessage
    ) -> bool:
        """Apply one wire op to a quarantine oracle with a validation gate:
        positions must resolve inside the op's own perspective and the
        sender must be in the quorum.  A malformed op is dropped and
        counted — it can corrupt neither this replica nor the batch."""
        try:
            c = msg.contents
            client = h.quorum[msg.client_id]  # KeyError: unknown sender
            n = tree.visible_length(msg.ref_seq, client)
            kind = c["type"]
            if kind == DeltaType.INSERT:
                if not isinstance(c["seg"], str):
                    # Legal-but-unsupported spec shapes fail LOUD (see
                    # _encode) — they are a feature gap, not poison.
                    raise NotImplementedError(
                        f"unsupported seg spec {type(c['seg']).__name__}"
                    )
                if not (0 <= c["pos1"] <= n):
                    raise ValueError(f"insert pos {c['pos1']} > length {n}")
            elif kind in (DeltaType.REMOVE, DeltaType.ANNOTATE):
                if not (0 <= c["pos1"] < c["pos2"] <= n):
                    raise ValueError(
                        f"range [{c['pos1']},{c['pos2']}) outside length {n}"
                    )
            elif kind in (DeltaType.OBLITERATE, DeltaType.OBLITERATE_SIDED):
                p1, s1, p2, s2 = decode_obliterate_places(c)
                from ..dds.shared_string import validate_obliterate_places

                validate_obliterate_places(p1, s1, p2, s2, n)
            self._oracle_apply(tree, h, msg)
            return True
        except NotImplementedError:
            raise  # feature gap, not poison: stay loud
        except Exception as e:  # noqa: BLE001 — the gate IS the handler
            self.counters.bump("poison_ops_dropped")
            if self.counters.logger is not None:
                self.counters.logger.error(
                    "poison_op_dropped", e, seq=msg.seq
                )
            return False

    def _quarantine_doc(self, d: int, reason: str) -> None:
        """Evict one doc from the device batch into the validated host
        oracle lane: checkpoint base + validated replay of the retained
        tail (malformed ops drop).  The rest of the batch is untouched."""
        h = self.hosts[d]
        self._normalize_native(h)
        tree = self._oracle_from_base(h)
        self.counters.gauge("quarantine_replay_len", len(h.log))
        for msg in h.log:
            self._oracle_apply_validated(tree, h, msg)
        tree.update_min_seq(h.min_seq)
        self.overflow.pop(d, None)
        self.seg_lanes.pop(d, None)
        flaps = self._flaps[d] = self._flaps.get(d, 0) + 1
        if self.poison_budget and flaps > self.poison_budget:
            # Flapping: the doc keeps getting re-poisoned after clean
            # readmissions.  Spend no more recovery work on it — route it
            # to the oracle lane permanently (still serviceable, never
            # auto-readmitted).
            self.quarantine.pop(d, None)
            self.quarantine_reason.pop(d, None)
            self._readmit_due.pop(d, None)
            self._readmit_interval.pop(d, None)
            self.oracles[d] = tree
            self.counters.bump("poison_routed_docs")
            if self.counters.logger is not None:
                self.counters.logger.error(
                    "doc_poison_routed", reason, doc=self.doc_keys[d],
                    flaps=flaps,
                )
        else:
            self.quarantine[d] = tree
            self.quarantine_reason[d] = reason
            if self.readmit_after_steps:
                # Exponential backoff: 1 flap -> base, 2 -> 2x, 3 -> 4x...
                interval = self.readmit_after_steps << min(flaps - 1, 16)
                self._readmit_interval[d] = interval
                self._readmit_due[d] = self._step_count + interval
        h.queue.clear()
        self._busy.discard(d)
        if d < self.n_docs:
            slot = int(self._slot[d])
            self.state = self.state._replace(
                error=self.state.error.at[slot].set(0)
            )
        self.counters.bump("quarantines")
        if self.counters.logger is not None:
            self.counters.logger.error(
                "doc_quarantined", reason, doc=self.doc_keys[d]
            )

    def readmit(self, d: int) -> bool:
        """Re-admit a quarantined doc to the lockstep batch: pack the
        oracle's (clean, validated) state back into the batch geometry and
        scatter it into the doc's row.  Returns False — doc stays
        quarantined — when the state no longer fits the batch geometry."""
        tree = self.quarantine.get(d)
        if tree is None:
            return False
        h = self.hosts[d]
        summary = tree.export_summary()
        try:
            row = kb.summary_to_state(
                summary, self.geometry,
                lambda p: self._prop_slot_for_geom(h, p, self.geometry),
            )
        except (ValueError, IndexError):
            return False
        slot = int(self._slot[d])
        self.state = jax.tree.map(
            lambda x, s: x.at[slot].set(s), self.state, row
        )
        del self.quarantine[d]
        self.quarantine_reason.pop(d, None)
        self._readmit_due.pop(d, None)
        self._readmit_interval.pop(d, None)
        # The scattered row is fresh device truth: invalidate the verified
        # digest so the watchdog re-verifies it on the next sweep.
        self._verified_digest.pop(d, None)
        # The oracle state becomes the doc's new replay base: the dropped
        # poison ops are gone from both the state and the log.
        h.base_summary = summary
        h.base_seq = max(h.base_seq, h.last_seq)
        h.log = [m for m in h.log if m.seq > h.base_seq]
        self.counters.bump("readmissions")
        return True

    # ---------------------------------------------------- placement/migration
    def shard_of(self, doc_idx: int) -> int:
        """The mesh shard currently hosting this doc's device row."""
        return self.placement_plane.shard_of(doc_idx)

    def placement(self) -> dict[str, int]:
        """doc key -> mesh shard: the summary-ownership alignment surface
        (server.partition_manager.ScribePool.align_to_placement)."""
        return self.placement_plane.placement(self.doc_keys)

    def shard_load(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-shard (applied ops since the last ``hot_shards`` reset,
        currently queued ops) — see placement.shard_load."""
        return placement.shard_load(self)

    def hot_shards(
        self, factor: float = 2.0, reset: bool = False, load=None
    ) -> list[int]:
        """Shards whose load (applied + queued ops) exceeds ``factor`` x
        the fleet mean — see placement.hot_shards."""
        return placement.hot_shards(self, factor, reset, load)

    def free_slots(self, shard: int) -> int:
        return self.placement_plane.free_slots(shard)

    def migrate_doc(self, d: int, dst_shard: int) -> bool:
        # ckpt_lock: migration mutates self.state/self._slot, which the
        # background checkpoint sweep reads (bulk host transfer + per-doc
        # slot slicing) — an unlocked scatter mid-sweep could checkpoint
        # a torn or vacated row as the doc's durable record.
        with self.ckpt_lock:
            return self._migrate_doc_locked(d, dst_shard)

    def _migrate_doc_locked(self, d: int, dst_shard: int) -> bool:
        """Live doc migration between mesh shards (hot-shard rebalancing).

        The handoff is checkpoint + summary adoption — the same primitives
        the recovery and scribe paths trust: the doc's device row exports
        through ``kb.state_to_summary`` (the checkpoint codec), re-packs at
        the batch geometry with ``kb.summary_to_state``, and scatters into
        a free slot on the destination shard; the vacated slot retires to
        the pristine proto row.  Observable state (text, annotations,
        obliterate table, exported summary) is byte-identical before and
        after.  Host-side queues, retained logs, and checkpoint floors
        travel with the doc untouched — a doc may migrate MID-STREAM with
        staged ops pending; they simply apply at the new slot on the next
        step.  Raises ``placement.PlacementError`` for a doc pinned to a
        parallel lane (segment-sharded or overflow: its serving state
        lives outside the fleet slot, so a silent slot handoff would
        strand it — drain or demote first).  Returns False (doc stays
        put) when the doc is oracle/quarantine-routed, already on
        ``dst_shard``, poisoned, or the destination has no free slot.
        """
        plane = self.placement_plane
        plane.validate(d, dst_shard)
        plane.require_migratable(
            d,
            "segment" if d in self.seg_lanes
            else "overflow" if d in self.overflow else None,
        )
        if d in self.oracles or d in self.quarantine:
            return False
        reservation = plane.reserve(d, dst_shard)
        if reservation is None:
            return False
        src_slot, dst_slot = reservation
        src_shard = src_slot // self.docs_per_shard
        h = self.hosts[d]
        row = jax.tree.map(lambda x: np.asarray(x[src_slot]), self.state)
        if int(row.error):
            plane.release(dst_slot)
            return False  # recover first; never migrate a latched row
        self._sync_native_props(h)
        summary = kb.state_to_summary(
            row, {v: k for k, v in h.prop_slot.items()}
        )
        try:
            new_row = kb.summary_to_state(
                summary, self.geometry,
                lambda p: self._prop_slot_for_geom(h, p, self.geometry),
            )
        except (ValueError, IndexError):
            plane.release(dst_slot)
            return False  # does not re-pack at batch geometry: stay put
        self.state = jax.tree.map(
            lambda x, s: x.at[dst_slot].set(s), self.state, new_row
        )
        self.state = jax.tree.map(
            lambda x, s: x.at[src_slot].set(s), self.state, self._proto
        )
        plane.commit(d, src_slot, dst_slot)
        # Fresh row content (text pool re-packed): the watchdog must
        # re-verify before the pre-filter may skip this doc again.
        self._verified_digest.pop(d, None)
        self.counters.bump("doc_migrations")
        instant(
            "migrate_doc", doc=self.doc_keys[d], src=src_shard, dst=dst_shard
        )
        return True

    def rebalance_hot_shards(
        self, factor: float = 2.0, max_moves: int = 1
    ) -> list[tuple[int, int, int]]:
        """Detect hot shards and live-migrate their deepest-queued docs to
        the coldest shards with free slots (one checkpoint + summary-
        adoption handoff per move — ``migrate_doc``).  Returns the
        ``(doc, src_shard, dst_shard)`` moves made; callers re-align the
        scribe pool afterwards (``ScribePool.align_to_placement``) so
        summary ownership follows the docs.  A shard hot because of ONE doc
        whose own queue exceeds the fleet mean cannot be rebalanced by
        placement; with a segs axis available that doc is promoted to the
        segment-parallel path instead and appears in the result with
        ``dst_shard == -1`` (its placement slot stays reserved).  The
        detection + move-selection skeleton is the shared plane's
        (placement.rebalance_hot_shards — the tree fleet rides the same
        one); the segment-parallel promotion of hot DOCUMENTS is this
        engine's hook into it."""
        return placement.rebalance_hot_shards(
            self, self.placement_plane, factor, max_moves,
            in_lane=self._in_lane,
            promote_hot_doc=(
                self.enable_segment_sharding if self.seg_shards > 1
                else None
            ),
        )

    def _sync_native_props(self, h: _DocHost) -> None:
        """Fold the native encoder's C++ prop-interning table into the host
        table, so checkpoints and migrations of native-mode docs carry REAL
        property ids instead of private kernel slot numbers (ROADMAP:
        native-path checkpoint fidelity).  No-op for object-path docs;
        safe to call repeatedly —
        both tables intern in first-seen stream order, so entries agree."""
        if h.native is None:
            return
        for prop, slot in h.native.prop_table().items():
            cur = h.prop_slot.setdefault(prop, slot)
            if cur != slot:
                raise RuntimeError(
                    f"native/host prop table skew: id {prop} -> {slot} vs {cur}"
                )

    # --------------------------------------------------------------- watchdog
    def watchdog(self, sample: int | None = None) -> list[int]:
        """Cross-check a rotating sample of batch docs against a host-oracle
        replay of checkpoint + tail; quarantine (oracle wins) on mismatch.
        Returns the doc indices that failed the check."""
        if self.recovery == "off":
            return []
        eligible = [
            d for d in range(self.n_docs)
            if not (
                d in self.overflow or d in self.oracles or d in self.quarantine
            )
            and not (d in self.seg_lanes and self.seg_lanes[d].queue)
            and self.hosts[d].mode == "obj"
            and not self.hosts[d].queue
        ]
        if eligible:
            # Device-digest pre-filter: one [D] device reduction per sweep
            # (NOT per step — it blocks on a device->host transfer).  A doc
            # whose digest AND ingested seq both match its last PASSED
            # check cannot have diverged since — skip its host-oracle
            # replay entirely (counted).
            self._digests = np.asarray(_fleet_digest(self.state))
            drifted = []
            for d in eligible:
                if d in self.seg_lanes:
                    # A segment lane's state lives off the batch rows, so
                    # the slot digest is pristine-stale; the lane's host-
                    # side version stamp (bumped at every dispatch/
                    # rebalance/compact) vouches instead — without it every
                    # sweep would oracle-replay exactly the fleet's
                    # longest-log docs.
                    mark = (
                        "seg", self.seg_lanes[d].version,
                        self.hosts[d].last_seq,
                    )
                else:
                    mark = (
                        int(self._digests[int(self._slot[d])]),
                        self.hosts[d].last_seq,
                    )
                if self._verified_digest.get(d) == mark:
                    self.counters.bump("watchdog_prefiltered")
                else:
                    drifted.append(d)
            eligible = drifted
        if not eligible:
            return []
        k = sample if sample is not None else self.watchdog_sample
        start = self._watchdog_cursor
        picks = [eligible[(start + i) % len(eligible)] for i in range(min(k, len(eligible)))]
        self._watchdog_cursor = (start + len(picks)) % max(len(eligible), 1)
        failed: list[int] = []
        for d in picks:
            h = self.hosts[d]
            try:
                tree = self._oracle_from_base(h)
                for msg in h.log:
                    self._oracle_apply(tree, h, msg)
                expected = tree.visible_text()
            except Exception:
                # The oracle replay itself failing means the log carries an
                # op the strict host path rejects — that is the quarantine
                # lane's job, not the watchdog's verdict to fake.
                self._quarantine_doc(d, "watchdog: oracle replay failed")
                failed.append(d)
                continue
            self.counters.bump("watchdog_checks")
            if mk.visible_text(self.doc_state(d)) != expected:
                self.counters.bump("watchdog_mismatches")
                self._quarantine_doc(d, "watchdog: device/oracle divergence")
                failed.append(d)
            elif d in self.seg_lanes:
                # Passed: pin the lane's host-side change mark so the next
                # sweep skips this doc until a dispatch/rebalance/compact
                # moves its state or the stream advances.
                self._verified_digest[d] = (
                    "seg", self.seg_lanes[d].version,
                    self.hosts[d].last_seq,
                )
            elif self._digests is not None:
                # Passed: pin (digest, seq) so the pre-filter can skip this
                # doc until its device state or ingested stream moves.
                self._verified_digest[d] = (
                    int(self._digests[int(self._slot[d])]),
                    self.hosts[d].last_seq,
                )
        return failed

    # ------------------------------------------------------------- checkpoint
    def maybe_checkpoint(self, force: bool = False, docs=None) -> list[int]:
        """Write durable checkpoint records for docs whose op count since
        the last checkpoint reached ``checkpoint_every`` (all dirty docs
        when ``force``), then truncate their replay logs to the tail.
        ``docs`` restricts the sweep to an explicit due list (the
        bounded-staleness writer's candidates) — those checkpoint whenever
        dirty, regardless of cadence.  Takes ``ckpt_lock`` for the record
        build only; callers must NOT hold it across this call (step()
        invokes it after its serving hold releases).  Returns the doc
        indices checkpointed."""
        if self.checkpoint_store is None:
            return []
        if docs is None and not force and self.checkpoint_every <= 0:
            return []
        with self.ckpt_lock:
            out, pending = self._checkpoint_sweep(force, docs)
        # Durable writes (one fsync per record) land OUTSIDE ckpt_lock —
        # for every caller: the background writer's sweeps and, since the
        # step() call site moved below its lock hold, the serving
        # thread's own cadence checkpoints too (fftpu-check
        # blocking-under-lock enforces this: ckpt_lock denies fsync).
        write_checkpoint_records(self, pending, "batch")
        return out

    def checkpoint_stale(
        self, max_ops_behind: int = 0, max_seconds_behind: float = 0.0
    ) -> list[int]:
        """Bounded-staleness delta sweep: checkpoint every dirty doc whose
        durable record is ``max_ops_behind`` applied ops or
        ``max_seconds_behind`` seconds behind the live stream (0 disables
        that bound).  Safe from a background thread — the record BUILD
        runs under ``ckpt_lock`` so it only ever observes op boundaries;
        the durable writes land after release so the sweep's fsyncs never
        stall the serving thread.  Returns the doc indices checkpointed."""
        if self.checkpoint_store is None or not (
            max_ops_behind or max_seconds_behind
        ):
            return []
        now = time.monotonic()
        with self.ckpt_lock:
            due = stale_due_docs(
                self.hosts, self.n_docs, max_ops_behind,
                max_seconds_behind, now,
            )
            if not due:
                return []
            with span("checkpoint_sweep", docs=len(due)):
                out, pending = self._checkpoint_sweep(force=False, docs=due)
            if out:
                self.counters.bump("stale_checkpoints_written", len(out))
        write_checkpoint_records(self, pending, "batch")
        return out

    def _checkpoint_sweep(
        self, force: bool, docs
    ) -> tuple[list[int], list[tuple[int, int, dict]]]:
        """Build-and-account half of a checkpoint sweep (under
        ``ckpt_lock``); the returned ``pending`` records go to
        ``_write_checkpoint_records`` after release."""
        candidates = range(self.n_docs) if docs is None else docs
        due = [
            d for d in candidates
            if self.hosts[d].ops_since_ckpt > 0
            and (
                force or docs is not None
                or self.hosts[d].ops_since_ckpt >= self.checkpoint_every
            )
        ]
        if not due:
            return [], []  # host-side check only: no device readback paid
        out: list[int] = []
        pending: list[tuple[int, int, dict]] = []
        # ONE bulk device->host transfer covers every due batch doc (the
        # per-doc summary walk below then slices host arrays; per-doc
        # device_get would serialize ~25 tiny transfers per doc against
        # the step pipeline).
        host_state = (
            jax.tree.map(np.asarray, self.state)
            if any(
                d not in self.quarantine
                and d not in self.oracles
                and d not in self.overflow
                and d not in self.seg_lanes
                for d in due
            )
            else None
        )
        err = np.asarray(host_state.error) if host_state is not None else None
        for d in due:
            h = self.hosts[d]
            if (
                h.queue
                or (d in self.overflow and self.overflow[d].queue)
                or (d in self.seg_lanes and self.seg_lanes[d].queue)
            ):
                continue  # staged-but-unapplied ops: state is mid-step
            lane = "batch"
            geometry = None
            if d in self.seg_lanes:
                # A segment lane checkpoints through the same summary codec
                # as everything else (gather the live prefixes first).  The
                # record restores as a batch row — or the fitted-overflow
                # path when it outgrew the batch geometry — and the
                # supervisor re-promotes if the doc is still hot.
                ln = self.seg_lanes[d]
                seg_host = jax.tree.map(np.asarray, ln.state)
                if int(seg_host.error):
                    continue  # never checkpoint a latched lane
                self._sync_native_props(h)
                summary = kb.state_to_summary(
                    mk.seg_gather_state(seg_host),
                    {v: k for k, v in h.prop_slot.items()},
                )
            elif d in self.quarantine:
                lane = "quarantine"
                summary = self.quarantine[d].export_summary()
            elif d in self.oracles:
                lane = "oracle"
                summary = self.oracles[d].export_summary()
            elif d in self.overflow:
                lane = "overflow"
                ln = self.overflow[d]
                if int(ln.state.error):
                    continue
                geometry = ln.geometry
                growths = ln.growths
                summary = kb.state_to_summary(
                    ln.state, {v: k for k, v in h.prop_slot.items()}
                )
            else:
                slot = int(self._slot[d])
                if err[slot]:
                    continue  # never checkpoint a poisoned row
                self._sync_native_props(h)
                summary = kb.state_to_summary(
                    jax.tree.map(lambda x, _s=slot: x[_s], host_state),
                    {v: k for k, v in h.prop_slot.items()},
                )
            record = {
                "engine": "doc_batch",
                "lane": lane,
                "summary": summary,
                "quorum": h.quorum,
                "prop_slot": {str(k): v for k, v in h.prop_slot.items()},
                "min_seq": h.min_seq,
                "mode": h.mode,
            }
            if geometry is not None:
                record["geometry"] = geometry
                record["growths"] = growths
            pending.append((d, h.last_seq, record))
            h.base_seq = h.last_seq
            h.base_summary = summary
            h.log = [m for m in h.log if m.seq > h.base_seq]
            if h.raw_log:
                h.raw_log = self._truncate_raw_log(h.raw_log, h.base_seq)
            h.ops_since_ckpt = 0
            h.dirty_since = 0.0
            h.boot_counting = False  # a new durable floor ends the boot phase
            self.counters.bump("checkpoints_written")
            out.append(d)
        return out, pending

    @staticmethod
    def _truncate_raw_log(raw_log: list[bytes], base_seq: int) -> list[bytes]:
        """Drop raw wire OP lines already covered by the checkpoint.  JOIN
        lines are retained regardless of seq: a later recovery replay
        rebuilds the quorum from them (_normalize_native), and a native
        doc's checkpoint record carries no parsed quorum to fall back on."""
        kept: list[bytes] = []
        for chunk in raw_log:
            lines = []
            for line in chunk.split(b"\n"):
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                    if (
                        rec.get("type") == MessageType.JOIN
                        or int(rec.get("sequenceNumber", 0)) > base_seq
                    ):
                        lines.append(line)
                except ValueError:
                    lines.append(line)
            if lines:
                kept.append(b"\n".join(lines) + b"\n")
        return kept

    def note_incident(self, started_at: float) -> None:
        """Back-date the current recovery incident to the supervisor's
        kill timestamp (``time.monotonic`` domain): the recovery histogram
        then measures kill -> first post-restore op applied, not merely
        restore -> applied."""
        self.recovery_tracker.begin(started_at)

    def restore_from_checkpoints(
        self,
        store=None,
        parallel: bool = True,
        max_workers: int | None = None,
        refresh: bool = False,
    ) -> list[int]:
        """Engine restart path: load each doc's durable checkpoint record,
        rebuild its state (batch row, overflow lane, or oracle/quarantine
        replica), and set the seq floor so the upstream replay of ops the
        checkpoint already covers is skipped.  Returns restored doc
        indices.

        ``parallel`` (default) is the batched fast path: all records load
        concurrently (thread pool over the store's ``load_many``) and
        every batch-lane doc seeds through ONE stacked host build + ONE
        scatter dispatch instead of a per-doc device round-trip.
        ``parallel=False`` is the sequential oracle — per-doc load,
        per-doc scatter, the original restore loop — kept byte-identical
        by contract (fuzzed in tests/test_recovery_plane.py).

        ``refresh`` is the warm-standby trailing mode: docs already
        restored RE-adopt a record strictly newer than their current seq
        floor (first-source-wins still holds for live serving — refresh
        refuses any doc with staged work).  A trailing standby calls this
        on a cadence so promotion starts from the freshest durable state.
        """
        store = store if store is not None else self.checkpoint_store
        if store is None:
            return []
        with self.ckpt_lock:
            return self._restore(store, parallel, max_workers, refresh)

    def _restore(self, store, parallel, max_workers, refresh) -> list[int]:
        t_start = time.monotonic()
        with span("restore_scan", docs=self.n_docs):
            # First-boot vs trailing/re-seed candidate selection is the
            # shared plane's (placement.restore_candidates): first source
            # wins for live serving, trailing never races staged work,
            # unchanged record files skip on one mtime stat per doc.
            candidates, cand_mtime = placement.restore_candidates(
                self, store, refresh, self._queue_depth
            )
        if not candidates:
            return []
        records = load_checkpoint_records(
            store, [self.doc_keys[d] for d in candidates],
            parallel=parallel, max_workers=max_workers,
        )
        restored: list[int] = []
        # Batch-lane rows collected host-side for the single scatter
        # (parallel path); the sequential oracle scatters per doc instead.
        batch_rows: list[tuple[int, object]] = []
        with span("restore_build", records=len(records)):
            for i, d in enumerate(candidates):
                rec = records.get(i)
                if rec is not None and d in cand_mtime:
                    # Load succeeded: this record content is now seen —
                    # future trails skip it until the file changes.
                    self._trail_mtime[d] = cand_mtime[d]
                if rec is None or rec.get("engine") != "doc_batch":
                    continue
                h = self.hosts[d]
                if refresh and h.restored:
                    if int(rec["seq"]) <= h.last_seq:
                        continue  # nothing newer to adopt
                    self.counters.bump("checkpoint_refreshes")
                if refresh:
                    self._drop_restored_identity(d)
                h.quorum = dict(rec.get("quorum", {}))
                h.prop_slot = {
                    int(k): v for k, v in rec.get("prop_slot", {}).items()
                }
                h.min_seq = rec.get("min_seq", 0)
                h.base_seq = h.last_seq = int(rec["seq"])
                h.base_summary = rec["summary"]
                # Restored docs consume parsed messages (the object path):
                # the native encoder cannot skip already-checkpointed seqs.
                h.mode = "obj"
                h.restored = True
                h.boot_counting = True
                lane = rec.get("lane", "batch")
                if lane in ("oracle", "quarantine"):
                    tree = RefMergeTree()
                    tree.import_summary(rec["summary"])
                    tree.update_min_seq(h.min_seq)
                    if lane == "oracle":
                        self.oracles[d] = tree
                    else:
                        self.quarantine[d] = tree
                        self.quarantine_reason[d] = "restored"
                        if self.readmit_after_steps:
                            # A restart must not strand the doc in
                            # quarantine when auto-readmission is the
                            # configured policy: schedule it like a first
                            # flap.
                            self._flaps.setdefault(d, 1)
                            self._readmit_interval[d] = (
                                self.readmit_after_steps
                            )
                            self._readmit_due[d] = (
                                self._step_count + self.readmit_after_steps
                            )
                elif lane == "overflow":
                    geom = {k: int(v) for k, v in rec["geometry"].items()}
                    state = kb.summary_to_state(
                        rec["summary"], geom,
                        lambda p, _h=h, _g=geom: self._prop_slot_for_geom(
                            _h, p, _g
                        ),
                    )
                    self.overflow[d] = self._make_lane(
                        state, geom, int(rec.get("growths", 1))
                    )
                else:
                    try:
                        row = kb.summary_to_state_host(
                            rec["summary"], self.geometry,
                            lambda p, _h=h: self._prop_slot_for_geom(
                                _h, p, self.geometry
                            ),
                        )
                    except (ValueError, IndexError):
                        # The checkpoint outgrew the batch geometry (a
                        # restart with smaller capacity — including fewer
                        # prop slots than the restored prop table):
                        # restore into an overflow lane at a fitted
                        # geometry.
                        geom = self._fit_geometry(
                            self.geometry, rec["summary"], len(h.prop_slot)
                        )
                        state = kb.summary_to_state(
                            rec["summary"], geom,
                            lambda p, _h=h, _g=geom: self._prop_slot_for_geom(
                                _h, p, _g
                            ),
                        )
                        self.overflow[d] = self._make_lane(state, geom, 1)
                    else:
                        slot = int(self._slot[d])
                        if parallel:
                            batch_rows.append((slot, row))
                        else:
                            self.state = jax.tree.map(
                                lambda x, s, _s=slot: x.at[_s].set(
                                    jnp.asarray(s)
                                ),
                                self.state, row,
                            )
                restored.append(d)
                self.counters.bump("docs_restored")
        if batch_rows:
            # ONE stacked transfer + ONE donated scatter dispatch seeds
            # every batch-lane doc (pow2-padded like the cohort scatter,
            # so the executable ladder stays log2(fleet) deep; pad lanes
            # route out of bounds via mode="drop").
            with span("restore_scatter", rows=len(batch_rows)):
                n = len(batch_rows)
                nc = 1 << (n - 1).bit_length()
                idx = np.full((nc,), batch_rows[-1][0], np.int32)
                idx[:n] = [s for s, _ in batch_rows]
                valid = np.zeros((nc,), bool)
                valid[:n] = True
                rows = [r for _, r in batch_rows]
                rows += [batch_rows[-1][1]] * (nc - n)
                stacked = jax.tree.map(
                    lambda *xs: jnp.asarray(np.stack(xs)), *rows
                )
                self.state = self._scatter_cohort(
                    self.state, stacked, jnp.asarray(idx), jnp.asarray(valid)
                )
        if restored and not refresh:
            # A real restore (not standby trailing) opens a recovery
            # incident: the clock runs until the first post-restore op
            # applies on device.  note_incident() back-dates it to the
            # supervisor's kill time when one is known.
            self.recovery_tracker.begin(t_start)
        return restored

    def adopt_boot_snapshot(
        self, doc_idx: int, record: dict
    ) -> placement.AdoptResult:
        """Client half of the fan-out plane's ``{"t":"resync","boot":true}``
        contract (the shared orchestration — placement.adopt_boot_snapshot —
        riding this engine's refresh re-seed path): a consumer that fell
        off the retained log re-seeds the document from a historian
        snapshot record (the scribe summary schema, ``engine: doc_batch``)
        and re-consumes from the returned floor; lanes, quorum, prop
        tables and the replay floor all reset consistently."""
        return placement.adopt_boot_snapshot(
            self, doc_idx, record, self._clear_staged
        )

    def _clear_staged(self, doc_idx: int) -> None:
        """Drop a doc's staged pre-gap work ahead of a boot-snapshot
        adoption: the refresh guard refuses docs with pending ops
        (trailing must not race serving), but a boot resync REPLACES the
        doc — pre-gap rows are covered by the snapshot."""
        self.hosts[doc_idx].queue.clear()
        for lane in (self.overflow.get(doc_idx),
                     self.seg_lanes.get(doc_idx)):
            if lane is not None:
                lane.queue.clear()
        self._busy.discard(doc_idx)

    def _drop_restored_identity(self, d: int) -> None:
        """Forget a doc's prior adoption before a refresh re-seed (warm-
        standby trailing only: the doc has no staged work by contract)."""
        self.overflow.pop(d, None)
        self.oracles.pop(d, None)
        self.quarantine.pop(d, None)
        self.quarantine_reason.pop(d, None)
        self.seg_lanes.pop(d, None)
        self._readmit_due.pop(d, None)
        self._readmit_interval.pop(d, None)
        self._verified_digest.pop(d, None)
        h = self.hosts[d]
        h.log.clear()
        h.raw_log.clear()
        h.queue.clear()
        self._busy.discard(d)

    def warmup(self) -> int:
        """Pre-compile the fleet's serving programs (warm-standby boot):
        dispatch all-NOOP megasteps at every pow2 depth up to
        ``megastep_k`` plus one compact through the exact serving entry
        points, so a promoted standby pays ZERO XLA compiles on its first
        real dispatch.  NOOP slices are identity by kernel contract, so
        state bytes are untouched.  Mesh-less fleets also get every cohort
        size at K = 1 (a cohort megastep, K > 1, still compiles on first
        use) and the cohort compaction at 8, 4, 2 and 1 lanes.  Returns the
        number of warmup dispatches run."""
        warmed = 0
        with self.ckpt_lock, span("warmup", k_max=self.megastep_k):
            stage = self._staging()
            if self.mesh is None:
                # The K=1 mesh-less fast path dispatches _step directly.
                ops, payloads = stage.acquire(1, self.capacity)
                dev_ops, dev_payloads = stage.upload(ops[0], payloads[0])
                self.state = self._step(self.state, dev_ops, dev_payloads)
                self._full_built = True
                warmed += 1
            if self.bucketing and self.capacity >= 4:
                # Every cohort size at K = 1 (gather, step, masked scatter
                # that keeps no lane): serving builds none of them once the
                # fleet-wide program above exists (_cold_and_shallow).
                lanes = 1
                while lanes <= self._cohort_lanes(self.capacity // 4):
                    ops, payloads = stage.acquire(1, lanes)
                    with_stack_room(
                        self._cohort_trio,
                        np.zeros((lanes,), np.int32), np.zeros((lanes,), bool),
                        ops, payloads,
                    )
                    warmed += 1
                    lanes *= 2
            depths = []
            k = 1
            while k <= self.megastep_k:
                depths.append(k)
                k *= 2
            if self.megastep_k > 1 and self.megastep_k not in depths:
                # _select_k clamps to min(megastep_k, pow2(need)), so a
                # non-pow2 configured K is itself a reachable dispatch
                # shape — skip it here and the first deep-queue dispatch
                # after promotion pays the compile warmup exists to kill.
                depths.append(self.megastep_k)
            for k in depths:
                if self.mesh is not None or k > 1:
                    ops, payloads = stage.acquire(k, self.capacity)
                    dev_ops, dev_payloads = stage.upload(ops, payloads)
                    self.state = self._megastep(
                        self.state, dev_ops, dev_payloads
                    )
                    warmed += 1
            mins = np.zeros((self.capacity,), np.int32)
            for d, h in enumerate(self.hosts):
                mins[self._slot[d]] = h.min_seq
            if self.mesh is not None:
                mins_dev = jax.device_put(mins, pm.shard_docs(self.mesh))
            else:
                mins_dev = jnp.asarray(mins)
            self.state = self._compact(self.state, mins_dev)
            warmed += 1
            if self.bucketing:
                # The cohort compaction, largest size first (serving goes
                # out in built sizes): rows at their own floors, so zamboni
                # finds nothing a fleet-wide one would not have dropped.
                lanes = min(8, self._cohort_lanes(max(1, self.capacity // 4)))
                while lanes:
                    idx = np.zeros((lanes,), np.int32)
                    self._dispatch_compact_cohort(idx, mins[idx])
                    warmed += 1
                    lanes //= 2
            jax.block_until_ready(self.state)
            # Absorb the warmup compiles into the watchdog count NOW, so
            # they show up as boot-time cache growth rather than landing
            # on the first serving step's poll.
            self.recompile_watchdog.poll()
        self.counters.gauge("warmup_dispatches", warmed)
        return warmed

    # ----------------------------------------------------------------- health
    def health(self) -> dict:
        """Per-engine degraded-mode health counters (bench + fleet status)."""
        ages = [
            h.last_seq - h.base_seq for h in self.hosts if h.last_seq
        ]
        # Megastep pipeline surface: configured depth, realized dispatch
        # amortization, and how often the double buffer actually overlapped
        # a pack with in-flight device work.
        self.counters.gauge("megastep_k", self.megastep_k)
        self.counters.gauge(
            "staging_overlap_packs",
            self._stage.overlapped_packs if self._stage is not None else 0,
        )
        self.counters.gauge(
            "staging_aliased_swaps",
            self._stage.aliased_swaps if self._stage is not None else 0,
        )
        self.counters.ratio(
            "steps_per_dispatch", "megastep_slices", "megastep_dispatches"
        )
        # Flow-control surface (graceful degradation; shared shape with
        # the tree engine via OverloadGate.emit_gauges).
        self.overload_gate.emit_gauges(
            self.counters, self.megastep_k * self.ops_per_step,
            max(
                (
                    self._queue_depth(d)
                    for d in self._busy | set(self.seg_lanes)
                    | set(self.overflow)
                ),
                default=0,
            ),
        )
        # Mesh/placement surface: per-shard load for hot-shard detection
        # (applied since the last hot_shards reset + queued right now).
        self.counters.gauge("n_shards", self.n_shards)
        # 2-D docs x segs surface: the segs-axis width, how many hot docs
        # are segment-sharded right now, and the per-shard live-segment
        # occupancy across all lanes (the rebalance trigger signal).
        # seg_promotions / seg_demotions / seg_rebalances counters ride the
        # snapshot; everything here reaches fleet status and /metrics.
        self.counters.gauge("segment_shards", self.seg_shards)
        self.counters.gauge("segment_sharded_docs", len(self.seg_lanes))
        if self.seg_lanes:
            occ = np.zeros((self.seg_shards,), np.int64)
            for lane in self.seg_lanes.values():
                occ += mk.seg_occupancy(lane.state)
            self.counters.gauge("seg_occupancy", [int(v) for v in occ])
            self.counters.gauge(
                "seg_lane_rebalances",
                sum(lane.rebalances for lane in self.seg_lanes.values()),
            )
        elif self.seg_shards > 1:
            # Gauges persist in the snapshot: zero them once the last lane
            # demotes, or a supervisor alarming on occupancy skew keeps
            # seeing the final promoted-state values forever.
            self.counters.gauge(
                "seg_occupancy", [0] * self.seg_shards
            )
            self.counters.gauge("seg_lane_rebalances", 0)
        if self.n_shards > 1:
            ops, depth = self.shard_load()
            self.counters.gauge("shard_ops", [int(v) for v in ops])
            self.counters.gauge(
                "shard_row_slots_scanned",
                [int(v) for v in self._shard_row_slots],
            )
            self.counters.gauge(
                "shard_queue_depth", [int(v) for v in depth]
            )
            self.counters.gauge(
                "hot_shards", self.hot_shards(load=ops + depth)
            )
        # Observability surface: program cache misses (recompiles, warmup
        # included), growth after first specialization (despecializations,
        # the mid-serve alarm), and op e2e latency (the op clock's
        # sequencer stamp -> applied-on-device), ms percentiles.
        self.counters.gauge("recompiles", self.recompile_watchdog.recompiles)
        self.counters.gauge(
            "despecializations", self.recompile_watchdog.despecializations
        )
        self.op_clock.emit_gauges(self.counters)
        # Recovery surface: per-incident recovery percentiles plus how far
        # the durable checkpoints trail the live stream right now (the
        # bounded-staleness writer's target signal).
        self.recovery_tracker.emit_gauges(self.counters)
        now = time.monotonic()
        self.counters.gauge(
            "dirty_docs",
            sum(1 for h in self.hosts if h.ops_since_ckpt > 0),
        )
        self.counters.gauge(
            "checkpoint_age_s",
            round(
                max(
                    (now - h.dirty_since for h in self.hosts
                     if h.dirty_since),
                    default=0.0,
                ),
                3,
            ),
        )
        from ..native import ingest_native

        snap = self.counters.snapshot()
        snap.update(
            # Which step path ran (slices): fleet-wide vs bucketed cohort.
            full_steps=self.full_steps,
            cohort_steps=self.cohort_steps,
            # Which wire decoder fed ingest_lines (native/ingest.cpp or
            # the per-message Python decode).
            ingest_plane=ingest_native.fed_plane(
                snap.get("ingest_native_chunks", 0),
                snap.get("ingest_python_chunks", 0),
                ingest_native.loaded(),
            ),
            quarantined_docs=len(self.quarantine),
            overflow_docs=len(self.overflow),
            oracle_docs=len(self.oracles),
            checkpoint_age_seqs=max(ages, default=0),
            retained_log_msgs=sum(len(h.log) for h in self.hosts),
            quarantine_flaps=sum(self._flaps.values()),
            readmits_scheduled=len(self._readmit_due),
        )
        return snap

    # ------------------------------------------------------------------ views
    def doc_state(self, doc_idx: int) -> mk.DocState:
        if doc_idx in self.seg_lanes:
            # Gather the per-shard live prefixes back into the canonical
            # single-doc layout (byte-identical to what the single-lane
            # kernel would hold — the seg path's oracle contract).
            return mk.seg_gather_state(
                jax.tree.map(np.asarray, self.seg_lanes[doc_idx].state)
            )
        if doc_idx in self.overflow:
            return self.overflow[doc_idx].state
        slot = int(self._slot[doc_idx])
        return jax.tree.map(lambda x: x[slot], self.state)

    def text(self, doc_idx: int) -> str:
        if doc_idx in self.quarantine:
            return self.quarantine[doc_idx].visible_text()
        if doc_idx in self.oracles:
            return self.oracles[doc_idx].visible_text()
        return mk.visible_text(self.doc_state(doc_idx))

    def texts(self) -> list[str]:
        """Every document's text, doc-indexed.  The batch state comes to
        the host in ONE transfer per column and is sliced there: reading
        ``text(d)`` per document costs ~30 small device slices plus their
        transfers each, which at fleet size is minutes of readback for
        what one bulk copy does in about a second."""
        host = jax.tree.map(np.asarray, self.state)
        off_batch = (
            set(self.quarantine) | set(self.oracles)
            | set(self.overflow) | set(self.seg_lanes)
        )
        out = []
        for d in range(self.n_docs):
            if d in off_batch:
                out.append(self.text(d))
            else:
                slot = int(self._slot[d])
                out.append(mk.visible_text(
                    jax.tree.map(lambda x, _s=slot: x[_s], host)
                ))
        return out

    def annotations(self, doc_idx: int) -> list[dict[int, int]]:
        if doc_idx in self.quarantine:
            return self.quarantine[doc_idx].annotations()
        if doc_idx in self.oracles:
            return self.oracles[doc_idx].annotations()
        raw = mk.annotations(self.doc_state(doc_idx))
        # Live native-path docs intern props in C++: fold the table in so
        # the view names real prop ids (same sync the checkpoint takes).
        self._sync_native_props(self.hosts[doc_idx])
        inv = {v: k for k, v in self.hosts[doc_idx].prop_slot.items()}
        return [{inv[p]: v for p, v in d.items()} for d in raw]

    def errors(self) -> np.ndarray:
        """Combined per-doc error vector across batch, lanes, and oracles.
        Quarantined docs read 0: they are isolated and serviceable — their
        degraded state surfaces through ``health()``, not as a latched
        error that would fail a convergence sweep."""
        by_slot = np.asarray(self.state.error)
        err = np.zeros((self.capacity,), by_slot.dtype)
        err[: self.n_docs] = by_slot[self._slot]  # doc-indexed view
        for d, lane in self.overflow.items():
            err[d] = int(lane.state.error)
        for d, lane in self.seg_lanes.items():
            err[d] = int(np.asarray(lane.state.error))
        for d in self.oracles:
            err[d] = 0
        for d in self.quarantine:
            err[d] = 0
        return err
