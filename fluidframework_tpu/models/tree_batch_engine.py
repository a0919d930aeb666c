"""TreeBatchEngine: batched sequenced tree-edit application across documents.

The SharedTree analog of ``doc_batch_engine``: D tree documents, each with
its own totally-ordered edit stream, stepped in lockstep device batches.

Host/device split (the seam SURVEY §7 step 7 names):

- host: per-doc EditManager runs the deterministic trunk translation
  (dds/tree/editmanager.py) — rebase is control-plane work over tiny mark
  lists; the result is a TRUNK-COORDINATE commit every replica agrees on.
- device: the forest state as NESTED columnar rows — (parent, field,
  index) SoA beside the value column (ops/tree_kernel.py
  NestedForestState; ref chunked-forest/uniformChunk.ts:42 generalized) —
  applying trunk commits as masked column arithmetic with bounded-depth
  path resolution.

The device path covers nested shapes end to end (VERDICT r3 next #3) and
mixed-type leaves (VERDICT r4 next #2): int/bool values inline in the
value column, str/float values in a per-doc append-only word pool
addressed by (offset, vlen) — the merge-tree kernel's text-pool pattern.
Only genuinely irregular commits fall back to a host Forest replica:
paths deeper than the kernel's MAX_PATH, split/cross-field moves or
moves mixed with other structural marks in one field, out-of-range ints,
and leaf values wider than one payload row — the same route-to-oracle
policy as the string engine.
"""

from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..dds.tree.changeset import (
    Insert,
    Modify,
    MoveIn,
    MoveOut,
    NodeChange,
    Remove,
    Skip,
    apply_commit,
    commit_from_json,
)
from ..dds.tree.editmanager import EditManager
from ..dds.tree.mark_pool import MarkPool
from ..dds.tree.mark_pool import pool_commit_from_json as _pool_commit_from_json
from ..dds.tree.field_kinds import OptionalChange
from ..dds.tree.forest import ROOT_FIELD, Forest, Node
from ..observability import OpClock, RecompileWatchdog, instant, span
from ..ops import tree_kernel as tk
from ..parallel import mesh as pm
from . import placement
from ..protocol.messages import MessageType, SequencedMessage
from ..utils.telemetry import HealthCounters
from .recovery import (
    RecoveryTracker,
    load_checkpoint_records,
    stale_due_docs,
    write_checkpoint_records,
)
from .staging import OverloadGate, RowQueue, StagingRing


@dataclass
class _TreeHost:
    em: EditManager = field(default_factory=EditManager)
    # Columnar pending op rows (see staging.RowQueue): flattened edits land
    # as row blocks, the drain consumes slice copies.
    queue: RowQueue = None
    # Trunk-coordinate commit suffix since ``checkpoint`` (replay source for
    # fallback routing); folded into the checkpoint forest every
    # CHECKPOINT_EVERY commits so host memory stays bounded.
    trunk_log: list[list] = field(default_factory=list)
    checkpoint: Forest = field(default_factory=Forest)
    device_commits: int = 0
    total_commits: int = 0
    # Durable-checkpoint floor (ops at or below base_seq are covered by the
    # stored record; a restarted consumer's replay of them is skipped).
    base_seq: int = 0
    last_seq: int = 0
    ops_since_ckpt: int = 0
    # Monotonic time the doc first went dirty after its last durable
    # checkpoint (0.0 = clean): the bounded-staleness writer's signal.
    dirty_since: float = 0.0
    # Set by restore_from_checkpoints: tail ops this doc applies are a
    # boot replay (counted as boot_replay_len in health until the first
    # post-boot checkpoint ends the boot phase).
    restored: bool = False
    boot_counting: bool = False


class UnsupportedShape(Exception):
    """A commit the columnar path cannot express."""


class _FlattenCollector:
    """One walk per trunk commit collects the structural KEY (field path /
    kind / payload arity — everything that determines row layout) and the
    DYNAMIC scalars (path indices, positions, counts, destinations,
    values, payload words).  A commit whose key was seen before skips all
    per-row numpy work: its cached _TranslationPlan turns the dynamics
    into row blocks with two vectorized fills (steady-state translation
    is a fill, not a walk)."""

    __slots__ = ("key", "dyn", "pay")

    _PTAG = {"v": 1, "w": 2, "r": 3}

    def __init__(self) -> None:
        self.key: list[tuple] = []
        self.dyn: list[int] = []
        # Per-row payload spec: None | ('v', val) | ('w', words) | ('r', vals)
        self.pay: list[tuple | None] = []

    def reset(self) -> None:
        self.key.clear()
        self.dyn.clear()
        self.pay.clear()

    def emit(self, kind, steps, fld, pos=0, count=0, dst=0, value=0,
             vkind=0, ntype=0, payload=None):
        if len(steps) > tk.MAX_PATH:
            raise UnsupportedShape("path deeper than kernel MAX_PATH")
        ptag = 0 if payload is None else self._PTAG[payload[0]]
        plen = len(payload[1]) if ptag >= 2 else ptag
        self.key.append(
            (kind, fld, ptag, plen, vkind, ntype, len(steps))
            + tuple(f for f, _ in steps)
        )
        dyn = self.dyn
        for _f, i in steps:
            dyn.append(i)
        dyn.append(pos)
        dyn.append(count)
        dyn.append(dst)
        dyn.append(value)
        self.pay.append(payload)


class _TranslationPlan:
    """Cached row layout for one commit shape: a static template block
    plus the (row, col) scatter of every dynamic cell.  ``fill`` reuses
    the plan's own scratch blocks — callers copy them out immediately
    (RowQueue.extend_block does), so steady state allocates nothing."""

    __slots__ = (
        "template", "dyn_rows", "dyn_cols", "scratch_ops", "scratch_pay",
    )

    def __init__(self, key: tuple, payload_len: int) -> None:
        t = tk._TGT
        m = len(key)
        self.template = np.zeros((m, tk.NESTED_OP_FIELDS), np.int32)
        dyn_rows: list[int] = []
        dyn_cols: list[int] = []
        for r, (kind, fld, _ptag, _plen, vkind, ntype, depth, *fids) in enumerate(key):
            row = self.template[r]
            row[0] = kind
            row[2] = depth
            for k, f in enumerate(fids):
                row[3 + 2 * k] = f
            row[t] = fld
            row[t + 5] = vkind
            row[t + 6] = ntype
            # Dynamic cells, in collector emission order: path indices,
            # then pos / count / dst / value.
            for k in range(depth):
                dyn_rows.append(r)
                dyn_cols.append(4 + 2 * k)
            for col in (t + 1, t + 2, t + 3, t + 4):
                dyn_rows.append(r)
                dyn_cols.append(col)
        self.dyn_rows = np.asarray(dyn_rows, np.int64)
        self.dyn_cols = np.asarray(dyn_cols, np.int64)
        self.scratch_ops = np.empty_like(self.template)
        # Payload cells beyond each row's fixed arity stay zero forever
        # (arity is part of the key), so one zeroing at build time
        # suffices — every fill rewrites exactly the same cells.
        self.scratch_pay = np.zeros((m, payload_len), np.int32)

    def fill(self, dyn: list[int], pays: list, seq: int):
        ops = self.scratch_ops
        np.copyto(ops, self.template)
        ops[:, 1] = seq
        if self.dyn_rows.size:
            ops[self.dyn_rows, self.dyn_cols] = dyn
        pay = self.scratch_pay
        for r, spec in enumerate(pays):
            if spec is None:
                continue
            tag, data = spec
            if tag == "v":
                pay[r, 0] = data
            else:  # 'w' words / 'r' run values
                pay[r, : len(data)] = data
        return ops, pay


# Watermark-accounting kind sets (the scalar _block_upper fast path; the
# vectorized branch derives the same sets from tk directly).
_GROW_KINDS = (int(tk.NestedOpKind.INSERT), int(tk.NestedOpKind.REPLACE_FIELD))
_POOLED_KINDS = _GROW_KINDS + (int(tk.NestedOpKind.SET),)
_POOLED_VKINDS = tuple(int(p) for p in tk._POOLED)

# Module-level jitted programs: shared compile cache across engine
# instances (keyed by input shapes), instead of per-instance jit closures.

_tree_step_jit = functools.partial(jax.jit, donate_argnums=(0,))(
    tk.apply_nested_fleet
)
_tree_megastep_jit = functools.partial(jax.jit, donate_argnums=(0,))(
    tk.apply_nested_megastep
)
# Module-level body (stable identity: parallel.mesh caches its
# shard_map-wrapped mesh programs by function).
_tree_compact_body = jax.vmap(tk.compact_nested)
_tree_compact_jit = functools.partial(jax.jit, donate_argnums=(0,))(
    _tree_compact_body
)


class TreeBatchEngine:
    """A fleet of tree replicas: host EditManagers + nested device columns."""

    CHECKPOINT_EVERY = 64  # trunk-log fold threshold (bounds host memory)
    COMPACT_FRACTION = 0.75  # row watermark that triggers a device compact

    def __init__(
        self,
        n_docs: int,
        capacity: int = 1024,
        ops_per_step: int = 16,
        max_insert_len: int = 16,
        pool_capacity: int = 4096,
        mesh=None,
        checkpoint_store=None,
        checkpoint_every: int = 0,
        doc_keys: list[str] | None = None,
        megastep_k: int = 1,
        spare_slots: int = 0,
        plan_cache: bool = True,
        mark_pool: bool = True,
        device_rebase: bool = False,
        native_wire: bool = True,
        telemetry=None,
        overload_high_watermark: int = 0,
        overload_low_watermark: int = 0,
    ) -> None:
        self.n_docs = n_docs
        self.capacity = capacity
        self.pool_capacity = pool_capacity
        self.ops_per_step = ops_per_step
        self.max_insert_len = max_insert_len
        # Megastep depth cap (see doc_batch_engine): up to K [D, B] op
        # slices fuse into one donated dispatch; K=1 is the exact
        # per-slice path.
        self.megastep_k = max(1, megastep_k)
        # Ingest watermarks (same flow-control contract as the string
        # engine): pause a doc's feed at 8x the megastep budget, resume
        # once a dispatch's worth remains.
        budget = self.megastep_k * ops_per_step
        self.overload_gate = OverloadGate(
            high=overload_high_watermark or 8 * budget,
            low=overload_low_watermark or budget,
        )
        # Pooled columnar mark store (dds/tree/mark_pool.py): one pool is
        # shared by every doc's EditManager so occupancy/reuse gauges are
        # fleet-wide.  ``mark_pool=False`` keeps the object-mark fold —
        # the byte-identity fuzz oracle, same pattern as plan_cache.
        self.markpool = MarkPool() if mark_pool else None
        # Device rebase window (PR 19): one shared DeviceRebaser so the
        # fleet shares the field-interning table and the health gauges
        # (device_rebase_fraction / rebase_fallbacks), same pattern as
        # the shared MarkPool.  Requires the pooled fold.
        self.rebaser = None
        if device_rebase and self.markpool is not None:
            from ..dds.tree.device_rebase import DeviceRebaser

            self.rebaser = DeviceRebaser(self.markpool)
        # ingest_lines rides the native tree decoder when a current
        # library is loaded (health()['ingest_plane'] names the decoder
        # that actually fed the fleet).
        self.native_wire = native_wire
        self.hosts = [
            _TreeHost(
                em=EditManager(
                    mark_pool=self.markpool, device_rebase=self.rebaser,
                ),
                queue=RowQueue(tk.NESTED_OP_FIELDS, max_insert_len),
            )
            for _ in range(n_docs)
        ]
        self.fallbacks: dict[int, Forest] = {}
        self.mesh = mesh
        self.checkpoint_store = checkpoint_store
        self.checkpoint_every = checkpoint_every
        # Checkpoint-plane lock + per-incident recovery clock (same
        # contract as doc_batch_engine: the bounded-staleness background
        # writer enters via checkpoint_stale under this lock; step/ingest
        # hold it so sweeps only see op boundaries).
        self.ckpt_lock = threading.RLock()
        # Durable-write plane: saves outside ckpt_lock, seq-fenced per doc
        # (same contract as DocBatchEngine).
        self._ckpt_io_lock = threading.Lock()
        self._ckpt_saved_seq: dict[int, int] = {}
        self.recovery_tracker = RecoveryTracker()
        # Record-file mtimes last seen by a refresh trail (standby
        # trailing: one stat per doc per poll, not a full record re-read).
        self._trail_mtime: dict[int, float] = {}
        self.doc_keys = list(doc_keys) if doc_keys is not None else [
            str(d) for d in range(n_docs)
        ]
        assert len(self.doc_keys) == n_docs
        # Warm the native decode plane with no lock held: ingest_lines
        # probes only the non-building tree_decode accessor under
        # ckpt_lock (fftpu-check blocking-under-lock — a lazy g++ run
        # under the serving lock convoys every ingest).
        from ..native import ingest_native as _ingest_native

        _ingest_native.warm()
        # tree_compactions is in every health line, 0 included: its
        # window delta is a metric (fleet-wide compacts on the serving
        # thread).
        self.counters = HealthCounters(telemetry, tree_compactions=0)
        # Interning tables shared by the fleet; ROOT_FIELD must be id 0
        # (the virtual root's field in the kernel's materializer).
        self._fields: dict[str, int] = {ROOT_FIELD: 0}
        self._types: dict[str, int] = {}
        # Translation plan cache: commit shape -> row-layout plan (see
        # _FlattenCollector).  ``plan_cache=False`` keeps the original
        # per-row emit path — the independent oracle the batch-vs-legacy
        # identity fuzz compares against.
        self.plan_cache = plan_cache
        self._plans: dict[tuple, _TranslationPlan] = {}
        self._collector = _FlattenCollector()
        self._PLAN_CACHE_MAX = 4096
        # Placement rides the shared plane (models/placement.py): doc ->
        # slot indirection with per-shard spare-slot free pools, the same
        # contract as the string engine (fleet capacity rounds up to a
        # mesh multiple; padding/free rows are inert pristine protos).
        # ``_slot`` aliases the plane's live array for hot-path packing.
        self.n_shards = mesh.devices.size if mesh is not None else 1
        # The op's own clock, as the string engine keeps it (one mechanism
        # for both families: observability/op_clock.py).
        self.op_clock = OpClock(self.n_shards, self.shard_of)
        self.placement_plane = placement.PlacementPlane(
            n_docs, self.n_shards, spare_slots
        )
        self.fleet_capacity = self.placement_plane.capacity
        self.docs_per_shard = self.placement_plane.docs_per_shard
        self._slot = self.placement_plane.slots
        # Per-shard applied-op counters (host-side): accumulated at drain
        # time, the hot-shard detection signal.
        self._shard_ops = np.zeros((self.n_shards,), np.int64)
        proto = tk.init_nested_forest(capacity, pool_capacity)
        self._proto = proto  # pristine row: retires vacated/re-seeded slots
        # Under a mesh: partition-rule-matched placement, each device
        # making its own rows.
        self.state = pm.init_fleet_state(proto, self.fleet_capacity, mesh)
        self._step = _tree_step_jit
        self._megastep = _tree_megastep_jit
        self._compact = _tree_compact_jit
        if mesh is not None:
            # shard_map-wrapped fleet programs (parallel.mesh): one
            # donated dispatch steps every shard, zero hot-path
            # collectives (same machinery as the string engine).
            # On a docs x segs mesh the doc dim shards over BOTH axes
            # flattened — the program specs must match the placement
            # init_fleet_state derives from the mesh, or the first
            # donated dispatch reshards the fleet.
            da = pm.fleet_doc_axes(mesh)
            specs = pm.fleet_state_specs(self.state, da)
            self._megastep = pm.mesh_fleet_program(
                tk.apply_nested_megastep, mesh, specs,
                arg_specs=(pm.P(None, da), pm.P(None, da)),
            )
            self._compact = pm.mesh_fleet_program(
                _tree_compact_body, mesh, specs, arg_specs=()
            )
        # Recompile watchdog (same contract as the string engine): cache
        # growth after warmup = a trace de-specialized mid-serve.
        self.recompile_watchdog = RecompileWatchdog()
        for prog_name, prog in (
            ("tree_step", self._step),
            ("tree_megastep", self._megastep),
            ("tree_compact", self._compact),
        ):
            self.recompile_watchdog.register(prog_name, prog)
        # Incremental busy set + preallocated double-buffered staging
        # (lazy), mirroring doc_batch_engine's megastep pipeline.
        self._busy: set[int] = set()
        self._stage: StagingRing | None = None
        # Host-side upper bound on each doc's row watermark (rows only grow
        # on INSERT ops, whose counts the host knows at staging time) — the
        # compaction trigger without a per-batch device readback.  The word
        # pool gets the same treatment: INSERT/SET of pooled values append
        # wordcount words (overwrites leak until compaction).
        self._rows_upper = np.zeros((n_docs,), np.int64)
        self._pool_upper = np.zeros((n_docs,), np.int64)

    # -------------------------------------------------------------- interning
    def _field_id(self, key: str) -> int:
        return self._fields.setdefault(key, len(self._fields))

    def _type_id(self, t: str) -> int:
        return self._types.setdefault(t, len(self._types))

    def _encode_value(self, v) -> tuple[int, int, list[int] | None]:
        """value -> (vkind, inline-value-or-wordcount, pool words).

        int/bool/None stay inline; str and float encode as pool words
        (codepoints / f64 halves — tk.encode_pooled_words).  Raises
        UnsupportedShape for values the columnar path cannot carry:
        out-of-range ints, strings wider than one payload row, exotic
        types — those documents route to the host Forest."""
        try:
            vk, val, words = tk.encode_pooled_words(v)
        except ValueError as e:
            raise UnsupportedShape(str(e)) from None
        if words is not None and len(words) > self.max_insert_len:
            raise UnsupportedShape(f"leaf value wider than payload row: {v!r}")
        return vk, val, words

    # ------------------------------------------------------------------ ingest
    @staticmethod
    def _unwrap(contents: dict):
        """Yield the tree edit ops inside a wire message: handles grouped
        batches and the runtime's address envelopes (containerRuntime ->
        datastore -> channel), so the engine ingests the same streams a
        container fleet produces."""
        if not isinstance(contents, dict):
            return
        if contents.get("type") == "groupedBatch":
            for inner in contents.get("contents", []):
                yield from TreeBatchEngine._unwrap(inner)
            return
        if contents.get("type") == "edit":
            yield contents
            return
        if "address" in contents and "contents" in contents:
            yield from TreeBatchEngine._unwrap(contents["contents"])

    def ingest(self, doc_idx: int, msg: SequencedMessage) -> None:
        """Integrate one sequenced message: EditManager translation on the
        host, op-row staging for the device (or fallback apply).
        Serialized on ``ckpt_lock`` against the background checkpoint
        writer."""
        if msg.type != MessageType.OP:
            return
        with self.ckpt_lock:
            # The per-message path feeds the op clock one row a message
            # (``ingest_lines`` feeds it once per feed instead).
            self.op_clock.feed(msg.timestamp, self.op_clock.now(), 1, doc_idx)
            self._ingest_op(doc_idx, msg)

    def _ingest_op(self, doc_idx: int, msg: SequencedMessage) -> None:
        for edit in self._unwrap(msg.contents):
            self._ingest_edit(doc_idx, msg, edit)

    def ingest_batch(self, doc_idxs, msgs) -> None:
        """Batch-delivery seam (BroadcasterLambda.subscribe_batch / the
        fleet feeder): tree translation is inherently per-edit — each
        commit rebases through the EditManager before it can flatten — so
        the batch win here is the translation plan cache + columnar
        RowQueue landing, which ``ingest`` already rides.  This wrapper
        keeps the two engine families API-compatible for batch callers."""
        for d, m in zip(doc_idxs, msgs):
            self.ingest(d, m)

    def ingest_lines(self, doc_idx: int, data: bytes) -> int:
        """Stage newline-separated wire JSON for one tree document — the
        firehose consumer seam (API parity with ``DocBatchEngine``).
        With the native tree decoder loaded (native/ingest.cpp
        ``ing_tree_decode``) and the mark pool enabled, the envelope + mark
        numeric plane decodes in C++ straight into pool columns; otherwise
        every line takes the Python parse (``health()['ingest_plane']``
        says which fed the fleet).  A malformed line lands all EARLIER lines, then
        raises through the Python decode (which owns error semantics) —
        per-document isolation, other docs' feeds are untouched.  Returns
        op rows staged (applied edits for fallback-routed docs)."""
        t_received = self.op_clock.received()
        with self.ckpt_lock, span("ingest", doc=doc_idx, bytes=len(data)):
            rows = self._ingest_lines(doc_idx, data)
            # The feed is the op clock's sample, on either decode.
            self.op_clock.feed_lines(data, t_received, rows, doc_idx)
            return rows

    def _ingest_lines(self, doc_idx: int, data: bytes) -> int:
        h = self.hosts[doc_idx]
        commits_before = h.total_commits
        rows_before = len(h.queue)
        tables = None
        if self.markpool is not None and self.native_wire:
            from ..native import ingest_native as inat

            try:
                tables = inat.tree_decode(data)  # None: no current library
            except ValueError:
                # Malformed line: re-decode in Python so the error carries
                # the Python path's exact semantics (earlier lines land).
                self.counters.bump("tree_native_decode_errors")
                tables = None
        if tables is not None:
            self.counters.bump("tree_native_batches")
            self._ingest_native_tables(doc_idx, data, tables)
        else:
            self.counters.bump("tree_python_batches")
            for raw in data.split(b"\n"):
                line = raw.strip()
                if line:
                    msg = SequencedMessage.from_json(line.decode())
                    if msg.type == MessageType.OP:
                        self._ingest_op(doc_idx, msg)
        if doc_idx in self.fallbacks:
            return h.total_commits - commits_before
        return len(h.queue) - rows_before

    def _ingest_native_tables(self, doc_idx: int, data: bytes, tables) -> None:
        import json as _json

        from ..dds.tree.mark_pool import pool_commit_from_native
        from ..native.ingest_native import TREE_ST_EDITS, TREE_ST_OPAQUE

        msgs, chgs, flds, marks, spans = (t.tolist() for t in tables)
        for m in msgs:
            status = m[10]
            if status != TREE_ST_EDITS and status != TREE_ST_OPAQUE:
                continue  # non-op line: the op path ignores it too
            msg = SequencedMessage(
                client_id=data[m[4] : m[4] + m[5]].decode(),
                client_seq=m[13], ref_seq=m[1], seq=m[0], min_seq=m[2],
                type=MessageType.OP, contents=None,
            )
            if status == TREE_ST_OPAQUE:
                # Grouped batches, address envelopes, dict-form commits,
                # escaped ids: the Python walk, exactly as without native.
                contents = _json.loads(data[m[11] : m[11] + m[12]])
                for edit in self._unwrap(contents):
                    self._ingest_edit(doc_idx, msg, edit)
                continue
            with span("host_fold_mark_alloc", doc=doc_idx):
                commit = pool_commit_from_native(
                    self.markpool, data, m, chgs, flds, marks, spans
                )
            self._ingest_edit(
                doc_idx, msg,
                {"sid": data[m[6] : m[6] + m[7]].decode(), "rev": m[3]},
                commit=commit,
            )

    def _ingest_edit(self, doc_idx: int, msg: SequencedMessage, c: dict,
                     commit=None) -> None:
        h = self.hosts[doc_idx]
        if h.base_seq and msg.seq <= h.base_seq:
            # Covered by the durable checkpoint (restart replay): skip.
            self.counters.bump("checkpointed_ops_skipped")
            return
        h.last_seq = max(h.last_seq, msg.seq)
        h.ops_since_ckpt += 1
        if not h.dirty_since:
            h.dirty_since = time.monotonic()
        if h.boot_counting:
            self.counters.bump("boot_replay_len")
        # Host-fold sub-phases (flight recorder): mark_alloc (wire ->
        # commit/mark construction), rebase (EditManager window fold),
        # compose (trunk-suffix fold into the checkpoint forest) and
        # translate (_flatten) — the phase_shares row that makes the
        # "Mark.__init__ is ~30% of host time" claim reproducible.
        if commit is None:
            with span("host_fold_mark_alloc", doc=doc_idx):
                if self.markpool is not None:
                    commit = _pool_commit_from_json(
                        self.markpool, c["changes"]
                    )
                else:
                    commit = commit_from_json(c["changes"])
        with span("host_fold_rebase", doc=doc_idx):
            trunk = h.em.add_sequenced(
                client_id=msg.client_id,
                revision=(c["sid"], c["rev"]),
                change=commit,
                ref_seq=msg.ref_seq,
                seq=msg.seq,
            )
            h.em.advance_min_seq(msg.min_seq)
        h.total_commits += 1
        if doc_idx in self.fallbacks:
            # Fallback docs apply directly; their trunk log is dead weight
            # (they can never be re-replayed onto the device path).
            apply_commit(self.fallbacks[doc_idx].root, trunk)
            return
        h.trunk_log.append(trunk)
        if len(h.trunk_log) >= self.CHECKPOINT_EVERY:
            # Fold the suffix into the checkpoint forest: bounded host
            # memory, and fallback routing replays only the tail.
            with span("host_fold_compose", doc=doc_idx):
                for t in h.trunk_log:
                    apply_commit(h.checkpoint.root, t)
                h.trunk_log.clear()
        try:
            with span("host_fold_translate", doc=doc_idx):
                ops_blk, pay_blk = self._flatten(trunk, msg.seq)
        except UnsupportedShape:
            self._route_to_fallback(doc_idx)
            return
        h.device_commits += 1
        rows_up, words_up = self._block_upper(ops_blk)
        self._rows_upper[doc_idx] += rows_up
        self._pool_upper[doc_idx] += words_up
        h.queue.extend_block(ops_blk, pay_blk)
        if h.queue:
            self._busy.add(doc_idx)

    @staticmethod
    def _block_upper(ops_blk: np.ndarray) -> tuple[int, int]:
        """(row, pool-word) upper bounds of an op-row block — vectorized
        watermark accounting (ingest and resync share it).  Tiny blocks
        (the per-edit ingest case) take a scalar walk: numpy reductions on
        2-row arrays cost more than the loop they replace."""
        if not len(ops_blk):
            return 0, 0
        if len(ops_blk) <= 8:
            t = tk._TGT
            rows = words = 0
            for r in ops_blk.tolist():
                if r[0] in _GROW_KINDS:
                    rows += r[t + 2]
                if r[0] in _POOLED_KINDS and r[t + 5] in _POOLED_VKINDS:
                    words += r[t + 4]
            return rows, words
        kinds = ops_blk[:, 0]
        ins = (kinds == tk.NestedOpKind.INSERT) | (
            kinds == tk.NestedOpKind.REPLACE_FIELD
        )
        vk = ops_blk[:, tk._TGT + 5]
        pooled_vk = vk == tk._POOLED[0]
        for p in tk._POOLED[1:]:
            pooled_vk |= vk == p
        pooled = (ins | (kinds == tk.NestedOpKind.SET)) & pooled_vk
        return (
            int(ops_blk[ins, tk._TGT + 2].sum()),
            int(ops_blk[pooled, tk._TGT + 4].sum()),
        )

    def _queued_upper(self, h: _TreeHost) -> tuple[int, int]:
        q_ops, _q_pay = h.queue.pending()
        return self._block_upper(q_ops)

    # --------------------------------------------------------------- flatten
    def _flatten(self, trunk_commit, seq: int) -> tuple[np.ndarray, np.ndarray]:
        """Trunk commit -> nested forest op-row BLOCKS ([M, F], [M, L]).

        Front-to-back walk in OUTPUT coordinates: every emitted op's
        positions (and every path step's sibling index) are valid in the
        state produced by the ops emitted before it, so sequential device
        application reproduces the simultaneous mark semantics exactly —
        including nested paths, which back-to-front emission could not
        keep stable.

        With the plan cache on (default), the walk only COLLECTS (key +
        dynamic scalars, plain list appends); the per-row numpy work runs
        once per commit SHAPE and replays as a vectorized fill (see
        _TranslationPlan).  ``plan_cache=False`` runs the original
        per-row emit — the identity-fuzz oracle."""
        if not self.plan_cache:
            return self._flatten_legacy(trunk_commit, seq)
        col = self._collector
        col.reset()
        for change in trunk_commit:
            if change.value is not None:
                raise UnsupportedShape("value change on the virtual root")
            for key, fc in change.fields.items():
                self._walk_field(fc, (), self._field_id(key), col.emit)
        key = tuple(col.key)
        plan = self._plans.get(key)
        if plan is None:
            plan = _TranslationPlan(key, self.max_insert_len)
            if len(self._plans) < self._PLAN_CACHE_MAX:
                self._plans[key] = plan
            self.counters.bump("translation_plan_misses")
        else:
            self.counters.bump("translation_plan_hits")
        return plan.fill(col.dyn, col.pay, seq)

    def _flatten_legacy(self, trunk_commit, seq: int) -> tuple[np.ndarray, np.ndarray]:
        """The pre-plan-cache path: one numpy row pair per emit."""
        ops_rows: list[np.ndarray] = []
        pay_rows: list[np.ndarray] = []
        L = self.max_insert_len
        empty = np.zeros((L,), np.int32)

        def emit(kind, steps, fld, pos=0, count=0, dst=0, value=0,
                 vkind=0, ntype=0, payload=None):
            if len(steps) > tk.MAX_PATH:
                raise UnsupportedShape("path deeper than kernel MAX_PATH")
            op = np.zeros((tk.NESTED_OP_FIELDS,), np.int32)
            op[0], op[1], op[2] = kind, seq, len(steps)
            for k, (f, i) in enumerate(steps):
                op[3 + 2 * k], op[4 + 2 * k] = f, i
            t = tk._TGT
            op[t], op[t + 1], op[t + 2], op[t + 3] = fld, pos, count, dst
            op[t + 4], op[t + 5], op[t + 6] = value, vkind, ntype
            ops_rows.append(op)
            if payload is None:
                pay_rows.append(empty)
            else:
                tag, data = payload
                pay = np.zeros((L,), np.int32)
                if tag == "v":
                    pay[0] = data
                else:
                    pay[: len(data)] = data
                pay_rows.append(pay)

        for change in trunk_commit:
            if change.value is not None:
                raise UnsupportedShape("value change on the virtual root")
            for key, fc in change.fields.items():
                self._walk_field(fc, (), self._field_id(key), emit)
        if not ops_rows:
            return (
                np.zeros((0, tk.NESTED_OP_FIELDS), np.int32),
                np.zeros((0, L), np.int32),
            )
        return np.stack(ops_rows), np.stack(pay_rows)

    def _walk_field(self, fc, steps: tuple, fid: int, emit) -> None:
        """Dispatch one field change by kind: sequence mark lists walk as
        before; optional/value whole-content sets become REPLACE_FIELD
        device ops; other kinds route to the host fallback."""
        if isinstance(fc, list):
            if fc:
                self._walk_marks(fc, steps, fid, emit)
            return
        if not isinstance(fc, OptionalChange):
            raise UnsupportedShape(f"field kind {getattr(fc, 'kind', fc)!r}")
        if fc.set is not None:
            content = fc.set[0]
            if content is None:
                emit(tk.NestedOpKind.REPLACE_FIELD, steps, fid, count=0)
                return
            vk, val, words = self._encode_value(content.value)
            nt = self._type_id(content.type)
            emit(tk.NestedOpKind.REPLACE_FIELD, steps, fid, count=1,
                 value=val if words is not None else 0, vkind=vk, ntype=nt,
                 payload=("w", words) if words is not None else ("v", val))
            child_steps = steps + ((fid, 0),)
            for key, kids in content.fields.items():
                if kids:
                    self._insert_content(
                        kids, child_steps, self._field_id(key), 0, emit
                    )
            return
        if fc.nested is not None and not fc.nested.is_empty():
            self._walk_node_change(fc.nested, steps, fid, 0, emit)

    def _walk_node_change(
        self, ch, steps: tuple, fid: int, pos: int, emit
    ) -> None:
        """A NodeChange against the node at (fid, pos) under ``steps``."""
        if ch.value is not None:
            vk, val, words = self._encode_value(ch.value[0])
            emit(tk.NestedOpKind.SET, steps, fid, pos=pos,
                 value=val, vkind=vk,
                 payload=("w", words) if words is not None else None)
        if any(ch.fields.values()):
            child_steps = steps + ((fid, pos),)
            for key, fc in ch.fields.items():
                self._walk_field(fc, child_steps, self._field_id(key), emit)

    def _walk_marks(self, marks, steps: tuple, fid: int, emit) -> None:
        if any(isinstance(m, (MoveOut, MoveIn)) for m in marks):
            self._emit_move_field(marks, steps, fid, emit)
            return
        out_pos = 0
        for m in marks:
            if isinstance(m, Skip):
                out_pos += m.count
            elif isinstance(m, Insert):
                out_pos += self._insert_content(
                    m.content, steps, fid, out_pos, emit
                )
            elif isinstance(m, Remove):
                emit(tk.NestedOpKind.REMOVE, steps, fid, pos=out_pos,
                     count=m.count)
            elif isinstance(m, Modify):
                self._walk_node_change(m.change, steps, fid, out_pos, emit)
                out_pos += 1
            else:
                raise UnsupportedShape(type(m).__name__)

    def _insert_content(
        self, nodes: list[Node], steps: tuple, fid: int, start: int, emit
    ) -> int:
        """Decompose a content forest into path-addressed inserts,
        parent-first; consecutive childless same-shape nodes batch into one
        op row.  Returns the number of nodes inserted at this level."""
        pos = start
        run_vals: list[int] = []
        run_shape: tuple[int, int] | None = None  # (vkind, ntype)

        def flush() -> None:
            nonlocal run_vals, run_shape
            if run_vals:
                emit(tk.NestedOpKind.INSERT, steps, fid,
                     pos=pos - len(run_vals), count=len(run_vals),
                     vkind=run_shape[0], ntype=run_shape[1],
                     payload=("r", list(run_vals)))
            run_vals, run_shape = [], None

        for node in nodes:
            vk, val, words = self._encode_value(node.value)
            nt = self._type_id(node.type)
            pooled = words is not None
            if pooled or (node.fields and any(node.fields.values())):
                # Pooled values carry their words in the payload row (one
                # node per op); interior nodes need their own op so child
                # inserts can address them parent-first.
                flush()
                emit(tk.NestedOpKind.INSERT, steps, fid, pos=pos, count=1,
                     value=val if pooled else 0, vkind=vk, ntype=nt,
                     payload=("w", words) if pooled else ("v", val))
                child_steps = steps + ((fid, pos),)
                for key, kids in node.fields.items():
                    if kids:
                        self._insert_content(
                            kids, child_steps, self._field_id(key), 0, emit
                        )
                pos += 1
            else:
                if run_shape not in (None, (vk, nt)) or len(run_vals) >= self.max_insert_len:
                    flush()
                run_shape = (vk, nt)
                run_vals.append(val)
                pos += 1
        flush()
        return pos - start

    def _emit_move_field(self, marks, steps: tuple, fid: int, emit) -> None:
        """A field containing a move: only the pure single-pair contiguous
        form maps to one device op (input coordinates); anything else —
        split moves, cross-field pairs, moves mixed with other structural
        marks — is host-fallback territory."""
        move_out: dict[int, tuple[int, int]] = {}
        move_in: dict[int, int] = {}
        in_pos = 0
        for m in marks:
            if isinstance(m, Skip):
                in_pos += m.count
            elif isinstance(m, MoveOut):
                if m.id in move_out:
                    raise UnsupportedShape("split move")
                move_out[m.id] = (in_pos, m.count)
                in_pos += m.count
            elif isinstance(m, MoveIn):
                if m.id in move_in:
                    raise UnsupportedShape("split move")
                move_in[m.id] = in_pos
            else:
                raise UnsupportedShape("mixed structural marks with move")
        if len(move_out) != 1 or set(move_out) != set(move_in):
            raise UnsupportedShape("non-single-pair move")
        (mid, (src, count)), = move_out.items()
        emit(tk.NestedOpKind.MOVE, steps, fid, pos=src, count=count,
             dst=move_in[mid])

    # ---------------------------------------------------------------- routing
    def _route_to_fallback(self, doc_idx: int) -> None:
        """Rebuild the document as a host Forest from its trunk log; all
        future commits apply there (route-to-oracle, like the string
        engine's recovery lanes)."""
        h = self.hosts[doc_idx]
        f = h.checkpoint  # trunk state up to the last checkpoint fold
        for trunk in h.trunk_log:
            apply_commit(f.root, trunk)
        self.fallbacks[doc_idx] = f
        h.checkpoint = Forest()
        h.trunk_log.clear()  # never replayed again
        h.queue.clear()
        self._busy.discard(doc_idx)
        # The doc's device columns are dead weight now; stop letting its
        # stale watermarks trigger fleet-wide compactions.
        self._rows_upper[doc_idx] = 0
        self._pool_upper[doc_idx] = 0

    # ------------------------------------------------------------------- step
    def pending_ops(self) -> int:
        return sum(len(h.queue) for h in self.hosts)

    # --------------------------------------------------------- flow control
    def update_overload(self) -> tuple[list[int], list[int]]:
        """Ingest watermark hysteresis (see doc_batch_engine): -> (newly
        paused docs, newly resumed docs)."""
        return self.overload_gate.update(
            self._busy, lambda d: len(self.hosts[d].queue)
        )

    def ingest_watermarks(self) -> dict:
        return self.overload_gate.watermarks(
            self.megastep_k * self.ops_per_step
        )

    @property
    def overloaded(self) -> bool:
        return bool(self.overload_gate.paused)

    def device_fraction(self) -> float:
        """Fraction of ingested commits applied on the device path."""
        total = sum(h.total_commits for h in self.hosts)
        dev = sum(h.device_commits for h in self.hosts)
        return dev / total if total else 1.0

    def _staging(self) -> StagingRing:
        if self._stage is None:
            self._stage = StagingRing(
                self.megastep_k, self.fleet_capacity, self.ops_per_step,
                tk.NESTED_OP_FIELDS, self.max_insert_len, mesh=self.mesh,
                doc_axis=(
                    pm.fleet_doc_axes(self.mesh)
                    if self.mesh is not None else "docs"
                ),
            )
        return self._stage

    def _select_k(self, busy: list[int]) -> int:
        """Megastep depth from the deepest busy queue (pow2-quantized,
        capped at megastep_k); K=1 degenerates to the per-slice path."""
        if self.megastep_k <= 1:
            return 1
        B = self.ops_per_step
        need = max(-(-len(self.hosts[d].queue) // B) for d in busy)
        return min(self.megastep_k, 1 << (max(need, 1).bit_length() - 1))

    def _drain_into(
        self, busy: list[int], ops: np.ndarray, payloads: np.ndarray
    ) -> list[int]:
        """Dequeue up to ops_per_step op rows per busy doc into its
        PLACEMENT slot's row of the zeroed staging arrays — slice copies,
        never a per-op Python loop.  Returns the rows written
        (buffer-reuse dirty tracking)."""
        B = self.ops_per_step
        written: list[int] = []
        for d in busy:
            h = self.hosts[d]
            take = min(B, len(h.queue))
            if not take:
                continue
            r = int(self._slot[d])
            src_ops, src_payloads = h.queue.take(take)
            ops[r, :take] = src_ops
            payloads[r, :take] = src_payloads
            # Charge the op count to the hosting shard (hot-shard signal).
            self._shard_ops[r // self.docs_per_shard] += take
            if not h.queue:
                self._busy.discard(d)
            written.append(r)
        return written

    def _compact_fleet(self) -> None:
        """One fleet-wide ``tree_compact`` on the serving thread, then the
        one readback that re-syncs the host's row and pool-word upper
        bounds to the true live counts."""
        with span("compact", docs=self.n_docs):
            self.counters.bump("tree_compactions")
            self.state = self._compact(self.state)
            # Resync = live rows/words (applied) + the counts still in
            # each doc's queue (unapplied) — dropping the queued part
            # would let a long churn stream overflow mid-step without
            # ever re-triggering compaction.
            queued_pairs = [self._queued_upper(h) for h in self.hosts]
            queued = np.array([q for q, _w in queued_pairs], np.int64)
            queued_words = np.array([w for _q, w in queued_pairs], np.int64)
            # Fallback docs keep stale live rows on device (nothing
            # compacts them away); excluding them here keeps the reset
            # in _route_to_fallback effective — otherwise one resync
            # resurrects an above-threshold watermark that no
            # compaction can ever lower, and the fleet compacts on
            # every batch forever.
            active = np.array(
                [d not in self.fallbacks for d in range(self.n_docs)]
            )
            nrow = np.asarray(self.state.nrow)[self._slot].astype(np.int64)
            words = np.asarray(self.state.pool_end)[self._slot].astype(
                np.int64
            )
            self._rows_upper = np.where(active, nrow + queued, 0)
            self._pool_upper = np.where(active, words + queued_words, 0)

    def compact(self, docs=None) -> None:
        """What the consumer calls on a summary ack (``docs``: the acked
        documents).  The tree fleet has no per-document compaction yet:
        whatever is staged is applied, then the whole fleet compacts."""
        if self._busy:
            self.step()
        self._compact_fleet()

    def step(self, in_flight=None) -> int:
        """Apply everything staged as batched device megasteps.  Holds
        ``ckpt_lock`` end to end (the background checkpoint writer only
        sweeps between steps) and closes any open recovery incident once
        staged work actually applied (kill -> first post-restore op).
        ``in_flight``, if given, is called once between the last dispatch
        and the readback that waits for it, with that dispatch's error
        latch (``DocBatchEngine.step``'s seam: the consumer reads its
        sockets there)."""
        with self.ckpt_lock:
            had_work = bool(self._busy)
            steps = self._step_fleet(in_flight)
            # The sync boundary: the error readback has proved the
            # dispatches retired, so every feed staged so far is applied.
            self.op_clock.resolve()
            if had_work and self.recovery_tracker.active:
                self.recovery_tracker.complete()
        # Cadence checkpoints after the serving lock releases (same
        # contract as DocBatchEngine.step): the durable fsyncs must not
        # run while every ingest contender queues on ckpt_lock.
        with span("housekeeping", kind="checkpoint"):
            self.maybe_checkpoint()
        return steps

    def _step_fleet(self, in_flight=None) -> int:
        steps = 0
        while self._busy:
            # Proactive compact: dead rows accumulate monotonically (stable
            # rows never reuse slots) — reclaim before overflow.  The
            # trigger is the host-side row UPPER BOUND (no per-batch device
            # sync); the one readback after compacting re-syncs it to the
            # true live counts.
            if (
                self._rows_upper.max() > self.capacity * self.COMPACT_FRACTION
                or self._pool_upper.max()
                > self.pool_capacity * self.COMPACT_FRACTION
            ):
                self._compact_fleet()
            busy = sorted(self._busy)
            with span("pack", kind="tree", docs=len(busy)):
                K = self._select_k(busy)
                stage = self._staging()
                ops, payloads = stage.acquire(K, self.fleet_capacity)
                for k in range(K):
                    stage.mark(
                        k, self._drain_into(busy, ops[k], payloads[k])
                    )
                    if k + 1 < K:
                        busy = [d for d in busy if d in self._busy]
            if self.mesh is None and K == 1:
                dev_ops, dev_payloads = stage.upload(ops[0], payloads[0])
                with span("dispatch", kind="tree", k=K):
                    self.state = self._step(
                        self.state, dev_ops, dev_payloads
                    )
            else:
                # Mesh path: always the [K, D, B] shard_map megastep (K=1
                # included — bit-identical to one batched dispatch), one
                # donated call stepping every chip.
                dev_ops, dev_payloads = stage.upload(ops, payloads)
                with span("dispatch", kind="tree", k=K,
                          shards=self.n_shards):
                    self.state = self._megastep(
                        self.state, dev_ops, dev_payloads
                    )
            steps += K
            self.counters.bump("megastep_dispatches")
            self.counters.bump("megastep_slices", K)
        with span("housekeeping"):
            self.recompile_watchdog.poll()
        if in_flight is not None:
            with span("readback", kind="in_flight"):
                in_flight(self.state.error)
        if self.mesh is not None:
            # Per-shard latch reduce: one scalar readback instead of a
            # cross-mesh [D] error gather on every step.
            with span("readback", kind="error_count"):
                clean = int(pm.error_count(self.state.error)) == 0
            if clean:
                return steps
        with span("readback", kind="error_vector"):
            err = np.asarray(self.state.error)
        # The host part: walk the vector.
        with span("recover"):
            for d in range(self.n_docs):
                s = int(self._slot[d])
                if err[s] and d not in self.fallbacks:
                    # Capacity/range overflow on device: replay on the host.
                    self._route_to_fallback(d)
                    self.counters.bump("fallback_routes")
                    self.state = self.state._replace(
                        error=self.state.error.at[s].set(0)
                    )
        return steps

    # ------------------------------------------------------------- checkpoint
    def maybe_checkpoint(self, force: bool = False, docs=None) -> list[int]:
        """Write durable checkpoint records (forest + EditManager window)
        for docs whose commit count since the last record reached
        ``checkpoint_every``; all dirty docs when ``force``.  The host
        trunk fold (``checkpoint`` forest) IS the snapshot state, so this
        needs no device readback.  ``docs`` restricts the sweep to an
        explicit due list (the bounded-staleness writer): those
        checkpoint whenever dirty, regardless of cadence."""
        if self.checkpoint_store is None:
            return []
        if docs is None and not force and self.checkpoint_every <= 0:
            return []
        with self.ckpt_lock:
            out, pending = self._checkpoint_sweep(force, docs)
        # Durable writes outside ckpt_lock (same contract as the string
        # engine): a background sweep's fsyncs must not stall serving.
        write_checkpoint_records(self, pending, "device")
        return out

    def checkpoint_stale(
        self, max_ops_behind: int = 0, max_seconds_behind: float = 0.0
    ) -> list[int]:
        """Bounded-staleness delta sweep (same contract as
        ``DocBatchEngine.checkpoint_stale``): checkpoint every dirty doc
        whose durable record trails by ``max_ops_behind`` applied ops or
        ``max_seconds_behind`` seconds.  Record build under ``ckpt_lock``;
        durable writes after release."""
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
        write_checkpoint_records(self, pending, "device")
        return out

    def _checkpoint_sweep(
        self, force: bool, docs
    ) -> tuple[list[int], list[tuple[int, int, dict]]]:
        out: list[int] = []
        pending: list[tuple[int, int, dict]] = []
        for d in (range(self.n_docs) if docs is None else docs):
            h = self.hosts[d]
            if h.ops_since_ckpt <= 0:
                continue
            if (
                docs is None and not force
                and h.ops_since_ckpt < self.checkpoint_every
            ):
                continue
            if d in self.fallbacks:
                lane = "fallback"
                forest_json = self.fallbacks[d].to_json()
            else:
                lane = "device"
                # Fold the trunk suffix so the checkpoint forest is the
                # full trunk state (this is the same fold the host-memory
                # bound performs, just on the durable cadence too).
                for t in h.trunk_log:
                    apply_commit(h.checkpoint.root, t)
                h.trunk_log.clear()
                forest_json = h.checkpoint.to_json()
            record = {
                "engine": "tree_batch",
                "lane": lane,
                "forest": forest_json,
                "em": h.em.summarize(),
                "commits": h.total_commits,
            }
            pending.append((d, h.last_seq, record))
            h.base_seq = h.last_seq
            h.ops_since_ckpt = 0
            h.dirty_since = 0.0
            h.boot_counting = False  # a new durable floor ends the boot phase
            self.counters.bump("checkpoints_written")
            out.append(d)
        return out, pending

    def note_incident(self, started_at: float) -> None:
        """Back-date the current recovery incident to the supervisor's
        kill timestamp (``time.monotonic`` domain)."""
        self.recovery_tracker.begin(started_at)

    def restore_from_checkpoints(
        self, store=None, parallel: bool = True,
        max_workers: int | None = None, refresh: bool = False,
    ) -> list[int]:
        """Engine restart path: rebuild each doc's host forest and
        EditManager window from its durable record, re-materialize the
        device columns from the forest (a synthesized whole-content insert
        commit), and set the seq floor so replayed ops the checkpoint
        covers are skipped.

        ``parallel`` (default) loads every record concurrently (thread
        pool over the store's ``load_many`` — the JSON read+parse is the
        restore's I/O phase); the host builds stay in doc order either
        way, and the re-materialized device rows land through the normal
        batched step, so the device half is already one megastep per K·B
        rows.  ``parallel=False`` is the sequential oracle (per-doc
        loads), byte-identical by contract.

        ``refresh`` is the warm-standby trailing mode: adopt docs that
        GAINED a record since the last pass, without opening a recovery
        incident — including the IN-PLACE RE-SEED of an already-adopted
        doc from a strictly newer record (string-engine parity): the
        doc's materialized pooled columns reset to the pristine proto row
        and the fresh forest re-materializes on top, so a promoted tree
        standby replays from each doc's freshest durable floor."""
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
                self, store, refresh, lambda d: len(self.hosts[d].queue)
            )
        if not candidates:
            return []
        records = load_checkpoint_records(
            store, [self.doc_keys[d] for d in candidates],
            parallel=parallel, max_workers=max_workers,
        )
        restored: list[int] = []
        for i, d in enumerate(candidates):
            rec = records.get(i)
            if rec is not None and d in cand_mtime:
                self._trail_mtime[d] = cand_mtime[d]
            if rec is None or rec.get("engine") != "tree_batch":
                continue
            h = self.hosts[d]
            if refresh and h.restored:
                if int(rec["seq"]) <= h.last_seq:
                    continue  # nothing newer to adopt
                self.counters.bump("checkpoint_refreshes")
            if refresh:
                # In-place re-seed: forget the prior adoption (host
                # windows, staged rows, fallback entry) and reset the
                # doc's materialized pooled columns to the pristine proto
                # row, so the fresh record's re-materialization lands on
                # clean state.
                self._drop_restored_identity(d)
            h.em = EditManager(mark_pool=self.markpool)
            h.em.load(rec["em"])
            h.base_seq = h.last_seq = int(rec["seq"])
            h.restored = True
            h.boot_counting = True
            h.total_commits = int(rec.get("commits", 0))
            forest = Forest()
            forest.load_json(rec["forest"])
            if rec.get("lane") == "fallback":
                self.fallbacks[d] = forest
                h.checkpoint = Forest()
                restored.append(d)
                self.counters.bump("docs_restored")
                continue
            h.checkpoint = forest
            if forest.root_field:
                # Re-materialize the device columns: the checkpoint forest
                # as one whole-content insert commit (same flatten path as
                # live commits, so interning and accounting match).
                ch = NodeChange()
                ch.fields[ROOT_FIELD] = [
                    Insert([n.clone() for n in forest.root_field])
                ]
                try:
                    ops_blk, pay_blk = self._flatten([ch], seq=h.base_seq)
                except UnsupportedShape:
                    self._route_to_fallback(d)
                    restored.append(d)
                    self.counters.bump("docs_restored")
                    continue
                rows_up, words_up = self._block_upper(ops_blk)
                self._rows_upper[d] += rows_up
                self._pool_upper[d] += words_up
                h.queue.extend_block(ops_blk, pay_blk)
                if h.queue:
                    self._busy.add(d)
            restored.append(d)
            self.counters.bump("docs_restored")
        if restored and not refresh:
            # A real restore (not standby trailing) opens a recovery
            # incident: the clock runs until the first post-restore step
            # applies staged work (the re-materialization rows count —
            # they ARE the restore's device half).  note_incident()
            # back-dates to the kill time.
            self.recovery_tracker.begin(t_start)
        if restored and refresh:
            # Trailing/re-seed hands back LIVE state: apply the staged
            # re-materializations now (unlike the string engine's direct
            # row scatter, the tree handoff rides the batched step), so a
            # promoted standby serves byte-identical reads immediately
            # and the next trailing pass's staged-work guard doesn't see
            # this pass's own rows.
            self._step_fleet()
        return restored

    # ----------------------------------------------------------------- warmup
    def warmup(self) -> int:
        """Pre-compile the fleet's serving programs (warm-standby boot):
        dispatch all-NOOP megasteps at every pow2 depth up to
        ``megastep_k`` plus one compact through the exact serving entry
        points, so a promoted standby pays ZERO XLA compiles on its first
        real dispatch.  Zeroed staging rows are NOOP by kernel contract
        (NestedOpKind.NOOP == 0), so state bytes are untouched.  Returns
        the number of warmup dispatches run."""
        warmed = 0
        with self.ckpt_lock, span("warmup", k_max=self.megastep_k):
            stage = self._staging()
            if self.mesh is None:
                # The K=1 mesh-less fast path dispatches _step directly.
                ops, payloads = stage.acquire(1, self.fleet_capacity)
                dev_ops, dev_payloads = stage.upload(ops[0], payloads[0])
                self.state = self._step(self.state, dev_ops, dev_payloads)
                warmed += 1
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
                    ops, payloads = stage.acquire(k, self.fleet_capacity)
                    dev_ops, dev_payloads = stage.upload(ops, payloads)
                    self.state = self._megastep(
                        self.state, dev_ops, dev_payloads
                    )
                    warmed += 1
            self.state = self._compact(self.state)
            warmed += 1
            jax.block_until_ready(self.state)
            # Absorb the warmup compiles into the watchdog count NOW, so
            # they show up as boot-time cache growth rather than landing
            # on the first serving step's poll.
            self.recompile_watchdog.poll()
        self.counters.gauge("warmup_dispatches", warmed)
        return warmed

    # ----------------------------------------------------------------- health
    def latency_histograms(self) -> dict:
        """Mergeable latency histograms for the metrics plane (API parity
        with ``DocBatchEngine``): the op clock's and the recovery clock's."""
        return {
            **self.op_clock.histograms(),
            "recovery_time": self.recovery_tracker.histogram,
        }

    def health(self) -> dict:
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
        hits = self.counters.get("translation_plan_hits")
        misses = self.counters.get("translation_plan_misses")
        self.counters.gauge(
            "translation_plan_hit_rate",
            round(hits / (hits + misses), 4) if hits + misses else 0.0,
        )
        self.counters.gauge("translation_plans", len(self._plans))
        # Mark-pool surface: hit rate = span demands answered by reusing
        # an existing immutable span (the incremental-rebase identity
        # reuse) over all demands; occupancy = live slots / pool storage.
        if self.markpool is not None:
            ps = self.markpool.stats()
            hits = ps["mark_pool_reuse_hits"]
            total = hits + ps["mark_pool_spans"]
            self.counters.gauge(
                "mark_pool_hit_rate",
                round(hits / total, 4) if total else 0.0,
            )
            for k, v in ps.items():
                self.counters.gauge(k, v)
        # Device-rebase surface: fraction of window steps resolved on
        # the kernel plane; fallbacks are the pooled-fold remainder
        # (ineligible commits + invalidated steps), counted never silent.
        if self.rebaser is not None:
            for k, v in self.rebaser.stats().items():
                self.counters.gauge(k, v)
        self.counters.gauge("recompiles", self.recompile_watchdog.recompiles)
        self.counters.gauge(
            "despecializations", self.recompile_watchdog.despecializations
        )
        # Flow-control surface (shared shape with the string engine via
        # OverloadGate.emit_gauges).
        self.overload_gate.emit_gauges(
            self.counters, self.megastep_k * self.ops_per_step,
            max((len(self.hosts[d].queue) for d in self._busy), default=0),
        )
        self.counters.gauge("n_shards", self.n_shards)
        if self.n_shards > 1:
            depth = [0] * self.n_shards
            for d in range(self.n_docs):
                q = len(self.hosts[d].queue)
                if q:
                    depth[self.shard_of(d)] += q
            self.counters.gauge("shard_queue_depth", depth)
        # Op latency (the op clock's sequencer stamp -> applied), then the
        # recovery surface (same shape as the string engine): incident
        # percentiles + current checkpoint staleness.
        self.op_clock.emit_gauges(self.counters)
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
        snap = self.counters.snapshot()
        from ..native import ingest_native as inat

        bound = (
            self.markpool is not None and self.native_wire and inat.loaded()
        )
        snap.update(
            # Which wire decoder fed ingest_lines, and whether the native
            # ``ing_tree_decode`` entry is bound at all.
            ingest_plane=inat.fed_plane(
                snap.get("tree_native_batches", 0),
                snap.get("tree_python_batches", 0),
                bound,
            ),
            tree_decode_bound=bound,
            fallback_docs=len(self.fallbacks),
            checkpoint_age_seqs=max(
                (h.last_seq - h.base_seq for h in self.hosts if h.last_seq),
                default=0,
            ),
            device_fraction=round(self.device_fraction(), 4),
        )
        return snap

    # ------------------------------------------------------------------ views
    def _name_tables(self) -> tuple[dict[int, str], dict[int, str]]:
        return (
            {v: k for k, v in self._fields.items()},
            {v: k for k, v in self._types.items()},
        )

    def tree_json(self, doc_idx: int) -> list[dict]:
        """The document's root field as forest JSON (Node.to_json shape)."""
        return self._doc_json(self.state, doc_idx, *self._name_tables())

    def trees_json(self) -> list[list[dict]]:
        """Every document's root field as forest JSON, in doc order: ONE
        readback of the fleet's columns and a host walk per document (the
        drain line's surface; ``tree_json`` slices one document on the
        device, thirteen small programs and transfers a document)."""
        host = jax.device_get(self.state)
        names = self._name_tables()
        return [self._doc_json(host, d, *names) for d in range(self.n_docs)]

    def _doc_json(self, state, doc_idx: int, field_names, type_names):
        if doc_idx in self.fallbacks:
            return [n.to_json() for n in self.fallbacks[doc_idx].root_field]
        slot = int(self._slot[doc_idx])
        st = jax.tree.map(lambda x: x[slot], state)
        return tk.nested_to_json(st, field_names, type_names)

    def values(self, doc_idx: int) -> list:
        """The document's root-field node values (int/str/float/bool
        leaves, None for valueless nodes)."""
        return [n.get("v") for n in self.tree_json(doc_idx)]

    def shard_of(self, doc_idx: int) -> int:
        """The mesh shard currently hosting this doc's device row."""
        return self.placement_plane.shard_of(doc_idx)

    def placement(self) -> dict[str, int]:
        """doc key -> mesh shard (ScribePool.align_to_placement surface)."""
        return self.placement_plane.placement(self.doc_keys)

    def shard_load(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-shard (applied ops since the last ``hot_shards`` reset,
        currently queued ops) — see placement.shard_load."""
        return placement.shard_load(self)

    def hot_shards(
        self, factor: float = 2.0, reset: bool = False, load=None
    ) -> list[int]:
        """Shards whose load (applied + queued ops) exceeds ``factor`` x
        the fleet mean — see placement.hot_shards (the same detection the
        string engine rides)."""
        return placement.hot_shards(self, factor, reset, load)

    def free_slots(self, shard: int) -> int:
        return self.placement_plane.free_slots(shard)

    def migrate_doc(self, d: int, dst_shard: int) -> bool:
        # ckpt_lock: migration mutates self.state and the slot map, which
        # the background checkpoint sweep and ingest both read.
        with self.ckpt_lock:
            return self._migrate_doc_locked(d, dst_shard)

    def _migrate_doc_locked(self, d: int, dst_shard: int) -> bool:
        """Live tree-doc migration between mesh shards (hot-shard
        rebalancing; string-engine parity).

        The handoff is the same trunk-fold + re-materialization the
        restore path trusts: the trunk suffix folds into the checkpoint
        forest (which then carries the doc's FULL ingested trunk state —
        including any rows still queued for the device, so the queue
        drops), the vacated slot retires to the pristine proto row, and
        the forest re-materializes at the destination slot as one
        whole-content insert staged through the normal batched step.
        Observable state (``tree_json``) is byte-identical once staged
        work applies; host EditManager windows and checkpoint floors
        travel with the doc untouched, so a doc may migrate MID-STREAM.
        Raises ``placement.PlacementError`` for a fallback-routed doc
        (its serving state lives in a host Forest, not the fleet slot).
        Returns False (doc stays put) when the doc is already on
        ``dst_shard``, its row latched an error, the forest cannot
        re-flatten, or the destination has no free slot."""
        plane = self.placement_plane
        plane.validate(d, dst_shard)
        plane.require_migratable(
            d, "fallback" if d in self.fallbacks else None
        )
        reservation = plane.reserve(d, dst_shard)
        if reservation is None:
            return False
        src_slot, dst_slot = reservation
        src_shard = src_slot // self.docs_per_shard
        h = self.hosts[d]
        if int(np.asarray(self.state.error)[src_slot]):
            plane.release(dst_slot)
            return False  # recover first; never migrate a latched row
        # Fold the trunk suffix: the checkpoint forest becomes the full
        # ingested trunk state (the same fold the checkpoint sweep and
        # fallback routing perform).
        for t in h.trunk_log:
            apply_commit(h.checkpoint.root, t)
        h.trunk_log.clear()
        ops_blk = pay_blk = None
        if h.checkpoint.root_field:
            ch = NodeChange()
            ch.fields[ROOT_FIELD] = [
                Insert([n.clone() for n in h.checkpoint.root_field])
            ]
            try:
                ops_blk, pay_blk = self._flatten([ch], seq=h.last_seq)
            except UnsupportedShape:
                plane.release(dst_slot)
                return False  # cannot re-pack: doc keeps serving in place
        # Queued rows are covered by the folded forest; re-staging them on
        # top of the re-materialization would double-apply.
        h.queue.clear()
        self._busy.discard(d)
        self.state = jax.tree.map(
            lambda x, s: x.at[src_slot].set(s), self.state, self._proto
        )
        plane.commit(d, src_slot, dst_slot)
        # The destination slot is pristine by pool invariant (spare slots
        # start as broadcast protos; retired slots reset above), so the
        # watermarks restart at the re-materialization bound.
        self._rows_upper[d] = 0
        self._pool_upper[d] = 0
        if ops_blk is not None and len(ops_blk):
            rows_up, words_up = self._block_upper(ops_blk)
            self._rows_upper[d] += rows_up
            self._pool_upper[d] += words_up
            h.queue.extend_block(ops_blk, pay_blk)
            self._busy.add(d)
        self.counters.bump("doc_migrations")
        instant(
            "migrate_doc", doc=self.doc_keys[d], src=src_shard,
            dst=dst_shard,
        )
        return True

    def rebalance_hot_shards(
        self, factor: float = 2.0, max_moves: int = 1
    ) -> list[tuple[int, int, int]]:
        """Detect hot shards and live-migrate their deepest-queued docs
        to the coldest shards with free slots — the shared plane's
        skeleton (placement.rebalance_hot_shards), one trunk-fold +
        re-materialization handoff per move.  Returns the ``(doc,
        src_shard, dst_shard)`` moves made; callers re-align the scribe
        pool afterwards so summary ownership follows the docs."""
        return placement.rebalance_hot_shards(
            self, self.placement_plane, factor, max_moves,
            in_lane=lambda d: d in self.fallbacks,
        )

    def adopt_boot_snapshot(
        self, doc_idx: int, record: dict
    ) -> placement.AdoptResult:
        """Client half of the fan-out plane's ``{"t":"resync","boot":true}``
        contract (the shared orchestration — placement.adopt_boot_snapshot —
        riding this engine's refresh re-seed path): a consumer that fell
        off the retained log re-seeds the document from a historian
        snapshot record (the scribe summary schema, ``engine:
        tree_batch``) and re-consumes from the returned floor; the host
        EditManager window, checkpoint forest, and materialized device
        columns all reset consistently."""
        return placement.adopt_boot_snapshot(
            self, doc_idx, record, self._clear_staged
        )

    def _clear_staged(self, doc_idx: int) -> None:
        """Drop a doc's staged pre-gap work ahead of a boot-snapshot
        adoption (the refresh guard refuses docs with pending ops; a boot
        resync REPLACES the doc, so pre-gap rows are covered)."""
        self.hosts[doc_idx].queue.clear()
        self._busy.discard(doc_idx)

    def _drop_restored_identity(self, d: int) -> None:
        """Forget a doc's prior adoption before a refresh re-seed (warm-
        standby trailing / boot-snapshot adoption: no staged work by
        contract).  The device half resets the doc's materialized pooled
        columns to the pristine proto row — re-materialization is
        incremental on top of whatever the row holds, so a re-seed must
        land on clean state (this reset is what closes the old
        'cannot be overwritten in place' parity gap)."""
        had_fallback = self.fallbacks.pop(d, None) is not None
        h = self.hosts[d]
        h.queue.clear()
        h.trunk_log.clear()
        h.checkpoint = Forest()
        self._busy.discard(d)
        self._rows_upper[d] = 0
        self._pool_upper[d] = 0
        if h.total_commits or h.restored or had_fallback:
            # Only docs that ever materialized (or whose slot may hold
            # stale pre-fallback content) pay the row reset; a fresh
            # standby's first adoption lands on already-pristine rows.
            slot = int(self._slot[d])
            self.state = jax.tree.map(
                lambda x, s: x.at[slot].set(s), self.state, self._proto
            )

    def errors(self) -> np.ndarray:
        return np.asarray(self.state.error)[self._slot]
