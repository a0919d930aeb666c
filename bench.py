"""Benchmarks: the five BASELINE.md target configs + p50 apply latency.

North-star metric (BASELINE.json): merge-tree ops/sec/chip across a fleet of
concurrent SharedString documents, target >= 1M ops/sec/chip on TPU with
reference-equivalent semantics (the semantics are enforced by the
differential test suite; this file measures throughput only).

Default (no args) is DRIVER MODE: runs every config below as a time-boxed
subprocess (the parent never initializes a JAX backend, so each child can
own the accelerator) and prints one JSON line each: configs 1-5, p50/p99
latency, and LAST the round headline (config 3's single-writer form, metric
name unchanged since r1 for comparability, with the multi-writer Zipf
config-3 number attached as co-headline).  Every measuring process reads
``jax.devices()`` itself and stamps its row with platform, device_kind and
device_count.  A run started without ``JAX_PLATFORMS=cpu`` that finds no
accelerator fails: there is no fallback to the CPU,
and a failed config makes the whole run exit non-zero.  With
``JAX_PLATFORMS=cpu`` the run is an explicit CPU run at reduced scale
(``reduced_scale`` on every row).  Explicit runs:

    python bench.py --config 1   # SharedString single-doc replay, 4 writers
    python bench.py --config 2   # SharedMap LWW, 256 concurrent setters
    python bench.py --config 3   # SharedString 10k docs, Zipf skew, 4 writers
    python bench.py --config 4   # SharedMatrix 256x256, 64 writers
    python bench.py --config 5   # SharedTree EditManager->device pipeline
    python bench.py --config latency   # p50/p99 remote-op apply latency
    python bench.py --config all       # all of the above, one line each

Each config line reports the DEVICE-ONLY number (jitted scan, host dispatch
excluded — the steady-state pipeline rate) in "value", plus
"ingest_ops_per_sec": the same wire trace pushed through the host ingest
path (JSON decode -> op encoding -> batch padding -> device step) at reduced
scale — the end-to-end bound when the host feeds the device from cold.

Multi-writer traces are REAL concurrency: writers stamp ref_seq at the
previous round boundary, so every op rebases against the other writers'
in-window ops on apply (insert/remove pairs are writer-local so positions
are valid by construction without simulating every replica).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

def _cpu_requested() -> bool:
    """True iff this run was STARTED as a CPU run (``JAX_PLATFORMS=cpu``):
    the one way to ask for the CPU.  Anything else expects an accelerator
    and fails without one."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def _require_accelerator(platform: str) -> None:
    """Exit non-zero when the run expected an accelerator and the measuring
    process ran on the CPU — a CPU number must never be recorded under a
    device metric's name."""
    if platform == "cpu" and not _cpu_requested():
        sys.exit(
            "bench.py: JAX found no accelerator (platform cpu) and the run "
            "was not started with JAX_PLATFORMS=cpu; refusing to measure"
        )


def _device_row() -> dict:
    """What this process's JAX actually runs on, for its result row."""
    import jax

    devs = jax.devices()
    _require_accelerator(devs[0].platform)
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


# ---------------------------------------------------------------------------
# Workload generators
# ---------------------------------------------------------------------------

def generate_workload(n_docs, ops_per_step, n_steps, ins_len, payload_len, seed=0):
    """Single-writer random edit traces with positions valid by construction.

    Returns ops[int32 S,D,B,8], payloads[int32 S,D,B,L], min_seqs[int32 S,D].
    """
    from fluidframework_tpu.ops import mergetree_kernel as mk
    from fluidframework_tpu.protocol.stamps import ALL_ACKED

    rng = np.random.default_rng(seed)
    D, B, S, L = n_docs, ops_per_step, n_steps, payload_len
    ops = np.zeros((S, D, B, mk.OP_FIELDS), np.int32)
    payloads = rng.integers(97, 123, size=(S, D, B, L), dtype=np.int32)
    lengths = np.zeros((D,), np.int64)
    seq = np.ones((D,), np.int64)
    for s in range(S):
        for b in range(B):
            do_insert = (rng.random(D) < 0.5) | (lengths < 2)
            pos = (rng.random(D) * (lengths + 1)).astype(np.int64)
            pos = np.minimum(pos, lengths)
            # insert: ins_len chars at pos
            ops[s, :, b, 0] = np.where(do_insert, mk.OpKind.INSERT, mk.OpKind.REMOVE)
            ops[s, :, b, 1] = seq
            ops[s, :, b, 2] = 0  # single writer: short client 0
            ops[s, :, b, 3] = ALL_ACKED  # sequential writer sees everything
            ops[s, :, b, 4] = np.where(do_insert, pos, np.minimum(pos, lengths - 2))
            ops[s, :, b, 5] = np.where(do_insert, 0, np.minimum(pos, lengths - 2) + 2)
            ops[s, :, b, 6] = np.where(do_insert, ins_len, 0)
            lengths = np.where(do_insert, lengths + ins_len, lengths - 2)
            seq += 1
    # MSN floor: everything applied so far is below the window.
    min_seqs = np.broadcast_to(
        (np.arange(S, dtype=np.int64)[:, None] + 1) * B, (S, D)
    ).astype(np.int32)
    # Layout: the doc axis must be minor ([S,B,F,D]) — trailing dims of 8
    # would be lane-padded to 128 on TPU (16x memory blowup on upload).
    ops = np.ascontiguousarray(np.moveaxis(ops, 1, -1))
    payloads = np.ascontiguousarray(np.moveaxis(payloads, 1, -1))
    return ops, payloads, min_seqs


def zipf_counts(n_docs: int, ops_per_step: int, a: float) -> np.ndarray:
    """Per-doc op counts by Zipf rank (doc 0 busiest, floor 1) — shared by
    the trace generator and config3's lane-boundary computation so the two
    can never diverge."""
    w = (np.arange(n_docs, dtype=np.float64) + 1.0) ** (-a)
    return np.maximum(1, np.round(ops_per_step * w / w[0]).astype(np.int64))


def generate_multiwriter(
    n_docs, ops_per_step, n_steps, writers, ins_len, payload_len,
    zipf_a=0.0, seed=0,
):
    """Multi-writer concurrent traces with REAL ref_seq lag.

    Each step is one round: every op in it stamps ref_seq at the previous
    round's last seq, so ops from different writers in a round are mutually
    concurrent and the kernel rebases them on apply.  Validity by
    construction: slots alternate per-writer (insert at a uniformly random
    own-perspective position) / (remove 2 chars of that same insert) — a
    writer only ever removes content it inserted, so no cross-writer
    position can be invalidated.

    ``zipf_a`` > 0 skews per-doc op counts by Zipf rank (doc 0 busiest);
    idle slots are NOOPs, so the device step models the real straggler
    problem (busiest doc dictates the step, the rest ride along).

    Returns ops[S,B,8,D], payloads[S,B,L,D], min_seqs[S,D], real_ops.
    """
    from fluidframework_tpu.ops import mergetree_kernel as mk

    rng = np.random.default_rng(seed)
    D, B, S, L, W = n_docs, ops_per_step, n_steps, payload_len, writers
    ops = np.zeros((S, D, B, mk.OP_FIELDS), np.int32)
    payloads = rng.integers(97, 123, size=(S, D, B, L), dtype=np.int32)

    if zipf_a > 0:
        counts = zipf_counts(D, B, zipf_a)
    else:
        counts = np.full((D,), B, np.int64)

    lengths = np.zeros((D,), np.int64)     # converged length at round start
    seq = np.zeros((D,), np.int64)         # last assigned seq per doc
    min_seqs = np.zeros((S, D), np.int32)
    real_ops = 0
    for s in range(S):
        ref = seq.copy()                   # round boundary = everyone's refSeq
        base = lengths.copy()              # round-start converged snapshot
        own_extra = np.zeros((D, W), np.int64)  # own-perspective growth
        pair_pos = np.zeros((D, W), np.int64)   # writer's last insert position
        for b in range(B):
            wtr = b % W
            active = b < counts
            # The op's perspective: the round-start snapshot plus THIS
            # writer's earlier ops in the round (other writers' same-round
            # ops are concurrent and invisible to it).
            own_len = base + own_extra[:, wtr]
            if b // W % 2 == 0:
                # Insert ins_len chars at a random own-perspective position.
                pos = (rng.random(D) * (own_len + 1)).astype(np.int64)
                pos = np.minimum(pos, own_len)
                pair_pos[:, wtr] = pos
                seq += active
                ops[s, :, b, 0] = np.where(active, mk.OpKind.INSERT, mk.OpKind.NOOP)
                ops[s, :, b, 1] = seq
                ops[s, :, b, 2] = wtr
                ops[s, :, b, 3] = ref
                ops[s, :, b, 4] = pos
                ops[s, :, b, 6] = ins_len
                own_extra[:, wtr] += np.where(active, ins_len, 0)
            else:
                # Remove 2 chars of this writer's own previous insert.
                pos = pair_pos[:, wtr]
                seq += active
                ops[s, :, b, 0] = np.where(active, mk.OpKind.REMOVE, mk.OpKind.NOOP)
                ops[s, :, b, 1] = seq
                ops[s, :, b, 2] = wtr
                ops[s, :, b, 3] = ref
                ops[s, :, b, 4] = pos
                ops[s, :, b, 5] = pos + 2
                own_extra[:, wtr] -= np.where(active, 2, 0)
            real_ops += int(active.sum())
        lengths = base + own_extra.sum(axis=1)
        min_seqs[s] = ref  # window floor: everything below this round
    ops = np.ascontiguousarray(np.moveaxis(ops, 1, -1))
    payloads = np.ascontiguousarray(np.moveaxis(payloads, 1, -1))
    return ops, payloads, min_seqs, real_ops


# ---------------------------------------------------------------------------
# Shared device runner (merge-tree fleet)
# ---------------------------------------------------------------------------

def _mergetree_run(args, D, gen, metric, lane_k: int | None = None):
    """Time a jitted scan of the merge-tree fleet over a generated trace.

    ``lane_k`` enables the two-lane straggler split for skewed fleets: the
    K busiest documents (front of the doc axis) run the full B-op scan,
    the long tail runs a 1-op scan — a Zipf tail doc carries one real op
    per step, and sweeping its state through HBM for all B scan iterations
    is pure bandwidth waste (the step cost is per-iteration state traffic,
    and HBM is the bottleneck)."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops import mergetree_kernel as mk

    B = args.ops_per_step
    proto = mk.init_state(
        max_segments=args.segments,
        remove_slots=4,
        prop_slots=2,
        text_capacity=args.text_capacity,
    )

    def _broadcast(n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), proto)

    def fresh_state():
        # Broadcast on device: no host->device bulk transfer (re-uploading
        # GB-scale state per rep would swamp everything).
        if lane_k is None:
            return _broadcast(D)
        return (_broadcast(lane_k), _broadcast(D - lane_k))

    import functools

    ce = args.compact_every

    def make_scan(ob_static: bool):
        """The whole run specialized on a STATIC obliterate flag: the
        common no-obliterate trace is one fully-fused, fully-donated scan.
        (A per-step lax.cond forces whole-state copies across the branch
        boundary — measured ~37% of the headline.)"""
        def apply_batch(s, ops, payloads):
            # ops: [B, F, lane docs]; the row loop ends at the lane's
            # deepest queue (mk.apply_fleet_ops).
            return mk.apply_fleet_ops(
                s, jnp.moveaxis(ops, -1, 0), jnp.moveaxis(payloads, -1, 0),
                ob_static,
            )

        compact_batch = jax.vmap(
            lambda s, m: mk.compact(mk.set_min_seq(s, m), ob_static)
        )

        def step_lane(s, ops, payloads, min_seqs, i):
            s = apply_batch(s, ops, payloads)
            return jax.lax.cond(
                (i + 1) % ce == 0,
                lambda s: compact_batch(s, min_seqs),
                lambda s: s,
                s,
            )

        def scan(state, all_ops, all_payloads, all_minseqs):
            def body(carry, xs):
                s, i = carry
                ops, payloads, min_seqs = xs
                if lane_k is None:
                    s = step_lane(s, ops, payloads, min_seqs, i)
                else:
                    sA, sB = s
                    sA = step_lane(
                        sA, ops[:, :, :lane_k], payloads[:, :, :lane_k],
                        min_seqs[:lane_k], i,
                    )
                    # Tail lane: only op slot 0 is ever populated.
                    sB = step_lane(
                        sB, ops[:1, :, lane_k:], payloads[:1, :, lane_k:],
                        min_seqs[lane_k:], i,
                    )
                    s = (sA, sB)
                return (s, i + 1), None

            (s, _), _ = jax.lax.scan(
                body,
                (state, jnp.zeros((), jnp.int32)),
                (all_ops, all_payloads, all_minseqs),
            )
            return s

        return scan

    # HOST-side dispatch between the two specializations: the trace is
    # host-built, so whether it contains obliterates is known before
    # launch. A device-side lax.cond would defeat the scan carry's
    # in-place aliasing (the whole [D,...] state re-copies per step —
    # measured ~40% of the headline) and a fresh bench state has an empty
    # ob table by construction.
    # Warmup and timed runs must share the SAME shapes, or jit re-traces and
    # the timed region would include a fresh XLA compile.
    ops, payloads, min_seqs, real_ops = gen()
    if lane_k is not None:
        assert not (ops[:, 1:, 0, lane_k:] != 0).any(), (
            "tail-lane docs must only use op slot 0"
        )
    has_ob = bool((ops[:, :, 0, :] == mk.OpKind.OBLITERATE).any())
    runner = jax.jit(make_scan(has_ob), donate_argnums=(0,))
    w = args.steps
    dev_w = (jnp.asarray(ops[:w]), jnp.asarray(payloads[:w]), jnp.asarray(min_seqs[:w]))
    dev_t = (jnp.asarray(ops[w:]), jnp.asarray(payloads[w:]), jnp.asarray(min_seqs[w:]))

    # Best of N timed reps: a single rep can catch a contention dip an
    # order of magnitude below steady state.  Each rep replays the
    # identical trace on a fresh state.
    dt = float("inf")
    errors = 0
    for _rep in range(args.reps):
        st = runner(fresh_state(), *dev_w)  # compiles once; warms every rep
        jax.block_until_ready(st)
        t0 = time.perf_counter()
        st = runner(st, *dev_t)
        jax.block_until_ready(st)
        dt = min(dt, time.perf_counter() - t0)
        # DocState is a NamedTuple (tuple subclass): only a PLAIN tuple
        # marks the two-lane carry.
        lanes = st if type(st) is tuple else (st,)
        errors = sum(int(np.asarray(jnp.sum(s.error != 0))) for s in lanes)
    ops_per_sec = (real_ops // 2) / dt  # generators emit 2*steps, half timed
    result = {
        "metric": metric,
        "value": round(ops_per_sec, 1),
        "unit": "ops/s",
        "vs_baseline": round(ops_per_sec / 1e6, 4),
    }
    if errors:
        result["error_docs"] = errors
    return result


def _string_ingest_rate(n_docs, rounds, writers, seed=0, megastep_k=8,
                        batch=True):
    """Host-ingest-inclusive rate: wire messages -> DocBatchEngine -> device.

    Measures the HOST feed rate: wire-shaped decode, op encoding, and
    landing in the per-doc staging queues.  ``batch=True`` (default — the
    production path) feeds the whole trace through the columnar
    ``ingest_batch`` fast path; ``batch=False`` measures the legacy
    per-message ``ingest`` walk for the before/after delta.

    The device drain runs OUTSIDE the timed region: the megastep ``step``
    (ISSUE 4) blocks on its on-device error readback, so timing it here
    would measure device compute (config3's ``value`` /
    ``wire_drain_ops_per_sec`` already do) — whereas the pre-megastep
    ``step`` this probe's r<=5 numbers included dispatched asynchronously
    and cost the timer almost nothing.  Megastep amortization rides along
    in ``engine_health`` (``steps_per_dispatch`` / ``megastep_k`` /
    ``staging_overlap_packs`` / ``ingest_batch_rows``).
    """
    from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    rng = np.random.default_rng(seed)
    eng = DocBatchEngine(
        n_docs, max_segments=4096, text_capacity=32768, max_insert_len=16,
        ops_per_step=16, use_mesh=False, recovery="off",
        megastep_k=megastep_k,
    )
    msgs: list[tuple[int, SequencedMessage]] = []
    for d in range(n_docs):
        for w in range(writers):
            eng.ingest(d, SequencedMessage(
                seq=0, min_seq=0, ref_seq=0, client_id=f"w{w}",
                client_seq=0, type=MessageType.JOIN,
                contents={"clientId": f"w{w}", "short": w},
            ))
    lengths = np.zeros((n_docs,), np.int64)
    seqs = np.zeros((n_docs,), np.int64)
    n_ops = 0
    for r in range(rounds):
        refs = seqs.copy()
        for w in range(writers):
            for d in range(n_docs):
                # Valid in the op's OWN perspective: the round-start snapshot
                # plus this writer's earlier ops (one op per writer per round
                # here, so just the snapshot).
                pos = int(rng.integers(0, lengths[d] + 1))
                seqs[d] += 1
                msgs.append(
                    (d, SequencedMessage(
                        seq=int(seqs[d]), min_seq=int(refs[d]),
                        ref_seq=int(refs[d]), client_id=f"w{w}", client_seq=r,
                        type=MessageType.OP,
                        contents={"type": 0, "pos1": pos, "seg": "abcd"},
                    ))
                )
                n_ops += 1
        lengths += 4 * writers  # converged growth lands at the round boundary
    # Warm the device program (one padded batch step) so the timed region
    # measures the steady feed path, not the first XLA compile.
    warm, msgs = msgs[: n_docs * writers], msgs[n_docs * writers :]
    n_ops -= len(warm)
    for d, m in warm:
        eng.ingest(d, m)
    eng.step()
    t0 = time.perf_counter()
    if batch:
        eng.ingest_batch([d for d, _ in msgs], [m for _, m in msgs])
    else:
        for d, m in msgs:
            eng.ingest(d, m)
    dt = time.perf_counter() - t0
    eng.step()
    assert not eng.errors().any()
    # Degraded-mode health counters ride along so BENCH artifacts track
    # quarantine/checkpoint/watchdog behavior release over release.
    return round(n_ops / dt, 1), eng.health()


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------

def _copy_args(args):
    """Configs tune their own defaults; never leak them into later configs
    of a --config all run."""
    out = argparse.Namespace(**vars(args))
    return out


def _scribe_probe(n_docs: int = 8, ops_per_doc: int = 64) -> dict:
    """Drive the scribe service over a synthetic op topic and report its
    health counters (summaries written, handle reuse, ack floor ages, log
    bytes reclaimed by compaction) so BENCH artifacts track the
    summarize -> ack -> compact loop release over release."""
    import contextlib
    import tempfile

    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )
    from fluidframework_tpu.server.ordered_log import ConsumerGroup, DurableTopic
    from fluidframework_tpu.server.scribe import ScribeConfig, ScribeLambda

    stack = contextlib.ExitStack()
    tmp = stack.enter_context(tempfile.TemporaryDirectory(prefix="bench-scribe-"))
    topic = DurableTopic(
        "deltas", 2, os.path.join(tmp, "log"),
        encode=lambda m: m.to_json(), decode=SequencedMessage.from_json,
    )
    stack.callback(topic.close)
    rng = np.random.default_rng(0)
    lengths = [0] * n_docs
    for d in range(n_docs):
        topic.produce(f"doc{d}", SequencedMessage(
            seq=0, min_seq=0, ref_seq=0, client_id="w0", client_seq=0,
            type=MessageType.JOIN, contents={"clientId": "w0", "short": 0},
        ))
    for s in range(1, ops_per_doc + 1):
        for d in range(n_docs):
            pos = int(rng.integers(0, lengths[d] + 1))
            topic.produce(f"doc{d}", SequencedMessage(
                seq=s, min_seq=0, ref_seq=s - 1, client_id="w0", client_seq=s,
                type=MessageType.OP,
                contents={"type": 0, "pos1": pos, "seg": "abcd"},
            ))
            lengths[d] += 4
    scribe = ScribeLambda(topic, os.path.join(tmp, "scribe"),
                          config=ScribeConfig(max_ops=16))
    stack.callback(scribe.close)
    fleet = ConsumerGroup(topic, "fleet", os.path.join(tmp, "scribe"))
    fleet.join("bench")
    t0 = time.perf_counter()
    n = scribe.pump()
    dt = time.perf_counter() - t0
    for p, rec in fleet.consume("bench"):
        fleet.commit(p, rec.offset + 1)
    scribe.compact(extra_groups=(fleet,))
    out = scribe.health()
    out["records_per_sec"] = round(n / dt, 1) if dt else None
    stack.close()  # closes scribe + topic + removes the tempdir
    return out


def _engine_round_driver(n_docs: int, megastep_k: int, seed: int = 0):
    """A per-round engine pipeline driver (ingest_batch + step per round —
    the production cadence, so every round crosses the instrumented
    ingest/upload/dispatch/readback phases): yields (engine, run_fn) where
    ``run_fn(n_rounds)`` returns the wall seconds for that many rounds."""
    from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    rng = np.random.default_rng(seed)
    # recovery="grow" (the production default): step() runs the error-latch
    # readback, so traces carry the full ingest -> upload -> dispatch ->
    # readback phase chain.
    eng = DocBatchEngine(
        n_docs, max_segments=4096, text_capacity=32768, max_insert_len=16,
        ops_per_step=16, use_mesh=False, recovery="grow",
        megastep_k=megastep_k,
    )
    for d in range(n_docs):
        eng.ingest(d, SequencedMessage(
            seq=0, min_seq=0, ref_seq=0, client_id="w0", client_seq=0,
            type=MessageType.JOIN, contents={"clientId": "w0", "short": 0},
        ))
    lengths = np.zeros((n_docs,), np.int64)
    seqs = np.zeros((n_docs,), np.int64)
    rounds_iter = [0]

    def one_round():
        r = rounds_iter[0]
        rounds_iter[0] += 1
        idxs, msgs = [], []
        for d in range(n_docs):
            pos = int(rng.integers(0, lengths[d] + 1))
            seqs[d] += 1
            idxs.append(d)
            msgs.append(SequencedMessage(
                seq=int(seqs[d]), min_seq=0, ref_seq=int(seqs[d]) - 1,
                client_id="w0", client_seq=r, type=MessageType.OP,
                contents={"type": 0, "pos1": pos, "seg": "abcd"},
                timestamp=time.time(),  # as the sequencer stamps it
            ))
            lengths[d] += 4
        eng.ingest_batch(idxs, msgs)
        eng.step()

    one_round()  # warm the compiled step outside any timer
    # The warmup round's latency samples include the XLA compile; reset so
    # the reported percentiles describe the steady pipeline.
    eng.op_clock = type(eng.op_clock)(eng.n_shards, eng.shard_of)

    def run(n_rounds: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            one_round()
        return time.perf_counter() - t0

    return eng, run, n_docs


_OBS_ROW: dict | None = None


def _observability_row(megastep_k: int = 8) -> dict:
    """The per-config observability attachment (ISSUE 7, cached once per
    process): op end-to-end latency percentiles and per-phase wall-time
    shares, measured by driving a small engine pipeline under a flight
    recorder.  Attached to every config row so each artifact line carries
    ``latency_p50_ms``/``latency_p99_ms``/``phase_shares``."""
    global _OBS_ROW
    if _OBS_ROW is None:
        from fluidframework_tpu.observability import (
            FlightRecorder,
            install,
            recorder,
            uninstall,
        )
        from fluidframework_tpu.observability.flight_recorder import (
            phase_shares,
        )

        rec = recorder()
        own = rec is None
        if own:
            rec = install(FlightRecorder(1 << 16))
        try:
            mark = len(rec.events())
            eng, run, _docs = _engine_round_driver(16, megastep_k)
            run(32)
            health = eng.health()
            _OBS_ROW = {
                "latency_p50_ms": health.get("latency_p50_ms"),
                "latency_p99_ms": health.get("latency_p99_ms"),
                "phase_shares": phase_shares(rec.events()[mark:]),
                "recompiles": health.get("recompiles", 0),
            }
        finally:
            if own:
                uninstall()
    return dict(_OBS_ROW)


def _attach_observability(res: dict, megastep_k: int = 8) -> dict:
    """Merge the shared observability row into one config result (never
    sinks the row; an error lands as ``observability_error``)."""
    try:
        for key, val in _observability_row(megastep_k).items():
            res.setdefault(key, val)
    except Exception as e:  # noqa: BLE001 — observability must not sink configs
        res.setdefault("observability_error", repr(e)[-200:])
    return res


def _recorder_overhead(
    megastep_k: int = 8, rounds: int = 24, reps: int = 4
) -> dict:
    """Measured recorder overhead budget (ISSUE 7 acceptance): the same
    engine pipeline (ingest_batch + megastep per round) timed with the
    flight recorder OFF vs ON.  The two modes INTERLEAVE (one engine each,
    alternating chunks) and each takes its best-of-``reps`` — the same
    contention defense every probe in this file uses; a sequential
    off-then-on pair minutes apart on a shared box measures drift, not
    instrumentation.  Spans are per phase per dispatch, so the real cost
    is a few microseconds against a multi-ms dispatch."""
    from fluidframework_tpu.observability import (
        FlightRecorder,
        install,
        recorder,
        uninstall,
    )

    had = recorder()
    try:
        uninstall()
        eng_off, run_off, n_docs = _engine_round_driver(16, megastep_k,
                                                        seed=1)
        install(FlightRecorder(1 << 16))
        eng_on, run_on, _ = _engine_round_driver(16, megastep_k, seed=1)
        best = {"off": float("inf"), "on": float("inf")}
        for _rep in range(reps):
            uninstall()
            best["off"] = min(best["off"], run_off(rounds))
            install(FlightRecorder(1 << 16))
            best["on"] = min(best["on"], run_on(rounds))
    finally:
        # The caller's recorder (bench --trace) must survive any probe
        # failure — never leave it uninstalled or shadowed by a probe ring.
        if had is not None:
            install(had)
        else:
            uninstall()
    off = rounds * n_docs / best["off"]
    on = rounds * n_docs / best["on"]
    return {
        "ops_per_sec_recorder_off": round(off, 1),
        "ops_per_sec_recorder_on": round(on, 1),
        "overhead_pct": round(max(0.0, (off - on) / off) * 100, 2),
    }


def _megastep_probe(megastep_k: int = 8, n_docs: int = 16) -> dict:
    """Drive a megastep-enabled DocBatchEngine over deep queues and report
    the realized dispatch amortization (ISSUE 4 headline surface): the
    counters that prove the fused pipeline is on and fusing
    (``steps_per_dispatch`` > 1), plus the staging double-buffer behavior."""
    # rounds sized so each doc's queue is >= megastep_k slices deep at the
    # drain (B=16 ops per slice in _string_ingest_rate), letting adaptive
    # K reach the configured cap.
    _rate, health = _string_ingest_rate(
        n_docs, rounds=max(16 * megastep_k, 8), writers=1,
        megastep_k=megastep_k,
    )
    return {
        key: health.get(key)
        for key in (
            "megastep_k", "steps_per_dispatch", "megastep_dispatches",
            "megastep_slices", "staging_overlap_packs",
            "staging_aliased_swaps",
        )
    }


def bench_headline(args) -> dict:
    """Driver headline: config 3's single-writer form (round-comparable)."""
    D, B = args.docs, args.ops_per_step

    def gen():
        total = 2 * args.steps
        ops, payloads, min_seqs = generate_workload(
            D, B, total, args.insert_len, args.payload_len
        )
        return ops, payloads, min_seqs, 2 * args.steps * D * B

    out = _mergetree_run(args, D, gen, "mergetree_ops_per_sec_per_chip")
    try:
        out["scribe_health"] = _scribe_probe()
    except Exception as e:  # noqa: BLE001 — the probe must never sink the headline
        out["scribe_health"] = {"error": repr(e)[-200:]}
    try:
        out["megastep"] = _megastep_probe(args.megastep_k)
        out["steps_per_dispatch"] = out["megastep"]["steps_per_dispatch"]
        out["megastep_k"] = out["megastep"]["megastep_k"]
    except Exception as e:  # noqa: BLE001 — the probe must never sink the headline
        out["megastep"] = {"error": repr(e)[-200:]}
    try:
        # Measured observability budget: flight-recorder on vs off over the
        # instrumented engine pipeline (acceptance: overhead <= 3%).
        out["recorder_overhead"] = _recorder_overhead(args.megastep_k)
    except Exception as e:  # noqa: BLE001 — the probe must never sink the headline
        out["recorder_overhead"] = {"error": repr(e)[-200:]}
    try:
        out["static_analysis"] = _static_analysis_probe()
    except Exception as e:  # noqa: BLE001 — the probe must never sink the headline
        out["static_analysis"] = {"error": repr(e)[-200:]}
    return out


def _static_analysis_probe() -> dict:
    """fftpu-check over the package (pure AST, ~seconds): the artifact
    records that the tree the numbers came from was hazard-clean — and the
    per-rule counts + baseline size when it wasn't."""
    from pathlib import Path

    from fluidframework_tpu.analysis.cli import run_all

    result = run_all(Path(__file__).resolve().parent / "fluidframework_tpu")
    return {
        "clean": not result["findings"],
        "counts": result["counts"],
        "n_baselined": len(result["suppressed"]),
        "n_stale_baseline": len(result["stale_baseline"]),
        "n_modules": result["n_modules"],
        # Per-pass wall time: the gate's own budget, tracked next to the
        # numbers it guards (the suite is 11 passes now — a pass that
        # quietly goes quadratic should show up in the artifact, not in
        # someone's pre-commit patience).
        "pass_times_ms": result["pass_times_ms"],
    }


def _seg_replay_rate(args, n_shards: int) -> dict:
    """Config-1's trace through the SEGMENT-PARALLEL serving path: one hot
    document, its merge-tree segment arrays block-sharded over a ``segs``
    mesh axis of ``n_shards`` devices, applied by the seg-parallel megastep
    (ops.mergetree_kernel.apply_megastep_seg under shard_map) — the 2-D
    docs x segs answer to the worst number on the board (one viral doc
    serializing a lane).  The warmup half grows the doc (with periodic
    re-blocks: growth from empty lands on the tail shard until a rebalance
    spreads it); the timed half replays on the balanced layout, exactly as
    production serves a long-lived hot doc between rebalance points.
    Reports the seg-path rate, the single-lane rate ON THE SAME TRACE, the
    ratio, and a full byte-identity check of the final states (the
    single-lane path is the oracle)."""
    import functools

    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops import mergetree_kernel as mk
    from fluidframework_tpu.parallel import mesh as pm

    devs = jax.devices()
    if len(devs) < n_shards:
        return {
            "segment_shards": n_shards, "ok": False,
            "reason": f"only {len(devs)} devices visible",
        }
    mesh = pm.docs_segs_mesh(devs[:n_shards], seg_shards=n_shards)
    B = args.ops_per_step
    ops, payloads, _min_seqs, real_ops = generate_multiwriter(
        1, B, 2 * args.steps, 4, args.insert_len, args.payload_len
    )
    # Doc-minor [S, B, F, 1] -> single-doc [S, B, F].
    ops3 = np.ascontiguousarray(ops[..., 0])
    pays3 = np.ascontiguousarray(payloads[..., 0])
    w = args.steps
    # Host-side proto: the single-lane runner donates its state, so every
    # rep re-uploads a fresh copy from numpy.
    proto = jax.tree.map(np.asarray, mk.init_state(
        max_segments=args.segments, remove_slots=4, prop_slots=2,
        text_capacity=args.text_capacity,
    ))

    # Single-lane oracle runner: the same [K, B] scan shape, one device.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def single_run(s, o, p):
        def body(st, xs):
            return mk.apply_ops(st, xs[0], xs[1], False), None

        out, _ = jax.lax.scan(body, s, (o, p))
        return out

    s_local = args.segments // n_shards
    specs = pm.seg_state_specs(proto)
    prog = pm.mesh_seg_program(mk.apply_megastep_seg, mesh, specs)

    def seg_warm_state():
        """Grow the doc through the warmup half with a re-block per
        quarter (bounds the tail-shard skew), ending balanced."""
        st = pm.shard_seg_state(
            mk.seg_shard_state(proto, n_shards, s_local), mesh
        )
        q = max(1, w // 4)
        for i in range(0, w, q):
            # Clamp to the warmup half: an unclamped last chunk would
            # re-apply the first timed slice(s) whenever w % q != 0,
            # double-applying ops on the seg path only.
            end = min(i + q, w)
            st = prog(
                st, jnp.asarray(ops3[i:end]), jnp.asarray(pays3[i:end])
            )
            st = pm.shard_seg_state(
                mk.seg_rebalance_state(
                    jax.tree.map(np.asarray, st), s_local=s_local
                ),
                mesh,
            )
        return st

    dev_t = (jnp.asarray(ops3[w:]), jnp.asarray(pays3[w:]))
    # Warm the TIMED [w, B, F] shape once: seg_warm_state compiles only
    # q-sized chunks, so with --reps 1 the first timed dispatch would pay
    # the full jit(shard_map) compile inside the timer — while the
    # single-lane runner's warmup call already uses its timed shape.
    jax.block_until_ready(prog(seg_warm_state(), *dev_t).text_end)
    best_seg = float("inf")
    seg_final = None
    for _rep in range(max(1, min(args.reps, 3))):
        st = seg_warm_state()
        jax.block_until_ready(st.text_end)
        t0 = time.perf_counter()
        st = prog(st, *dev_t)
        jax.block_until_ready(st.text_end)
        best_seg = min(best_seg, time.perf_counter() - t0)
        seg_final = st
    best_single = float("inf")
    single_final = None
    for _rep in range(max(1, min(args.reps, 3))):
        st = single_run(
            jax.tree.map(jnp.asarray, proto),
            jnp.asarray(ops3[:w]), jnp.asarray(pays3[:w]),
        )
        jax.block_until_ready(st.text_end)
        t0 = time.perf_counter()
        st = single_run(st, *dev_t)
        jax.block_until_ready(st.text_end)
        best_single = min(best_single, time.perf_counter() - t0)
        single_final = st
    timed_ops = real_ops // 2
    a = mk.canonical_doc(single_final)
    b = mk.canonical_doc(mk.seg_gather_state(jax.tree.map(np.asarray, seg_final)))
    identical = all(np.array_equal(a[k], b[k]) for k in a)
    seg_rate = timed_ops / best_seg
    single_rate = timed_ops / best_single
    return {
        "segment_shards": n_shards,
        "ok": True,
        "seg_ops_per_sec": round(seg_rate, 1),
        "singlelane_ops_per_sec": round(single_rate, 1),
        "seg_speedup": round(seg_rate / single_rate, 3),
        "seg_identity": bool(identical),
        "errors": int(np.asarray(seg_final.error)),
    }


def bench_config1(args) -> dict:
    """Config 1: SharedString single-doc replay (BASELINE.md row 1): one
    document, 4 concurrent writers, sequential device scan — the per-doc
    replay rate (ref client.replay.spec.ts workloads).  With
    ``--seg-shards N`` the row also records the SEGMENT-PARALLEL replay of
    the same trace over an N-shard segs axis (``seg_ops_per_sec`` /
    ``seg_speedup`` / byte-identity vs the single lane)."""
    args = _copy_args(args)
    if not args.segments_explicit:
        # A long replay on ONE doc: segment count grows with the whole
        # trace, so the single replica needs the fleet's headroom.
        args.segments = 16384
    if not args.tc_explicit:
        args.text_capacity = 131072

    def gen():
        return generate_multiwriter(
            1, args.ops_per_step, 2 * args.steps, 4,
            args.insert_len, args.payload_len,
        )

    out = _mergetree_run(args, 1, gen, "config1_singledoc_replay_ops_per_sec")
    if args.seg_shards > 1:
        try:
            seg = _seg_replay_rate(args, args.seg_shards)
            out["segment"] = seg
            if seg.get("ok"):
                out["segment_shards"] = seg["segment_shards"]
                out["seg_ops_per_sec"] = seg["seg_ops_per_sec"]
        except Exception as e:  # noqa: BLE001 — probe must not sink the row
            out["segment"] = {"error": repr(e)[-300:]}
    out["ingest_ops_per_sec"], out["engine_health"] = _string_ingest_rate(
        1, rounds=64, writers=4, megastep_k=args.megastep_k
    )
    return out


def bench_config3(args) -> dict:
    """Config 3 as written: 10k docs, Zipf-skewed op counts, 4 writers per
    doc with real ref_seq lag.  Per-doc capacity is halved vs the headline
    so the 10k-doc fleet state fits one chip's HBM."""
    args = _copy_args(args)
    if not args.docs_explicit:
        args.docs = 10_000
    if not args.segments_explicit:
        args.segments = 1024
    if not args.tc_explicit:
        args.text_capacity = 8192
    if not args.steps_explicit:
        args.steps = min(args.steps, 12)
    D = args.docs

    def gen():
        return generate_multiwriter(
            D, args.ops_per_step, 2 * args.steps, 4,
            args.insert_len, args.payload_len, zipf_a=1.1,
        )

    # Two-lane straggler split: docs whose Zipf op count exceeds 1 run the
    # full B-op scan; the long tail (1 op/step) runs a 1-op scan. The
    # boundary comes from the same count law the generator uses, rounded
    # up to a 128-lane multiple (doc is the minor/lane axis on TPU).
    counts = zipf_counts(D, args.ops_per_step, 1.1)
    busy = int(np.sum(counts > 1))
    lane_k = min(max(-(-busy // 128) * 128, 128), D)
    out = _mergetree_run(
        args, D, gen, "config3_mergetree_zipf_ops_per_sec_per_chip",
        lane_k=lane_k if lane_k < D else None,
    )
    out["docs"] = D
    if lane_k < D:
        out["lanes"] = [lane_k, D - lane_k]
    out["ingest_ops_per_sec"], out["engine_health"] = _string_ingest_rate(
        min(D, 128), rounds=16, writers=4, megastep_k=args.megastep_k
    )
    # The columnar fast path IS the default ingest now; the named probe
    # keeps the artifact self-describing, and the per-message rate shows
    # the batch-vs-walk delta release over release.
    out["ingest_batch_ops_per_sec"] = out["ingest_ops_per_sec"]
    out["ingest_per_msg_ops_per_sec"], _ = _string_ingest_rate(
        min(D, 128), rounds=16, writers=4, megastep_k=args.megastep_k,
        batch=False,
    )
    native = _native_ingest_rate()
    if native is not None:
        out["native_ingest_ops_per_sec"] = native
    wire = _wire_ingest_rate()
    if wire is not None:
        out["wire_ingest_ops_per_sec"] = wire[0]
        out["wire_drain_ops_per_sec"] = wire[1]
    return out


def _wire_ingest_rate(
    n_docs: int = 4, writers: int = 2, rounds: int = 400
) -> tuple[float, float] | None:
    """Wire-bytes -> device through the PRODUCT stack: netserver firehose
    over real TCP -> FleetConsumer -> native/ingest.cpp -> batched device
    step (VERDICT r3 weak #4).  Two waves: wave 1 warms the consumer and
    the engine's compiled step; wave 2 (pre-sequenced, buffered by the
    server's consumer queue) is the timed region.  Returns (end-to-end
    rate incl. the batched device apply, drain rate bytes->staged rows) —
    the second is the one comparable to native_ingest_ops_per_sec, which
    measures the encoder alone (VERDICT r4 next #4)."""
    from fluidframework_tpu.dds.shared_string import SharedString
    from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
    from fluidframework_tpu.native.ingest_native import available
    from fluidframework_tpu.server.fleet_consumer import FleetConsumer
    from fluidframework_tpu.server.netserver import NetworkServer

    if not available():
        return None
    rng = np.random.default_rng(0)
    srv = NetworkServer().start()
    try:
        fleets = []
        for i in range(n_docs):
            with srv.lock:
                doc = srv.service.document(f"d{i}")
                ws = []
                for w in range(writers):
                    c = SharedString(client_id=f"d{i}w{w}")
                    doc.connect(c.client_id, c.process)
                    ws.append(c)
                doc.process_all()
            fleets.append((f"d{i}", ws))

        def wave(n_rounds: int) -> int:
            rows = 0
            for _r in range(n_rounds):
                for doc_id, ws in fleets:
                    with srv.lock:
                        doc = srv.service.document(doc_id)
                        for c in ws:
                            n = len(c.text)
                            if rng.random() < 0.7 or n < 4:
                                c.insert_text(int(rng.integers(0, n + 1)), "abcd")
                            else:
                                p = int(rng.integers(0, n - 1))
                                c.remove_range(p, p + 1)
                            for m in c.take_outbox():
                                doc.submit(m)
                                rows += 1
                        doc.process_all()
            return rows

        warm_rows = wave(8)
        eng = DocBatchEngine(
            n_docs, max_segments=4096, text_capacity=65536, max_insert_len=8,
            ops_per_step=32, use_mesh=False, recovery="off",
        )
        fc = FleetConsumer("127.0.0.1", srv.port, eng, [d for d, _ in fleets])
        try:
            fc.run_for(warm_rows)  # drains catch-up + compiles the step
            timed_rows = wave(rounds)  # buffered by the consumer queue
            time.sleep(0.25)  # let the producer-side writer threads settle
            t0 = time.perf_counter()
            idle = 0
            while fc.rows_staged < warm_rows + timed_rows:
                if fc.pump(0.005) == 0:
                    idle += 1
                    if idle >= 2000:
                        return None
                else:
                    idle = 0
            t_drain = time.perf_counter() - t0
            fc.step()
            dt = time.perf_counter() - t0
            if eng.errors().any():
                return None
            return round(timed_rows / dt, 1), round(timed_rows / t_drain, 1)
        finally:
            fc.close()
    finally:
        srv.stop()


def _native_ingest_rate(n_ops: int = 200_000) -> float | None:
    """Wire JSON-lines -> op tensors through the C++ encoder
    (native/ingest.cpp) — the production byte-stream feed rate."""
    from fluidframework_tpu.native.ingest_native import (
        NativeIngestEncoder,
        available,
    )
    from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage

    if not available():
        return None
    rng = np.random.default_rng(0)
    lines = [
        SequencedMessage(
            seq=0, min_seq=0, ref_seq=0, client_id="w", client_seq=0,
            type=MessageType.JOIN, contents={"clientId": "w", "short": 0},
        ).to_json()
    ]
    length = 0
    for i in range(n_ops):
        pos = int(rng.integers(0, length + 1))
        lines.append(
            SequencedMessage(
                seq=i + 1, min_seq=0, ref_seq=i, client_id="w", client_seq=i,
                type=MessageType.OP,
                contents={"type": 0, "pos1": pos, "seg": "abcd"},
            ).to_json()
        )
        length += 4
    data = ("\n".join(lines) + "\n").encode()
    enc = NativeIngestEncoder(64, 4)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        ops, _payloads = enc.encode(data)
        best = min(best, time.perf_counter() - t0)
    assert len(ops) == n_ops
    return round(n_ops / best, 1)


def bench_config2(args) -> dict:
    """Config 2: SharedMap LWW, one map, 256 concurrent setters
    (BASELINE.md row 2; ref mapKernel.ts LWW semantics)."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops import map_kernel as mpk

    rng = np.random.default_rng(0)
    K = 256
    B = 256  # one op per writer per round
    S = args.steps
    state = mpk.init_state(K)

    def make(S):
        kinds = rng.integers(1, 3, size=(S, B)).astype(np.int32)  # SET/DELETE
        keys = rng.integers(0, K, size=(S, B)).astype(np.int32)
        vals = rng.integers(0, 1 << 20, size=(S, B)).astype(np.int32)
        seqs = (np.arange(S * B, dtype=np.int32).reshape(S, B)) + 1
        return tuple(map(jnp.asarray, (kinds, keys, vals, seqs)))

    def run(state, kinds, keys, vals, seqs):
        def body(s, xs):
            return mpk.apply_batch(s, *xs), None

        out, _ = jax.lax.scan(body, state, (kinds, keys, vals, seqs))
        return out

    runner = jax.jit(run, donate_argnums=(0,))
    warm = make(S)
    timed = make(S)
    state = runner(state, *warm)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    state = runner(state, *timed)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    val = S * B / dt

    # Ingest-inclusive: host interning + array build per round.
    intern: dict[str, int] = {}
    apply_jit = jax.jit(mpk.apply_batch)
    state2 = mpk.init_state(K)

    def one_round(state2, n):
        kinds_l, keys_l, vals_l, seqs_l = [], [], [], []
        for _w in range(B):
            key = f"k{rng.integers(0, K)}"
            slot = intern.setdefault(key, len(intern) % K)
            kinds_l.append(1)
            keys_l.append(slot)
            vals_l.append(int(rng.integers(0, 1000)))
            seqs_l.append(n + 1)
            n += 1
        return apply_jit(
            state2,
            jnp.asarray(kinds_l, jnp.int32), jnp.asarray(keys_l, jnp.int32),
            jnp.asarray(vals_l, jnp.int32), jnp.asarray(seqs_l, jnp.int32),
        ), n

    state2, _ = one_round(state2, 0)  # warm the compile
    jax.block_until_ready(state2)
    t0 = time.perf_counter()
    n = 0
    for _r in range(32):
        state2, n = one_round(state2, n)
    jax.block_until_ready(state2)
    ingest = n / (time.perf_counter() - t0)

    return {
        "metric": "config2_map_lww_ops_per_sec",
        "value": round(val, 1),
        "unit": "ops/s",
        "vs_baseline": round(val / 1e6, 4),
        "writers": B,
        "ingest_ops_per_sec": round(ingest, 1),
    }


def bench_config4(args) -> dict:
    """Config 4: SharedMatrix 256x256, 64 writers (BASELINE.md row 4):
    cell-set storm from 64 concurrent writers + structural row/col edits
    from one writer (positions stay valid under every perspective)."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops import matrix_kernel as mxk

    rng = np.random.default_rng(0)
    B = 64
    S = args.steps
    W = 64
    state = mxk.init_state(max_rows=256, max_cols=256, max_segments=128)

    # Seed structure: 128 rows / 128 cols from writer 0 (sequenced first).
    seed_ops = np.zeros((2, mxk.MATRIX_OP_FIELDS), np.int32)
    seed_ops[0] = [mxk.MatrixOpKind.INSERT_ROWS, 1, 0, 0, 0, 128, 0, 0]
    seed_ops[1] = [mxk.MatrixOpKind.INSERT_COLS, 2, 0, 1, 0, 128, 0, 0]
    state = jax.jit(mxk.apply_ops)(state, jnp.asarray(seed_ops))

    def make(S, seq0):
        ops = np.zeros((S, B, mxk.MATRIX_OP_FIELDS), np.int32)
        seq = seq0
        for s in range(S):
            ref = seq
            for b in range(B):
                seq += 1
                ops[s, b] = [
                    mxk.MatrixOpKind.SET_CELL, seq, b % W, ref,
                    int(rng.integers(0, 128)), int(rng.integers(0, 128)),
                    int(rng.integers(0, 1 << 20)), 0,
                ]
        return jnp.asarray(ops), seq

    def run(state, all_ops):
        def body(s, ops):
            return mxk.apply_ops(s, ops), None

        out, _ = jax.lax.scan(body, state, all_ops)
        return out

    runner = jax.jit(run, donate_argnums=(0,))
    warm, seq = make(S, 2)
    timed, seq = make(S, seq)
    state = runner(state, warm)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    state = runner(state, timed)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    val = S * B / dt

    # Ingest-inclusive at the SAME compiled shape: host trace gen + upload +
    # the already-compiled runner.
    t0 = time.perf_counter()
    ops_np, _ = make(S, seq)
    state = runner(state, ops_np)
    jax.block_until_ready(state)
    ingest = S * B / (time.perf_counter() - t0)

    return {
        "metric": "config4_matrix_ops_per_sec",
        "value": round(val, 1),
        "unit": "ops/s",
        "vs_baseline": round(val / 1e6, 4),
        "writers": W,
        "ingest_ops_per_sec": round(ingest, 1),
    }


def bench_config5(args) -> dict:
    """Config 5: the REAL SharedTree pipeline (VERDICT r3 weak #3): D docs
    x 4 concurrent writers submitting sequenced nested edits with real
    ref_seq lag, flowing EditManager rebase (host) -> nested columnar
    forest apply (device) through TreeBatchEngine.

    "value" is the DEVICE phase rate (batch assembly + the jitted nested
    forest apply over everything staged); "pipeline_edits_per_sec" is the
    end-to-end rate including the host EditManager translation."""
    from fluidframework_tpu.dds.tree.changeset import (
        commit_to_json,
        make_insert,
        make_set_value,
    )
    from fluidframework_tpu.dds.tree.schema import leaf
    from fluidframework_tpu.models.tree_batch_engine import TreeBatchEngine
    from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage

    rng = np.random.default_rng(0)
    D = 16 if not args.docs_explicit else args.docs
    W = 4
    ROUNDS = max(2, args.steps // 4)
    OPS_PER_WRITER = 8

    def edit_msg(doc_seq, ref, writer, rev, change):
        return SequencedMessage(
            client_id=f"w{writer}", client_seq=rev, ref_seq=ref,
            seq=doc_seq, min_seq=max(0, ref - 1), type=MessageType.OP,
            contents={"type": "edit", "sid": f"s{writer}", "rev": rev,
                      "changes": commit_to_json([change])},
        )

    def rand_leaf():
        """Realistic mixed-type content: ~40% short strings (pool path),
        the rest ints — string leaves must ride the device path too
        (VERDICT r4 next #2)."""
        if rng.random() < 0.4:
            n = int(rng.integers(3, 11))
            return leaf("".join(chr(97 + int(c)) for c in rng.integers(0, 26, n)))
        return leaf(int(rng.integers(1000)))

    def make_stream():
        """One doc's sequenced stream: W writer-owned subtrees plus one
        SHARED subtree where concurrent inserts genuinely conflict and
        rebase against each other."""
        msgs = []
        seq = 0
        from fluidframework_tpu.dds.tree.forest import Node

        for w in range(W + 1):  # writer subtrees + the shared one
            seq += 1
            msgs.append(edit_msg(
                seq, seq - 1, 0, seq,
                make_insert([], "", w, [Node(type="obj", fields={
                    "kids": [leaf(0)]})]),
            ))
        revs = [seq] * W
        sizes = [1] * (W + 1)
        for _r in range(ROUNDS):
            ref = seq
            for w in range(W):
                for k in range(OPS_PER_WRITER):
                    seq += 1
                    revs[w] += 1
                    if k % 2 == 0:
                        # Conflicting concurrent insert in the shared tree.
                        msgs.append(edit_msg(
                            seq, ref, w, revs[w],
                            make_insert([("", W)], "kids", 0, [rand_leaf()]),
                        ))
                        sizes[W] += 1
                    else:
                        # Writer-local set/insert under its own subtree.
                        if rng.random() < 0.5 and sizes[w] > 0:
                            sv = rand_leaf().value
                            msgs.append(edit_msg(
                                seq, ref, w, revs[w],
                                make_set_value(
                                    [("", w), ("kids", int(rng.integers(sizes[w])))],
                                    sv),
                            ))
                        else:
                            msgs.append(edit_msg(
                                seq, ref, w, revs[w],
                                make_insert([("", w)], "kids",
                                            int(rng.integers(sizes[w] + 1)),
                                            [rand_leaf()]),
                            ))
                            sizes[w] += 1
        return msgs

    streams = [make_stream() for _ in range(D)]
    n_edits = sum(len(s) for s in streams)
    cap = max(2048, 2 * max(len(s) for s in streams))
    eng = TreeBatchEngine(D, capacity=cap, ops_per_step=32,
                          pool_capacity=8 * cap)

    t0 = time.perf_counter()
    for d, msgs in enumerate(streams):
        for m in msgs:
            eng.ingest(d, m)
    t_host = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.step()
    t_dev = time.perf_counter() - t0
    assert not eng.errors().any() and not eng.fallbacks
    assert eng.device_fraction() == 1.0

    # Object-mark oracle on the SAME streams (host fold only): the pooled
    # path's speedup + byte-identity, recorded side by side (PR 14 — the
    # mark_pool=False fold is the fuzz oracle, same pattern as plan_cache).
    oracle = TreeBatchEngine(D, capacity=cap, ops_per_step=32,
                             pool_capacity=8 * cap, mark_pool=False)
    t0 = time.perf_counter()
    for d, msgs in enumerate(streams):
        for m in msgs:
            oracle.ingest(d, m)
    t_oracle = time.perf_counter() - t0
    identity = all(
        json.dumps(eng.hosts[d].em.summarize(), sort_keys=True)
        == json.dumps(oracle.hosts[d].em.summarize(), sort_keys=True)
        for d in range(D)
    )

    # Device rebase window (PR 19): the same streams through a
    # device_rebase=True engine — kernel-vs-pooled byte-identity on every
    # doc summary plus the end-to-end ingest rate with the window fold on
    # the tensor plane (fallbacks counted in its health gauges).
    dev_reb = TreeBatchEngine(D, capacity=cap, ops_per_step=32,
                              pool_capacity=8 * cap, device_rebase=True)
    t0 = time.perf_counter()
    for d, msgs in enumerate(streams):
        for m in msgs:
            dev_reb.ingest(d, m)
    t_reb = time.perf_counter() - t0
    reb_identity = all(
        json.dumps(dev_reb.hosts[d].em.summarize(), sort_keys=True)
        == json.dumps(eng.hosts[d].em.summarize(), sort_keys=True)
        for d in range(D)
    )
    reb_health = dev_reb.health()

    # Kernel microbench: W >> 1 windows of multi-mark conflicting commits
    # in ONE warmed vmapped dispatch vs the pooled host fold on identical
    # windows — the [windows x commits] plane the per-doc serving path
    # (W=1 per dispatch) cannot show on its own.
    kern_speedup, kern_identity = _rebase_kernel_microbench(rng)

    health = eng.health()
    dev_rate = n_edits / t_dev
    pipeline = n_edits / (t_host + t_dev)
    out = {
        "metric": "config5_tree_device_edits_per_sec",
        "value": round(dev_rate, 1),
        "unit": "edits/s",
        "vs_baseline": round(dev_rate / 1e6, 4),
        "docs": D,
        "writers": W,
        "edits": n_edits,
        "pipeline_edits_per_sec": round(pipeline, 1),
        "host_translation_edits_per_sec": round(n_edits / t_host, 1),
        "oracle_host_edits_per_sec": round(n_edits / t_oracle, 1),
        "mark_pool_speedup": round(t_oracle / t_host, 2),
        "mark_pool_identity": identity,
        "mark_pool_hit_rate": health.get("mark_pool_hit_rate", 0.0),
        "pool_occupancy": health.get("pool_occupancy", 0.0),
        "translation_plan_hit_rate": health.get(
            "translation_plan_hit_rate", 0.0
        ),
        "device_rebase_edits_per_sec": round(n_edits / t_reb, 1),
        "device_rebase_identity": reb_identity,
        "device_rebase_fraction": reb_health.get(
            "device_rebase_fraction", 0.0
        ),
        "rebase_fallbacks": reb_health.get("rebase_fallbacks", 0),
        "rebase_kernel_speedup": kern_speedup,
        "rebase_kernel_identity": kern_identity,
        "engine_health": health,
    }
    # Acceptance shape (PR 19): the serving pipeline itself, or — when
    # the probed backend cannot express the win at W=1 dispatch depth —
    # the batched kernel plane at >= 1.5x with the run flagged degraded.
    if pipeline < 1.5 * 2019.0 and kern_speedup >= 1.5:
        out["degraded"] = True
    if getattr(args, "artifact", None):
        with open(args.artifact, "w") as f:
            json.dump(out, f, indent=2)
    return out


def _rebase_kernel_microbench(rng, n_windows: int = 256, window: int = 8):
    """(speedup, identity) of the batched rebase kernel over the pooled
    host fold on identical [windows x commits] workloads.

    Each window folds one multi-mark commit through ``window`` conflicting
    multi-insert commits in the same field — the shape where the host
    pays the full _rebase_cols column walk per leg.  Speedup is best-of-3
    wall for the whole window set; identity is a byte-compare of the
    decoded kernel fold against mark_pool.rebase_pair on a sample of
    windows."""
    import jax

    from fluidframework_tpu.dds.tree import mark_pool as mp
    from fluidframework_tpu.dds.tree.changeset import (
        Commit,
        Insert,
        NodeChange,
        Skip,
        commit_to_json,
        _wrap,
    )
    from fluidframework_tpu.dds.tree.device_rebase import DeviceRebaser
    from fluidframework_tpu.dds.tree.schema import leaf
    from fluidframework_tpu.ops.tree_kernel import rebase_window_batched

    pool = mp.MarkPool()

    def multi_insert():
        """[Skip, Insert, Skip, Insert, ...] over ~4 scattered positions."""
        marks = []
        cur = 0
        for p in sorted(rng.choice(32, size=4, replace=False)):
            p = int(p)
            if p > cur:
                marks.append(Skip(p - cur))
                cur = p
            marks.append(Insert([leaf(int(rng.integers(1000)))]))
        return mp.pool_commit(pool, Commit([
            _wrap([("", 0)], NodeChange(fields={"kids": marks})),
        ]))

    windows = [
        (multi_insert(), [multi_insert() for _ in range(window)])
        for _ in range(n_windows)
    ]

    # --- host fold (identical inputs, fresh is-identity caches) ----------
    t_host = float("inf")
    for _rep in range(3):
        t0 = time.perf_counter()
        host_out = []
        for c, xs in windows:
            cc = c
            new_xs = []
            for x in xs:
                cc, xw = mp.rebase_pair(cc, x)
                new_xs.append(xw)
            host_out.append((cc, new_xs))
        t_host = min(t_host, time.perf_counter() - t0)

    # --- batched kernel: encode once, one vmapped dispatch ----------------
    reb = DeviceRebaser(pool)
    encs = [(reb.encode_commit(c), [reb.encode_commit(x) for x in xs])
            for c, xs in windows]
    assert all(e is not None and all(x is not None for x in xe)
               for e, xe in encs)
    import jax.numpy as jnp

    cs = jax.tree.map(lambda *a: jnp.stack(a),
                      *[reb._enc_dev(e) for e, _ in encs])
    xss = jax.tree.map(lambda *a: jnp.stack(a),
                       *[reb._stack(xe, 0) for _, xe in encs])
    elig = jnp.ones((n_windows, window), bool)
    final, outs = rebase_window_batched(cs, xss, elig)  # warm/compile
    jax.block_until_ready(final)
    t_kern = float("inf")
    for _rep in range(3):
        t0 = time.perf_counter()
        final, outs = rebase_window_batched(cs, xss, elig)
        jax.block_until_ready(final)
        t_kern = min(t_kern, time.perf_counter() - t0)
    assert bool(jnp.all(outs.valid))

    # --- identity: decoded kernel fold == host fold (sampled windows) -----
    identity = True
    for i in range(0, n_windows, max(1, n_windows // 16)):
        c, xs = windows[i]
        kc, kxs, _stages = reb.fold(c, xs)
        hc, hxs = host_out[i]
        if commit_to_json(kc) != commit_to_json(hc) or any(
            commit_to_json(a) != commit_to_json(b)
            for a, b in zip(kxs, hxs)
        ):
            identity = False
    return round(t_host / t_kern, 2), identity


def bench_latency(args) -> dict:
    """p50/p99 remote-op apply latency (BASELINE.json's second metric):
    time from a sequenced op reaching the device pipeline to its state
    being applied.  Measured as a K-op sequential chain compiled as one
    program (per-op device apply latency = wall / K — what a resident
    ingest loop pays per op), with the host->device dispatch round trip
    reported separately (``host_roundtrip_us``), since it can dominate
    single-dispatch wall time."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops import mergetree_kernel as mk
    from fluidframework_tpu.protocol.stamps import ALL_ACKED

    state = mk.init_state(max_segments=16384, text_capacity=131072)
    K = 64

    chain = jax.jit(mk.apply_ops, donate_argnums=(0,))

    def make_chunk(seq0, length):
        ops = np.zeros((K, mk.OP_FIELDS), np.int32)
        payloads = np.zeros((K, 16), np.int32)
        payloads[:, :4] = [97, 98, 99, 100]
        for i in range(K):
            ops[i] = [
                mk.OpKind.INSERT, seq0 + i + 1, 0, ALL_ACKED,
                ((seq0 + i) * 31) % (length + 4 * i + 1), 0, 4, 0,
            ]
        return jnp.asarray(ops), jnp.asarray(payloads)

    # Resident state: ~1k segments before measuring.
    seq, length = 0, 0
    for _ in range(16):
        ops, payloads = make_chunk(seq, length)
        state = chain(state, ops, payloads)
        seq += K
        length += 4 * K
    jax.block_until_ready(state)

    samples = []
    for _ in range(50):
        ops, payloads = make_chunk(seq, length)
        jax.block_until_ready((ops, payloads))
        t0 = time.perf_counter()
        state = chain(state, ops, payloads)
        jax.block_until_ready(state)
        samples.append((time.perf_counter() - t0) / K)
        seq += K
        length += 4 * K
    assert int(state.error) == 0

    # Host dispatch round trip (runtime): one tiny transfer.
    rt = []
    for _ in range(20):
        t0 = time.perf_counter()
        jax.block_until_ready(jnp.zeros((1,), jnp.int32) + 1)
        rt.append(time.perf_counter() - t0)

    # Budget attribution (VERDICT r4 next #10): wall time of a SINGLE-op
    # jitted apply = dispatch overhead + one apply; subtracting the
    # K-chain amortized apply isolates the per-call dispatch share — the
    # number that decides whether the correctness path's one-op-per-call
    # design needs batching on this transport.
    ops1 = np.zeros((1, mk.OP_FIELDS), np.int32)
    pay1 = np.zeros((1, 16), np.int32)
    pay1[0, :4] = [97, 98, 99, 100]
    singles = []
    for i in range(30):
        ops1[0] = [mk.OpKind.INSERT, seq + i + 1, 0, ALL_ACKED, 0, 0, 4, 0]
        o, p = jnp.asarray(ops1), jnp.asarray(pay1)
        jax.block_until_ready((o, p))
        t0 = time.perf_counter()
        state = chain(state, o, p)  # same jit; new shape = one more cache entry
        jax.block_until_ready(state)
        if i >= 5:  # skip the compile + warmup samples
            singles.append(time.perf_counter() - t0)

    # Megastep amortization (ISSUE 4): the per-dispatch overhead spread
    # over a K-slice fused megastep (lax.scan over slices, one donated
    # dispatch — the engines' production path).  Self-consistent batched
    # comparison: the SAME [D=1, B=1] op slices dispatched K=1 per call
    # (before), fused K=8 per call (after), and fused K=64 (the amortized-
    # apply asymptote that isolates the dispatch component).  The unbatched
    # chain numbers above are NOT comparable (vmap turns lax.cond branches
    # into pay-both-sides selects), so the megastep budget derives its own
    # before/after shares.
    mega = jax.jit(mk.apply_megastep, donate_argnums=(0,))
    mstate = jax.tree.map(lambda x: x[None], state)  # [1, ...] doc batch

    def make_mega(km, seq0, length):
        ops = np.zeros((km, 1, 1, mk.OP_FIELDS), np.int32)
        payloads = np.zeros((km, 1, 1, 16), np.int32)
        payloads[..., :4] = [97, 98, 99, 100]
        for k in range(km):
            ops[k, 0, 0] = [
                mk.OpKind.INSERT, seq0 + k + 1, 0, ALL_ACKED,
                ((seq0 + k) * 31) % (length + 4 * k + 1), 0, 4, 0,
            ]
        return jnp.asarray(ops), jnp.asarray(payloads)

    mega_slice_us = {}
    for km, reps in ((1, 30), (8, 30), (64, 10)):
        walls = []
        for i in range(reps):
            mo, mp = make_mega(km, seq, length)
            jax.block_until_ready((mo, mp))
            t0 = time.perf_counter()
            mstate = mega(mstate, mo, mp)
            jax.block_until_ready(mstate)
            if i >= 3:  # skip the compile + warmup samples
                walls.append(time.perf_counter() - t0)
            seq += km
            length += 4 * km
        # Best-of, not median: the three K loops run minutes apart on a
        # shared chip, and a contention dip in one loop would otherwise
        # invert the before/after comparison.
        mega_slice_us[km] = float(min(walls)) * 1e6 / km

    p50 = float(np.percentile(samples, 50) * 1e6)
    p99 = float(np.percentile(samples, 99) * 1e6)
    single_us = float(np.percentile(singles, 50)) * 1e6
    dispatch_us = max(single_us - p50, 0.0)
    apply_floor = mega_slice_us[64]  # dispatch amortized to ~nothing
    share_before = max(mega_slice_us[1] - apply_floor, 0.0) / mega_slice_us[1]
    share_after = max(mega_slice_us[8] - apply_floor, 0.0) / mega_slice_us[8]
    return {
        "metric": "remote_op_apply_latency_p50",
        "value": round(p50, 1),
        "unit": "us",
        "vs_baseline": None,
        "p99_us": round(p99, 1),
        "host_roundtrip_us": round(float(np.percentile(rt, 50)) * 1e6, 1),
        # One-line budget: amortized apply vs per-call dispatch overhead.
        "budget": {
            "amortized_apply_us": round(p50, 1),
            "single_op_wall_us": round(single_us, 1),
            "dispatch_overhead_us": round(dispatch_us, 1),
            "dispatch_share": round(dispatch_us / single_us, 3) if single_us else None,
        },
        # Megastep before/after (batched, self-consistent — see comment at
        # the measurement): per-slice wall and dispatch share at K=1 vs
        # the K=8 fused dispatch the engines run by default.
        "megastep_budget": {
            "megastep_k": 8,
            "steps_per_dispatch": 8,
            "slice_wall_us_k1": round(mega_slice_us[1], 1),
            "slice_wall_us_k8": round(mega_slice_us[8], 1),
            "amortized_apply_floor_us": round(apply_floor, 1),
            "dispatch_share_before": round(share_before, 3),
            "dispatch_share_after": round(share_after, 3),
        },
    }


# ---------------------------------------------------------------------------
# Driver mode: the no-arg entry point.  Every config is a time-boxed child
# that owns the accelerator for its lifetime; a config that fails leaves
# an {"error": ...} row AND makes the run exit non-zero.
# ---------------------------------------------------------------------------

def bench_multichip_child(args) -> dict:
    """One mesh-served fleet measurement at ``--devices N``: the full
    serving pipeline — RowQueue staging -> StagingRing shard-layout upload
    -> shard_map megastep dispatch -> per-shard error reduce — timed over a
    pre-staged multi-slice workload.  The parent (``--config multichip``)
    forces N virtual CPU devices via XLA_FLAGS on an explicit CPU run; on
    real hardware the first N visible devices form the mesh."""
    import jax

    n_req = args.devices
    devs = jax.devices()
    if len(devs) < n_req:
        return {
            "n_devices": n_req, "ok": False, "skipped": True,
            "reason": f"only {len(devs)} devices visible",
        }
    from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
    from fluidframework_tpu.parallel.mesh import doc_mesh, docs_segs_mesh

    seg_width = min(args.seg_shards, n_req) if args.seg_shards > 1 else 0
    if seg_width > 1:
        # The 2-D mesh point: docs x segs over the same devices — the
        # fleet shards over both axes flattened, the seg replay carves
        # the segs axis.
        mesh = docs_segs_mesh(devs[:n_req], seg_width)
        # docs_segs_mesh clamps the requested width to a divisor of the
        # device count; record/replay the CLAMPED width so the seg point
        # matches the mesh_shape it sits next to in the artifact.
        from fluidframework_tpu.parallel.mesh import SEG_AXIS

        seg_width = int(dict(mesh.shape)[SEG_AXIS])
    else:
        mesh = doc_mesh(devs[:n_req])
    D, B, S = args.docs, args.ops_per_step, args.steps
    L = args.payload_len
    ops, payloads, _min_seqs = generate_workload(
        D, B, S, args.insert_len, L
    )
    # The generator emits doc-minor [S, B, F, D] (upload layout); the
    # RowQueue staging path wants per-doc [B, F] blocks.
    ops = np.ascontiguousarray(np.moveaxis(ops, -1, 1))
    payloads = np.ascontiguousarray(np.moveaxis(payloads, -1, 1))
    total_ops = S * D * B

    def run_once():
        eng = DocBatchEngine(
            D, max_segments=args.segments, text_capacity=args.text_capacity,
            max_insert_len=L, ops_per_step=B, mesh=mesh, use_mesh=True,
            megastep_k=args.megastep_k,
        )
        for d in range(D):
            q = eng.hosts[d].queue
            for s in range(S):
                q.extend_block(ops[s, d], payloads[s, d])
            eng._busy.add(d)
        t0 = time.perf_counter()
        eng.step()  # drains every staged slice; recover() gate included
        jax.block_until_ready(eng.state.text_end)
        dt = time.perf_counter() - t0
        assert not eng.errors().any(), "bench workload latched errors"
        return dt, eng

    run_once()  # warmup: compile + cache load outside every timer
    best, eng = min(
        (run_once() for _ in range(max(1, args.reps))), key=lambda r: r[0]
    )
    health = eng.health()
    row = {
        "metric": "multichip_fleet_ops_per_sec",
        "n_devices": n_req,
        "ok": True,
        "value": round(total_ops / best, 1),
        "unit": "ops/s",
        "total_ops": total_ops,
        "docs": D,
        "megastep_k": health.get("megastep_k"),
        "steps_per_dispatch": health.get("steps_per_dispatch"),
        "n_shards": health.get("n_shards"),
    }
    if args.seg_shards > 1:
        # The hot-doc segment-parallel point at this device count: the
        # whole segs axis serves ONE viral doc (config1's shape), recorded
        # next to the fleet number so the artifact carries the full 2-D
        # story per count.
        row["mesh_shape"] = {k: int(v) for k, v in dict(mesh.shape).items()}
        try:
            seg_args = _copy_args(args)
            seg_args.segments = max(args.segments, 4096)
            seg_args.text_capacity = max(args.text_capacity, 65536)
            row["segment"] = _seg_replay_rate(seg_args, max(seg_width, 1))
            if row["segment"].get("ok"):
                row["segment_shards"] = row["segment"]["segment_shards"]
                row["seg_ops_per_sec"] = row["segment"]["seg_ops_per_sec"]
        except Exception as e:  # noqa: BLE001 — probe must not sink the row
            row["segment"] = {"error": repr(e)[-300:]}
    return row


_MULTICHIP_COUNTS = (1, 2, 4, 8)
_MULTICHIP_CHILD_TIMEOUT = 600.0


def bench_multichip(args) -> dict:
    """MULTICHIP headline: fleet ops/s through the mesh serving path at
    1/2/4/8 devices, with scaling efficiency per count.

    The fleet (total docs and ops) is held CONSTANT across device counts,
    so ``scaling_efficiency`` = ops/s(N) / ops/s(1) measures what the
    shard layer costs: on the CPU box the N devices are virtual (XLA host
    platform device count — all counts share the same cores, so a healthy
    mesh reads ~1.0 and anything below is partitioning overhead), while on
    real accelerators each shard owns a chip and the same number reflects
    strong-scaling speedup / N.  Emits one JSON line and (with
    ``--artifact``) writes the full per-device table as the MULTICHIP
    round artifact — per-count ops/s, efficiency, and the same
    reduced_scale flag as the BENCH rows.  Each count is a child that owns
    the devices; this parent dispatches nothing until they are done."""
    reduced = _cpu_requested()

    per_device: list[dict] = []
    for n in _MULTICHIP_COUNTS:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--config", "multichip-child", "--devices", str(n)]
        if args.seg_shards > 1:
            cmd += ["--seg-shards", str(args.seg_shards)]
        if reduced:
            cmd += ["--docs", "128", "--steps", "8", "--reps", "3",
                    "--segments", "512", "--text-capacity", "8192"]
        env = dict(os.environ)
        if reduced:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                env.get("XLA_FLAGS", ""),
            )
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
        try:
            r = subprocess.run(
                cmd, capture_output=True, text=True,
                timeout=_MULTICHIP_CHILD_TIMEOUT, env=env,
            )
            row = None
            for line in reversed(r.stdout.strip().splitlines()):
                try:
                    parsed = json.loads(line)
                except (json.JSONDecodeError, ValueError):
                    continue
                if isinstance(parsed, dict):
                    row = parsed
                    break
            if row is None:
                row = {"n_devices": n, "ok": False,
                       "error": (r.stderr or "no JSON output").strip()[-300:]}
        except subprocess.TimeoutExpired:
            row = {"n_devices": n, "ok": False,
                   "error": f"timed out after {_MULTICHIP_CHILD_TIMEOUT:.0f}s"}
        except OSError as e:
            row = {"n_devices": n, "ok": False, "error": str(e)}
        per_device.append(row)

    base = next(
        (row.get("value") for row in per_device
         if row.get("ok") and row.get("n_devices") == 1), None,
    )
    for row in per_device:
        if row.get("ok") and base:
            speedup = row["value"] / base
            row["speedup"] = round(speedup, 3)
            # Efficiency normalizes by the silicon actually added: real
            # accelerators add a chip per device (speedup / N); virtual
            # CPU devices all share the same cores (denominator 1 — the
            # measure is shard-layer overhead, ~1.0 healthy).
            row["scaling_efficiency"] = round(
                speedup if reduced else speedup / row["n_devices"], 3
            )
    tail_ok = [row for row in per_device if row.get("ok")]
    out = {
        "metric": "multichip_fleet_ops_per_sec",
        "value": tail_ok[-1]["value"] if tail_ok else None,
        "unit": "ops/s",
        "n_devices": tail_ok[-1]["n_devices"] if tail_ok else None,
        "scaling_efficiency": (
            tail_ok[-1].get("scaling_efficiency") if tail_ok else None
        ),
        "virtual_devices": bool(reduced),
        "per_device": per_device,
        # As the measuring children reported it (jax.devices() there).
        **{k: (tail_ok[-1].get(k) if tail_ok else None)
           for k in ("platform", "device_kind", "device_count")},
    }
    if args.seg_shards > 1:
        # Headline surface of the 2-D point: the last successful count's
        # segment-parallel rate, and whether EVERY count's final state was
        # byte-identical to the single-lane oracle.
        seg_rows = [
            row for row in per_device
            if isinstance(row.get("segment"), dict) and row["segment"].get("ok")
        ]
        # The ACTUAL (clamped) width of the row the headline rate comes
        # from — the child clamps the requested width to a divisor of its
        # device count, so args.seg_shards can disagree with every row.
        out["segment_shards"] = (
            seg_rows[-1]["segment"]["segment_shards"]
            if seg_rows else args.seg_shards
        )
        if seg_rows:
            out["seg_ops_per_sec"] = seg_rows[-1]["segment"]["seg_ops_per_sec"]
            out["seg_identity"] = all(
                row["segment"].get("seg_identity") for row in seg_rows
            )
    if not tail_ok:
        # No count produced a number: an error, not a row of nulls.
        sys.exit("bench.py multichip: no device count ran:\n"
                 + json.dumps(per_device, indent=2)[-2000:])
    if reduced:
        out["reduced_scale"] = True
    if getattr(args, "artifact", None):
        with open(args.artifact, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    return out


def _run_soak_child(timeout_s: float = 1800.0, **cfg) -> dict:
    """One chaos soak in a FRESH subprocess (no inherited jit executables):
    recovery intervals then measure real process-cold restore — a
    successor fleet in production pays its own compiles, and an in-process
    rerun that inherits them would report a recovery tail ~100x better
    than reality.  The child inherits this run's environment untouched
    (platform and compile-cache placement are decided outside) and stamps
    the row with the devices ITS jax reports."""
    prog = (
        "import json, sys\n"
        "import jax\n"
        "from fluidframework_tpu.testing.chaos import run_soak\n"
        "out = run_soak(**json.loads(sys.argv[1]))\n"
        "devs = jax.devices()\n"
        "out.update(platform=devs[0].platform,\n"
        "           device_kind=devs[0].device_kind,\n"
        "           device_count=len(devs))\n"
        "print(json.dumps(out))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", prog, json.dumps(cfg)],
        capture_output=True, text=True, timeout=timeout_s,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"soak child {cfg} failed:\n{r.stderr.strip()[-2000:]}"
        )
    row = json.loads(r.stdout.strip().splitlines()[-1])
    _require_accelerator(row["platform"])
    return row


def bench_soak(args) -> dict:
    """``--config soak``: the chaos/soak harness over the full serving
    stack (testing/chaos.py) — Zipf-popularity traffic with connect/
    disconnect churn driven through a seeded fault schedule (fleet
    kill/restart, torn sockets, nack storms, scribe crash mid-fold,
    delayed partition fsyncs) against the admission-controlled netserver
    front + checkpointed device fleet + ScribePool.  Invariants (byte
    identity vs a fault-free oracle replay, no double-acks, bounded queue
    depth/RSS) are HARD assertions — a violation fails the config rather
    than skewing a number.  Emits the SLO row: p50/p99 op latency UNDER
    FAULT plus shed/pause/backoff counters (the SOAK round artifact via
    ``--artifact``)."""
    seed = int(os.environ.get("FFTPU_SOAK_SEED", "10"))
    ticks = args.steps if args.steps_explicit else int(
        os.environ.get("FFTPU_SOAK_TICKS", "240")
    )
    n_docs = args.docs if args.docs_explicit else 6
    # r12 recovery plane: the headline soak runs WITH the warm standby +
    # bounded-staleness checkpoint writer (FFTPU_SOAK_STANDBY=0 opts
    # out), and unless FFTPU_SOAK_COMPARE=0 a second, r10-equivalent
    # non-standby run on the same box quantifies the recovery-p99 win.
    # Each soak runs in its OWN subprocess: in-process back-to-back runs
    # share jit executable caches, which silently pre-warms the cold
    # run's post-kill compiles and erases the very recovery tail under
    # measurement (r10's 16.8 s p99 IS that first process-cold restore).
    standby = os.environ.get("FFTPU_SOAK_STANDBY", "1") != "0"
    # r16 placement plane: the soak fleet is MIXED by default — tree docs
    # ride the same Zipf ranking, fault schedule, and byte-identity
    # invariants as the string docs (FFTPU_SOAK_TREE_DOCS=0 opts out),
    # so the artifact carries per-family recovery percentiles.
    n_tree_docs = int(os.environ.get("FFTPU_SOAK_TREE_DOCS", "3"))
    out = _run_soak_child(
        seed=seed, ticks=ticks, n_docs=n_docs,
        n_tree_docs=n_tree_docs, standby=standby,
        ckpt_stale_seconds=0.25 if standby else 0.0,
    )
    if standby and os.environ.get("FFTPU_SOAK_COMPARE", "1") != "0":
        # The 30 s recovery bound is the headline run's SLO; the cold
        # comparison exists to measure how slow process-cold restore is
        # (mixed-fleet tree re-materialization pays its own compiles and
        # lands well past 30 s), so it runs under a relaxed ceiling.
        cold = _run_soak_child(seed=seed, ticks=ticks,
                               n_docs=n_docs, n_tree_docs=n_tree_docs,
                               recovery_bound_s=180.0)
        out["no_standby"] = {
            k: cold.get(k) for k in (
                "recovery_p50_ms", "recovery_p99_ms",
                "tree_recovery_p50_ms", "tree_recovery_p99_ms",
                "p50_ms", "p99_ms", "duration_s",
            )
        }
        out["no_standby"]["fleet_restarts"] = (
            cold["counters"]["fleet_restarts"]
        )
        if out.get("recovery_p99_ms") and cold.get("recovery_p99_ms"):
            out["recovery_speedup"] = round(
                cold["recovery_p99_ms"] / out["recovery_p99_ms"], 2
            )
        if (out.get("tree_recovery_p99_ms")
                and cold.get("tree_recovery_p99_ms")):
            out["tree_recovery_speedup"] = round(
                cold["tree_recovery_p99_ms"] / out["tree_recovery_p99_ms"],
                2,
            )
    if _cpu_requested():
        out["reduced_scale"] = True
    if getattr(args, "artifact", None):
        with open(args.artifact, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    return out


def _fanout_mint(n_ops: int, payload_len: int = 24):
    """Sequenced messages for one hot doc via a real sequencer (join +
    n_ops client ops, the firehose wire shape)."""
    from fluidframework_tpu.protocol.messages import UnsequencedMessage
    from fluidframework_tpu.server.sequencer import Sequencer

    seqr = Sequencer()
    msgs = [seqr.join("w0")]
    body = "x" * payload_len
    for i in range(n_ops):
        msgs.append(seqr.ticket(UnsequencedMessage(
            client_id="w0", client_seq=i + 1, ref_seq=msgs[-1].seq,
            contents={"type": 0, "pos1": i, "seg": body},
        )))
    return msgs


def _fanout_sweep_point(n_subs: int, n_ops: int, pump: int) -> dict:
    """One subscriber-count point: fresh messages (so the encode counter
    counts THIS run), N virtual subscribers on one hot doc, timed publish
    (the under-the-service-lock half) and timed drain (the per-subscriber
    half), byte-identity sampled against the firehose oracle."""
    from fluidframework_tpu.fanout import FanoutPlane
    from fluidframework_tpu.protocol.messages import wire_encode_count

    msgs = _fanout_mint(n_ops)
    plane = FanoutPlane(ring_frames=1 << 16, ring_bytes=1 << 30)
    plane.ensure_doc("hot", last_seq=0)
    sampled = []
    peers = []
    for i in range(n_subs):
        if i in (0, n_subs // 2, n_subs - 1):
            chunks: list[bytes] = []
            peer = plane.new_peer(sink=chunks.append)
            sampled.append((peer, chunks))
        else:
            peer = plane.new_peer(sink=None)
        plane.attach("hot", peer, flavor="wire", last_seq=0)
        peers.append(peer)
    enc0 = wire_encode_count()
    publish_calls = 0
    t0 = time.perf_counter_ns()
    for lo in range(0, len(msgs), pump):
        plane.publish("hot", msgs[lo:lo + pump])
        publish_calls += 1
    t_publish = time.perf_counter_ns() - t0
    encodes = wire_encode_count() - enc0
    t0 = time.perf_counter_ns()
    for peer in peers:
        plane.drain_virtual(peer)
    t_drain = time.perf_counter_ns() - t0
    oracle = b"".join(m.wire_line() for m in msgs)
    identity_ok = all(b"".join(c) == oracle for _p, c in sampled)
    n_total = len(msgs)
    pumps = plane.stats()["frames_published"]
    return {
        "n_subscribers": n_subs,
        "n_ops": n_total,
        "pumps": pumps,
        "wire_encodes": encodes,
        "encodes_per_op": round(encodes / n_total, 4),
        "frame_encodes_per_doc_pump": round(pumps / publish_calls, 4),
        "per_op_publish_ns": round(t_publish / n_total, 1),
        "per_delivery_ns": round(t_drain / (n_total * n_subs), 2),
        "publish_ops_per_sec": round(n_total / (t_publish / 1e9), 1),
        "deliveries_per_sec": round(
            n_total * n_subs / (t_drain / 1e9), 1
        ),
        "byte_identity": identity_ok,
    }


def _fanout_resync_point(n_ops: int = 512, pump: int = 8) -> dict:
    """Drop-and-resync byte-identity vs the firehose oracle: a tiny ring,
    one stalled subscriber draining late, one live subscriber."""
    from fluidframework_tpu.fanout import FanoutPlane

    msgs = _fanout_mint(n_ops)
    log = list(msgs)

    def source(_doc, from_seq):
        return [m for m in log if m.seq > from_seq]

    plane = FanoutPlane(resync_source=source, ring_frames=4)
    plane.ensure_doc("hot", last_seq=0)
    live_chunks: list[bytes] = []
    slow_chunks: list[bytes] = []
    live = plane.new_peer(sink=live_chunks.append)
    slow = plane.new_peer(sink=slow_chunks.append)
    plane.attach("hot", live, flavor="wire", last_seq=0)
    plane.attach("hot", slow, flavor="wire", last_seq=0)
    half = len(msgs) // 2
    for lo in range(0, half, pump):
        plane.publish("hot", msgs[lo:lo + pump])
        plane.drain_virtual(live)
    plane.drain_virtual(slow)  # forced off the 4-frame ring: resync
    for lo in range(half, len(msgs), pump):
        plane.publish("hot", msgs[lo:lo + pump])
        plane.drain_virtual(live)
    plane.drain_virtual(slow)
    oracle = b"".join(m.wire_line() for m in msgs)
    stats = plane.stats()
    return {
        "resyncs": stats["resyncs"],
        "frames_evicted": stats["frames_evicted"],
        "slow_byte_identity": b"".join(slow_chunks) == oracle,
        "live_byte_identity": b"".join(live_chunks) == oracle,
        "live_resyncs": live.resyncs,
    }


def _fanout_boot_point(n_requests: int = 64) -> dict:
    """Snapshot-boot tier: cold GET vs conditional-GET/304 latency over
    real HTTP against a content-addressed summary with shared subtrees."""
    import http.client

    from fluidframework_tpu.fanout import HistorianTier
    from fluidframework_tpu.server.gitstore import GitSnapshotStore

    store = GitSnapshotStore()
    summary = {
        f"channel_{i:03d}": {
            "header": {"seq": i, "kind": "sharedString"},
            "body": {"text": "t" * 256, "props": {"k": i}},
        }
        for i in range(64)
    }
    store.save(100, summary)
    summary["channel_000"]["body"]["text"] = "changed"
    store.save(200, summary)
    sha = store.versions[-1][1]
    tier = HistorianTier(lambda d: store if d == "hot" else None).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", tier.port, timeout=30)

        def req(path, headers=None):
            t0 = time.perf_counter_ns()
            conn.request("GET", path, headers=headers or {})
            r = conn.getresponse()
            r.read()
            return r.status, (time.perf_counter_ns() - t0) / 1e6

        cold, not_modified = [], []
        for _ in range(n_requests):
            status, ms = req(f"/doc/hot/snapshot/{sha}")
            assert status == 200
            cold.append(ms)
            status, ms = req(
                f"/doc/hot/snapshot/{sha}",
                headers={"If-None-Match": f'"{sha}"'},
            )
            assert status == 304
            not_modified.append(ms)
        status, _ms = req(f"/doc/hot/path/{sha}?path=channel_001/body")
        conn.close()
        cold_p50 = float(np.median(cold))
        nm_p50 = float(np.median(not_modified))
        return {
            "n_requests": n_requests,
            "cold_ms_p50": round(cold_p50, 3),
            "etag304_ms_p50": round(nm_p50, 3),
            "etag304_speedup": round(cold_p50 / nm_p50, 2) if nm_p50 else None,
            "path_read_ok": status == 200,
            "git_sharing_ratio": round(store.sharing_ratio(), 3),
            "tier_stats": tier.stats(),
        }
    finally:
        tier.stop()


def bench_fanout(args) -> dict:
    """``--config fanout``: the read fan-out plane on ONE hot doc — a
    subscriber-count sweep (1k -> 100k virtual subscribers) proving the
    encode-once contract (wire encodes independent of N, one frame per
    (doc, pump)) and flat per-op publish cost, a drop-and-resync
    byte-identity check vs the firehose oracle, and the snapshot-boot
    tier's cold-vs-304 latency (the FANOUT round artifact via
    ``--artifact``)."""
    n_ops = args.steps * 16 if args.steps_explicit else 2048
    pump = 32
    sweep_counts = [1_000, 10_000, 100_000]
    if args.docs_explicit:  # small-box shrink knob reuses --docs
        sweep_counts = [c for c in sweep_counts if c <= args.docs * 100]
        sweep_counts = sweep_counts or [1_000]
    sweep = [_fanout_sweep_point(n, n_ops, pump) for n in sweep_counts]
    lo, hi = sweep[0], sweep[-1]
    out = {
        "metric": "fanout_per_delivery_ns",
        "value": hi["per_delivery_ns"],
        "unit": "ns",
        "vs_baseline": None,
        "n_ops": n_ops,
        "pump_batch": pump,
        "subscriber_sweep": sweep,
        # The two acceptance invariants, computed across the sweep edges:
        # encodes never scale with N, publish cost per op stays flat.
        "encode_growth_vs_subscribers": round(
            hi["wire_encodes"] / lo["wire_encodes"], 4
        ),
        "per_op_publish_cost_ratio": round(
            hi["per_op_publish_ns"] / lo["per_op_publish_ns"], 3
        ),
        "byte_identity_all": all(p["byte_identity"] for p in sweep),
        "resync": _fanout_resync_point(),
        "snapshot_boot": _fanout_boot_point(),
    }
    # Host plane only: no JAX device takes part in this config.
    out["platform"] = "host"
    if getattr(args, "artifact", None):
        with open(args.artifact, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    return out


def bench_loadgen(args) -> dict:
    """``--config loadgen``: the multi-process traffic plant — N worker OS
    processes over real TCP against real netserver shards + checkpointed
    device fleets, mixed workloads across five channel families, four
    phase barriers, a boot storm through the historian snapshot tier, and
    a byte-identity convergence verdict (the LOADGEN round artifact via
    ``--artifact``).  On a small box the worker count clamps (flagged
    ``reduced_scale`` — the plant is real either way, just narrower).
    The plant's device fleets run on the CPU: one process per (shard,
    family) cannot share a chip (ROADMAP D1)."""
    import tempfile

    from fluidframework_tpu.loadgen.coordinator import run_loadgen

    want_workers = 6
    cpus = os.cpu_count() or 1
    n_workers = want_workers if cpus >= 8 else 4
    with tempfile.TemporaryDirectory(prefix="loadgen-") as workdir:
        report = run_loadgen(
            workdir, seed=17, n_workers=n_workers, n_shards=2,
            ramp_ops=8, steady_ops=24, boots=6, deadline_s=900.0,
            fleet_platform="cpu",
        )
    out = {
        "metric": "loadgen_steady_p99_ms",
        "value": report["phases"]["steady"].get("p99_ms"),
        "unit": "ms",
        "vs_baseline": None,
        **report,
    }
    # report["platform"] is what the fleets' readiness lines said.
    if n_workers < want_workers:
        out["reduced_scale"] = True  # clamped plant, not broken numbers
    if getattr(args, "artifact", None):
        with open(args.artifact, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    return out


_CHILD_TIMEOUTS = {
    "1": 900.0, "2": 600.0, "3": 1500.0, "4": 600.0, "5": 900.0,
    "latency": 600.0, "headline": 1500.0,
}


def _run_child(key: str, reduced: bool, timeout_s: float):
    """Run one config as a time-boxed subprocess; return (dict|None, err).
    A child that exits non-zero is a failure even if it printed a row."""
    cmd = [sys.executable, os.path.abspath(__file__), "--config", key]
    if reduced:
        # Explicit CPU run: shrink to scales that finish on a small host.
        cmd += ["--docs", "128", "--steps", "4", "--reps", "2"]
    try:
        r = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return None, f"timed out after {timeout_s:.0f}s"
    except OSError as e:
        return None, str(e)
    if r.returncode != 0:
        return None, (
            f"exit {r.returncode}: "
            + (r.stderr or r.stdout or "no output").strip()[-500:]
        )
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            parsed = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(parsed, dict):  # scalars/null are stray prints
            return parsed, None
    return None, (r.stderr or "no JSON output").strip()[-500:]


def _driver_main() -> int:
    """Run every config as a child; returns the process exit code (non-zero
    when any config failed).  This parent never touches a JAX backend."""
    reduced = _cpu_requested()
    results: dict[str, dict] = {}
    failed: list[str] = []
    order = ["1", "2", "3", "4", "5", "latency", "headline"]
    for key in order:
        res, err = _run_child(key, reduced, _CHILD_TIMEOUTS[key])
        if res is None:
            failed.append(key)
            res = {"metric": _metric_name(key), "value": None,
                   "unit": _unit_name(key), "vs_baseline": None,
                   "error": err}
        if reduced:
            res["reduced_scale"] = True  # requested CPU: small, not broken
        results[key] = res
        if key != "headline":
            print(json.dumps(res), flush=True)
    head = results["headline"]
    c3 = results.get("3", {})
    if c3.get("value"):
        head["config3_multiwriter_zipf_ops_per_sec"] = c3["value"]
    print(json.dumps(head), flush=True)
    if failed:
        print(f"bench.py: configs failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


def _unit_name(key: str) -> str:
    return {"latency": "us", "5": "edits/s"}.get(key, "ops/s")


def _metric_name(key: str) -> str:
    return {
        "1": "config1_singledoc_replay_ops_per_sec",
        "2": "config2_map_lww_ops_per_sec",
        "3": "config3_mergetree_zipf_ops_per_sec_per_chip",
        "4": "config4_matrix_ops_per_sec",
        "5": "config5_tree_device_edits_per_sec",
        "latency": "remote_op_apply_latency_p50",
        "headline": "mergetree_ops_per_sec_per_chip",
    }[key]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", default=None,
                   choices=["1", "2", "3", "4", "5", "latency", "headline",
                            "multichip", "multichip-child", "soak", "fanout",
                            "loadgen", "all"])
    p.add_argument("--devices", type=int, default=1,
                   help="mesh device count for the multichip-child config")
    p.add_argument("--artifact", default=None,
                   help="with --config multichip: also write the full "
                        "per-device table to this JSON file (the "
                        "MULTICHIP round artifact)")
    p.add_argument("--docs", type=int, default=None)
    # (segments/text-capacity/steps also use None defaults so per-config
    # tuning never clobbers an explicitly requested value.)
    p.add_argument("--segments", type=int, default=None)
    p.add_argument("--text-capacity", type=int, default=None)
    p.add_argument("--ops-per-step", type=int, default=16)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--warmup-steps", type=int, default=16)
    p.add_argument("--insert-len", type=int, default=4)
    p.add_argument("--payload-len", type=int, default=8)
    p.add_argument("--compact-every", type=int, default=4)
    p.add_argument("--seg-shards", type=int, default=0,
                   help="record the segment-parallel hot-doc path: config1 "
                        "adds a seg-sharded replay of its trace over an "
                        "N-shard segs axis (seg_ops_per_sec + byte-identity "
                        "vs the single lane); multichip builds a 2-D "
                        "docs x segs mesh per device count and attaches "
                        "the seg point to every row")
    p.add_argument("--megastep-k", type=int, default=8,
                   help="max op slices fused into one device dispatch in "
                        "the engine-level probes (1 = per-slice dispatch, "
                        "the pre-megastep behavior)")
    # Best-of-N: interleaved measurements showed >3x swing between
    # cold/contended and warm steady state, and N=3 regularly reported a
    # contention dip as the result.
    p.add_argument("--reps", type=int, default=8)
    p.add_argument("--trace", default=None,
                   help="record the run's flight-recorder trace "
                        "(ingest/upload/dispatch/readback spans from every "
                        "instrumented engine path) and dump Chrome "
                        "trace-event JSON to this path (Perfetto-loadable)")
    args = p.parse_args()
    if len(sys.argv) == 1:
        return _driver_main()  # children measure; no backend here
    from fluidframework_tpu.utils import compile_cache

    # Every config runs as its own process: without the persistent cache
    # each pays every geometry's XLA compile from scratch.
    compile_cache.enable()
    # Configs whose numbers come from child processes (or from no device
    # at all) must not take the accelerator here; every other config
    # measures in this process and checks its backend BEFORE measuring.
    device_row = (
        None if args.config in ("multichip", "soak", "loadgen", "fanout")
        else _device_row()
    )
    trace_recorder = None
    if args.trace:
        from fluidframework_tpu.observability import FlightRecorder, install

        trace_recorder = install(FlightRecorder(1 << 18))
    args.docs_explicit = args.docs is not None
    args.segments_explicit = args.segments is not None
    args.tc_explicit = args.text_capacity is not None
    args.steps_explicit = args.steps is not None
    if args.docs is None:
        args.docs = 1024
    if args.segments is None:
        args.segments = 2048
    if args.text_capacity is None:
        args.text_capacity = 16384
    if args.steps is None:
        args.steps = 96

    table = {
        "1": bench_config1,
        "2": bench_config2,
        "3": bench_config3,
        "4": bench_config4,
        "5": bench_config5,
        "latency": bench_latency,
        "headline": bench_headline,
        "multichip": bench_multichip,
        "multichip-child": bench_multichip_child,
        "soak": bench_soak,
        "fanout": bench_fanout,
        "loadgen": bench_loadgen,
    }
    def _emit(res: dict) -> None:
        # Every config row carries the observability attachment
        # (latency_p50_ms / latency_p99_ms / phase_shares — ISSUE 7).
        # The soak row is exempt: its p50/p99 are measured UNDER FAULT on
        # the real stack — attaching the synthetic probe's numbers next to
        # them would invite reading the wrong column.  The fanout row is
        # host-plane only (no engine in the loop): the device probe's
        # latency columns would be noise next to its ns-scale numbers.
        # The loadgen row's latencies are end-to-end over real sockets
        # from real worker processes — same rule as soak.
        if res.get("metric", "").startswith(("soak_", "fanout_", "loadgen_")):
            print(json.dumps(res), flush=True)
            return
        res = _attach_observability(res, args.megastep_k)
        res.update(device_row or {})
        print(json.dumps(res), flush=True)

    if args.config is None:
        # Flags without --config: the pre-driver-mode behavior (headline
        # at the requested scale, honoring the explicit flags).
        _emit(bench_headline(args))
    elif args.config == "all":
        for key in ("1", "2", "3", "4", "5", "latency", "headline"):
            _emit(table[key](args))
    else:
        _emit(table[args.config](args))
    if trace_recorder is not None:
        n = trace_recorder.export_chrome_trace(args.trace)
        print(json.dumps({
            "trace": args.trace, "events": n,
            "dropped": trace_recorder.dropped,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
