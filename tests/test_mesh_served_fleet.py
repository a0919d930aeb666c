"""The mesh-served string fleet as ``string4_uniform_wide`` serves it.

The cell's configuration (``benchmark/configs/string_fleet_10k_mesh4.json``)
is one ``fleet_main --mesh 4``: a ``DocBatchEngine`` on a 4-shard ``docs``
mesh, every loop one ``shard_map`` fleet-wide step, fed through
``ingest_lines``.  Here the same at a size the CPU holds, on four of
``conftest.py``'s virtual devices:

- (a) the cell's own mix (``plants/shared_string.string_edit``: uniform
  documents, one writer each, inserts, removes, annotates, sided obliterates
  up to the budget) against the plain reference (``oracle_text``: the host
  merge-tree of ``dds/mergetree_ref.py``) and every writer, byte for byte,
  for a document count that is no multiple of the shard count, at depth 1
  and at depth 33 (K = 2);
- (b) one shard 32 rows deep and the other three 1 row deep in ONE slice:
  state and error latch bit-equal to the same rows on one device (under
  ``shard_map`` each shard's ``row_count`` is its own);
- the fleet's state built in place on the mesh (``init_fleet_state``);
- (c) ``health()``'s per-shard counters: ``shard_row_slots_scanned`` is the
  per-shard deepest take summed over the slices, zero in the first health
  line, and the delta of ``shard_ops`` sums to the rows applied;
- the three readers of the device trace that a CPU rehearsal cannot feed
  (no TPU plane), on a synthetic ``ctx`` with a known answer.
"""

from __future__ import annotations

import importlib
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.dds.shared_string import SharedString
from fluidframework_tpu.loadgen.coordinator import oracle_text
from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.native.ingest_native import available
from fluidframework_tpu.ops import mergetree_kernel as mk
from fluidframework_tpu.parallel import mesh as pm
from fluidframework_tpu.server import LocalService

from test_mesh_conformance import _assert_leaves_equal, make_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from plants.shared_string import string_edit  # noqa: E402

N_SHARDS = 4
N_DOCS = 10          # 3 + 3 + 3 + 1 documents a shard: no multiple of 4
B = 32               # fleet_main's ops_per_step
MAX_OBLITERATES = 8  # the configuration's plant.params


def _mesh():
    return pm.doc_mesh(jax.devices()[:N_SHARDS])


def _engine(mesh):
    return DocBatchEngine(
        N_DOCS, max_segments=512, text_capacity=4096, max_insert_len=8,
        ops_per_step=B, megastep_k=8, mesh=mesh, use_mesh=mesh is not None,
    )


class Fleet:
    """One writer a document behind a ``LocalService`` sequencer, and the
    bytes of each document's log that the engine has not been fed yet."""

    def __init__(self, seed: int) -> None:
        self.rng = random.Random(seed)
        self.svc = LocalService()
        self.writers = []
        self.fed = [0] * N_DOCS
        self.obliterates = [0] * N_DOCS
        for d in range(N_DOCS):
            doc = self.svc.document(f"s{d}")
            c = SharedString(client_id=f"s{d}-w0")
            doc.connect(c.client_id, c.process)
            doc.process_all()
            self.writers.append(c)

    def edit(self, d: int, depth: int) -> None:
        """``depth`` edits of document ``d`` in one flush, as the plant
        makes them: one device row each."""
        c, doc = self.writers[d], self.svc.document(f"s{d}")
        for _ in range(depth):
            if string_edit(self.rng, c,
                           self.obliterates[d] < MAX_OBLITERATES):
                self.obliterates[d] += 1
        for m in c.take_outbox():
            doc.submit(m)
        doc.process_all()

    def feed(self, eng) -> dict[int, int]:
        """Every unfed line through ``ingest_lines``; rows staged by doc."""
        rows = {}
        for d in range(N_DOCS):
            log = self.svc.document(f"s{d}").sequencer.log
            new = log[self.fed[d]:]
            self.fed[d] = len(log)
            if new:
                n = eng.ingest_lines(
                    d, b"".join((m.to_json() + "\n").encode() for m in new))
                if n:
                    rows[d] = n
        return rows


needs_native = pytest.mark.skipif(
    not available(), reason="native ingest library failed to build")


# ------------------------------------------------ (a) against the oracle
@needs_native
@pytest.mark.parametrize("depth,k", [(1, 1), (33, 2)])
def test_mesh_served_engine_matches_the_host_oracle(depth, k):
    fleet = Fleet(seed=32 + depth)
    eng = _engine(_mesh())
    assert eng.n_shards == N_SHARDS and not eng.bucketing
    assert {eng.shard_of(d) for d in range(N_DOCS)} == set(range(N_SHARDS))
    untouched = 4                       # never edited: must stay empty
    docs = [d for d in range(N_DOCS) if d != untouched]
    rounds = 40 if depth == 1 else 3
    deepest_dispatch = 0
    for _ in range(rounds):
        for d in fleet.rng.sample(docs, 5):
            fleet.edit(d, depth)
        fleet.feed(eng)
        before = eng.counters.snapshot().get("megastep_slices", 0)
        eng.step()
        after = eng.counters.snapshot()["megastep_slices"]
        deepest_dispatch = max(deepest_dispatch, after - before)
    assert deepest_dispatch == k        # 33 rows are two slices of 32
    assert not eng.errors().any()
    h = eng.health()
    assert h["quarantined_docs"] == 0 and h["overflow_docs"] == 0
    assert h["cohort_steps"] == 0       # no cohort path under a mesh
    for d in range(N_DOCS):
        want = oracle_text(fleet.svc.document(f"s{d}").sequencer.log)
        assert fleet.writers[d].text == want, f"doc {d}: writer != oracle"
        assert eng.text(d) == want, f"doc {d}: device text != oracle"
    assert eng.text(untouched) == ""
    assert sum(fleet.obliterates) > 0   # the mix reached its obliterates


# ------------------------------------- (b) each shard's own trip count
@pytest.mark.parametrize("deep_shard", range(N_SHARDS))
def test_one_deep_shard_steps_bit_equal_to_one_device(deep_shard):
    D, L = 8, 6                          # two documents a shard
    proto = mk.init_state(64, 3, 2, 512, 4)
    fleet = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (D,) + x.shape), proto)
    mesh = _mesh()
    s_mesh = pm.shard_fleet_state(fleet, mesh)
    specs = pm.fleet_state_specs(s_mesh, "docs")
    mega = pm.mesh_fleet_program(
        mk.apply_megastep, mesh, specs,
        arg_specs=(pm.P(None, "docs"), pm.P(None, "docs")))
    oracle = jax.jit(mk.apply_megastep)
    rings, _seqs = make_trace(7 + deep_shard, D, 1, B, L, n_rings=2)
    s_one = fleet
    for i, (ops, pays) in enumerate(rings):
        for d in range(D):
            if d // 2 != deep_shard:    # the other three: one row deep
                ops[:, d, 1:] = 0
                pays[:, d, 1:] = 0
        per_shard = [int(mk.row_count(jnp.asarray(ops[0, 2 * s:2 * s + 2])))
                     for s in range(N_SHARDS)]
        assert per_shard == [B if s == deep_shard else 1
                             for s in range(N_SHARDS)]
        s_mesh = mega(s_mesh, jnp.asarray(ops), jnp.asarray(pays))
        s_one = oracle(s_one, jnp.asarray(ops), jnp.asarray(pays))
        _assert_leaves_equal(s_one, s_mesh, f"shard {deep_shard} ring {i}")
    assert int(pm.error_count(s_mesh.error)) == int(
        np.count_nonzero(np.asarray(s_one.error)))


def test_fleet_state_is_built_in_place_on_the_mesh():
    """``init_fleet_state``: the same values and the same placement as a
    broadcast sharded afterwards, made by one program whose outputs are
    sharded (no device is handed the whole fleet to split)."""
    mesh = _mesh()
    proto = mk.init_state(64, 3, 2, 512, 4)
    capacity = 12
    built = pm.init_fleet_state(proto, capacity, mesh)
    want = pm.shard_fleet_state(
        jax.tree.map(
            lambda x: jnp.broadcast_to(x, (capacity,) + x.shape), proto),
        mesh)
    _assert_leaves_equal(want, built, "init_fleet_state")
    for a, b in zip(jax.tree.leaves(built), jax.tree.leaves(want)):
        assert a.sharding == b.sharding
        assert {s.data.shape[0] for s in a.addressable_shards} == {
            capacity // N_SHARDS}
    assert _engine(mesh).state.seg_len.sharding == built.seg_len.sharding


# --------------------------------------------- (c) the per-shard counters
def _expected_shard_slots(eng, depths: dict[int, int]) -> list[int]:
    """Per shard, the deepest take among its documents, summed over the
    slices: every slice takes min(B, what is left) of every queue, however
    the engine groups slices into dispatches."""
    left = dict(depths)
    out = [0] * N_SHARDS
    while any(left.values()):
        deepest = [0] * N_SHARDS
        for d, n in left.items():
            take = min(B, n)
            s = eng.shard_of(d)
            deepest[s] = max(deepest[s], take)
            left[d] = n - take
        out = [a + b for a, b in zip(out, deepest)]
    return out


@needs_native
@pytest.mark.parametrize("depths", [
    pytest.param({0: 1, 3: 1, 6: 1, 9: 1}, id="one-row-a-shard"),
    pytest.param({1: 32, 4: 1, 7: 1, 9: 1}, id="one-shard-32-deep"),
    pytest.param({0: 5, 2: 33, 5: 2}, id="k2-and-an-idle-shard"),
    pytest.param({0: 70, 1: 3, 3: 40, 8: 1, 9: 97}, id="uneven-k4"),
])
def test_shard_row_slots_scanned_is_each_shards_deepest_take(depths):
    fleet = Fleet(seed=5)
    eng = _engine(_mesh())
    first = eng.health()
    assert first["shard_row_slots_scanned"] == [0] * N_SHARDS
    assert first["shard_ops"] == [0] * N_SHARDS
    for d, depth in depths.items():
        fleet.edit(d, depth)
    rows = fleet.feed(eng)
    assert rows == depths               # one device row an edit
    eng.step()
    h = eng.health()
    want = _expected_shard_slots(eng, depths)
    assert h["shard_row_slots_scanned"] == want
    by_shard = [0] * N_SHARDS
    for d, n in depths.items():
        by_shard[eng.shard_of(d)] += n
    assert h["shard_ops"] == by_shard
    assert sum(h["shard_ops"]) == sum(rows.values())
    # The fleet's own trip count is the deepest shard's, slice by slice.
    slices = -(-max(depths.values()) // B)
    assert h["megastep_slices"] == slices
    assert h["row_slots_scanned"] == sum(
        min(B, max(0, max(depths.values()) - B * k)) for k in range(slices))
    assert max(want) == h["row_slots_scanned"]
    # A second loop adds to the counters: they are cumulative.
    fleet.edit(9, 2)
    fleet.feed(eng)
    eng.step()
    again = eng.health()["shard_row_slots_scanned"]
    assert again == [w + (2 if s == eng.shard_of(9) else 0)
                     for s, w in enumerate(want)]
    for d in range(N_DOCS):
        assert eng.text(d) == fleet.writers[d].text


def test_one_device_engine_reports_no_per_shard_counters():
    eng = _engine(None)
    h = eng.health()
    assert "shard_row_slots_scanned" not in h and "shard_ops" not in h


# ------------------------------------- the new readers on a synthetic ctx
def _reader(name: str):
    return importlib.import_module("layer_metrics." + name)


def _status(t: float, rows: int = 0, **health) -> tuple[float, dict]:
    return t, {"rows": rows, "health": health}


@pytest.mark.parametrize("metric,key", [
    ("shard_ops_skew", "shard_ops"),
    ("shard_depth_skew", "shard_row_slots_scanned"),
])
def test_per_shard_skew_readers(metric, key):
    mod = _reader(metric)
    ctx = {"w0": 100.0, "w1": 110.0, "traced": {}, "parsed": [
        _status(99.0, **{key: [0, 0, 0, 0]}),          # before the window
        _status(100.5, **{key: [10, 10, 10, 10]}),
        _status(105.0, **{key: [40, 30, 20, 50]}),
        _status(109.5, **{key: [110, 60, 35, 90]}),
        _status(110.5, **{key: [900, 60, 35, 90]}),    # after it
    ]}
    assert mod.read(ctx) == pytest.approx(100 / 25)
    # The parent of this PR counts no depth: nothing to read, no error.
    ctx["parsed"] = [_status(t, cohort_steps=0) for t in (101.0, 109.0)]
    assert mod.read(ctx) is None
    # A shard that got nothing in the window: no ratio.
    ctx["parsed"] = [_status(101.0, **{key: [1, 1, 1, 1]}),
                     _status(109.0, **{key: [5, 1, 3, 4]})]
    assert mod.read(ctx) is None
    ctx["parsed"] = []
    assert mod.read(ctx) is None


def test_a_traced_run_keeps_the_windows_per_shard_deltas_as_evidence():
    """``breakdown.mesh_window``: what PERF.md quotes (the deltas of
    ``shard_ops`` add up to the rows applied between the same two lines)."""
    breakdown: dict = {}
    ctx = {"w0": 100.0, "w1": 110.0, "traced": {"breakdown": breakdown},
           "parsed": [
               _status(100.5, rows=40, shard_ops=[10, 10, 10, 10],
                       shard_row_slots_scanned=[4, 4, 4, 4]),
               _status(109.5, rows=335, shard_ops=[110, 60, 35, 130],
                       shard_row_slots_scanned=[30, 28, 20, 31])]}
    assert _reader("shard_ops_skew").read(ctx) == pytest.approx(120 / 25)
    window = breakdown["mesh_window"]
    assert window["shard_ops"] == [100, 50, 25, 120]
    assert window["shard_row_slots_scanned"] == [26, 24, 16, 27]
    assert sum(window["shard_ops"]) == window["rows_applied"] == 295
    # On the parent the depth list is missing and says so.
    for _t, s in ctx["parsed"]:
        del s["health"]["shard_row_slots_scanned"]
    _reader("shard_ops_skew").read(ctx)
    assert breakdown["mesh_window"]["shard_row_slots_scanned"] is None


def test_mesh_busy_skew_reader():
    mod = _reader("mesh_busy_skew")
    devices = [{"plane": f"/device:TPU:{i}", "busy_ns": ns}
               for i, ns in enumerate([4_000, 5_000, 4_500, 4_000])]
    ctx = {"traced": {"device_summary": {"devices": devices}}}
    assert mod.read(ctx) == pytest.approx(1.25)
    assert mod.read({"traced": {"device_summary": {
        "devices": devices[:1]}}}) is None
    assert mod.read({"traced": {}}) is None


def test_mesh_step_readers_read_whole_executions_of_the_traced_device():
    """``mesh_step_device_ms`` and ``mesh_step_roofline`` on a synthetic
    trace: three executions of the shard_map megastep program on the traced
    device, the first and the last cut by the trace's edges."""
    ms = 1_000_000
    clock = {"stop_perf_ns": int(110.0e9)}
    # The traced span is 108..110 s on the host's clock.
    traced = {
        "clock": clock, "window_s": 2.0,
        "module_events": [
            ["jit_apply_megastep", 0, 10 * ms],          # cut: not counted
            ["jit_apply_megastep", 500 * ms, 40 * ms],
            ["jit_apply_megastep", 1000 * ms, 60 * ms],
            ["jit_apply_megastep", 1900 * ms, 5 * ms],   # cut
        ],
        "flight": [("dispatch", 108.4, 108.41,
                    {"kind": "full", "k": 1, "shards": 4, "rows": 2}),
                   ("dispatch", 108.9, 108.91,
                    {"kind": "full", "k": 1, "shards": 4, "rows": 3})],
    }
    doc_bytes = 1_000_000
    ctx = {
        "traced": traced, "n_docs": 8,
        "ready": {"device_kind": "TPU v5 lite",
                  "resident_bytes_per_device": {
                      str(i): 2 * doc_bytes for i in range(4)}},
        "spec": {"cell": {"chips": 4}},
        # One loop inside the span: rows 100 -> 110 over 4 documents.
        "status": [(108.0, 100), (108.5, 110)],
        "groups": [(0.0, 0.0, 100, 1, 0)] + [
            (0.0, 0.0, 100 + 2 * (i + 1), 2, i % 4) for i in range(5)],
    }
    assert _reader("mesh_step_device_ms").read(ctx) == pytest.approx(50.0)
    need = 2.0 * 4 * doc_bytes + 10 * 4 * (8 + 8)
    least_s = need / (819e9 * 4)
    assert _reader("mesh_step_roofline").read(ctx) == pytest.approx(
        100.0 * least_s / 0.050)
    # No device plane in the trace (a CPU rehearsal): nothing to read.
    ctx["traced"] = {"flight": traced["flight"]}
    assert _reader("mesh_step_device_ms").read(ctx) is None
    assert _reader("mesh_step_roofline").read(ctx) is None
