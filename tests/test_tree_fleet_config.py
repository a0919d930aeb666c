"""The SharedTree deployment of the benchmark (``tree_fleet_256x16k`` and its
cell ``tree1_edit_steady``, PR 28) at a small size on the CPU: the served tree
fleet against the plain reference and every writer, the control of
``correct``, the kernel's scopes, the engine's spans, the cell's files and
each new per-layer reader on a recorded ``ctx``."""

from __future__ import annotations

import copy
import functools
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import run as bench_run  # noqa: E402
from plants import shared_tree  # noqa: E402

from fluidframework_tpu.loadgen.coordinator import oracle_tree  # noqa: E402
from fluidframework_tpu.models import tree_batch_engine as tbe  # noqa: E402
from fluidframework_tpu.native.ingest_native import available  # noqa: E402
from fluidframework_tpu.observability import flight_recorder as fr  # noqa: E402
from fluidframework_tpu.ops import tree_kernel as tk  # noqa: E402
from fluidframework_tpu.server.fleet_consumer import FleetConsumer  # noqa: E402

CELL = "tree1_edit_steady"
WEIGHTS = {"insert_root": 0.45, "insert_nested": 0.27, "set_value": 0.24,
           "remove": 0.04}
SMALL = {"nodes_per_doc": 256, "root_nodes": 32, "fill_run": 16,
         "churned_docs": 0, "churn_nodes": 0, "weights": WEIGHTS}
N_DOCS, CAPACITY, B = 8, 1024, 8

needs_native = pytest.mark.skipif(
    not available(), reason="native ingest library unavailable")


def _norm(x):
    return json.loads(json.dumps(x))


class Fleet:
    """Front + sequencer + 4 writers a document (the benchmark's plant) and
    the device tier in this process: ``FleetConsumer`` over a
    ``TreeBatchEngine``."""

    def __init__(self, seed: int, k: int = 8, params: dict = SMALL,
                 n_docs: int = N_DOCS) -> None:
        self.plant = shared_tree.Plant(seed, n_docs, params)
        for d in self.plant.doc_ids:
            self.plant.join(d, 4)
        self.eng = tbe.TreeBatchEngine(
            n_docs, capacity=CAPACITY, ops_per_step=B,
            max_insert_len=params["fill_run"], megastep_k=k,
            doc_keys=self.plant.doc_ids)
        self.fc = FleetConsumer(
            "127.0.0.1", self.plant.port, self.eng, self.plant.doc_ids)

    def burst(self, depth: int, docs=None) -> None:
        for d in docs or self.plant.doc_ids:
            for _ in range(depth):
                self.plant.edit(d)
            self.plant.flush(d)
        self.fc.run_for(self.plant.ops)

    def final(self) -> dict:
        return {"health": self.eng.health(), "errors": 0,
                "rows": self.fc.rows_staged,
                "trees": _norm(dict(zip(self.plant.doc_ids,
                                        self.eng.trees_json())))}

    def close(self) -> None:
        self.fc.close()
        self.plant.stop()


@pytest.fixture
def fleet_of():
    made = []

    def make(*args, **kwargs) -> Fleet:
        made.append(Fleet(*args, **kwargs))
        return made[-1]

    yield make
    for f in made:
        f.close()


# ------------------------------------------------- the served fleet, small
@needs_native
@pytest.mark.parametrize("k", [1, 2, 4, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_served_tree_fleet_equals_reference_and_writers(fleet_of, seed, k):
    f = fleet_of(seed, k)
    fill = f.plant.fill_rows()
    assert fill == 16
    # The fill and some steady edits in ONE flush a document, by its first
    # writer: a queue deep enough for a megastep of K slices.
    f.burst(fill + B * k)
    assert all(len(ws) == 4 for ws in f.plant.writers.values())
    rng = np.random.default_rng(seed)
    for _tick in range(12):
        # Ticks with concurrent ops: up to 6 edits on a document, made by
        # different writers in turn against the state before the tick.
        for d in rng.choice(N_DOCS, size=4, replace=False):
            doc_id = f.plant.doc_ids[int(d)]
            for _ in range(int(rng.integers(1, 7))):
                f.plant.edit(doc_id)
            f.plant.flush(doc_id)
        f.fc.run_for(f.plant.ops)
    h = f.eng.health()
    assert f.plant.nacks == 0 and not f.eng.errors().any()
    assert f.fc.rows_staged == f.plant.ops      # one device row an edit
    assert h["fallback_docs"] == 0 and h["device_fraction"] == 1.0
    assert h["ingest_plane"] == "native"
    # The burst reached the K this case names (and only a cap of 1 stays
    # on the per-slice program).
    assert h["megastep_slices"] - h["megastep_dispatches"] >= k - 1
    trees = f.eng.trees_json()
    for i, doc_id in enumerate(f.plant.doc_ids):
        log = list(f.plant.srv.service.document(doc_id).sequencer.log)
        want = _norm(oracle_tree(log))
        assert _norm(trees[i]) == want, doc_id
        assert _norm(f.eng.tree_json(i)) == want, doc_id
        for w in f.plant.writers[doc_id]:
            assert _norm(w.root_json()) == want, w.client_id
    check = f.plant.verify(f.final(), f.plant.doc_ids, [], seed, 20.0, 512)
    assert check["ok"] and check["verified"] == N_DOCS, check


@needs_native
def test_fill_builds_the_configured_tree(fleet_of):
    f = fleet_of(5)
    f.burst(16)
    for doc_id in f.plant.doc_ids:
        root = f.plant.writers[doc_id][0].root
        assert f.plant.nodes(doc_id) == SMALL["nodes_per_doc"]
        assert len(root) == SMALL["root_nodes"]
        below = SMALL["nodes_per_doc"] - len(root)
        assert below >= SMALL["nodes_per_doc"] // 2
        assert any(n.fields.get(shared_tree.SUB_FIELD) for n in root)


# ----------------------------------------------- the control of `correct`
def _alter_root_value(tree):
    tree[3]["v"] = tree[3]["v"] + 1


def _alter_nested_value(tree):
    node = next(n for n in tree if n.get("f"))
    node["f"][shared_tree.SUB_FIELD][0]["v"] += 1


def _drop_a_node(tree):
    del tree[-1]


@needs_native
@pytest.mark.parametrize(
    "alter", [_alter_root_value, _alter_nested_value, _drop_a_node])
def test_verify_refuses_one_altered_document(fleet_of, alter):
    f = fleet_of(7)
    f.burst(20)
    final = f.final()
    ids = f.plant.doc_ids
    assert f.plant.verify(final, ids, [], 1, 20.0, 512)["ok"]
    bad = copy.deepcopy(final)
    alter(bad["trees"][ids[5]])
    got = f.plant.verify(bad, ids, [], 1, 20.0, 512)
    assert not got["ok"] and ids[5] in got["why"], got


@needs_native
@pytest.mark.parametrize("key,value", [
    ("fallback_docs", 1), ("device_fraction", 0.99)])
def test_verify_refuses_a_document_off_the_device_path(fleet_of, key, value):
    f = fleet_of(8)
    f.burst(16)
    final = f.final()
    final["health"][key] = value
    assert not f.plant.verify(
        final, f.plant.doc_ids, [], 1, 20.0, 512)["ok"]


@needs_native
def test_verify_refuses_a_ladder_too_shallow_to_fill(fleet_of):
    f = fleet_of(9)
    f.burst(15)                 # one row short of the fill
    got = f.plant.verify(f.final(), f.plant.doc_ids, [], 1, 20.0, 512)
    assert not got["ok"] and "ladder" in got["why"]


# ------------------------------------------------------- kernel scopes
@functools.lru_cache(maxsize=1)
def _lowered_texts() -> tuple[str, str]:
    proto = tk.init_nested_forest(32, 16)
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (2,) + x.shape), proto)
    ops = jnp.zeros((2, 2, tk.NESTED_OP_FIELDS), jnp.int32)
    payloads = jnp.zeros((2, 2, 4), jnp.int32)
    step = tbe._tree_step_jit.lower(state, ops, payloads).as_text(
        debug_info=True)
    compact = tbe._tree_compact_jit.lower(state).as_text(debug_info=True)
    return step, compact


@pytest.mark.parametrize("path", [
    *tk.NESTED_SCOPES, "insert/pool_write", "set_value/pool_write",
    "remove/kill_descendants", "replace_field/kill_descendants"])
def test_nested_op_scopes_are_in_the_lowered_step(path):
    import tree_scopes

    step, _compact = _lowered_texts()
    # MLIR carries the scope path as the name of each op's location; XLA
    # joins the parts into the instruction's op_name, which the trace keeps
    # and the benchmark's reader maps back to a scope path (a scope entered
    # right under the vmap is written ``vmap(insert)``).
    names = set(re.findall(r'loc\("([^"]*)"', step))
    found = {tree_scopes.scope_of(n) for n in names}
    if "/" in path:
        assert path in found, sorted(found)
    else:   # pool_write and kill_descendants also nest under a kind
        assert any(path in f.split("/") for f in found), sorted(found)
        assert any(re.search(rf"(?:^|/)(?:vmap\()?{path}\)?(?:/|$)", n)
                   for n in names)


def test_compact_is_scoped_and_the_scopes_are_the_benchmarks():
    import tree_scopes

    _step, compact = _lowered_texts()
    # Entered right under the vmap, the scope is written into its name.
    assert 'loc("jit(compact_nested)/vmap(compact)/' in compact
    assert tree_scopes.scope_of(
        "jit(compact_nested)/vmap(compact)/add") == "compact"
    assert set(tree_scopes.TREE_SCOPES) == {*tk.NESTED_SCOPES, "compact"}
    assert tree_scopes.scope_of(
        "jit(apply_nested_ops)/vmap()/while/body/remove/kill_descendants/"
        "select_n") == "remove/kill_descendants"
    assert tree_scopes.scope_of("jit(f)/while/body/add") == "unscoped"


# ------------------------------------- the slot-wise step is the old step
def _random_batches(seed: int, docs: int = 4, slots: int = 8, batches: int = 5):
    """Op rows that build two-level trees and then edit them: bulk and
    nested inserts, removes of roots that hold subtrees, sets, a whole-field
    replace, moves, ops that fail (a latch must read the same), NOOP slots."""
    rng = np.random.default_rng(seed)
    t, L = tk._TGT, 4
    n_root = [0] * docs
    for b in range(batches):
        ops = np.zeros((docs, slots, tk.NESTED_OP_FIELDS), np.int32)
        pay = rng.integers(0, 99, (docs, slots, L)).astype(np.int32)
        for d in range(docs):
            for k in range(slots):
                r = rng.random()
                row = ops[d, k]
                row[1] = b * slots + k + 1
                if r < 0.15:
                    continue                                    # NOOP
                if n_root[d] < 3 or r < 0.35:
                    c = int(rng.integers(1, L + 1))
                    row[0] = tk.NestedOpKind.INSERT
                    row[t + 1], row[t + 2] = rng.integers(0, n_root[d] + 1), c
                    row[t + 5] = tk.VKIND_INT
                    n_root[d] += c
                elif r < 0.6:                   # under a root node's field 1
                    row[0], row[2] = tk.NestedOpKind.INSERT, 1
                    row[3], row[4] = 0, rng.integers(0, n_root[d])
                    row[t], row[t + 2], row[t + 5] = 1, 2, tk.VKIND_INT
                elif r < 0.72:
                    row[0] = tk.NestedOpKind.REMOVE
                    row[t + 1], row[t + 2] = rng.integers(0, n_root[d]), 1
                    n_root[d] -= 1
                elif r < 0.8:
                    row[0] = tk.NestedOpKind.SET
                    row[t + 1], row[t + 4] = rng.integers(0, n_root[d]), 7
                    row[t + 5] = tk.VKIND_INT
                elif r < 0.86:
                    row[0], row[2] = tk.NestedOpKind.REPLACE_FIELD, 1
                    row[3], row[4] = 0, rng.integers(0, n_root[d])
                    row[t], row[t + 2], row[t + 5] = 1, 1, tk.VKIND_INT
                elif r < 0.93:
                    row[0] = tk.NestedOpKind.MOVE
                    row[t + 1], row[t + 2] = 0, 1
                    row[t + 3] = rng.integers(0, n_root[d] + 1)
                else:                           # out of range: latches
                    row[0] = tk.NestedOpKind.REMOVE
                    row[t + 1], row[t + 2] = n_root[d] + 5, 1
        yield jnp.asarray(ops), jnp.asarray(pay)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_fleet_step_is_bit_identical_to_the_per_document_step(seed):
    proto = tk.init_nested_forest(256, 32)
    a = b = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (4,) + x.shape), proto)
    per_doc = jax.jit(jax.vmap(tk.apply_nested_ops))
    fleet = jax.jit(tk.apply_nested_fleet)
    batches = list(_random_batches(seed))
    for ops, pay in batches:
        a, b = per_doc(a, ops, pay), fleet(b, ops, pay)
        for name, x, y in zip(a._fields, a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y)), name
    assert int(np.asarray(a.alive).sum()) > 20
    assert np.asarray(a.error).any()        # the failing ops latched
    # The megastep is the fleet step over K slices.
    c = jax.tree.map(lambda x: jnp.broadcast_to(x, (4,) + x.shape), proto)
    c = jax.jit(tk.apply_nested_megastep)(
        c, jnp.stack([o for o, _ in batches]),
        jnp.stack([p for _, p in batches]))
    for name, x, y in zip(a._fields, a, c):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name


# --------------------------------------------------------- engine spans
@functools.lru_cache(maxsize=1)
def _recorded_spans() -> tuple:
    """Spans of a small served fleet: a fill, concurrent edits and one
    compaction, with a recorder installed."""
    rec = fr.install(fr.FlightRecorder(1 << 16))
    try:
        f = Fleet(11, 2, {**SMALL, "churned_docs": 1, "churn_nodes": 528})
        try:
            f.burst(16 + 33 + 1)        # the churned document's whole fill
            f.burst(4)
            health = f.eng.health()
        finally:
            f.close()
    finally:
        fr.uninstall()
    return tuple(e for e in rec.events() if e.ph == "X"), health


@needs_native
@pytest.mark.parametrize("name", [
    "ingest", "pack", "compact", "recover", "housekeeping"])
def test_new_engine_spans_are_recorded_clear_of_the_device_spans(name):
    events, _health = _recorded_spans()
    mine = [e for e in events if e.name == name]
    assert mine, name
    device = [e for e in events if e.name in ("dispatch", "readback")]
    assert device
    for e in mine:
        for d in device:
            apart = (e.ts_ns + e.dur_ns <= d.ts_ns
                     or d.ts_ns + d.dur_ns <= e.ts_ns)
            assert apart, (e, d)


@needs_native
def test_span_labels_and_the_compaction_counter():
    events, health = _recorded_spans()
    by = {}
    for e in events:
        by.setdefault(e.name, []).append(e)
    assert all(e.args["kind"] == "tree" for e in by["dispatch"])
    assert all(e.args["kind"] == "tree" for e in by["pack"])
    assert {(e.args or {}).get("kind") for e in by["housekeeping"]} == {
        None, "checkpoint"}
    # The host fold's spans lie inside the ingest that fed them.
    ing = [(e.ts_ns, e.ts_ns + e.dur_ns) for e in by["ingest"]]
    for e in by["host_fold_rebase"]:
        assert any(a <= e.ts_ns and e.ts_ns + e.dur_ns <= b for a, b in ing)
    assert health["tree_compactions"] == len(by["compact"]) >= 1


def test_no_recorder_no_span_and_a_zero_counter():
    assert fr.recorder() is None
    eng = tbe.TreeBatchEngine(2, capacity=64, ops_per_step=4)
    assert eng.health()["tree_compactions"] == 0
    assert eng.step() == 0


# ------------------------------------------------------ the cell's files
@functools.lru_cache(maxsize=1)
def _spec() -> dict:
    return bench_run.load_cell(CELL)


def test_cell_files_load_and_say_what_the_issue_fixed():
    spec = _spec()
    cfg, own, params = spec["config"], spec["own"], spec["params"]
    assert spec["cell"]["chips"] == 1 and cfg["mesh"] == 0
    assert cfg["docs"] == 256 and cfg["architecture"] is None
    assert cfg["writers"] == {
        "hot_threshold_ops_per_s": 0, "hot": 4, "rest": 4}
    flags = cfg["fleet_main_flags"]
    assert flags[:4] == ["--family", "tree", "--capacity", "16384"]
    assert flags[4:] == [
        "--max-insert-len", str(cfg["plant"]["params"]["fill_run"])]
    assert cfg["rehearsal"]["fleet_main_flags"] == flags
    assert cfg["plant"]["module"] == "shared_tree"
    assert cfg["plant"]["params"]["nodes_per_doc"] == 10000
    assert params["doc_distribution"] == "zipf" and params["zipf_s"] == 0.99
    assert params["cap_ops_per_s"] == 16 and params["tick_s"] == 0.05
    assert 0 < params["rate_ops_per_s"] <= 320
    assert own["warm_seconds"] == 5
    bench = spec["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    mine = [m["name"] for m in spec["per_layer"] if m["name"].startswith(
        "tree_")]
    assert len(mine) == 7
    for name in mine:
        assert importlib.import_module("layer_metrics." + name).NAME == name


def test_ladder_fills_every_document_and_reaches_every_program():
    spec = _spec()
    cfg, ladder = spec["config"], spec["own"]["ladder"]
    plant = shared_tree.Plant.__new__(shared_tree.Plant)
    for k, v in cfg["plant"]["params"].items():
        setattr(plant, k, v)
    p = cfg["plant"]["params"]
    rows, churned = plant.fill_rows(), plant.fill_rows(True)
    assert rows == 157 and churned == rows + p["churn_nodes"] // 64 + 1
    per_doc = [0] * cfg["docs"]
    for b in ladder:
        for d in range(b["docs"]):
            per_doc[d] += b["depth"]
    assert per_doc[0] >= churned and min(per_doc[1:]) >= rows
    # Every K the engine can select at --megastep-k 8 and 32 ops a step.
    ks = {min(8, 1 << (max(-(-b["depth"] // 32), 1).bit_length() - 1))
          for b in ladder}
    assert ks == {1, 2, 4, 8}
    # The churned document's row bound passes COMPACT_FRACTION in the
    # ladder, so tree_compact is compiled and run before the window.
    used = p["churn_nodes"] + p["nodes_per_doc"]
    assert used > tbe.TreeBatchEngine.COMPACT_FRACTION * 16384


@pytest.mark.parametrize("seed", [1, 2147483999, 2200000123])
def test_schedule_keeps_the_cap_and_no_document_reaches_compaction(seed):
    spec = _spec()
    cfg, params = spec["config"], spec["params"]
    gen = importlib.import_module("generators.poisson_docs")
    n_docs, window_s, warm_s = cfg["docs"], 45.0, 5.0
    rates = gen.doc_rates(params, n_docs, seed)
    assert rates.max() <= 16 + 1e-9
    assert abs(rates.sum() - params["rate_ops_per_s"]) < 1e-6
    assert 2 <= int((rates >= 16 - 1e-9).sum()) <= 6
    edits = np.zeros(n_docs, np.int64)
    for secs, stream in ((warm_s, 1), (window_s, 2)):
        ticks, docs = gen.schedule(params, n_docs, secs, seed, stream)
        edits += np.bincount(docs, minlength=n_docs)
        # No document is offered more than its cap allows in any second.
        per_s = np.zeros((n_docs, int(secs) + 1), np.int64)
        np.add.at(per_s, (docs, (ticks * params["tick_s"]).astype(int)), 1)
        assert per_s.max() <= 16 + 5 * 4        # 16/s Poisson, 5 sigma
    # Rows a document can hold when the window ends: its fill, the ladder's
    # steady edits and every edit of warm-up and window as an insert.
    ladder = sum(b["depth"] for b in spec["own"]["ladder"]
                 if b["docs"] == n_docs)
    w = cfg["plant"]["params"]["weights"]
    assert abs(sum(w.values()) - 1.0) < 1e-9
    rows = cfg["plant"]["params"]["nodes_per_doc"] + (ladder - 157) + edits
    assert rows.max() < tbe.TreeBatchEngine.COMPACT_FRACTION * 16384


# ------------------------------------------------------ the new readers
def _reader(name: str):
    return importlib.import_module("layer_metrics." + name)


def _status(rows, hits, compactions=0, fraction=1.0, **health):
    return {"rows": rows, "health": {
        "translation_plan_hits": hits, "translation_plan_misses": 3,
        "tree_compactions": compactions, "device_fraction": fraction,
        **health}}


def _ctx(**more) -> dict:
    return {"w0": 100.0, "w1": 110.0, "parsed": [], "traced": {}, **more}


def test_tree_rows_per_edit_reads_rows_over_edits_translated():
    r = _reader("tree_rows_per_edit")
    parsed = [(99.0, _status(5, 5)), (101.0, _status(1000, 997)),
              (109.0, _status(1320, 1317)), (111.0, _status(1400, 1397))]
    assert r.read(_ctx(parsed=parsed)) == 1.0
    parsed[2] = (109.0, _status(1310, 1317))     # ten edits rebased away
    assert r.read(_ctx(parsed=parsed)) == pytest.approx(310 / 320)


def test_tree_device_fraction_reads_the_windows_last_line():
    r = _reader("tree_device_fraction")
    parsed = [(101.0, _status(1, 1)), (109.0, _status(2, 2, fraction=0.5)),
              (111.0, _status(3, 3))]
    assert r.read(_ctx(parsed=parsed)) == 50.0


def test_tree_compacts_in_window_counts_to_the_done_line():
    r = _reader("tree_compacts_in_window")
    parsed = [(99.0, _status(1, 1, 0)), (101.0, _status(2, 2, 1)),
              (109.0, _status(3, 3, 1))]
    assert r.read(_ctx(parsed=parsed, final=_status(4, 4, 3))) == 2
    assert r.read(_ctx(parsed=parsed)) == 0


@pytest.mark.parametrize("metric,spans,want", [
    ("tree_rebase_busy_share", ["host_fold_rebase"], 10.0),
    ("tree_fold_busy_share",
     ["host_fold_rebase", "host_fold_translate", "host_fold_compose",
      "host_fold_mark_alloc"], 40.0),
    ("tree_fold_busy_share", ["host_fold_translate"], 10.0),
])
def test_fold_span_share_readers(metric, spans, want):
    flight = [(s, 99.5, 100.5, {}) for s in spans]      # clipped: 0.5 s
    flight += [(s, 104.0, 104.5, {}) for s in spans]    # 0.5 s
    flight.append(("ingest", 100.0, 110.0, {}))         # not a fold span
    got = _reader(metric).read(_ctx(traced={"flight": flight}))
    assert got == pytest.approx(want)


def _traced_ctx(step_ms=(30.0, 31.0, 29.0)):
    """Five module events on one device: a cut step, three whole steps (one
    of them a megastep), a compaction and a cut step; two loops' stamps."""
    ms = 1_000_000
    events = [["jit_apply_nested_fleet", 0, 7 * ms]]
    t = 10 * ms
    for i, d in enumerate(step_ms):
        name = "jit_apply_nested_megastep" if i == 1 else (
            "jit_apply_nested_fleet")
        events.append([name, t, int(d * ms)])
        t += 50 * ms
    events.append(["jit_compact_nested", t, 5 * ms])
    events.append(["jit_apply_nested_fleet", t + 50 * ms, 2 * ms])
    spec = _spec()
    # groups: (due, sent_at, ops sent so far, ops, doc)
    groups = [(0, 0, 10, 10, 1), (0, 0, 13, 3, 2), (0, 0, 14, 1, 1)]
    return _ctx(
        spec=spec, groups=groups, n_docs=256,
        status=[(104.9, 0), (105.0, 13), (105.1, 14)],
        ready={"device_kind": "TPU v5 lite"},
        traced={"module_events": events, "window_s": 1.0,
                "clock": {"stop_perf_ns": int(105.5e9)},
                "breakdown": {"device_ops": [], "idle_gaps": []}})


def test_tree_step_device_ms_counts_whole_step_executions_only():
    ctx = _traced_ctx()
    assert _reader("tree_step_device_ms").read(ctx) == pytest.approx(30.0)


def test_tree_step_roofline_is_bytes_from_shapes_over_device_time():
    import tree_roofline

    ctx = _traced_ctx()
    g = ctx["spec"]["config"]["geometry"]
    doc = 4 * (9 * 16384 + 4096 + 3)
    row = 4 * (22 + g["max_insert_len"])
    assert tree_roofline.doc_state_bytes(g) == doc
    assert tree_roofline.op_row_bytes(g) == row
    # Loop 1: documents 1 and 2, 13 rows; loop 2: document 1, one row.
    need = ((2 * 2 * doc + 13 * row) + (2 * doc + row)) / 2
    assert tree_roofline.bytes_needed_per_loop(ctx) == need
    got = _reader("tree_step_roofline").read(ctx)
    assert got == pytest.approx(100.0 * (need / 819e9) / 0.030)
    assert 0 < got < 1


@pytest.mark.parametrize("name", [
    "tree_step_device_ms", "tree_step_roofline", "tree_fold_busy_share",
    "tree_rebase_busy_share", "tree_device_fraction", "tree_rows_per_edit",
    "tree_compacts_in_window"])
def test_new_readers_find_nothing_at_a_parent_commit(name):
    # A string fleet's lines, no fold span, no tree program in the trace.
    line = {"rows": 5, "health": {"cohort_steps": 1, "full_steps": 0}}
    ctx = _ctx(
        parsed=[(101.0, line), (109.0, line)], final=line,
        traced={"flight": [("ingest", 101.0, 102.0, {})],
                "module_events": [["jit__fleet_step", 0, 5],
                                  ["jit__fleet_step", 9, 5],
                                  ["jit__fleet_step", 19, 5]]})
    assert _reader(name).read(ctx) is None
