"""Observability plane tests (ISSUE 7): histogram percentile correctness
against numpy quantiles, flight-recorder ring wraparound, Chrome-trace JSON
schema validity, /metrics text-format parse round-trip, recompile watchdog,
the telemetry satellites (PerformanceEvent start timestamp,
SampledTelemetryHelper.flush_all), the fftpu-trace summarizer, and an e2e
smoke asserting a fleet run produces ingest -> upload -> dispatch ->
readback spans with consistent nesting plus a scrapeable metrics surface.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from fluidframework_tpu.models.doc_batch_engine import DocBatchEngine
from fluidframework_tpu.observability import (
    FlightRecorder,
    MetricsPlane,
    MetricsServer,
    RecompileWatchdog,
    install,
    parse_prometheus,
    phase_totals,
    render_prometheus,
    uninstall,
)
from fluidframework_tpu.observability.flight_recorder import phase_shares
from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage
from fluidframework_tpu.server.fleet_consumer import FleetConsumer
from fluidframework_tpu.server.netserver import NetworkServer
from fluidframework_tpu.tools import trace_viewer
from fluidframework_tpu.utils.telemetry import (
    Histogram,
    Logger,
    PerformanceEvent,
    SampledTelemetryHelper,
)


@pytest.fixture(autouse=True)
def _no_global_recorder():
    """Every test starts and ends with no global recorder installed."""
    uninstall()
    yield
    uninstall()


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_empty_and_single_sample(self):
        h = Histogram()
        assert h.percentile(0.5) is None
        assert h.snapshot() == {"count": 0}
        h.record(0.0042)
        # Single sample: clamping to [min, max] makes the answer exact.
        for q in (0.0, 0.5, 0.99, 1.0):
            assert h.percentile(q) == pytest.approx(0.0042)
        snap = h.snapshot()
        assert snap["count"] == 1 and snap["p99"] == pytest.approx(0.0042)

    @pytest.mark.parametrize("dist", ["uniform", "lognormal"])
    def test_percentiles_vs_numpy(self, dist):
        rng = np.random.default_rng(7)
        if dist == "uniform":
            samples = rng.uniform(1e-5, 1e-1, size=5000)
        else:
            samples = np.exp(rng.normal(-7.0, 1.5, size=5000))
        h = Histogram()
        for v in samples:
            h.record(float(v))
        for q in (0.5, 0.9, 0.99):
            got = h.percentile(q)
            want = float(np.quantile(samples, q))
            # Log-bucketed: within one bucket (factor `growth`) of exact.
            assert want / h.growth <= got <= want * h.growth, (q, got, want)
        assert h.count == len(samples)
        assert h.min == pytest.approx(samples.min())
        assert h.max == pytest.approx(samples.max())
        assert h.sum == pytest.approx(samples.sum(), rel=1e-9)

    def test_merge_equals_single(self):
        rng = np.random.default_rng(3)
        samples = rng.uniform(1e-6, 1e-2, size=2000)
        whole, a, b = Histogram(), Histogram(), Histogram()
        for v in samples:
            whole.record(float(v))
        for v in samples[:777]:
            a.record(float(v))
        for v in samples[777:]:
            b.record(float(v))
        a.merge(b)
        assert a.count == whole.count and a.sum == pytest.approx(whole.sum)
        for q in (0.5, 0.9, 0.99):
            assert a.percentile(q) == whole.percentile(q)

    def test_merge_empty_and_layout_mismatch(self):
        a, b = Histogram(), Histogram()
        a.record(1.0)
        a.merge(b)  # merging an empty histogram is a no-op
        assert a.count == 1 and a.percentile(0.5) == pytest.approx(1.0)
        with pytest.raises(ValueError, match="layouts"):
            a.merge(Histogram(growth=2.0))
        with pytest.raises(ValueError):
            a.percentile(1.5)


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_wraparound(self):
        rec = FlightRecorder(capacity=8)
        for i in range(20):
            rec.instant(f"e{i}")
        assert len(rec) == 8
        assert rec.dropped == 12
        names = [e.name for e in rec.events()]
        assert names == [f"e{i}" for i in range(12, 20)]  # oldest first
        ts = [e.ts_ns for e in rec.events()]
        assert ts == sorted(ts)

    def test_span_nesting_and_instants(self):
        rec = install(FlightRecorder())
        from fluidframework_tpu.observability import instant, span

        with span("outer", k=1):
            with span("inner"):
                pass
            instant("mark", x=2)
        evs = rec.events()
        by_name = {e.name: e for e in evs}
        assert by_name["outer"].ph == "X" and by_name["outer"].args == {"k": 1}
        # inner is contained in outer (complete events record at exit, so
        # inner lands first, but its window nests inside outer's).
        outer, inner = by_name["outer"], by_name["inner"]
        assert outer.ts_ns <= inner.ts_ns
        assert inner.ts_ns + inner.dur_ns <= outer.ts_ns + outer.dur_ns
        assert by_name["mark"].ph == "i"

    def test_noop_without_recorder(self):
        from fluidframework_tpu.observability import instant, span

        with span("free"):  # no recorder installed: must not raise
            instant("free2")

    def test_chrome_trace_schema(self, tmp_path):
        rec = FlightRecorder()
        with rec.span("phase_a", doc="d0"):
            pass
        rec.instant("recompile", program="p")
        path = tmp_path / "trace.json"
        n = rec.export_chrome_trace(str(path))
        assert n == 2
        doc = json.loads(path.read_text())
        assert isinstance(doc["traceEvents"], list)
        assert doc["displayTimeUnit"] == "ms"
        for ev in doc["traceEvents"]:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
            assert ev["ph"] in ("X", "i")
            if ev["ph"] == "X":
                assert "dur" in ev and ev["dur"] >= 0
            else:
                assert ev["s"] == "t"
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert spans[0]["args"] == {"doc": "d0"}

    def test_phase_totals_and_shares(self):
        rec = FlightRecorder()
        with rec.span("a"):
            pass
        with rec.span("a"):
            pass
        with rec.span("b"):
            pass
        totals = phase_totals(rec.events())
        assert set(totals) == {"a", "b"} and totals["a"] >= 0
        shares = phase_shares(rec.events())
        assert sum(shares.values()) == pytest.approx(1.0, abs=0.01)


class TestRecompileWatchdog:
    def test_counts_cache_growth(self):
        import jax

        fn = jax.jit(lambda x: x + 1)
        rec = install(FlightRecorder())
        wd = RecompileWatchdog()
        wd.register("probe", fn)
        wd.register("probe", fn)  # idempotent
        with pytest.raises(AttributeError):
            # An untrackable program must not register silently and then
            # report zero recompiles forever.
            wd.register("not_jitted", lambda x: x)
        assert wd.poll() == 0
        fn(np.zeros((2,), np.float32))
        first = wd.poll()
        assert first >= 1 and wd.recompiles == first
        # A NEW shape after the program specialized = de-specialization:
        # counted AND emits the instant event.
        fn(np.zeros((3,), np.float32))
        assert wd.poll() >= 1
        assert wd.per_program["probe"] == wd.recompiles >= 2
        assert any(e.name == "recompile" for e in rec.events())


# ---------------------------------------------------------------------------
# Metrics plane
# ---------------------------------------------------------------------------


class TestMetricsPlane:
    def test_render_parse_round_trip(self):
        h = Histogram()
        for v in (0.001, 0.002, 0.004, 0.1):
            h.record(v)
        tree = {
            "engine": {
                "rows": 42,
                "ok": True,
                "shard_queue_depth": [3, 0, 7],
                "label": "not-a-metric",
            },
            "latency": {"op_latency": h},
        }
        text = render_prometheus(tree)
        parsed = parse_prometheus(text)
        assert parsed[("fftpu_engine_rows", ())] == 42.0
        assert parsed[("fftpu_engine_ok", ())] == 1.0
        assert parsed[
            ("fftpu_engine_shard_queue_depth", (("idx", "2"),))
        ] == 7.0
        assert parsed[("fftpu_latency_op_latency_count", ())] == 4.0
        p50 = parsed[("fftpu_latency_op_latency", (("quantile", "0.5"),))]
        assert 0.001 <= p50 <= 0.01
        # Non-numeric leaves are /status-only.
        assert not any("label" in name for name, _ in parsed)

    def test_netserver_http_front_routes(self):
        from fluidframework_tpu.server.netserver import ServicePlane

        plane = ServicePlane().start()
        try:
            with plane.nexus.lock:
                plane.service.document("d0")
            base = f"http://127.0.0.1:{plane.http.port}"
            text = urllib.request.urlopen(f"{base}/metrics").read().decode()
            parsed = parse_prometheus(text)
            assert parsed[("fftpu_n_docs", ())] == 1.0
            assert parsed[("fftpu_docs_d0_log_depth", ())] == 0.0
            status = json.loads(
                urllib.request.urlopen(f"{base}/status").read()
            )
            assert status["docs"]["d0"]["pending"] == 0
            assert status["uptime_s"] >= 0
        finally:
            plane.stop()

    def test_scribe_state_and_log_depth_scrape(self, tmp_path):
        """Scribe pool state + ordered-log depth flow through the plane:
        fold spans land in the trace, health renders as gauges."""
        from fluidframework_tpu.server.ordered_log import DurableTopic
        from fluidframework_tpu.server.scribe import ScribeConfig, ScribeLambda

        rec = install(FlightRecorder())
        topic = DurableTopic(
            "deltas", 1, str(tmp_path / "log"),
            encode=lambda m: m.to_json(),
            decode=SequencedMessage.from_json,
        )
        try:
            topic.produce("d0", SequencedMessage(
                seq=0, min_seq=0, ref_seq=0, client_id="w0", client_seq=0,
                type=MessageType.JOIN,
                contents={"clientId": "w0", "short": 0},
            ))
            for s in range(1, 5):
                topic.produce("d0", SequencedMessage(
                    seq=s, min_seq=0, ref_seq=s - 1, client_id="w0",
                    client_seq=s, type=MessageType.OP,
                    contents={"type": 0, "pos1": 0, "seg": "ab"},
                ))
            scribe = ScribeLambda(
                topic, str(tmp_path / "scribe"),
                config=ScribeConfig(max_ops=2),
            )
            try:
                scribe.pump()
                names = {e.name for e in rec.events()}
                assert {"scribe.fold", "scribe.summarize",
                        "scribe.ack"} <= names
                plane = MetricsPlane()
                plane.register("scribe", scribe.health)
                parsed = parse_prometheus(plane.metrics_text())
                assert parsed[("fftpu_scribe_summaries_written", ())] >= 1
                assert ("fftpu_scribe_log_lag", ()) in parsed
                assert (
                    "fftpu_scribe_log_depth", (("idx", "0"),)
                ) in parsed
            finally:
                scribe.close()
        finally:
            topic.close()

    def test_server_scrape(self):
        plane = MetricsPlane()
        plane.register("src", lambda: {"value": 5, "note": "text"})
        plane.register("bad", lambda: 1 / 0)
        srv = MetricsServer(plane, port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            text = urllib.request.urlopen(f"{base}/metrics").read().decode()
            assert parse_prometheus(text)[("fftpu_src_value", ())] == 5.0
            status = json.loads(
                urllib.request.urlopen(f"{base}/status").read()
            )
            assert status["src"] == {"value": 5, "note": "text"}
            assert "scrape_error" in status["bad"]
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{base}/nope")
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Telemetry satellites
# ---------------------------------------------------------------------------


class TestTelemetrySatellites:
    def test_performance_event_start_timestamp(self):
        import time as _time

        log = Logger()
        before = _time.time()
        with PerformanceEvent(log, "load", docId="d"):
            pass
        (e,) = log.matching(category="performance")
        # Backward-compatible schema: old fields intact, startTime added.
        assert e["eventName"] == "load_end" and e["duration"] >= 0
        assert before <= e["startTime"] <= _time.time()

    def test_performance_event_cancel_carries_start(self):
        log = Logger()
        with pytest.raises(RuntimeError):
            with PerformanceEvent(log, "load"):
                raise RuntimeError("boom")
        (e,) = log.matching(category="error")
        assert e["startTime"] > 0

    def test_flush_all_drains_residual_buckets(self):
        log = Logger()
        h = SampledTelemetryHelper(log, "applyOp", sample_every=10)
        for _ in range(7):
            h.record(0.001, bucket="insert")
        for _ in range(3):
            h.record(0.002, bucket="remove")
        assert not log.matching(eventName="applyOp")  # below sample_every
        assert h.flush_all() == 2
        events = log.matching(eventName="applyOp")
        assert {e["bucket"] for e in events} == {"insert", "remove"}
        assert sum(e["count"] for e in events) == 10
        assert h.flush_all() == 0  # idempotent once drained

    def test_engine_flush_telemetry_via_status_snapshot(self):
        from fluidframework_tpu.server.fleet_main import status_snapshot

        log = Logger()
        eng = DocBatchEngine(
            1, max_segments=64, text_capacity=512, max_insert_len=8,
            ops_per_step=4, use_mesh=False, recovery="off", telemetry=log,
        )
        _feed_engine(eng, n_docs=1, rounds=3)
        assert not log.matching(eventName="engine_step")  # below sample_every
        snap = status_snapshot(eng, ["d0"])
        (e,) = log.matching(eventName="engine_step")
        assert e["bucket"] == "step" and e["count"] == 3
        assert snap["health"]["latency_samples"] >= 0


# ---------------------------------------------------------------------------
# Engine integration: latency histograms + spans + metrics surface
# ---------------------------------------------------------------------------


def _feed_engine(eng, n_docs: int, rounds: int, seq0: int = 0) -> int:
    for d in range(n_docs):
        if seq0 == 0:
            eng.ingest(d, SequencedMessage(
                seq=0, min_seq=0, ref_seq=0, client_id="w0", client_seq=0,
                type=MessageType.JOIN,
                contents={"clientId": "w0", "short": 0},
            ))
    seq = seq0
    for _r in range(rounds):
        idxs, msgs = [], []
        seq += 1
        for d in range(n_docs):
            idxs.append(d)
            msgs.append(SequencedMessage(
                seq=seq, min_seq=0, ref_seq=seq - 1, client_id="w0",
                client_seq=seq, type=MessageType.OP,
                contents={"type": 0, "pos1": 0, "seg": "ab"},
                timestamp=time.time(),  # as the sequencer stamps it
            ))
        eng.ingest_batch(idxs, msgs)
        eng.step()
    return seq


class TestEngineObservability:
    def test_latency_histograms_in_health(self):
        eng = DocBatchEngine(
            2, max_segments=64, text_capacity=512, max_insert_len=8,
            ops_per_step=4, use_mesh=False, recovery="off",
        )
        _feed_engine(eng, n_docs=2, rounds=4)
        h = eng.health()
        # Every message is a sample now (one row each on this path): the
        # constructor's sampling option went with the second sampler.
        assert h["latency_samples"] == 8
        assert h["latency_p99_ms"] >= h["latency_p50_ms"] >= 0
        hists = eng.latency_histograms()
        assert hists["op_latency"].count == 8
        # ``op_latency`` IS the op clock's sequenced -> applied, and the
        # stages ride beside it.
        assert hists["op_latency"] is eng.op_clock.sequenced_to_applied
        assert {"sequenced_to_received", "received_to_applied",
                "sequenced_to_applied", "recovery_time"} <= set(hists)
        assert eng.op_clock.rows == 8 and eng.op_clock.unstamped_rows == 0

    def test_engine_spans_and_metrics_text(self):
        rec = install(FlightRecorder())
        eng = DocBatchEngine(
            2, max_segments=64, text_capacity=512, max_insert_len=8,
            ops_per_step=4, use_mesh=False, recovery="grow",
        )
        _feed_engine(eng, n_docs=2, rounds=2)
        names = {e.name for e in rec.events()}
        assert {"ingest", "upload", "dispatch"} <= names
        plane = MetricsPlane()
        plane.register("engine", eng.health)
        plane.register("latency", eng.latency_histograms)
        parsed = parse_prometheus(plane.metrics_text())
        assert parsed[("fftpu_engine_latency_samples", ())] > 0
        assert ("fftpu_engine_recompiles", ()) in parsed
        assert parsed[
            ("fftpu_latency_op_latency", (("quantile", "0.99"),))
        ] > 0
        for stage in ("sequenced_to_received", "received_to_applied",
                      "sequenced_to_applied"):
            assert parsed[(f"fftpu_latency_{stage}_count", ())] == 4.0


# ---------------------------------------------------------------------------
# fftpu-trace CLI
# ---------------------------------------------------------------------------


class TestTraceViewer:
    def test_summarize_trace_file(self, tmp_path, capsys):
        rec = FlightRecorder()
        with rec.span("dispatch", k=4):
            with rec.span("upload", shards=1):
                pass
        rec.instant("recompile", program="fleet_megastep", cache_size=2)
        rec.instant("migrate_doc", doc="d0", src=0, dst=1)
        path = str(tmp_path / "t.json")
        rec.export_chrome_trace(path)
        assert trace_viewer.main([path, "--top", "5"]) == 0
        out = capsys.readouterr().out
        assert "phase shares" in out
        assert "dispatch" in out and "upload" in out
        assert "recompile events: 1" in out
        assert "fleet_megastep" in out
        assert "migrate_doc" in out

    def test_bad_file(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        assert trace_viewer.main([str(bad)]) == 1


# ---------------------------------------------------------------------------
# E2E smoke: a fleet run traces end to end and scrapes
# ---------------------------------------------------------------------------


def _assert_consistent_nesting(events) -> None:
    """Per thread, any two spans are either disjoint or properly nested —
    the invariant that makes the Perfetto rendering a tree."""
    by_tid: dict[int, list] = {}
    for e in events:
        if e.ph == "X":
            by_tid.setdefault(e.tid, []).append(e)
    for spans in by_tid.values():
        spans.sort(key=lambda e: (e.ts_ns, -e.dur_ns))
        for i, a in enumerate(spans):
            for b in spans[i + 1:]:
                a0, a1 = a.ts_ns, a.ts_ns + a.dur_ns
                b0, b1 = b.ts_ns, b.ts_ns + b.dur_ns
                assert b0 >= a1 or b1 <= a1, (
                    f"partial overlap: {a.name} and {b.name}"
                )


class TestFleetE2E:
    def test_fleet_run_traces_and_scrapes(self, tmp_path):
        from fluidframework_tpu.dds.shared_string import SharedString

        rec = install(FlightRecorder())
        srv = NetworkServer().start()
        try:
            rows = 0
            with srv.lock:
                doc = srv.service.document("d0")
                w = SharedString(client_id="w0")
                doc.connect(w.client_id, w.process)
                doc.process_all()
                for i in range(12):
                    w.insert_text(0, "ab")
                    for m in w.take_outbox():
                        doc.submit(m)
                        rows += 1
                doc.process_all()
            eng = DocBatchEngine(
                1, max_segments=128, text_capacity=1024, max_insert_len=8,
                ops_per_step=8, use_mesh=False, recovery="grow",
            )
            fc = FleetConsumer("127.0.0.1", srv.port, eng, ["d0"])
            try:
                fc.run_for(rows)
                assert eng.text(0) == w.text
            finally:
                fc.close()
        finally:
            srv.stop()

        events = rec.events()
        names = {e.name for e in events}
        # The full pipeline left its trace: wire decode -> staging upload
        # -> megastep dispatch -> error-latch readback.
        assert {"ingest", "upload", "dispatch", "readback"} <= names, names
        _assert_consistent_nesting(events)
        # Op latency resolved through the same run, from the sequencer's
        # wire stamps: every row the native path staged is on the clock.
        assert eng.op_clock.sequenced_to_applied.count == rows
        assert eng.op_clock.unstamped_rows == 0
        assert eng.health()["latency_p99_ms"] > 0
        # The trace is Perfetto-loadable JSON.
        path = str(tmp_path / "fleet.json")
        n = rec.export_chrome_trace(path)
        assert n == len(events)
        doc = json.loads(open(path).read())
        assert len(doc["traceEvents"]) == n
        # And the run scrapes: engine health + latency through one plane.
        plane = MetricsPlane()
        plane.register("fleet", eng.health)
        plane.register("latency", eng.latency_histograms)
        parsed = parse_prometheus(plane.metrics_text())
        assert parsed[("fftpu_fleet_latency_samples", ())] > 0
        assert ("fftpu_latency_op_latency", (("quantile", "0.5"),)) in parsed
