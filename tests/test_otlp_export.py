"""OTLP export paths (internals/telemetry.py + observability/exporter.py):
loopback collector payload shapes, the idempotent ``_otlp_mark`` re-export
guard shared by the periodic flusher and the end-of-run hook, histogram
data points, and the never-raises contract against a refusing collector."""

from __future__ import annotations

import http.server
import json
import threading
import time

import pytest

from pathway_tpu.internals import telemetry, tracing
from pathway_tpu.internals.telemetry import OtlpExporter, export_from_env
from pathway_tpu.internals.tracing import Tracer
from pathway_tpu.observability.exporter import PeriodicFlusher
from pathway_tpu.observability.histogram import LogHistogram


class Collector:
    """Loopback OTLP/HTTP collector; ``mode`` = ok | refuse | hang-free
    error (connection reset via closing early)."""

    def __init__(self, mode: str = "ok"):
        collector = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n)) if n else {}
                collector.received.append((self.path, body))
                if collector.mode == "refuse":
                    self.send_response(503)
                    self.end_headers()
                    self.wfile.write(b"no")
                    return
                self.send_response(200)
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        self.mode = mode
        self.received: list = []
        self.server = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        self.endpoint = f"http://127.0.0.1:{self.server.server_address[1]}"
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self.thread.start()

    def stop(self):
        self.server.shutdown()
        self.server.server_close()

    def paths(self):
        return [p for p, _ in self.received]


@pytest.fixture
def collector():
    c = Collector()
    yield c
    c.stop()


def _traced_tracer() -> Tracer:
    tracer = Tracer(None)
    with tracer.span("engine.run", worker=0):
        # as the executor does it: the row counters ride the tick's append
        with tracer.span("tick", time=42) as tick:
            tick.counter = ("engine_rows.w0", {"input": 5.0, "output": 3.0})
    return tracer


def test_traces_and_metrics_payload_shape(collector, monkeypatch):
    monkeypatch.setenv("PATHWAY_TELEMETRY_SERVER", collector.endpoint)
    monkeypatch.delenv("PATHWAY_MONITORING_SERVER", raising=False)
    tracer = _traced_tracer()
    export_from_env(tracer)
    assert "/v1/traces" in collector.paths()
    assert "/v1/metrics" in collector.paths()
    _, traces = next(x for x in collector.received if x[0] == "/v1/traces")
    scope_spans = traces["resourceSpans"][0]["scopeSpans"][0]
    names = {s["name"] for s in scope_spans["spans"]}
    assert {"engine.run", "tick"} <= names
    for s in scope_spans["spans"]:
        assert int(s["endTimeUnixNano"]) >= int(s["startTimeUnixNano"])
        assert len(s["traceId"]) == 32 and len(s["spanId"]) == 16
    _, metrics = next(x for x in collector.received if x[0] == "/v1/metrics")
    m = metrics["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
    by_name = {x["name"]: x for x in m}
    assert by_name["engine_rows.w0.input"]["gauge"]["dataPoints"][0][
        "asDouble"
    ] == 5.0


def test_otlp_mark_guard_is_idempotent(collector, monkeypatch):
    monkeypatch.setenv("PATHWAY_TELEMETRY_SERVER", collector.endpoint)
    monkeypatch.delenv("PATHWAY_MONITORING_SERVER", raising=False)
    tracer = _traced_tracer()
    export_from_env(tracer)
    n_first = len(collector.received)
    assert n_first > 0
    # re-export with no new events: the mark guard suppresses the push
    export_from_env(tracer)
    assert len(collector.received) == n_first
    # new events → only the tail is exported
    with tracer.span("graph.build"):
        pass
    export_from_env(tracer)
    assert len(collector.received) > n_first
    _, traces = next(
        x for x in collector.received[n_first:] if x[0] == "/v1/traces"
    )
    tail_names = [
        s["name"]
        for s in traces["resourceSpans"][0]["scopeSpans"][0]["spans"]
    ]
    assert tail_names == ["graph.build"], "tail export must not resend"


def test_refusing_collector_never_raises(monkeypatch):
    refusing = Collector(mode="refuse")
    try:
        monkeypatch.setenv("PATHWAY_TELEMETRY_SERVER", refusing.endpoint)
        monkeypatch.delenv("PATHWAY_MONITORING_SERVER", raising=False)
        tracer = _traced_tracer()
        export_from_env(tracer)  # 503s swallowed
        assert refusing.received, "payload was still attempted"
        # flusher path also swallows refusals
        flusher = PeriodicFlusher(
            interval_s=3600, endpoints=[refusing.endpoint]
        )
        flusher.flush_once()
        assert flusher.flushes == 1
    finally:
        refusing.stop()


def test_unreachable_collector_never_raises(monkeypatch):
    monkeypatch.setenv("PATHWAY_TELEMETRY_SERVER", "http://127.0.0.1:9")
    tracer = _traced_tracer()
    export_from_env(tracer)  # connection refused swallowed
    exp = OtlpExporter("http://127.0.0.1:9")
    assert exp._post("/v1/traces", {"resourceSpans": []}) is False


def test_histogram_payload_shape():
    h = LogHistogram()
    for v in [1_000, 2_000, 1_000_000]:
        h.observe(v)
    exp = OtlpExporter("http://127.0.0.1:1", run_id="r9")
    payload = exp.histograms_payload(
        [("pathway.tick_duration", {"worker": 0}, h.snapshot())],
        1_000_000_000,
    )
    m = payload["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
    assert m[0]["name"] == "pathway.tick_duration"
    hist = m[0]["histogram"]
    assert hist["aggregationTemporality"] == 2
    pt = hist["dataPoints"][0]
    assert pt["count"] == "3"
    assert float(pt["sum"]) == pytest.approx(1_003_000 / 1e9)
    # OTLP invariant: len(bucketCounts) == len(explicitBounds) + 1
    assert len(pt["bucketCounts"]) == len(pt["explicitBounds"]) + 1
    assert sum(int(c) for c in pt["bucketCounts"]) == 3
    assert pt["explicitBounds"] == sorted(pt["explicitBounds"])


def test_periodic_flusher_exports_spans_and_histograms(collector, tmp_path):
    from pathway_tpu.observability.hub import ObservabilityHub
    from pathway_tpu.engine.executor import EngineStats

    tracer = Tracer(str(tmp_path / "t.json"))
    tracing._active = tracer
    tracing._env_checked = True
    tracing._programmatic = True
    try:
        with tracer.span("engine.run"):
            pass
        stats = EngineStats()
        stats.tick_duration.observe(5_000_000)
        hub = ObservabilityHub()
        hub.register_worker(0, stats)
        flusher = PeriodicFlusher(
            interval_s=3600, hub=hub, endpoints=[collector.endpoint]
        )
        flusher.flush_once()
        # crash-durable local trace file written mid-run
        assert (tmp_path / "t.json").exists()
        assert "/v1/traces" in collector.paths()
        hist_posts = [
            body
            for path, body in collector.received
            if path == "/v1/metrics"
            and any(
                "histogram" in m
                for m in body["resourceMetrics"][0]["scopeMetrics"][0][
                    "metrics"
                ]
            )
        ]
        assert hist_posts, "histogram snapshots not exported"
        n = len(collector.received)
        flusher.flush_once()  # no new spans → only histograms re-post
        trace_posts = [p for p, _ in collector.received[n:] if p == "/v1/traces"]
        assert trace_posts == []
    finally:
        tracing.deactivate()


def test_flusher_runs_on_interval(collector):
    flusher = PeriodicFlusher(interval_s=0.05, endpoints=[collector.endpoint])
    flusher.start()
    try:
        deadline = time.monotonic() + 5
        while flusher.flushes < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert flusher.flushes >= 2
    finally:
        flusher.stop()


def test_start_periodic_flusher_env_gating(monkeypatch):
    from pathway_tpu.observability.exporter import start_periodic_flusher

    monkeypatch.delenv("PATHWAY_TELEMETRY_SERVER", raising=False)
    monkeypatch.delenv("PATHWAY_MONITORING_SERVER", raising=False)
    monkeypatch.delenv("PATHWAY_TRACE_FILE", raising=False)
    tracing.deactivate()
    try:
        # nothing to flush → no thread
        assert start_periodic_flusher() is None
        # endpoint set but interval 0 → disabled
        monkeypatch.setenv("PATHWAY_TELEMETRY_SERVER", "http://127.0.0.1:9")
        monkeypatch.setenv("PATHWAY_TELEMETRY_FLUSH_S", "0")
        assert start_periodic_flusher() is None
        # endpoint + positive interval → running flusher
        monkeypatch.setenv("PATHWAY_TELEMETRY_FLUSH_S", "3600")
        flusher = start_periodic_flusher()
        assert flusher is not None
        flusher.stop()
    finally:
        tracing.deactivate()
