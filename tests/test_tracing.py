"""Span tracing (internals/tracing.py) — the no-egress analog of the
reference's OTLP telemetry (src/engine/telemetry.rs:47-156 + the build/run
spans in python/pathway/internals/graph_runner/telemetry.py)."""

import json

import pytest

import pathway_tpu as pw
from pathway_tpu.internals import tracing
from pathway_tpu.internals.parse_graph import G


@pytest.fixture(autouse=True)
def _reset_graph_and_tracer():
    G.clear()
    yield
    G.clear()
    tracing.deactivate()


def _small_pipeline():
    t = pw.debug.table_from_markdown(
        """
        a | b
        1 | x
        2 | x
        3 | y
        """
    )
    return t.groupby(pw.this.b).reduce(pw.this.b, s=pw.reducers.sum(pw.this.a))


def test_trace_file_written(tmp_path, monkeypatch):
    path = tmp_path / "trace.json"
    monkeypatch.setenv("PATHWAY_TRACE_FILE", str(path))
    out = _small_pipeline()
    rows = []
    pw.io.subscribe(out, on_change=lambda **kw: rows.append(kw))
    pw.run()
    assert path.exists()
    doc = json.loads(path.read_text())
    names = [e["name"] for e in doc["traceEvents"]]
    assert "graph.build" in names
    assert "engine.run" in names
    assert "tick" in names
    # per-node duration events carry emitted row counts
    node_events = [
        e
        for e in doc["traceEvents"]
        if "#" in e.get("name", "") and e.get("ph") == "X"
    ]
    assert node_events and all("rows" in e["args"] for e in node_events)
    # spans nest: every tick lies inside engine.run
    run_ev = next(e for e in doc["traceEvents"] if e["name"] == "engine.run")
    for tick in (e for e in doc["traceEvents"] if e["name"] == "tick"):
        assert tick["ts"] >= run_ev["ts"]
        assert tick["ts"] + tick["dur"] <= run_ev["ts"] + run_ev["dur"] + 1e3


def test_no_trace_file_when_disabled(tmp_path, monkeypatch):
    monkeypatch.delenv("PATHWAY_TRACE_FILE", raising=False)
    out = _small_pipeline()
    pw.io.subscribe(out, on_change=lambda **kw: None)
    pw.run()
    assert list(tmp_path.iterdir()) == []
    assert tracing.get_tracer() is None


def test_sharded_run_traces_all_workers(tmp_path, monkeypatch):
    path = tmp_path / "sharded.json"
    monkeypatch.setenv("PATHWAY_TRACE_FILE", str(path))
    monkeypatch.setenv("PATHWAY_THREADS", "3")
    out = _small_pipeline()
    pw.io.subscribe(out, on_change=lambda **kw: None)
    pw.run()
    monkeypatch.delenv("PATHWAY_THREADS")
    doc = json.loads(path.read_text())
    runs = [e for e in doc["traceEvents"] if e["name"] == "engine.run"]
    assert len(runs) == 3
    assert {e["args"]["worker"] for e in runs} == {0, 1, 2}
    # three workers → three distinct threads in the trace
    assert len({e["tid"] for e in runs}) == 3


def test_programmatic_activation_survives_run(tmp_path, monkeypatch):
    monkeypatch.delenv("PATHWAY_TRACE_FILE", raising=False)
    path = tmp_path / "prog_run.json"
    tracing.activate(str(path))
    out = _small_pipeline()
    pw.io.subscribe(out, on_change=lambda **kw: None)
    pw.run()  # init_from_env must not clobber the activated tracer
    assert path.exists()
    names = {e["name"] for e in json.loads(path.read_text())["traceEvents"]}
    assert "engine.run" in names
    # a second run on the same tracer re-flushes with both runs' spans
    G.clear()
    out = _small_pipeline()
    pw.io.subscribe(out, on_change=lambda **kw: None)
    pw.run()
    events = json.loads(path.read_text())["traceEvents"]
    assert sum(1 for e in events if e["name"] == "engine.run") == 2


def test_flush_write_failure_warns_not_raises(tmp_path):
    tracer = tracing.Tracer(str(tmp_path / "no/such/dir/t.json"))
    tracer.instant("x")
    with pytest.warns(RuntimeWarning, match="could not write trace file"):
        assert tracer.flush() is None


def test_trace_flushed_when_run_raises(tmp_path, monkeypatch):
    path = tmp_path / "failing.json"
    monkeypatch.setenv("PATHWAY_TRACE_FILE", str(path))
    t = pw.debug.table_from_markdown(
        """
        a
        1
        """
    )

    def boom(row):
        raise RuntimeError("node failure")

    pw.io.subscribe(t.select(b=pw.apply(boom, pw.this.a)),
                    on_change=lambda **kw: None)
    with pytest.raises(Exception):
        # apply errors become Error rows; force a hard failure via on_change
        out = _small_pipeline()
        pw.io.subscribe(out, on_change=lambda **kw: 1 / 0)
        pw.run()
    assert path.exists()  # flush happens in finally even on failure


def test_event_buffer_is_bounded(tmp_path):
    tracer = tracing.Tracer(str(tmp_path / "cap.json"), max_events=10)
    for i in range(100):
        tracer.instant(f"e{i}")
    assert len(tracer._events) <= 10
    tracer.flush()
    doc = json.loads((tmp_path / "cap.json").read_text())
    dropped = [
        e for e in doc["traceEvents"] if e["name"] == "trace.dropped_events"
    ]
    assert dropped and dropped[0]["args"]["count"] >= 90
    # the surviving window is the most recent one
    assert any(e["name"] == "e99" for e in doc["traceEvents"])


def test_programmatic_activation(tmp_path):
    tracer = tracing.activate(str(tmp_path / "prog.json"))
    # a counter sample rides its span's own append, as the executor's row
    # counters ride the tick's (``complete(..., counter=...)``)
    with tracer.span("outer", k=1) as sp:
        tracer.instant("marker")
        sp.counter = ("c", {"v": 2.0})
    written = tracer.flush()
    assert written == str(tmp_path / "prog.json")
    doc = json.loads((tmp_path / "prog.json").read_text())
    phases = {e["name"]: e["ph"] for e in doc["traceEvents"]}
    assert phases["outer"] == "X"
    assert phases["marker"] == "i"
    assert phases["c"] == "C"
    # flush is idempotent
    assert tracer.flush() is None


def test_overflow_drop_never_orphans_counter(tmp_path):
    # spans and their counter samples are appended as one atomic pair; the
    # overflow drop must never keep a counter whose tick span was dropped
    tracer = tracing.Tracer(str(tmp_path / "t.json"), max_events=8)
    import time as _time

    for i in range(50):
        tracer.complete(
            "tick", _time.perf_counter_ns(), {"time": i},
            counter=("rows", {"n": float(i)}),
        )
    # the first surviving event is never an orphaned counter sample
    assert tracer._events[0]["ph"] != "C"
    # and every surviving counter is directly preceded by its span
    for j, ev in enumerate(tracer._events):
        if ev["ph"] == "C":
            assert tracer._events[j - 1]["ph"] == "X"
    assert tracer._dropped > 0


def test_events_since_cursor_correct_across_drop(tmp_path):
    tracer = tracing.Tracer(str(tmp_path / "t.json"), max_events=10)
    for i in range(5):
        tracer.instant(f"a{i}")
    events, mark = tracer.events_since(0)
    assert [e["name"] for e in events] == [f"a{i}" for i in range(5)]
    # overflow between exports: more events appended than the buffer holds
    for i in range(40):
        tracer.instant(f"b{i}")
    events, mark2 = tracer.events_since(mark)
    names = [e["name"] for e in events]
    # nothing before the cursor is re-exported (no double export) ...
    assert not any(n.startswith("a") for n in names)
    # ... the tail is contiguous and ends at the newest event (no skips
    # within the surviving window) ...
    tail = [f"b{i}" for i in range(40)][-len(names):]
    assert names == tail
    # ... and a drained cursor exports nothing
    assert tracer.events_since(mark2) == ([], mark2)


def test_local_comm_flow_events_link_workers(tmp_path, monkeypatch):
    # threads in one process: exchange flows must cross-link sender and
    # receiver tick spans via deterministic ids (s on one tid, f on others)
    path = tmp_path / "flows.json"
    monkeypatch.setenv("PATHWAY_TRACE_FILE", str(path))
    monkeypatch.setenv("PATHWAY_THREADS", "2")
    out = _small_pipeline()
    pw.io.subscribe(out, on_change=lambda **kw: None)
    pw.run()
    monkeypatch.delenv("PATHWAY_THREADS")
    doc = json.loads(path.read_text())
    starts = {e["id"]: e for e in doc["traceEvents"] if e.get("ph") == "s"}
    ends = {e["id"]: e for e in doc["traceEvents"] if e.get("ph") == "f"}
    linked = [i for i in starts if i in ends]
    assert linked, (len(starts), len(ends))
    # the two halves of at least one flow live on different worker threads
    assert any(starts[i]["tid"] != ends[i]["tid"] for i in linked)
    # clock-sync metadata always present (merge anchor, even single-process)
    sync = [
        e for e in doc["traceEvents"] if e["name"] == "trace.clock_sync"
    ]
    assert sync and "origin_unix_ns" in sync[0]["args"]
    assert sync[0]["args"]["run_id"]


def test_multiprocess_trace_files_cross_link(tmp_path):
    # satellite: spawn 2 real processes with PATHWAY_TRACE_FILE; both .p<N>
    # parts must be valid Chrome Trace JSON with engine.run/tick spans and
    # flow-event ids that cross-link the files
    import os
    import socket
    import subprocess
    import sys
    import textwrap

    prog = tmp_path / "prog.py"
    prog.write_text(textwrap.dedent(
        """
        import pathway_tpu as pw

        t = pw.debug.table_from_markdown(
            \"\"\"
            a | b
            1 | x
            2 | x
            3 | y
            4 | y
            \"\"\"
        )
        out = t.groupby(pw.this.b).reduce(
            pw.this.b, s=pw.reducers.sum(pw.this.a)
        )
        pw.io.subscribe(out, on_change=lambda **kw: None)
        pw.run()
        """
    ))
    base = tmp_path / "trace.json"
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PATHWAY_TRACE_FILE": str(base),
    }
    env.pop("PATHWAY_THREADS", None)
    env.pop("PATHWAY_PROCESSES", None)
    proc = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "-t", "1", "--first-port", str(port),
            sys.executable, str(prog),
        ],
        env=env, timeout=180, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    docs = {}
    for p in (0, 1):
        part = tmp_path / f"trace.json.p{p}"
        assert part.exists()
        docs[p] = json.loads(part.read_text())  # valid Chrome Trace JSON
        names = {e["name"] for e in docs[p]["traceEvents"]}
        assert "engine.run" in names and "tick" in names, sorted(names)
    # cross-link: a flow id started in one process finishes in the other
    starts = {
        (e["id"], p)
        for p in docs
        for e in docs[p]["traceEvents"]
        if e.get("ph") == "s"
    }
    ends = {
        (e["id"], p)
        for p in docs
        for e in docs[p]["traceEvents"]
        if e.get("ph") == "f"
    }
    cross = {
        i for (i, p) in starts for (j, q) in ends if i == j and p != q
    }
    assert cross, (len(starts), len(ends))
    # both parts agree on the spawn-stamped run id
    run_ids = {
        e["args"]["run_id"]
        for p in docs
        for e in docs[p]["traceEvents"]
        if e["name"] == "trace.clock_sync"
    }
    assert len(run_ids) == 1


def test_metrics_expose_trace_drops(tmp_path):
    # a truncated trace window must be visible on /metrics — 0 when the
    # tracer is healthy, the drop count after overflow, absent when off
    from pathway_tpu.observability import ObservabilityHub
    from pathway_tpu.observability.prometheus import parse_exposition

    hub = ObservabilityHub()
    tracer = tracing.activate(str(tmp_path / "d.json"))
    try:
        key = ("pathway_trace_dropped_events_total", ())
        assert parse_exposition(hub.render_metrics())[key] == 0
        tracer._max_events = 4
        for i in range(20):
            tracer.instant(f"e{i}")
        assert parse_exposition(hub.render_metrics())[key] > 0
    finally:
        tracing.deactivate()
    assert key not in parse_exposition(hub.render_metrics())


def test_cluster_rollup_reports_peer_trace_drops(monkeypatch, tmp_path):
    # a PEER's truncated timeline must surface on the merged /metrics as a
    # per-process-labeled series (a transiently unreachable peer then
    # drops its series instead of decreasing a summed counter, which
    # Prometheus would misread as a reset)
    from pathway_tpu.observability import ObservabilityHub
    from pathway_tpu.observability.prometheus import parse_exposition

    hub = ObservabilityHub(
        process_id=0, n_processes=2, peer_http=[("127.0.0.1", 1)]
    )
    peer_doc: dict = {
        "process_id": 1,
        "workers": [],
        "comm": {},
        "trace_dropped": 11,
    }
    monkeypatch.setattr(
        ObservabilityHub, "_scrape_peer",
        staticmethod(lambda host, port: peer_doc),
    )
    tracer = tracing.activate(str(tmp_path / "r.json"))
    tracer._dropped = 3
    try:
        values = parse_exposition(hub.render_metrics())
        key = "pathway_trace_dropped_events_total"
        assert values[(key, (("process", "1"),))] == 11
        assert values[(key, (("process", "0"),))] == 3
        # peer outage: its series disappears, process 0's is unchanged
        monkeypatch.setattr(
            ObservabilityHub, "_scrape_peer",
            staticmethod(lambda host, port: None),
        )
        values = parse_exposition(hub.render_metrics())
        assert (key, (("process", "1"),)) not in values
        assert values[(key, (("process", "0"),))] == 3
    finally:
        tracing.deactivate()


# -- OTLP push (reference telemetry.rs:63-156) -------------------------------


class _Collector:
    """Loopback OTLP/HTTP collector capturing POSTed payloads."""

    def __init__(self):
        import http.server
        import json as _json
        import threading as _threading

        collector = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = _json.loads(self.rfile.read(n))
                collector.received.append((self.path, body))
                self.send_response(200)
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        self.received = []
        self.server = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        self.thread = _threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self.thread.start()

    def stop(self):
        self.server.shutdown()
        self.server.server_close()


def test_otlp_exporter_payload_shapes():
    from pathway_tpu.internals.telemetry import OtlpExporter
    from pathway_tpu.internals.tracing import Tracer

    tracer = Tracer(None)
    import time as _time

    tracer.complete(
        "graph.build", _time.perf_counter_ns(), {"tables": 2},
        counter=("engine.rows", {"ingested": 42.0}),
    )
    exp = OtlpExporter("http://127.0.0.1:1", run_id="r1")
    spans = exp.spans_payload(tracer._events, 1_000_000_000)
    span_list = spans["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert span_list[0]["name"] == "graph.build"
    assert span_list[0]["traceId"] == exp.trace_id
    assert int(span_list[0]["endTimeUnixNano"]) >= int(
        span_list[0]["startTimeUnixNano"]
    )
    assert {"key": "tables", "value": {"intValue": "2"}} in span_list[0][
        "attributes"
    ]
    res_attrs = {
        a["key"]: a["value"]["stringValue"]
        for a in spans["resourceSpans"][0]["resource"]["attributes"]
    }
    assert res_attrs["service.name"] == "pathway_tpu"
    assert res_attrs["run.id"] == "r1"
    metrics = exp.metrics_payload(tracer._events, 1_000_000_000)
    m = metrics["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
    assert m[0]["name"] == "engine.rows.ingested"
    assert m[0]["gauge"]["dataPoints"][0]["asDouble"] == 42.0


def test_otlp_export_posts_to_collector(monkeypatch):
    collector = _Collector()
    try:
        monkeypatch.setenv(
            "PATHWAY_TELEMETRY_SERVER", f"http://127.0.0.1:{collector.port}"
        )
        monkeypatch.delenv("PATHWAY_TRACE_FILE", raising=False)
        import pathway_tpu as pw
        from pathway_tpu.internals import tracing
        from pathway_tpu.internals.parse_graph import G

        tracing._env_checked = False  # re-read env
        G.clear()
        t = pw.debug.table_from_markdown("a\n1\n2")
        out = t.select(b=pw.this.a + 1)
        pw.debug.compute_and_print(out)
        G.clear()
        paths = [p for p, _ in collector.received]
        assert "/v1/traces" in paths, paths
        _, traces = next(x for x in collector.received if x[0] == "/v1/traces")
        names = [
            s["name"]
            for s in traces["resourceSpans"][0]["scopeSpans"][0]["spans"]
        ]
        assert "engine.run" in names  # run_tables path: executor spans
    finally:
        collector.stop()
        tracing._env_checked = False
