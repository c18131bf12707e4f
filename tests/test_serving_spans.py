"""Spans and counters inside the serving path (internals/tracing.py's
recorder at the layer boundaries of a retrieve and a write): a small
``DocumentStoreServer`` on the CPU answers a few requests, and every span of
the table in docs/observability.md is there with a shared ``req`` / ``tick``,
each child inside its parent; nothing is recorded when nobody asked; a live
``jax.profiler`` session alone gets the spans, as a file and in the
profiler's host plane; the counters count the calls made."""

import contextlib
import glob
import http.client
import json
import os
import socket
import tempfile
import threading
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals import tracing
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.serve.stats import SERVE_STATS

ROWS, DIM, BLOCK = 96, 16, 32
REQUEST_SPANS = ("rest.request", "rest.admit", "rest.in_engine", "rest.reply")
SEARCH_SPANS = ("index.embed", "index.score", "index.fetch", "index.pack")
EMBED_SPANS = ("embed.tokenize", "embed.dispatch")


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    # the span directory of profiler sessions goes under the test's own tmp
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path / "tmp"))
    os.makedirs(tmp_path / "tmp")
    monkeypatch.delenv("PATHWAY_TRACE_FILE", raising=False)
    monkeypatch.setattr(tracing, "_session", None)
    tracing.deactivate()
    G.clear()
    yield
    from pathway_tpu.io.http._server import terminate_all

    terminate_all()
    tracing.deactivate()
    G.clear()


@contextlib.contextmanager
def _serving():
    """A document store of ROWS pre-embedded rows behind a REST server; yields
    ``post(payload, route) -> (status, body)``. Leaving stops the run (and
    with it flushes whatever was recorded)."""
    from pathway_tpu.internals.run import request_stop
    from pathway_tpu.io.http._server import terminate_all
    from pathway_tpu.models.embedder import Embedder, EmbedderConfig
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    rows = np.random.default_rng(7).standard_normal((ROWS, DIM)).astype(np.float32)
    fed, stop = threading.Event(), threading.Event()

    class Feed(pw.io.python.ConnectorSubject):
        def run(self):
            for s in range(0, ROWS, BLOCK):
                ids = np.arange(s, s + BLOCK)
                self.next_batch({
                    "id": ids, "data": [f"row {i}" for i in ids],
                    "_metadata": [{"path": f"d{i}"} for i in ids],
                    "vec": list(rows[s:s + BLOCK]),
                })
                self.commit()
            fed.set()
            stop.wait()

    schema = pw.schema_builder({
        "id": pw.column_definition(dtype=int, primary_key=True),
        "data": str, "_metadata": dict, "vec": np.ndarray,
    })
    docs = pw.io.python.read(Feed(), schema=schema, autocommit_duration_ms=None)
    embedder = Embedder(EmbedderConfig(
        vocab_size=128, dim=DIM, n_layers=1, n_heads=2, max_len=32))
    store = DocumentStore(
        docs, BruteForceKnnFactory(dimensions=DIM, reserved_space=ROWS,
                                   metric="cos", embedder=embedder),
        vector_column="vec")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = DocumentStoreServer("127.0.0.1", port, store)
    thread = server.run(threaded=True)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)

    def post(payload, route="/v1/retrieve"):
        conn.request("POST", route, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    try:
        assert fed.wait(60) and server.webserver._started.wait(60)
        # the feed's last block may tick after the first request: ask (a
        # tick a time, no clock) until the store holds every row
        for _ in range(100):
            if post({}, "/v1/statistics")[1].get("file_count") == ROWS:
                break
        else:
            raise AssertionError("the index was not built")
        yield post
    finally:
        conn.close()
        stop.set()
        request_stop()
        terminate_all()
        thread.join(60)
        assert not thread.is_alive()


def _retrieve(post, n):
    for i in range(n):
        status, body = post({"query": f"what is row {i}", "k": 3})
        assert status == 200 and len(body) == 3, (status, body)


def _complete(path):
    doc = json.loads(open(path).read())
    return doc, [e for e in doc["traceEvents"] if e.get("ph") == "X"]


def _inside(child, parent, slack_us=1.0):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + slack_us)


def test_every_span_of_the_serving_path_with_shared_ids(tmp_path):
    path = tmp_path / "run.json"
    tracing.activate(str(path))
    with _serving() as post:
        _retrieve(post, 4)
    doc, events = _complete(path)
    by_name: dict = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    for name in (*REQUEST_SPANS, "connector.window", "engine.park", "tick",
                 "index.apply", "index.search", "index.upload", *SEARCH_SPANS,
                 *EMBED_SPANS):
        assert by_name.get(name), f"no {name} span"

    ticks = {e["args"]["tick"]: e for e in by_name["tick"]}
    assert all(e["args"]["time"] == t and "rows_in" in e["args"] for t, e in ticks.items())
    # a request: four spans under one req, children inside rest.request,
    # rest.in_engine ending inside the tick that answered it
    requests = {e["args"]["req"]: e for e in by_name["rest.request"]}
    retrieves = [r for r in requests.values() if r["args"]["route"] == "/v1/retrieve"]
    assert len(retrieves) == 4 and all(r["args"]["status"] == 200 for r in retrieves)
    for name in REQUEST_SPANS[1:]:
        mine = {e["args"]["req"]: e for e in by_name[name]}
        assert set(mine) == set(requests)
        for req, e in mine.items():
            assert e["args"]["parent"] == "rest.request"
            assert _inside(e, requests[req]), (name, e, requests[req])
    for e in by_name["rest.in_engine"]:
        tick = ticks[e["args"]["tick"]]
        assert tick["ts"] <= e["ts"] + e["dur"] <= tick["ts"] + tick["dur"] + 1.0
    # a search: under its tick, its stages inside it, the embedder's inside theirs
    searches = {e["args"]["tick"]: e for e in by_name["index.search"]}
    assert len(searches) == 4
    for tick_id, s in searches.items():
        # under the node that searched, which is an event of that tick
        assert s["args"]["parent"].startswith("ExternalIndexNode#")
        assert s["args"]["q"] == 1 and s["args"]["k"] == 3
        assert _inside(s, ticks[tick_id])
    assert [s["args"]["dirty"] for s in by_name["index.search"]] == [True, False, False, False]
    for name in (*SEARCH_SPANS, "index.upload"):
        for e in by_name[name]:
            assert e["args"]["parent"] == "index.search"
            assert _inside(e, searches[e["args"]["tick"]]), name
    embeds = {e["args"]["tick"]: e for e in by_name["index.embed"]}
    for name in EMBED_SPANS:
        for e in by_name[name]:
            assert e["args"]["parent"] == "index.embed"
            assert _inside(e, embeds[e["args"]["tick"]]), name
    # one four-word question: one text of four real tokens in a program of
    # one row of sixteen, and the tokenizer says how many tokens it made
    assert by_name["embed.dispatch"][0]["args"] == {
        "bucket": 16, "rows": 1, "texts": 1, "tokens": 4, "computed": 16,
        "parent": "index.embed",
        "tick": by_name["embed.dispatch"][0]["args"]["tick"]}
    assert by_name["embed.tokenize"][0]["args"]["tokens"] == 4
    assert by_name["index.upload"][0]["args"]["bytes"] == ROWS * DIM * 4
    # the write path: one index.apply per fed block, inside its tick
    applies = by_name["index.apply"]
    assert [a["args"]["added"] for a in applies] == [BLOCK] * (ROWS // BLOCK)
    assert all(a["args"]["removed"] == 0 and _inside(a, ticks[a["args"]["tick"]])
               for a in applies)
    windows = by_name["connector.window"]
    # every window closes on its commit marker (the last retraction's may
    # be overtaken by the subject's end when the run stops)
    assert {"commit"} <= {w["args"]["reason"] for w in windows} <= {"commit", "done"}
    assert sorted(w["args"]["rows"] for w in windows)[-3:] == [BLOCK] * 3
    # parked and ticking never overlap on the engine thread
    for p in by_name["engine.park"]:
        assert not any(t["ts"] < p["ts"] + p["dur"] - 1.0 and p["ts"] < t["ts"] + t["dur"] - 1.0
                       for t in ticks.values())
    # the file's clock: origin + ts is time.monotonic_ns()
    sync = next(e for e in doc["traceEvents"] if e["name"] == "trace.clock_sync")
    last = max(e["ts"] + e["dur"] for e in events)
    assert 0 < time.monotonic_ns() - (sync["args"]["origin_monotonic_ns"] + last * 1e3) < 60e9
    # the counters, sampled into the file at the flush
    sample = [e for e in doc["traceEvents"] if e["name"] == "serve_stats"][-1]
    assert sample["ph"] == "C" and sample["args"]["index_searches_total"] >= 4


def test_nothing_is_recorded_and_no_file_appears_when_nobody_asked():
    with _serving() as post:
        _retrieve(post, 2)
        assert tracing.get_tracer() is None
    assert tracing.run_tracer() is None
    assert not os.path.exists(tracing.spans_dir())


def test_a_profiler_session_alone_gets_the_spans_on_both_clocks(tmp_path):
    import jax
    from jax.profiler import ProfileData

    assert jax.profiler.TraceAnnotation.is_enabled() is False
    with _serving() as post:
        _retrieve(post, 2)  # before the session: not recorded
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "profile"), profiler_options=opts)
        try:
            assert tracing.get_tracer() is not None  # seen in the middle of a run
            _retrieve(post, 3)
        finally:
            jax.profiler.stop_trace()
        assert tracing.get_tracer() is None
        _retrieve(post, 2)  # after it: not recorded
        assert not os.path.exists(tracing.spans_dir())  # written when the run ends
    files = os.listdir(tracing.spans_dir())
    assert files == [f"{os.getpid()}.json"]
    doc, events = _complete(os.path.join(tracing.spans_dir(), files[0]))
    sync = next(e for e in doc["traceEvents"] if e["name"] == "trace.clock_sync")
    assert sync["args"]["origin_monotonic_ns"] > 0
    names = {e["name"] for e in events}
    assert {*REQUEST_SPANS, "tick", "index.search", *SEARCH_SPANS, *EMBED_SPANS} <= names
    assert sum(e["name"] == "index.search" for e in events) == 3
    # the engine's consolidation counters at the session's first span and at
    # the flush: what lies between them was consolidated from the session on
    first, *_, last = [e["args"] for e in doc["traceEvents"] if e["name"] == "fusion_stats"]
    assert last["consolidation_rows_total"] > first["consolidation_rows_total"]
    assert (last["consolidation_rows_hashed_total"] - first["consolidation_rows_hashed_total"]
            < last["consolidation_rows_total"] - first["consolidation_rows_total"])
    # the same spans lie in the profiler's host plane, on the trace's clock
    # (rest.in_engine ends on another thread than it began: file only)
    xplane = glob.glob(str(tmp_path / "profile" / "**" / "*.xplane.pb"), recursive=True)[0]
    host = {ev.name for plane in ProfileData.from_file(xplane).planes
            if plane.name.startswith("/host:") for line in plane.lines for ev in line.events}
    assert {"rest.request", "rest.admit", "rest.reply", "tick", "index.search",
            *SEARCH_SPANS, *EMBED_SPANS} <= host


def test_each_counter_equals_the_calls_made():
    at_start = dict(SERVE_STATS)
    with _serving() as post:
        before = dict(SERVE_STATS)  # the index is built, nothing searched yet
        _retrieve(post, 3)
        assert post({}, "/v1/statistics")[0] == 200
    got = {k: SERVE_STATS[k] - before[k] for k in SERVE_STATS}
    assert got["index_searches_total"] == 3 and got["index_search_queries_total"] == 3
    assert got["index_uploads_total"] == 1
    assert got["index_upload_bytes_total"] == ROWS * DIM * 4
    assert before["index_rows_added_total"] - at_start["index_rows_added_total"] == ROWS
    assert got["index_rows_added_total"] == 0 and got["index_rows_removed_total"] == 0
    # per request a window for its row and one for its retraction (that of
    # the last poll before may close after `before`, that of the last
    # request may still be queued when the run stops)
    assert 2 * 4 - 1 <= got["connector_windows_total"] <= 2 * 4 + 1
    assert SERVE_STATS["connector_windows_total"] - at_start["connector_windows_total"] >= (
        ROWS // BLOCK + 2 * 5 - 1)
    # "what is row <i>": four tokens in a 16-token bucket, three times
    assert got["embed_real_tokens_total"] == 12 and got["embed_padded_tokens_total"] == 48
    # a dispatch a search, nothing cut; the whole declared set of programs
    # (a model of 32 positions: five of sixteen tokens a row, four of 32) was
    # compiled by the first
    assert got["embed_dispatches_total"] == 3 and got["embed_truncated_texts_total"] == 0
    assert got["embed_shapes_compiled_total"] == 9
    assert got["queries_total"] == 4


def test_the_span_clock_and_the_monotonic_clock_agree():
    # Linux: both read CLOCK_MONOTONIC, so origin_monotonic_ns + ts is
    # time.monotonic_ns() for any span of the file
    a = time.perf_counter_ns()
    m = time.monotonic_ns()
    b = time.perf_counter_ns()
    assert a <= m <= b
    tracer = tracing.Tracer(None)
    assert abs((tracer.origin_monotonic_ns - tracer._origin)) < 1_000_000


def test_session_files_are_pruned_to_the_newest_eight():
    os.makedirs(tracing.spans_dir())
    for i in range(11):
        path = os.path.join(tracing.spans_dir(), f"{i}.json")
        open(path, "w").write("{}")
        os.utime(path, (i, i))
    tracer = tracing.Tracer(os.path.join(tracing.spans_dir(), "mine.json"))
    tracer.session = True
    tracer.instant("x")
    assert tracer.flush() is not None
    assert sorted(os.listdir(tracing.spans_dir())) == sorted(
        ["mine.json"] + [f"{i}.json" for i in range(4, 11)])
