"""The engine thread's own time, by name (internals/tracing.py's recorder in
the streaming loops, in ``_sweep`` and in three operator classes): a traced
``DocumentStoreServer`` on the CPU whose engine thread is covered from its
first tick to its last by ``tick``, ``engine.poll`` and ``engine.park``;
every node event belongs to its tick; the phases of ``GroupByReduce``, ``Join``
and ``Subscribe`` lie inside their node and name it; ``rest.wake`` lies
between the future's resolution and the handler's reply; the sharded loops
record the same ``engine.poll``; with nothing recording, nothing is
appended."""

import os
import sys
import tempfile
import threading

import pytest

import pathway_tpu as pw
from pathway_tpu.internals import tracing
from pathway_tpu.internals.parse_graph import G
from test_serving_spans import _complete, _inside, _retrieve, _serving

PHASES = {
    "GroupByReduce": ("groupby.update", "groupby.emit"),
    "Join": ("join.consolidate", "join.probe"),
    "Subscribe": ("subscribe.deliver",),
}


def _fresh():
    tracing.deactivate()
    tracing._session = None
    G.clear()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(document, complete events) of one traced run that served 6 requests."""
    from pathway_tpu.io.http._server import terminate_all

    tmp = tmp_path_factory.mktemp("engine_spans")
    path = tmp / "run.json"
    _fresh()
    tracing.activate(str(path))
    try:
        with _serving() as post:
            _retrieve(post, 6)
    finally:
        terminate_all()
        _fresh()
    return _complete(path)


def _union_us(intervals, lo, hi):
    covered, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            covered += b - a
            end = b
    return covered


def _span(e):
    return e["ts"], e["ts"] + e["dur"]


def test_the_engine_thread_is_covered_from_its_first_tick_to_its_last(traced):
    _, events = traced
    ticks = [e for e in events if e["name"] == "tick"]
    engine = {e["tid"] for e in ticks}
    assert len(engine) == 1
    mine = [e for e in events if e["tid"] in engine
            and e["name"] in ("tick", "engine.poll", "engine.park")]
    assert {e["name"] for e in mine} == {"tick", "engine.poll", "engine.park"}
    lo, hi = min(e["ts"] for e in ticks), max(e["ts"] + e["dur"] for e in ticks)
    covered = _union_us([_span(e) for e in mine], lo, hi)
    assert covered >= 0.95 * (hi - lo), (covered, hi - lo)
    # the three never overlap: they partition the thread's time
    assert sum(min(b, hi) - max(a, lo) for a, b in map(_span, mine)
               if b > lo and a < hi) <= covered + 1.0 * len(mine)


def test_a_poll_says_what_it_found_and_ends_where_a_tick_or_a_park_begins(traced):
    _, events = traced
    polls = [e for e in events if e["name"] == "engine.poll"]
    starts = sorted(e["ts"] for e in events if e["name"] in ("tick", "engine.park"))
    assert polls and all(set(e["args"]) == {"sources", "rounds", "rows"} for e in polls)
    assert all(e["args"]["sources"] >= 2 for e in polls)  # the feed and the REST routes
    for e in polls:
        end = e["ts"] + e["dur"]
        following = next((s for s in starts if s >= end - 1.0), None)
        if e["args"]["rounds"]:
            assert e["args"]["rows"] >= 1
        if following is not None:  # the run's last poll may end with the run
            assert following - end < 50_000, (e, following)
    assert any(e["args"]["rounds"] for e in polls) and any(
        not e["args"]["rounds"] for e in polls)


def test_every_node_event_belongs_to_its_tick(traced):
    _, events = traced
    ticks = {e["args"]["tick"]: e for e in events if e["name"] == "tick"}
    nodes = [e for e in events if "#" in e["name"]]
    assert len(nodes) > len(ticks)
    for e in nodes:
        assert e["args"]["parent"] == "tick" and "rows" in e["args"], e
        assert _inside(e, ticks[e["args"]["tick"]]), e


@pytest.mark.parametrize("family", sorted(PHASES))
def test_phases_name_their_node_lie_inside_it_and_sum_to_no_more(traced, family):
    _, events = traced
    nodes = {(e["name"], e["args"]["tick"]): e for e in events
             if e["name"].startswith(family + "#")}
    phases = [e for e in events if e["name"] in PHASES[family]]
    assert {e["name"] for e in phases} == set(PHASES[family])
    inside: dict = {}
    for e in phases:
        key = (e["args"]["parent"], e["args"]["tick"])
        assert key in nodes, e
        assert _inside(e, nodes[key]), (e, nodes[key])
        inside[key] = inside.get(key, 0.0) + e["dur"]
    for key, total in inside.items():
        assert total <= nodes[key]["dur"] + 1.0, (key, total, nodes[key])
    # what each phase says of itself
    for e in phases:
        a = e["args"]
        if e["name"] == "groupby.update":
            assert a["path"] in ("dense", "general") and a["reducers"] >= 1
            assert 1 <= a["groups"] <= a["rows"]
        elif e["name"] == "join.consolidate":
            assert a["side"] in ("left", "right") and 0 <= a["hashed"]
        elif e["name"] == "join.probe":
            assert a["rows"] >= 1 and a["matches"] >= 0
        else:
            assert a["rows"] >= 0


def test_a_span_inside_a_node_takes_the_node_as_parent_and_the_ticks_id(traced):
    _, events = traced
    searches = [e for e in events if e["name"] == "index.search"]
    nodes = {(e["name"], e["args"]["tick"]) for e in events if "#" in e["name"]}
    assert len(searches) == 6
    for s in searches:
        assert (s["args"]["parent"], s["args"]["tick"]) in nodes
    # the request's span that ends on the engine thread still finds its tick,
    # now through the node and the phase it ends under
    ticks = {e["args"]["tick"] for e in events if e["name"] == "tick"}
    inside = [e for e in events if e["name"] == "rest.in_engine"]
    assert inside and all(e["args"]["tick"] in ticks for e in inside)
    delivers = [_span(e) for e in events if e["name"] == "subscribe.deliver"]
    for e in inside:  # the response writer resolves it in the node's on_time_end
        end = e["ts"] + e["dur"]
        assert any(a - 1.0 <= end <= b + 1.0 for a, b in delivers), e


def test_the_wake_up_lies_between_the_futures_resolution_and_the_reply(traced):
    _, events = traced
    by_req: dict = {}
    for e in events:
        if e["name"].startswith("rest."):
            by_req.setdefault(e["args"]["req"], {})[e["name"]] = e
    wakes = [r for r in by_req.values() if "rest.wake" in r]
    assert len(wakes) == len(by_req) >= 6  # the retrieves and the statistics calls
    for r in wakes:
        wake, inside, reply = r["rest.wake"], r["rest.in_engine"], r["rest.reply"]
        assert wake["args"]["parent"] == "rest.request"
        assert _inside(wake, r["rest.request"])
        assert inside["ts"] + inside["dur"] - 1.0 <= wake["ts"]
        assert wake["ts"] + wake["dur"] <= reply["ts"] + 1.0
        # nothing else lies between them: the wake-up is the whole stretch
        # (to a thread switch between two statements)
        assert wake["ts"] - (inside["ts"] + inside["dur"]) < 50_000
        assert reply["ts"] - (wake["ts"] + wake["dur"]) < 50_000


def test_the_clock_record_says_under_which_switch_interval_it_was_taken(traced):
    doc, _ = traced
    sync = next(e for e in doc["traceEvents"] if e["name"] == "trace.clock_sync")
    assert sync["args"]["switch_interval_s"] == sys.getswitchinterval()


def test_with_nothing_recording_a_request_and_a_tick_append_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.delenv("PATHWAY_TRACE_FILE", raising=False)
    appended = []
    monkeypatch.setattr(tracing.Tracer, "_append",
                        lambda self, *evs: appended.extend(evs))
    made = []
    real_init = tracing.Tracer.__init__
    monkeypatch.setattr(tracing.Tracer, "__init__",
                        lambda self, *a, **k: (made.append(self), real_init(self, *a, **k))[1])
    _fresh()
    try:
        with _serving() as post:
            _retrieve(post, 2)
            assert tracing.get_tracer() is None
    finally:
        from pathway_tpu.io.http._server import terminate_all

        terminate_all()
        _fresh()
    assert not made and not appended
    assert tracing.run_tracer() is None
    assert not os.path.exists(tracing.spans_dir())
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("threads, async_exec", [(1, "0"), (2, "0"), (2, "1")],
                         ids=["one-worker", "sharded-lock-step", "sharded-async"])
def test_the_three_streaming_loops_record_the_same_poll(tmp_path, monkeypatch,
                                                         threads, async_exec):
    """A streaming wordcount under each loop: every worker thread that ticks
    also polls, and on each of them tick, poll and park do not overlap."""
    n, batch = 2_000, 250
    words = [f"w{i % 53}" for i in range(n)]

    class Feed(pw.io.python.ConnectorSubject):
        def run(self):
            for s in range(0, n, batch):
                self.next_batch({"word": words[s:s + batch]})
                self.commit()

    _fresh()
    path = tmp_path / "run.json"
    tracing.activate(str(path))
    counts: dict = {}
    lock = threading.Lock()

    def on_change(key, row, time, is_addition):
        with lock:
            counts[row["word"]] = row["c"] if is_addition else counts.get(row["word"])

    t = pw.io.python.read(Feed(), schema=pw.schema_from_types(word=str),
                          autocommit_duration_ms=None)
    pw.io.subscribe(t.groupby(pw.this.word).reduce(pw.this.word, c=pw.reducers.count()),
                    on_change=on_change)
    monkeypatch.setenv("PATHWAY_THREADS", str(threads))
    monkeypatch.setenv("PATHWAY_ASYNC_EXEC", async_exec)
    try:
        pw.run()
    finally:
        monkeypatch.setenv("PATHWAY_THREADS", "1")
        monkeypatch.delenv("PATHWAY_ASYNC_EXEC", raising=False)
        _fresh()
    assert sum(counts.values()) == n
    _, events = _complete(path)
    by_tid: dict = {}
    for e in events:
        if e["name"] in ("tick", "engine.poll", "engine.park"):
            by_tid.setdefault(e["tid"], []).append(e)
    ticking = [evs for evs in by_tid.values() if any(e["name"] == "tick" for e in evs)]
    assert len(ticking) == threads
    for evs in ticking:
        polls = [e for e in evs if e["name"] == "engine.poll"]
        assert polls and all(set(e["args"]) == {"sources", "rounds", "rows"} for e in polls)
        evs.sort(key=lambda e: e["ts"])
        for a, b in zip(evs, evs[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a, b)
    # a poll counts the rows it found (a round found inside an open park is
    # the park's, so not every row is under a poll)
    found = [e["args"] for evs in ticking for e in evs if e["name"] == "engine.poll"]
    assert all((a["rows"] > 0) == (a["rounds"] > 0) for a in found)
    assert sum(a["rows"] for a in found) <= n
    # a grouping node's phases are there under the sharded loops too
    assert {"groupby.update", "groupby.emit"} <= {e["name"] for e in events}
