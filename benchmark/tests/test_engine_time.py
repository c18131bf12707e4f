"""The traced window split by what the engine thread was in
(``lib/engine_time.py``) and the nine per-layer readers built on it: the
partition of hand-made spans gives the shares worked out by hand and sums to
the window; each reader reads those spans and gives ``None`` on a span file of
a program that does not cover its engine thread; a traced CPU rehearsal
declared in a manifest of the test's own prints all nine, and the five idle
shares meet the device's idle time."""

import json
import os
import shutil
import tempfile

import harness
import pytest
import run as bench_run
from lib import engine_time
from test_program_spans import _span, _write

READ = "tiny-bert.read-c4"
NINE = {  # name: (unit, layer, moves)
    "idle_tick_head_pct": ("%", "device", "retrieve_qps"),
    "idle_tick_tail_pct": ("%", "device", "retrieve_qps"),
    "idle_between_ticks_pct": ("%", "device", "retrieve_qps"),
    "idle_in_search_pct": ("%", "device", "retrieve_qps"),
    "tick_groupby_ms": ("ms", "dataflow tick and batch formation", "retrieve_p50_ms"),
    "tick_join_ms": ("ms", "dataflow tick and batch formation", "retrieve_p50_ms"),
    "tick_subscribe_ms": ("ms", "dataflow tick and batch formation", "retrieve_p50_ms"),
    "resume_wait_ms": ("ms", "REST edge, admission", "retrieve_p50_ms"),
    "search_q_spread": ("queries", "dataflow tick and batch formation", "retrieve_qps"),
}
ORIGIN = 5000.0


def _node(name, t0, t1, tick, rows=1):
    return _span(name, t0, t1, rows=rows, tick=tick, parent="tick")


def _tick_a():
    """Tick 7, 1.000-1.100: head 30 ms, envelope 40 (embed.dispatch start to
    fetch end), tail 30; a search of 6; nodes and a wake-up in the tail."""
    kid = {"tick": 7, "parent": "index.search"}
    return [
        _span("tick", 1.000, 1.100, tick=7, time=7, rows_in=6),
        _node("ExternalIndexNode#7", 1.015, 1.081, 7),
        _span("index.search", 1.020, 1.080, tick=7, q=6, dirty=False, k=10,
              parent="ExternalIndexNode#7"),
        _span("index.embed", 1.021, 1.036, q=6, **kid),
        _span("embed.tokenize", 1.022, 1.029, tick=7, parent="index.embed"),
        _span("embed.dispatch", 1.030, 1.035, tick=7, parent="index.embed"),
        _span("index.fetch", 1.050, 1.070, **kid),
        _node("Join#15", 1.081, 1.082, 7),
        _node("GroupByReduce#18", 1.085, 1.088, 7),
        _node("Join#23", 1.089, 1.091, 7),
        _node("Subscribe#28", 1.092, 1.093, 7),
        _span("rest.wake", 1.095, 1.099, req=1, parent="rest.request"),
    ]


def _tick_b():
    """Tick 9, 1.300-1.350: no search, so all head; its group-by counts in no
    median."""
    return [
        _span("tick", 1.300, 1.350, tick=9, time=9, rows_in=8),
        _node("GroupByReduce#18", 1.310, 1.320, 9),
    ]


def _tick_c():
    """Tick 11, 1.360-1.460: an upload ahead of the embed opens the envelope;
    head 12 ms, envelope 68, tail 20; a search of 10."""
    kid = {"tick": 11, "parent": "index.search"}
    return [
        _span("tick", 1.360, 1.460, tick=11, time=11, rows_in=10),
        _span("index.search", 1.370, 1.450, tick=11, q=10, dirty=True, k=10,
              parent="ExternalIndexNode#7"),
        _span("index.upload", 1.372, 1.380, bytes=1024, **kid),
        _span("embed.dispatch", 1.385, 1.390, tick=11, parent="index.embed"),
        _span("index.fetch", 1.400, 1.440, **kid),
        _node("GroupByReduce#18", 1.441, 1.446, 11),
        _node("Join#15", 1.447, 1.448, 11),
        _node("Join#23", 1.449, 1.452, 11),
        _node("Subscribe#28", 1.453, 1.455, 11),
        _span("rest.wake", 1.456, 1.462, req=2, parent="rest.request"),
    ]


POLL_A = _span("engine.poll", 1.100, 1.102, sources=2, rounds=0, rows=0)
PARK = _span("engine.park", 1.102, 1.300)
POLL_B = _span("engine.poll", 1.350, 1.352, sources=2, rounds=1, rows=10)


def _everything():
    return [*_tick_a(), POLL_A, PARK, *_tick_b(), POLL_B, *_tick_c()]


def _as_loaded(events):
    """The events as ``program_spans`` hands them on: seconds on the window's clock."""
    return [{"name": e["name"], "t0": ORIGIN + e["ts"] * 1e-6,
             "t1": ORIGIN + (e["ts"] + e["dur"]) * 1e-6, "args": e["args"]} for e in events]


@pytest.mark.parametrize("events, window, want", [
    pytest.param([*_tick_a(), POLL_A], (0.9, 1.2),
                 {"head": 0.030, "envelope": 0.040, "tail": 0.030, "parked": 0.0,
                  "between": 0.200}, id="a-tick-with-one-search"),
    pytest.param([*_tick_b(), POLL_B], (1.25, 1.40),
                 {"head": 0.050, "envelope": 0.0, "tail": 0.0, "parked": 0.0,
                  "between": 0.100}, id="a-tick-with-none-is-all-head"),
    pytest.param([POLL_B, *_tick_c()], (1.35, 1.50),
                 {"head": 0.012, "envelope": 0.068, "tail": 0.020, "parked": 0.0,
                  "between": 0.050}, id="an-upload-ahead-of-the-embed-opens-the-envelope"),
    pytest.param([*_tick_a(), POLL_A, PARK, *_tick_b(), POLL_B], (1.0, 1.36),
                 {"head": 0.080, "envelope": 0.040, "tail": 0.030, "parked": 0.198,
                  "between": 0.012}, id="a-park-between-two-ticks"),
    pytest.param([POLL_B, *_tick_c()], (1.36, 1.41),
                 {"head": 0.012, "envelope": 0.038, "tail": 0.0, "parked": 0.0,
                  "between": 0.0}, id="the-window-cuts-a-tick"),
    pytest.param(_everything(), (0.9, 1.5),
                 {"head": 0.092, "envelope": 0.108, "tail": 0.050, "parked": 0.198,
                  "between": 0.152}, id="all-of-it"),
])
def test_the_partition_gives_the_shares_by_hand_and_sums_to_the_window(events, window, want):
    tw = {"t0": ORIGIN + window[0], "t1": ORIGIN + window[1]}
    got = engine_time.partition(_as_loaded(events), tw)
    assert got["window"] == pytest.approx(window[1] - window[0])
    for part, seconds in want.items():
        assert got[part] == pytest.approx(seconds, abs=1e-6), (part, got)
    assert sum(got[p] for p in want) == pytest.approx(got["window"])


def test_spans_that_do_not_cover_the_engine_thread_have_no_partition():
    before = [e for e in _everything() if e["name"] != "engine.poll"]
    tw = {"t0": ORIGIN + 0.9, "t1": ORIGIN + 1.5}
    assert engine_time.partition(_as_loaded(before), tw) is None
    assert engine_time.partition(None, tw) is None
    assert engine_time.partition(_as_loaded(_everything()), tw)["ticks"] == 3


BY_HAND = {
    "idle_tick_head_pct": 100 * 0.092 / 0.6,
    "idle_tick_tail_pct": 100 * 0.050 / 0.6,
    "idle_between_ticks_pct": 100 * 0.152 / 0.6,
    "idle_in_search_pct": 100 * (0.108 - 0.058) / 0.6,  # the trace's busy_s below
    "tick_groupby_ms": 4.0,  # 3 and 5: tick 9 holds no search
    "tick_join_ms": 3.5,  # 1 + 2 and 1 + 3
    "tick_subscribe_ms": 1.5,
    "resume_wait_ms": 5.0,  # 4 and 6
    "search_q_spread": 3.2,  # a search of 6 and one of 10: 9.6 - 6.4, interpolated
}
CELL = {"trace_window": {"t0": ORIGIN + 0.9, "t1": ORIGIN + 1.5}}
TRACE = {"stand_in": False, "busy_s": 0.058, "window_s": 0.6}


def _reader(name):
    return bench_run.layer_reader(name, os.path.dirname(harness.REHEARSAL))


@pytest.mark.parametrize("name", sorted(NINE))
def test_a_reader_reads_the_spans_by_hand(tmp_path, monkeypatch, name):
    assert set(BY_HAND) == set(NINE)
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    _write(tmp_path / "pathway-tpu" / "spans", "1.json", ORIGIN, _everything())
    assert _reader(name)(TRACE, [], {}, CELL) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(NINE))
def test_a_reader_gives_none_on_a_span_file_of_the_parents_kind(tmp_path, monkeypatch, name):
    """Before PR 38: no ``engine.poll``, no ``rest.wake``, a node event with
    its rows alone, a search under the tick."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    read = _reader(name)
    assert read(TRACE, [], {}, CELL) is None  # no span directory at all
    before = []
    for e in _everything():
        if e["name"] in ("engine.poll", "rest.wake"):
            continue
        args = dict(e["args"])
        if "#" in e["name"]:
            args = {"rows": args["rows"]}
        elif e["name"] == "index.search":
            args["parent"] = "tick"
        before.append({**e, "args": args})
    _write(tmp_path / "pathway-tpu" / "spans", "1.json", ORIGIN, before)
    assert read(TRACE, [], {}, CELL) is None
    assert read(None, [], {}, {"trace_window": None}) is None


def _manifest(tmp_path) -> str:
    """The rehearsal's manifest and files, copied, with the nine declared in
    the read cell, and ``engine_parked_pct`` beside them for the identity."""
    root = tmp_path / "manifest"
    shutil.copytree(os.path.join(harness.TESTS, "rehearsal"), root)
    manifest = json.load(open(root / "BENCHMARK.json"))
    for name, (unit, layer, moves) in NINE.items():
        manifest["per_layer"].append(
            {"name": name, "unit": unit, "better": "lower", "source": "program_span",
             "layer": layer, "moves": moves, "workloads": [READ]})
    manifest["per_layer"].append(
        {"name": "engine_parked_pct", "unit": "%", "better": "lower",
         "source": "program_span", "layer": "dataflow tick and batch formation",
         "moves": "retrieve_qps", "workloads": [READ]})
    json.dump(manifest, open(root / "BENCHMARK.json", "w"))
    return str(root / "BENCHMARK.json")


def test_a_traced_rehearsal_prints_all_nine_and_the_idle_shares_meet(tmp_path):
    tmp = tmp_path / "tmp"
    tmp.mkdir()
    code, result, err = harness.run_cell(
        READ, seconds=3, trace=1, manifest=_manifest(tmp_path), env={"TMPDIR": str(tmp)})
    assert code == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert set(NINE) <= set(got), sorted(set(NINE) - set(got))
    units = {n: m["unit"] for n, m in result["metrics"].items()}
    assert all(units[n] == NINE[n][0] for n in NINE)
    # the five shares are the window less the device's busy time, whatever
    # stood in for the device
    facts = json.load(open(os.path.join(harness.BENCH, "out", READ, "child_facts.json")))
    tw = facts["trace_window"]
    idle = 100.0 * (1.0 - result["device"]["busy_s"] / (tw["t1"] - tw["t0"]))
    shares = ("idle_tick_head_pct", "idle_tick_tail_pct", "idle_between_ticks_pct",
              "idle_in_search_pct", "engine_parked_pct")
    assert sum(got[n] for n in shares) == pytest.approx(idle, abs=1e-6), got
    assert all(got[n] >= 0 for n in shares[:3]) and got["engine_parked_pct"] >= 0
    # one caller a tick on the CPU: every search holds one query
    assert got["search_q_spread"] == 0
    assert 0 < got["tick_groupby_ms"] + got["tick_join_ms"] + got["tick_subscribe_ms"]
    assert got["resume_wait_ms"] > 0
