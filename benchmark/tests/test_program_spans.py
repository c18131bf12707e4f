"""The per-layer metrics that read the program's own spans
(``lib/program_spans.py`` and the ``layers/`` files that use it): they come
out of a traced CPU rehearsal declared in a manifest of the test's own, and
every reader returns ``None`` when the span directory holds nothing of the
cell's traced window."""

import json
import os
import re
import shutil
import tempfile

import harness
import pytest
import run as bench_run
from lib import program_spans as ps

READ, LIVE = "tiny-bert.read-c4", "tiny-bert.live-upsert-c4"
RETRIEVE = {  # name: (layer, moves)
    "window_wait_ms": ("REST edge, admission", "retrieve_p50_ms"),
    "rest_slice_ms": ("REST edge, admission", "retrieve_p50_ms"),
    "tick_host_ms": ("dataflow tick and batch formation", "retrieve_p50_ms"),
    "engine_parked_pct": ("dataflow tick and batch formation", "retrieve_qps"),
    "search_fetch_wait_ms": ("index engine", "retrieve_p50_ms"),
    "search_host_self_ms": ("index engine", "retrieve_p50_ms"),
}
WRITE = ("index_apply_ms", "index_upload_ms", "writing_tick_ms")


def _manifest(tmp_path) -> str:
    """The rehearsal's manifest and files, copied, with the new metrics
    declared: the six of the retrieve path in both cells, the three of the
    write path in the live one."""
    root = tmp_path / "manifest"
    shutil.copytree(os.path.join(harness.TESTS, "rehearsal"), root)
    manifest = json.load(open(root / "BENCHMARK.json"))
    for name, (layer, moves) in RETRIEVE.items():
        manifest["per_layer"].append(
            {"name": name, "unit": "%" if name.endswith("_pct") else "ms",
             "better": "lower", "source": "program_span", "layer": layer,
             "moves": moves, "workloads": [READ, LIVE]})
    for name in WRITE:
        manifest["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower", "source": "program_span",
             "layer": "index upload", "moves": "freshness_p95_s", "workloads": [LIVE]})
    json.dump(manifest, open(root / "BENCHMARK.json", "w"))
    return str(root / "BENCHMARK.json")


@pytest.mark.parametrize("cell", [READ, LIVE])
def test_a_traced_rehearsal_reports_every_new_metric(tmp_path, monkeypatch, cell):
    tmp = tmp_path / "tmp"
    tmp.mkdir()
    code, result, err = harness.run_cell(
        cell, seconds=3, trace=1, manifest=_manifest(tmp_path), env={"TMPDIR": str(tmp)})
    assert code == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    got = {n: m["value"] for n, m in result["metrics"].items()}
    want = set(RETRIEVE) | (set(WRITE) if cell == LIVE else set())
    assert want <= set(got), sorted(want - set(got))
    assert all(got[n] >= 0 for n in want), got
    assert 0 <= got["engine_parked_pct"] <= 100
    # the old ones are still there, read from the harness's own spans
    assert "queries_per_search" in got
    # the program wrote its spans only because the profiler ran
    assert len(os.listdir(tmp / "pathway-tpu" / "spans")) == 1
    if cell == READ:
        # a request's wait for its tick and that tick's own work lie inside
        # the request: under the median request of the same traced second
        # (on the CPU the profiler slows that second, so the whole window's
        # p50, which the run logs, can read a little less than it)
        facts = json.load(open(os.path.join(harness.BENCH, "out", cell, "child_facts.json")))
        monkeypatch.setattr(tempfile, "tempdir", str(tmp))
        traced = ps.load({"trace_window": facts["trace_window"]})
        p50 = ps.median([ps.ms(s) for s in ps.named(traced, "rest.request")])
        logged = float(re.search(r"p50 ([0-9.]+) ms", err).group(1))
        assert got["window_wait_ms"] + got["tick_host_ms"] < p50 < 2 * logged, (got, p50, logged)


def _span(name, t0, t1, **args):
    return {"name": name, "ph": "X", "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6, "args": args}


def _write(directory, name, origin_s, events):
    os.makedirs(directory, exist_ok=True)
    meta = {"name": "trace.clock_sync", "ph": "i", "ts": 0.0,
            "args": {"origin_monotonic_ns": int(origin_s * 1e9)}}
    with open(os.path.join(directory, name), "w") as f:
        json.dump({"traceEvents": [meta] + events}, f)


def _one_request(tick=7, req=1):
    """One request and the tick that answered it, seconds from the origin."""
    kid = {"tick": tick, "parent": "index.search"}
    return [
        _span("rest.request", 1.000, 1.100, req=req),
        _span("rest.in_engine", 1.002, 1.095, req=req, tick=tick),
        _span("engine.park", 1.000, 1.050),
        _span("tick", 1.050, 1.096, tick=tick, time=tick),
        _span("index.apply", 1.051, 1.055, tick=tick, added=8, removed=8),
        _span("index.search", 1.060, 1.080, tick=tick, q=1, dirty=False, k=3),
        _span("index.upload", 1.061, 1.063, bytes=1024, **kid),
        _span("index.fetch", 1.068, 1.077, **kid),
    ]


def test_readers_read_the_file_of_their_window_and_no_other(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    manifest_dir = os.path.dirname(harness.REHEARSAL)
    readers = {n: bench_run.layer_reader(n, manifest_dir) for n in (*RETRIEVE, *WRITE)}
    cell = {"trace_window": {"t0": 5000.5, "t1": 5002.5}}

    def readings():
        return {n: read(None, [], {}, cell) for n, read in readers.items()}

    # no directory, an empty one, a file that is no span file
    assert set(readings().values()) == {None}
    spans = tmp_path / "pathway-tpu" / "spans"
    os.makedirs(spans)
    assert set(readings().values()) == {None}
    (spans / "1.json").write_text("{}")
    assert set(readings().values()) == {None}
    # only a file of another window (another run of the same machine)
    _write(spans, "2.json", 100.0, _one_request())
    assert set(readings().values()) == {None}
    # the cell's own: origin 5000 s, so the request lies at 5001.0-5001.1
    _write(spans, "3.json", 5000.0, _one_request())
    got = readings()
    assert got["window_wait_ms"] == pytest.approx(48.0)  # 1.002 -> 1.050
    assert got["rest_slice_ms"] == pytest.approx(7.0)  # 100 - 93
    assert got["tick_host_ms"] == pytest.approx(26.0)  # 46 - 20
    assert got["engine_parked_pct"] == pytest.approx(2.5)  # 50 ms of 2 s
    assert got["search_fetch_wait_ms"] == pytest.approx(9.0)
    assert got["search_host_self_ms"] == pytest.approx(11.0)  # 20 - 9
    assert got["index_apply_ms"] == pytest.approx(4.0)
    assert got["index_upload_ms"] == pytest.approx(2.0)
    assert got["writing_tick_ms"] == pytest.approx(46.0)
    # a span that started before the window is not read
    early = {"trace_window": {"t0": 5001.04, "t1": 5003.0}}
    assert readers["window_wait_ms"](None, [], {}, early) is None
    assert readers["tick_host_ms"](None, [], {}, early) == pytest.approx(26.0)
