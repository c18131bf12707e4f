"""``workcount_embed`` against a count worked out by hand, and the four readers
of the embed forward against spans and a trace written by hand: what they read
from the packed forward, and that a program that lacks it gives them nothing."""

import json
import os

import harness  # noqa: F401  (puts benchmark/ on the path)
import pytest
import run as bench_run
from lib import peaks, program_spans as ps, workcount, workcount_embed

LARGE = dict(hidden_size=1024, intermediate_size=4096, num_hidden_layers=24)
V5E = peaks.peaks_for("TPU v5 lite")
CELL = "e5-large-v2-longq-1200k.read-long-c16"


def test_embed_flops_by_hand_at_the_long_cells_shape():
    # a query of 512 tokens: 24 layers of 24 d^2 (qkv 6, proj 2, mlp 16) +
    # 4 s d of attention over its own 512 positions, a token
    one = 24 * (24 * 1024**2 + 4 * 512 * 1024)
    assert workcount.encoder_flops_per_token(LARGE, 512) == one == 654_311_424
    assert workcount_embed.embed_flops([512], LARGE) == 512 * one
    # a text's attention sees its own length, not its neighbour's: two texts
    # are two sums, and short ones cost less a token
    short = 24 * (24 * 1024**2 + 4 * 32 * 1024)
    assert workcount_embed.embed_flops([512, 32], LARGE) == 512 * one + 32 * short
    # the mix's mean tick: eight queries, 2,077 tokens, about 1.3 TFLOP,
    # 6.6 ms at the bfloat16 peak
    tick = [227] * 8 + [261]
    assert sum(tick) == 2077
    assert workcount_embed.embed_flops(tick, LARGE) / V5E["bf16_flops"] == \
        pytest.approx(6.6e-3, rel=0.03)
    assert workcount_embed.embed_flops([], LARGE) == 0.0


def _reader(name):
    return bench_run.layer_reader(name, harness.ROOT)


def _span_file(tmp_path, monkeypatch, events):
    """A span file as the program's end-of-run flush writes it, the origin of
    its clock at 0: ``ts`` and ``dur`` in microseconds."""
    monkeypatch.setattr(ps.tempfile, "gettempdir", lambda: str(tmp_path))
    os.makedirs(ps.spans_dir())
    doc = {"traceEvents": [
        {"name": "trace.clock_sync", "ph": "M", "args": {"origin_monotonic_ns": 0}},
        *({"ph": "X", **e} for e in events)]}
    with open(os.path.join(ps.spans_dir(), "1.json"), "w") as f:
        json.dump(doc, f)
    return {"trace_window": {"t0": 0.0, "t1": 1.0}, "config": LARGE,
            "chip": V5E, "mix": {}}


def test_the_span_readers_read_the_packed_forward(tmp_path, monkeypatch):
    events = []
    for i, (texts, tokens, rows) in enumerate([(8, 2077, 5), (8, 1800, 4)]):
        t = 1000.0 + 40_000.0 * i
        events += [
            {"name": "index.search", "ts": t, "dur": 30_000.0, "args": {"tick": i, "q": texts}},
            {"name": "embed.tokenize", "ts": t + 10.0, "dur": 9_000.0 + 2_000.0 * i,
             "args": {"tick": i, "q": texts, "tokens": tokens}},
            {"name": "embed.dispatch", "ts": t + 12_000.0, "dur": 1_500.0,
             "args": {"tick": i, "bucket": 512, "rows": rows, "texts": texts,
                      "tokens": tokens, "computed": rows * 512}}]
    cell = _span_file(tmp_path, monkeypatch, events)
    assert _reader("embed_dispatches_per_search")(None, [], {}, cell) == 1.0
    assert _reader("embed_pad_pct")(None, [], {}, cell) == \
        pytest.approx(100.0 * (1 - 3877 / (9 * 512)))
    assert _reader("embed_tokenize_ms")(None, [], {}, cell) == pytest.approx(10.0)


def test_a_program_without_the_packed_forward_gives_the_span_readers_what_it_has(
        tmp_path, monkeypatch):
    # the parent: a dispatch a bucket, spans with ``bucket`` and ``rows`` alone
    events = [{"name": "index.search", "ts": 1000.0, "dur": 30_000.0, "args": {"tick": 0}}]
    events += [{"name": "embed.dispatch", "ts": 2000.0 + 2000.0 * j, "dur": 1_500.0,
                "args": {"tick": 0, "bucket": b, "rows": 2}} for j, b in enumerate((128, 256, 512))]
    cell = _span_file(tmp_path, monkeypatch, events)
    assert _reader("embed_dispatches_per_search")(None, [], {}, cell) == 3.0
    assert _reader("embed_pad_pct")(None, [], {}, cell) is None
    assert _reader("embed_tokenize_ms")(None, [], {}, cell) is None


def test_no_span_file_gives_every_span_reader_none(tmp_path, monkeypatch):
    monkeypatch.setattr(ps.tempfile, "gettempdir", lambda: str(tmp_path))
    cell = {"trace_window": {"t0": 0.0, "t1": 1.0}}
    for name in ("embed_dispatches_per_search", "embed_pad_pct", "embed_tokenize_ms"):
        assert _reader(name)(None, [], {}, cell) is None


def test_embed_roofline_is_needed_work_over_the_programs_device_time():
    read = _reader("embed_roofline")
    cell = {"config": LARGE, "chip": V5E}
    tokens = [512, 512, 227, 32]
    needed_s = workcount_embed.embed_flops(tokens, LARGE) / V5E["bf16_flops"]
    trace = {"stand_in": False, "programs": {
        "embed_tokens": {"calls": 2, "total_s": 4 * needed_s, "median_s": 2 * needed_s},
        "embed_take": {"calls": 2, "total_s": 1.0, "median_s": 0.5},
        "topk_scores": {"calls": 2, "total_s": 1.0, "median_s": 0.5}}}
    assert read(trace, [], {"traced_tokens": tokens}, cell) == pytest.approx(25.0)
    # nothing to read is None, never 0: no such program, no traced request,
    # a CPU stand-in, no chip
    assert read({"stand_in": False, "programs": {"topk_scores": trace["programs"]["topk_scores"]}},
                [], {"traced_tokens": tokens}, cell) is None
    assert read(trace, [], {"traced_tokens": []}, cell) is None
    assert read({**trace, "stand_in": True}, [], {"traced_tokens": tokens}, cell) is None
    assert read(trace, [], {"traced_tokens": tokens}, {"config": LARGE, "chip": None}) is None
    assert read(None, [], {"traced_tokens": tokens}, cell) is None


def test_the_new_metrics_are_declared_where_they_have_something_to_read():
    """The roofline share against the compute peak is the long cell's alone (a
    program of sixteen tokens a row is bound by a read of its parameters); the
    three span readers read every cell, so that the control cell shows the
    contrast; and the long cell is listed by the read cells' span metrics."""
    manifest = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, better, source, moves) in {
        "embed_roofline": ("%", "higher", "device_trace", "retrieve_qps"),
        "embed_dispatches_per_search": ("calls", "lower", "program_span", "retrieve_qps"),
        "embed_pad_pct": ("%", "lower", "program_span", "retrieve_qps"),
        "embed_tokenize_ms": ("ms", "lower", "program_span", "retrieve_p50_ms"),
    }.items():
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (unit, better, source, moves)
        assert m["layer"] == "embed forward"
        assert m.get("workloads") == ([CELL] if name == "embed_roofline" else None)
    for name in ("tick_host_ms", "search_clean_ms", "search_host_self_ms", "search_fetch_wait_ms",
                 "window_wait_ms", "rest_slice_ms", "engine_parked_pct"):
        assert declared[name]["workloads"][-1] == CELL
