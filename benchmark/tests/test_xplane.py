"""The reduction from trace to numbers, on a trace small enough to work out by
hand and on the short slice of a real v5e trace kept beside this file."""

import json
import os

import harness
import pytest
from lib import xplane

SYNTHETIC = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_embed_tokens(1)", 1.000, 0.002), ("jit_topk_scores(2)", 1.002, 0.010),
                        ("jit_embed_tokens(1)", 1.020, 0.002), ("jit_topk_scores(2)", 1.022, 0.012)],
        "XLA Ops": [("fusion.1", 1.000, 0.002), ("convert.7", 1.002, 0.004), ("top_k", 1.006, 0.006),
                    ("fusion.1", 1.020, 0.002), ("convert.7", 1.022, 0.005), ("top_k", 1.027, 0.007),
                    ("copy.3", 1.0275, 0.001)],  # overlaps top_k: must not count twice
    },
    "/host:CPU": {
        "python3": [("search", 0.999, 0.015), ("embed", 0.9995, 0.001),
                    ("search", 1.019, 0.016), ("noise", 0.990, 0.050)],
    },
}


def test_synthetic_trace_by_hand():
    r = xplane.reduce_planes(SYNTHETIC, ("search", "embed"))
    assert r["stand_in"] is False and r["devices"] == ["/device:TPU:0"]
    assert r["window_s"] == pytest.approx(1.040 - 0.990)
    assert r["busy_s"] == pytest.approx(0.012 + 0.014)       # two busy runs, overlap once
    topk = xplane.program_time(r, "topk_scores")
    assert topk["calls"] == 2 and topk["mean_s"] == pytest.approx(0.011)
    assert xplane.program_time(r, "embed_tokens")["median_s"] == pytest.approx(0.002)
    under = xplane.program_time(r, "", span="search", other_than="topk_scores")
    assert under["calls"] == 2 and under["median_s"] == pytest.approx(0.002)
    assert xplane.program_time(r, "no_such_program") is None
    assert r["device_ops"][0] == ["top_k", pytest.approx(0.013)]
    gaps = dict((n, t) for n, t in r["idle_gaps"] if n.startswith("all gaps"))
    # 0.990..1.000 and 1.034..1.040 outside, 1.012..1.020 half under the first search
    assert gaps["all gaps: outside any span"] == pytest.approx(0.010 + 0.006 + 0.008)


def test_an_idle_tail_counts_as_window():
    r = xplane.reduce_planes(SYNTHETIC, ("search", "embed"), traced_s=0.100)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.026)
    gaps = dict((n, t) for n, t in r["idle_gaps"] if n.startswith("all gaps"))
    assert gaps["all gaps: outside any span"] == pytest.approx(0.024 + 0.050)


def test_no_device_and_no_stand_in_reduces_to_nothing():
    assert xplane.reduce_planes({"/host:CPU": {"python3": [("search", 0.0, 1.0)]}}) is None
    assert xplane.reduce_planes({}) is None


def test_program_names():
    assert xplane.program_name("jit_topk_scores(123456)") == "topk_scores"
    assert xplane.program_name("jit_embed_tokens") == "embed_tokens"


RECORDED = os.path.join(harness.TESTS, "recorded", "v5e_read_c16_slice.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded slice")
def test_recorded_v5e_slice():
    planes = json.load(open(RECORDED))
    r = xplane.reduce_planes(planes, ("search", "embed"))
    assert r["stand_in"] is False and r["devices"] == ["/device:TPU:0"]
    assert 0 < r["busy_s"] <= r["window_s"]
    assert xplane.program_time(r, "topk_scores")["calls"] >= 1
    # the embedder jits a partial: the trace calls its program jit__unknown
    embed = xplane.program_time(r, "", span="search", other_than="topk_scores")
    assert embed["calls"] == 2 and embed["median_s"] == pytest.approx(1.925e-3, rel=1e-2)
    assert xplane.program_time(r, "topk_scores")["median_s"] == pytest.approx(7.163e-3, rel=1e-2)
    assert r["busy_s"] == pytest.approx(0.0176, rel=0.05)
