"""The live mix answered whole. With every search 1.5 s slower (``--fault
slow``) the writer's commits pile up behind a tick and reach the engine merged
into one delta, as they do behind a long tick on the chip: every reply still
holds ``k`` rows of one index state, and no write is lost. And a traced live
run finds the spans of a writing tick, with the new per-layer readers dropped
in beside the rehearsal's manifest."""

import json
import os

import harness
import pytest
from lib import workcount_write

LIVE = "tiny-bert.live-upsert-c4"
NEW = ("writing_tick_ms", "index_apply_ms", "index_write_device_ms",
       "index_write_roofline", "index_reuploads_in_window")


def test_commits_merged_behind_a_slow_tick_are_answered_whole():
    code, result, err = harness.run_cell(LIVE, seed=7, seconds=6,
                                         extra=("--fault", "slow"), timeout=600)
    assert code == 0, err[-3000:]
    compared = {n: c["value"] for n, c in result["compared"].items()}
    assert compared["bad_replies"] == 0, err[-3000:]
    assert compared["order_violations"] == 0 and compared["lost_writes"] == 0
    assert compared["count_off"] == 0
    assert result["correct"] is True, result["compared"]


def test_a_traced_live_run_reads_the_writing_tick(tmp_path):
    manifest = json.load(open(harness.REHEARSAL))
    src = os.path.dirname(harness.REHEARSAL)
    for c in manifest["configs"]:
        c["file"] = os.path.join(src, c["file"])
    root = {p["name"]: p for p in json.load(
        open(os.path.join(harness.ROOT, "BENCHMARK.json")))["per_layer"]}
    for name in NEW:
        manifest["per_layer"].append(dict(root[name], workloads=[LIVE]))
    path = tmp_path / "BENCHMARK.json"
    json.dump(manifest, open(path, "w"))
    for kind in ("traffic", "limits"):
        os.symlink(os.path.join(src, kind), tmp_path / kind)
    code, result, err = harness.run_cell(LIVE, seconds=3, trace=1, manifest=str(path))
    assert code == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    got = result["metrics"]
    assert got["writing_tick_ms"]["value"] > 0 and got["index_apply_ms"]["value"] > 0
    assert "search_dirty_ms" in got
    # writes went in place: no whole placement inside the traced second
    assert got["index_reuploads_in_window"]["value"] == 0
    # nothing a CPU run cannot know is written under a device metric's name
    assert not set(got) & {"index_write_device_ms", "index_write_roofline"}


@pytest.mark.parametrize("metric,expect", [
    # 8 slots of width 768: 8 float32 rows in at 3,072 B, written as bfloat16
    # at 1,536 B (the device copy of cos and ip), 16 mask bytes
    ("cos", 8 * (3072 + 1536) + 16), ("ip", 36880),
    # l2 keeps a float32 copy: written at 3,072 B, the count of before PR 32
    ("l2", 8 * 3072 * 2 + 16),
])
def test_write_bytes_by_hand(metric, expect):
    assert workcount_write.index_write_bytes(8, 768, metric) == expect
