"""The CPU rehearsal of the three cells at a tiny size, through the benchmark's own
command: the last line's keys, ``correct`` true, the control not correct, and
no chip means no run."""

import harness
import pytest

READ, LIVE = "tiny-bert.read-c4", "tiny-bert.live-upsert-c4"
SCOPED = "tiny-bert-tenants.read-scoped-c4"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", [READ, LIVE, SCOPED])
def test_untraced_run_reports_the_end_to_end_metrics(cell):
    code, result, err = harness.run_cell(cell, seconds=3, extra=("--control", "1"))
    assert code == 0, err[-3000:]
    assert KEYS <= set(result) and list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20
    # the tail is read per layer (``window_p95_ms``): no cell holds it to a bound
    want = {"setup_s", "retrieve_qps", "retrieve_p50_ms"}
    if cell == LIVE:
        want.add("freshness_p95_s")
    assert set(result["metrics"]) == want
    compared = ["rank_gap", "score_err", "bad_replies"] + {
        LIVE: ["order_violations", "lost_writes", "count_off"],
        SCOPED: ["out_of_scope"]}.get(cell, [])
    assert list(result["compared"]) == compared
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    # the control — the reference in fp8 in the program's place — is not correct
    limits = {k: v["limit"] for k, v in result["compared"].items()}
    control = result["control"]
    assert control["rank_gap"] > limits["rank_gap"] or control["score_err"] > limits["score_err"]
    # and stands well clear of what the program reads
    assert control["score_err"] > 3 * result["compared"]["score_err"]["value"]


@pytest.mark.parametrize("cell", [READ, LIVE, SCOPED])
def test_traced_run_reports_the_per_layer_metrics(cell):
    code, result, err = harness.run_cell(cell, seconds=3, trace=1)
    assert code == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    got = set(result["metrics"])
    assert {"queries_per_search", "compiles_in_window", "window_p95_ms"} <= got
    assert result["metrics"]["window_p95_ms"]["value"] > 0
    assert ("search_dirty_ms" if cell == LIVE else "search_clean_ms") in got
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    # nothing a CPU run cannot know is written under a device metric's name
    assert not got & {"embed_device_ms", "topk_scores_roofline", "retrieve_mfu",
                      "device_idle_pct"}


def test_without_a_chip_and_without_the_callers_say_so_it_refuses():
    # JAX_PLATFORMS unset: JAX falls back to the CPU by itself, which the
    # harness must not take for a rehearsal
    code, result, err = harness.run_cell(
        READ, seconds=1, env={"JAX_PLATFORMS": None, "TPU_SKIP_MDS_QUERY": "1"},
        timeout=240)
    assert code != 0 and result is None, err[-2000:]
    assert "no chip" in err
