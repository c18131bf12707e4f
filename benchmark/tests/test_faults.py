"""The rest of a run with the timed path broken underneath: ``correct`` has to
come out false, once for each fault a cell can have (one chip: no exchange)."""

import harness
import pytest

READ, LIVE = "tiny-bert.read-c4", "tiny-bert.live-upsert-c4"
SCOPED = "tiny-bert-tenants.read-scoped-c4"


@pytest.mark.parametrize("cell,fault,caught_by", [
    (READ, "answer", {"score_err", "rank_gap"}),   # an answer altered where it is produced
    (READ, "token", {"score_err", "rank_gap"}),    # a token altered where it is produced
    (READ, "half", {"rank_gap"}),                  # half of the rows left out of the scan
    (LIVE, "stale", {"lost_writes", "score_err"}), # writes acknowledged, state unchanged
    (SCOPED, "ignore_scope", {"out_of_scope"}),    # the engine is handed no filter
    (SCOPED, "scope_short", {"bad_replies"}),      # a scoped reply loses its last row
])
def test_a_broken_path_is_not_correct(cell, fault, caught_by):
    code, result, err = harness.run_cell(cell, seconds=3, extra=("--fault", fault),
                                         timeout=400)
    assert code == 0, err[-3000:]
    assert result["correct"] is False
    over = {n for n, c in result["compared"].items() if c["value"] > c["limit"]}
    assert over & caught_by, result["compared"]
