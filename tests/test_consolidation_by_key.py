"""Consolidation by key first: row content is hashed only inside groups of
entries that share a key (``engine/delta.py::consolidation_plan``, behind
``Delta.consolidated``, ``_SortedSide._consolidate`` and
``Join._check_unique_ids``).

The references below are the full-hash forms the engine had before: every
row's content hashed, whatever its key. The new code must produce the same
multiset of (key, row, diff) for every input — only the order of the
surviving entries, and which rows were looked at, may differ.
"""

from __future__ import annotations

import copy

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import keys as K
from pathway_tpu.engine.delta import (
    Delta,
    column_of_values,
    consolidation_plan,
    rows_to_columns,
)
from pathway_tpu.engine.error import ERROR_LOG, Error as EngineError
from pathway_tpu.engine.fusion import FUSION_STATS
from pathway_tpu.engine.operators import Join, StaticSource, _SortedSide
from pathway_tpu.internals.parse_graph import G

SEEDS = [0, 1, 2, 3]


@pytest.fixture(autouse=True)
def _clean_graph():
    G.clear()
    yield
    G.clear()


# ---------------------------------------------------------------------------
# references: consolidation as it was, hashing every row
# ---------------------------------------------------------------------------


def _reference_consolidated(d: Delta) -> Delta:
    if not len(d):
        return d
    row_sig = K.derive_pair(
        d.keys, K.mix_columns(list(d.data.values()), len(d), register=False)
    )
    order = np.argsort(row_sig, kind="stable")
    sig_sorted = row_sig[order]
    starts = np.concatenate([[0], np.flatnonzero(np.diff(sig_sorted) != 0) + 1])
    sums = np.add.reduceat(d.diffs[order], starts)
    keep = sums != 0
    out = d.take(order[starts[keep]])
    out.diffs = sums[keep]
    return out


def _canon(v):
    """A cell as something hashable that equal cells share."""
    if isinstance(v, np.ndarray):
        return ("nd", v.shape, v.dtype.str, v.tobytes())
    if isinstance(v, tuple):
        return ("t",) + tuple(_canon(x) for x in v)
    if isinstance(v, dict):
        return ("d",) + tuple(sorted((k, _canon(x)) for k, x in v.items()))
    if isinstance(v, EngineError):
        return "ERR"
    if isinstance(v, np.generic):
        return v.item()
    return v


def _entries(d: Delta | None) -> list[tuple]:
    if d is None:
        return []
    return [
        (key, tuple(_canon(c) for c in row), diff)
        for key, row, diff in d.iter_rows()
    ]


def _folded(entries) -> dict:
    out: dict = {}
    for key, row, diff in entries:
        out[(key, row)] = out.get((key, row), 0) + diff
    return {k: v for k, v in out.items() if v}


# ---------------------------------------------------------------------------
# seeded deltas
# ---------------------------------------------------------------------------

_WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta"]


def _cell(rng, kind: str):
    if kind == "int":
        return int(rng.integers(0, 5))
    if kind == "text":
        return _WORDS[int(rng.integers(len(_WORDS)))]
    if kind == "reply":  # what a retrieve reply row carries
        n = int(rng.integers(1, 4))
        return tuple(
            rng.integers(0, 3, size=4).astype(np.float32) for _ in range(n)
        )
    if kind == "meta":
        return (
            None if rng.random() < 0.3
            else {"path": _WORDS[int(rng.integers(3))], "n": int(rng.integers(2))}
        )
    raise AssertionError(kind)


def _row(rng, kinds):
    return tuple(_cell(rng, k) for k in kinds)


def _delta(keys, rows, diffs, names) -> Delta:
    return Delta(
        keys=np.asarray(keys, dtype=np.uint64),
        data=rows_to_columns(rows, names),
        diffs=np.asarray(diffs, dtype=np.int64),
    )


_KINDS = {
    "dense": ("int", "int"),
    "object": ("text", "reply", "meta"),
}


def _case(name: str, seed: int, kinds):
    """(keys, rows, diffs) of one named shape, seeded."""
    rng = np.random.default_rng([seed, len(name)])
    keys, rows, diffs = [], [], []

    def add(k, r, d):
        keys.append(k)
        rows.append(r)
        diffs.append(d)

    if name == "distinct_mixed_signs":
        for k in rng.permutation(40)[:16]:
            add(int(k), _row(rng, kinds), 1 if rng.random() < 0.5 else -1)
    elif name == "retract_and_insert_same_row":
        for k in range(8):
            r = _row(rng, kinds)
            add(k, r, -1)
            add(k, copy.deepcopy(r), 1)  # equal cells, other objects
    elif name == "update":
        for k in range(8):
            old, new = _row(rng, kinds), _row(rng, kinds)
            while _canon(new) == _canon(old):
                new = _row(rng, kinds)
            add(k, old, -1)
            add(k, new, 1)
    elif name == "duplicate_inserts":
        for k in range(6):
            r = _row(rng, kinds)
            add(k, r, 1)
            add(k, copy.deepcopy(r), 1)
        add(99, _row(rng, kinds), 1)
    elif name == "zero_diffs":
        for k in range(10):
            add(k, _row(rng, kinds), int(rng.integers(-1, 2)))
        r = _row(rng, kinds)
        add(3, r, 0)
    elif name == "random_mix":
        pool = [_row(rng, kinds) for _ in range(5)]
        for _ in range(int(rng.integers(20, 60))):
            add(
                int(rng.integers(0, 12)),
                pool[int(rng.integers(len(pool)))],
                int(rng.integers(-2, 3)),
            )
    else:
        raise AssertionError(name)
    order = rng.permutation(len(keys))
    return (
        [keys[i] for i in order],
        [rows[i] for i in order],
        [diffs[i] for i in order],
    )


_CASES = [
    "distinct_mixed_signs", "retract_and_insert_same_row", "update",
    "duplicate_inserts", "zero_diffs", "random_mix",
]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cells", sorted(_KINDS))
@pytest.mark.parametrize("case", _CASES)
def test_consolidated_equals_full_hash_reference(case, cells, seed):
    kinds = _KINDS[cells]
    names = [f"c{i}" for i in range(len(kinds))]
    keys, rows, diffs = _case(case, seed, kinds)
    d = _delta(keys, rows, diffs, names)
    want = _folded(_entries(_reference_consolidated(d)))
    assert want == _folded(_entries(d))  # the reference is itself sound
    got = _entries(d.consolidated())
    assert _folded(got) == want
    # fully consolidated: one entry per (key, row), none at zero
    assert len(got) == len(want)
    assert all(diff for _, _, diff in got)
    # engine-internal edges may keep +1/+1 apart, never a different multiset
    assert _folded(_entries(d.consolidated(multiset_ok=True))) == want
    if case == "retract_and_insert_same_row":
        assert not got
    if case == "update":
        assert sorted(diff for _, _, diff in got) == [-1] * 8 + [1] * 8
    if case == "duplicate_inserts":
        assert sorted(diff for _, _, diff in got) == [1] + [2] * 6


@pytest.mark.parametrize("fusion", ["0", "1"])
@pytest.mark.parametrize("case", _CASES)
def test_consolidated_is_unconditional(case, fusion, monkeypatch):
    # the by-key pass is exact, so PATHWAY_FUSION neither gates nor changes it
    monkeypatch.setenv("PATHWAY_FUSION", fusion)
    kinds = _KINDS["object"]
    keys, rows, diffs = _case(case, 11, kinds)
    d = _delta(keys, rows, diffs, ["a", "b", "c"])
    before = FUSION_STATS["consolidation_rows_hashed_total"]
    got = d.consolidated()
    assert _folded(_entries(got)) == _folded(_entries(_reference_consolidated(d)))
    hashed = FUSION_STATS["consolidation_rows_hashed_total"] - before
    if case == "distinct_mixed_signs":
        assert hashed == 0
    else:
        assert 0 < hashed <= len(d)


def test_consolidated_keeps_input_order_of_survivors():
    d = _delta(
        [5, 9, 5, 7, 9, 3],
        [("a",), ("b",), ("a",), ("c",), ("x",), ("d",)],
        [1, -1, 1, 1, 1, -1],
        ["w"],
    )
    out = d.consolidated()
    assert out.keys.tolist() == [5, 9, 7, 9, 3]
    assert out.diffs.tolist() == [2, -1, 1, 1, -1]
    assert [r[0] for _, r, _ in out.iter_rows()] == ["a", "b", "c", "x", "d"]


def test_consolidation_plan_none_when_nothing_to_do():
    ids = np.arange(16, dtype=np.uint64)
    diffs = np.where(np.arange(16) % 2, 1, -1).astype(np.int64)
    assert consolidation_plan(ids, [column_of_values(list("abcdefghijklmnop"))], diffs) is None


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------


def _reply_delta(keys, diffs, seed=0):
    rng = np.random.default_rng(seed)
    kinds = _KINDS["object"]
    return _delta(keys, [_row(rng, kinds) for _ in keys], diffs, ["a", "b", "c"])


def test_counter_distinct_keys_hash_nothing():
    d = _reply_delta(list(range(100, 116)), [1, -1] * 8)
    seen = FUSION_STATS["consolidation_rows_total"]
    hashed = FUSION_STATS["consolidation_rows_hashed_total"]
    assert d.consolidated() is d
    assert FUSION_STATS["consolidation_rows_total"] == seen + 16
    assert FUSION_STATS["consolidation_rows_hashed_total"] == hashed


def test_counter_recurring_key_hashes_its_group_only():
    keys = list(range(100, 116))
    keys[4] = keys[9] = keys[12] = 100  # one group of four entries
    d = _reply_delta(keys, [1, -1] * 8)
    seen = FUSION_STATS["consolidation_rows_total"]
    hashed = FUSION_STATS["consolidation_rows_hashed_total"]
    out = d.consolidated()
    assert _folded(_entries(out)) == _folded(_entries(_reference_consolidated(d)))
    assert FUSION_STATS["consolidation_rows_total"] == seen + 16
    assert FUSION_STATS["consolidation_rows_hashed_total"] == hashed + 4


def test_counters_ship_on_metrics():
    from pathway_tpu.engine.fusion import fusion_stats_snapshot
    from pathway_tpu.observability.prometheus import render_snapshots

    snap = fusion_stats_snapshot()
    assert "consolidation_rows_total" in snap
    assert "consolidation_rows_hashed_total" in snap
    text = render_snapshots([], fusion_stats={"0": snap})
    assert "pathway_fusion_consolidation_rows_total" in text
    assert "pathway_fusion_consolidation_rows_hashed_total" in text


# ---------------------------------------------------------------------------
# _SortedSide against a dictionary model
# ---------------------------------------------------------------------------


def _side_view(side: _SortedSide, qjks: np.ndarray) -> dict:
    """{(jk, row_key, canon values): net count} of what a probe yields."""
    net: dict = {}
    for q_idx, rkeys, cols, counts in side.probe(qjks):
        for i in range(len(rkeys)):
            ident = (
                int(qjks[q_idx[i]]), int(rkeys[i]),
                tuple(_canon(c[i]) for c in cols),
            )
            net[ident] = net.get(ident, 0) + int(counts[i])
    return {k: v for k, v in net.items() if v}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cells", sorted(_KINDS))
@pytest.mark.parametrize("batch", [3, 40, 300])
def test_sorted_side_agrees_with_model(batch, cells, seed):
    # batch 300 rides the deferred (pending) lane, 3 and 40 the eager tiers
    kinds = _KINDS[cells]
    rng = np.random.default_rng([seed, batch])
    side = _SortedSide(len(kinds))
    model: dict = {}  # (jk, key) -> row, live rows only
    all_jks = np.arange(1, 9, dtype=np.uint64)
    next_key = 1000

    def apply(entries):
        jks = np.array([e[0] for e in entries], dtype=np.uint64)
        keys = np.array([e[1] for e in entries], dtype=np.uint64)
        cols = list(rows_to_columns([e[2] for e in entries],
                                    [str(i) for i in range(len(kinds))]).values())
        diffs = np.array([e[3] for e in entries], dtype=np.int64)
        side.apply(jks, keys, cols, diffs)

    def check():
        want = {
            (jk, key, tuple(_canon(c) for c in row)): 1
            for (jk, key), row in model.items()
        }
        assert _side_view(side, all_jks) == want
        totals = side.totals(all_jks)
        for jk, tot in zip(all_jks.tolist(), totals.tolist()):
            assert tot == sum(1 for (j, _k) in model if j == jk)

    for _round in range(6):  # inserts: tiers build up
        entries = []
        for _ in range(batch):
            jk, key = int(rng.integers(1, 9)), next_key
            next_key += 1
            model[(jk, key)] = _row(rng, kinds)
            entries.append((jk, key, model[(jk, key)], 1))
        apply(entries)
        check()
    for _round in range(8):  # retractions and updates, over several tiers
        live = list(model)
        picks = rng.permutation(len(live))[: max(1, batch // 2)]
        entries = []
        for p in picks:
            jk, key = live[p]
            old = model.pop((jk, key))
            entries.append((jk, key, old, -1))
            if rng.random() < 0.4:  # an update: same (jk, key), another row
                new = _row(rng, kinds)
                while _canon(new) == _canon(old):
                    new = _row(rng, kinds)
                model[(jk, key)] = new
                entries.append((jk, key, new, 1))
        order = rng.permutation(len(entries))
        apply([entries[i] for i in order])
        check()
    side._compact()
    check()
    assert sum(len(r[0]) for r in side._runs) == len(model)


def test_sorted_side_merge_hashes_only_the_pairs_that_meet():
    side = _SortedSide(1)
    n = 64
    jks = np.arange(n, dtype=np.uint64)
    keys = np.arange(1000, 1000 + n, dtype=np.uint64)
    vals = column_of_values([f"v{i}" for i in range(n)])
    side.apply(jks, keys, [vals], np.ones(n, dtype=np.int64))
    before = FUSION_STATS["consolidation_rows_hashed_total"]
    # retract half of them: the tail run is within 2x, so it merges at once
    half = np.arange(0, n, 2)
    side.apply(jks[half], keys[half], [vals[half]],
               -np.ones(len(half), dtype=np.int64))
    assert len(side._runs) == 1 and len(side._runs[0][0]) == n // 2
    # each retraction and its insert, not the 96 rows of the two runs
    assert FUSION_STATS["consolidation_rows_hashed_total"] - before == n


# ---------------------------------------------------------------------------
# id-preserving join (key_mode="left"): through a duplicate match and back
# ---------------------------------------------------------------------------


def _reference_check_unique_ids(self: Join, delta: Delta | None) -> Delta | None:
    """``Join._check_unique_ids`` as it was: every output row hashed."""
    if self._key_mode == "pair" or delta is None or not len(delta):
        return delta
    n = len(delta)
    sigs = K.mix_columns(list(delta.data.values()), n, register=False).tolist()
    keys_l = delta.keys.tolist()
    diffs_l = delta.diffs.tolist()
    cols = [np.asarray(delta.data[c]) for c in self.column_names]
    state = self._idstate
    old_proj = {k: self._project_id_key(k) for k in set(keys_l)}
    for i, (k, sg, df) in enumerate(zip(keys_l, sigs, diffs_l)):
        ent = state.setdefault(k, {})
        cur = ent.get(sg)
        if cur is None:
            ent[sg] = [tuple(c[i] for c in cols), df]
        else:
            cur[1] += df
            if cur[1] == 0:
                del ent[sg]
        if not ent:
            state.pop(k, None)
    out_keys, out_rows, out_diffs = [], [], []
    for k, old in old_proj.items():
        new = self._project_id_key(k)
        if [(s, c) for s, _, c in new] == [(s, c) for s, _, c in old]:
            continue
        old_dup = any(s is self._DUP_SIG for s, _, _ in old)
        new_dup = any(s is self._DUP_SIG for s, _, _ in new)
        if new_dup and not old_dup:
            ERROR_LOG.record(f"duplicate key: {K.fmt_key(k)}", "join")
        for _, row, cnt in old:
            out_keys.append(k)
            out_rows.append(row)
            out_diffs.append(-cnt)
        for _, row, cnt in new:
            out_keys.append(k)
            out_rows.append(row)
            out_diffs.append(cnt)
    if not out_keys:
        return None
    return _reference_consolidated(Delta(
        keys=np.array(out_keys, dtype=np.uint64),
        data=rows_to_columns(out_rows, self.column_names),
        diffs=np.array(out_diffs, dtype=np.int64),
    ))


class _ReferenceJoin(Join):
    _check_unique_ids = _reference_check_unique_ids


def _empty_source(names):
    return StaticSource(
        np.empty(0, dtype=np.uint64),
        {n: np.empty(0, dtype=object) for n in names},
    )


def _id_join(cls, mode="left", key_mode="left"):
    return cls(
        _empty_source(["jk", "v"]), _empty_source(["jk", "w"]),
        "jk", "jk", ["v"], ["w"], ["v", "w"], mode=mode, key_mode=key_mode,
    )


def _side_delta(entries, col):
    """entries: (row_key, jk, value, diff)."""
    if not entries:
        return None
    return Delta(
        keys=np.array([e[0] for e in entries], dtype=np.uint64),
        data={
            "jk": np.array([e[1] for e in entries], dtype=np.uint64),
            col: column_of_values([e[2] for e in entries]),
        },
        diffs=np.array([e[3] for e in entries], dtype=np.int64),
    )


def _tick(node, t, left, right):
    """(folded output of the tick, "duplicate key" entries it logged)."""
    start = ERROR_LOG.next_index
    out = node.process(t, [_side_delta(left, "v"), _side_delta(right, "w")])
    _first, new, _next = ERROR_LOG.entries_since(start)
    logged = [m for m, _c, _s in new if m.startswith("duplicate key")]
    return _folded(_entries(out)), logged


def test_id_join_through_a_duplicate_match_and_back():
    ERROR_LOG.clear()
    node = _id_join(Join)
    L, R1, R2 = 7, 100, 101
    state: dict = {}

    def step(t, left, right):
        out, logged = _tick(node, t, left, right)
        for ident, diff in out.items():
            state[ident] = state.get(ident, 0) + diff
            if not state[ident]:
                del state[ident]
        return out, logged

    hashed0 = FUSION_STATS["consolidation_rows_hashed_total"]
    # the left row alone: its pad, nothing hashed (an id seen once)
    out, logged = step(0, [(L, 1, "ten", 1)], [])
    assert out == {(L, ("ten", None)): 1} and not logged
    assert FUSION_STATS["consolidation_rows_hashed_total"] == hashed0
    assert node._idstate[L] == {Join._UNHASHED: [("ten", None), 1]}
    # a first match: pad out, match in — two candidates under one id
    out, logged = step(1, [], [(R1, 1, 100, 1)])
    assert out == {(L, ("ten", None)): -1, (L, ("ten", 100)): 1} and not logged
    assert Join._UNHASHED not in node._idstate[L]
    # a second match: ONE Error row and ONE log entry
    out, logged = step(2, [], [(R2, 1, 200, 1)])
    assert out == {(L, ("ten", 100)): -1, (L, ("ten", "ERR")): 1}
    assert logged == [f"duplicate key: {K.fmt_key(L)}"]
    assert state == {(L, ("ten", "ERR")): 1}
    # a third changes nothing and logs nothing more
    out, logged = step(3, [], [(102, 1, 300, 1)])
    assert out == {} and not logged
    out, logged = step(4, [], [(102, 1, 300, -1)])
    assert out == {} and not logged
    # back to one match: the row recovers
    out, logged = step(5, [], [(R2, 1, 200, -1)])
    assert out == {(L, ("ten", "ERR")): -1, (L, ("ten", 100)): 1} and not logged
    assert state == {(L, ("ten", 100)): 1}
    # and the left row leaves: a lone retraction of the id's one row
    out, logged = step(6, [(L, 1, "ten", -1)], [])
    assert out == {(L, ("ten", 100)): -1} and not logged
    assert state == {} and node._idstate == {}


_JOIN_SHAPES = [("left", "left"), ("inner", "left"), ("right", "right"), ("outer", "left")]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mode,key_mode", _JOIN_SHAPES)
def test_id_join_equals_full_hash_reference(mode, key_mode, seed):
    ERROR_LOG.clear()
    rng = np.random.default_rng([seed, len(mode)])
    new, ref = _id_join(Join, mode, key_mode), _id_join(_ReferenceJoin, mode, key_mode)
    live = [{}, {}]  # per side: row_key -> (jk, value)
    next_key = [1, 1000]
    id_side = 0 if key_mode == "left" else 1

    def value(side):
        if side == 0:
            return _cell(rng, "reply") if rng.random() < 0.5 else _cell(rng, "text")
        return _cell(rng, "int")

    def side_tick(side):
        entries = []
        for _ in range(int(rng.integers(0, 4))):
            action = rng.random()
            if action < 0.5 or not live[side]:
                key = next_key[side]
                next_key[side] += 1
                live[side][key] = (int(rng.integers(1, 4)), value(side))
                entries.append((key, *live[side][key], 1))
            else:
                key = list(live[side])[int(rng.integers(len(live[side])))]
                if any(e[0] == key for e in entries):
                    continue
                jk, old = live[side].pop(key)
                entries.append((key, jk, old, -1))
                # an update under the same row key, on the other side only:
                # while an id shows its Error row, a changed id-side row
                # does not reach the output (then as now)
                if action < 0.75 and side != id_side:
                    live[side][key] = (jk, value(side))
                    entries.append((key, *live[side][key], 1))
        return entries

    state_new: dict = {}
    state_ref: dict = {}
    for t in range(60):
        left, right = side_tick(0), side_tick(1)
        out_new, logged_new = _tick(new, t, left, right)
        out_ref, logged_ref = _tick(ref, t, left, right)
        assert out_new == out_ref, t
        assert sorted(logged_new) == sorted(logged_ref), t
        for state, out in ((state_new, out_new), (state_ref, out_ref)):
            for ident, diff in out.items():
                state[ident] = state.get(ident, 0) + diff
                if not state[ident]:
                    del state[ident]
        # an id-keyed join shows at most one row per id, whatever matched
        assert all(c == 1 for c in state_new.values())
        assert len({k for k, _ in state_new}) == len(state_new)
    assert state_new == state_ref
    assert set(new._idstate) == set(ref._idstate)


def test_id_join_duplicate_and_recovery_through_the_api():
    from pathway_tpu.debug import table_from_markdown as T
    from pathway_tpu.internals.graph_runner import GraphRunner

    ERROR_LOG.clear()
    left = T(
        """
        k | v  | __time__ | __diff__
        1 | 10 | 2        | 1
        2 | 20 | 2        | 1
        """
    )
    right = T(
        """
        k | w   | __time__ | __diff__
        1 | 100 | 4        | 1
        1 | 200 | 6        | 1
        2 | 900 | 6        | 1
        1 | 200 | 8        | -1
        """
    )
    j = left.join_left(right, left.k == right.k, id=pw.left.id).select(
        pw.left.v, w=pw.fill_error(pw.right.w, -1)
    )
    log = pw.global_error_log().select(pw.this.message)
    caps = GraphRunner().run_tables(j, log)
    assert sorted(r for _, r in caps[0].state.iter_items()) == [(10, 100), (20, 900)]
    msgs = [r[0] for _, r in caps[1].state.iter_items()]
    assert len([m for m in msgs if m.startswith("duplicate key")]) == 1
