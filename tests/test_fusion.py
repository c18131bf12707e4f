"""Fused-vs-unfused parity matrix + fusion unit gates (PR 14 tentpole).

Every pipeline shape the fusion pass touches — linear select/filter
chains, groupby reducer preambles with content-key reuse, joins with
absorbed pre-join projection, error-row UDFs, None/mixed-dtype batches,
persisted and sharded runs — must produce results identical to the
``PATHWAY_FUSION=0`` per-node escape hatch: same rows, same DIFF
multiset, and the same engine keys bit-for-bit (pointers are
user-visible). Row-error semantics (per-row ``EngineError`` values and
error-log entries) must match exactly; any batch that cannot be proven
safe falls back to the per-node path (counted, asserted here).

Decline-reason coverage (the ``fusion_reasons`` check_all gate keys on
these constants): REASON_DISABLED, REASON_MIXED_ERROR_SCOPES.
"""

from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import fusion
from pathway_tpu.engine import keys as K
from pathway_tpu.engine import operators as ops
from pathway_tpu.engine.delta import Delta
from pathway_tpu.engine.fusion import (
    FUSION_STATS,
    REASON_DISABLED,
    REASON_MIXED_ERROR_SCOPES,
    FusedChain,
    plan_chains,
)
from pathway_tpu.internals.parse_graph import G


@pytest.fixture(autouse=True)
def _clean_graph():
    G.clear()
    yield
    G.clear()


# ---------------------------------------------------------------------------
# harness: run one pipeline under both arms, capture every sink batch
# ---------------------------------------------------------------------------


def _collect(build, monkeypatch, fused: bool, threads: int | None = None):
    """Run ``build(sink)`` and return (entries, netted) where entries is
    the multiset of (key, row, diff) the sink saw and netted applies the
    diffs (the user-visible final state)."""
    monkeypatch.setenv("PATHWAY_FUSION", "1" if fused else "0")
    if threads is not None:
        monkeypatch.setenv("PATHWAY_THREADS", str(threads))
    G.clear()
    entries: list[tuple] = []

    def on_batch(time, b):
        cols = [b.data[c] for c in b.columns]
        for i in range(len(b.keys)):
            row = tuple(repr(c[i]) for c in cols)
            entries.append((int(b.keys[i]), row, int(b.diffs[i])))

    build(lambda table: pw.io.subscribe(table, on_batch=on_batch))
    pw.run()
    G.clear()
    if threads is not None:
        monkeypatch.delenv("PATHWAY_THREADS")
    netted: Counter = Counter()
    for key, row, diff in entries:
        netted[(key, row)] += diff
    return Counter(entries), +netted


def _assert_parity(build, monkeypatch, threads=None, exact_entries=True):
    fused_entries, fused_net = _collect(build, monkeypatch, True, threads)
    unfused_entries, unfused_net = _collect(build, monkeypatch, False, threads)
    # the final netted state (rows × multiplicity, keys included) is the
    # hard contract — identical bit-for-bit
    assert fused_net == unfused_net
    if exact_entries:
        # stateless chains additionally keep the exact per-batch entry
        # multiset (batch-internal order/diff-splitting is unspecified
        # only where consolidation identity legitimately applies)
        assert fused_entries == unfused_entries
    return fused_net


def _stream(column_batches, schema):
    """A python connector replaying the given per-commit column dicts."""

    class Feed(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for batch in column_batches:
                self.next_batch({k: list(v) for k, v in batch.items()})
                self.commit()

    return pw.io.python.read(Feed(), schema=schema, autocommit_duration_ms=None)


# ---------------------------------------------------------------------------
# chain parity
# ---------------------------------------------------------------------------


def test_chain_select_filter_select_parity(monkeypatch):
    before = FUSION_STATS["chains_total"]

    def build(sink):
        t = _stream(
            [{"a": list(range(s, s + 500))} for s in range(0, 5000, 500)],
            pw.schema_from_types(a=int),
        )
        out = (
            t.select(b=pw.this.a * 2, a=pw.this.a)
            .filter(pw.this.b % 3 != 0)
            .select(c=pw.this.b + pw.this.a)
        )
        sink(out)

    _assert_parity(build, monkeypatch)
    assert FUSION_STATS["chains_total"] > before


def test_chain_multiple_filters_mask_deferral_parity(monkeypatch):
    def build(sink):
        t = _stream(
            [{"a": list(range(2000))}], pw.schema_from_types(a=int)
        )
        out = (
            t.filter(pw.this.a % 2 == 0)
            .select(b=pw.this.a + 1, a=pw.this.a)
            .filter(pw.this.b % 5 != 0)
            .select(c=pw.this.b * 3 - pw.this.a)
        )
        sink(out)

    net = _assert_parity(build, monkeypatch)
    assert len(net) == 800  # 1000 evens minus the b%5==0 fifth


def test_chain_none_and_mixed_dtype_batches_parity(monkeypatch):
    def build(sink):
        t = _stream(
            [
                {"a": [1, 2, 3]},
                {"a": [None, 4, None]},          # None-carrying batch
                {"a": [5.5, 6, 7]},              # dtype flip mid-stream
            ],
            pw.schema_from_types(a=float),
        )
        out = t.select(
            b=pw.apply_with_type(
                lambda x: None if x is None else x * 2.0,
                float, pw.this.a,
            )
        ).filter(pw.this.b.is_not_none()).select(c=pw.this.b + 0.5)
        sink(out)

    _assert_parity(build, monkeypatch)


def test_chain_error_rows_exact_semantics(monkeypatch):
    """Division errors flow as per-row EngineError values; the filter
    predicate over them carries Errors. The fused path must drop those
    rows with EXACTLY the per-node error-log entries — each error
    created and logged ONCE (no re-evaluation on the handling path)."""
    from pathway_tpu.engine.error import ERROR_LOG

    def build(sink):
        t = _stream(
            [{"a": [2, 0, 4, 0, 8]}], pw.schema_from_types(a=int)
        )
        out = t.select(b=100 // pw.this.a, a=pw.this.a).filter(
            pw.this.b > 20
        ).select(c=pw.this.b + pw.this.a)
        sink(out)

    def log_count():
        try:
            return len(ERROR_LOG.entries_since(0)[0])
        except Exception:
            return None

    l0 = log_count()
    fused_entries, fused_net = _collect(build, monkeypatch, True)
    l1 = log_count()
    unfused_entries, unfused_net = _collect(build, monkeypatch, False)
    l2 = log_count()
    assert fused_net == unfused_net
    assert fused_entries == unfused_entries
    if l0 is not None:
        # identical number of error-log entries on both arms: 2 row
        # errors (division by zero) + 2 filter skips per run
        assert (l1 - l0) == (l2 - l1)


def test_raising_member_falls_back_and_resumes(monkeypatch):
    """A batch-wide raise inside a fused kernel re-runs through the
    per-node path — resuming FROM the failing member, so completed
    members' kernels (and their error logs) never fire twice."""
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    calls = {"first": 0, "boom": 0}

    src = _mk_source()

    def first_kernel(cols, keys):
        calls["first"] += 1
        return cols["a"] * 2

    def flaky_kernel(cols, keys):
        calls["boom"] += 1
        if calls["boom"] == 1:
            raise RuntimeError("transient")
        return cols["b"] + 1

    r1 = ops.Rowwise(src, {"b": first_kernel})
    r2 = ops.Rowwise(r1, {"c": flaky_kernel})
    chain = FusedChain([r1, r2])
    before = FUSION_STATS["fallbacks_total"]
    d = Delta(keys=np.arange(4, dtype=np.uint64), data={"a": np.arange(4)})
    out = chain.process(0, [d])
    assert FUSION_STATS["fallbacks_total"] == before + 1
    assert list(out.data["c"]) == [1, 3, 5, 7]
    assert calls["first"] == 1  # completed member NOT re-run
    assert calls["boom"] == 2   # failing member resumed per-node


# ---------------------------------------------------------------------------
# groupby preamble + content-key reuse
# ---------------------------------------------------------------------------


def test_wordcount_parity_with_key_reuse(monkeypatch):
    before = FUSION_STATS["key_reuse_total"]

    def build(sink):
        t = _stream(
            [
                {"word": [f"w{i % 37}" for i in range(s, s + 400)]}
                for s in range(0, 4000, 400)
            ],
            pw.schema_from_types(word=str),
        )
        counts = t.groupby(pw.this.word).reduce(
            pw.this.word, c=pw.reducers.count()
        )
        sink(counts)

    # groupby emits retract/insert waves whose batch-splitting is
    # identical either way, but only the netted state is the contract
    _assert_parity(build, monkeypatch, exact_entries=False)
    assert FUSION_STATS["key_reuse_total"] > before


def test_groupby_sum_reducer_preamble_parity(monkeypatch):
    def build(sink):
        t = _stream(
            [{"k": [i % 7 for i in range(1000)],
              "v": list(range(1000))}],
            pw.schema_from_types(k=int, v=int),
        )
        sink(t.groupby(pw.this.k).reduce(
            pw.this.k, s=pw.reducers.sum(pw.this.v),
            n=pw.reducers.count(),
        ))

    _assert_parity(build, monkeypatch, exact_entries=False)


def test_key_reuse_requires_content_provenance():
    """Deltas without content provenance (replace_data, mixed concat)
    must not claim it — the reuse fast path keys on it."""
    d = Delta(keys=np.arange(3, dtype=np.uint64),
              data={"a": np.arange(3)})
    d.keys_content_cols = ("a",)
    assert d.take(np.array([0, 2])).keys_content_cols == ("a",)
    assert d.replace_data({"a": np.arange(3)}).keys_content_cols is None
    from pathway_tpu.engine.delta import concat_deltas

    d2 = Delta(keys=np.arange(3, 6, dtype=np.uint64),
               data={"a": np.arange(3)})
    assert concat_deltas([d, d2], ["a"]).keys_content_cols is None
    d2.keys_content_cols = ("a",)
    assert concat_deltas([d, d2], ["a"]).keys_content_cols == ("a",)


def test_explicit_key_rows_have_no_provenance():
    """The row-ingest path must not stamp provenance on batches carrying
    explicit engine keys (rest_connector plumbing) — their keys are NOT
    a fold of the content columns."""
    from pathway_tpu.io.python import PythonSubjectSource

    class _Subj:
        pass

    src = PythonSubjectSource.__new__(PythonSubjectSource)
    src.names = ["a"]
    src.defaults = {}
    src.pk_indices = None
    src._float_cols = set()
    src._emitted = 0
    plain = src._make_delta([{"a": 1}, {"a": 2}], True)
    assert plain.keys_content_cols == ("a",)
    explicit = src._make_delta(
        [{"a": 1}, (1, {"a": 2}, 12345)], False
    )
    assert explicit.keys_content_cols is None
    assert int(explicit.keys[1]) == 12345


# ---------------------------------------------------------------------------
# join preamble + arrangement fast paths
# ---------------------------------------------------------------------------


def _join_pipeline(sink, mode="inner"):
    import pandas as pd

    right = pw.debug.table_from_pandas(
        pd.DataFrame({"rid": list(range(50)), "g": [i % 5 for i in range(50)]})
    )
    rng = np.random.default_rng(3)
    hi = 50 if mode == "inner" else 70
    fids = rng.integers(0, hi, 2000).tolist()
    facts = _stream(
        [{"fid": fids[s:s + 400]} for s in range(0, 2000, 400)],
        pw.schema_from_types(fid=int),
    )
    join_fn = facts.join if mode == "inner" else facts.join_left
    joined = join_fn(right, facts.fid == right.rid).select(g=right.g)
    agg = joined.groupby(pw.this.g).reduce(
        pw.this.g, c=pw.reducers.count()
    )
    sink(agg)


def test_join_groupby_parity(monkeypatch):
    _assert_parity(
        lambda sink: _join_pipeline(sink), monkeypatch, exact_entries=False
    )


def test_outer_join_groupby_parity(monkeypatch):
    _assert_parity(
        lambda sink: _join_pipeline(sink, mode="left"),
        monkeypatch, exact_entries=False,
    )


def test_sorted_side_deferred_maintenance_parity(monkeypatch):
    """Deferred sort/merge (fusion lane) must read back identically to
    the eager arrangement, including across a pickle snapshot."""
    import pickle

    def feed(side):
        rng = np.random.default_rng(0)
        for s in range(0, 3000, 500):
            jks = rng.integers(0, 200, 500).astype(np.uint64)
            keys = np.arange(s, s + 500, dtype=np.uint64)
            side.apply(jks, keys, [np.arange(s, s + 500)],
                       np.ones(500, dtype=np.int64))

    monkeypatch.setenv("PATHWAY_FUSION", "1")
    lazy = ops._SortedSide(1)
    feed(lazy)
    assert lazy._pending  # really deferred
    assert len(lazy) == 3000
    monkeypatch.setenv("PATHWAY_FUSION", "0")
    eager = ops._SortedSide(1)
    feed(eager)
    q = np.arange(0, 250, dtype=np.uint64)
    monkeypatch.setenv("PATHWAY_FUSION", "1")

    def harvest(side):
        out = []
        for qi, keys, cols, counts in side.probe(q):
            out.extend(zip(qi.tolist(), keys.tolist(), counts.tolist()))
        return sorted(out)

    assert harvest(lazy) == harvest(eager)
    assert np.array_equal(lazy.totals(q), eager.totals(q))
    # snapshot sees the arranged representation
    lazy2 = ops._SortedSide(1)
    feed(lazy2)
    restored = pickle.loads(pickle.dumps(lazy2))
    assert harvest(restored) == harvest(eager)


def test_hash_range_index_matches_searchsorted(monkeypatch):
    monkeypatch.setenv("PATHWAY_FUSION", "1")  # the index is fusion's
    side = ops._SortedSide(1)
    rng = np.random.default_rng(1)
    n = 8192
    jks = rng.integers(0, 500, n).astype(np.uint64)
    side._apply_now(jks, np.arange(n, dtype=np.uint64),
                    [np.arange(n)], np.ones(n, dtype=np.int64))
    run = side._runs[0]
    q = rng.integers(0, 700, 3000).astype(np.uint64)  # misses included
    lo0 = np.searchsorted(run[0], q, "left")
    hi0 = np.searchsorted(run[0], q, "right")
    # two probes with distinct query arrays arm + build the index
    side._ranges(run, q.copy())
    lo1, hi1 = side._ranges(run, q.copy())
    ent = side._jk_hash_idx[id(run[0])]
    assert ent[2] is not None  # hash index really built
    # match ranges agree; misses are empty either way (searchsorted
    # reports lo==hi at the insertion point, the index reports 0,0)
    assert np.array_equal(hi0 - lo0, hi1 - lo1)
    hits = hi0 > lo0
    assert np.array_equal(lo0[hits], lo1[hits])
    assert np.array_equal(hi0[hits], hi1[hits])
    assert ((hi1 == lo1) | hits).all()


# ---------------------------------------------------------------------------
# consolidation identity fast path
# ---------------------------------------------------------------------------


def test_consolidated_identity_unique_insertions(monkeypatch):
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    before = FUSION_STATS["consolidation_skips_total"]
    d = Delta(keys=np.arange(100, dtype=np.uint64),
              data={"a": np.arange(100)})
    assert d.consolidated() is d
    assert FUSION_STATS["consolidation_skips_total"] > before


def test_consolidated_duplicates_still_merge(monkeypatch):
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    keys = np.array([7, 7, 9], dtype=np.uint64)
    d = Delta(keys=keys, data={"a": np.array([1, 1, 2])})
    out = d.consolidated()
    assert out is not d and len(out) == 2
    assert sorted(out.diffs.tolist()) == [1, 2]
    # multiset_ok (engine-internal edge) may keep duplicates unmerged
    d2 = Delta(keys=keys.copy(), data={"a": np.array([1, 1, 2])})
    assert d2.consolidated(multiset_ok=True) is d2


def test_consolidated_retractions_always_cancel(monkeypatch):
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    d = Delta(
        keys=np.array([1, 1], dtype=np.uint64),
        data={"a": np.array([5, 5])},
        diffs=np.array([1, -1], dtype=np.int64),
    )
    assert len(d.consolidated()) == 0
    assert len(d.consolidated(multiset_ok=True)) == 0


def test_all_unique_native_and_fallback():
    rng = np.random.default_rng(2)
    uniq = rng.permutation(np.arange(10_000)).astype(np.uint64)
    assert K.all_unique(uniq)
    dup = uniq.copy()
    dup[-1] = dup[0]
    assert not K.all_unique(dup)
    assert K.all_unique(np.array([0, 1], dtype=np.uint64))
    assert not K.all_unique(np.array([0, 1, 0], dtype=np.uint64))


# ---------------------------------------------------------------------------
# persisted + sharded runs
# ---------------------------------------------------------------------------


def test_persisted_fused_state_restores_under_unfused(tmp_path, monkeypatch):
    """State written by a fused run must restore bit-identically under
    the escape hatch (and vice versa): key reuse is value-identical, so
    snapshots and ack floors carry across the knob."""
    import os as _os

    from pathway_tpu.persistence import Backend, Config

    pdir = tmp_path / "pstate"

    def run(words, fused):
        monkeypatch.setenv("PATHWAY_FUSION", "1" if fused else "0")
        G.clear()
        cfg = Config.simple_config(Backend.filesystem(_os.fspath(pdir)))

        class Feed(pw.io.python.ConnectorSubject):
            def run(self) -> None:
                for w in words:
                    self.next(word=w)
                self.commit()

        t = pw.io.python.read(
            Feed(), schema=pw.schema_from_types(word=str), name="w",
            autocommit_duration_ms=None,
        )
        counts = t.groupby(pw.this.word).reduce(
            pw.this.word, c=pw.reducers.count()
        )
        seen: dict = {}

        def on_change(key, row, time, is_addition):
            if is_addition:
                seen[int(key)] = (row["word"], int(row["c"]))

        pw.io.subscribe(counts, on_change=on_change)
        pw.run(persistence_config=cfg)
        G.clear()
        return seen

    first = run(["a", "b", "a", "c"], fused=True)
    assert {v for v in first.values()} == {("a", 2), ("b", 1), ("c", 1)}
    # restart UNFUSED from the fused snapshot, with more rows appended
    second = run(["a", "b", "a", "c", "b", "d"], fused=False)
    assert {v for v in second.values()} == {("b", 2), ("d", 1)}
    # group keys agree across the knob: 'b' updated under the SAME key
    b_key_first = [k for k, v in first.items() if v[0] == "b"]
    b_key_second = [k for k, v in second.items() if v[0] == "b"]
    assert b_key_first == b_key_second


@pytest.mark.slow
def test_sharded_wordcount_parity(monkeypatch):
    def build(sink):
        t = _stream(
            [
                {"word": [f"w{i % 23}" for i in range(s, s + 300)]}
                for s in range(0, 1800, 300)
            ],
            pw.schema_from_types(word=str),
        )
        sink(t.groupby(pw.this.word).reduce(
            pw.this.word, c=pw.reducers.count()
        ))

    _assert_parity(build, monkeypatch, threads=2, exact_entries=False)


# ---------------------------------------------------------------------------
# planning, decline reasons, attribution, jit tier, cache eviction
# ---------------------------------------------------------------------------


def _mk_rowwise(inp, name="b"):
    return ops.Rowwise(inp, {name: lambda cols, keys: cols["a"] * 2})


def _mk_source():
    return ops.StaticSource(
        np.arange(4, dtype=np.uint64), {"a": np.arange(4)}
    )


def test_plan_declines_when_disabled(monkeypatch):
    monkeypatch.setenv("PATHWAY_FUSION", "0")
    src = _mk_source()
    r1 = _mk_rowwise(src)
    r2 = ops.Rowwise(r1, {"c": lambda cols, keys: cols["b"] + 1})
    cap = ops.Capture(r2)
    plans = plan_chains([src, r1, r2, cap])
    assert len(plans) == 1 and not plans[0].fused
    assert plans[0].reason == REASON_DISABLED
    # the executor honours the plan: no FusedChain in the built graph
    from pathway_tpu.engine.executor import Executor

    ex = Executor([src, r1, r2, cap])
    assert not any(isinstance(n, FusedChain) for n in ex.nodes)


def test_plan_declines_mixed_error_scopes(monkeypatch):
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    src = _mk_source()
    r1 = _mk_rowwise(src)
    r2 = ops.Rowwise(r1, {"c": lambda cols, keys: cols["b"] + 1})
    r1.error_scope = 1
    r2.error_scope = 2
    cap = ops.Capture(r2)
    plans = plan_chains([src, r1, r2, cap])
    assert len(plans) == 1 and not plans[0].fused
    assert plans[0].reason == REASON_MIXED_ERROR_SCOPES


def test_lint_surfaces_decline_reason_verbatim(monkeypatch):
    """The fusion-chain diagnostic cross-checks the compiler's actual
    decisions: declined chains carry the verbatim reason at warning
    severity, fused chains downgrade to info."""
    from pathway_tpu.testing import T

    def program():
        t = T("a\n1\n2\n3")
        res = (
            t.select(b=pw.this.a * 2)
            .filter(pw.this.b > 2)
            .select(c=pw.this.b + 1)
        )
        pw.io.subscribe(res, on_change=lambda **kw: None)
        return pw.analyze().by_id("fusion-chain")

    monkeypatch.setenv("PATHWAY_FUSION", "1")
    fused = program()
    assert fused and all(d.severity == "info" for d in fused)
    assert any("fuses into one compiled kernel" in d.message for d in fused)
    G.clear()
    monkeypatch.setenv("PATHWAY_FUSION", "0")
    declined = program()
    assert declined and all(d.severity == "warning" for d in declined)
    assert any(REASON_DISABLED in d.message for d in declined)


def test_attribution_names_member_inside_chain():
    """Per-chain cost splits re-derive per-operator attribution: the
    slow member's label (not the FusedChain label) carries the time."""
    import time as _t

    from pathway_tpu.engine.executor import EngineStats

    src = _mk_source()
    fast = _mk_rowwise(src)

    def slow_kernel(cols, keys):
        _t.sleep(0.01)
        return cols["b"] + 1

    slow = ops.Rowwise(fast, {"c": slow_kernel})
    chain = FusedChain([fast, slow])
    stats = EngineStats()
    stats.detailed = True
    chain._engine_stats = stats
    d = Delta(keys=np.arange(4, dtype=np.uint64), data={"a": np.arange(4)})
    out = chain.process(0, [d])
    assert out is not None and list(out.data["c"]) == [1, 3, 5, 7]
    slow_label = f"Rowwise#{slow.node_id}"
    fast_label = f"Rowwise#{fast.node_id}"
    assert stats.time_by_node[slow_label] > stats.time_by_node[fast_label]
    assert f"FusedChain#{chain.node_id}" not in stats.time_by_node


def test_numeric_chain_select_filter_select_rows(monkeypatch):
    """A pure numeric select-filter-select chain fuses into one node and
    emits exactly the rows the expressions define."""
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    G.clear()
    before = FUSION_STATS["chains_total"]
    n = 64
    batches = [{"a": list(range(s, s + n))} for s in range(0, 5 * n, n)]
    got: list = []

    t = _stream(batches, pw.schema_from_types(a=int))
    # every kernel is total, so the filter's mask stays deferred across
    # the last select
    out = t.select(b=pw.this.a * 3 + 1, a=pw.this.a).filter(
        pw.this.b > 16
    ).select(c=pw.this.b - pw.this.a)
    pw.io.subscribe(out, on_batch=lambda tm, b: got.extend(
        zip(b.data["c"].tolist(), b.diffs.tolist())
    ))
    pw.run()
    G.clear()
    assert FUSION_STATS["chains_total"] > before
    want = sorted(
        (2 * a + 1, 1) for a in range(5 * n) if 3 * a + 1 > 16
    )
    assert sorted(got) == want


def test_filter_only_chain_carries_every_output_column(monkeypatch):
    """A chain with no Rowwise passes its input columns through: every
    output column must come out, also the ones no predicate reads."""
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    G.clear()
    n = 64
    batches = [
        {"a": list(range(s, s + n)), "b": list(range(s, s + n)),
         "c": list(range(s, s + n))}
        for s in range(0, 4 * n, n)
    ]
    got: list = []
    before = FUSION_STATS["chains_total"]
    t = _stream(batches, pw.schema_from_types(a=int, b=int, c=int))
    out = t.filter(pw.this.a > 1).filter(pw.this.b > 2)
    pw.io.subscribe(out, on_batch=lambda tm, bb: got.extend(
        zip(bb.data["a"].tolist(), bb.data["b"].tolist(),
            bb.data["c"].tolist())
    ))
    pw.run()
    G.clear()
    assert FUSION_STATS["chains_total"] > before
    assert sorted(got) == [(v, v, v) for v in range(3, 4 * n)]


def test_large_numeric_batches_leave_x64_off(monkeypatch):
    """Host expressions are numpy kernels at every batch size: a numeric
    chain that keeps seeing large batches must not change the precision
    mode the device programs of this process run in."""
    from pathway_tpu.utils import jaxcfg  # noqa: F401
    import jax

    monkeypatch.setenv("PATHWAY_FUSION", "1")
    G.clear()
    n, batches = 8192, 20
    kept = [0]
    t = _stream(
        [{"a": np.arange(s, s + n)} for s in range(0, batches * n, n)],
        pw.schema_from_types(a=int),
    )
    out = t.select(b=pw.this.a * 3 + 1).filter(pw.this.b > 16)

    def on_batch(tm, b):
        kept[0] += len(b.keys)

    pw.io.subscribe(out, on_batch=on_batch)
    pw.run()
    G.clear()
    assert kept[0] == batches * n - 6
    assert jax.config.jax_enable_x64 is False


def _wide_rows(n: int) -> dict[str, list]:
    """Operands whose results need all 64 bits: ints near 2**62 of both
    signs, factors near 2**31, floats that differ below float32's
    resolution."""
    i = list(range(n))
    return {
        "i": i,
        "a": [(1 if k % 2 else -1) * (2**62 - 1 - 1_000_003 * k) for k in i],
        "b": [(1 if k % 3 else -1) * (1 + k % 997) for k in i],
        "x": [2**31 - 1 - 7 * k for k in i],
        "f": [1.0 + k * 2.0**-40 for k in i],
        "g": [(1 if k % 5 else -1) * (0.1 + k * 2.0**-33) for k in i],
    }


#: op -> (int expression, its reference on Python ints; float expression,
#: its reference on Python floats, which are IEEE doubles)
_WIDE_CASES = {
    "+": (lambda t: t.a + t.b, lambda r: r["a"] + r["b"],
          lambda t: t.f + t.g, lambda r: r["f"] + r["g"]),
    "-": (lambda t: t.a - t.b, lambda r: r["a"] - r["b"],
          lambda t: t.f - t.g, lambda r: r["f"] - r["g"]),
    "*": (lambda t: t.x * (t.x - t.b), lambda r: r["x"] * (r["x"] - r["b"]),
          lambda t: t.f * t.g, lambda r: r["f"] * r["g"]),
    "//": (lambda t: t.a // t.b, lambda r: r["a"] // r["b"],
           lambda t: t.f // t.g, lambda r: r["f"] // r["g"]),
    "%": (lambda t: t.a % t.b, lambda r: r["a"] % r["b"],
          lambda t: t.f % t.g, lambda r: r["f"] % r["g"]),
    # int / int divides the operands as doubles
    "/": (lambda t: t.a // t.x, lambda r: r["a"] // r["x"],
          lambda t: t.a / t.b, lambda r: float(r["a"]) / float(r["b"])),
    "cast": (lambda t: pw.cast(int, t.f * 2.0**61),
             lambda r: int(r["f"] * 2.0**61),
             lambda t: pw.cast(float, t.a) + t.f,
             lambda r: float(r["a"]) + r["f"]),
}


@pytest.mark.parametrize("op", list(_WIDE_CASES))
def test_wide_values_agree_at_small_and_large_batches(op, monkeypatch):
    """64-bit semantics are the numpy kernel's own at every batch size:
    the same select-filter-select chain fed 8 rows and 8,192 rows a batch
    gives the rows Python's ints and doubles give, bit for bit."""
    int_expr, int_ref, float_expr, float_ref = _WIDE_CASES[op]
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    large, small, n_small = 8192, 8, 64
    rows = _wide_rows(large)
    schema = pw.schema_from_types(a=int, b=int, x=int, f=float, g=float, i=int)

    def run(batches) -> dict:
        G.clear()
        got: dict = {}
        t = _stream(batches, schema)
        out = t.select(
            i=pw.this.i, ri=int_expr(pw.this), rf=float_expr(pw.this)
        ).filter(pw.this.i > 2).select(
            i=pw.this.i, ri=pw.this.ri - pw.this.i, rf=pw.this.rf * 0.5
        )

        def on_batch(tm, b):
            assert b.data["ri"].dtype == np.int64
            assert b.data["rf"].dtype == np.float64
            for i, ri, rf in zip(*(b.data[c].tolist() for c in ("i", "ri", "rf"))):
                got[i] = (ri, rf)

        pw.io.subscribe(out, on_batch=on_batch)
        pw.run()
        G.clear()
        return got

    at_large = run([rows])
    at_small = run([
        {c: v[s:s + small] for c, v in rows.items()}
        for s in range(0, n_small, small)
    ])
    want = {}
    for k in range(3, large):
        r = {c: v[k] for c, v in rows.items()}
        want[k] = (int_ref(r) - k, float_ref(r) * 0.5)
    assert at_large == want
    assert at_small == {k: want[k] for k in range(3, n_small)}


def test_fusion_counters_render_on_metrics():
    from pathway_tpu.observability.prometheus import render_snapshots

    text = render_snapshots(
        [], fusion_stats={"0": fusion.fusion_stats_snapshot()}
    )
    for key in FUSION_STATS:
        assert f"pathway_fusion_{key}" in text
