"""groupby/reduce behavior — mirrors reference test_common.py reduce suites."""

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import operators as ops
from pathway_tpu.engine.delta import Delta
from pathway_tpu.engine.error import Error as EngineError, errors_seen
from pathway_tpu.engine.executor import Node
from pathway_tpu.engine.fusion import FUSION_STATS
from pathway_tpu.engine.reducers import (
    REDUCERS,
    _H,
    _MultisetReducer,
    StatefulReducer,
    make_reducer,
)
from pathway_tpu.internals import tracing
from pathway_tpu.internals.graph_runner import GraphRunner
from pathway_tpu.testing import (
    T,
    assert_table_equality_wo_index,
)


def _t():
    return T(
        """
        k | v
        a | 1
        a | 2
        b | 3
        b | 4
        b | 5
        """
    )


def test_count():
    res = _t().groupby(pw.this.k).reduce(pw.this.k, c=pw.reducers.count())
    expected = T(
        """
        k | c
        a | 2
        b | 3
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_sum_min_max():
    res = _t().groupby(pw.this.k).reduce(
        pw.this.k,
        s=pw.reducers.sum(pw.this.v),
        mn=pw.reducers.min(pw.this.v),
        mx=pw.reducers.max(pw.this.v),
    )
    expected = T(
        """
        k | s  | mn | mx
        a | 3  | 1  | 2
        b | 12 | 3  | 5
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_avg():
    res = _t().groupby(pw.this.k).reduce(pw.this.k, a=pw.reducers.avg(pw.this.v))
    expected = T(
        """
        k | a
        a | 1.5
        b | 4.0
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_reduce_expression_over_reducers():
    res = _t().groupby(pw.this.k).reduce(
        pw.this.k,
        r=pw.reducers.sum(pw.this.v) * 10 + pw.reducers.count(),
    )
    expected = T(
        """
        k | r
        a | 32
        b | 123
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_reducer_arg_expression():
    res = _t().groupby(pw.this.k).reduce(
        pw.this.k, s=pw.reducers.sum(pw.this.v * 2)
    )
    expected = T(
        """
        k | s
        a | 6
        b | 24
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_global_reduce():
    res = _t().reduce(s=pw.reducers.sum(pw.this.v), c=pw.reducers.count())
    expected = T(
        """
        s  | c
        15 | 5
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_sorted_tuple_and_tuple():
    res = _t().groupby(pw.this.k).reduce(
        pw.this.k, st=pw.reducers.sorted_tuple(pw.this.v)
    )
    got = pw.debug.table_to_dicts(res)[1]
    vals = sorted(tuple(v) for v in got["st"].values())
    assert vals == [(1, 2), (3, 4, 5)]


def test_unique_and_any():
    t = T(
        """
        k | u
        a | x
        a | x
        b | y
        """
    )
    res = t.groupby(pw.this.k).reduce(pw.this.k, u=pw.reducers.unique(pw.this.u))
    expected = T(
        """
        k | u
        a | x
        b | y
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_unique_raises_on_multiple():
    t = T(
        """
        k | u
        a | x
        a | y
        """
    )
    res = t.groupby(pw.this.k).reduce(pw.this.k, u=pw.reducers.unique(pw.this.u))
    with pytest.raises(ValueError, match="unique"):
        pw.debug.table_to_dicts(res)


def test_argmin_argmax():
    t = T(
        """
        id | k | v
        1  | a | 10
        2  | a | 5
        3  | b | 7
        """
    )
    res = t.groupby(pw.this.k).reduce(
        pw.this.k,
        lo=pw.reducers.argmin(pw.this.v),
        hi=pw.reducers.argmax(pw.this.v),
    )
    # argmin of group a is row id 2, argmax row id 1
    ids, cols = pw.debug.table_to_dicts(t)
    rids, rcols = pw.debug.table_to_dicts(res)
    by_k = {rcols["k"][k]: k for k in rids}
    id_by_v = {cols["v"][k]: k for k in ids}
    assert int(rcols["lo"][by_k["a"]]) == int(id_by_v[5])
    assert int(rcols["hi"][by_k["a"]]) == int(id_by_v[10])
    assert int(rcols["lo"][by_k["b"]]) == int(id_by_v[7])


def test_groupby_incremental_with_retractions():
    """Streamed input with deletions: final state reflects retraction-correct
    min/max/sum (the reference's differential reduce semantics)."""
    t = T(
        """
        k | v | __time__ | __diff__
        a | 1 | 2        | 1
        a | 2 | 2        | 1
        a | 3 | 4        | 1
        a | 3 | 6        | -1
        a | 1 | 8        | -1
        """
    )
    res = t.groupby(pw.this.k).reduce(
        pw.this.k,
        s=pw.reducers.sum(pw.this.v),
        mn=pw.reducers.min(pw.this.v),
        mx=pw.reducers.max(pw.this.v),
        c=pw.reducers.count(),
    )
    expected = T(
        """
        k | s | mn | mx | c
        a | 2 | 2  | 2  | 1
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_group_disappears_on_full_retraction():
    t = T(
        """
        k | v | __time__ | __diff__
        a | 1 | 2        | 1
        b | 2 | 2        | 1
        a | 1 | 4        | -1
        """
    )
    res = t.groupby(pw.this.k).reduce(pw.this.k, c=pw.reducers.count())
    expected = T(
        """
        k | c
        b | 1
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_groupby_multiple_keys():
    t = T(
        """
        a | b | v
        1 | x | 1
        1 | y | 2
        1 | x | 3
        2 | x | 4
        """
    )
    res = t.groupby(pw.this.a, pw.this.b).reduce(
        pw.this.a, pw.this.b, s=pw.reducers.sum(pw.this.v)
    )
    expected = T(
        """
        a | b | s
        1 | x | 4
        1 | y | 2
        2 | x | 4
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_earliest_latest():
    t = T(
        """
        k | v | __time__
        a | 1 | 2
        a | 2 | 4
        a | 3 | 6
        """
    )
    res = t.groupby(pw.this.k).reduce(
        pw.this.k,
        first=pw.reducers.earliest(pw.this.v),
        last=pw.reducers.latest(pw.this.v),
    )
    expected = T(
        """
        k | first | last
        a | 1     | 3
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_ndarray_reducer():
    import numpy as np

    res = _t().groupby(pw.this.k).reduce(
        pw.this.k, arr=pw.reducers.ndarray(pw.this.v)
    )
    _, cols = pw.debug.table_to_dicts(res)
    arrays = {sorted(a.tolist())[0]: a for a in cols["arr"].values()}
    assert sorted(arrays[1].tolist()) == [1, 2]
    assert sorted(arrays[3].tolist()) == [3, 4, 5]


def test_custom_stateful_reducer():
    def combine(state, values, diff):
        (v,) = values
        return (state or 0) + v * v * diff

    res = _t().groupby(pw.this.k).reduce(
        pw.this.k, ss=pw.reducers.stateful_single(combine, pw.this.v)
    )
    expected = T(
        """
        k | ss
        a | 5
        b | 50
        """
    )
    assert_table_equality_wo_index(res, expected)


def test_custom_accumulator():
    class SumAcc(pw.BaseCustomAccumulator):
        def __init__(self, s):
            self.s = s

        @classmethod
        def from_row(cls, row):
            return cls(row[0])

        def update(self, other):
            self.s += other.s

        def retract(self, other):
            self.s -= other.s

        def compute_result(self):
            return self.s

    sum_red = pw.reducers.udf_reducer(SumAcc)
    res = _t().groupby(pw.this.k).reduce(pw.this.k, s=sum_red(pw.this.v))
    expected = T(
        """
        k | s
        a | 3
        b | 12
        """
    )
    assert_table_equality_wo_index(res, expected)


# -- the general path's two loops (engine/operators.py `_update_general`) ----
#
# A batch is folded by column (`_fold_columns`: every multiset reducer gives
# its entries at once) unless the Error latch has tripped or it is one row
# (the row loop). Both write one state: what follows feeds the same seeded
# batches through either and asks for the same output, the same state to the
# type, the same order of touched groups, and that an entry one loop put in
# is taken out by the other.

MULTISET = sorted(n for n, c in REDUCERS.items() if issubclass(c, _MultisetReducer))
#: reducers whose `extract` compares entries: plain values of one type, and
#: the unhashable ones (`_H` orders itself against anything)
ORDERED = {"min", "max", "argmin", "argmax"}


class _Source(Node):
    def __init__(self):
        super().__init__([], ["g", "num", "obj", "rank"])


#: reducers whose result is a tuple of the values: `_rows_equal` compares
#: two such rows with ``!=``, which an array inside a tuple cannot answer
TUPLES = {"tuple", "sorted_tuple", "tuple_by"}


def _pool(name, rng, extracted=True):
    """Values of the ``object`` column: the unhashable kinds of a reply row
    (a dict, an ndarray, a tuple of dicts, None) among plain ones, less what
    the reducer's ``extract`` cannot take where the values are ``extracted``."""
    unhashable = [
        {"path": "d1", "ver": 0},
        {"ver": 0, "path": "d1"},  # the same dict, keys in another order
        {"path": "d2", "tags": ["a", {"b": 1}]},
        np.arange(3, dtype=np.float32),
        np.arange(3, dtype=np.int64),
        ({"path": "d1"}, {"path": "d2"}),
        [1, 2, 3],
    ]
    plain = [float(x) for x in rng.integers(0, 4, 3)]
    rest = [None, "text", (1, 2), b"raw", True]
    if not extracted:
        return unhashable + plain + rest
    if name == "ndarray":  # `extract` stacks the values: none that is a sequence
        return [v for v in unhashable if isinstance(v, dict)] + plain + [None]
    if name in TUPLES:
        unhashable = [v for v in unhashable if not isinstance(v, np.ndarray)]
    if name in ORDERED:
        return unhashable + plain
    if name == "sorted_tuple":  # ordered too, and it puts None last itself
        return unhashable + plain + [None]
    return unhashable + plain + rest


def _node(name):
    multiset = (
        (lambda: make_reducer(name, skip_nones=True))
        if name in ("tuple", "sorted_tuple", "ndarray")
        else (lambda: make_reducer(name))
    )
    args = (lambda col: ["rank", col]) if name == "tuple_by" else (lambda col: [col])
    # what a stateful reducer is handed, in the order it is handed it
    seen = StatefulReducer(
        lambda acc, vals, diff: (acc or ()) + ((float(vals[0]), diff),)
    )
    return ops.GroupByReduce(
        _Source(),
        ["g"],
        [
            ("n", make_reducer("count"), []),
            ("over_obj", multiset(), args("obj")),
            ("total", make_reducer("sum"), ["num"]),
            ("over_num", multiset(), args("num")),
            ("seen", seen, ["num"]),
            ("over_rank", multiset(), args("rank")),
        ],
    )


def _batches(name, seed):
    """Seeded batches of (row key, group, num, obj, rank, diff): insertions,
    retractions of rows that live, an entry twice in one batch, a row
    replaced within a batch; the last batch takes every live row back."""
    rng = np.random.default_rng(seed)
    pool = _pool(name, rng)

    def row(key, g):
        if name == "unique":  # one distinct value a group, or `extract` raises
            return (key, g, float(g), pool[g % len(pool)], float(g))
        return (key, g, float(rng.integers(0, 5)), pool[rng.integers(len(pool))],
                float(rng.normal()))

    live: list[tuple] = []
    out = []
    next_key = 1
    for b in range(6):
        batch = []
        for _ in range(int(rng.integers(4, 12))):
            r = row(next_key, int(rng.integers(0, 4)))
            next_key += 1
            batch.append(r + (1,))
            live.append(r)
        if b >= 1:
            for _ in range(int(rng.integers(1, 4))):  # retractions
                r = live.pop(int(rng.integers(len(live))))
                batch.append(r + (-1,))
            twice = live[int(rng.integers(len(live)))]  # an entry twice
            batch += [twice + (1,), twice + (1,)]
            live += [twice, twice]
            old = live.pop(int(rng.integers(len(live))))  # a row replaced
            new = row(old[0], old[1])
            batch += [old + (-1,), new + (1,)]
            live.append(new)
        if b == 3:  # a batch of one row, which takes the row loop either way
            out.append(batch)
            r = row(next_key, 0)
            next_key += 1
            batch = [r + (1,)]
            live.append(r)
        out.append(batch)
    out.append([r + (-1,) for r in live])
    return out


def _delta(batch):
    obj = np.empty(len(batch), dtype=object)
    for i, r in enumerate(batch):
        obj[i] = r[3]
    return Delta(
        keys=np.array([r[0] for r in batch], dtype=np.uint64),
        data={
            "g": np.array([r[1] for r in batch], dtype=np.int64),
            "num": np.array([r[2] for r in batch], dtype=np.float64),
            "obj": obj,
            "rank": np.array([r[4] for r in batch], dtype=np.float64),
        },
        diffs=np.array([r[5] for r in batch], dtype=np.int64),
    )


def _typed(v):
    """A value with the type of everything in it, so that ``==`` on the
    result is equality to the bit and to the type."""
    if isinstance(v, _H):
        return ("_H", _typed(v.k), _typed(v.v))
    if isinstance(v, np.ndarray):
        if v.dtype == object:
            return ("ndarray", "object", [_typed(x) for x in v.tolist()])
        return ("ndarray", str(v.dtype), v.shape, v.tobytes())
    if isinstance(v, np.generic):
        return (type(v).__name__, v.tobytes())
    if isinstance(v, dict):
        return ("dict", [(_typed(k), _typed(x)) for k, x in v.items()])
    if isinstance(v, (list, tuple)):
        return (type(v).__name__, [_typed(x) for x in v])
    if isinstance(v, float):
        return ("float", repr(v))
    return (type(v).__name__, v)


def _typed_delta(d):
    if d is None:
        return None
    return (_typed(d.keys), _typed(d.diffs),
            [(c, _typed(np.asarray(d.data[c]))) for c in d.data])


def _run(name, seed, loop_of_batch, monkeypatch):
    """Feed the batches through one node, batch ``b`` by the loop
    ``loop_of_batch(b)`` says; (outputs, states, touched groups) a batch."""
    node = _node(name)
    touched = []
    emit = node._emit_general
    node._emit_general = lambda affected: (touched.append(list(affected)), emit(affected))[1]
    outs, states = [], []
    for b, batch in enumerate(_batches(name, seed)):
        by_row = loop_of_batch(b) == "row"
        monkeypatch.setattr(ops, "errors_seen", lambda by_row=by_row: by_row)
        # `earliest` and `latest` keep the time in the entry: a row leaves at
        # the time it came
        time = 2 if name in ("earliest", "latest") else 2 * (b + 1)
        outs.append(_typed_delta(node.process(time, [_delta(batch)])))
        states.append(_typed(node._state))
    return node, outs, states, touched


@pytest.mark.parametrize("name", MULTISET)
def test_the_column_loop_and_the_row_loop_write_the_same_state(name, monkeypatch):
    assert set(MULTISET) >= {
        "tuple_by", "tuple", "sorted_tuple", "min", "max", "argmin", "argmax",
        "unique", "any", "ndarray", "earliest", "latest",
    }
    for seed in (3, 2_147_483_659):
        by_row = _run(name, seed, lambda b: "row", monkeypatch)
        by_column = _run(name, seed, lambda b: "column", monkeypatch)
        # one loop fills a group and the other empties it, both ways round
        mixed = _run(name, seed, lambda b: ("row", "column")[b % 2], monkeypatch)
        mixed_too = _run(name, seed, lambda b: ("column", "row")[b % 2], monkeypatch)
        for node, outs, states, touched in (by_column, mixed, mixed_too):
            assert outs == by_row[1]
            assert states == by_row[2]
            assert touched == by_row[3]
            # every row was taken back: no group, no entry is left
            assert node._state == {} and states[-2] != ("dict", [])
    # the entries themselves: equal, hash-equal, of the same types
    red = make_reducer(name)
    rng = np.random.default_rng(5)
    pool = _pool(name, rng, extracted=False)
    obj = np.empty(len(pool), dtype=object)
    for i, v in enumerate(pool):
        obj[i] = v
    num = rng.normal(size=len(pool))
    keys = list(range(10, 10 + len(pool)))
    for cols in ([num, obj], [obj, num], [num, num.astype(np.float32)],
                 [np.arange(len(pool)), np.arange(len(pool), dtype=np.uint64)]):
        if name != "tuple_by":
            cols = cols[1:]
        column = red._entries(cols, keys, 4, {})
        rows = [red._entry(tuple(c[i] for c in cols), keys[i], 4)
                for i in range(len(pool))]
        assert _typed(column) == _typed(rows)
        assert [hash(e) for e in column] == [hash(e) for e in rows]
        assert all(e in {r: None for r in rows} for e in column)


def test_the_counters_and_the_span_say_which_loop_ran(tmp_path, monkeypatch):
    node = _node("tuple_by")
    first = _batches("tuple_by", 3)[0]
    one_row, rest = first[:1], first[1:]
    n_reducers, n_multiset = 6, 3
    counted = lambda: (FUSION_STATS["groupby_rows_total"],  # noqa: E731
                       FUSION_STATS["groupby_rows_by_column_total"])
    tracer = tracing.activate(str(tmp_path / "run.json"))
    try:
        with monkeypatch.context() as m:
            m.setattr(ops, "errors_seen", lambda: False)
            rows0, by_column0 = counted()
            node.process(2, [_delta(rest)])
            assert counted() == (rows0 + len(rest) * n_reducers,
                                 by_column0 + len(rest) * n_multiset)
            # one row is folded as a row
            node.process(4, [_delta(one_row)])
            assert counted() == (rows0 + len(first) * n_reducers,
                                 by_column0 + len(rest) * n_multiset)
        filled = _typed(node._state)
        # the latch itself: while an Error lives the batch takes the row loop,
        # and takes out what the column loop put in
        held = EngineError.silent("somewhere else in the process")
        assert errors_seen()
        node.process(6, [_delta([r[:5] + (-1,) for r in first])])
        assert counted() == (rows0 + 2 * len(first) * n_reducers,
                             by_column0 + len(rest) * n_multiset)
        assert node._state == {} and filled != ("dict", [])
        del held
    finally:
        tracing.deactivate()
    updates = [e["args"] for e in tracer._events if e.get("name") == "groupby.update"]
    assert [(a["path"], a["loop"], a["rows"]) for a in updates] == [
        ("general", "column", len(rest)),
        ("general", "row", 1),
        ("general", "row", len(first)),
    ]
    assert all(a["reducers"] == n_reducers and a["groups"] >= 1 for a in updates)
    # the dense path has no loop to name
    dense = ops.GroupByReduce(
        _Source(), ["g"], [("n", make_reducer("count"), []),
                           ("total", make_reducer("sum"), ["num"])])
    tracer = tracing.activate(str(tmp_path / "dense.json"))
    try:
        rows0, by_column0 = counted()
        dense.process(2, [_delta(first)])
        assert counted() == (rows0, by_column0)
    finally:
        tracing.deactivate()
    (update,) = [e["args"] for e in tracer._events if e.get("name") == "groupby.update"]
    assert update["path"] == "dense" and "loop" not in update


def test_reducers_over_one_expression_are_handed_one_array():
    """`_repack` orders four `tuple_by` by one expression: the lowering
    computes it once, and what the reducers build from it is shared."""
    t = T(
        """
        k | v | w
        a | 1 | 5
        a | 2 | 6
        b | 3 | 7
        """
    )
    order = -pw.this.v
    res = t.groupby(pw.this.k).reduce(
        pw.this.k,
        vs=pw.reducers.tuple_by(order, pw.this.v),
        ws=pw.reducers.tuple_by(order, pw.this.w),
        other=pw.reducers.tuple_by(-pw.this.w, pw.this.w),
        called=pw.reducers.tuple_by(pw.apply(lambda v: -v, pw.this.v), pw.this.w),
        called_too=pw.reducers.tuple_by(pw.apply(lambda v: -v, pw.this.v), pw.this.w),
    )
    runner = GraphRunner()
    runner.lower(res)
    (gb,) = [n for n in runner._nodes if isinstance(n, ops.GroupByReduce)]
    by_name = {name: args for name, _, args in gb._reducers}
    assert by_name["__r0"][0] == by_name["__r1"][0]  # the same sort key
    assert by_name["__r0"][1] != by_name["__r1"][1]
    assert by_name["__r1"][1] == by_name["__r2"][1]  # `w` twice is one column too
    # another expression, and a call (which may answer differently each
    # time), are columns of their own
    assert len({by_name[f"__r{i}"][0] for i in range(5)}) == 4
    got = pw.debug.table_to_dicts(res)[1]
    by_k = {k: key for key, k in got["k"].items()}
    for column, a, b in (("vs", (2, 1), (3,)), ("ws", (6, 5), (7,)), ("other", (6, 5), (7,)),
                         ("called", (6, 5), (7,)), ("called_too", (6, 5), (7,))):
        assert tuple(got[column][by_k["a"]]) == a and tuple(got[column][by_k["b"]]) == b
