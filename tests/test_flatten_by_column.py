"""Flatten by column (``engine/operators.py::Flatten.process``): a whole
delta is exploded in one numpy step a column, where the node used to build a
one-element array and call ``K.derive`` for every item.

The reference below is that by-row form, item by item, with the scalar key
mix. The node must give the same keys to the bit (they are row ids
downstream), the same rows in the same order, the same diffs, the same
column dtypes and the same error log.
"""

from __future__ import annotations

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import keys as K
from pathway_tpu.engine.delta import Delta, rows_to_columns
from pathway_tpu.engine.error import ERROR_LOG, Error as EngineError
from pathway_tpu.engine.operators import Flatten, StaticSource


def _reference(d: Delta, col: str) -> tuple[Delta | None, int]:
    """The by-row flatten, and how many rows it skipped."""
    names = list(d.data)
    flat_ix = names.index(col)
    arrs = [d.data[c] for c in names]
    keys_out: list[int] = []
    rows_out: list[tuple] = []
    diffs_out: list[int] = []
    skipped = 0
    for i in range(len(d)):
        value = arrs[flat_ix][i]
        items = None
        if value is not None and not isinstance(value, EngineError):
            try:
                items = list(value)
            except TypeError:
                items = None
        if items is None:
            skipped += 1
            continue
        base = tuple(a[i] for a in arrs)
        for pos, item in enumerate(items):
            keys_out.append(K.derive_scalar(int(d.keys[i]), pos * 2 + 0x7))
            rows_out.append(base[:flat_ix] + (item,) + base[flat_ix + 1 :])
            diffs_out.append(int(d.diffs[i]))
    if not keys_out:
        return None, skipped
    return (
        Delta(
            keys=np.array(keys_out, dtype=np.uint64),
            data=rows_to_columns(rows_out, names),
            diffs=np.array(diffs_out, dtype=np.int64),
        ),
        skipped,
    )


def _same_cell(a, b) -> bool:
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return (
            isinstance(a, np.ndarray)
            and isinstance(b, np.ndarray)
            and a.dtype == b.dtype
            and np.array_equal(a, b)
        )
    return type(a) is type(b) and (a == b or a is b)


def _keys(n: int, seed: int = 5) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 2**64, size=n, dtype=np.uint64
    )


def _delta(cells: list, diffs=None, **passengers) -> Delta:
    """``cells`` in column ``x``, passengers before and after it."""
    data = {}
    names = list(passengers)
    for name in names[: len(names) // 2]:
        data[name] = passengers[name]
    data["x"] = _obj(cells)
    for name in names[len(names) // 2 :]:
        data[name] = passengers[name]
    return Delta(keys=_keys(len(cells)), data=data, diffs=diffs)


def _obj(values: list) -> np.ndarray:
    out = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        out[i] = v
    return out


def _reply_shape() -> Delta:
    """What ``DataIndex._matching`` flattens: 16 replies of ten (id, score)
    pairs, each beside its query's id (``origin_id`` is the row's key)."""
    rng = np.random.default_rng(11)
    keys = _keys(16, seed=12)
    replies = [
        tuple(
            (int(i), float(s))
            for i, s in zip(
                rng.integers(0, 2**63, size=10), rng.random(10)
            )
        )
        for _ in range(16)
    ]
    return Delta(
        keys=keys,
        data={"_pw_index_reply": _obj(replies), "_pw_query_id": keys.copy()},
        diffs=np.array([1] * 8 + [-1] * 8, dtype=np.int64),
    )


CASES = {
    "ragged": lambda: _delta([[1, 2, 3], [4], [5, 6, 7, 8, 9]]),
    "empty_among_full": lambda: _delta([[1, 2], [], [3], ()]),
    "none_among_good": lambda: _delta([[1, 2], None, [3]]),
    "error_among_good": lambda: _delta([["a"], EngineError(), ["b", "c"]]),
    "scalar_json_among_good": lambda: _delta(
        [pw.Json([1, 2]), pw.Json(42), pw.Json(["x"])]
    ),
    "non_iterable_scalars": lambda: _delta([[1.5], 7, 2.5, [3.5]]),
    "str_cell": lambda: _delta(["abc", "", "de"]),
    "ndarray_cell": lambda: _delta(
        [np.arange(3), np.array([1.5, 2.5]), np.zeros(0)]
    ),
    "json_array": lambda: _delta(
        [pw.Json([1, "a", None]), pw.Json([{"k": 1}]), pw.Json([])]
    ),
    "tuple_and_dict_cells": lambda: _delta([(1, 2.0), {"k": 1, "m": 2}, [None]]),
    "diffs_minus_one": lambda: _delta(
        [[1, 2], [3]], diffs=np.array([-1, -1], dtype=np.int64)
    ),
    "diffs_plus_two": lambda: _delta(
        [[1, 2], [3, 4, 5]], diffs=np.array([2, 2], dtype=np.int64)
    ),
    "diffs_mixed": lambda: _delta(
        [[1], [2, 3], [], [4, 5, 6]], diffs=np.array([1, -1, 2, -3], dtype=np.int64)
    ),
    "dense_passengers": lambda: _delta(
        [[1, 2], [3], [4, 5, 6]],
        i=np.array([10, 20, 30], dtype=np.int64),
        f=np.array([0.5, 1.5, 2.5], dtype=np.float64),
        b=np.array([True, False, True]),
        u=_keys(3, seed=6),
    ),
    "narrow_dense_passengers": lambda: _delta(
        [[1, 2], [3]],
        i32=np.array([1, 2], dtype=np.int32),
        f32=np.array([0.5, 1.5], dtype=np.float32),
    ),
    "object_passengers": lambda: _delta(
        [["a", "b"], ["c"], ["d", "e"]],
        s=_obj(["p", "q", "r"]),
        ints=_obj([1, 2, 3]),
        mixed=_obj([1, None, "z"]),
        arr=_obj([np.arange(2), np.arange(3), np.arange(4)]),
    ),
    "skipped_rows_with_passengers": lambda: _delta(
        [None, [1, 2], pw.Json(1.5), [3]],
        i=np.array([1, 2, 3, 4], dtype=np.int64),
        s=_obj(["a", "b", "c", "d"]),
    ),
    "all_skipped": lambda: _delta([None, EngineError(), pw.Json(3)]),
    "all_empty": lambda: _delta([[], (), ""]),
    "reply_shape": _reply_shape,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_flatten_by_column_is_the_by_row_flatten(case):
    d = CASES[case]()
    col = "_pw_index_reply" if case == "reply_shape" else "x"
    want, skipped = _reference(d, col)
    node = Flatten(StaticSource(d.keys[:0], {c: a[:0] for c, a in d.data.items()}), col)
    logged = ERROR_LOG.total
    got = node.process(0, [d])
    assert ERROR_LOG.total - logged == skipped
    if skipped:
        assert ERROR_LOG.entries()[-skipped:] == [
            ("non-iterable value in flatten column; row skipped", "flatten")
        ] * skipped
    if want is None:
        assert got is None
        return
    assert got.keys.dtype == np.uint64 and got.keys.tolist() == want.keys.tolist()
    assert got.diffs.dtype == np.int64 and got.diffs.tolist() == want.diffs.tolist()
    assert list(got.data) == list(want.data)
    for name, column in want.data.items():
        assert got.data[name].dtype == column.dtype, name
        assert len(got.data[name]) == len(column)
        assert all(map(_same_cell, got.data[name].tolist(), column.tolist())), name


def test_flatten_child_keys_are_pinned():
    """Three literal child keys: a change to the mix, or to a position's
    salt, changes every row id a flatten has ever given out."""
    parent = np.array([0x0123456789ABCDEF], dtype=np.uint64)
    d = Delta(keys=parent, data={"x": _obj([list(range(10))])})
    out = Flatten(StaticSource(parent[:0], {"x": _obj([])}), "x").process(0, [d])
    assert [hex(int(out.keys[p])) for p in (0, 1, 9)] == [
        "0x7ffb32ec46e23bc1",
        "0xf1d22184b964a191",
        "0x9b46646382ade3e5",
    ]
