"""Native C keyspace kernel: bit-parity with the pure-Python path.

Parity is load-bearing: persisted snapshots store keys, so the two
implementations must agree on every value class or recovery would
mis-route rows after an environment change.
"""

from __future__ import annotations

import datetime
import enum
import hashlib

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import keys as K
from pathway_tpu.engine.delta import consolidation_plan
from pathway_tpu.engine.fusion import FUSION_STATS
from pathway_tpu.native import get_native

native = get_native()

pytestmark = pytest.mark.skipif(
    native is None, reason="no C compiler available to build the native module"
)

CORPUS_ROWS = [
    (),
    (None,),
    (True, False),
    (0, 1, -1, 2**62, -(2**62), 123456789),
    (0.0, -0.0, 1.5, float("inf"), -2.75e300),
    ("", "hello", "héllo wörld", "x" * 1000),
    (b"", b"raw\x00bytes", b"y" * 500),
    (("nested", 1), ("deep", ("er", 2.5), None)),
    (np.int64(42), np.float64(2.5), np.bool_(True)),
    (np.array([1.0, 2.0, 3.0]),),
    ({"a": 1},),  # falls back to repr hashing, must still agree
]


def test_blake2b8_matches_hashlib():
    for data in [b"", b"a", b"hello world", b"z" * 127, b"z" * 128, b"z" * 129,
                 b"q" * 1000]:
        expected = int.from_bytes(
            hashlib.blake2b(data, digest_size=8).digest(), "little"
        )
        assert native.blake2b8(data) == expected, f"len={len(data)}"


def test_splitmix_matches_python():
    for x in [0, 1, 0xDEADBEEF, 2**64 - 1, 0x9E3779B97F4A7C15]:
        assert native.splitmix64(x) == int(K._splitmix(np.uint64(x)))


def test_hash_rows_parity():
    for salt in (0, 7, 0xC0):
        py = K._hash_values_py(CORPUS_ROWS, salt)
        out = np.empty(len(CORPUS_ROWS), dtype=np.uint64)
        native.hash_rows(CORPUS_ROWS, salt, K._hash_scalar, out)
        assert list(out) == list(py)


def test_hash_values_uses_native_and_agrees():
    rows = [("word", i, float(i) / 3) for i in range(1000)]
    assert list(K.hash_values(rows)) == list(K._hash_values_py(rows))


def test_native_speedup_on_string_rows():
    import time

    rows = [(f"token-{i}", f"text {i % 97}", i) for i in range(20000)]
    t0 = time.perf_counter()
    out = np.empty(len(rows), dtype=np.uint64)
    native.hash_rows(rows, 0, K._hash_scalar, out)
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter()
    K._hash_values_py(rows)
    t_py = time.perf_counter() - t0
    # native should be dramatically faster; 3x is a conservative floor
    assert t_native * 3 < t_py, (t_native, t_py)



# -- 128-bit keyspace: HI lane parity + conflation detection -----------------


def test_blake2b16hi_matches_hashlib():
    for data in (b"", b"hello", b"x" * 1000, "héllo".encode()):
        exp = int.from_bytes(
            hashlib.blake2b(data, digest_size=16).digest()[8:16], "little"
        )
        assert native.blake2b16hi(data) == exp


def test_splitmix2_matches_python():
    for x in (0, 1, 2**63, 0xDEADBEEF, 2**64 - 1):
        assert native.splitmix64_2(x) == K._splitmix2_int(x)
        assert native.splitmix64_2(x) == int(K._splitmix2(np.uint64(x)))


def test_hash_scalars2_parity_with_python():
    flat = [v for row in CORPUS_ROWS for v in row]
    lo = np.empty(len(flat), dtype=np.uint64)
    hi = np.empty(len(flat), dtype=np.uint64)
    native.hash_scalars2(flat, K._hash_scalar, K._hash_scalar_hi, None, lo, hi)
    for i, v in enumerate(flat):
        assert int(lo[i]) == K._hash_scalar(v) & ((1 << 64) - 1), v
        assert int(hi[i]) == K._hash_scalar_hi(v), v


def test_hash_rows2_lo_lane_bit_identical_to_hash_rows():
    # the LO lane is the persisted engine keyspace: widening must not
    # change a single existing key
    lo = np.empty(len(CORPUS_ROWS), dtype=np.uint64)
    hi = np.empty(len(CORPUS_ROWS), dtype=np.uint64)
    native.hash_rows2(
        CORPUS_ROWS, 7, 7, K._hash_scalar, K._hash_scalar_hi, None, lo, hi
    )
    old = np.empty(len(CORPUS_ROWS), dtype=np.uint64)
    native.hash_rows(CORPUS_ROWS, 7, K._hash_scalar, old)
    assert list(lo) == list(old)
    assert list(lo) == list(K._hash_values_py(CORPUS_ROWS, 7))


def test_hi_lane_independent_of_lo_lane():
    # if HI were a function of LO, lane collisions would always agree on
    # HI and detection could never fire; check the lanes decorrelate
    vals = [f"s{i}" for i in range(64)] + list(range(64))
    lo = np.empty(len(vals), dtype=np.uint64)
    hi = np.empty(len(vals), dtype=np.uint64)
    native.hash_scalars2(vals, K._hash_scalar, K._hash_scalar_hi, None, lo, hi)
    assert len(set(map(int, lo))) == len(vals)
    assert len(set(map(int, hi))) == len(vals)
    assert not np.any(lo == hi)


def test_string_memo_bit_identical():
    vals = ["alpha", "beta", "alpha", "beta", "alpha"] * 10
    memo: dict = {}
    lo_m = np.empty(len(vals), dtype=np.uint64)
    hi_m = np.empty(len(vals), dtype=np.uint64)
    native.hash_scalars2(vals, K._hash_scalar, K._hash_scalar_hi, memo, lo_m, hi_m)
    lo = np.empty(len(vals), dtype=np.uint64)
    hi = np.empty(len(vals), dtype=np.uint64)
    native.hash_scalars2(vals, K._hash_scalar, K._hash_scalar_hi, None, lo, hi)
    assert list(lo_m) == list(lo) and list(hi_m) == list(hi)
    assert set(memo) == {"alpha", "beta"}
    out_m = np.empty(len(vals), dtype=np.uint64)
    lomemo: dict = {}
    native.hash_scalars(vals, K._hash_scalar, out_m, lomemo)
    assert list(out_m) == list(lo)


def test_key_registry_detects_lane_collision():
    reg = native.KeyRegistry(1000)
    lo = np.array([10, 20, 30], dtype=np.uint64)
    hi = np.array([1, 2, 3], dtype=np.uint64)
    assert reg.register(lo, hi) == -1
    assert reg.register(lo, hi) == -1  # re-registering same keys is fine
    clash_lo = np.array([20], dtype=np.uint64)
    clash_hi = np.array([99], dtype=np.uint64)
    assert reg.register(clash_lo, clash_hi) == 0
    assert reg.stats()[0] == 3


def test_key_registry_freezes_at_cap():
    reg = native.KeyRegistry(4)
    lo = np.arange(100, 110, dtype=np.uint64)
    hi = np.arange(200, 210, dtype=np.uint64)
    assert reg.register(lo, hi) == -1
    size, frozen = reg.stats()
    assert frozen == 1 and size <= 8
    # frozen: registered prefix still detects, unregistered keys pass
    assert reg.register(np.array([100], np.uint64), np.array([5], np.uint64)) == 0


def test_register_keys_raises_key_collision_error():
    import pathway_tpu.engine.keys as keys_mod

    saved = keys_mod._REGISTRY
    keys_mod._REGISTRY = None
    try:
        keys_mod._get_registry()
        keys_mod._register_keys(
            np.array([77], dtype=np.uint64), np.array([1], dtype=np.uint64)
        )
        with pytest.raises(K.KeyCollisionError, match="collision"):
            keys_mod._register_keys(
                np.array([77], dtype=np.uint64), np.array([2], dtype=np.uint64)
            )
    finally:
        keys_mod._REGISTRY = saved


def test_py_key_registry_matches_native_semantics():
    pyreg = K._PyKeyRegistry(1000)
    lo = np.array([10, 20], dtype=np.uint64)
    hi = np.array([1, 2], dtype=np.uint64)
    assert pyreg.register(lo, hi) == -1
    assert pyreg.register(np.array([20], np.uint64), np.array([9], np.uint64)) == 0


def test_mix_columns_registers_and_detects_synthetic_conflation(monkeypatch):
    # two different value columns whose LO lanes collide (forced via a
    # stubbed LO hash) must fail the run instead of conflating rows
    import pathway_tpu.engine.keys as keys_mod

    saved = keys_mod._REGISTRY
    keys_mod._REGISTRY = None
    try:
        keys_mod._get_registry()
        a = keys_mod.mix_columns([np.array(["x1"], dtype=object)], 1)
        # same LO fold can only repeat with the same values -> no error
        keys_mod.mix_columns([np.array(["x1"], dtype=object)], 1)
        # now register a forged pair with the same LO but different HI
        with pytest.raises(K.KeyCollisionError):
            keys_mod._register_keys(
                np.asarray(a, dtype=np.uint64),
                np.array([0xBAD], dtype=np.uint64),
            )
    finally:
        keys_mod._REGISTRY = saved


# --- the cells of a reply row, hashed in C (PR 37) -----------------------
# native.c::hash_scalar2 takes numpy scalars, dicts, arrays and whatever the
# Python ladder hashes by repr; subclasses of the ladder's types go back to
# the ladder. Every value must come out as keys._hash_scalar / _hash_scalar_hi
# give it, on every entry point.

_M64 = (1 << 64) - 1
_ROW_SEED_LO = 0xA0761D6478BD642F  # keys.mix_columns / native.c ROW_SEED


class _Color(enum.IntEnum):
    RED = 3


class _Meters(float):
    pass


class _Liters(float):
    def __float__(self):
        return 2.0


class _Tags(dict):
    pass


class _Name(str):
    pass


class _Pair(tuple):
    pass


class _Wide(np.int64):
    pass


class _Grid(np.ndarray):
    pass


class _Unknown:
    def __repr__(self):
        return "_Unknown()"


class _SaysInt:
    """isinstance believes __class__, so the ladder hashes this as an int."""

    @property
    def __class__(self):
        return int

    def __int__(self):
        return 7

    def __repr__(self):
        return "_SaysInt()"


def _reply_row(i: int) -> tuple:
    """The three list cells of a /v1/retrieve reply: ten scores, ten matched
    ids, ten _metadata."""
    return (
        tuple(np.float64(1.0 / (i + j + 1)) for j in range(10)),
        tuple(np.uint64((2**63 + 977 * i + j) & _M64) for j in range(10)),
        tuple({"path": f"d{i + j}", "ver": j} for j in range(10)),
    )


_INT_TYPES = [np.int8, np.int16, np.int32, np.int64, np.longlong, np.intc,
              np.uint8, np.uint16, np.uint32, np.uint64, np.ulonglong, np.uintc]
_FLOAT_TYPES = [np.float16, np.float32, np.float64, np.longdouble]

_BASE = np.arange(12, dtype=np.int32).reshape(3, 4)

CELLS: dict = {
    "np.bool_-true": np.bool_(True),
    "np.bool_-false": np.bool_(False),
    **{
        f"{t.__name__}-{edge}": t(getattr(np.iinfo(t), edge))
        for t in _INT_TYPES for edge in ("min", "max")
    },
    **{f"{t.__name__}-5": t(5) for t in _INT_TYPES},
    "int8-minus-one": np.int8(-1),
    "int64-minus-one": np.int64(-1),
    "uint64-2**63": np.uint64(2**63),
    "uint64-above-2**63": np.uint64(2**63 + 5),
    **{
        f"{t.__name__}-{text}": t(text)
        for t in _FLOAT_TYPES
        for text in ("1.5", "nan", "-0.0", "inf", "-inf", "1e-7")
    },
    "float16-largest": np.float16(65504),
    "float32-inexact-in-double": np.float32(0.1),
    "dict": {"path": "d3", "ver": 0},
    "dict-empty": {},
    "dict-of-numpy": {"score": np.float64(0.5), "id": np.uint64(2**63)},
    "dict-nested": {"a": {"b": [np.float32(1.5), None]}, "c": (1, "x")},
    "dict-subclass": _Tags(a=1),
    "Json": pw.Json({"a": [1, 2]}),
    "datetime": datetime.datetime(2024, 2, 29, 12, 30, 15),
    "timedelta": datetime.timedelta(days=3, seconds=5),
    "np.datetime64": np.datetime64("2024-02-29"),
    "complex": complex(1, 2),
    "list": [1, "a", np.float64(2.0)],
    "unknown-class": _Unknown(),
    "array-0d": np.array(5),
    "array-1d": np.arange(4),
    "array-1d-float32": np.linspace(0, 1, 64, dtype=np.float32),
    "array-2d": _BASE,
    "array-non-contiguous": _BASE[:, ::2],
    "array-transposed": _BASE.T,
    "array-empty": np.empty((0, 3)),
    "array-of-objects": np.array(["a", None], dtype=object)[:1],
    "array-same-bytes-other-shape": _BASE.reshape(4, 3),
    "ndarray-subclass": np.arange(4).view(_Grid),
    "IntEnum": _Color.RED,
    "float-subclass": _Meters(1.5),
    "float-subclass-own-__float__": _Liters(1.0),
    "str-subclass": _Name("n"),
    "np.str_": np.str_("n"),
    "np.bytes_": np.bytes_(b"n"),
    "tuple-subclass": _Pair((1, np.float64(2.0))),
    "np.int64-subclass": _Wide(5),
    "lying-__class__": _SaysInt(),
    "reply-row": _reply_row(0),
}
CELLS["tuple-of-all"] = tuple(CELLS.values())
CELLS["tuple-nested"] = (None, (np.uint8(255), ({"k": np.int16(-2)},), _BASE), "s")

#: what C still gives to the ladder: subclasses of its types, and longdouble
_TO_LADDER = {
    "ndarray-subclass", "IntEnum", "float-subclass", "float-subclass-own-__float__",
    "str-subclass", "np.str_", "np.bytes_", "tuple-subclass",
    "np.int64-subclass", "lying-__class__",
} | {name for name in CELLS if name.startswith("longdouble-")}


def _fold(seed: int, mix, lanes: list[int]) -> int:
    acc = seed
    for lane in lanes:
        acc = mix(acc ^ lane)
    return acc


@pytest.mark.parametrize("name", list(CELLS))
def test_cell_hashes_as_the_python_ladder_on_every_entry_point(name):
    v = CELLS[name]
    lo, hi = K._hash_scalar(v) & _M64, K._hash_scalar_hi(v)
    salt = 0xC0FFEE
    # hash_scalars / hash_scalars2: bare, and inside tuples
    vals = [v, (v,), (1, (v, "x"), v)]
    want = [(K._hash_scalar(x) & _M64, K._hash_scalar_hi(x)) for x in vals]
    assert want[0] == (lo, hi)
    out = np.empty(3, dtype=np.uint64)
    native.hash_scalars(vals, K._hash_scalar, out)
    assert [int(x) for x in out] == [w[0] for w in want]
    out_lo, out_hi = np.empty(3, dtype=np.uint64), np.empty(3, dtype=np.uint64)
    native.hash_scalars2(
        vals, K._hash_scalar, K._hash_scalar_hi, None, out_lo, out_hi
    )
    assert [(int(a), int(b)) for a, b in zip(out_lo, out_hi)] == want
    # hash_rows / hash_rows2: the row fold over (v, "k", v) and (v,)
    rows = [(v, "k", v), (v,)]
    k_lo, k_hi = K._hash_scalar("k"), K._hash_scalar_hi("k")
    want_lo = [
        _fold(_ROW_SEED_LO ^ salt, K._splitmix_int, lanes)
        for lanes in ([lo, k_lo, lo], [lo])
    ]
    want_hi = [
        _fold(K._ROW_SEED_HI ^ salt, K._splitmix2_int, lanes)
        for lanes in ([hi, k_hi, hi], [hi])
    ]
    assert [int(x) for x in K._hash_values_py(rows, salt)] == want_lo
    out = np.empty(2, dtype=np.uint64)
    native.hash_rows(rows, salt, K._hash_scalar, out)
    assert [int(x) for x in out] == want_lo
    out_lo, out_hi = np.empty(2, dtype=np.uint64), np.empty(2, dtype=np.uint64)
    native.hash_rows2(
        rows, salt, salt, K._hash_scalar, K._hash_scalar_hi, None, out_lo, out_hi
    )
    assert [int(x) for x in out_lo] == want_lo
    assert [int(x) for x in out_hi] == want_hi
    # mix_cols2: the same fold by column
    cols = []
    for j in range(3):
        col = np.empty(2, dtype=object)
        col[0], col[1] = rows[0][j], v
        cols.append(col)
    native.mix_cols2(
        cols, 2, salt, salt, K._hash_scalar, K._hash_scalar_hi, None,
        out_lo, out_hi,
    )
    assert int(out_lo[0]) == want_lo[0] and int(out_hi[0]) == want_hi[0]
    assert int(out_lo[1]) == _fold(
        _ROW_SEED_LO ^ salt, K._splitmix_int, [lo, lo, lo]
    )


@pytest.mark.parametrize("name", list(CELLS))
def test_fallback_calls_are_counted_one_a_lane(name):
    """The C code hashes what is plain data itself and returns how many values
    it handed to the ladder: one a lane for a subclass, none otherwise."""
    v = CELLS[name]
    expected = len(_TO_LADDER) if name == "tuple-of-all" else int(name in _TO_LADDER)
    out, out_hi = np.empty(1, dtype=np.uint64), np.empty(1, dtype=np.uint64)
    assert native.hash_scalars([v], K._hash_scalar, out) == expected
    assert native.hash_rows([(v,)], 0, K._hash_scalar, out) == expected
    assert native.hash_scalars2(
        [v], K._hash_scalar, K._hash_scalar_hi, None, out, out_hi
    ) == 2 * expected
    assert native.hash_rows2(
        [(v,)], 0, 0, K._hash_scalar, K._hash_scalar_hi, None, out, out_hi
    ) == 2 * expected


#: value -> (LO, HI), written down from keys._hash_scalar / _hash_scalar_hi
#: before native.c knew these types: the LO lane is the persisted keyspace,
#: so neither side may move, even if both moved together
GOLDEN = {
    "np.float64": (np.float64(0.8125), 0x85EBFD92FAF73D46, 0xD607B487F8B9F8AE),
    "np.float32": (np.float32(1.5), 0xD6DAB18E1392608A, 0x835606F64F35F6B0),
    "np.float16-nan": (np.float16("nan"), 0xC72971D82E15BB46, 0x182CEEF4E0FE8B58),
    "np.uint64": (np.uint64(2**63 + 5), 0x54A64D19D7534F30, 0x145752615652E38C),
    "np.int8": (np.int8(-1), 0xE4D971771B652C20, 0xBEBC2C5C2C7CF25E),
    "np.bool_": (np.bool_(True), 0x5F35F7EE72CD6CED, 0x1FBACE1A1CA09530),
    "dict": ({"path": "d3", "ver": 0}, 0x7D192ADAFCEE281B, 0x58F907015A1D0B9B),
    "datetime": (
        datetime.datetime(2024, 2, 29, 12, 30, 15),
        0x2E82CBBF7549CEDA, 0x4276A5AE4FD1452A,
    ),
    "Json": (pw.Json({"a": [1, 2]}), 0xD13CEE5947E5079D, 0x58CC0AA099D2C152),
    "array": (
        np.arange(6, dtype=np.float32).reshape(2, 3),
        0xE3C51670B872061C, 0xBF2133B8124C3B75,
    ),
    "reply-cells": (
        (
            tuple(np.float64(x) for x in (0.8125, 0.75, -0.0)),
            tuple(np.uint64(x) for x in (2**63 + 5, 17, 2**64 - 1)),
            ({"path": "d3", "ver": 0}, {"path": "d4", "ver": 1}),
        ),
        0xF6644E150797102C, 0x2254293B3D4BF491,
    ),
}


@pytest.mark.parametrize("name", list(GOLDEN))
def test_golden_keys_do_not_move(name):
    v, lo, hi = GOLDEN[name]
    assert (K._hash_scalar(v), K._hash_scalar_hi(v)) == (lo, hi)
    out_lo, out_hi = np.empty(1, dtype=np.uint64), np.empty(1, dtype=np.uint64)
    native.hash_scalars2(
        [v], K._hash_scalar, K._hash_scalar_hi, None, out_lo, out_hi
    )
    assert (int(out_lo[0]), int(out_hi[0])) == (lo, hi)
    native.hash_scalars([v], K._hash_scalar, out_lo)
    assert int(out_lo[0]) == lo


def _reply_columns(rows: int) -> list[np.ndarray]:
    cols = [np.empty(rows, dtype=object) for _ in range(3)]
    for i in range(rows):
        for col, cell in zip(cols, _reply_row(i)):
            col[i] = cell
    return cols


def test_reply_shaped_column_never_leaves_c():
    cols = _reply_columns(16)
    before = FUSION_STATS["hash_fallback_calls_total"]
    for col in cols:
        K.hash_column(col)
        K._column_lanes(col)
    K.mix_columns(cols, 16, register=False)
    K.mix_columns_fused(cols, 16, register=False)
    K.hash_values(list(zip(*cols)), register=False)
    assert FUSION_STATS["hash_fallback_calls_total"] == before
    # a value C does not take is one call on the LO lane, two on both
    col = np.empty(2, dtype=object)
    col[0], col[1] = _Color.RED, (1.5, {"a": 1})
    K.hash_column(col)
    assert FUSION_STATS["hash_fallback_calls_total"] == before + 1
    K._column_lanes(col)
    assert FUSION_STATS["hash_fallback_calls_total"] == before + 3


def test_reply_consolidation_same_with_and_without_native(monkeypatch):
    """A tick's merge: every reply row once as an insert, half of them again
    as the retraction that cancels it, two with a changed cell."""
    import pathway_tpu.native as native_pkg

    n = 16
    cols = _reply_columns(n)
    again = np.arange(0, n, 2)
    changed = [c[again].copy() for c in cols]
    changed[2][0] = ({"path": "moved", "ver": 9},) * 10
    changed[0][1] = tuple(np.float64(0.25) for _ in range(10))
    ids = K._splitmix(np.arange(n, dtype=np.uint64))
    all_ids = np.concatenate([ids, ids[again]])
    all_cols = [np.concatenate([c, d]) for c, d in zip(cols, changed)]
    diffs = np.concatenate(
        [np.ones(n, dtype=np.int64), -np.ones(len(again), dtype=np.int64)]
    )
    before = FUSION_STATS["hash_fallback_calls_total"]
    keep, sums = consolidation_plan(all_ids, all_cols, diffs)
    assert FUSION_STATS["hash_fallback_calls_total"] == before
    # the six cancelled pairs are gone; the two changed rows stay, both signs
    assert len(keep) == n + len(again) - 2 * (len(again) - 2)
    assert sorted(sums.tolist()) == [-1, -1] + [1] * (n - len(again) + 2)
    monkeypatch.setattr(native_pkg, "_tried", True)
    monkeypatch.setattr(native_pkg, "_cached", None)
    assert native_pkg.get_native() is None
    keep_py, sums_py = consolidation_plan(all_ids, all_cols, diffs)
    assert keep.tolist() == keep_py.tolist()
    assert sums.tolist() == sums_py.tolist()
