"""Compile ColumnExpression trees to whole-batch columnar kernels.

This replaces two reference components at once:
- the static type interpreter (``python/pathway/internals/type_interpreter.py``)
- the row-at-a-time typed Rust interpreter (``src/engine/expression.rs:325``)

An expression DAG compiles to ONE function over column arrays: a numpy
kernel, at every batch size and on every host. The dataflow runs on the
host by design; the accelerator is for the dense kernels (knn, embedder)
that amortize a transfer, and nothing here imports jax.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from . import dtype as dt
from . import expression as expr_mod
from ..engine import keys as K
from ..engine.error import Error as EngineError
from .json import Json
from .expression import (
    ApplyExpression,
    AsyncApplyExpression,
    CastExpression,
    CoalesceExpression,
    ColumnBinaryOpExpression,
    ColumnConstExpression,
    ColumnExpression,
    ColumnReference,
    ColumnUnaryOpExpression,
    ConvertExpression,
    DeclareTypeExpression,
    FillErrorExpression,
    GetExpression,
    IdReference,
    IfElseExpression,
    IsNoneExpression,
    IsNotNoneExpression,
    MakeTupleExpression,
    MethodCallExpression,
    PointerExpression,
    ReducerExpression,
    RequireExpression,
    UnwrapExpression,
)

_NUMERIC = {dt.INT, dt.FLOAT, dt.BOOL}


class ColumnEnv:
    """Resolution of column references to engine column names + dtypes."""

    def __init__(self) -> None:
        self._map: dict[tuple[int, str], tuple[str | None, dt.DType]] = {}

    def add(self, table: Any, name: str, engine_col: str | None, dtype: dt.DType) -> None:
        self._map[(id(table), name)] = (engine_col, dtype)

    def add_table(self, table: Any, prefix: str = "") -> None:
        for name, dtype in table.schema.dtypes().items():
            self.add(table, name, prefix + name, dtype)
        self.add(table, "id", None if not prefix else prefix + "id", dt.POINTER)

    def resolve(self, ref: ColumnReference) -> tuple[str | None, dt.DType]:
        key = (id(ref.table), ref.name)
        if key not in self._map:
            raise KeyError(
                f"column {ref.name!r} is not available in this context "
                f"(table {ref.table!r})"
            )
        return self._map[key]

    def signature(self) -> frozenset:
        """Identity of the binding environment — compile results are valid
        for any env with the same bindings (used to reuse compiled kernels
        across pw.iterate rounds instead of rebuilding them every round)."""
        return frozenset(
            (k, v[0], str(v[1])) for k, v in self._map.items()
        )


@dataclass
class Compiled:
    fn: Callable[[dict[str, np.ndarray], np.ndarray], np.ndarray]
    dtype: dt.DType
    #: the whole tree is dense numeric with total ops — the chain-fusion
    #: pass (engine/fusion.py) uses this as the mask-deferral proof (a
    #: total kernel evaluated on masked-out rows cannot raise, build Error
    #: carriers, or touch the error log)
    total: bool = False


def infer_dtype(expr: ColumnExpression, env: ColumnEnv) -> dt.DType:
    """Static dtype of an expression (reference: type_interpreter.py)."""
    if isinstance(expr, ReducerExpression):
        return _reducer_dtype(expr, env)
    _, dtype, _ = _build(expr, env)
    return dtype


def _reducer_dtype(expr: ReducerExpression, env: ColumnEnv) -> dt.DType:
    name = expr._reducer
    arg_ts = [infer_dtype(a, env) for a in expr._args]
    if name == "count":
        return dt.INT
    if name in ("sum", "min", "max", "unique", "any", "earliest", "latest"):
        return arg_ts[0] if arg_ts else dt.ANY
    if name in ("argmin", "argmax"):
        return dt.POINTER
    if name == "avg":
        return dt.FLOAT
    if name == "sorted_tuple" or name == "tuple":
        return dt.List(arg_ts[0] if arg_ts else dt.ANY)
    if name == "ndarray":
        return dt.Array(1, arg_ts[0] if arg_ts else dt.FLOAT)
    return dt.ANY


def compile_expr(expr: ColumnExpression, env: ColumnEnv) -> Compiled:
    # memoize per (expression, bindings): pw.iterate re-lowers the same
    # captured subgraph every fixpoint round — without this each round
    # would rebuild every kernel closure from scratch
    cache: dict | None = getattr(expr, "_compiled_cache", None)
    if cache is None:
        cache = {}
        try:
            expr._compiled_cache = cache  # type: ignore[attr-defined]
        except Exception:
            cache = None
    sig = env.signature() if cache is not None else None
    if cache is not None and sig in cache:
        return cache[sig]
    result = Compiled(*_build(expr, env))
    try:
        # static-analysis breadcrumbs (pathway_tpu/analysis): the lowered
        # engine nodes hold only compiled kernels — tagging each kernel
        # with its source expression tree + static dtype lets the analyzer
        # walk the compiled graph without re-deriving the compile
        result.fn._pw_expr = expr
        result.fn._pw_dtype = result.dtype
        # chain-fusion breadcrumb (engine/fusion.py): a filter's mask
        # stays deferred across kernels that are total
        result.fn._pw_total = result.total
        if isinstance(expr, ColumnReference) and not isinstance(
            expr, IdReference
        ):
            # plain column pass-through: the groupby/join content-key
            # reuse fast path matches these against the source delta's
            # key-derivation columns (operators.py)
            try:
                engine_col, _cdt = env.resolve(expr)
                if engine_col is not None:
                    result.fn._pw_colref = engine_col
            except KeyError:
                pass
    except (AttributeError, TypeError):
        pass
    if cache is not None:
        cache[sig] = result
    return result


# ---------------------------------------------------------------------------
# dtype rules
# ---------------------------------------------------------------------------

_CMP_OPS = {"==", "!=", "<", "<=", ">", ">="}
_ARITH_OPS = {"+", "-", "*", "/", "//", "%", "**", "@"}
_BITS_OPS = {"&", "|", "^", "<<", ">>"}


def binop_dtype(op: str, l: dt.DType, r: dt.DType) -> dt.DType:
    lu, ru = dt.unoptionalize(l), dt.unoptionalize(r)
    opt = l.is_optional or r.is_optional

    def w(t: dt.DType) -> dt.DType:
        return dt.Optional(t) if opt else t

    if op in _CMP_OPS:
        return w(dt.BOOL)
    if op in ("<<", ">>"):
        # shifts are integer arithmetic even on bools (True << True == 2);
        # the &/|/^ bool-closure rule must not apply
        if lu in (dt.INT, dt.BOOL) and ru in (dt.INT, dt.BOOL):
            return w(dt.INT)
        return w(dt.ANY)
    if op in _BITS_OPS:
        if lu == dt.BOOL and ru == dt.BOOL:
            return w(dt.BOOL)
        if lu == dt.INT and ru == dt.INT:
            return w(dt.INT)
        return w(dt.ANY)
    if op in _ARITH_OPS:
        # datetime algebra
        if lu in (dt.DATE_TIME_NAIVE, dt.DATE_TIME_UTC):
            if op == "-" and ru == lu:
                return w(dt.DURATION)
            if op in ("+", "-") and ru == dt.DURATION:
                return w(lu)
        if lu == dt.DURATION:
            if op == "+" and ru in (dt.DATE_TIME_NAIVE, dt.DATE_TIME_UTC):
                return w(ru)
            if op in ("+", "-") and ru == dt.DURATION:
                return w(dt.DURATION)
            if op in ("*",) and ru == dt.INT:
                return w(dt.DURATION)
            if op == "/" and ru == dt.DURATION:
                return w(dt.FLOAT)
            if op == "//" and ru == dt.DURATION:
                return w(dt.INT)
            if op in ("/", "//") and ru == dt.INT:
                return w(dt.DURATION)
        if lu == dt.STR and ru == dt.STR and op == "+":
            return w(dt.STR)
        if (lu == dt.STR and ru == dt.INT or lu == dt.INT and ru == dt.STR) and op == "*":
            return w(dt.STR)
        if isinstance(lu, dt.Array) or isinstance(ru, dt.Array):
            return w(lu if isinstance(lu, dt.Array) else ru)
        if op == "/":
            if lu in (dt.INT, dt.FLOAT, dt.BOOL) and ru in (dt.INT, dt.FLOAT, dt.BOOL):
                return w(dt.FLOAT)
        if lu == dt.FLOAT or ru == dt.FLOAT:
            if lu in _NUMERIC and ru in _NUMERIC:
                return w(dt.FLOAT)
        if lu in (dt.INT, dt.BOOL) and ru in (dt.INT, dt.BOOL):
            return w(dt.INT)
        if lu == dt.ANY or ru == dt.ANY:
            return w(dt.ANY)
    return w(dt.ANY)


# ---------------------------------------------------------------------------
# build: returns (fn, dtype, total)
# ---------------------------------------------------------------------------


def _build(
    expr: ColumnExpression, env: ColumnEnv
) -> tuple[Callable, dt.DType, bool]:
    if isinstance(expr, expr_mod.SelfKeysExpression):
        return (lambda cols, keys: keys), dt.POINTER, True

    if isinstance(expr, expr_mod.HiddenRef):
        name = expr._engine_name
        dtype = expr._dtype if expr._dtype is not None else dt.ANY
        numericable = dt.unoptionalize(dtype) in _NUMERIC
        return (lambda cols, keys: cols[name]), dtype, numericable

    if isinstance(expr, IdReference):
        engine_col, dtype = env.resolve(expr)
        if engine_col is None:
            return (lambda cols, keys: keys), dt.POINTER, True
        return (lambda cols, keys: cols[engine_col]), dtype, True

    if isinstance(expr, ColumnReference):
        engine_col, dtype = env.resolve(expr)
        if engine_col is None:
            return (lambda cols, keys: keys), dt.POINTER, True
        numericable = dt.unoptionalize(dtype) in _NUMERIC or dtype == dt.POINTER
        return (lambda cols, keys: cols[engine_col]), dtype, numericable

    if isinstance(expr, ColumnConstExpression):
        v = expr._value
        dtype = dt.dtype_of_value(v)
        numericable = dtype in _NUMERIC
        return (lambda cols, keys: v), dtype, numericable

    if isinstance(expr, ColumnBinaryOpExpression):
        lf, ldt, lok = _build(expr._left, env)
        rf, rdt, rok = _build(expr._right, env)
        op = expr._op
        out_dt = binop_dtype(op, ldt, rdt)
        fn = _binop_fn(op, lf, rf, ldt, rdt)
        total = (
            lok
            and rok
            and dt.unoptionalize(out_dt) in _NUMERIC
            and not ldt.is_optional
            and not rdt.is_optional
            and dt.unoptionalize(ldt) in _NUMERIC
            and dt.unoptionalize(rdt) in _NUMERIC
            # divisions are not total: zero denominators become per-row
            # Error values
            and op not in ("/", "//", "%")
        )
        return fn, out_dt, total

    if isinstance(expr, ColumnUnaryOpExpression):
        f, d, ok = _build(expr._expr, env)
        op = expr._op
        if op == "-":
            return (lambda cols, keys: -f(cols, keys)), d, ok
        if op == "~":
            out_dt = d
            def notfn(cols, keys, f=f):
                v = f(cols, keys)
                if isinstance(v, np.ndarray) and v.dtype == object:
                    return np.array([None if x is None else not x for x in v], dtype=object)
                return np.logical_not(v) if dt.unoptionalize(d) == dt.BOOL else ~v
            return notfn, out_dt, ok and dt.unoptionalize(d) in _NUMERIC
        if op == "abs":
            return (lambda cols, keys: np.abs(f(cols, keys))), d, ok
        raise NotImplementedError(f"unary op {op}")

    if isinstance(expr, IsNoneExpression):
        f, d, ok = _build(expr._expr, env)
        negate = isinstance(expr, IsNotNoneExpression)

        def fn(cols, keys, f=f, negate=negate):
            v = f(cols, keys)
            if isinstance(v, np.ndarray) and v.dtype == object:
                out = np.fromiter((x is None for x in v), dtype=bool, count=len(v))
            elif isinstance(v, np.ndarray):
                out = np.zeros(len(v), dtype=bool)
            else:
                out = np.zeros(len(keys), dtype=bool) if v is not None else np.ones(len(keys), dtype=bool)
            return ~out if negate else out

        return fn, dt.BOOL, False

    if isinstance(expr, IfElseExpression):
        cf, cd, cok = _build(expr._if, env)
        tf, td, tok = _build(expr._then, env)
        ef, ed, eok = _build(expr._else, env)
        out_dt = dt.types_lca(td, ed)

        def fn(cols, keys):
            cond = cf(cols, keys)
            tv, ev = tf(cols, keys), ef(cols, keys)
            if isinstance(cond, np.ndarray) and cond.dtype == object:
                cond = np.array([bool(x) for x in cond], dtype=bool)
            out = np.where(cond, tv, ev)
            return out

        total = cok and tok and eok and dt.unoptionalize(out_dt) in _NUMERIC
        return fn, out_dt, total

    if isinstance(expr, CoalesceExpression):
        parts = [_build(a, env) for a in expr._args]
        out_dt = dt.types_lca_many([p[1] for p in parts])
        non_none = [p[1] for p in parts if p[1] != dt.NONE]
        if non_none and any(not p[1].is_optional and p[1] != dt.NONE for p in parts):
            out_dt = dt.unoptionalize(out_dt)

        def fn(cols, keys):
            n = len(keys)
            result = _materialize(parts[0][0](cols, keys), n)
            for f, _, _ in parts[1:]:
                mask = np.fromiter((x is None for x in result), dtype=bool, count=n)
                if not mask.any():
                    break
                nxt = _materialize(f(cols, keys), n)
                result = np.where(mask, nxt, result)
            return _densify(result, out_dt)

        return fn, out_dt, False

    if isinstance(expr, RequireExpression):
        f, d, ok = _build(expr._expr, env)
        conds = [_build(a, env) for a in expr._args]

        def fn(cols, keys):
            n = len(keys)
            result = _materialize(f(cols, keys), n)
            mask = np.zeros(n, dtype=bool)
            for cfn, _, _ in conds:
                v = _materialize(cfn(cols, keys), n)
                mask |= np.fromiter((x is None for x in v), dtype=bool, count=n)
            if mask.any():
                result = result.astype(object)
                result[mask] = None
            return result

        return fn, dt.Optional(d), False

    if isinstance(expr, UnwrapExpression):
        f, d, ok = _build(expr._expr, env)

        def fn(cols, keys):
            v = _materialize(f(cols, keys), len(keys))
            if v.dtype == object:
                for x in v:
                    if x is None:
                        raise ValueError("cannot unwrap, None found in column")
                    if isinstance(x, EngineError):
                        raise ValueError(
                            f"cannot unwrap, Error found in column: {x.message}"
                        )
                return _densify(v, dt.unoptionalize(d))
            return v

        return fn, dt.unoptionalize(d), False

    if isinstance(expr, FillErrorExpression):
        f, d, ok = _build(expr._expr, env)
        rf, rd, rok = _build(expr._replacement, env)

        def fn(cols, keys):
            n = len(keys)
            try:
                v = _materialize(f(cols, keys), n)
            except Exception:
                # a vectorized kernel raises batch-wide; retry row by row so
                # only the genuinely failing rows receive the replacement —
                # the reference's per-row Value::Error replacement semantics
                repl = _materialize(rf(cols, keys), n)
                v = np.empty(n, dtype=object)
                for i in range(n):
                    row_cols = {c: a[i : i + 1] for c, a in cols.items()}
                    try:
                        out_i = _materialize(f(row_cols, keys[i : i + 1]), 1)[0]
                    except Exception:
                        out_i = repl[i]
                    # errors can also flow through as values (not raises)
                    v[i] = repl[i] if isinstance(out_i, EngineError) else out_i
                return _densify(v, dt.types_lca(d, rd))
            if v.dtype == object:
                err_mask = np.array(
                    [isinstance(x, EngineError) for x in v], dtype=bool
                )
                if err_mask.any():
                    repl = _materialize(rf(cols, keys), n)
                    v = v.copy()
                    v[err_mask] = repl[err_mask]
                # all errors gone — restore the dense (vectorizable) dtype
                return _densify(v, dt.types_lca(d, rd))
            return v

        return fn, dt.types_lca(d, rd), False

    if isinstance(expr, (CastExpression, ConvertExpression)):
        f, d, ok = _build(expr._expr, env)
        target = expr._return_type
        tu = dt.unoptionalize(target)
        fn = _cast_fn(f, d, target)
        total = (
            ok
            and dt.unoptionalize(d) in _NUMERIC
            and tu in _NUMERIC
            and not d.is_optional
        )
        return fn, target, total

    if isinstance(expr, DeclareTypeExpression):
        f, d, ok = _build(expr._expr, env)
        target = expr._return_type
        return f, target, ok and dt.unoptionalize(target) in _NUMERIC

    if isinstance(expr, PointerExpression):
        parts = [_build(a, env) for a in expr._args]
        if expr._instance is not None:
            parts.append(_build(expr._instance, env))
        optional = getattr(expr, "_optional", False)

        def fn(cols, keys):
            n = len(keys)
            arrs = [_materialize(p[0](cols, keys), n) for p in parts]
            ptrs = K.mix_columns(arrs, n)
            if optional:
                # pointer_from(..., optional=True): any None argument
                # makes the pointer None (reference prev/next tables)
                null = np.zeros(n, dtype=bool)
                for a in arrs:
                    aa = np.asarray(a)
                    if aa.dtype == object:
                        null |= np.fromiter(
                            (v is None for v in aa), bool, n
                        )
                if null.any():
                    out = np.empty(n, dtype=object)
                    for i in range(n):
                        out[i] = None if null[i] else ptrs[i]
                    return out
            return ptrs

        out_dt = dt.Optional(dt.POINTER) if optional else dt.POINTER
        return fn, out_dt, False

    if isinstance(expr, MakeTupleExpression):
        parts = [_build(a, env) for a in expr._args]

        def fn(cols, keys):
            n = len(keys)
            arrs = [_materialize(p[0](cols, keys), n) for p in parts]
            out = np.empty(n, dtype=object)
            for i in range(n):
                out[i] = tuple(_unnp(a[i]) for a in arrs)
            return out

        out_dt = dt.Tuple(*[p[1] for p in parts])
        return fn, out_dt, False

    if isinstance(expr, GetExpression):
        of, odt, _ = _build(expr._obj, env)
        ixf, _, _ = _build(expr._index, env)
        df, ddt, _ = _build(expr._default, env)
        check = expr._check_if_exists

        def fn(cols, keys):
            n = len(keys)
            objs = _materialize(of(cols, keys), n)
            idxs = _materialize(ixf(cols, keys), n)
            dfts = _materialize(df(cols, keys), n)
            out = np.empty(n, dtype=object)
            for i in range(n):
                try:
                    v = objs[i]
                    if isinstance(v, dict):
                        out[i] = v[idxs[i]] if check else v.get(idxs[i], dfts[i])
                    else:
                        out[i] = v[idxs[i]]
                except (KeyError, IndexError, TypeError):
                    if check:
                        raise
                    out[i] = dfts[i]
            return out

        out_dt = dt.ANY
        if isinstance(dt.unoptionalize(odt), dt.List):
            out_dt = dt.unoptionalize(odt).wrapped
        elif isinstance(dt.unoptionalize(odt), dt.Tuple):
            args = dt.unoptionalize(odt).args
            if args:
                out_dt = dt.types_lca_many(list(args))
        elif dt.unoptionalize(odt) == dt.JSON:
            out_dt = dt.JSON
        if not check:
            out_dt = dt.types_lca(out_dt, ddt)
        return fn, out_dt, False

    if isinstance(expr, (AsyncApplyExpression, ApplyExpression)):
        return _build_apply(expr, env)

    if isinstance(expr, MethodCallExpression):
        from .expressions_namespaces import compile_method

        return compile_method(expr, env, _build)

    if isinstance(expr, ReducerExpression):
        raise TypeError(
            f"reducer {expr._reducer!r} used outside of a reduce() context"
        )

    raise NotImplementedError(f"cannot compile {type(expr).__name__}")


#: process-wide UDF path counters (satellite of the rowwise-fast-path
#: work): which execution path applies landed on — lifted (static
#: exec/AST lift at compile time), traced (probe-row plan built at
#: runtime, one per dtype signature), or per-row Python (counted in
#: rows, the number that actually hurts). Snapshotted onto /metrics as
#: pathway_udf_* and into the signals plane (observability.hub).
UDF_STATS: dict[str, int] = {
    "lifted_total": 0,
    "traced_total": 0,
    "perrow_rows_total": 0,
}


def udf_stats_snapshot() -> dict[str, float]:
    return {k: float(v) for k, v in UDF_STATS.items()}


def _pylist(a: np.ndarray) -> list:
    """Column array -> plain Python list, numpy scalars unwrapped in ONE
    pass (``tolist`` for dense dtypes) instead of a per-row ``_unnp``
    dispatch inside the UDF loop."""
    out = a.tolist()
    if a.dtype != object:
        return out
    return [x.item() if isinstance(x, np.generic) else x for x in out]


def _dispatch_perrow(fn_user, lists, klists, n, prop_none, return_type):
    """Vectorized residual dispatcher: the per-row path as ONE resolved
    loop — fn looked up once, argument columns pre-converted to Python
    lists, no per-row ``_unnp``/list-comprehension machinery. Per-row
    failures still become per-row Error values (reference Value::Error,
    value.rs:226)."""
    out = np.empty(n, dtype=object)
    name = getattr(fn_user, "__name__", "apply")
    if not klists and not prop_none:
        if len(lists) == 1:
            i = 0
            for a in lists[0]:
                try:
                    out[i] = fn_user(a)
                except Exception as e:
                    out[i] = EngineError(f"{type(e).__name__}: {e}", name)
                i += 1
        else:
            i = 0
            for args_i in zip(*lists):
                try:
                    out[i] = fn_user(*args_i)
                except Exception as e:
                    out[i] = EngineError(f"{type(e).__name__}: {e}", name)
                i += 1
    else:
        knames = list(klists)
        kcols = [klists[k] for k in knames]
        rows = zip(*lists) if lists else iter([()] * n)
        i = 0
        for args_i in rows:
            if prop_none and any(a is None for a in args_i):
                out[i] = None
                i += 1
                continue
            kw = {k: c[i] for k, c in zip(knames, kcols)}
            try:
                out[i] = fn_user(*args_i, **kw)
            except Exception as e:
                out[i] = EngineError(f"{type(e).__name__}: {e}", name)
            i += 1
    return _densify(out, return_type)


def _dtype_sig(arrs: list, karrs: dict) -> tuple | None:
    """Runtime dtype signature of one batch's argument columns — the
    guard that keeps a traced plan from serving rows it was not traced
    for. Dense arrays are uniform by construction (dtype char); object
    arrays are scanned (one C-speed type pass). None = this batch is
    not plan-servable (mixed types, None rows, Error carriers) and must
    run per-row."""
    sig: list = []
    for a in list(arrs) + [karrs[k] for k in sorted(karrs)]:
        if a.dtype != object:
            sig.append(a.dtype.char)
            continue
        kinds = set(map(type, a.tolist()))
        if len(kinds) != 1:
            return None
        t = next(iter(kinds))
        if t is type(None) or t is EngineError:
            return None
        sig.append(t)
    return tuple(sig)


def _build_apply(
    expr: "ApplyExpression", env: ColumnEnv
) -> tuple[Callable, dt.DType, bool]:
    """Compile an apply node through the fast-path ladder:

    1. static lift (bytecode-execution trace, then AST lift) — the UDF
       becomes a columnar kernel at compile time;
    2. probe-row tracing at runtime, guarded by the batch's dtype
       signature (re-traced per signature on mixed-dtype streams);
    3. the vectorized per-row dispatcher — genuinely impure/unliftable
       callables, counted on /metrics.

    Lifted and traced kernels carry a per-row fallback: any batch-wide
    raise re-runs that batch through the exact per-row path (safe — the
    lift gates admit only side-effect-free callables), so row-error
    semantics are identical on every path.
    """
    import asyncio
    import inspect

    fn_user = expr._fn
    prop_none = expr._propagate_none
    is_coro = inspect.iscoroutinefunction(fn_user)
    deterministic = getattr(expr, "_deterministic", True)
    lift_eligible = (
        deterministic
        and not is_coro
        and not prop_none
        and os.environ.get("PATHWAY_UDF_LIFT", "auto") != "off"
    )
    trace_eligible = (
        deterministic
        and not is_coro
        and not prop_none
        and os.environ.get("PATHWAY_UDF_TRACE", "auto") != "off"
    )

    # arg kernels are built once and shared by every path (the refusal
    # memo and the Optional-dtype lift gate are keyed by arg dtypes)
    parts: list | None = None
    kparts: dict | None = None

    def _arg_parts() -> tuple[list, dict]:
        nonlocal parts, kparts
        if parts is None:
            parts = [_build(a, env) for a in expr._args]
            kparts = {
                k: _build(v, env) for k, v in expr._kwargs.items()
            }
        return parts, kparts

    def _lift_key() -> tuple:
        p, kp = _arg_parts()
        return (
            fn_user.__code__,
            tuple(str(x[1]) for x in p),
            tuple(sorted((k, str(x[1])) for k, x in kp.items())),
        )

    def _perrow(cols, keys):
        """The exact per-row path — also the fallback a lifted/traced
        kernel retries a raising batch through."""
        n = len(keys)
        p, kp = _arg_parts()
        lists = [_pylist(_materialize(x[0](cols, keys), n)) for x in p]
        klists = {
            k: _pylist(_materialize(x[0](cols, keys), n))
            for k, x in kp.items()
        }
        UDF_STATS["perrow_rows_total"] += n
        return _dispatch_perrow(
            fn_user, lists, klists, n, prop_none, expr._return_type
        )

    def _guard(vec: Callable) -> Callable:
        def fn(cols, keys):
            try:
                return vec(cols, keys)
            except Exception:
                return _perrow(cols, keys)

        return fn

    def _args_optional() -> bool:
        """Optional args stay off the static lift: a lifted kernel
        propagates None through _objsafe while the per-row path raises
        into a per-row Error — the runtime trace handles optional
        streams instead (its signature guard routes None-carrying
        batches per-row). Plain column refs resolve without building
        their kernels, preserving the lift fast path's lazy arg builds;
        only computed argument trees force a real build."""
        computed = False
        for a in list(expr._args) + list(expr._kwargs.values()):
            if isinstance(a, ColumnConstExpression):
                continue
            if isinstance(a, ColumnReference):  # incl. IdReference
                try:
                    _, d = env.resolve(a)
                except KeyError:
                    return True  # unresolvable here: stay off the lift
                if d.is_optional:
                    return True
                continue
            computed = True
        if computed:
            p, kp = _arg_parts()
            return any(x[1].is_optional for x in p + list(kp.values()))
        return False

    def _note_outcome(status: str, refusal: str | None = None) -> None:
        # static-analysis breadcrumb (analysis/passes.py dispatch-tax
        # pass): which ladder rung this apply landed on, and — when it
        # fell off the static lift — exactly why
        try:
            expr._pw_lift_outcome = {
                "status": status,
                "refusal": refusal,
                "traceable": None,  # filled on the dynamic path
            }
        except (AttributeError, TypeError):
            pass

    #: why the static lift was not even attempted (analysis surfaces it)
    refusal_reason: str | None = None
    if not lift_eligible:
        if not deterministic:
            refusal_reason = "declared non-deterministic"
        elif is_coro:
            refusal_reason = "async UDF"
        elif prop_none:
            refusal_reason = "propagate_none=True"
        else:
            refusal_reason = "PATHWAY_UDF_LIFT=off"

    # ---- 1. static lift (exec trace, then AST) -----------------------
    if lift_eligible and getattr(fn_user, "__code__", None) is not None:
        if (
            fn_user.__code__ in _LIFT_REFUSED_CODES
            and _lift_key() in _LIFT_REFUSED
        ):
            # memoized refusal: skip the re-trace, keep the recorded why
            refusal_reason = _LIFT_REFUSED[_lift_key()]
        elif _args_optional():
            refusal_reason = (
                "Optional-dtype arguments (runtime probe-trace handles "
                "None-carrying batches instead)"
            )
        else:
            traced = None
            gate_reason = _liftable_reason(fn_user)
            if gate_reason is None:
                # execution trace (reference expression.rs:325 — no
                # Python in the hot loop): call the lambda on the
                # ARGUMENT EXPRESSIONS; a pure-operator lambda returns a
                # ColumnExpression tree
                try:
                    traced = fn_user(*expr._args, **expr._kwargs)
                except Exception:
                    traced = None
                if not isinstance(traced, ColumnExpression) or isinstance(
                    traced, (ApplyExpression, AsyncApplyExpression)
                ):
                    traced = None
            if traced is None:
                # widened AST lift: method chains, dict access,
                # conditionals, builtin subset — no user code runs
                from .udf_lift import ast_lift

                ast_why: list = []
                traced = ast_lift(
                    fn_user, expr._args, expr._kwargs, reason_out=ast_why
                )
                if traced is None:
                    refusal_reason = gate_reason or (
                        f"AST lift: {ast_why[0]}" if ast_why
                        else "AST lift refused"
                    )
            lifted = None
            if traced is not None:
                try:
                    lifted, _odt, agg = _build(traced, env)
                except Exception as e:
                    # the traced tree may hit operator/dtype combinations
                    # the columnar compiler refuses (e.g. str * int);
                    # per-row Python still handles those
                    lifted = None
                    refusal_reason = (
                        f"columnar compile refused the lifted tree: {e}"
                    )
            if lifted is not None:
                UDF_STATS["lifted_total"] += 1
                _note_outcome("lifted")
                return (
                    _align_dtype(_guard(lifted), expr._return_type),
                    expr._return_type, agg,
                )
            from .udf_lift import evict_oldest_half

            if len(_LIFT_REFUSED) >= 4096:
                evict_oldest_half(_LIFT_REFUSED)
                _LIFT_REFUSED_CODES.clear()
                _LIFT_REFUSED_CODES.update(k[0] for k in _LIFT_REFUSED)
            _LIFT_REFUSED[_lift_key()] = refusal_reason
            _LIFT_REFUSED_CODES.add(fn_user.__code__)

    parts, kparts = _arg_parts()

    if is_coro:
        def fn_async(cols, keys):
            n = len(keys)
            arrs = [_materialize(p[0](cols, keys), n) for p in parts]
            karrs = {
                k: _materialize(p[0](cols, keys), n)
                for k, p in kparts.items()
            }

            async def gather():
                return await asyncio.gather(*[
                    fn_user(
                        *[_unnp(a[i]) for a in arrs],
                        **{k: _unnp(v[i]) for k, v in karrs.items()},
                    )
                    for i in range(n)
                ], return_exceptions=True)

            results = _run_async(gather())
            out = np.empty(n, dtype=object)
            for i, r in enumerate(results):
                if isinstance(r, BaseException):
                    if not isinstance(r, Exception):
                        raise r  # CancelledError etc. must not become data
                    out[i] = EngineError(
                        f"{type(r).__name__}: {r}",
                        getattr(fn_user, "__name__", "async apply"),
                    )
                else:
                    out[i] = r
            return _densify(out, expr._return_type)

        _note_outcome("async", refusal_reason)
        return fn_async, expr._return_type, False

    # ---- 2./3. runtime: probe-row trace, else vectorized per-row -----
    trace_ok = False
    if trace_eligible:
        from .udf_lift import traceable

        trace_ok = traceable(fn_user)
    _note_outcome("dynamic", refusal_reason)
    try:
        expr._pw_lift_outcome["traceable"] = trace_ok
    except (AttributeError, TypeError):
        pass
    plans: dict[tuple, Callable] = {}
    refused_sigs: set = set()

    def _try_trace(sig, arrs, karrs, cols, keys):
        from .udf_lift import TraceRefused, trace_probe

        try:
            probe = [_unnp(a[0]) for a in arrs]
            kprobe = {k: _unnp(v[0]) for k, v in karrs.items()}
            texpr, probe_val = trace_probe(
                fn_user, probe, list(expr._args), kprobe, dict(expr._kwargs)
            )
            kernel, _odt, _agg = _build(texpr, env)
            kernel = _align_dtype(kernel, expr._return_type)
            # consistency check: the compiled plan must reproduce the
            # probe row's genuine result before it serves the stream
            row0 = {c: a[:1] for c, a in cols.items()}
            got = _unnp(_materialize(kernel(row0, keys[:1]), 1)[0])
            same = got == probe_val or (
                isinstance(got, float)
                and isinstance(probe_val, float)
                and np.isnan(got)
                and np.isnan(probe_val)
            )
            if not bool(same):
                raise TraceRefused
        except (TraceRefused, Exception):
            refused_sigs.add(sig)
            return None
        plans[sig] = kernel
        UDF_STATS["traced_total"] += 1
        return kernel

    def fn(cols, keys):
        n = len(keys)
        arrs = [_materialize(p[0](cols, keys), n) for p in parts]
        karrs = {
            k: _materialize(p[0](cols, keys), n) for k, p in kparts.items()
        }
        if trace_ok and n:
            sig = _dtype_sig(arrs, karrs)
            if sig is not None:
                plan = plans.get(sig)
                if plan is None and sig not in refused_sigs:
                    plan = _try_trace(sig, arrs, karrs, cols, keys)
                if plan is not None:
                    try:
                        return plan(cols, keys)
                    except Exception:
                        pass  # batch-wide raise: exact per-row semantics
        lists = [_pylist(a) for a in arrs]
        klists = {k: _pylist(v) for k, v in karrs.items()}
        UDF_STATS["perrow_rows_total"] += n
        return _dispatch_perrow(
            fn_user, lists, klists, n, prop_none, expr._return_type
        )

    return fn, expr._return_type, False


#: (fn code, arg dtypes) -> refusal reason (str | None) of apply lambdas
#: whose lift attempt failed — rebuilds skip the re-trace and land on the
#: per-row kernel directly, carrying the recorded reason into the
#: dispatch-tax lint diagnostic.
#: Insertion-ordered dict so hitting the cap evicts the OLDEST half
#: instead of clearing wholesale (a long-lived multi-pipeline process
#: must not re-trace every lambda at once); _LIFT_REFUSED_CODES is
#: rebuilt from the surviving keys on every eviction.
#: Two-level: the dtype-qualified key is only computed (it forces the
#: arg builds) for code objects that have SOME refusal on record —
#: never-refused lambdas pay nothing on the lift fast path
_LIFT_REFUSED: dict = {}
_LIFT_REFUSED_CODES: set = set()
#: liftability verdict per code object (bytecode-only property, so the
#: code object is the exact cache key); skips the dis scan on rebuilds.
#: Value is None (liftable) or the first blocking construct as a string
#: (surfaced verbatim by the per-row dispatch-tax lint diagnostic)
_LIFTABLE_CACHE: dict[Any, str | None] = {}


def _liftable(fn: Callable) -> bool:
    return _liftable_reason(fn) is None


def _liftable_reason(fn: Callable) -> str | None:
    """Safe to trace symbolically: a plain function whose bytecode contains
    no calls, no global/closure reads and no imports — so executing it once
    on expression placeholders cannot run user side effects per trace that
    the per-row path would have run per row, and captures no late-binding
    state. Operator expressions (``lambda x: x * 2 + 1``) pass; anything
    calling functions, reading globals/closures, or branching on values
    (guarded separately by ColumnExpression.__bool__ raising) falls back.
    Returns None when liftable, else the first blocking construct (the
    dispatch-tax diagnostic surfaces it verbatim). Memoized per code
    object — the verdict is a pure bytecode property."""
    code = getattr(fn, "__code__", None)
    if code is not None and code in _LIFTABLE_CACHE:
        return _LIFTABLE_CACHE[code]
    import dis

    try:
        instructions = list(dis.get_instructions(fn))
    except TypeError:
        return "not introspectable bytecode"
    blocked = (
        "CALL", "LOAD_GLOBAL", "LOAD_DEREF", "IMPORT", "MAKE_FUNCTION",
        # writes are side effects too: lifting would elide the per-row
        # store and leave the target bound to an expression placeholder
        "STORE_GLOBAL", "STORE_DEREF", "STORE_ATTR", "STORE_SUBSCR",
        # iteration over a ColumnExpression placeholder never terminates
        # (__getitem__ exists, __iter__ does not → legacy protocol spins)
        "GET_ITER", "FOR_ITER", "GET_AITER",
        # generator/comprehension machinery implies iteration as well
        "YIELD", "RETURN_GENERATOR",
        # identity tests fold silently at trace time: `a is None` on the
        # placeholder is plain False with NO __bool__ call, so a
        # None-handling branch would vanish from the traced tree
        "IS_OP", "POP_JUMP_IF_NONE", "POP_JUMP_IF_NOT_NONE",
    )
    verdict: str | None = None
    for ins in instructions:
        if ins.opname.startswith(blocked):
            what = f" ({ins.argval})" if isinstance(ins.argval, str) else ""
            verdict = f"bytecode gate: {ins.opname}{what}"
            break
    if code is not None:
        if len(_LIFTABLE_CACHE) >= 1024:
            from .udf_lift import evict_oldest_half

            evict_oldest_half(_LIFTABLE_CACHE)
        _LIFTABLE_CACHE[code] = verdict
    return verdict


def _align_dtype(fn: Callable, want: dt.DType) -> Callable:
    """Cast a lifted-apply column to the dtype the ``apply`` declared, so
    downstream consumers see the same runtime dtype the per-row path's
    ``_densify`` would have produced (e.g. int arithmetic lifted under a
    declared float return)."""
    target = {
        dt.INT: np.int64, dt.FLOAT: np.float64, dt.BOOL: np.bool_
    }.get(want)
    if target is None:
        return fn

    def cast(cols, keys):
        out = fn(cols, keys)
        # anything without a dtype (a constant's row value) passes through
        dtype = getattr(out, "dtype", None)
        if (
            dtype is not None
            and getattr(dtype, "kind", None) in "ifb"
            and getattr(out, "ndim", None) == 1
            and np.dtype(dtype) != target
        ):
            return out.astype(target)
        return out

    return cast


def _run_async(coro):
    import asyncio

    try:
        loop = asyncio.get_running_loop()
    except RuntimeError:
        return asyncio.run(coro)
    import concurrent.futures

    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        return pool.submit(asyncio.run, coro).result()


def _materialize(v: Any, n: int) -> np.ndarray:
    if isinstance(v, np.ndarray) and v.ndim == 1 and len(v) == n:
        return v
    out = np.empty(n, dtype=object)
    if isinstance(v, np.ndarray):
        out[:] = list(v)
    else:
        # fill() assigns the object per cell — slice-assigning tuple/list
        # values would make numpy broadcast them as nested arrays
        out.fill(v)
    return out


def _unnp(v: Any) -> Any:
    if isinstance(v, np.generic):
        return v.item()
    return v


def _densify(arr: np.ndarray, dtype: dt.DType) -> np.ndarray:
    """Try to store an object array densely according to its declared dtype."""
    if arr.dtype != object:
        return arr
    target = dtype.numpy_dtype
    if target == np.dtype(object) or dtype.is_optional:
        return arr
    try:
        return arr.astype(target)
    except (ValueError, TypeError):
        return arr


def _binop_fn(op, lf, rf, ldt, rdt):
    lu, ru = dt.unoptionalize(ldt), dt.unoptionalize(rdt)

    if op in ("/", "//", "%") and (
        op != "/" or (lu in _NUMERIC and ru in _NUMERIC)
    ):
        base = {
            "/": np.true_divide, "//": np.floor_divide, "%": np.mod
        }[op]

        def vec(lv, rv, keys):
            ra = np.asarray(rv)
            if ra.dtype.kind in "iuf":
                zeros = ra == 0
                if zeros.any():
                    # reference DivisionByZero (expression.rs:846,935):
                    # zero denominators yield per-row Error values, not
                    # numpy's silent 0/inf
                    n = len(keys)
                    with np.errstate(divide="ignore", invalid="ignore"):
                        res = base(lv, rv)
                    out = _materialize(res, n).astype(object)
                    for i in np.flatnonzero(np.broadcast_to(zeros, (n,))):
                        out[i] = EngineError("division by zero", op)
                    return out
            return base(lv, rv)

        return _objsafe(vec, op, lf, rf)
    if op == "&" and lu == dt.BOOL and ru == dt.BOOL:
        return _objsafe(
            lambda lv, rv, keys: np.logical_and(lv, rv), op, lf, rf
        )
    if op == "|" and lu == dt.BOOL and ru == dt.BOOL:
        return _objsafe(
            lambda lv, rv, keys: np.logical_or(lv, rv), op, lf, rf
        )

    import operator as _op

    py_ops = {
        "+": _op.add, "-": _op.sub, "*": _op.mul, "/": _op.truediv,
        "**": _op.pow, "==": _op.eq, "!=": _op.ne, "<": _op.lt,
        "<=": _op.le, ">": _op.gt, ">=": _op.ge, "&": _op.and_,
        "|": _op.or_, "^": _op.xor, "@": _op.matmul,
        "<<": _op.lshift, ">>": _op.rshift,
    }
    f = py_ops[op]

    if op in _CMP_OPS and (lu == dt.POINTER or ru == dt.POINTER):
        def fn(cols, keys):
            return f(np.asarray(lf(cols, keys), dtype=np.uint64), np.asarray(rf(cols, keys), dtype=np.uint64))
        return fn

    if op == "@":
        def fn_mm(cols, keys):
            l, r = lf(cols, keys), rf(cols, keys)
            n = len(keys)
            la, ra = _materialize(l, n), _materialize(r, n)
            out = np.empty(n, dtype=object)
            for i in range(n):
                out[i] = la[i] @ ra[i]
            return out
        return fn_mm
    if op in ("+", "-", "*", "/", "**", "==", "!=", "<", "<=", ">", ">=",
              "&", "|", "^", "<<", ">>"):
        # object columns may carry None/Error rows — handle per element.
        # Applied even for statically dense dtypes: upstream zero-division
        # injects Error rows into columns typed non-optional, and _objsafe
        # only pays one dtype check when the operands stay dense
        return _objsafe(lambda lv, rv, keys: f(lv, rv), op, lf, rf)
    raise AssertionError(f"unhandled binop {op!r}")  # every py_ops key is covered above


def _objsafe(vec_fn, op, lf, rf):
    """Wrap a value-level vectorized op: operands are evaluated ONCE, then
    either handed to ``vec_fn`` (dense fast path) or walked per-row with
    None/Error semantics. ``vec_fn(lv, rv, keys)`` must not re-invoke the
    operand closures — that re-evaluation compounds 2**depth over nested
    expressions (review finding r3)."""
    import operator as _op

    py_ops = {
        "+": _op.add, "-": _op.sub, "*": _op.mul, "/": _op.truediv,
        "//": _op.floordiv, "%": _op.mod, "**": _op.pow,
        "==": _op.eq, "!=": _op.ne, "<": _op.lt, "<=": _op.le,
        ">": _op.gt, ">=": _op.ge,
        "&": lambda a, b: (a and b) if isinstance(a, (bool, np.bool_)) else a & b,
        "|": lambda a, b: (a or b) if isinstance(a, (bool, np.bool_)) else a | b,
        "^": _op.xor, "<<": _op.lshift, ">>": _op.rshift,
    }
    f = py_ops[op]

    def fn(cols, keys):
        l, r = lf(cols, keys), rf(cols, keys)
        lo = isinstance(l, np.ndarray) and l.dtype == object
        ro = isinstance(r, np.ndarray) and r.dtype == object
        if not lo and not ro:
            return vec_fn(l, r, keys)
        n = len(keys)
        la, ra = _materialize(l, n), _materialize(r, n)
        out = np.empty(n, dtype=object)
        for i in range(n):
            a, b = _unnp(la[i]), _unnp(ra[i])
            if isinstance(a, EngineError):
                out[i] = a  # errors flow through expressions (value.rs:226)
            elif isinstance(b, EngineError):
                out[i] = b
            elif a is None or b is None:
                out[i] = None
            else:
                try:
                    out[i] = f(a, b)
                except Exception as e:  # noqa: BLE001 — row error, not batch
                    # reference: any DataError becomes a per-row Value::Error
                    out[i] = EngineError(f"{type(e).__name__}: {e}", op)
        return out

    return fn


def _cast_fn(f, src: dt.DType, target: dt.DType):
    tu = dt.unoptionalize(target)
    su = dt.unoptionalize(src)

    def convert_scalar(v):
        if v is None or isinstance(v, EngineError):
            return v
        if isinstance(v, Json):
            # .as_int()/.as_str()/… are STRICT typed accessors over the
            # json VALUE (reference expression.py as_* over Value::Json):
            # a type mismatch yields None per the Optional return type —
            # and str(Json) would re-serialize ('"x"', not 'x')
            v = v.value
            if tu == dt.INT:
                return v if type(v) is int else None
            if tu == dt.FLOAT:
                return float(v) if type(v) in (int, float) else None
            if tu == dt.BOOL:
                return v if type(v) is bool else None
            if tu == dt.STR:
                return v if type(v) is str else None
            return v
        if tu == dt.INT:
            return int(v)
        if tu == dt.FLOAT:
            return float(v)
        if tu == dt.BOOL:
            return bool(v)
        if tu == dt.STR:
            return str(v)
        return v

    def fn(cols, keys):
        v = f(cols, keys)
        n = len(keys)
        arr = _materialize(v, n) if not isinstance(v, np.ndarray) else v
        if arr.dtype == object:
            out = np.empty(n, dtype=object)
            for i in range(n):
                out[i] = convert_scalar(arr[i])
            return _densify(out, target)
        if tu == dt.INT:
            return np.asarray(arr).astype(np.int64)
        if tu == dt.FLOAT:
            return np.asarray(arr).astype(np.float64)
        if tu == dt.BOOL:
            return np.asarray(arr).astype(bool)
        if tu == dt.STR:
            out = np.empty(n, dtype=object)
            av = np.asarray(arr)
            for i in range(n):
                out[i] = str(_unnp(av[i]))
            return out
        return arr

    return fn
