"""Metadata filter expressions for index queries.

The reference filters candidate documents with JMESPath boolean queries
(``src/external_integration/mod.rs:373``, via the jmespath crate). That
library isn't in this environment, so this module implements the subset the
indexing/RAG surfaces actually use, compiled to a Python predicate over the
metadata JSON dict:

    path.to.field == 'value'      (also != < <= > >=; numbers via `123`)
    contains(path, 'x')           starts_with / ends_with
    globmatch('pat', path)        glob on string fields
    expr && expr, expr || expr, !expr, parentheses

One parser, one tree, two evaluators: ``compile_metadata_filter`` gives the
predicate over one dict (what a filter means), ``eval_filter_columns`` the
same answers for many slots at once from dictionary-encoded columns, the
leaves computed by the very code of the predicate (``_compare``, ``_call``)
once for each distinct value and never once for each slot.
"""

from __future__ import annotations

import fnmatch
import re
from typing import Any, Callable

import numpy as np

__all__ = [
    "compile_metadata_filter", "parse_metadata_filter", "eval_filter_columns",
    "lookup_path", "FilterSyntaxError",
]


class FilterSyntaxError(ValueError):
    pass


_TOKEN = re.compile(
    r"\s*(?:(?P<op>==|!=|<=|>=|<|>|&&|\|\||!|\(|\)|,)"
    r"|(?P<str>'[^']*'|\"[^\"]*\")"
    r"|(?P<tick>`[^`]*`)"
    r"|(?P<num>-?\d+(?:\.\d+)?)"
    r"|(?P<ident>[A-Za-z_][A-Za-z0-9_.]*))"
)


def _lex(src: str) -> list[tuple[str, str]]:
    out, pos = [], 0
    while pos < len(src):
        m = _TOKEN.match(src, pos)
        if m is None:
            if src[pos:].strip() == "":
                break
            raise FilterSyntaxError(f"bad filter syntax at {src[pos:]!r}")
        pos = m.end()
        for kind in ("op", "str", "tick", "num", "ident"):
            tok = m.group(kind)
            if tok is not None:
                out.append((kind, tok))
                break
    return out


class _Parser:
    """Recursive descent: or → and → unary → comparison/primary."""

    def __init__(self, tokens: list[tuple[str, str]]):
        self.toks = tokens
        self.i = 0

    def peek(self):
        return self.toks[self.i] if self.i < len(self.toks) else (None, None)

    def take(self):
        tok = self.peek()
        self.i += 1
        return tok

    def expect(self, value: str):
        kind, tok = self.take()
        if tok != value:
            raise FilterSyntaxError(f"expected {value!r}, got {tok!r}")

    def parse(self):
        node = self.or_expr()
        if self.i != len(self.toks):
            raise FilterSyntaxError(f"trailing tokens: {self.toks[self.i:]}")
        return node

    def or_expr(self):
        node = self.and_expr()
        while self.peek() == ("op", "||"):
            self.take()
            rhs = self.and_expr()
            node = ("or", node, rhs)
        return node

    def and_expr(self):
        node = self.unary()
        while self.peek() == ("op", "&&"):
            self.take()
            rhs = self.unary()
            node = ("and", node, rhs)
        return node

    def unary(self):
        if self.peek() == ("op", "!"):
            self.take()
            return ("not", self.unary())
        if self.peek() == ("op", "("):
            self.take()
            node = self.or_expr()
            self.expect(")")
            return self.maybe_comparison(node)
        return self.comparison()

    def value(self):
        kind, tok = self.take()
        if kind == "str":
            return ("lit", tok[1:-1])
        if kind == "num":
            return ("lit", float(tok) if "." in tok else int(tok))
        if kind == "tick":
            import json

            return ("lit", json.loads(tok[1:-1]))
        if kind == "ident":
            if tok in ("contains", "starts_with", "ends_with", "globmatch"):
                if self.peek() == ("op", "("):
                    self.take()
                    a = self.value()
                    self.expect(",")
                    b = self.value()
                    self.expect(")")
                    return ("call", tok, a, b)
            if tok == "true":
                return ("lit", True)
            if tok == "false":
                return ("lit", False)
            if tok == "null":
                return ("lit", None)
            return ("path", tok.split("."))
        raise FilterSyntaxError(f"unexpected token {tok!r}")

    def comparison(self):
        return self.maybe_comparison(self.value())

    def maybe_comparison(self, lhs):
        kind, tok = self.peek()
        if kind == "op" and tok in ("==", "!=", "<", "<=", ">", ">="):
            self.take()
            rhs = self.value()
            return ("cmp", tok, lhs, rhs)
        return lhs


def lookup_path(meta: Any, path: "list[str] | tuple[str, ...]") -> Any:
    """The value a filter's ``path`` reads in one metadata dict, or None."""
    cur = meta
    for p in path:
        if isinstance(cur, dict):
            cur = cur.get(p)
        else:
            return None
    return cur


def _compare(op: str, l: Any, r: Any) -> bool:
    try:
        if op == "==":
            return l == r
        if op == "!=":
            return l != r
        if l is None or r is None:
            return False
        if op == "<":
            return l < r
        if op == "<=":
            return l <= r
        if op == ">":
            return l > r
        return l >= r
    except TypeError:
        return False


def _call(fn: str, a: Any, b: Any) -> bool:
    if fn == "globmatch":
        # jmespath-extension argument order: globmatch(pattern, field)
        return isinstance(b, str) and isinstance(a, str) and fnmatch.fnmatch(b, a)
    if not isinstance(a, str):
        if fn == "contains" and isinstance(a, (list, tuple)):
            return b in a
        return False
    b = "" if b is None else str(b)
    if fn == "contains":
        return b in a
    if fn == "starts_with":
        return a.startswith(b)
    return a.endswith(b)


def _eval(node, meta: Any) -> Any:
    tag = node[0]
    if tag == "lit":
        return node[1]
    if tag == "path":
        return lookup_path(meta, node[1])
    if tag == "and":
        return bool(_eval(node[1], meta)) and bool(_eval(node[2], meta))
    if tag == "or":
        return bool(_eval(node[1], meta)) or bool(_eval(node[2], meta))
    if tag == "not":
        return not bool(_eval(node[1], meta))
    if tag == "cmp":
        return _compare(node[1], _eval(node[2], meta), _eval(node[3], meta))
    if tag == "call":
        return _call(node[1], _eval(node[2], meta), _eval(node[3], meta))
    raise FilterSyntaxError(f"cannot evaluate node {node!r}")


def parse_metadata_filter(src: Any) -> tuple:
    """The tree both evaluators walk. A callable is a predicate over the
    whole metadata: ``compile_metadata_filter`` hands it back untouched, and
    over columns it is one node (``pred``) of the tree."""
    if callable(src):
        return ("pred", src)
    return _Parser(_lex(str(src))).parse()


# -- the same tree over columns ---------------------------------------------
# A value of many slots is (codes, values): ``values[codes[i]]`` is slot i's,
# and ``codes`` None means one value for every slot (a literal).


def _pairs(fn: Callable[[Any, Any], Any], a, b):
    """``fn`` of two such values, called once for each distinct pair: every
    pair there can be, or where the slots are fewer than those (a few slots
    of a column of many values, as a write evaluates) only the pairs they
    hold."""
    (ca, va), (cb, vb) = a, b
    if ca is None and cb is None:
        return None, [fn(va[0], vb[0])]
    if ca is None or cb is None:
        codes = cb if ca is None else ca
    else:
        codes = ca.astype(np.int64) * len(vb) + cb
    pairs = range(len(va) * len(vb))
    if len(pairs) > len(codes):
        present, codes = np.unique(codes, return_inverse=True)
        pairs = present.tolist()
    return codes, [fn(va[p // len(vb)], vb[p % len(vb)]) for p in pairs]


def _truth(value, n: int) -> np.ndarray:
    codes, values = value
    truth = np.fromiter((bool(v) for v in values), bool, len(values))
    return np.broadcast_to(truth, n) if codes is None else truth[codes]


def _eval_columns(node, column, n: int):
    tag = node[0]
    if tag == "lit":
        return None, [node[1]]
    if tag == "path":
        return column(tuple(node[1]))
    if tag == "pred":
        codes, values = column(())
        return codes, [node[1](v) for v in values]
    if tag == "cmp":
        op = node[1]
        return _pairs(lambda l, r: _compare(op, l, r),
                      _eval_columns(node[2], column, n), _eval_columns(node[3], column, n))
    if tag == "call":
        fn = node[1]
        return _pairs(lambda a, b: _call(fn, a, b),
                      _eval_columns(node[2], column, n), _eval_columns(node[3], column, n))
    if tag == "not":
        keep = ~_truth(_eval_columns(node[1], column, n), n)
    elif tag in ("and", "or"):
        l = _truth(_eval_columns(node[1], column, n), n)
        r = _truth(_eval_columns(node[2], column, n), n)
        keep = l & r if tag == "and" else l | r
    else:
        raise FilterSyntaxError(f"cannot evaluate node {node!r}")
    return keep.astype(np.intp), [False, True]


def eval_filter_columns(
    ast: tuple,
    column: "Callable[[tuple[str, ...]], tuple[np.ndarray, list]]",
    n: int,
) -> np.ndarray:
    """What ``compile_metadata_filter``'s predicate gives for each of ``n``
    slots, as a boolean array. ``column(path)`` is (codes [n], values) with
    ``values[codes[i]]`` what ``lookup_path`` reads at ``path`` in slot i's
    metadata (None for a slot with none); the path ``()`` is the whole
    metadata, which only a callable filter reads."""
    return np.array(_truth(_eval_columns(ast, column, n), n))


def compile_metadata_filter(src: Any) -> Callable[[Any], bool] | None:
    """Compile a filter string to a predicate over a metadata dict.
    None (or None-valued cell) means "match everything"."""
    if src is None:
        return None
    if callable(src):
        return src
    ast = parse_metadata_filter(src)

    def predicate(meta: Any) -> bool:
        return bool(_eval(ast, meta if meta is not None else {}))

    return predicate
