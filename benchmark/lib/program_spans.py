"""The spans the program itself records while a profiler session is live
(``pathway_tpu/internals/tracing.py``), for the per-layer readers.

The harness carries nothing the program recorded on the host, so the readers
find the file themselves: the program's end-of-run flush writes Chrome-trace
JSON to ``<tempfile.gettempdir()>/pathway-tpu/spans/<pid>.json`` when spans
were recorded only because a profile was being taken. Its
``trace.clock_sync`` metadata holds ``origin_monotonic_ns``, so that
``origin_monotonic_ns + ts`` is ``time.monotonic_ns()``: the clock of
``cell["trace_window"]``. A program that records no such file (the parent of
the PR that added this) gives every reader ``None``.
"""

from __future__ import annotations

import functools
import json
import os
import tempfile


def spans_dir() -> str:
    return os.path.join(tempfile.gettempdir(), "pathway-tpu", "spans")


@functools.lru_cache(maxsize=8)
def _read(path: str, mtime_ns: int) -> list[dict] | None:
    """The file's complete spans as {name, t0, t1, args}, seconds on
    ``time.monotonic()``; None for a file that is no span file. Each reader
    of a run asks for the same file: parsed once."""
    try:
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        origin = next(e["args"]["origin_monotonic_ns"] for e in events
                      if e.get("name") == "trace.clock_sync")
    except (OSError, ValueError, KeyError, TypeError, StopIteration):
        return None
    return [{"name": e["name"], "t0": (origin + e["ts"] * 1e3) * 1e-9,
             "t1": (origin + (e["ts"] + e["dur"]) * 1e3) * 1e-9,
             "args": e.get("args") or {}}
            for e in events if e.get("ph") == "X"]


def load(cell: dict) -> list[dict] | None:
    """The program's spans that started inside the cell's traced window, from
    the newest span file that overlaps it; None when there is none."""
    tw, directory = cell.get("trace_window"), spans_dir()
    if not tw or not os.path.isdir(directory):
        return None
    files = [(os.stat(os.path.join(directory, n)).st_mtime_ns, os.path.join(directory, n))
             for n in os.listdir(directory) if n.endswith(".json")]
    for mtime_ns, path in sorted(files, reverse=True):
        spans = _read(path, mtime_ns)
        if spans and min(s["t0"] for s in spans) < tw["t1"] \
                and max(s["t1"] for s in spans) > tw["t0"]:
            return [s for s in spans if tw["t0"] <= s["t0"] < tw["t1"]] or None
    return None


def named(spans: list[dict] | None, name: str, **where) -> list[dict]:
    return [s for s in spans or () if s["name"] == name
            and all(s["args"].get(k) == v for k, v in where.items())]


def ms(span: dict) -> float:
    return (span["t1"] - span["t0"]) * 1e3


def median(values: list[float]) -> float | None:
    d = sorted(values)
    if not d:
        return None
    mid = len(d) // 2
    return d[mid] if len(d) % 2 else (d[mid - 1] + d[mid]) / 2


def by_id(spans: list[dict], key: str) -> dict:
    """{span's ``args[key]``: span}, the last of each id."""
    return {s["args"][key]: s for s in spans if key in s["args"]}


def ticks_holding(spans: list[dict] | None, child: str, **where) -> list[tuple[dict, list[dict]]]:
    """(tick, its ``child`` spans) for every tick that holds one."""
    ticks = by_id(named(spans, "tick"), "tick")
    held: dict = {}
    for s in named(spans, child, **where):
        if s["args"].get("tick") in ticks:
            held.setdefault(s["args"]["tick"], []).append(s)
    return [(ticks[t], kids) for t, kids in held.items()]


def searches(spans: list[dict] | None, **where) -> list[tuple[dict, dict[str, dict]]]:
    """(``index.search``, {name: child span}) for each search whose
    attributes equal ``where``: its children are the spans of its tick that
    name it as parent and lie inside it."""
    kids: dict = {}
    for s in spans or ():
        if s["args"].get("parent") == "index.search":
            kids.setdefault(s["args"].get("tick"), []).append(s)
    return [(s, {c["name"]: c for c in kids.get(s["args"].get("tick"), ())
                 if s["t0"] <= c["t0"] and c["t1"] <= s["t1"]})
            for s in named(spans, "index.search", **where)]


def union_s(spans: list[dict], t0: float, t1: float) -> float:
    """Seconds of [t0, t1) that the spans cover."""
    covered, end = 0.0, t0
    for s in sorted(spans, key=lambda s: s["t0"]):
        a, b = max(s["t0"], end), min(s["t1"], t1)
        if b > a:
            covered += b - a
            end = b
    return covered
