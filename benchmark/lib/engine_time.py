"""The traced window on the engine thread, by what the thread was in: what the
host was doing while the device sat idle.

Every device program of a cell is enqueued and waited for inside an
``index.search``, and the engine thread runs one tick at a time, so the
window falls into five stretches (``partition``), all from the program's own
spans (``lib/program_spans.py``, on ``time.monotonic()`` like the window):

- a tick's *device envelope*: from the start of the first span under its
  ``index.search`` that hands the device work (``index.upload``,
  ``index.mask`` or ``embed.dispatch``, whichever comes first) to the end of
  its last ``index.fetch``;
- its *head*, tick start to envelope start (the nodes ahead of the search,
  the tokenizer), and its *tail*, envelope end to tick end (the reply's way
  out); a tick that holds no search is all head;
- *parked*: the union of ``engine.park``;
- *between*: what is left, tick end to the next tick's start less the park:
  ``engine.poll`` and time under no span (the thread did not run).

The device is busy only inside envelopes, so with the device trace's busy
seconds ``100 * (head + tail + between + parked + envelope - busy_s) /
window`` is ``device_idle_pct``: four shares from the spans, one tied to the
device trace, and that they meet is the check that the two clocks are one.

A span file with no ``engine.poll`` is of a program that does not cover its
engine thread (before PR 38): ``spans_of`` gives None and so does every reader
built on it. ``program_spans.load`` keeps the spans that *started* inside the
window, so the tick under way when the window opens counts as between (half
a tick on average, 0.2% of 5 s); spans are cut at the window's end.
"""

from __future__ import annotations

from lib import program_spans as ps

#: the spans under an ``index.search`` that hand the device its first work
HANDS_OVER = ("index.upload", "index.mask", "embed.dispatch")


def spans_of(cell: dict) -> list[dict] | None:
    """The cell's traced spans if the program covers its engine thread."""
    spans = ps.load(cell)
    return spans if ps.named(spans, "engine.poll") else None


def envelopes(spans: list[dict]) -> dict:
    """{tick id: (start, end)} of the device envelope of every tick that has one."""
    searches: dict = {}
    for s in ps.named(spans, "index.search"):
        searches.setdefault(s["args"].get("tick"), []).append(s)
    first: dict = {}
    last: dict = {}
    for s in spans:
        tick = s["args"].get("tick")
        if s["name"] in HANDS_OVER and any(
                q["t0"] <= s["t0"] <= q["t1"] for q in searches.get(tick, ())):
            first[tick] = min(first.get(tick, s["t0"]), s["t0"])
        elif s["name"] == "index.fetch" and tick in searches:
            last[tick] = max(last.get(tick, s["t1"]), s["t1"])
    return {t: (first[t], last[t]) for t in first if t in last and last[t] > first[t]}


def partition(spans: list[dict] | None, tw: dict | None) -> dict | None:
    """Seconds of the window in ``head``, ``envelope``, ``tail``, ``between``
    and ``parked`` (they sum to ``window``), and ``ticks``; None for spans
    that do not cover the engine thread."""
    if not ps.named(spans, "engine.poll"):
        return None
    t0, t1 = tw["t0"], tw["t1"]

    def cut(a: float, b: float) -> float:
        return max(0.0, min(b, t1) - max(a, t0))

    found = envelopes(spans)
    out = {"head": 0.0, "envelope": 0.0, "tail": 0.0}
    ticks = ps.named(spans, "tick")
    for tick in ticks:
        a, b = tick["t0"], tick["t1"]
        e0, e1 = found.get(tick["args"].get("tick"), (b, b))
        e0, e1 = min(max(e0, a), b), min(max(e1, a), b)
        out["head"] += cut(a, e0)
        out["envelope"] += cut(e0, e1)
        out["tail"] += cut(e1, b)
    out["parked"] = ps.union_s(ps.named(spans, "engine.park"), t0, t1)
    out["window"] = t1 - t0
    out["between"] = out["window"] - sum(out[k] for k in ("head", "envelope", "tail", "parked"))
    out["ticks"] = len(ticks)
    return out


def share_pct(cell: dict, part: str) -> float | None:
    """``part`` of the cell's partition as a percentage of its traced window."""
    split = partition(spans_of(cell), cell.get("trace_window"))
    return None if split is None else 100.0 * split[part] / split["window"]


def nodes_ms(cell: dict, family: str) -> float | None:
    """Median, over the ticks that hold a search, of the time of the tick's
    node events of one class (``Join#15`` + ``Join#23`` + ...)."""
    spans = spans_of(cell)
    total = {tick["args"]["tick"]: 0.0 for tick, _ in ps.ticks_holding(spans, "index.search")}
    for s in spans or ():
        if s["name"].startswith(family + "#") and s["args"].get("tick") in total:
            total[s["args"]["tick"]] += ps.ms(s)
    return ps.median(list(total.values()))
