"""By hand, after a traced run: what the program's own spans of the traced
window say (``lib/program_spans.py``) — every span name with its count and
median, a request's path stage by stage, the engine thread's time by kind, and
what the engine did under the longest stretches in which no request ended.

    python3 benchmark/tests/dump_spans.py benchmark/out/<cell> [stretch ms]

Reads ``<out>/child_facts.json`` for the traced window and the newest span
file under ``<tempdir>/pathway-tpu/spans/`` that overlaps it.
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import program_spans as ps  # noqa: E402


def main() -> int:
    out_dir = sys.argv[1]
    stretch_ms = float(sys.argv[2]) if len(sys.argv) > 2 else 120.0
    with open(os.path.join(out_dir, "child_facts.json")) as f:
        tw = json.load(f)["trace_window"]
    spans = ps.load({"trace_window": tw})
    if not spans:
        print(f"no span file of the window {tw} under {ps.spans_dir()}")
        return 1
    width = tw["t1"] - tw["t0"]
    print(f"traced window {width:.3f} s; {len(spans)} spans started inside it")
    by_name = collections.defaultdict(list)
    for s in spans:
        by_name[s["name"]].append(ps.ms(s))
    for name, d in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
        print(f"  {name:28s} n {len(d):6d}  median {ps.median(d):9.3f} ms  "
              f"total {sum(d) / 1e3:8.3f} s")

    # -- a request, stage by stage (medians over the requests that have them all)
    ticks = ps.by_id(ps.named(spans, "tick"), "tick")
    parts = {n: ps.by_id(ps.named(spans, n), "req")
             for n in ("rest.admit", "rest.in_engine", "rest.reply")}
    search_of_tick = {s["args"].get("tick"): s for s in ps.named(spans, "index.search")}
    stages = collections.defaultdict(list)
    for r in ps.named(spans, "rest.request"):
        req = r["args"].get("req")
        if not all(req in p for p in parts.values()):
            continue
        admit, inside, reply = (parts[n][req] for n in parts)
        tick = ticks.get(inside["args"].get("tick"))
        search = search_of_tick.get(inside["args"].get("tick"))
        if tick is None or search is None:
            continue
        for name, a, b in (
            ("entry -> admitted", r["t0"], admit["t1"]),
            ("admitted -> row sent to the engine", admit["t1"], inside["t0"]),
            ("row sent -> its tick starts", inside["t0"], tick["t0"]),
            ("tick start -> search start", tick["t0"], search["t0"]),
            ("search", search["t0"], search["t1"]),
            ("search end -> future resolved", search["t1"], inside["t1"]),
            ("future resolved -> handler resumes", inside["t1"], reply["t0"]),
            ("reply", reply["t0"], reply["t1"]),
            ("reply end -> response returned", reply["t1"], r["t1"]),
            ("whole request", r["t0"], r["t1"]),
        ):
            stages[name].append((b - a) * 1e3)
    print("a request, stage by stage (median ms, p95 ms):")
    for name, d in stages.items():
        d.sort()
        print(f"  {name:38s} {ps.median(d):9.3f} {d[int(0.95 * (len(d) - 1))]:9.3f}  n {len(d)}")

    # -- the engine thread: parked, in ticks by kind, neither
    parked = ps.union_s(ps.named(spans, "engine.park"), tw["t0"], tw["t1"])
    kinds = collections.defaultdict(lambda: [0, 0.0])
    with_search = {id(t) for t, _ in ps.ticks_holding(spans, "index.search")}
    with_apply = {id(t) for t, _ in ps.ticks_holding(spans, "index.apply")}
    for t in ps.named(spans, "tick"):
        kind = ("with a search" if id(t) in with_search else
                "with writes" if id(t) in with_apply else
                f"other, rows_in {min(t['args'].get('rows_in', 0), 2)}{'+' if t['args'].get('rows_in', 0) > 2 else ''}")
        kinds[kind][0] += 1
        kinds[kind][1] += ps.ms(t)
    in_ticks = ps.union_s(ps.named(spans, "tick"), tw["t0"], tw["t1"])
    print(f"engine thread: parked {100 * parked / width:.1f}%, in ticks "
          f"{100 * in_ticks / width:.1f}%, neither {100 * (1 - (parked + in_ticks) / width):.1f}%")
    for kind, (n, total) in sorted(kinds.items(), key=lambda kv: -kv[1][1]):
        print(f"  ticks {kind:22s} n {n:5d}  mean {total / n:8.3f} ms  total {total / 1e3:7.3f} s")
    windows = ps.named(spans, "connector.window")
    print("connector windows by reason:",
          dict(collections.Counter(w["args"].get("reason") for w in windows)),
          "rows per window:",
          dict(collections.Counter(w["args"].get("rows") for w in windows).most_common(6)))

    # -- stretches in which no request ended, and what the engine did there
    ends = sorted(r["t1"] for r in ps.named(spans, "rest.request"))
    gaps = sorted(((b - a, a) for a, b in zip(ends, ends[1:])), reverse=True)
    print(f"longest stretches with no request ending (over {stretch_ms:.0f} ms are opened):")
    for gap, at in gaps[:5]:
        print(f"  {gap * 1e3:8.1f} ms at {at - tw['t0']:.3f} s of the trace")
        if gap * 1e3 < stretch_ms:
            continue
        for s in sorted(spans, key=lambda s: s["t0"]):
            if s["t1"] > at and s["t0"] < at + gap and s["name"] in (
                    "tick", "engine.park", "index.search", "index.fetch",
                    "index.upload", "index.apply", "connector.window"):
                print(f"      {s['name']:18s} +{(s['t0'] - at) * 1e3:8.2f} ms  "
                      f"{ps.ms(s):8.2f} ms  {s['args']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
