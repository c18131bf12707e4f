"""By hand, after a traced run: what the engine thread's own spans say of the
traced window that the per-layer metrics do not print (``lib/engine_time.py``
over ``lib/program_spans.py``) — the window split into head, envelope, tail,
between and parked, with the device's busy seconds beside them; every node of
a searching tick with its phases and its self time; ``engine.poll`` by
``rounds``; which engine span was open when a handler resumed (``rest.wake``);
and the longest stretches of the engine thread under no span.

    python3 benchmark/tests/dump_nodes.py benchmark/out/<cell> [stretches listed]

Reads ``<out>/child_facts.json`` for the traced window and the device's busy
time, and the newest span file under ``<tempdir>/pathway-tpu/spans/`` that
overlaps the window. Needs a program that covers its engine thread (PR 38).
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import engine_time  # noqa: E402
from lib import program_spans as ps  # noqa: E402

#: span names recorded on the engine thread (the REST handlers' and the
#: connectors' are on other threads)
ENGINE_PREFIXES = ("tick", "engine.", "index.", "embed.", "groupby.", "join.",
                   "subscribe.", "fusion.")


def on_engine_thread(span: dict) -> bool:
    return "#" in span["name"] or span["name"].startswith(ENGINE_PREFIXES)


def uncovered(spans: list[dict], tw: dict) -> list[tuple[float, float]]:
    """(length, start) of every stretch of the window under none of ``tick``,
    ``engine.poll`` and ``engine.park``, longest first."""
    cover = sorted((s["t0"], s["t1"]) for s in spans
                   if s["name"] in ("tick", "engine.poll", "engine.park"))
    gaps, end = [], tw["t0"]
    for a, b in cover:
        if a > end:
            gaps.append((min(a, tw["t1"]) - end, end))
        end = max(end, b)
        if end >= tw["t1"]:
            break
    if end < tw["t1"]:
        gaps.append((tw["t1"] - end, end))
    return sorted((g for g in gaps if g[0] > 0), reverse=True)


def open_at(engine_spans: list[dict], t: float) -> dict | None:
    """The innermost engine span open at ``t``."""
    inside = [s for s in engine_spans if s["t0"] <= t < s["t1"]]
    return max(inside, key=lambda s: s["t0"]) if inside else None


def switch_interval_s() -> float | None:
    """``switch_interval_s`` of the newest span file's ``trace.clock_sync``."""
    directory = ps.spans_dir()
    files = sorted((os.path.join(directory, n) for n in os.listdir(directory)
                    if n.endswith(".json")), key=os.path.getmtime)
    with open(files[-1]) as f:
        events = json.load(f)["traceEvents"]
    sync = next(e for e in events if e.get("name") == "trace.clock_sync")
    return sync["args"].get("switch_interval_s")


def main() -> int:
    out_dir = sys.argv[1]
    listed = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    with open(os.path.join(out_dir, "child_facts.json")) as f:
        facts = json.load(f)
    tw, trace = facts["trace_window"], facts.get("trace")
    spans = engine_time.spans_of({"trace_window": tw})
    if not spans:
        print(f"no span file under {ps.spans_dir()} covers the engine thread in the window {tw}")
        return 1
    width = tw["t1"] - tw["t0"]
    split = engine_time.partition(spans, tw)

    # -- the window by what the engine thread was in, beside the device's busy time
    print(f"traced window {width:.3f} s, {split['ticks']} ticks, one every "
          f"{1e3 * width / max(split['ticks'], 1):.2f} ms")
    for part in ("head", "envelope", "tail", "between", "parked"):
        print(f"  {part:9s} {split[part]:8.4f} s  {100 * split[part] / width:6.2f}%")
    if trace:
        busy, idle = trace["busy_s"], 100 * (1 - trace["busy_s"] / trace["window_s"])
        in_search = 100 * (split["envelope"] - busy) / width
        shares = sum(100 * split[p] / width for p in ("head", "tail", "between", "parked"))
        print(f"  device busy {busy:.4f} s of a device window of {trace['window_s']:.4f} s: "
              f"device_idle_pct {idle:.2f}; envelope less busy (idle_in_search_pct) "
              f"{in_search:.2f}; the five shares {shares + in_search:.2f}, "
              f"{shares + in_search - idle:+.2f} from device_idle_pct")

    # -- the engine thread's cover, and where it has none
    gaps = uncovered(spans, tw)
    lost = sum(g for g, _ in gaps)
    shown = gaps[:listed]
    print(f"engine thread under tick, engine.poll or engine.park: "
          f"{100 * (1 - lost / width):.2f}% of the window; {len(gaps)} uncovered stretches, "
          f"{lost * 1e3:.2f} ms in all, median {1e3 * (ps.median([g for g, _ in gaps]) or 0):.4f} ms")
    rest = width - sum(g for g, _ in shown)
    print(f"  of the window less the {len(shown)} stretches listed below: "
          f"{100 * (1 - (lost - sum(g for g, _ in shown)) / rest):.2f}%")
    engine = sorted((s for s in spans if on_engine_thread(s)), key=lambda s: s["t0"])
    for gap, at in shown:
        before = max((s for s in engine if s["t1"] <= at + 1e-7 and s["name"] in
                      ("tick", "engine.poll", "engine.park")), key=lambda s: s["t1"], default=None)
        print(f"  {gap * 1e3:9.4f} ms at {at - tw['t0']:8.4f} s of the trace, after "
              f"{before['name'] if before else 'the window opened'}")

    # -- engine.poll by the rounds it found
    by_rounds = collections.defaultdict(list)
    for s in ps.named(spans, "engine.poll"):
        by_rounds[s["args"].get("rounds")].append(s)
    print("engine.poll by rounds:")
    for rounds, found in sorted(by_rounds.items(), key=lambda kv: str(kv[0])):
        d = [ps.ms(s) for s in found]
        rows = ps.median([s["args"].get("rows", 0) for s in found])
        print(f"  rounds {rounds}: n {len(d):5d}  median {ps.median(d):7.4f} ms  max {max(d):8.4f}  "
              f"total {sum(d) / 1e3:7.4f} s  median rows {rows}")

    # -- a searching tick, node by node, phases under their node
    searching = {t["args"]["tick"]: t for t, _ in ps.ticks_holding(spans, "index.search")}
    writing = {t["args"]["tick"] for t, _ in ps.ticks_holding(spans, "index.apply")}
    kids = collections.defaultdict(list)  # (tick, parent name) -> spans
    for s in spans:
        if "parent" in s["args"] and "tick" in s["args"]:
            kids[(s["args"]["tick"], s["args"]["parent"])].append(s)
    for kind, ids in (("reading", set(searching) - writing), ("writing", set(searching) & writing)):
        if not ids:
            continue
        whole = [ps.ms(searching[i]) for i in ids]
        less = [ps.ms(searching[i]) - sum(ps.ms(s) for s in spans if s["name"] == "index.search"
                                          and s["args"].get("tick") == i) for i in ids]
        print(f"searching ticks, {kind}: n {len(ids)}  tick median {ps.median(whole):.3f} ms, "
              f"less its search {ps.median(less):.3f}; by node (median ms a tick, of the ticks "
              "that hold it; self = less the spans that name it as parent)")
        nodes = collections.defaultdict(list)  # node name -> [(ms, self ms, {phase: ms})]
        for i in ids:
            for node in kids[(i, "tick")]:
                if "#" not in node["name"]:
                    continue
                inner = collections.defaultdict(float)
                for s in kids[(i, node["name"])]:
                    inner[s["name"]] += ps.ms(s)
                nodes[node["name"]].append(
                    (ps.ms(node), ps.ms(node) - sum(inner.values()), dict(inner)))
        for name, seen in sorted(nodes.items(), key=lambda kv: -sum(v[0] for v in kv[1]))[:16]:
            print(f"  {name:26s} n {len(seen):4d}  {ps.median([v[0] for v in seen]):8.3f}  "
                  f"self {ps.median([v[1] for v in seen]):8.3f}")
            phases = collections.defaultdict(list)
            for _, _, inner in seen:
                for phase, total in inner.items():
                    phases[phase].append(total)
            for phase, d in sorted(phases.items(), key=lambda kv: -sum(kv[1])):
                print(f"      {phase:22s} n {len(d):4d}  {ps.median(d):8.3f}")

    # -- which engine span was open when a handler resumed
    wakes = ps.named(spans, "rest.wake")
    print(f"rest.wake: n {len(wakes)}  median {ps.median([ps.ms(w) for w in wakes])} ms; "
          f"switch interval {switch_interval_s()} s; the innermost engine span open at its end:")
    where = collections.defaultdict(list)
    for w in wakes:
        s = open_at(engine, w["t1"])
        where["none" if s is None else s["name"]].append(ps.ms(w))
    for name, d in sorted(where.items(), key=lambda kv: -len(kv[1])):
        print(f"  {name:26s} {100 * len(d) / len(wakes):6.2f}%  n {len(d):5d}  "
              f"median wake {ps.median(d):7.3f} ms")
    in_node = sum(len(d) for n, d in where.items() if "#" in n or n.startswith(
        ("groupby.", "join.", "subscribe.", "fusion.")))
    in_fetch = len(where.get("index.fetch", ()))
    print(f"  in index.fetch {100 * in_fetch / max(len(wakes), 1):.2f}%, in a node or its phases "
          f"{100 * in_node / max(len(wakes), 1):.2f}%, in none "
          f"{100 * len(where.get('none', ())) / max(len(wakes), 1):.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
