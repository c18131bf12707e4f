"""By hand, after a traced run of a live cell: the ticks of the traced window
split into writing ones (they hold an ``index.apply``) and reading ones, the
nodes under each kind with medians and maxima, and the index spans.

    python3 benchmark/tests/dump_writing_ticks.py benchmark/out/<cell>
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import program_spans as ps  # noqa: E402


def inside(child: dict, parent: dict) -> bool:
    return parent["t0"] <= child["t0"] and child["t1"] <= parent["t1"] + 1e-6


def main() -> int:
    with open(os.path.join(sys.argv[1], "child_facts.json")) as f:
        tw = json.load(f)["trace_window"]
    spans = ps.load({"trace_window": tw})
    if not spans:
        print(f"no span file of the window {tw} under {ps.spans_dir()}")
        return 1
    applies = ps.named(spans, "index.apply")
    nodes = [s for s in spans if "#" in s["name"]]
    kinds = collections.defaultdict(list)
    for tick in ps.named(spans, "tick"):
        writing = any(inside(a, tick) for a in applies)
        kinds["writing" if writing else "reading"].append(tick)
    for kind, ticks in kinds.items():
        d = [ps.ms(t) for t in ticks]
        print(f"{kind} ticks: n {len(d)}  median {ps.median(d):.3f} ms  max {max(d):.3f} ms")
        by_node = collections.defaultdict(list)
        for tick in ticks:
            for node in nodes:
                if inside(node, tick):
                    by_node[node["name"]].append(ps.ms(node))
        for name, v in sorted(by_node.items(), key=lambda kv: -sum(kv[1]))[:14]:
            print(f"   {name:26s} n {len(v):4d}  median {ps.median(v):8.3f}  max {max(v):8.3f}")
    for name in ("index.apply", "index.upload", "index.write", "index.search",
                 "index.fetch", "index.score"):
        found = ps.named(spans, name)
        d = [ps.ms(s) for s in found]
        if d:
            print(f"{name:14s} n {len(d):4d}  median {ps.median(d):8.3f}  max {max(d):8.3f}  "
                  f"first {found[0]['args']}")
    for dirty in (True, False):
        d = [ps.ms(s) for s in ps.named(spans, "index.search", dirty=dirty)]
        print(f"index.search entered dirty={dirty}: n {len(d)}  median {ps.median(d)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
