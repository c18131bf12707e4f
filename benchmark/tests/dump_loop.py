"""By hand, after any run: what the closed loop did over the window, by 3 s.
Requests completed, the median and the 95th percentile of their latency, the
batch sizes the searches carried (which split of the callers the loop sat in:
a search of one query is the one-and-fifteen state), the stretches with no
reply, and the child's collections. It showed that the load generator's own
collections threw the loop out of its state (``PERF.md`` section 6, PR 32).

    python3 benchmark/tests/dump_loop.py benchmark/out/<cell>
"""

import collections
import json
import os
import sys

import numpy as np

STEP_S = 3.0


def main() -> int:
    out = sys.argv[1]
    with open(os.path.join(out, "requests.jsonl")) as f:
        reqs = [r for r in map(json.loads, f) if r["client"].startswith("r")]
    with open(os.path.join(out, "child_facts.json")) as f:
        facts = json.load(f)
    send = np.array([r["send"] for r in reqs])  # seconds from the window's opening
    recv = np.array([r["recv"] for r in reqs])
    lat = (recv - send) * 1e3
    end = float(send.max())
    searches = [s for s in facts["spans"] if s["name"] == "search"]
    # the spans are on the absolute clock: the first search of the window
    # starts within a tick of its opening
    base = min((s["t0"] for s in searches), default=0.0)
    at = np.array([s["t0"] - base for s in searches])
    q = np.array([s["q"] for s in searches])
    inside = send >= 0
    print(f"{int(inside.sum())} requests in the window: p50 {np.percentile(lat[inside], 50):.2f} "
          f"p95 {np.percentile(lat[inside], 95):.2f} p99 {np.percentile(lat[inside], 99):.2f} ms; "
          f"searches by queries carried: {sorted(collections.Counter(q.tolist()).items())}")
    print("from s: completed, p50 ms, p95 ms, searches by queries carried (most frequent first)")
    for b in np.arange(0.0, end, STEP_S):
        sent = (send >= b) & (send < b + STEP_S)
        if not sent.any():
            continue
        split = collections.Counter(q[(at >= b) & (at < b + STEP_S)].tolist()).most_common(4)
        print(f" {b:5.0f}: {int(((recv >= b) & (recv < b + STEP_S)).sum()):5d} "
              f"{np.percentile(lat[sent], 50):7.2f} {np.percentile(lat[sent], 95):7.2f}  {split}")
    done = np.sort(recv[recv >= 0])
    gaps = np.diff(done)
    quiet = sorted((round(float(done[i]), 2), round(float(gaps[i]) * 1e3))
                   for i in np.argsort(-gaps)[:12] if gaps[i] > 0.08)
    print("stretches over 80 ms with no reply (at s, ms):", quiet)
    print("the child's collections in the window:", facts["gc_in_window"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
