"""By hand, on the chip: print what a kept trace holds (planes, lines, the
commonest event names) and save a short slice of it as JSON, the form in which
``benchmark/tests/`` keeps its small recorded trace.

    python3 benchmark/tests/dump_trace.py <dir with *.xplane.pb> <out dir> [slice seconds]
"""

import collections
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import xplane  # noqa: E402


def main() -> int:
    src, out = sys.argv[1], sys.argv[2]
    width = float(sys.argv[3]) if len(sys.argv) > 3 else 0.06
    path = glob.glob(os.path.join(src, "**", "*.xplane.pb"), recursive=True)[0]
    planes = xplane.read_planes(path)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "trace_summary.txt"), "w") as f:
        print(path, os.path.getsize(path), "bytes", file=f)
        for name, lines in planes.items():
            print("PLANE", name, file=f)
            for ln, evs in lines.items():
                if not evs:
                    continue
                top = collections.Counter(e[0] for e in evs).most_common(12)
                t0 = min(e[1] for e in evs)
                t1 = max(e[1] + e[2] for e in evs)
                print(f"  LINE {ln!r}: {len(evs)} events, {t0:.6f}..{t1:.6f} s; {top}", file=f)
    # a slice from the middle, every event that starts inside it
    starts = [e[1] for lines in planes.values() for evs in lines.values() for e in evs]
    mid = (min(starts) + max(starts)) / 2
    cut = {name: {ln: [e for e in evs if mid <= e[1] < mid + width]
                  for ln, evs in lines.items()}
           for name, lines in planes.items()}
    cut = {n: {ln: evs for ln, evs in lines.items() if evs} for n, lines in cut.items()}
    with open(os.path.join(out, "trace_slice.json"), "w") as f:
        json.dump({n: l for n, l in cut.items() if l}, f)
    print(json.dumps(xplane.reduce_planes(planes, ("search", "embed")), indent=1)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
