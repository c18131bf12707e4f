"""Share of its roofline that ``jit(topk_scores)`` reaches: the least time the
chip could take for what the call needs (``workcount.topk_scores_work`` at the
mean number of queries a traced search carried, over the live rows) over the
program's mean device time per call in the trace."""

import sys

from lib import workcount, xplane


def read(trace, spans, counts, cell):
    if not trace or trace.get("stand_in") or not cell.get("chip"):
        return None
    t = xplane.program_time(trace, "topk_scores")
    tw = cell["trace_window"]
    q = [s["q"] for s in spans if s["name"] == "search" and tw["t0"] <= s["t0"] < tw["t1"]]
    if not t or not q:
        return None
    cfg = cell["config"]
    flops, nbytes = workcount.topk_scores_work(
        sum(q) / len(q), counts["live_rows"], cfg["hidden_size"], cfg["k"])
    least, bound = workcount.least_time(flops, nbytes, cell["chip"])
    print(f"[layer] topk_scores: {bound}-bound, least {least * 1e3:.3f} ms, device "
          f"{t['mean_s'] * 1e3:.3f} ms a call over {t['calls']} calls", file=sys.stderr)
    return 100.0 * least / t["mean_s"]
