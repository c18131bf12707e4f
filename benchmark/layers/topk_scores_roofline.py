"""Share of its roofline that ``jit(topk_scores)`` reaches: the least time the
chip could take for what the call needs (``workcount.topk_scores_work`` at the
mean number of queries a traced search carried, over the live rows) over the
program's mean device time per call in the trace.

In a scoped mix a search is ``workcount.scoped_topk_scores_work`` at that mean
number of queries, each over the mean scope of the traced requests (the rows
of the folder each was confined to, the live rows for one with no filter).
Which requests shared a search is not recorded, so the union of a batch's
scopes is counted as the mean scope once, which no union is smaller than: the
share errs low. It is taken over all the traced searches against all the
program's device time, since how many calls a filtered search makes is the
program's business."""

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
    q_mean, device_s = sum(q) / len(q), t["mean_s"]
    if cell["mix"].get("scope"):
        scopes = counts["traced_scope_rows"]
        if not scopes:
            return None
        scope = sum(scopes) / len(scopes)
        flops, nbytes = workcount.scoped_topk_scores_work(
            q_mean, q_mean * scope, scope, cfg["hidden_size"], cfg["k"])
        device_s = t["mean_s"] * t["calls"] / len(q)
    else:
        flops, nbytes = workcount.topk_scores_work(
            q_mean, counts["live_rows"], cfg["hidden_size"], cfg["k"])
    least, bound = workcount.least_time(flops, nbytes, cell["chip"])
    print(f"[layer] topk_scores: {bound}-bound, least {least * 1e3:.3f} ms a search, "
          f"device {t['mean_s'] * 1e3:.3f} ms a call over {t['calls']} calls", file=sys.stderr)
    return 100.0 * least / device_s
