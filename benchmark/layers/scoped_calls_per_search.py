"""Executions of the device programs whose name holds ``topk_scores`` over the
searches of the traced window: 1.0 where a search is one scan whatever its
filters, the number of filtered queries and more where each is scanned alone.
Both sides are taken inside the trace: the executions that started under a
``search`` span the profiler recorded, over the searches that lie wholly
inside the traced window (one cut by its edge has its scan on either side)."""

from lib import xplane


def read(trace, spans, counts, cell):
    if not trace or trace.get("stand_in"):
        return None
    t = (xplane.program_time(trace, "topk_scores", span="search")
         or xplane.program_time(trace, "topk_scores"))
    tw = cell["trace_window"]
    searches = sum(s["name"] == "search" and tw["t0"] <= s["t0"] and s["t1"] <= tw["t1"]
                   for s in spans)
    return t["calls"] / searches if t and searches else None
