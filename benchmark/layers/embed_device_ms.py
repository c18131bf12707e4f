"""Device time of the embed forward per call, from the trace's program line:
the median over the traced calls. The program is ``jit(embed_tokens)``; the
embedder jits a ``functools.partial``, which the trace names ``jit__unknown``,
so it is found as the program that runs under a ``search`` span and is not
``topk_scores`` (and by its own name, should a later PR give it one)."""

from lib import xplane


def read(trace, spans, counts, cell):
    if not trace or trace.get("stand_in"):
        return None
    t = (xplane.program_time(trace, "embed_tokens")
         or xplane.program_time(trace, "", span="search", other_than="topk_scores"))
    return t["median_s"] * 1e3 if t else None
