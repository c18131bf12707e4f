"""Device time of the embed forward per call, from the trace's program line:
the median over the traced calls. The program is the family's ``PROGRAM``,
found by that name; where the trace names it otherwise (a jitted
``functools.partial`` is ``jit__unknown``), it is the program that runs under
a ``search`` span and is not ``topk_scores``."""

from lib import xplane


def read(trace, spans, counts, cell):
    if not trace or trace.get("stand_in"):
        return None
    t = (xplane.program_time(trace, cell["family"].PROGRAM)
         or xplane.program_time(trace, "", span="search", other_than="topk_scores"))
    return t["median_s"] * 1e3 if t else None
