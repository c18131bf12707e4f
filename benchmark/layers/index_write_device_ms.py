"""Mean device time of the in-place index write (``jit_index_write``) per
call, from the trace's program line. It has to stay flat in the capacity: a
write that copied the block would read the time of a pass over it."""

from lib import xplane


def read(trace, spans, counts, cell):
    if not trace or trace.get("stand_in"):
        return None
    t = xplane.program_time(trace, "index_write")
    return t["mean_s"] * 1e3 if t else None
