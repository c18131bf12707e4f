"""Share of the traced window the engine thread spent in ticks after their
device envelope (``lib/engine_time.py``): the reply's way out (``index.pack``,
the joins, the group-by, ``Subscribe``, the tick's callbacks), with the
device idle. One of the five shares of ``device_idle_pct``."""

from lib import engine_time


def read(trace, spans, counts, cell):
    return engine_time.share_pct(cell, "tail")
