"""Share of the traced window between one tick's end and the next tick's
start, less ``engine.park`` (``lib/engine_time.py``): ``engine.poll`` and time
the engine thread did not run. One of the five shares of
``device_idle_pct``."""

from lib import engine_time


def read(trace, spans, counts, cell):
    return engine_time.share_pct(cell, "between")
