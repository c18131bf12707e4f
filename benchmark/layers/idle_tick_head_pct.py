"""Share of the traced window the engine thread spent in ticks ahead of their
device envelope (``lib/engine_time.py``): the nodes before the search and the
tokenizer, with the device idle. One of the five shares of
``device_idle_pct``."""

from lib import engine_time


def read(trace, spans, counts, cell):
    return engine_time.share_pct(cell, "head")
