"""Median host span of ``search`` calls entered with ``_dirty`` false (no
upload): embed forward, score and top-k, fetch and packing."""

from lib import spans as spans_mod


def read(trace, spans, counts, cell):
    return spans_mod.median_ms(spans, "search", dirty=False)
