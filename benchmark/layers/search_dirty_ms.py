"""Median host span of ``search`` calls entered with ``_dirty`` true: the
whole float32 block is uploaded again before the scan."""

from lib import spans as spans_mod


def read(trace, spans, counts, cell):
    return spans_mod.median_ms(spans, "search", dirty=True)
