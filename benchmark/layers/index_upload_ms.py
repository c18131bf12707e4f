"""Median ``index.upload``: the whole host block and its valid mask handed
to the device again, by the first search after a write."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(s) for s in ps.named(ps.load(cell), "index.upload")])
