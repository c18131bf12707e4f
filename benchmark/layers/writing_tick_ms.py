"""Median ``tick`` that holds an ``index.apply``: a tick with writes, whole."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(tick) for tick, _ in
                      ps.ticks_holding(ps.load(cell), "index.apply")])
