"""Median self time of a ``tick`` that holds an ``index.search``: the tick
less that search — the dataflow's own host work around a retrieve."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(tick) - sum(ps.ms(s) for s in searches)
                      for tick, searches in ps.ticks_holding(ps.load(cell), "index.search")])
