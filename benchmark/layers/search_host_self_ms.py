"""Median of ``index.search`` less its ``index.fetch``, over searches entered
clean: what the host itself does in a search — tokenise, dispatch, pack."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(search) - ps.ms(kids["index.fetch"])
                      for search, kids in ps.searches(ps.load(cell), dirty=False)
                      if "index.fetch" in kids])
