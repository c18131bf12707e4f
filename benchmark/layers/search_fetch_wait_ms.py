"""Median ``index.fetch`` of searches entered clean: the host blocked on the
device (embed forward, scores and top-k) until the answer is fetched."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(kids["index.fetch"])
                      for _, kids in ps.searches(ps.load(cell), dirty=False)
                      if "index.fetch" in kids])
