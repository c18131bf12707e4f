"""Median ``index.mask``: forming a filtered search's ``valid`` [q, n] on the
host's clock — each query's mask looked up in the cache (built where it is not
there) and the stack dispatched."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(s) for s in ps.named(ps.load(cell), "index.mask")])
