"""Median ``rest.wake``: from the engine thread resolving a request's future
to its handler running again on the server's loop (the loop's turn, and the
interpreter lock the engine thread holds)."""

from lib import engine_time
from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(s) for s in ps.named(engine_time.spans_of(cell), "rest.wake")])
