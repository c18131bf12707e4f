"""Median of ``rest.request`` less its ``rest.in_engine``: admission, the hop
to the engine's queue and the reply on the loop — the HTTP-to-dataflow
slice of a request."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    mine = ps.load(cell)
    inside = ps.by_id(ps.named(mine, "rest.in_engine"), "req")
    return ps.median([ps.ms(r) - ps.ms(inside[r["args"]["req"]])
                      for r in ps.named(mine, "rest.request")
                      if r["args"].get("req") in inside])
