"""Share of the traced window the engine thread spent parked with no round
to tick (the union of ``engine.park`` spans, cut to the window)."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    mine = ps.load(cell)
    if mine is None:
        return None
    tw = cell["trace_window"]
    parked = ps.union_s(ps.named(mine, "engine.park"), tw["t0"], tw["t1"])
    return 100.0 * parked / (tw["t1"] - tw["t0"])
