"""Median over requests of the wait between a request's row going to the
engine (``rest.in_engine`` starts: ``_next_with_key`` + ``commit()``) and the
start of the ``tick`` that answered it: the connector's commit window and the
ticks queued before its own."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    mine = ps.load(cell)
    ticks = ps.by_id(ps.named(mine, "tick"), "tick")
    waits = [(ticks[s["args"]["tick"]]["t0"] - s["t0"]) * 1e3
             for s in ps.named(mine, "rest.in_engine")
             if s["args"].get("tick") in ticks]
    return ps.median(waits)
