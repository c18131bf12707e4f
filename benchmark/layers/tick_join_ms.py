"""Median, over the ticks that hold a search, of the tick's ``Join#<id>`` node
events together (since PR 38 a node event names its tick)."""

from lib import engine_time


def read(trace, spans, counts, cell):
    return engine_time.nodes_ms(cell, "Join")
