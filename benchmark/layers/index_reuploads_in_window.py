"""Whole placements of the index block inside the traced window:
``index.upload`` spans that handed the whole host block to the device again
(a program that marks its uploads says so in ``whole``; one that does not
has no other kind). A store that writes in place reads 0."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    mine = ps.load(cell)
    if mine is None:
        return None
    return float(sum(s["args"].get("whole", True) for s in ps.named(mine, "index.upload")))
