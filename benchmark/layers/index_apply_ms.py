"""Median ``index.apply``: one tick's rows removed from and added to the
index engine's host block (no upload: that is ``index_upload_ms``)."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(s) for s in ps.named(ps.load(cell), "index.apply")])
