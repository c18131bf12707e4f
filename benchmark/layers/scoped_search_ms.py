"""Median ``index.search`` of the searches that carried a filter (``filtered``
>= 1) and were entered clean: embed, the masks from the cache, one masked scan,
fetch and packing. A program whose ``index.search`` says nothing of filters
(the parent of the PR that added this) gives None."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(s) for s in ps.named(ps.load(cell), "index.search", dirty=False)
                      if s["args"].get("filtered", 0) >= 1])
