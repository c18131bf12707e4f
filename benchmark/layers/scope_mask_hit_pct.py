"""Share of the traced window's filtered queries whose mask was on the device
when their search began: the sum of ``hits`` over the sum of ``filtered`` of
the ``index.mask`` spans. 100 once every folder asked has been seen."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    masks = ps.named(ps.load(cell), "index.mask")
    filtered = sum(s["args"].get("filtered", 0) for s in masks)
    if not filtered:
        return None
    return 100.0 * sum(s["args"].get("hits", 0) for s in masks) / filtered
