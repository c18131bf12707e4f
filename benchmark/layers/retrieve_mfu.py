"""The whole retrieve step's share of the chip's bf16 peak: the FLOP that the
requests completed in the traced span needed (the model over their real
tokens, as its family counts them, plus the scan over the live rows,
``workcount.retrieve_flops``; in a scoped mix over the live rows of each
request's own scope, ``workcount.scoped_retrieve_flops``) over traced seconds
times the peak."""

import functools

from lib import workcount


def read(trace, spans, counts, cell):
    tw, chip = cell.get("trace_window"), cell.get("chip")
    if not tw or not chip or not counts.get("traced_requests"):
        return None
    cfg = cell["config"]
    per_token = functools.partial(cell["family"].flops_per_token, cfg)
    if cell["mix"].get("scope"):
        flops = sum(workcount.scoped_retrieve_flops(t, rows, cfg["hidden_size"], per_token)
                    for t, rows in zip(counts["traced_tokens"], counts["traced_scope_rows"]))
    else:
        flops = sum(workcount.retrieve_flops(t, counts["live_rows"], cfg["hidden_size"], per_token)
                    for t in counts["traced_tokens"])
    return 100.0 * flops / ((tw["t1"] - tw["t0"]) * chip["bf16_flops"])
