"""The whole retrieve step's share of the chip's bf16 peak: the FLOP that the
requests completed in the traced span needed (encoder over their real tokens
plus the scan over the live rows, ``workcount.retrieve_flops``) over traced
seconds times the peak."""


def read(trace, spans, counts, cell):
    tw, chip = cell.get("trace_window"), cell.get("chip")
    if not tw or not chip or not counts.get("traced_requests"):
        return None
    return 100.0 * counts["traced_flops"] / ((tw["t1"] - tw["t0"]) * chip["bf16_flops"])
