"""Share of the positions the embed forward computed that held no token of a
query: 1 - the sum of ``tokens`` (real) over the sum of ``computed`` (rows x
row length) of the traced ``embed.dispatch`` spans. A program whose spans
carry neither (before the packed forward) gives nothing to read."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    sent = [s["args"] for s in ps.named(ps.load(cell), "embed.dispatch")
            if "tokens" in s["args"] and s["args"].get("computed")]
    if not sent:
        return None
    return 100.0 * (1.0 - sum(a["tokens"] for a in sent) / sum(a["computed"] for a in sent))
