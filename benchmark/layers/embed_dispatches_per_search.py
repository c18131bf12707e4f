"""Forwards the embedder dispatched for each search of the traced window that
embedded its queries: ``embed.dispatch`` spans over the ``index.search`` spans
of their ticks (a search cut by the window's edge is left out with its
dispatches). 1.0 where a search's queries go to the device as one program
whatever lengths they mix; the number of length buckets a search's queries
fall into where each bucket is a call."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    loaded = ps.load(cell)
    searched = {s["args"].get("tick") for s in ps.named(loaded, "index.search")}
    sent = [s["args"].get("tick") for s in ps.named(loaded, "embed.dispatch")
            if s["args"].get("tick") in searched]
    return len(sent) / len(set(sent)) if sent else None
