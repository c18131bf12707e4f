"""Median ``embed.tokenize``: a search's queries through the tokenizer, on
the host's clock, before anything is handed to the device (the head of the
tick; every word is walked in Python)."""

from lib import program_spans as ps


def read(trace, spans, counts, cell):
    return ps.median([ps.ms(s) for s in ps.named(ps.load(cell), "embed.tokenize")])
