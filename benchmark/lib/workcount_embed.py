"""Operations that the embed forward of a set of texts NEEDS, computed from
their real lengths (kept beside ``workcount.py``, which counts the retrieve
step): what padding, packing or bucketing a program adds is not in it."""

from __future__ import annotations

from lib import workcount


def embed_flops(tokens: list[int], model: dict) -> float:
    """The encoder over each text alone, at its own length, [CLS] and [SEP]
    counted: a text of ``t`` tokens needs ``t`` times
    ``workcount.encoder_flops_per_token(model, t)`` (its attention looks at its
    own ``t`` positions and at no neighbour's, no padding's)."""
    return float(sum(t * workcount.encoder_flops_per_token(model, t) for t in tokens))
