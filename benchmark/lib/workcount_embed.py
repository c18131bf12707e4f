"""Operations that the embed forward of a set of texts NEEDS, computed from
their real lengths (kept beside ``workcount.py``, which counts the retrieve
step): what padding, packing or bucketing a program adds is not in it."""

from __future__ import annotations

from typing import Callable


def embed_flops(tokens: list[int], per_token: Callable[[int], float]) -> float:
    """The model over each text alone, at its own length, its family's framing
    counted: a text of ``t`` tokens needs ``t`` times ``per_token(t)``, the
    family's ``flops_per_token`` of the model at that length (its attention
    looks at its own ``t`` positions and at no neighbour's, no padding's)."""
    return float(sum(t * per_token(t) for t in tokens))
