"""Operations and bytes that a result NEEDS, computed from shapes, whatever
implements it — so a later PR that changes storage or fuses the scan cannot
make the count stale. A model's work per token is its family's
(``models/<model_type>.py::flops_per_token``), handed in as ``per_token``."""

from __future__ import annotations

from typing import Callable


def topk_scores_work(q: int, n: int, d: int, k: int) -> tuple[float, float]:
    """(FLOP, bytes) of scoring ``q`` queries against ``n`` live rows of width
    ``d`` and keeping ``k``: one read of the index at the scoring precision the
    configuration states (bfloat16 products), the float32 queries in, scores
    and ids out."""
    return 2.0 * q * n * d, n * d * 2.0 + q * d * 4.0 + q * k * 8.0


def scoped_topk_scores_work(q: float, scoped_rows: float, union_rows: float,
                            d: int, k: int) -> tuple[float, float]:
    """(FLOP, bytes) of one batch of ``q`` queries each confined to a scope of
    its own: a query is scored against the live rows of its scope
    (``scoped_rows`` is their sum over the batch), and the batch reads each row
    of the union of its scopes once (``union_rows``), with the queries in and
    scores and ids out as in ``topk_scores_work``. The bytes of the mask, or of
    whatever else tells a program which rows a scope holds, are left out: the
    result needs the rows, and how a program finds them is its own business.
    So a program that scans every row under a mask reads lower than one that
    reads only the scopes, and neither reads over 100%."""
    return 2.0 * d * scoped_rows, union_rows * d * 2.0 + q * d * 4.0 + q * k * 8.0


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Seconds the chip could not beat, and which peak bounds it."""
    t_c, t_m = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def retrieve_flops(tokens: int, n: int, d: int, per_token: Callable[[int], float]) -> float:
    """One request: the model over its REAL tokens (no bucket padding),
    ``per_token(tokens)`` each (the family's ``flops_per_token`` of the model),
    plus 2·n·d for its scan over the live rows (not the capacity)."""
    return tokens * per_token(tokens) + 2.0 * n * d


def scoped_retrieve_flops(tokens: int, scope_rows: float, d: int,
                          per_token: Callable[[int], float]) -> float:
    """One request confined to a scope: the model over its real tokens plus
    2·d for each live row of its scope (of the store, for a request with no
    filter), and for no row outside it: ``retrieve_flops`` with the scope in
    the store's place."""
    return retrieve_flops(tokens, scope_rows, d, per_token)
