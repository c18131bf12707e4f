"""Operations and bytes that a result NEEDS, computed from shapes, whatever
implements it — so a later PR that changes storage or fuses the scan cannot
make the count stale."""

from __future__ import annotations


def encoder_flops_per_token(model: dict, seq: int) -> float:
    """Per token, all layers: 2·d·3d (qkv) + 2·d·d (proj) + 4·d·h (mlp) +
    4·s·d (attention), the standard accounting (copied from
    ``bench.py::_embed_throughput``)."""
    d, h = model["hidden_size"], model["intermediate_size"]
    per_layer = 2 * d * 3 * d + 2 * d * d + 4 * d * h + 4 * seq * d
    return float(model["num_hidden_layers"] * per_layer)


def topk_scores_work(q: int, n: int, d: int, k: int) -> tuple[float, float]:
    """(FLOP, bytes) of scoring ``q`` queries against ``n`` live rows of width
    ``d`` and keeping ``k``: one read of the index at the scoring precision the
    configuration states (bfloat16 products), the float32 queries in, scores
    and ids out."""
    return 2.0 * q * n * d, n * d * 2.0 + q * d * 4.0 + q * k * 8.0


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Seconds the chip could not beat, and which peak bounds it."""
    t_c, t_m = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def retrieve_flops(tokens: int, n: int, d: int, model: dict) -> float:
    """One request: the encoder over its REAL tokens (no bucket padding) plus
    2·n·d for its scan over the live rows (not the capacity)."""
    return tokens * encoder_flops_per_token(model, tokens) + 2.0 * n * d
