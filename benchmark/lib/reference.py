"""The plain reference's shared parts: a text's tokens, framed as its model's
family says, and a blocked exact cosine scan, in straightforward
``jax.numpy``. The model's forward is its family's (``models/<model_type>.py``:
``reference_params``, ``encode``), and every matrix product of either goes
through ``mm``. Imports nothing of ``pathway_tpu`` and takes nothing the
program has made: weights, vocabulary and rows come from the seed.

``precision="float32"`` is the reference (every product at ``highest``).
``precision="fp8"`` is the control of "How correct is decided": the same
mathematics with every matrix product's operands rounded to float8 (e4m3, one
scale per tensor), the nearest precision below the bfloat16 the configurations
state. It stands in the program's place and has to come out not correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib import family

_HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, b, precision: str):
    """Every matrix product of a reference, so that the control rounds each."""
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(a, b, precision=_HI)


def token_ids(texts: list[str], vocab_index: dict[str, int], width: int,
              fam) -> np.ndarray:
    """Each text's words framed by the family (``fam.frame``), padded with 0
    to ``width``. The traffic draws whole words of the vocabulary, so a word is
    one token; any other word is an error here, not a guess."""
    out = np.zeros((len(texts), width), np.int32)
    for i, t in enumerate(texts):
        ids = fam.frame([vocab_index[w] for w in t.split()])
        if len(ids) > width:
            raise ValueError(f"text of {len(ids)} tokens exceeds {width}")
        out[i, :len(ids)] = ids
    return out


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _scan_block(q, rows, live, best_s, best_i, offset, *, k: int, precision: str):
    sc = mm(q, rows.T, precision)
    sc = jnp.where(live[None, :], sc, -jnp.inf)
    s, i = jax.lax.top_k(sc, k)
    s = jnp.concatenate([best_s, s], axis=1)
    i = jnp.concatenate([best_i, i + offset], axis=1)
    s2, pos = jax.lax.top_k(s, k)
    return s2, jnp.take_along_axis(i, pos, axis=1)


def scan_topk(queries: np.ndarray, rows: np.ndarray, live: np.ndarray, k: int,
              precision: str = "float32", block: int = 65_536):
    """Exact top-``k`` of ``queries @ rows.T`` over the rows where ``live``,
    block by block so that it fits beside nothing. (scores, row ids), best
    first, on the host."""
    q = jnp.asarray(queries, jnp.float32)
    best_s = jnp.full((len(queries), k), -jnp.inf, jnp.float32)
    best_i = jnp.full((len(queries), k), -1, jnp.int32)
    for s in range(0, len(rows), block):
        e = min(s + block, len(rows))
        best_s, best_i = _scan_block(
            q, jnp.asarray(rows[s:e]), jnp.asarray(live[s:e]), best_s, best_i,
            jnp.int32(s), k=k, precision=precision,
        )
    return np.asarray(best_s), np.asarray(best_i)


# -- tests/test_embedder_packed.py's entry points (``datagen`` has the note) --


def _bert():
    return family.load({"model_type": "bert"})


def tokenize(texts: list[str], vocab_index: dict[str, int], width: int) -> np.ndarray:
    return token_ids(texts, vocab_index, width, _bert())


def to_device(weights: dict[str, np.ndarray]):
    return _bert().reference_params(weights)


def encode(params, ids: np.ndarray, model: dict, precision: str = "float32",
           batch: int = 256) -> np.ndarray:
    return _bert().encode(params, ids, model, precision, batch)
