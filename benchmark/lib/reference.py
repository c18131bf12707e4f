"""The plain reference: a float32 BERT forward (masked mean pool, L2 norm) and
a blocked exact cosine scan, in straightforward ``jax.numpy``. Imports nothing
of ``pathway_tpu`` and takes nothing the program has made: weights, vocabulary
and rows come from the seed (``datagen``).

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

_HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, precision: str):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(a, b, precision=_HI)


def tokenize(texts: list[str], vocab_index: dict[str, int], width: int) -> np.ndarray:
    """``[CLS] w1 .. wn [SEP]`` padded with 0 to ``width``. The traffic draws
    whole words of the vocabulary, so a word is one token; any other word is an
    error here, not a guess."""
    out = np.zeros((len(texts), width), np.int32)
    for i, t in enumerate(texts):
        ids = [vocab_index["[CLS]"], *(vocab_index[w] for w in t.split()),
               vocab_index["[SEP]"]]
        if len(ids) > width:
            raise ValueError(f"text of {len(ids)} tokens exceeds {width}")
        out[i, :len(ids)] = ids
    return out


def _layernorm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


LAYER = "encoder.layer."


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "precision"))
def _forward(p, layers, ids, *, n_heads: int, eps: float, precision: str):
    """``p``: the tensors outside the layers by their HF names; ``layers``: each
    layer tensor stacked along a leading axis, by its name inside the layer.
    One layer is traced and scanned over, so the program stays small."""
    mm = functools.partial(_mm, precision=precision)
    mask = ids > 0
    b, s = ids.shape
    x = (p["embeddings.word_embeddings.weight"][ids]
         + p["embeddings.position_embeddings.weight"][:s][None]
         + p["embeddings.token_type_embeddings.weight"][0][None, None])
    x = _layernorm(x, p["embeddings.LayerNorm.weight"],
                   p["embeddings.LayerNorm.bias"], eps)
    hd = x.shape[-1] // n_heads

    def heads(t):
        return t.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)

    def layer(x, w):
        def dense(t, name):
            return mm(t, w[name + ".weight"].T) + w[name + ".bias"]

        q = heads(dense(x, "attention.self.query"))
        k = heads(dense(x, "attention.self.key"))
        v = heads(dense(x, "attention.self.value"))
        sc = mm(q, k.transpose(0, 1, 3, 2)) / np.sqrt(hd)
        sc = jnp.where(mask[:, None, None, :], sc, -jnp.inf)
        att = jax.nn.softmax(sc, axis=-1)
        ctx = mm(att, v).transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = _layernorm(x + dense(ctx, "attention.output.dense"),
                       w["attention.output.LayerNorm.weight"],
                       w["attention.output.LayerNorm.bias"], eps)
        h = jax.nn.gelu(dense(x, "intermediate.dense"), approximate=False)
        x = _layernorm(x + dense(h, "output.dense"),
                       w["output.LayerNorm.weight"],
                       w["output.LayerNorm.bias"], eps)
        return x, None

    x, _ = jax.lax.scan(layer, x, layers)
    m = mask[:, :, None].astype(jnp.float32)
    pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)


def encode(params, ids: np.ndarray, model: dict, precision: str = "float32",
           batch: int = 256) -> np.ndarray:
    """Unit vectors [n, d] (float32, on the host) of token rows ``ids``; always
    in batches of ``batch`` rows (the last padded), so one program serves."""
    outer, layers = params
    out = []
    for s in range(0, len(ids), batch):
        part = ids[s:s + batch]
        pad = batch - len(part)
        if pad:
            part = np.concatenate([part, np.repeat(part[-1:], pad, 0)])
        vec = _forward(
            outer, layers, jnp.asarray(part),
            n_heads=model["num_attention_heads"],
            eps=float(model["layer_norm_eps"]), precision=precision,
        )
        out.append(np.asarray(vec)[:batch - pad])
    return np.concatenate(out)


def to_device(state_dict: dict[str, np.ndarray]):
    """(tensors outside the layers, layer tensors stacked by layer)."""
    outer = {k: jnp.asarray(v) for k, v in state_dict.items() if not k.startswith(LAYER)}
    per_layer: dict[str, dict[int, np.ndarray]] = {}
    for k, v in state_dict.items():
        if k.startswith(LAYER):
            i, name = k[len(LAYER):].split(".", 1)
            per_layer.setdefault(name, {})[int(i)] = v
    layers = {name: jnp.asarray(np.stack([t[i] for i in range(len(t))]))
              for name, t in per_layer.items()}
    return outer, layers


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _scan_block(q, rows, live, best_s, best_i, offset, *, k: int, precision: str):
    sc = _mm(q, rows.T, precision)
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
