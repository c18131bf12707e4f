"""The BERT family (HF ``BertModel``, post-layernorm, ``model_type`` "bert") as
the store's embedder: what the benchmark needs of a model, in one file that a
configuration names by its ``model_type`` (``lib/family.py``).

- the keys of a configuration that make the model (``MODEL_KEYS``);
- the vocabulary's specials and first word id, and the framing of a text
  (``frame``, ``FRAMING`` tokens);
- the weights from the seed (``make_weights``);
- the plain reference (``reference_params``, ``encode``): float32 at
  ``highest``, and with ``precision="fp8"`` the control, which has to come out
  not correct; it imports nothing of ``pathway_tpu``;
- the work a token needs (``flops_per_token``), counted over real tokens;
- the device programs that the embed readers sum (``PROGRAM``);
- the program's embedder, built through its public entry points
  (``build_embedder``).

Loaded in the parent, which never imports JAX: JAX and the program are
imported inside the functions that need them.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from lib import datagen

#: what ``Deployment.model`` takes from a configuration
MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "intermediate_size", "vocab_size", "max_position_embeddings",
              "type_vocab_size", "layer_norm_eps", "initializer_range")
#: BERT's special ids in the published 30,522-entry vocabulary
SPECIALS = {0: "[PAD]", 100: "[UNK]", 101: "[CLS]", 102: "[SEP]", 103: "[MASK]"}
FIRST_WORD_ID = 1000
_ID = {token: i for i, token in SPECIALS.items()}
#: tokens ``frame`` adds to a text's words
FRAMING = 2
#: the device programs of the embed forward, by the name the trace gives them
PROGRAM = "embed_tokens"


def frame(word_ids: list[int]) -> list[int]:
    """``[CLS] w1 .. wn [SEP]``."""
    return [_ID["[CLS]"], *word_ids, _ID["[SEP]"]]


# -- weights ----------------------------------------------------------------


def tensor_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """HF ``BertModel`` state-dict names and shapes for a ``config.json``."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    shapes: dict[str, tuple[int, ...]] = {
        "embeddings.word_embeddings.weight": (model["vocab_size"], d),
        "embeddings.position_embeddings.weight":
            (model["max_position_embeddings"], d),
        "embeddings.token_type_embeddings.weight":
            (model["type_vocab_size"], d),
        "embeddings.LayerNorm.weight": (d,),
        "embeddings.LayerNorm.bias": (d,),
    }
    for i in range(model["num_hidden_layers"]):
        p = f"encoder.layer.{i}."
        for name, shape in (
            ("attention.self.query", (d, d)), ("attention.self.key", (d, d)),
            ("attention.self.value", (d, d)),
            ("attention.output.dense", (d, d)),
            ("intermediate.dense", (ff, d)), ("output.dense", (d, ff)),
        ):
            shapes[p + name + ".weight"] = shape
            shapes[p + name + ".bias"] = (shape[0],)
        for name in ("attention.output.LayerNorm", "output.LayerNorm"):
            shapes[p + name + ".weight"] = (d,)
            shapes[p + name + ".bias"] = (d,)
    return shapes


def make_weights(seed: int, model: dict) -> dict[str, np.ndarray]:
    """A seeded random HF-named float32 state dict: every tensor normal at
    ``initializer_range``; LayerNorm weights are 1 plus that (so that no scale
    or bias is an identity the comparison could not see)."""
    shapes = tensor_shapes(model)
    std = float(model["initializer_range"])
    names = sorted(shapes)

    def one(ix: int) -> np.ndarray:
        name = names[ix]
        a = datagen.stream(seed, 3, ix).standard_normal(shapes[name], dtype=np.float32)
        a *= std
        if name.endswith("LayerNorm.weight"):
            a += 1.0
        return a

    with ThreadPoolExecutor(datagen.threads()) as pool:
        return dict(zip(names, pool.map(one, range(len(names)))))


# -- the plain reference ----------------------------------------------------


def _layernorm(x, w, b, eps):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


LAYER = "encoder.layer."


def _forward(p, layers, ids, *, n_heads: int, eps: float, precision: str):
    """``p``: the tensors outside the layers by their HF names; ``layers``: each
    layer tensor stacked along a leading axis, by its name inside the layer.
    One layer is traced and scanned over, so the program stays small."""
    import jax
    import jax.numpy as jnp

    from lib import reference

    mm = functools.partial(reference.mm, precision=precision)
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


@functools.cache
def _jitted_forward():
    import jax

    return jax.jit(_forward, static_argnames=("n_heads", "eps", "precision"))


def encode(params, ids: np.ndarray, model: dict, precision: str = "float32",
           batch: int = 256) -> np.ndarray:
    """Unit vectors [n, d] (float32, on the host) of token rows ``ids``; always
    in batches of ``batch`` rows (the last padded), so one program serves."""
    import jax.numpy as jnp

    outer, layers = params
    out = []
    for s in range(0, len(ids), batch):
        part = ids[s:s + batch]
        pad = batch - len(part)
        if pad:
            part = np.concatenate([part, np.repeat(part[-1:], pad, 0)])
        vec = _jitted_forward()(
            outer, layers, jnp.asarray(part),
            n_heads=model["num_attention_heads"],
            eps=float(model["layer_norm_eps"]), precision=precision,
        )
        out.append(np.asarray(vec)[:batch - pad])
    return np.concatenate(out)


def reference_params(weights: dict[str, np.ndarray]):
    """(tensors outside the layers, layer tensors stacked by layer), on the
    device."""
    import jax.numpy as jnp

    outer = {k: jnp.asarray(v) for k, v in weights.items() if not k.startswith(LAYER)}
    per_layer: dict[str, dict[int, np.ndarray]] = {}
    for k, v in weights.items():
        if k.startswith(LAYER):
            i, name = k[len(LAYER):].split(".", 1)
            per_layer.setdefault(name, {})[int(i)] = v
    layers = {name: jnp.asarray(np.stack([t[i] for i in range(len(t))]))
              for name, t in per_layer.items()}
    return outer, layers


# -- work -------------------------------------------------------------------


def flops_per_token(model: dict, seq: int) -> float:
    """Per token of a text of ``seq`` real tokens, all layers: 2·d·3d (qkv) +
    2·d·d (proj) + 4·d·h (mlp) + 4·s·d (attention), the standard accounting."""
    d, h = model["hidden_size"], model["intermediate_size"]
    per_layer = 2 * d * 3 * d + 2 * d * d + 4 * d * h + 4 * seq * d
    return float(model["num_hidden_layers"] * per_layer)


# -- the program's embedder -------------------------------------------------


def build_embedder(weights: dict[str, np.ndarray], model: dict,
                   vocab_index: dict[str, int], dtype):
    """The program's embedder of these weights and this vocabulary, served in
    ``dtype``, through its public loader."""
    from pathway_tpu.models.embedder import Embedder
    from pathway_tpu.models.wordpiece import WordPieceTokenizer

    return Embedder.from_pretrained(
        weights, n_heads=model["num_attention_heads"],
        tokenizer=WordPieceTokenizer(dict(vocab_index)), dtype=dtype,
    )
