"""TPU-native text embedder — the flagship on-device model.

Replaces the reference LLM xpack's CPU-bound ``SentenceTransformerEmbedder``
(``python/pathway/xpacks/llm/embedders.py:217``) with a pure-JAX transformer
encoder that runs on the MXU in bf16: mean-pooled, L2-normalized sentence
embeddings. Weights can be tensor-parallel sharded over a mesh "model" axis
(attention heads + MLP hidden split), with batch data-parallel over "data".

Deterministic init (seeded) so the framework is self-contained; loading
pretrained MiniLM-class weights is a straight param-tree mapping.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class EmbedderConfig:
    vocab_size: int = 30528
    dim: int = 384
    n_layers: int = 6
    n_heads: int = 12
    mlp_ratio: int = 4
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    #: "preln" — the self-contained deterministic-init encoder;
    #: "bert" — post-layernorm with biases, numerically matching HF
    #: BertModel so MiniLM-class pretrained checkpoints load verbatim
    arch: str = "preln"
    ln_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


def init_params(cfg: EmbedderConfig, seed: int = 0) -> dict:
    """Initialize a parameter pytree (dense f32 master weights)."""
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 4 + 8 * cfg.n_layers)
    k = iter(keys)

    def dense(kk, fan_in, shape):
        return (jax.random.normal(kk, shape, jnp.float32) / np.sqrt(fan_in)).astype(
            jnp.float32
        )

    params: dict = {
        "tok_emb": dense(next(k), cfg.dim, (cfg.vocab_size, cfg.dim)),
        "pos_emb": dense(next(k), cfg.dim, (cfg.max_len, cfg.dim)),
        "ln_f_scale": jnp.ones((cfg.dim,), jnp.float32),
        "ln_f_bias": jnp.zeros((cfg.dim,), jnp.float32),
        "layers": [],
    }
    hidden = cfg.dim * cfg.mlp_ratio
    for _ in range(cfg.n_layers):
        layer = {
            "qkv": dense(next(k), cfg.dim, (cfg.dim, 3 * cfg.dim)),
            "proj": dense(next(k), cfg.dim, (cfg.dim, cfg.dim)),
            "mlp_in": dense(next(k), cfg.dim, (cfg.dim, hidden)),
            "mlp_out": dense(next(k), hidden, (hidden, cfg.dim)),
            "ln1_scale": jnp.ones((cfg.dim,), jnp.float32),
            "ln1_bias": jnp.zeros((cfg.dim,), jnp.float32),
            "ln2_scale": jnp.ones((cfg.dim,), jnp.float32),
            "ln2_bias": jnp.zeros((cfg.dim,), jnp.float32),
        }
        params["layers"].append(layer)
        for _ in range(4):
            next(k, None)
    return params


def _layernorm(x, scale, bias, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _block(x, layer, cfg: EmbedderConfig, mask):
    # attention — bf16 matmuls land on the MXU; softmax in f32
    h = _layernorm(x, layer["ln1_scale"], layer["ln1_bias"])
    b, s, d = h.shape
    qkv = h @ layer["qkv"].astype(cfg.dtype)
    q, kk, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 3)

    q, kk, v = heads(q), heads(kk), heads(v)
    scores = (q @ kk.transpose(0, 1, 3, 2)).astype(jnp.float32) / np.sqrt(cfg.head_dim)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    att = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    out = (att @ v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + out @ layer["proj"].astype(cfg.dtype)
    # MLP
    h = _layernorm(x, layer["ln2_scale"], layer["ln2_bias"])
    h = jax.nn.gelu(h @ layer["mlp_in"].astype(cfg.dtype))
    x = x + h @ layer["mlp_out"].astype(cfg.dtype)
    return x


def _bert_block(x, layer, cfg: EmbedderConfig, mask):
    """Post-layernorm encoder block matching HF BertLayer exactly (dense
    biases, residual-then-LN, exact erf GELU). bf16/f32 matmuls on the MXU,
    softmax + layernorm statistics in f32."""
    b, s, d = x.shape
    dt = cfg.dtype

    def dense(t, name):
        return t @ layer[f"{name}_w"].astype(dt) + layer[f"{name}_b"].astype(dt)

    def heads(t):
        return t.reshape(b, s, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 3)

    q, kk, v = heads(dense(x, "q")), heads(dense(x, "k")), heads(dense(x, "v"))
    scores = (q @ kk.transpose(0, 1, 3, 2)).astype(jnp.float32) / np.sqrt(cfg.head_dim)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    att = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = (att @ v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = _layernorm(
        x + dense(out, "proj"), layer["ln1_scale"], layer["ln1_bias"], cfg.ln_eps
    )
    h = jax.nn.gelu(dense(x, "mlp_in").astype(jnp.float32), approximate=False)
    x = _layernorm(
        x + dense(h.astype(dt), "mlp_out"),
        layer["ln2_scale"], layer["ln2_bias"], cfg.ln_eps,
    )
    return x


def embed_tokens(params: dict, token_ids: jax.Array, cfg: EmbedderConfig) -> jax.Array:
    """token_ids int32 [batch, seq] (0 = pad) -> f32 [batch, dim], L2-normed
    (mean pooling + normalize — the sentence-transformers MiniLM head)."""
    mask = token_ids > 0
    s = token_ids.shape[1]
    x = params["tok_emb"].astype(cfg.dtype)[token_ids] + params["pos_emb"].astype(
        cfg.dtype
    )[:s][None, :, :]
    if cfg.arch == "bert":
        x = x + params["type_emb"].astype(cfg.dtype)[0][None, None, :]
        x = _layernorm(
            x, params["emb_ln_scale"], params["emb_ln_bias"], cfg.ln_eps
        )
        for layer in params["layers"]:
            x = _bert_block(x, layer, cfg, mask)
    else:
        for layer in params["layers"]:
            x = _block(x, layer, cfg, mask)
        x = _layernorm(x, params["ln_f_scale"], params["ln_f_bias"])
    # masked mean pool
    m = mask[:, :, None].astype(jnp.float32)
    pooled = (x.astype(jnp.float32) * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True).clip(1e-9)


def _np(v) -> np.ndarray:
    """Tensor-library-agnostic ndarray view (torch tensors or arrays)."""
    if hasattr(v, "detach"):
        v = v.detach().cpu().numpy()
    return np.asarray(v, dtype=np.float32)


def load_hf_state_dict(
    state_dict: dict, *, n_heads: int | None = None
) -> tuple[dict, EmbedderConfig]:
    """Map a HF ``BertModel``/MiniLM checkpoint (the param tree
    ``models/embedder.py`` has promised since round 1; reference
    ``xpacks/llm/embedders.py:217`` wraps the same family) onto the
    TPU encoder. HF Linear weights are (out, in) — transposed here to the
    (in, out) matmul layout. Accepts torch tensors or arrays; tolerates the
    ``bert.``-prefixed naming some exports use."""
    sd = {k.removeprefix("bert."): v for k, v in state_dict.items()}
    tok = _np(sd["embeddings.word_embeddings.weight"])
    pos = _np(sd["embeddings.position_embeddings.weight"])
    n_layers = 1 + max(
        int(k.split(".")[2]) for k in sd if k.startswith("encoder.layer.")
    )
    inter = _np(sd["encoder.layer.0.intermediate.dense.weight"]).shape[0]
    dim = tok.shape[1]
    if n_heads is None:
        # the head count is NOT derivable from tensor shapes, and the head
        # partition changes attention output — it must come from the
        # checkpoint's config.json (from_pretrained reads it) or the caller
        raise ValueError(
            "load_hf_state_dict: pass n_heads= (attention output depends on "
            "the head partition; it cannot be inferred from tensor shapes — "
            "see num_attention_heads in the checkpoint's config.json)"
        )
    cfg = EmbedderConfig(
        vocab_size=tok.shape[0], dim=dim, n_layers=n_layers,
        n_heads=n_heads, mlp_ratio=max(1, inter // dim),
        max_len=pos.shape[0], arch="bert", ln_eps=1e-12,
    )
    params: dict = {
        "tok_emb": jnp.asarray(tok),
        "pos_emb": jnp.asarray(pos),
        "type_emb": jnp.asarray(_np(sd["embeddings.token_type_embeddings.weight"])),
        "emb_ln_scale": jnp.asarray(_np(sd["embeddings.LayerNorm.weight"])),
        "emb_ln_bias": jnp.asarray(_np(sd["embeddings.LayerNorm.bias"])),
        "layers": [],
    }
    for i in range(n_layers):
        p = f"encoder.layer.{i}."
        layer = {}
        for ours, theirs in (
            ("q", "attention.self.query"),
            ("k", "attention.self.key"),
            ("v", "attention.self.value"),
            ("proj", "attention.output.dense"),
            ("mlp_in", "intermediate.dense"),
            ("mlp_out", "output.dense"),
        ):
            layer[f"{ours}_w"] = jnp.asarray(_np(sd[p + theirs + ".weight"]).T)
            layer[f"{ours}_b"] = jnp.asarray(_np(sd[p + theirs + ".bias"]))
        layer["ln1_scale"] = jnp.asarray(_np(sd[p + "attention.output.LayerNorm.weight"]))
        layer["ln1_bias"] = jnp.asarray(_np(sd[p + "attention.output.LayerNorm.bias"]))
        layer["ln2_scale"] = jnp.asarray(_np(sd[p + "output.LayerNorm.weight"]))
        layer["ln2_bias"] = jnp.asarray(_np(sd[p + "output.LayerNorm.bias"]))
        params["layers"].append(layer)
    return params, cfg


class Embedder:
    """Host-facing embedder with a cached jitted forward per shape bucket."""

    def __init__(self, cfg: EmbedderConfig | None = None, seed: int = 0,
                 params: dict | None = None, tokenizer: Any = None):
        self.cfg = cfg or EmbedderConfig()
        self.params = params if params is not None else init_params(self.cfg, seed)
        self.tokenizer = tokenizer
        cfg = self.cfg

        def forward(params, token_ids):
            return embed_tokens(params, token_ids, cfg=cfg)

        # a named function, not a bare functools.partial: a device trace
        # then shows the program as ``jit_embed_tokens``, not ``jit__unknown``
        forward.__name__ = forward.__qualname__ = "embed_tokens"
        self._fwd = jax.jit(forward)

    @classmethod
    def from_pretrained(
        cls, source: Any, *, tokenizer: Any = None, dtype: Any = None,
        n_heads: int | None = None,
    ) -> "Embedder":
        """Build from a pretrained MiniLM/BERT checkpoint.

        ``source``: a HF state dict (pass ``n_heads=`` — the head partition
        is not derivable from tensor shapes), or a local directory with
        ``pytorch_model.bin`` + ``config.json`` (``num_attention_heads`` is
        read from it) and optionally ``vocab.txt``, which becomes the
        WordPiece tokenizer. No network access is attempted."""
        import json as _json
        import os

        if isinstance(source, (str, os.PathLike)):
            path = os.fspath(source)
            import torch  # baked in; state dicts are torch-serialized

            state_dict = torch.load(
                os.path.join(path, "pytorch_model.bin"),
                map_location="cpu", weights_only=True,
            )
            cfg_file = os.path.join(path, "config.json")
            if n_heads is None and os.path.exists(cfg_file):
                with open(cfg_file) as f:
                    n_heads = int(_json.load(f)["num_attention_heads"])
            vocab_file = os.path.join(path, "vocab.txt")
            if tokenizer is None and os.path.exists(vocab_file):
                from .wordpiece import WordPieceTokenizer

                tokenizer = WordPieceTokenizer.from_vocab_file(vocab_file)
        else:
            state_dict = source
        params, cfg = load_hf_state_dict(state_dict, n_heads=n_heads)
        if dtype is not None:
            cfg = dataclasses.replace(cfg, dtype=dtype)
        return cls(cfg, params=params, tokenizer=tokenizer)

    def __call__(self, token_ids: np.ndarray) -> np.ndarray:
        return np.asarray(self._fwd(self.params, jnp.asarray(token_ids, jnp.int32)))

    def embed_texts_device(self, texts: list[str], max_len: int = 128) -> jax.Array:
        """Embeddings as a device-resident array (no host fetch): consumers
        that feed another device computation (the KNN scorer) pipeline the
        dispatches and pay ONE blocking fetch for the whole chain.

        The sequence is bucketed to the smallest power of two covering the
        longest REAL token run (min 16): pad columns are masked out of
        attention and the mean pool, so truncating them is numerically
        equivalent (differences ~1e-4 from the finite -1e9 attention mask
        vs absent columns), and a 4-token serve query pays a 16-token
        forward instead of a ``max_len`` one (the dominant slice of REST
        p50 off-TPU). One jit cache entry per bucket."""
        from ..internals.tracing import span
        from ..serve.stats import bump

        max_len = min(max_len, self.cfg.max_len)  # position-table bound
        with span("embed.tokenize", q=len(texts)):
            if self.tokenizer is not None:
                toks = self.tokenizer.encode_batch(texts, max_len)
            else:
                if self.cfg.arch == "bert":
                    raise RuntimeError(
                        "pretrained (arch='bert') embedder has no tokenizer: "
                        "the hashing stand-in would feed token ids the "
                        "checkpoint was never trained on — load with a "
                        "vocab.txt (WordPiece) or pass tokenizer="
                    )
                toks = tokenize_batch(texts, self.cfg.vocab_size, max_len)
            toks = np.asarray(toks, dtype=np.int32)
        n, width = toks.shape
        if n == 0:
            return self._fwd(self.params, jnp.asarray(toks))
        # PER-TEXT buckets: each text's embedding is a pure function of
        # (text, its own bucket) — never of the other texts in the batch
        # (batch-derived buckets would make a re-embedded document's
        # vector drift with batch composition and churn the maintained
        # index; review finding). Texts group by bucket and each group
        # runs one forward; results reassemble device-side.
        lengths = (toks > 0).sum(axis=1)
        buckets = np.maximum(
            16, 2 ** np.ceil(np.log2(np.maximum(lengths, 1))).astype(np.int64)
        )
        buckets = np.minimum(buckets, width)
        # useful work over attempted work in the forward
        bump("embed_real_tokens_total", int(lengths.sum()))
        bump("embed_padded_tokens_total", int(buckets.sum()))
        uniq = np.unique(buckets)
        if len(uniq) == 1:
            b = int(uniq[0])
            with span("embed.dispatch", bucket=b, rows=n):
                return self._fwd(self.params, jnp.asarray(toks[:, :b]))
        out = None
        for b in uniq.tolist():
            ix = np.flatnonzero(buckets == b)
            with span("embed.dispatch", bucket=b, rows=len(ix)):
                part = self._fwd(self.params, jnp.asarray(toks[ix, :b]))
            if out is None:
                out = jnp.zeros((n, part.shape[1]), part.dtype)
            out = out.at[jnp.asarray(ix)].set(part)
        return out

    def embed_texts(self, texts: list[str], max_len: int = 128) -> np.ndarray:
        return np.asarray(self.embed_texts_device(texts, max_len))


def tokenize_batch(texts: list[str], vocab_size: int, max_len: int) -> np.ndarray:
    """Deterministic hashing tokenizer (feature-hashing — a self-contained
    stand-in for a learned vocab; swap with a real WordPiece for pretrained
    weights)."""
    out = np.zeros((len(texts), max_len), dtype=np.int32)
    for i, t in enumerate(texts):
        words = t.lower().split()[: max_len]
        for j, w in enumerate(words):
            out[i, j] = (hash_word(w) % (vocab_size - 2)) + 2
    return out


def hash_word(w: str) -> int:
    h = 2166136261
    for ch in w.encode("utf-8"):
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h
