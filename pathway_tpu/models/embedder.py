"""TPU-native text embedder — the flagship on-device model.

Replaces the reference LLM xpack's CPU-bound ``SentenceTransformerEmbedder``
(``python/pathway/xpacks/llm/embedders.py:217``) with a pure-JAX transformer
encoder that runs on the MXU in bf16: mean-pooled, L2-normalized sentence
embeddings. Weights can be tensor-parallel sharded over a mesh "model" axis
(attention heads + MLP hidden split), with batch data-parallel over "data".

The encoder here is the published BERT block (post-layernorm, biases, exact
GELU), numerically HF ``BertModel``, at all-MiniLM-L6-v2's widths by default.
Its parameters come from a checkpoint (``load_hf_state_dict``) or from a seed
(``init_params``, as BERT initialises them) in one layout, and its tokenizer is
WordPiece: the checkpoint's vocabulary, or for a seeded encoder a vocabulary
built in code (``WordPieceTokenizer.generated``), so the framework runs
self-contained.

The ``Embedder`` serves either that encoder or the LFM2-MoE decoder of
``lfm2.py`` (``from_pretrained(..., config=)`` chooses by ``model_type``):
tokenizing, packing, the declared program shapes, dispatch and warm-up are
shared, and the forward, the resident parameters and the loader are the
model's (``_model_of``).
"""

from __future__ import annotations

import dataclasses
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp
import numpy as np

from .wordpiece import WordPieceTokenizer


@dataclasses.dataclass(frozen=True)
class EmbedderConfig:
    """The encoder's widths; the defaults are all-MiniLM-L6-v2's published
    ``config.json``."""

    vocab_size: int = 30522
    dim: int = 384
    n_layers: int = 6
    n_heads: int = 12
    mlp_ratio: int = 4
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    ln_eps: float = 1e-12

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


#: BERT's token type vocabulary (``type_vocab_size``): a single text is type 0
TYPE_VOCAB = 2


def init_params(cfg: EmbedderConfig, seed: int = 0) -> dict:
    """Seeded float32 masters on the host in ``load_hf_state_dict``'s layout,
    valued as BERT initialises them: matrices and tables normal at 0.02,
    biases zero, layernorm scales one."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    def norm(prefix):
        return {f"{prefix}_scale": np.ones(cfg.dim, np.float32),
                f"{prefix}_bias": np.zeros(cfg.dim, np.float32)}

    d, hidden = cfg.dim, cfg.dim * cfg.mlp_ratio
    products = {"q": (d, d), "k": (d, d), "v": (d, d), "proj": (d, d),
                "mlp_in": (d, hidden), "mlp_out": (hidden, d)}
    params: dict = {"tok_emb": normal(cfg.vocab_size, d), "pos_emb": normal(cfg.max_len, d),
                    "type_emb": normal(TYPE_VOCAB, d), **norm("emb_ln"), "layers": []}
    for _ in range(cfg.n_layers):
        layer = {}
        for name, shape in products.items():
            layer[f"{name}_w"] = normal(*shape)
            layer[f"{name}_b"] = np.zeros(shape[1], np.float32)
        params["layers"].append({**layer, **norm("ln1"), **norm("ln2")})
    return params


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _gelu(h):
    """Exact GELU, h·Φ(h), as ``0.5·h·(1 + erf(h/√2))``: PyTorch's ``gelu``
    and so HF BERT's ``"gelu"``, the formula of the published checkpoints.

    Not JAX's own exact ``gelu`` (``approximate=False``), the same function
    through ``erfc(-h/√2)``: XLA expands a float32 ``erfc`` into both of its
    branches and a select, and on the TPU fuses that into the operand of the
    product that reads the activation (``mlp_out``), which then runs at about
    half its rate. ``erf`` stays one operation and lands in the epilogue of
    the product that makes ``h`` (``mlp_in``)."""
    return 0.5 * h * (1.0 + jax.lax.erf(h * np.float32(np.sqrt(0.5))))


def _bert_block(x, layer, cfg: EmbedderConfig, attend):
    """Post-layernorm encoder block matching HF BertLayer exactly (dense
    biases, residual-then-LN, exact erf GELU). bf16/f32 matmuls on the MXU,
    softmax + layernorm statistics in f32.

    ``attend(project)`` is the block's attention, the one thing the forwards
    vary: ``project(name)`` is ``x``'s [b, s, d] projection by ``q``, ``k`` or
    ``v``, and it returns the attended values, [b, s, d]. It asks for each
    projection where it first reads it, so a program's operations come in the
    order of a block written out in one piece."""
    dt = cfg.dtype

    def dense(t, name):
        return t @ layer[f"{name}_w"].astype(dt) + layer[f"{name}_b"].astype(dt)

    out = attend(lambda name: dense(x, name))
    x = _layernorm(
        x + dense(out, "proj"), layer["ln1_scale"], layer["ln1_bias"], cfg.ln_eps
    )
    h = _gelu(dense(x, "mlp_in").astype(jnp.float32))
    x = _layernorm(
        x + dense(h.astype(dt), "mlp_out"),
        layer["ln2_scale"], layer["ln2_bias"], cfg.ln_eps,
    )
    return x


def _masked_attention(mask, cfg: EmbedderConfig):
    """Softmax attention in which a query sees the keys ``mask`` lets it see
    (``mask`` broadcasts to [batch, heads, query, key])."""

    def attend(project):
        q = project("q")
        b, s, d = q.shape

        def heads(t):
            return t.reshape(b, s, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 3)

        q, kk, v = heads(q), heads(project("k")), heads(project("v"))
        scores = (q @ kk.transpose(0, 1, 3, 2)).astype(jnp.float32) / np.sqrt(cfg.head_dim)
        scores = jnp.where(mask, scores, -1e30)
        att = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        return (att @ v).transpose(0, 2, 1, 3).reshape(b, s, d)

    return attend


def _embeddings(params: dict, token_ids: jax.Array, pos: jax.Array,
               cfg: EmbedderConfig) -> jax.Array:
    """The first layer's input: each token's row plus its position's row
    (``pos``, looked up by the caller) plus type 0's, through the embedding
    layernorm."""
    # the table is cast and then looked up: looked up in float32, the compiler
    # copies the whole table twice a call (0.23 ms at 30,522 x 768; chip run)
    x = params["tok_emb"].astype(cfg.dtype)[token_ids] + pos
    x = x + params["type_emb"].astype(cfg.dtype)[0][None, None, :]
    return _layernorm(x, params["emb_ln_scale"], params["emb_ln_bias"], cfg.ln_eps)


def embed_tokens(
    params: dict, token_ids: jax.Array, cfg: EmbedderConfig, *,
    segments: jax.Array | None = None, positions: jax.Array | None = None,
    texts: int | None = None,
) -> jax.Array:
    """token_ids int32 [batch, seq] (0 = pad) -> f32 [batch, dim], L2-normed
    (mean pooling + normalize — the sentence-transformers MiniLM head).

    With ``segments`` a row holds several texts one after another along the
    token axis: ``segments`` [batch, seq] names the text of each position
    (0 .. ``texts`` - 1 over the whole batch; padding carries ``texts``, a
    segment of its own), ``positions`` [batch, seq] restart at every text.
    Attention stays inside a segment and the pool and the norm are taken for
    each: -> f32 [``texts``, dim], a text that is not there a zero vector.

    ``params["layers"]`` is a list of layers, laid out one after another
    (the compiler fetches a layer's matrices while the one before it runs),
    or the same tensors stacked along a leading layer axis
    (``stack_layers``), which the program loops over with one traced layer:
    it then costs a layer's compile and code, not a model's. Every matrix,
    bias and table is cast to ``cfg.dtype`` where it is read, so float32
    masters (``init_params``, ``load_hf_state_dict``) run as they are; the
    ``Embedder`` keeps its parameters in that dtype (``resident_params``),
    where the casts do nothing and the vectors are the same to the bit."""
    if segments is None:
        keep = token_ids > 0
        mask = keep[:, None, None, :]
        pos = params["pos_emb"].astype(cfg.dtype)[: token_ids.shape[1]][None, :, :]
    else:
        mask = (segments[:, :, None] == segments[:, None, :])[:, None, :, :]
        pos = params["pos_emb"].astype(cfg.dtype)[positions]
    x = _embeddings(params, token_ids, pos, cfg)
    attend = _masked_attention(mask, cfg)
    layers = params["layers"]
    if isinstance(layers, dict):
        x, _ = jax.lax.scan(
            lambda h, layer: (_bert_block(h, layer, cfg, attend), None), x, layers)
    else:
        for layer in layers:
            x = _bert_block(x, layer, cfg, attend)
    x = x.astype(jnp.float32)
    if segments is None:
        # masked mean pool
        m = keep[:, :, None].astype(jnp.float32)
        pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    else:
        # a text's positions lie in one row: summed there (a product with the
        # row's membership matrix, at full float32), and the other rows add
        # exact zeros, so its sum does not depend on which row it is in
        member = (segments[:, :, None] == jnp.arange(texts)).astype(jnp.float32)
        sums = jnp.einsum("rlt,rld->rtd", member, x,
                          precision=jax.lax.Precision.HIGHEST).sum(0)
        pooled = sums / jnp.maximum(member.sum((0, 1)), 1.0)[:, None]
    return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True).clip(1e-9)


def stack_layers(layers: list[dict]) -> dict:
    """The layers' tensors stacked along a leading layer axis: the form
    ``embed_tokens`` loops over."""
    return {k: jnp.stack([layer[k] for layer in layers]) for k in layers[0]}


def _is_norm(name: str) -> bool:
    """A layernorm scale or bias: read in float32 (``_layernorm``)."""
    return name.startswith(("ln", "emb_ln"))


def _placed(name: str, value: Any, dtype: Any) -> jax.Array:
    """One tensor on the device as the programs read it: a layernorm
    parameter as it is, anything else cast to ``dtype`` there, the same
    rounding as the cast inside a program."""
    value = jnp.asarray(value)
    return value if _is_norm(name) else value.astype(dtype)


def resident_params(params: dict, cfg: EmbedderConfig) -> dict:
    """The parameters as the ``Embedder``'s programs read them, placed on
    the device one tensor at a time, so that a float32 set is never there
    whole beside them: the matrices, their biases and the token, position
    and type tables in ``cfg.dtype``, cast once here; layernorm scales and
    biases as they are (float32). The layers are kept twice, as a list for
    the programs that lay them out (``layers``) and stacked for those that
    loop over them (``stacked``): a program of a few rows is bound by one
    read of its parameters, and read from a stack, even by static slices,
    it waits for each matrix where from a list the compiler fetches the next
    layer's while one runs (chip readings: ``PERF.md`` section 6)."""
    dt = cfg.dtype
    out = {k: _placed(k, v, dt) for k, v in params.items() if k != "layers"}
    out["layers"] = [{k: _placed(k, v, dt) for k, v in layer.items()}
                     for layer in params["layers"]]
    out["stacked"] = stack_layers(out["layers"])
    return out


def handed(params: dict, looped: bool) -> dict:
    """The parameters one program is handed: the tables with the layers
    stacked, for a program that loops over them, or as a list, for one
    that lays them out; never the form it does not read."""
    tables = {k: v for k, v in params.items() if k not in ("layers", "stacked")}
    return {**tables, "layers": params["stacked"] if looped else params["layers"]}


def _model_of(cfg: Any):
    """The module that computes ``cfg``'s model (its ``init_params``,
    ``resident_params``, ``handed`` and ``embed_tokens``): this one for the
    BERT encoder, ``lfm2`` for the LFM2-MoE decoder."""
    if isinstance(cfg, EmbedderConfig):
        import sys

        return sys.modules[__name__]
    from . import lfm2

    return lfm2


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
    ``bert.``-prefixed naming some exports use. The float32 masters stay on
    the host: an ``Embedder`` places them in the dtype it computes in,
    tensor by tensor (``resident_params``)."""
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
        max_len=pos.shape[0], ln_eps=1e-12,
    )
    params: dict = {
        "tok_emb": tok,
        "pos_emb": pos,
        "type_emb": _np(sd["embeddings.token_type_embeddings.weight"]),
        "emb_ln_scale": _np(sd["embeddings.LayerNorm.weight"]),
        "emb_ln_bias": _np(sd["embeddings.LayerNorm.bias"]),
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
            layer[f"{ours}_w"] = _np(sd[p + theirs + ".weight"]).T
            layer[f"{ours}_b"] = _np(sd[p + theirs + ".bias"])
        layer["ln1_scale"] = _np(sd[p + "attention.output.LayerNorm.weight"])
        layer["ln1_bias"] = _np(sd[p + "attention.output.LayerNorm.bias"])
        layer["ln2_scale"] = _np(sd[p + "output.LayerNorm.weight"])
        layer["ln2_bias"] = _np(sd[p + "output.LayerNorm.bias"])
        params["layers"].append(layer)
    return params, cfg


#: texts one dispatch carries at the most: every program has this many outputs
TEXTS_PER_DISPATCH = 32
#: programs compiled at a time when the declared set is warmed
WARM_THREADS = 4
#: The programs the served path runs, as {row length: row counts}: a call's
#: texts are laid out in ``R`` rows of ``L`` tokens, and ``(R, L)`` is always
#: one of these (``declared_shapes`` fits them to a model's positions), the
#: texts packed along the token axis into the rows. Lengths are few
#: because a text shorter than its row shares it with others; row counts at
#: the longest length are fine enough that rounding up costs a tick of eight
#: passages a seventh at the most, the others powers of two, and a length's
#: counts end where the next length holds as many tokens in fewer rows.
#: Nothing else is compiled while serving.
SHAPES = {16: (1, 2, 4, 8, 16), 128: (1, 2, 4, 8), 512: (1, 2, 3, 4, 5, 6, 8, 10, 12, 16)}
#: a stored text's row is at least this long (then the power of two that holds it)
STORED_ROW_MIN = 16


def _rows_by_length(max_len: int) -> dict[int, tuple[int, ...]]:
    """``SHAPES`` for a model of ``max_len`` positions: the lengths under
    ``max_len``, then ``max_len`` itself with the row counts of the first
    declared length that holds it (of the longest, for a model of more
    positions than any)."""
    lengths = sorted(SHAPES)
    last = next((n for n in lengths if n >= max_len), lengths[-1])
    return {**{n: SHAPES[n] for n in lengths if n < max_len}, max_len: SHAPES[last]}


def declared_shapes(max_len: int) -> tuple[tuple[int, int], ...]:
    """Every (R, L) a model of ``max_len`` positions is served at, by length."""
    return tuple((r, n) for n, rows in _rows_by_length(max_len).items() for r in rows)


def _blank_ids(rows: int, length: int) -> np.ndarray:
    """int32 [3, R, L] (tokens, segments, positions) of a program that holds
    no text yet: every position padding, in the padding's own segment."""
    ids = np.zeros((3, rows, length), np.int32)
    ids[1] = TEXTS_PER_DISPATCH
    return ids


def _pack(lengths: np.ndarray, row: int) -> list[list[int]]:
    """First fit, longest first: the texts (by index) of each row of ``row``
    tokens."""
    rows: list[list[int]] = []
    free: list[int] = []
    for i in np.argsort(-lengths, kind="stable").tolist():
        n = int(lengths[i])
        for r, room in enumerate(free):
            if room >= n:
                free[r] -= n
                rows[r].append(i)
                break
        else:
            free.append(row - n)
            rows.append([i])
    return rows


class Embedder:
    """Host-facing embedder: one jitted forward on the served path, run at
    the shapes of ``self.shapes`` and at no other."""

    def __init__(self, cfg: Any = None, seed: int = 0,
                 params: dict | None = None, tokenizer: Any = None):
        """``cfg``: the model's shape, an ``EmbedderConfig`` (the BERT
        encoder) or an ``lfm2.Lfm2Config`` (the LFM2-MoE decoder); its module
        computes the forward (``_model_of``). ``params``: float32 masters (the
        model's ``load_hf_state_dict`` tree on the host; ``init_params(cfg,
        seed)``'s where none are given), kept as the model's
        ``resident_params`` makes them; the caller's own copy is not touched.
        ``tokenizer``: a ``WordPieceTokenizer``; a seeded model given none
        gets ``WordPieceTokenizer.generated``, one given ``params`` none."""
        from ..internals.tracing import span
        from ..serve.stats import bump

        self.cfg = cfg or EmbedderConfig()
        model = self._model = _model_of(self.cfg)
        masters = params if params is not None else model.init_params(self.cfg, seed)
        with span("embed.place") as sp:
            #: every form of the parameters the programs read, and nothing else
            #: of them on the device: setting it to None frees the model
            self.params = model.resident_params(masters, self.cfg)
            placed = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.params))
            if sp is not None:
                sp.args["bytes"] = placed
        bump("embed_param_bytes_total", placed)
        if tokenizer is None and params is None:
            tokenizer = WordPieceTokenizer.generated(self.cfg.vocab_size)
        self.tokenizer = tokenizer
        cfg = self.cfg
        #: every (rows, length) the served forward is run at, by length
        self.shapes = declared_shapes(cfg.max_len)
        self._rows_of = _rows_by_length(cfg.max_len)
        self._question = self.shapes[0][1]

        def forward(params, ids):
            # ids int32 [3, R, L]: tokens, segments, positions. Rows of question
            # length are bound by one read of the parameters, so their few
            # programs lay the encoder's layers out, as a search's program always
            # did; the passage programs are bound by their products, and loop
            # over one layer: cheap to compile and to keep (``_handed``; the
            # decoder's programs all read its one list of layers)
            return model.embed_tokens(params, ids[0], cfg, segments=ids[1],
                                      positions=ids[2], texts=TEXTS_PER_DISPATCH)

        def token_rows(params, token_ids):
            return model.embed_tokens(params, token_ids, cfg)

        def take(vectors, rows):
            return vectors[:rows]

        # named functions, not bare functools.partial: a device trace then
        # shows the programs as ``jit_embed_tokens`` and ``jit_embed_take``,
        # not ``jit__unknown``
        forward.__name__ = forward.__qualname__ = "embed_tokens"
        take.__name__ = take.__qualname__ = "embed_take"
        token_rows.__name__ = token_rows.__qualname__ = "embed_tokens"
        self._fwd = jax.jit(forward)
        self._take = jax.jit(take, static_argnums=1)
        self._token_rows = jax.jit(token_rows)
        self._warm_lock = threading.Lock()
        self._count_lock = threading.Lock()
        self._warm = False
        self._compiled = 0

    @classmethod
    def from_pretrained(
        cls, source: Any, *, tokenizer: Any = None, dtype: Any = None,
        n_heads: int | None = None, config: dict | None = None,
        max_len: int | None = None,
    ) -> "Embedder":
        """Build from a pretrained checkpoint: a BERT/MiniLM encoder, or an
        LFM2-MoE decoder (HF ``model_type`` ``lfm2_moe``).

        ``source``: a HF state dict, or a local directory with
        ``pytorch_model.bin`` + ``config.json`` and optionally ``vocab.txt``,
        which becomes the WordPiece tokenizer. ``config``: the checkpoint's
        ``config.json`` as a dict; its ``model_type`` chooses the model (BERT
        where there is none), and a BERT state dict needs it or ``n_heads=``
        (the head partition is not derivable from tensor shapes).
        ``max_len``: a decoder's longest text served, in positions
        (``lfm2.SERVED_POSITIONS`` where none is given; an encoder serves its
        position table's). No network access is attempted."""
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
            if config is None and os.path.exists(cfg_file):
                with open(cfg_file) as f:
                    config = _json.load(f)
            vocab_file = os.path.join(path, "vocab.txt")
            if tokenizer is None and os.path.exists(vocab_file):
                tokenizer = WordPieceTokenizer.from_vocab_file(vocab_file)
        else:
            state_dict = source
        if config is not None and config.get("model_type") == "lfm2_moe":
            from . import lfm2

            params, cfg = lfm2.load_hf_state_dict(state_dict, config, dtype=dtype,
                                                  max_len=max_len)
            return cls(cfg, params=params, tokenizer=tokenizer)
        if n_heads is None and config is not None:
            n_heads = int(config["num_attention_heads"])
        params, cfg = load_hf_state_dict(state_dict, n_heads=n_heads)
        if dtype is not None:
            cfg = dataclasses.replace(cfg, dtype=dtype)
        return cls(cfg, params=params, tokenizer=tokenizer)

    def __call__(self, token_ids: np.ndarray) -> np.ndarray:
        """Rows of token ids (0 = pad), one text a row, at the caller's own
        shape: a program apart from the served ones."""
        return np.asarray(self._token_rows(self._handed(looped=False),
                                           jnp.asarray(token_ids, jnp.int32)))

    def _handed(self, looped: bool) -> dict:
        """The parameters one program is handed: the form it reads (the
        model's ``handed``)."""
        return self._model.handed(self.params, looped)

    def warm(self) -> None:
        """Compile (or load from the compile cache) every program the served
        path can run: the forward at each of ``self.shapes`` and the cut of
        its result to 1 .. ``TEXTS_PER_DISPATCH`` vectors, several at a time.
        The embedder's first call does it, a query's or an ingest's, where
        the owner has not; after it no served call compiles."""
        if self._warm:
            return
        with self._warm_lock:
            if self._warm:
                return
            with ThreadPoolExecutor(WARM_THREADS) as pool:
                out = list(pool.map(lambda shape: self._forward(_blank_ids(*shape)),
                                    self.shapes))
            for n in range(1, TEXTS_PER_DISPATCH + 1):
                self._take(out[0], n)
            jax.block_until_ready(out)
            self._warm = True

    def _forward(self, ids: np.ndarray) -> jax.Array:
        from ..serve.stats import bump

        out = self._fwd(self._handed(looped=ids.shape[2] > self._question),
                        jnp.asarray(ids))
        with self._count_lock:
            compiled = self._fwd._cache_size()
            if compiled != self._compiled:
                bump("embed_shapes_compiled_total", compiled - self._compiled)
                self._compiled = compiled
        return out

    def _tokenize(self, texts: list[str], max_len: int | None):
        """Token rows [n, width] (0 = pad), each text's length and the limit
        they were cut at: ``max_len``, the model's positions where the caller
        states none or more."""
        from ..internals.tracing import span
        from ..serve.stats import bump

        limit = self.cfg.max_len if max_len is None else min(max_len, self.cfg.max_len)
        with span("embed.tokenize", q=len(texts)) as sp:
            # one token over the limit is asked for, so that a text that is
            # cut can be told from one that just fits
            if self.tokenizer is None:
                raise RuntimeError(
                    "pretrained embedder has no tokenizer: ids of another "
                    "vocabulary would feed the checkpoint tokens it was never "
                    "trained on — load with a vocab.txt (WordPiece) or pass "
                    "tokenizer="
                )
            toks = self.tokenizer.encode_batch(texts, limit + 1)
            toks = np.array(toks, dtype=np.int32)  # a copy: a cut text is closed in place
            lengths = (toks > 0).sum(axis=1)
            cut = np.flatnonzero(lengths > limit)
            if len(cut):
                # the tokenizer's last token closes the text ([SEP]): kept
                toks[cut, limit - 1] = toks[cut, limit]
                toks[cut, limit:] = 0
                lengths[cut] = limit
                bump("embed_truncated_texts_total", len(cut))
            if sp is not None:
                sp.args["tokens"] = int(lengths.sum())
        return toks, lengths, limit

    def _plan(self, lengths: np.ndarray):
        """(R, L, rows) for the texts of these lengths packed into one
        program: the shortest declared length that holds the longest text and
        whose row counts hold the packing. None where no program holds them."""
        if len(lengths) > TEXTS_PER_DISPATCH:
            return None
        longest = int(lengths.max(initial=0))
        for length, counts in self._rows_of.items():
            if length < longest:
                continue
            rows = _pack(lengths, length)
            fit = next((r for r in counts if r >= len(rows)), None)
            if fit is not None:
                return fit, length, rows
        return None

    def _dispatch(self, toks, lengths, texts: list[int], plan) -> jax.Array:
        """One program over ``texts`` (indices into ``toks``), laid out as
        ``plan`` says: -> [TEXTS_PER_DISPATCH, dim] on the device, the vector
        of ``texts[j]`` at ``j``."""
        n_rows, length, rows = plan
        ids = _blank_ids(n_rows, length)
        for r, members in enumerate(rows):
            at = 0
            for j in members:
                n = int(lengths[texts[j]])
                ids[0, r, at:at + n] = toks[texts[j], :n]
                ids[1, r, at:at + n] = j
                ids[2, r, at:at + n] = np.arange(n)
                at += n
        with self._dispatching(length, n_rows, len(texts), int(lengths[texts].sum())):
            return self._forward(ids)

    def _dispatching(self, length: int, rows: int, texts: int, real: int):
        """The span and the counters of one forward handed to the device:
        useful work (``real`` tokens) over attempted work (rows x length);
        for a model with experts, the (token, expert) assignments its real
        tokens make over its expert layers, known here without a fetch."""
        from ..internals.tracing import span
        from ..serve.stats import bump

        bump("embed_dispatches_total")
        bump("embed_real_tokens_total", real)
        bump("embed_padded_tokens_total", rows * length)
        experts = {}
        if getattr(self.cfg, "moe_layers", 0):
            experts = {"moe_layers": self.cfg.moe_layers,
                       "experts_per_token": self.cfg.experts_per_token}
            bump("embed_expert_assignments_total",
                 real * self.cfg.moe_layers * self.cfg.experts_per_token)
        return span("embed.dispatch", bucket=length, rows=rows, texts=texts,
                    tokens=real, computed=rows * length, **experts)

    def _packed(self, toks, lengths, texts: list[int]) -> list[tuple[jax.Array, int]]:
        """The dispatches that embed ``texts``, as (vectors, how many of them
        count): one, unless no program holds them all."""
        plan = self._plan(lengths[texts])
        if plan is not None:
            return [(self._dispatch(toks, lengths, texts, plan), len(texts))]
        half = len(texts) // 2
        return (self._packed(toks, lengths, texts[:half])
                + self._packed(toks, lengths, texts[half:]))

    def embed_texts_device(self, texts: list[str], max_len: int | None = None,
                           rows: int | None = None) -> jax.Array:
        """Embeddings as a device-resident array (no host fetch): consumers
        that feed another device computation (the KNN scorer) pipeline the
        dispatches and pay ONE blocking fetch for the whole chain. This is
        the served path, for texts that are asked and never stored.

        A text is embedded whole up to the model's own positions
        (``max_len`` may state less); a longer one is cut there and counted
        (``embed_truncated_texts_total``). The call's texts are packed along
        the token axis into the rows of one program of ``self.shapes``
        (attention, positions, pool and norm per text), so a call is one
        dispatch whatever lengths it mixes; only a call that no program
        holds (over ``TEXTS_PER_DISPATCH`` texts, or more tokens than the
        largest shape) is split. ``rows`` pads the result with zero vectors
        to that many rows. Every program is compiled before the first call
        returns (``warm``)."""
        toks, lengths, _ = self._tokenize(texts, max_len)
        self.warm()
        rows = len(texts) if rows is None else rows
        if not len(texts):
            return jnp.zeros((rows, self.cfg.dim), jnp.float32)
        parts = self._packed(toks, lengths, list(range(len(texts))))
        if len(parts) == 1 and rows <= TEXTS_PER_DISPATCH:
            return self._take(parts[0][0], rows)
        out = jnp.concatenate([self._take(v, n) for v, n in parts])
        return out if rows == len(texts) else jnp.pad(out, ((0, rows - len(texts)), (0, 0)))

    def embed_texts(self, texts: list[str], max_len: int | None = None) -> np.ndarray:
        """Embeddings on the host, for texts whose vectors are stored: a
        stored vector is a function of its text alone, never of the texts
        embedded with it (a re-embedded document must not drift with its
        batch and churn the maintained index). So stored texts are not
        packed: each has a row to itself, as long as the power of two that
        holds it (``STORED_ROW_MIN`` at the least, the limit at the most),
        and the texts of one length go to the device together, as one
        forward of exactly their rows. Ingest has no deadline: its programs
        are compiled as its lengths and counts are met, not declared, and
        the served set is compiled here too (``warm``), so that a store fed
        with texts has its queries' programs before its first query, which
        has a deadline (a sharded server's gather gives a shard 5 s)."""
        toks, lengths, limit = self._tokenize(texts, max_len)
        self.warm()
        out = np.zeros((len(texts), self.cfg.dim), np.float32)
        if not len(texts):
            return out
        own = np.minimum(limit, np.maximum(
            STORED_ROW_MIN, 2 ** np.ceil(np.log2(np.maximum(lengths, 1))).astype(np.int64)))
        sent = []
        for length in np.unique(own).tolist():
            of_length = np.flatnonzero(own == length)
            ids = np.zeros((len(of_length), length), np.int32)
            width = min(length, toks.shape[1])
            ids[:, :width] = toks[of_length, :width]
            with self._dispatching(length, len(of_length), len(of_length),
                                   int(lengths[of_length].sum())):
                sent.append((of_length, self._token_rows(self._handed(looped=False),
                                                         jnp.asarray(ids))))
        # every program is on its way before the first result is waited for
        for of_length, vectors in sent:
            out[of_length] = np.asarray(vectors)
        return out
