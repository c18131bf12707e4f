"""The LFM2-MoE family (HF ``model_type`` "lfm2_moe", LiquidAI's LFM2-8B-A1B)
as the store's embedder: a causal decoder of gated short convolutions and
grouped-query attention with a dropless top-k expert layer after its leading
dense layers, pooled at each text's last token (``<|im_end|>``), L2-normalised.

What the benchmark needs of a model, as ``bert.py`` gives it for BERT:

- the keys of a configuration that make the model (``MODEL_KEYS``): the HF
  ``config.json``'s, ``initializer_range`` and the served limit
  (``served_positions``);
- the vocabulary's specials and first word id, and the framing of a text
  (``frame``: ``<|startoftext|> w1 .. wn <|im_end|>``);
- the weights from the seed (``make_weights``), by HF name;
- the plain reference (``reference_params``, ``encode``): float32 at
  ``highest``, every product through ``reference.mm``, computed layer by layer
  with only the running layer's tensors on the device, each expert over the
  tokens routed to it; with ``precision="fp8"`` the control, which has to
  come out not correct. It imports nothing of ``pathway_tpu``;
- the work a token needs (``flops_per_token``) and the parameter bytes a call
  must read (``param_bytes_per_call``), from shapes;
- the device programs that the embed readers sum (``PROGRAM``);
- the program's embedder, built through its public loader
  (``build_embedder``).

Loaded in the parent, which never imports JAX: JAX and the program are
imported inside the functions that need them.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import threading
from collections.abc import Mapping
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from lib import datagen

#: what ``Deployment.model`` takes from a configuration: the HF config's keys
MODEL_KEYS = ("model_type", "vocab_size", "hidden_size", "num_hidden_layers",
              "layer_types", "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "moe_intermediate_size", "num_dense_layers",
              "num_experts", "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "use_expert_bias", "conv_L_cache", "conv_bias",
              "norm_eps", "rope_theta", "max_position_embeddings", "initializer_range",
              "served_positions")
#: the published vocabulary's first ids (pad 0, bos 1, eos 7); the ids up to
#: ``FIRST_WORD_ID`` stand for its other specials and reserved ids
SPECIALS = {0: "<|pad|>", 1: "<|startoftext|>", 2: "<|endoftext|>", 3: "<|fim_pre|>",
            4: "<|fim_mid|>", 5: "<|fim_suf|>", 6: "<|im_start|>", 7: "<|im_end|>"}
FIRST_WORD_ID = 1000
_ID = {token: i for i, token in SPECIALS.items()}
#: tokens ``frame`` adds to a text's words
FRAMING = 2
#: the device programs of the embed forward, by the name the trace gives them
PROGRAM = "embed_tokens"
ATTENTION = "full_attention"


def frame(word_ids: list[int]) -> list[int]:
    """``<|startoftext|> w1 .. wn <|im_end|>``: the text as the decoder is fed
    it, pooled at its closing token."""
    return [_ID["<|startoftext|>"], *word_ids, _ID["<|im_end|>"]]


# -- weights ----------------------------------------------------------------


def _layer_types(model: dict) -> list[str]:
    return list(model["layer_types"][:model["num_hidden_layers"]])


def tensor_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """HF ``lfm2_moe`` state-dict names and shapes for a ``config.json``."""
    d, hd = model["hidden_size"], model["hidden_size"] // model["num_attention_heads"]
    hq, kv = model["num_attention_heads"], model["num_key_value_heads"]
    ff, f, e = model["intermediate_size"], model["moe_intermediate_size"], model["num_experts"]
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (model["vocab_size"], d),
        "model.embedding_norm.weight": (d,),
    }
    for i, kind in enumerate(_layer_types(model)):
        p = f"model.layers.{i}."
        shapes[p + "operator_norm.weight"] = (d,)
        shapes[p + "ffn_norm.weight"] = (d,)
        if kind == ATTENTION:
            a = p + "self_attn."
            shapes.update({a + "q_proj.weight": (hq * hd, d), a + "k_proj.weight": (kv * hd, d),
                           a + "v_proj.weight": (kv * hd, d), a + "out_proj.weight": (d, hq * hd),
                           a + "q_layernorm.weight": (hd,), a + "k_layernorm.weight": (hd,)})
        else:
            c = p + "conv."
            shapes.update({c + "in_proj.weight": (3 * d, d),
                           c + "conv.weight": (d, 1, model["conv_L_cache"]),
                           c + "out_proj.weight": (d, d)})
        m = p + "feed_forward."
        if i < model["num_dense_layers"]:
            shapes.update({m + "w1.weight": (ff, d), m + "w3.weight": (ff, d),
                           m + "w2.weight": (d, ff)})
        else:
            shapes[m + "gate.weight"] = (e, d)
            shapes[m + "expert_bias"] = (e,)
            for j in range(e):
                x = f"{m}experts.{j}."
                shapes.update({x + "w1.weight": (f, d), x + "w3.weight": (f, d),
                               x + "w2.weight": (d, f)})
    return shapes


def _program_has_a_loader() -> None:
    """The program under test must load what is drawn here: a checkout whose
    ``pathway_tpu`` has no LFM2 module stops at the first step of the run,
    not after the minutes of reference that precede building the server.
    Looks for the file; imports nothing of the program."""
    spec = importlib.util.find_spec("pathway_tpu")
    roots = list(spec.submodule_search_locations or []) if spec else []
    if not any(os.path.isfile(os.path.join(r, "models", "lfm2.py")) for r in roots):
        raise RuntimeError("the program under test has no lfm2_moe loader "
                           f"(no pathway_tpu/models/lfm2.py under {roots or 'any path'})")


class _Drawn(Mapping):
    """The seeded state dict by HF name, each tensor drawn when it is read.

    Held whole, the float32 tensors of the cut are 12.8 GB of the host,
    beside the rows, for the run's whole life. Here a read draws its tensor's
    group (one layer's tensors, or those outside the layers) in threads and
    holds it until a read of another layer: the program's loader and the
    reference, which read layer by layer, draw each layer once a pass, and
    no more than a layer (1.5 GB) and the tensors outside the layers
    (0.54 GB) stay. A tensor is the same bytes at every read."""

    def __init__(self, seed: int, shapes: dict[str, tuple[int, ...]], std: float):
        self._seed, self._shapes, self._std = seed, shapes, std
        self._names = sorted(shapes)
        self._ix = {name: i for i, name in enumerate(self._names)}
        self._groups: dict[str, list[str]] = {}
        for name in self._names:
            self._groups.setdefault(_group(name), []).append(name)
        self._held: dict[str, dict[str, np.ndarray]] = {}
        self._lock = threading.Lock()

    def _draw(self, name: str) -> np.ndarray:
        a = datagen.stream(self._seed, 3, self._ix[name]).standard_normal(
            self._shapes[name], dtype=np.float32)
        a *= self._std
        if name.endswith("norm.weight"):
            a += 1.0
        return a

    def __getitem__(self, name: str) -> np.ndarray:
        if name not in self._ix:
            raise KeyError(name)
        group = _group(name)
        with self._lock:
            if group not in self._held:
                for old in [g for g in self._held if g != _OUTSIDE]:
                    del self._held[old]
                names = self._groups[group]
                with ThreadPoolExecutor(datagen.threads()) as pool:
                    self._held[group] = dict(zip(names, pool.map(self._draw, names)))
            return self._held[group][name]

    def __contains__(self, name) -> bool:
        return name in self._ix

    def __iter__(self):
        return iter(self._names)

    def __len__(self) -> int:
        return len(self._names)


_OUTSIDE = ""


def _group(name: str) -> str:
    """``model.layers.{i}.`` for a layer's tensor, else ``_OUTSIDE``."""
    if name.startswith("model.layers."):
        return name[:name.index(".", len("model.layers.")) + 1]
    return _OUTSIDE


def make_weights(seed: int, model: dict) -> Mapping[str, np.ndarray]:
    """A seeded random HF-named float32 state dict, drawn as it is read
    (``_Drawn``): every tensor normal at ``initializer_range`` (the expert
    biases too, so that they move the choice of experts without deciding it:
    the sigmoid scores they are added to spread by about 0.2); norm weights
    are 1 plus that."""
    _program_has_a_loader()
    return _Drawn(seed, tensor_shapes(model), float(model["initializer_range"]))


# -- the plain reference ----------------------------------------------------
#
# Texts are sorted by length and run in groups of one width (a power of two
# from ``NARROWEST``) and ``GROUP_TOKENS`` tokens, each row padded on the
# right; a causal model pooled at a text's last token never reads the padding
# after it. The
# groups' hidden states stay on the device and the layers pass over them one
# at a time, each layer's float32 tensors moved there for its turn alone. What
# works on tokens alone (the dense SwiGLU, the router, the experts, the pool)
# sees a group as [GROUP_TOKENS, d], so that it compiles once for every width.

#: tokens of one group (rows x width), which bounds its activations
GROUP_TOKENS = 16_384
#: an expert's tokens are gathered into a block of a multiple of this many rows
EXPERT_BLOCK = 1024
#: the narrowest group (texts of up to this many tokens share it)
NARROWEST = 128


def reference_params(weights: Mapping[str, np.ndarray]):
    """(the tensors outside the layers, a view of each layer's by the name
    after ``model.layers.{i}.``): nothing is read here, and ``encode`` moves
    each layer's tensors to the device for its turn."""
    n = 1 + max((int(_group(name).split(".")[2]) for name in weights
                 if _group(name) != _OUTSIDE), default=-1)
    return weights, [_Layer(weights, f"model.layers.{i}.") for i in range(n)]


class _Layer(Mapping):
    """One layer's tensors of a state dict, by their names after ``prefix``."""

    def __init__(self, weights: Mapping[str, np.ndarray], prefix: str):
        self._weights, self._prefix = weights, prefix
        self._names = [n[len(prefix):] for n in weights if n.startswith(prefix)]

    def __getitem__(self, name: str) -> np.ndarray:
        return self._weights[self._prefix + name]

    def __iter__(self):
        return iter(self._names)

    def __len__(self) -> int:
        return len(self._names)


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


@functools.cache
def _steps():
    """The reference's jitted pieces (JAX imported on first use); every
    product goes through ``reference.mm``."""
    import jax
    import jax.numpy as jnp

    from lib import reference

    def linear(x, w, precision):  # x @ w.T for a HF Linear weight (out, in)
        return reference.mm(x, jnp.swapaxes(w, -1, -2), precision)

    statics = ("precision", "eps")

    @functools.partial(jax.jit, static_argnames=statics + ("taps",))
    def conv_op(x, w, *, precision, eps, taps):
        h = _rmsnorm(x, w["operator_norm.weight"], eps)
        b, c, u = jnp.split(linear(h, w["conv.in_proj.weight"], precision), 3, axis=-1)
        bu = b * u
        kernel = w["conv.conv.weight"][:, 0, :]  # [d, L]: column L-1 reads t itself
        s = bu.shape[1]
        padded = jnp.pad(bu, ((0, 0), (taps - 1, 0), (0, 0)))
        y = sum(padded[:, i:i + s] * kernel[:, i] for i in range(taps))
        return x + linear(c * y, w["conv.out_proj.weight"], precision)

    @functools.partial(jax.jit, static_argnames=statics + ("heads", "kv", "theta"))
    def attention_op(x, w, *, precision, eps, heads, kv, theta):
        b, s, d = x.shape
        hd = d // heads
        h = _rmsnorm(x, w["operator_norm.weight"], eps)
        q = linear(h, w["self_attn.q_proj.weight"], precision).reshape(b, s, heads, hd)
        k = linear(h, w["self_attn.k_proj.weight"], precision).reshape(b, s, kv, hd)
        v = linear(h, w["self_attn.v_proj.weight"], precision).reshape(b, s, kv, hd)
        q = _rmsnorm(q, w["self_attn.q_layernorm.weight"], eps)
        k = _rmsnorm(k, w["self_attn.k_layernorm.weight"], eps)
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
        cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
        sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]

        def rotate(t):
            t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
            return t * cos + jnp.concatenate([-t2, t1], -1) * sin

        q, k = rotate(q), rotate(k)
        rep = heads // kv
        k = jnp.repeat(k, rep, axis=2).transpose(0, 2, 3, 1)   # [b, H, hd, s]
        v = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3)   # [b, H, s, hd]
        sc = reference.mm(q.transpose(0, 2, 1, 3), k, precision) / np.sqrt(hd)
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        att = jax.nn.softmax(sc, axis=-1)
        ctx = reference.mm(att, v, precision).transpose(0, 2, 1, 3).reshape(b, s, d)
        return x + linear(ctx, w["self_attn.out_proj.weight"], precision)

    @functools.partial(jax.jit, static_argnames=statics)
    def dense_ffn(x, w, *, precision, eps):
        h = _rmsnorm(x, w["ffn_norm.weight"], eps)
        g = linear(h, w["feed_forward.w1.weight"], precision)
        u = linear(h, w["feed_forward.w3.weight"], precision)
        return x + linear(jax.nn.silu(g) * u, w["feed_forward.w2.weight"], precision)

    @functools.partial(jax.jit, static_argnames=statics + ("k", "bias", "norm", "scale"))
    def route(x, w, *, precision, eps, k, bias, norm, scale):
        """(normed tokens [T, d], experts [T, k], weights [T, k])."""
        h = _rmsnorm(x, w["ffn_norm.weight"], eps)
        s = jax.nn.sigmoid(linear(h, w["feed_forward.gate.weight"], precision))
        choose = s + w["feed_forward.expert_bias"] if bias else s
        _, experts = jax.lax.top_k(choose, k)
        weight = jnp.take_along_axis(s, experts, axis=1)
        if norm:
            weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
        return h, experts, weight * scale

    @functools.partial(jax.jit, static_argnames=("precision",))
    def experts(x, h, rows, at, weights, w1, w3, w2, *, precision):
        """``x`` [T, d] plus each expert's SwiGLU over its own tokens:
        ``rows`` [E, block] names them (padding points at row T) and ``at``
        their entries of the flat [T x k] ``weights``; ``w1``, ``w3``, ``w2``
        are the experts' weights stacked [E, ...]."""
        t = h.shape[0]
        hx = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])[rows]
        y = linear(jax.nn.silu(linear(hx, w1, precision)) * linear(hx, w3, precision),
                   w2, precision)
        y = y * jnp.where(rows < t, weights[at], 0.0)[..., None]
        out = jnp.zeros((t + 1, h.shape[1]), jnp.float32).at[rows.reshape(-1)].add(
            y.reshape(-1, h.shape[1]))
        return x + out[:t]

    @functools.partial(jax.jit, static_argnames=("eps",))
    def pool(x, last, norm_w, *, eps):
        v = _rmsnorm(x[last], norm_w, eps)
        return v / jnp.linalg.norm(v, axis=-1, keepdims=True)

    return conv_op, attention_op, dense_ffn, route, experts, pool


def _groups(lengths: np.ndarray, batch: int) -> list[tuple[np.ndarray, int]]:
    """(row indices, width) of each group: a row's width is the power of two
    (``NARROWEST`` at the least) that holds it, and the rows of one width,
    shortest first, go in groups of ``min(batch, GROUP_TOKENS // width)``."""
    widths = np.maximum(NARROWEST, 2 ** np.ceil(np.log2(np.maximum(lengths, 1)))).astype(np.int64)
    out = []
    for width in np.unique(widths).tolist():
        rows = np.flatnonzero(widths == width)
        rows = rows[np.argsort(lengths[rows], kind="stable")]
        size = max(1, min(batch, GROUP_TOKENS // width))
        out += [(rows[s:s + size], width) for s in range(0, len(rows), size)]
    return out


def _moe(x, w, stacked, model, precision, real, steps):
    """The expert layer over one group's tokens ``x`` [T, d]: each expert's
    SwiGLU over the real tokens routed to it, gathered in a block of a
    multiple of ``EXPERT_BLOCK`` rows (as many as the busiest expert needs),
    then added back, weighted, to its tokens."""
    import jax.numpy as jnp

    _, _, _, route, experts, _ = steps
    h, chosen, weight = route(
        x, w, precision=precision, eps=float(model["norm_eps"]),
        k=model["num_experts_per_tok"], bias=bool(model["use_expert_bias"]),
        norm=bool(model["norm_topk_prob"]), scale=float(model["routed_scaling_factor"]))
    chosen = np.asarray(chosen)
    t, k = chosen.shape
    tok, slot = np.nonzero(np.repeat(real[:, None], k, axis=1))
    expert = chosen[tok, slot]
    order = np.argsort(expert, kind="stable")
    counts = np.bincount(expert, minlength=model["num_experts"])
    block = max(EXPERT_BLOCK, -(-int(counts.max()) // EXPERT_BLOCK) * EXPERT_BLOCK)
    rows = np.full((model["num_experts"], block), t, np.int32)
    at = np.zeros((model["num_experts"], block), np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    place = np.arange(len(order)) - np.repeat(starts, counts)
    rows[expert[order], place] = tok[order]
    at[expert[order], place] = tok[order] * k + slot[order]
    return experts(x, h, jnp.asarray(rows), jnp.asarray(at), weight.reshape(-1), *stacked,
                   precision=precision)


def encode(params, ids: np.ndarray, model: dict, precision: str = "float32",
           batch: int = 256) -> np.ndarray:
    """Unit vectors [n, d] (float32, on the host) of token rows ``ids``
    (right-padded with 0): the final norm's vector at each row's last token."""
    import jax.numpy as jnp

    steps = _steps()
    conv_op, attention_op, dense_ffn, _, _, pool = steps
    outer, layers = params
    d = model["hidden_size"]
    lengths = (ids > 0).sum(1)
    groups = _groups(lengths, batch)
    table = jnp.asarray(outer["model.embed_tokens.weight"])
    xs, reals = [], []
    for rows, width in groups:
        full = max(1, min(batch, GROUP_TOKENS // width))
        part = np.zeros((full, width), np.int32)
        part[:len(rows), :min(width, ids.shape[1])] = ids[rows, :width]
        part[len(rows):] = part[len(rows) - 1]
        xs.append(table[jnp.asarray(part)])
        real = np.zeros((full, width), bool)
        real[:len(rows)] = np.arange(width)[None, :] < lengths[rows][:, None]
        reals.append(real.reshape(-1))
    del table
    eps, kinds = float(model["norm_eps"]), _layer_types(model)
    for i, kind in enumerate(kinds):
        w = {name: jnp.asarray(v) for name, v in layers[i].items()
             if ".experts." not in name}
        # the op's tensors and the ffn's apart, so that each step compiles once
        op = {k: v for k, v in w.items() if not k.startswith(("ffn_norm", "feed_forward"))}
        ffn = {k: v for k, v in w.items() if k not in op}
        dense = i < model["num_dense_layers"]
        stacked = None
        if not dense:
            stacked = [jnp.stack([jnp.asarray(layers[i][f"feed_forward.experts.{e}.{n}"])
                                  for e in range(model["num_experts"])])
                       for n in ("w1.weight", "w3.weight", "w2.weight")]
        for g, x in enumerate(xs):
            if kind == ATTENTION:
                x = attention_op(x, op, precision=precision, eps=eps,
                                 heads=model["num_attention_heads"],
                                 kv=model["num_key_value_heads"],
                                 theta=float(model["rope_theta"]))
            else:
                x = conv_op(x, op, precision=precision, eps=eps, taps=model["conv_L_cache"])
            shape, x = x.shape, x.reshape(-1, d)
            if dense:
                x = dense_ffn(x, ffn, precision=precision, eps=eps)
            else:
                x = _moe(x, ffn, stacked, model, precision, reals[g], steps)
            xs[g] = x.reshape(shape)
        del w, op, ffn, stacked
    out = np.zeros((len(ids), d), np.float32)
    norm_w = jnp.asarray(outer["model.embedding_norm.weight"])
    for (rows, width), x in zip(groups, xs):
        # each row's last token in the group's [tokens, d]; padded to the most
        # rows a group can hold, so that the pool compiles once
        flat = np.zeros(max(len(rows), GROUP_TOKENS // NARROWEST), np.int32)
        flat[:len(rows)] = np.arange(len(rows)) * width + lengths[rows] - 1
        out[rows] = np.asarray(pool(x.reshape(-1, d), jnp.asarray(flat), norm_w,
                                    eps=eps))[:len(rows)]
    return out


# -- work -------------------------------------------------------------------


def _parameters(model: dict) -> dict[str, int]:
    """Parameters by part: ``outside`` (every one a call reads whatever its
    tokens, the embedding table left out), ``expert`` (one expert of one
    layer) and ``per_token`` (those a token's products use: each layer's
    ``op``, the dense SwiGLUs, and the router and ``num_experts_per_tok``
    experts of each MoE layer)."""
    d, hq, kv = model["hidden_size"], model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // hq
    conv = 3 * d * d + d * d + d * model["conv_L_cache"]
    attn = d * hq * hd + 2 * d * kv * hd + hq * hd * d
    dense = 3 * d * model["intermediate_size"]
    expert = 3 * d * model["moe_intermediate_size"]
    router = d * model["num_experts"]
    outside = per_token = 0
    for i, kind in enumerate(_layer_types(model)):
        op = attn if kind == ATTENTION else conv
        norms = 2 * d + (2 * hd if kind == ATTENTION else 0)
        if i < model["num_dense_layers"]:
            outside += op + dense + norms
            per_token += op + dense
        else:
            outside += op + router + model["num_experts"] + norms
            per_token += op + router + model["num_experts_per_tok"] * expert
    return {"outside": outside + d, "expert": expert, "per_token": per_token}


def flops_per_token(model: dict, seq: int) -> float:
    """Per token of a text of ``seq`` real tokens: 2 x the parameters its
    products use (each layer's ``op``, the dense SwiGLUs, each MoE layer's
    router and ``num_experts_per_tok`` experts) plus, for each attention
    layer, 2·(seq + 1)·H·hd for QKᵀ and as many for AV over the causal half
    (a token attends to (seq + 1) / 2 keys on average)."""
    d = model["hidden_size"]
    attn_layers = sum(k == ATTENTION for k in _layer_types(model))
    return float(2 * _parameters(model)["per_token"] + attn_layers * 2 * (seq + 1) * d)


def param_bytes_per_call(model: dict, tokens: int) -> float:
    """bfloat16 bytes a forward of ``tokens`` real tokens must read: every
    parameter outside the experts (the embedding table's looked-up rows
    alone) and, in each MoE layer, the experts its tokens can choose, at most
    ``num_experts_per_tok`` x ``tokens`` of them."""
    p = _parameters(model)
    moe_layers = model["num_hidden_layers"] - model["num_dense_layers"]
    experts = min(model["num_experts"], model["num_experts_per_tok"] * tokens)
    return 2.0 * (p["outside"] + moe_layers * experts * p["expert"]
                  + tokens * model["hidden_size"])


# -- the program's embedder -------------------------------------------------


def build_embedder(weights: dict[str, np.ndarray], model: dict,
                   vocab_index: dict[str, int], dtype):
    """The program's embedder of these weights and this vocabulary, served in
    ``dtype`` up to ``served_positions`` tokens, through its public loader."""
    from pathway_tpu.models.embedder import Embedder
    from pathway_tpu.models.wordpiece import WordPieceTokenizer

    tokenizer = WordPieceTokenizer(
        dict(vocab_index), cls_token="<|startoftext|>", sep_token="<|im_end|>",
        pad_token="<|pad|>", unk_token="<|endoftext|>")
    return Embedder.from_pretrained(
        weights, config=dict(model), max_len=model["served_positions"],
        tokenizer=tokenizer, dtype=dtype)
