"""LFM2-MoE (HF ``lfm2_moe``, LiquidAI's LFM2-8B-A1B) as the store's
embedder: a causal decoder pooled at each text's last token (the final
RMSNorm's vector there, L2-normalised), as decoder retrievers are served
(E5-Mistral, arXiv:2401.00368).

Every layer is pre-norm with no biases, ``h = x + op(RMSNorm(x))`` then
``out = h + ffn(RMSNorm(h))``, with RMSNorm ``x·rsqrt(mean(x²) + eps)·w``:

- a ``conv`` layer's ``op`` is the gated short convolution: ``B, C, u =
  split3(in_proj x)``, ``y = C ⊙ causal_depthwise_conv(B ⊙ u)`` over
  ``conv_L_cache`` taps, then ``out_proj``;
- a ``full_attention`` layer's ``op`` is grouped-query attention with an
  RMSNorm on each query and key head, rotary positions (half split), causal
  softmax at ``head_dim**-0.5``, then ``out_proj``;
- ``ffn`` is a SwiGLU ``w2(silu(w1 h) ⊙ w3 h)`` in the first
  ``num_dense_layers`` layers and after them the dropless top-k expert layer
  of ``moe.py``;
- a final RMSNorm (``embedding_norm``) closes the model.

The forward runs over the packed rows the ``Embedder`` builds: several texts
one after another along a row, each a segment. A convolution tap and an
attention key reach back only within the query's own segment, positions
restart at each text, and padding is given to no expert.

Products run in ``cfg.dtype`` with float32 accumulation; the residual stream,
the norms, the rotary positions, the softmax and the router are float32.

The parameters are held on the device once, a list of layers that every
program lays out (``resident_params``): at layers 0-9 of LFM2-8B-A1B a
``[3, 512]`` forward read 11.7 ms from the list and 28.9 ms scanning over the
same layers stacked by position in their [attn, conv, conv, conv] period,
where each step sliced a layer's experts out of the stack (TPU v5e).
"""

from __future__ import annotations

import dataclasses
from typing import Any

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp
import numpy as np

from . import moe

_F32 = jnp.float32
ATTENTION = "full_attention"
#: the longest text a decoder is served at where its caller states no limit:
#: the longest declared program (``embedder.SHAPES``), not the positions the
#: model was trained to (128,000 for LFM2), which no served program declares
SERVED_POSITIONS = 512


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The decoder's shape, by HF ``lfm2_moe`` ``config.json`` key."""

    vocab_size: int
    dim: int                      # hidden_size
    n_heads: int                  # num_attention_heads
    n_kv_heads: int               # num_key_value_heads
    intermediate: int             # intermediate_size: the dense layers' SwiGLU
    moe_intermediate: int         # moe_intermediate_size: one expert's SwiGLU
    n_experts: int                # num_experts
    experts_per_token: int        # num_experts_per_tok
    n_dense: int                  # num_dense_layers
    layer_types: tuple[str, ...]  # "conv" or "full_attention", one a layer
    conv_len: int = 3             # conv_L_cache
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    #: positions a served text is embedded at, at the most
    max_len: int = SERVED_POSITIONS
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def moe_layers(self) -> int:
        return max(0, self.n_layers - self.n_dense)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "Lfm2Config":
        """From a HF ``lfm2_moe`` ``config.json``; the served limit is
        ``SERVED_POSITIONS`` or the model's own positions, the fewer, unless
        ``max_len`` is given."""
        layer_types = tuple(config["layer_types"][:config["num_hidden_layers"]])
        fields = dict(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            intermediate=config["intermediate_size"],
            moe_intermediate=config["moe_intermediate_size"],
            n_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            n_dense=config["num_dense_layers"], layer_types=layer_types,
            conv_len=config.get("conv_L_cache", 3),
            norm_eps=float(config.get("norm_eps", 1e-5)),
            rope_theta=float(config.get("rope_theta", 1e6)),
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            routed_scaling_factor=float(config.get("routed_scaling_factor", 1.0)),
            use_expert_bias=bool(config.get("use_expert_bias", True)),
            max_len=min(SERVED_POSITIONS, int(config.get("max_position_embeddings",
                                                         SERVED_POSITIONS))),
        )
        if config.get("conv_bias"):
            raise ValueError("lfm2: a short convolution with biases is not supported")
        return cls(**{**fields, **{k: v for k, v in overrides.items() if v is not None}})


# -- parameters -----------------------------------------------------------------


def _is_float32(name: str) -> bool:
    """Kept in float32 where placed: the norms, the convolution's taps (read
    by elementwise float32 arithmetic) and the router (a float32 product)."""
    return name.endswith("norm") or name in ("conv", "router", "expert_bias")


def init_params(cfg: Lfm2Config, seed: int = 0) -> dict:
    """Seeded float32 masters on the host in ``load_hf_state_dict``'s layout:
    matrices normal at 0.02, norm weights one, expert biases zero."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    d, hd = cfg.dim, cfg.head_dim
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        layer = {"op_norm": np.ones(d, np.float32), "ffn_norm": np.ones(d, np.float32)}
        if kind == ATTENTION:
            layer.update(qkv=normal(d, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
                         q_norm=np.ones(hd, np.float32), k_norm=np.ones(hd, np.float32),
                         out_proj=normal(d, d))
        else:
            layer.update(in_proj=normal(d, 3 * d), conv=normal(cfg.conv_len, d),
                         out_proj=normal(d, d))
        if i < cfg.n_dense:
            layer.update(w13=normal(d, 2 * cfg.intermediate), w2=normal(cfg.intermediate, d))
        else:
            f = cfg.moe_intermediate
            layer.update(router=normal(d, cfg.n_experts),
                         expert_bias=np.zeros(cfg.n_experts, np.float32),
                         w13=normal(cfg.n_experts, d, 2 * f), w2=normal(cfg.n_experts, f, d))
        layers.append(layer)
    return {"tok_emb": normal(cfg.vocab_size, d), "final_norm": np.ones(d, np.float32),
            "layers": layers}


def _np(v) -> np.ndarray:
    if hasattr(v, "detach"):
        v = v.detach().cpu().numpy()
    return np.asarray(v, dtype=np.float32)


def load_hf_state_dict(state_dict: dict, config: dict, **overrides) -> tuple[dict, Lfm2Config]:
    """Map a HF ``lfm2_moe`` state dict onto the decoder's layout: Linear
    weights (out, in) as (in, out), q/k/v side by side (``qkv``), each SwiGLU's
    ``w1`` and ``w3`` side by side (``w13``), the convolution's taps [L, d] in
    time order (tap ``L - 1`` reads the position itself), and each MoE
    layer's experts one after another. Accepts torch tensors or arrays;
    ``overrides`` are ``Lfm2Config`` fields (``max_len``, ``dtype``).

    Nothing is copied on the host: a tensor is a float32 view of the
    checkpoint's, and a tuple of views (joined along its last axis) or a list
    (stacked) is put together where ``resident_params`` places it on the
    device (a layer's experts in float32 would be 1.4 GB apiece)."""
    cfg = Lfm2Config.from_hf(config, **overrides)
    sd = state_dict

    def w(name):  # a Linear's weight as (in, out)
        return _np(sd[name]).T

    layers = []
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        layer = {"op_norm": _np(sd[p + "operator_norm.weight"]),
                 "ffn_norm": _np(sd[p + "ffn_norm.weight"])}
        if kind == ATTENTION:
            a = p + "self_attn."
            layer.update(qkv=tuple(w(a + n + ".weight") for n in ("q_proj", "k_proj", "v_proj")),
                         q_norm=_np(sd[a + "q_layernorm.weight"]),
                         k_norm=_np(sd[a + "k_layernorm.weight"]),
                         out_proj=w(a + "out_proj.weight"))
        else:
            c = p + "conv."
            layer.update(in_proj=w(c + "in_proj.weight"),
                         conv=_np(sd[c + "conv.weight"]).reshape(cfg.dim, cfg.conv_len).T,
                         out_proj=w(c + "out_proj.weight"))
        f = p + "feed_forward."
        if i < cfg.n_dense:
            layer.update(w13=(w(f + "w1.weight"), w(f + "w3.weight")), w2=w(f + "w2.weight"))
        else:
            e = [f"{f}experts.{j}." for j in range(cfg.n_experts)]
            layer.update(
                router=w(f + "gate.weight"),
                expert_bias=(_np(sd[f + "expert_bias"]) if cfg.use_expert_bias
                             else np.zeros(cfg.n_experts, np.float32)),
                w13=[(w(x + "w1.weight"), w(x + "w3.weight")) for x in e],
                w2=[w(x + "w2.weight") for x in e])
        layers.append(layer)
    return {"tok_emb": _np(sd["model.embed_tokens.weight"]),
            "final_norm": _np(sd["model.embedding_norm.weight"]), "layers": layers}, cfg


def _placed(name: str, value: Any, dtype: Any) -> jax.Array:
    """One tensor on the device as the programs read it: an array as it is,
    a tuple's parts joined along the last axis, a list's items stacked; each
    part cast to ``dtype`` there (the rounding of a cast inside a program)
    unless it is read in float32 (``_is_float32``)."""
    if isinstance(value, list):
        return jnp.stack([_placed(name, v, dtype) for v in value])
    if isinstance(value, tuple):
        return jnp.concatenate([_placed(name, v, dtype) for v in value], axis=-1)
    value = jnp.asarray(value)
    return value if _is_float32(name) else value.astype(dtype)


def resident_params(params: dict, cfg: Lfm2Config) -> dict:
    """The parameters as the programs read them, once: the tables and a list
    of layers, each MoE layer's experts stacked. Placed a part at a time, so
    that no float32 copy of the model is ever on the device, or made on the
    host."""
    dt = cfg.dtype
    return {"tok_emb": _placed("tok_emb", params["tok_emb"], dt),
            "final_norm": _placed("final_norm", params["final_norm"], dt),
            "layers": [{k: _placed(k, v, dt) for k, v in layer.items()}
                       for layer in params["layers"]]}


def handed(params: dict, looped: bool) -> dict:
    """Every program reads the one form the parameters are held in."""
    return params


# -- the forward ----------------------------------------------------------------


def _mm(x, w, dt):
    return jnp.matmul(x.astype(dt), w.astype(dt), preferred_element_type=_F32)


def _rmsnorm(x, w, eps):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(_F32)


def _short_conv(h, layer, cfg: Lfm2Config, same):
    """``out_proj(C ⊙ conv(B ⊙ u))``; ``same[j - 1]`` [R, L] says whether the
    position ``j`` back lies in the same text (else the tap reads zero, as the
    published causal padding does at a text's start)."""
    with jax.named_scope("short_conv"):
        b, c, u = jnp.split(_mm(h, layer["in_proj"], cfg.dtype), 3, axis=-1)
        bx = b * u
        taps = layer["conv"].astype(_F32)  # [L, d]: tap L - 1 - j reads j back
        y = bx * taps[-1]
        for j in range(1, cfg.conv_len):
            back = jnp.pad(bx, ((0, 0), (j, 0), (0, 0)))[:, :-j]
            y = y + jnp.where(same[j - 1][..., None], back, 0.0) * taps[-1 - j]
        return _mm(c * y, layer["out_proj"], cfg.dtype)


def _rotary(positions, cfg: Lfm2Config):
    """(cos, sin) [R, L, head_dim] of each position, half-split layout."""
    hd = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    f = positions.astype(_F32)[..., None] * jnp.asarray(inv, _F32)
    f = jnp.concatenate([f, f], axis=-1)
    return jnp.cos(f), jnp.sin(f)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, :, None] + rotated * sin[:, :, None]


def _gqa(h, layer, cfg: Lfm2Config, mask, cos, sin):
    """Grouped-query attention: query head ``g · n_rep + j`` reads key and
    value head ``g``; ``mask`` [R, L, L] is causal within a segment."""
    with jax.named_scope("gqa"):
        r, n, _ = h.shape
        hq, kv, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
        q, k, v = jnp.split(_mm(h, layer["qkv"], dt), [hq * hd, (hq + kv) * hd], axis=-1)
        q = _rope(_rmsnorm(q.reshape(r, n, hq, hd), layer["q_norm"], cfg.norm_eps), cos, sin)
        k = _rope(_rmsnorm(k.reshape(r, n, kv, hd), layer["k_norm"], cfg.norm_eps), cos, sin)
        q = q.reshape(r, n, kv, hq // kv, hd)
        s = jnp.einsum("rqgjd,rkgd->rgjqk", q.astype(dt), k.astype(dt),
                       preferred_element_type=_F32) * np.float32(hd ** -0.5)
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("rgjqk,rkgd->rqgjd", p.astype(dt), v.reshape(r, n, kv, hd).astype(dt),
                       preferred_element_type=_F32)
        return _mm(o.reshape(r, n, hq * hd), layer["out_proj"], dt)


def _ffn(h, layer, cfg: Lfm2Config, dense: bool, valid):
    dt = cfg.dtype
    if dense:
        g, u = jnp.split(_mm(h, layer["w13"], dt), 2, axis=-1)
        return _mm(jax.nn.silu(g) * u, layer["w2"], dt)
    r, n, d = h.shape
    flat = h.reshape(r * n, d)
    experts, weights = moe.route(
        flat, layer["router"], layer["expert_bias"] if cfg.use_expert_bias else None,
        cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor)
    out = moe.experts_swiglu(flat, experts, weights, layer["w13"], layer["w2"], valid, dt)
    return out.reshape(r, n, d)


def _layer(x, layer, cfg: Lfm2Config, kind: str, dense: bool, ctx):
    mask, same, cos, sin, valid = ctx
    h = _rmsnorm(x, layer["op_norm"], cfg.norm_eps)
    x = x + (_gqa(h, layer, cfg, mask, cos, sin) if kind == ATTENTION
             else _short_conv(h, layer, cfg, same))
    return x + _ffn(_rmsnorm(x, layer["ffn_norm"], cfg.norm_eps), layer, cfg, dense, valid)


def embed_tokens(
    params: dict, token_ids: jax.Array, cfg: Lfm2Config, *,
    segments: jax.Array | None = None, positions: jax.Array | None = None,
    texts: int | None = None,
) -> jax.Array:
    """token_ids int32 [R, L] -> float32 [texts, dim], L2-normalised: the
    final norm's vector at each text's last token.

    With ``segments`` (the ``Embedder``'s packed rows) a row holds several
    texts: ``segments`` [R, L] names each position's text (0 .. ``texts`` -
    1; padding carries ``texts``), ``positions`` restart at every text, and a
    text that is not there comes out a zero vector. Without, each row is one
    text padded on the right with id 0: -> [R, dim]."""
    r, n = token_ids.shape
    if segments is None:
        texts = r
        segments = jnp.where(token_ids > 0, jnp.arange(r, dtype=jnp.int32)[:, None], r)
        positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (r, n))
    x = params["tok_emb"].astype(cfg.dtype)[token_ids].astype(_F32)
    real = segments < texts
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    mask = (segments[:, :, None] == segments[:, None, :]) & causal
    same = [jnp.pad(segments[:, j:] == segments[:, :-j], ((0, 0), (j, 0)))
            for j in range(1, cfg.conv_len)]
    cos, sin = _rotary(positions, cfg)
    ctx = (mask, same, cos, sin, real.reshape(-1))
    for i, layer in enumerate(params["layers"]):
        x = _layer(x, layer, cfg, cfg.layer_types[i], i < cfg.n_dense, ctx)
    # each text's last token: where the next position holds another segment
    after = jnp.pad(segments[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    last = real & (segments != after)
    member = ((segments[:, :, None] == jnp.arange(texts)) & last[:, :, None]).astype(_F32)
    pooled = jnp.einsum("rlt,rld->td", member, x, precision=jax.lax.Precision.HIGHEST)
    pooled = _rmsnorm(pooled, params["final_norm"], cfg.norm_eps)
    return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True).clip(1e-9)
