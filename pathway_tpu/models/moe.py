"""A dropless top-k expert layer: every token is computed by each of the
``k`` experts its router chooses, and by no other, with no capacity and no
dropped token.

The router is the one of HF ``lfm2_moe``: ``s = sigmoid(h @ router)`` in
float32, the top ``k`` of ``s + expert_bias`` choose the experts (the bias
balances load and only chooses), and the chosen entries of ``s`` itself weigh
them, normalised to sum to one (``norm_topk_prob``) and scaled by
``routed_scaling_factor``.

The experts run as grouped products (``jax.lax.ragged_dot``): the ``tokens x
k`` assignments are sorted by expert, each expert's SwiGLU is applied to its
own contiguous group, and the weighted results are added back to their tokens.
A token that is not real (padding of a packed row) is given no assignment: its
rows are sorted past the last group and come out zero.
"""

from __future__ import annotations

from typing import Any

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def route(h: jax.Array, router: jax.Array, expert_bias: jax.Array | None, k: int, *,
          norm_topk_prob: bool = True, scale: float = 1.0):
    """(experts [T, k] int32, weights [T, k] float32) of float32 tokens ``h``
    [T, d]: the product with ``router`` [d, E] at full float32, so that no
    rounding of the compute dtype flips a near tie."""
    with jax.named_scope("moe.route"):
        logits = jnp.matmul(h.astype(_F32), router.astype(_F32),
                            precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        choice = s if expert_bias is None else s + expert_bias.astype(_F32)
        _, experts = jax.lax.top_k(choice, k)
        w = jnp.take_along_axis(s, experts, axis=-1)
        if norm_topk_prob:
            w = w / (w.sum(-1, keepdims=True) + 1e-6)
        return experts, w * scale


def experts_swiglu(h: jax.Array, experts: jax.Array, weights: jax.Array,
                   w13: jax.Array, w2: jax.Array, valid: jax.Array, dtype: Any) -> jax.Array:
    """Σ over each token's chosen experts of ``weight · w2(silu(g) ⊙ u)``, with
    ``[g, u] = h @ w13[e]``: float32 [T, d].

    ``w13`` [E, d, 2f] and ``w2`` [E, f, d] are the experts' matrices and
    ``valid`` [T] marks the real tokens. Products in ``dtype`` with float32
    accumulation."""
    with jax.named_scope("moe.experts"):
        t, k = experts.shape
        n = w13.shape[0]
        # a padding token's assignments get key n: sorted past the last group
        key = jnp.where(valid[:, None], experts, n).reshape(-1)
        order = jnp.argsort(key, stable=True)
        token = order // k
        sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
        gu = jax.lax.ragged_dot(h.astype(dtype)[token], w13.astype(dtype), sizes,
                                preferred_element_type=_F32)
        g, u = jnp.split(gu, 2, axis=-1)
        y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(dtype), w2.astype(dtype),
                               sizes, preferred_element_type=_F32)
        # rows past the last group (padding) may hold anything
        kept = key[order] < n
        y = jnp.where(kept[:, None], y * weights.reshape(-1)[order][:, None], 0.0)
        return jnp.zeros((t, y.shape[-1]), _F32).at[token].add(y)
