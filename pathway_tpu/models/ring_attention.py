"""Ring attention — sequence-parallel attention over a device mesh.

Long documents embed as one sequence sharded across devices on a ``seq``
mesh axis: each device holds its Q/K/V block, K/V blocks rotate around the
ring via ``lax.ppermute`` (ICI neighbor hops, overlapping compute with
transfer), and softmax is accumulated online (flash-attention style
running max/normalizer), so no device ever materializes the full S×S score
matrix. This is the long-context capability the framework treats as
first-class; the reference has no attention kernels at all (SURVEY §5.7) —
its "long sequence" machinery is temporal windowing.

Numerics: scores and accumulators in float32, inputs may be bf16.
"""

from __future__ import annotations

import functools
from typing import Any

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "full_attention"]

_NEG = -1e30


def full_attention(q, k, v, mask, scale: float):
    """Reference single-device attention (correctness oracle for the ring).

    q,k,v: [B, S, H, D]; mask: [B, S] bool (key-side padding mask).
    """
    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kh = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vh = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    scores = (qh @ kh.transpose(0, 1, 3, 2)) * jnp.float32(scale)
    scores = jnp.where(mask[:, None, None, :], scores, jnp.float32(_NEG))
    att = jax.nn.softmax(scores, axis=-1)
    out = att @ vh
    return out.transpose(0, 2, 1, 3)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array,
    mesh: Mesh,
    axis: str,
    scale: float,
) -> jax.Array:
    """Sequence-parallel attention.

    q,k,v: [B, S, H, D] sharded over S on mesh axis ``axis``;
    mask: [B, S] bool, sharded the same way. Returns [B, S, H, D] f32,
    sharded over S.
    """
    n = mesh.shape[axis]
    perm = [(j, (j + 1) % n) for j in range(n)]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis),
        ),
        out_specs=P(None, axis, None, None),
        check_vma=False,
    )
    def inner(qb, kb, vb, mb):
        b, s, h, d = qb.shape
        qh = qb.transpose(0, 2, 1, 3).astype(jnp.float32)  # [B,H,s,D]

        def accumulate(carry_olm, kb, vb, mb):
            o, m, l = carry_olm
            kh = kb.transpose(0, 2, 1, 3).astype(jnp.float32)
            vh = vb.transpose(0, 2, 1, 3).astype(jnp.float32)
            scores = (qh @ kh.transpose(0, 1, 3, 2)) * jnp.float32(scale)  # [B,H,s,s_blk]
            scores = jnp.where(mb[:, None, None, :], scores, jnp.float32(_NEG))
            m_new = jnp.maximum(m, scores.max(-1))
            p = jnp.exp(scores - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            o_new = o * corr[..., None] + p @ vh
            return (o_new, m_new, l_new)

        def step(_, carry):
            olm, kb, vb, mb = carry
            olm = accumulate(olm, kb, vb, mb)
            # rotate the K/V/mask blocks one hop around the ring (ICI)
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
            mb = lax.ppermute(mb, axis, perm)
            return (olm, kb, vb, mb)

        o0 = jnp.zeros((b, h, s, d), jnp.float32)
        m0 = jnp.full((b, h, s), jnp.float32(_NEG), jnp.float32)
        l0 = jnp.zeros((b, h, s), jnp.float32)
        # n-1 rotations suffice: the last block is consumed without another
        # round of collectives
        olm, kb, vb, mb = lax.fori_loop(
            0, n - 1, step, ((o0, m0, l0), kb, vb, mb)
        )
        o, m, l = accumulate(olm, kb, vb, mb)
        out = o / jnp.maximum(l, jnp.float32(1e-30))[..., None]
        return out.transpose(0, 2, 1, 3)

    return inner(q, k, v, mask)


def embed_tokens_long(
    params: dict,
    token_ids: jax.Array,
    cfg: Any,
    mesh: Mesh,
    axis: str = "data",
) -> jax.Array:
    """Long-context embedding forward: the encoder of
    ``models.embedder.embed_tokens`` (same parameters, same block) with the
    sequence dimension sharded over `axis`, attention run as a ring, and a
    global masked mean pool. token_ids int32 [B, S] (0 = pad),
    S % mesh.shape[axis] == 0. Positions use modular position embeddings for
    S beyond cfg.max_len."""
    from .embedder import _bert_block, _embeddings

    mask = token_ids > 0
    s = token_ids.shape[1]
    pos = params["pos_emb"].astype(cfg.dtype)[jnp.arange(s) % params["pos_emb"].shape[0]]
    x = _embeddings(params, token_ids, pos[None, :, :], cfg)

    def attend(project):
        def heads(t):
            return t.reshape(*t.shape[:2], cfg.n_heads, cfg.head_dim)

        att = ring_attention(
            heads(project("q")), heads(project("k")), heads(project("v")), mask,
            mesh, axis, scale=1.0 / float(cfg.head_dim) ** 0.5,
        )
        return att.reshape(*att.shape[:2], cfg.dim).astype(cfg.dtype)

    for layer in params["layers"]:
        x = _bert_block(x, layer, cfg, attend)
    m = mask[:, :, None].astype(jnp.float32)
    pooled = (x.astype(jnp.float32) * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True).clip(1e-9)
