"""The flagship distributed step: embed → exchange → index → retrieve → learn.

This is the framework's "training step" analog — one tick of the Adaptive-RAG
north-star pipeline (BASELINE.json) jitted over a 2D (data, model) mesh:

- **dp**: token batches sharded over ``data``;
- **tp**: embedder QKV/MLP weights sharded over ``model`` (XLA inserts the
  psum/all-gather for the split matmuls);
- **index sharding (the sp/ep analog)**: KNN index rows sharded over
  ``data``; queries hit every shard, local top-k, all-gather merge;
- **record exchange**: embeddings routed to owner shards by key low bits via
  bucketed all-to-all (the timely exchange analog, parallel/exchange.py);
- a contrastive gradient step on the embedder params (SGD) so the whole
  backward pass also compiles under the same shardings.
"""

from __future__ import annotations

import functools
from typing import Any

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.knn import sharded_knn_search
from .embedder import EmbedderConfig, embed_tokens, init_params


def param_shardings(mesh: Mesh, params: dict) -> dict:
    """Tensor-parallel layout: split QKV/MLP hidden over the model axis."""

    def spec_for(path: str):
        if path in ("qkv", "mlp_in"):
            return P(None, "model")
        if path in ("proj", "mlp_out"):
            return P("model", None)
        return P()

    def map_tree(p):
        out = {}
        for k, v in p.items():
            if k == "layers":
                out[k] = [
                    {kk: NamedSharding(mesh, spec_for(kk)) for kk in layer}
                    for layer in v
                ]
            else:
                out[k] = NamedSharding(mesh, P())
        return out

    return map_tree(params)


def make_step(mesh: Mesh, cfg: EmbedderConfig, k: int = 4, lr: float = 1e-3):
    """Build the jitted full step over the mesh."""

    def loss_fn(params, tokens_a, tokens_b):
        ea = embed_tokens(params, tokens_a, cfg)
        eb = embed_tokens(params, tokens_b, cfg)
        logits = (ea @ eb.T) / 0.07
        labels = jnp.arange(ea.shape[0])
        loss = (
            -jax.nn.log_softmax(logits, axis=-1)[labels, labels].mean()
            - jax.nn.log_softmax(logits.T, axis=-1)[labels, labels].mean()
        )
        return loss, ea

    def step(params, tokens, tokens_aug, index, insert_at, queries):
        (loss, emb), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, tokens_aug
        )
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        # ingest: write the fresh embeddings into the sharded index
        index = jax.lax.dynamic_update_slice(
            index, emb.astype(index.dtype), (insert_at, 0)
        )
        # retrieve: sharded brute-force KNN with all-gather merge
        qe = embed_tokens(params, queries, cfg)
        scores, ids = sharded_knn_search(mesh, "data", qe, index, k)
        return params, index, loss, scores, ids

    in_shardings = (
        param_shardings(mesh, init_params(cfg, 0)),
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P("data", None)),
        None,
        NamedSharding(mesh, P()),
    )
    return jax.jit(step, in_shardings=in_shardings, donate_argnums=(3,))


def run_one_step(mesh: Mesh, cfg: EmbedderConfig | None = None, batch: int = 8, seq: int = 16, k: int = 2):
    """Build tiny inputs and run one full distributed step (dryrun path)."""
    data_size = mesh.shape["data"]
    cfg = cfg or EmbedderConfig(
        vocab_size=1024, dim=64, n_layers=2, n_heads=4, max_len=seq
    )
    batch = max(batch, data_size)
    batch -= batch % data_size
    capacity = max(4 * batch, data_size * 8)
    capacity -= capacity % data_size

    params = init_params(cfg, 0)
    params = jax.device_put(params, param_shardings(mesh, params))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (batch, seq)), jnp.int32)
    tokens_aug = jnp.where(tokens % 7 == 0, 1, tokens)
    index = jax.device_put(
        jnp.zeros((capacity, cfg.dim), jnp.float32),
        NamedSharding(mesh, P("data", None)),
    )
    queries = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, seq)), jnp.int32)

    step = make_step(mesh, cfg, k=k)
    params, index, loss, scores, ids = step(
        params, tokens, tokens_aug, index, 0, queries
    )
    jax.block_until_ready((params, index, loss, scores, ids))
    return float(loss), np.asarray(scores), np.asarray(ids)
