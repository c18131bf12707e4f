"""TPU-native KNN kernels.

Replaces the reference's native ANN engines — USearch HNSW
(``src/external_integration/usearch_integration.rs``) and the brute-force
CPU index (``brute_force_knn_integration.rs``) — with XLA kernels: scoring
is one bf16 matmul on the MXU (batch × index), top-k via ``lax.top_k``.
A mesh-sharded variant splits the index rows across devices and merges
local top-k with an all-gather — the "sharded vector index over ICI" of
BASELINE.json's north star.

The same local-top-k → global-top-k shape exists at two scales: within a
device mesh the merge is the in-XLA all-gather below; across WORKERS the
serve plane (``serve/router.py``) carries each shard's host-side candidate
list over the wire and merges with :func:`merge_shard_topk` — the
host-side generalization of this file's gather-merge.
"""

from __future__ import annotations

import functools
from typing import Any

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "topk_scores", "knn_search", "ShardedKnnIndex", "sharded_knn_search",
    "merge_shard_topk", "index_write", "index_writer", "WRITE_BUCKETS",
    "storage_dtype", "index_fill", "PLACE_ROWS", "stack_valid", "mask_write",
]

#: row counts an in-place write is padded to (a larger batch goes in pieces
#: of the last): a fixed set of shapes, so every write program of a block can
#: be compiled when the block is placed and none compiles while serving
WRITE_BUCKETS = (8, 64, 512)

#: rows of the host block handed to the device at a time when a block is
#: placed (256 MiB of float32 at width 1024): what the device holds beside
#: the block it fills is one such chunk, never a second block
PLACE_ROWS = 1 << 16


def storage_dtype(metric: str):
    """The dtype a device copy of an index is kept in: the one ``topk_scores``
    multiplies in, where nothing else reads the block. ``cos`` and ``ip`` read
    the rows only as bfloat16 operands, so the copy is bfloat16, rounded once
    when a row is written and not at every scan; ``l2`` also reads the
    float32 rows for their norms, so its copy stays float32."""
    return jnp.dtype(jnp.bfloat16 if metric in ("cos", "ip") else jnp.float32)


def merge_shard_topk(
    parts: "list[list[tuple[Any, float]]]", k: int
) -> "list[tuple[Any, float]]":
    """Merge per-shard best-first (key, score) candidate lists into a
    global top-k on the host — the cross-worker counterpart of
    ``sharded_knn_search``'s in-mesh all-gather merge (scores compare
    higher-is-better; duplicate keys keep their best score)."""
    from ..serve.merge import merge_topk

    return merge_topk(parts, k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def topk_scores(
    queries: jax.Array,
    index: jax.Array,
    k: int,
    metric: str = "cos",
    valid: jax.Array | None = None,
):
    """queries [q, d] (f32), index [n, d] -> (scores [q,k], ids [q,k]).

    index is float32 or already ``storage_dtype(metric)``: the cast below is
    then no operation and the scan reads the block once, at two bytes a value.
    cos: both sides assumed L2-normalized → dot product == cosine.
    l2: negative squared distance (higher is closer).
    valid [n] bool: rows where False are masked to -inf BEFORE top-k
    (capacity padding must never displace real documents); valid [q, n] is a
    mask for each query (its filter's rows among the live ones).
    """
    qb = queries.astype(jnp.bfloat16)
    ib = index.astype(jnp.bfloat16)
    if metric in ("cos", "ip"):
        # cos assumes L2-normalized inputs; ip is the raw inner product
        scores = (qb @ ib.T).astype(jnp.float32)
    else:
        sq_i = (index.astype(jnp.float32) ** 2).sum(-1)
        dots = (qb @ ib.T).astype(jnp.float32)
        sq_q = (queries.astype(jnp.float32) ** 2).sum(-1, keepdims=True)
        scores = -(sq_q - 2 * dots + sq_i[None, :])
    if valid is not None:
        scores = jnp.where(valid if valid.ndim == 2 else valid[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def index_write(block: jax.Array, valid: jax.Array, slots: jax.Array,
                rows: jax.Array, live: jax.Array):
    """block [n, d], valid [n] with ``rows`` [m, d] (float32, cast here to the
    block's dtype) and ``live`` [m] written at ``slots`` [m]. A slot may repeat
    as long as it repeats with the same row (padding to a bucket does): the
    write is then the same whichever lands."""
    return block.at[slots].set(rows.astype(block.dtype)), valid.at[slots].set(live)


@functools.cache
def index_writer(out_shardings=None):
    """The one in-place write program of a device-resident index block
    (``jit_index_write`` in a device trace): block and mask are donated, so
    the update touches the written rows and no second block exists. With
    ``out_shardings`` the result keeps the block's sharding: left to itself
    the compiler replicates the result of an update on a TPU mesh, and every
    chip then holds the whole index."""
    return jax.jit(index_write, donate_argnums=(0, 1), out_shardings=out_shardings)


@functools.partial(jax.jit, donate_argnums=(0,))
def index_fill(block: jax.Array, rows: jax.Array, start: jax.Array):
    """``rows`` [m, d] cast to the block's dtype and written over
    ``block[start:start + m]``, the block donated: how a block is placed chunk
    by chunk (``jit_index_fill`` in a device trace)."""
    return jax.lax.dynamic_update_slice(
        block, rows.astype(block.dtype), (start, jnp.zeros_like(start)))


@jax.jit
def stack_valid(*masks: jax.Array):
    """The masks [n] of a search's queries, one a query, as the ``valid``
    [q, n] of ``topk_scores``: one program for each number of queries."""
    return jnp.stack(masks)


@functools.partial(jax.jit, donate_argnums=(0,))
def mask_write(mask: jax.Array, slots: jax.Array, keep: jax.Array):
    """mask [n] with ``keep`` [m] written at ``slots`` [m], the mask donated:
    how a cached filter mask follows an in-place write of the block. A slot
    may repeat with the same value, as in ``index_write``."""
    return mask.at[slots].set(keep)


def knn_search(queries: np.ndarray, index: np.ndarray, k: int, metric: str = "cos"):
    s, i = topk_scores(jnp.asarray(queries), jnp.asarray(index), k, metric)
    return np.asarray(s), np.asarray(i)


def sharded_knn_search(
    mesh: Mesh,
    axis: str,
    queries: jax.Array,
    index_sharded: jax.Array,
    k: int,
    metric: str = "cos",
    valid_sharded: jax.Array | None = None,
):
    """Index rows sharded over `axis`; queries replicated. Each device scores
    its shard and takes a local top-k; an all-gather over `axis` + global
    top-k merges — the collective rides the ICI. k must be ≤ rows per shard.
    """
    n_shards = mesh.shape[axis]
    rows_per_shard = index_sharded.shape[0] // n_shards
    if k > rows_per_shard:
        raise ValueError(
            f"k={k} exceeds rows per shard ({rows_per_shard}); "
            "raise index capacity or lower k"
        )

    specs_in = [P(), P(axis, None)]
    args = [queries, index_sharded]
    if valid_sharded is not None:
        specs_in.append(P(axis))
        args.append(valid_sharded)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=tuple(specs_in),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def search(q, shard, *maybe_valid):
        my = jax.lax.axis_index(axis)
        v = maybe_valid[0] if maybe_valid else None
        s, i = topk_scores(q, shard, k, metric, valid=v)
        i = i + my * rows_per_shard
        # gather all shards' candidates, merge to global top-k
        all_s = jax.lax.all_gather(s, axis, axis=1).reshape(q.shape[0], -1)
        all_i = jax.lax.all_gather(i, axis, axis=1).reshape(q.shape[0], -1)
        gs, gpos = jax.lax.top_k(all_s, k)
        gi = jnp.take_along_axis(all_i, gpos, axis=1)
        return gs, gi

    return search(*args)


class ShardedKnnIndex:
    """Device-resident brute-force index with insert/query (host API).

    Capacity-padded: rows beyond ``size`` are masked by a -inf score via a
    validity column, so shapes stay static for XLA. Single-device by default;
    pass a mesh to shard rows across devices.
    """

    def __init__(
        self,
        dim: int,
        capacity: int = 1 << 20,
        metric: str = "cos",
        mesh: Mesh | None = None,
        axis: str = "data",
    ):
        self.dim = dim
        self.capacity = capacity
        self.metric = metric
        self.mesh = mesh
        self.axis = axis
        self.size = 0
        shardings = None
        dtype = storage_dtype(metric)
        if mesh is not None:
            shardings = (
                NamedSharding(mesh, P(axis, None)), NamedSharding(mesh, P(axis))
            )
            self._data = jax.device_put(
                jnp.zeros((capacity, dim), dtype), shardings[0]
            )
            self._valid_d = jax.device_put(
                jnp.zeros((capacity,), jnp.bool_), shardings[1]
            )
        else:
            self._data = jnp.zeros((capacity, dim), dtype)
            self._valid_d = jnp.zeros((capacity,), jnp.bool_)

        self._write = index_writer(shardings)
        self._keys: list[Any] = []

    def add(self, vectors: np.ndarray, keys: list[Any] | None = None) -> None:
        n = len(vectors)
        if self.size + n > self.capacity:
            raise ValueError("index capacity exceeded")
        self._data, self._valid_d = self._write(
            self._data, self._valid_d,
            np.arange(self.size, self.size + n, dtype=np.int32),
            np.asarray(vectors, np.float32), np.ones(n, np.bool_),
        )
        self._keys.extend(keys if keys is not None else range(self.size, self.size + n))
        self.size += n

    def query(self, queries: np.ndarray, k: int):
        k_eff = min(k, max(self.size, 1))
        if self.mesh is not None:
            # the sharded merge needs k candidates from every shard
            k_eff = min(k_eff, self.capacity // self.mesh.shape[self.axis])
            s, i = sharded_knn_search(
                self.mesh, self.axis, jnp.asarray(queries, jnp.float32),
                self._data, k_eff, self.metric, valid_sharded=self._valid_d,
            )
        else:
            s, i = topk_scores(
                jnp.asarray(queries, jnp.float32), self._data, k_eff,
                self.metric, valid=self._valid_d,
            )
        return np.asarray(s), np.asarray(i)

    def keys_of(self, ids: np.ndarray):
        return [
            [self._keys[j] if 0 <= j < len(self._keys) else None for j in row]
            for row in ids
        ]
