"""Record exchange by key over a device mesh.

The reference exchanges records between timely workers through channel
allocators (``external/timely-dataflow/communication/src/allocator/``);
keys route by their low bits (``value.rs:38``). Here the same routing is a
**bucketed all-to-all**: rows are counted per destination shard, padded to a
static per-shard capacity (XLA needs static shapes), and exchanged with
``jax.lax.all_to_all`` inside ``shard_map`` so the transfer rides the ICI.
"""

from __future__ import annotations

import functools

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..engine import keys as K


def shard_rows(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Destination shard per row (low key bits, reference SHARD_MASK)."""
    return K.shard_of(keys, n_shards)


def bucketed_all_to_all(
    mesh: Mesh,
    axis: str,
    values: jax.Array,  # global [n_shards*cap_in, d], sharded over `axis`
    dest: jax.Array,  # global [n_shards*cap_in] destination shard (-1 = empty)
    cap_out: int,  # per-device output capacity (multiple of n_shards)
):
    """Exchange rows to their destination shards.

    Every device buckets its local rows by destination into a
    [n_shards, cap_bucket] layout, all-to-all swaps buckets, and flattens
    arrivals. Returns (global [n_shards*cap_out, d] values,
    [n_shards*cap_out] validity), sharded over `axis`.
    """
    n_shards = mesh.shape[axis]
    d = values.shape[-1]
    cap_bucket = cap_out // n_shards

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=(P(axis, None), P(axis)),
        check_vma=False,
    )
    def exchange(vals, dest):
        vals = vals.reshape(-1, d)  # this device's block
        dest = dest.reshape(-1)
        # position within destination bucket = running count per destination
        one_hot = jax.nn.one_hot(dest, n_shards, dtype=jnp.int32)  # -1 → all-zero row
        within = jnp.cumsum(one_hot, axis=0) - 1
        pos = jnp.take_along_axis(
            within, jnp.clip(dest, 0)[:, None], axis=1
        ).squeeze(-1)
        ok = (dest >= 0) & (pos < cap_bucket) & (pos >= 0)
        safe_dest = jnp.clip(dest, 0)
        safe_pos = jnp.clip(pos, 0, cap_bucket - 1)
        buckets = jnp.zeros((n_shards, cap_bucket, d), vals.dtype)
        valid = jnp.zeros((n_shards, cap_bucket), jnp.bool_)
        # scatter-add so masked-out rows (adding 0) can never clobber a slot
        # (zero must keep vals' dtype: 0.0 would promote uint32 payloads)
        buckets = buckets.at[safe_dest, safe_pos].add(
            jnp.where(ok[:, None], vals, jnp.zeros((), vals.dtype))
        )
        valid = valid.at[safe_dest, safe_pos].max(ok)
        # swap bucket b to device b over the ICI
        recv = jax.lax.all_to_all(buckets, axis, split_axis=0, concat_axis=0)
        recv_valid = jax.lax.all_to_all(valid, axis, split_axis=0, concat_axis=0)
        return recv.reshape(n_shards * cap_bucket, d), recv_valid.reshape(
            n_shards * cap_bucket
        )

    return exchange(values, dest)
