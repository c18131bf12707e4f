"""A dropless top-k expert layer: every token is computed by each of the
``k`` experts its router chooses, and by no other, with no capacity and no
dropped token.

The router is the one of HF ``lfm2_moe``: ``s = sigmoid(h @ router)`` in
float32, the top ``k`` of ``s + expert_bias`` choose the experts (the bias
balances load and only chooses), and the chosen entries of ``s`` itself weigh
them, normalised to sum to one (``norm_topk_prob``) and scaled by
``routed_scaling_factor``.

The experts run as two grouped products, Pallas kernels over the ``tokens x
k`` assignments sorted by expert: ``moe_gate_up`` applies each expert's SwiGLU
to its own contiguous group of rows, ``moe_down`` its output matrix, and each
token gathers its ``k`` rows back through the inverse of the sort, weighs them
and sums them in float32. A token that is not real (padding of a packed row)
is given no assignment: its rows are sorted past the last group, no kernel
visits them, and it comes out zero.
"""

from __future__ import annotations

import functools
from typing import Any

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

_F32 = jnp.float32
#: sorted assignment rows a grid step computes: the MXU's 128, so that a group
#: that starts or ends inside a tile costs at most a tile's rows (one expert
#: layer of LFM2-8B-A1B at [4, 512] on a TPU v5e: 2.93 ms with tiles of 128
#: rows, 2.92 with 64, 2.97 with 256, 3.70 with 512)
_TM = 128
#: the widest output tile a kernel step writes; at LFM2-8B-A1B's d 2048 and f
#: 1792 each product's whole output width is one tile, so an expert's weights
#: are read into VMEM once, at its first tile, and kept while its tiles run
#: (2% faster there than tiles of half the width)
_TN = 2048
#: VMEM a kernel may hold: the gate/up kernel's two [d, f] weight blocks,
#: double-buffered, are 29.4 MB at LFM2-8B-A1B's widths (v5e has 128 MiB)
_VMEM = 96 * 2**20


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


def _lane_tile(width: int) -> int:
    """The widest tile of at most ``_TN`` columns that divides ``width`` and
    is a multiple of 128 (the lanes), or the whole width where none is: a
    width under 128, as a small test model's, runs in interpret mode only."""
    for tn in range(min(width, _TN) // 128 * 128, 0, -128):
        if width % tn == 0:
            return tn
    return width


def _kernel(offsets, group_ids, m_tiles, x, *refs, epilogue, tm):
    """One grid step: the rows of m-tile ``m_tiles[i]`` that belong to group
    ``group_ids[i]``, times that group's weight blocks, through ``epilogue``;
    the tile's other rows keep what an earlier step of the same tile wrote."""
    *ws, out = refs
    i = pl.program_id(1)
    group = group_ids[i]
    row = m_tiles[i] * tm + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    mine = (row >= offsets[group]) & (row < offsets[group + 1])
    y = epilogue(*(jnp.dot(x[...], w[...], preferred_element_type=_F32) for w in ws))
    out[...] = jnp.where(mine, y.astype(out.dtype), out[...])


def _schedule(sizes: jax.Array, m: int):
    """The grid of a grouped product over ``m`` sorted rows in groups of
    ``sizes``: (offsets, group of a step, m-tile of a step), steps. Only the
    m-tiles that hold a group's rows are visited, in order, a tile that two
    groups share once for each."""
    return make_group_metadata(group_sizes=sizes, m=m, tm=_TM, start_group=jnp.int32(0),
                               num_nonzero_groups=sizes.shape[0], visit_empty_groups=False)


def _grouped(name: str, x: jax.Array, w: jax.Array, schedule, *,
             halves: int, epilogue, out_dtype) -> jax.Array:
    """``epilogue(x[g] @ w[g][:, part])`` over each group ``g`` of ``x``'s
    rows, ``w`` [E, K, halves · N] split into ``halves`` column blocks of N
    handed to ``epilogue`` together: [M, N] of ``out_dtype``. The grid
    (``_schedule``) runs n-tiles outermost and each step over the whole K, so
    that an expert's weight blocks are read once while its tiles run; rows
    past the last group are never written and may hold anything."""
    m, k = x.shape
    n = w.shape[2] // halves
    tn = _lane_tile(n)
    (offsets, group_ids, m_tiles), tiles = schedule

    def w_block(half):
        return pl.BlockSpec((None, k, tn),
                            lambda j, i, o, g, t: (g[i], 0, half * (n // tn) + j))

    return pl.pallas_call(
        functools.partial(_kernel, epilogue=epilogue, tm=_TM),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, tiles),
            in_specs=[pl.BlockSpec((_TM, k), lambda j, i, o, g, t: (t[i], 0))]
            + [w_block(h) for h in range(halves)],
            out_specs=pl.BlockSpec((_TM, tn), lambda j, i, o, g, t: (t[i], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM),
        interpret=jax.default_backend() != "tpu", name=name,
    )(offsets, group_ids, m_tiles, x, *([w] * halves))


def _swiglu(g, u):
    return jax.nn.silu(g) * u


def _combine(y: jax.Array, inv: jax.Array, weights: jax.Array, valid: jax.Array) -> jax.Array:
    """Each token's ``k`` rows of ``y`` [M, d], sorted by expert, gathered at
    ``inv`` [T, k] (their sorted positions), weighed by ``weights`` [T, k] and
    summed: float32 [T, d]. The terms are added in ascending sorted position,
    from the first, the order in which a sequential scatter-add into zeros
    applies them. A padding token's positions lie past the last group, in rows
    no kernel writes, so it is masked to an exact zero by a ``where`` (a
    product with zero keeps a NaN)."""
    inv, weights = jax.lax.sort((inv, weights), dimension=1, num_keys=1)
    # one [T, d] gather a slot: gathered as one [T, k, d], the rows are written
    # out whole and laid out again slot by slot (the combine alone at [8, 512]
    # on a TPU v5e: 0.64 ms a slot at a time, 1.96 as one gather, 1.98 as the
    # weighed scatter-add into the tokens)
    out = y[inv[:, 0]] * weights[:, :1]
    for j in range(1, inv.shape[1]):
        out = out + y[inv[:, j]] * weights[:, j:j + 1]
    return jnp.where(valid[:, None], out, 0.0)


# jitted, so that the expert layers of a program share one lowering of the
# kernels: in interpret mode each takes a third of a second to lower
@functools.partial(jax.jit, static_argnums=6)
def experts_swiglu(h: jax.Array, experts: jax.Array, weights: jax.Array,
                   w13: jax.Array, w2: jax.Array, valid: jax.Array, dtype: Any) -> jax.Array:
    """Σ over each token's chosen experts of ``weight · w2(silu(g) ⊙ u)``, with
    ``[g, u] = h @ w13[e]``: float32 [T, d].

    ``w13`` [E, d, 2f] and ``w2`` [E, f, d] are the experts' matrices and
    ``valid`` [T] marks the real tokens. Products in ``dtype`` with float32
    accumulation; the SwiGLU is float32, rounded once to ``dtype`` in the
    gate/up kernel, so no float32 [T·k, 2f] is ever written out."""
    with jax.named_scope("moe.experts"):
        t, k = experts.shape
        n = w13.shape[0]
        # a padding token's assignments get key n: sorted past the last group;
        # the rows are padded to whole m-tiles the same way
        key = jnp.where(valid[:, None], experts, n).reshape(-1)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order).reshape(t, k)
        order = jnp.pad(order, (0, -order.size % _TM), constant_values=t * k)
        token = jnp.minimum(order // k, t - 1)
        sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
        schedule = _schedule(sizes, order.size)
        act = _grouped("moe_gate_up", h.astype(dtype)[token], w13.astype(dtype), schedule,
                       halves=2, epilogue=_swiglu, out_dtype=dtype)
        y = _grouped("moe_down", act, w2.astype(dtype), schedule,
                     halves=1, epilogue=lambda y: y, out_dtype=_F32)
        return _combine(y, inv, weights, valid)
