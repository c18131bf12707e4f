"""The expert layer's grouped kernels (``models/moe.py``: ``moe_gate_up`` with
the SwiGLU in its epilogue, ``moe_down``), in interpret mode, held to
``jax.lax.ragged_dot`` and the float32 SwiGLU over group layouts that catch a
grid that visits the wrong tiles: an expert with no rows, every assignment on
one expert, groups that start inside a tile, every token padding, a row count
that is no multiple of the tile, widths under one tile, and widths of several
output tiles. Over the same layouts, the combine (``moe._combine``: each
token's rows gathered back through the inverse of the sort) is held to the
weighed scatter-add it replaced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import moe

K = 4  # experts a token
E = 8
#: layout -> (rows each expert is given by the real tokens, padding tokens,
#: d, f, widest output tile); every layout's widths differ, so that no
#: program traced under one ``_TN`` is reused under another
LAYOUTS = {
    "an_expert_with_no_rows": ([100, 0, 132, 0, 60, 200, 0, 24], 7, 256, 256, None),
    "every_assignment_on_one_expert": ([0, 0, 0, 256, 0, 0, 0, 0], 0, 128, 256, None),
    "groups_that_start_mid_tile": ([37, 91, 128, 5, 250, 1, 0, 0], 10, 256, 128, None),
    "every_token_padding": ([0] * E, 32, 128, 128, None),
    "rows_no_multiple_of_the_tile": ([13, 29, 0, 7, 41, 3, 11, 0], 11, 384, 128, None),
    "widths_under_one_tile": ([60, 60, 20, 20, 40, 0, 40, 16], 5, 64, 32, None),
    "widths_of_several_tiles": ([37, 91, 128, 5, 250, 1, 0, 0], 3, 256, 384, 128),
}


def _assignments(sizes, padding, rng):
    """(experts [T, K], valid [T]): the real tokens' assignments give expert
    ``e`` ``sizes[e]`` rows; a padding token's experts are anything."""
    keys = np.repeat(np.arange(E), sizes)
    rng.shuffle(keys)
    real = keys.reshape(-1, K)
    pad = rng.integers(0, E, (padding, K))
    experts = np.concatenate([real, pad]).astype(np.int32)
    valid = np.arange(len(experts)) < len(real)
    return jnp.asarray(experts), jnp.asarray(valid)


def _ragged_experts(h, experts, weights, w13, w2, valid, dtype):
    """The expert layer with ``jax.lax.ragged_dot`` in the kernels' place."""
    t, k = experts.shape
    n = w13.shape[0]
    key = jnp.where(valid[:, None], experts, n).reshape(-1)
    order = jnp.argsort(key, stable=True)
    token = order // k
    sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    gu = jax.lax.ragged_dot(h.astype(dtype)[token], w13.astype(dtype), sizes,
                            preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(dtype), w2.astype(dtype), sizes,
                           preferred_element_type=jnp.float32)
    y = jnp.where((key[order] < n)[:, None], y * weights.reshape(-1)[order][:, None], 0.0)
    return jnp.zeros((t, y.shape[-1]), jnp.float32).at[token].add(y)


def _close(got, want, dtype):
    """float32: the same products summed in another order; bfloat16: at most
    a rounding of the kernel's output apart, where the two sums straddle one."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not want.size:
        return
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7 * np.abs(want).max())


@jax.jit
def _kernels_and_ragged_dot(x, w13, w2, group_sizes):
    """Each kernel alone over sorted rows in groups of ``group_sizes``, beside
    ``ragged_dot`` and the float32 SwiGLU; the down kernel is handed the
    reference's activations, so that each is held to its own product."""
    dtype = x.dtype
    schedule = moe._schedule(group_sizes, x.shape[0])
    act = moe._grouped("moe_gate_up", x, w13, schedule, halves=2, epilogue=moe._swiglu,
                       out_dtype=dtype)
    g, u = jnp.split(jax.lax.ragged_dot(x, w13, group_sizes,
                                        preferred_element_type=jnp.float32), 2, axis=-1)
    act_want = (jax.nn.silu(g) * u).astype(dtype)
    y = moe._grouped("moe_down", act_want, w2, schedule, halves=1, epilogue=lambda y: y,
                     out_dtype=jnp.float32)
    y_want = jax.lax.ragged_dot(act_want, w2, group_sizes, preferred_element_type=jnp.float32)
    return act, act_want, y, y_want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_kernels_equal_ragged_dot_and_the_float32_swiglu(layout, dtype, monkeypatch):
    sizes, padding, d, f, tile = LAYOUTS[layout]
    if tile is not None:
        monkeypatch.setattr(moe, "_TN", tile)
    rng = np.random.default_rng(sorted(LAYOUTS).index(layout))
    w13 = jnp.asarray(rng.standard_normal((E, d, 2 * f), np.float32) / np.sqrt(d), dtype)
    w2 = jnp.asarray(rng.standard_normal((E, f, d), np.float32) / np.sqrt(f), dtype)
    real = sum(sizes)

    m = max(-(-real // moe._TM), 1) * moe._TM
    x = jnp.asarray(rng.standard_normal((m, d), np.float32), dtype)
    act, act_want, y, y_want = _kernels_and_ragged_dot(x, w13, w2, jnp.asarray(sizes, jnp.int32))
    assert act.shape == (m, f) and act.dtype == dtype
    assert y.shape == (m, d) and y.dtype == jnp.float32
    _close(act[:real], act_want[:real], dtype)
    _close(y[:real], y_want[:real], dtype)

    # the layer: real tokens as the ragged_dot form, padding tokens exact zeros
    experts, valid = _assignments(sizes, padding, rng)
    h = jnp.asarray(rng.standard_normal((experts.shape[0], d), np.float32))
    weights = jnp.asarray(rng.uniform(0.1, 1.0, experts.shape), jnp.float32)
    got = np.asarray(moe.experts_swiglu(h, experts, weights, w13, w2, valid, dtype))
    want = np.asarray(jax.jit(_ragged_experts, static_argnums=6)(
        h, experts, weights, w13, w2, valid, dtype))
    keep = np.asarray(valid)
    assert np.isfinite(got).all()
    _close(got[keep], want[keep], dtype)
    assert (got[~keep] == 0).all()


def _scatter_combine(y, order, key, weights, n):
    """The combine as a weighed scatter-add: each sorted row ``y[p]`` times its
    assignment's weight, added into its token, rows past the last group
    masked off."""
    t, k = weights.shape
    kept = jnp.pad(key, (0, 1), constant_values=n)[order] < n
    token = jnp.minimum(order // k, t - 1)
    scale = jnp.pad(weights.reshape(-1), (0, 1))[order]
    y = jnp.where(kept[:, None], y * scale[:, None], 0.0)
    return jnp.zeros((t, y.shape[-1]), jnp.float32).at[token].add(y)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_combine_gathers_what_the_scatter_add_summed(layout, dtype, monkeypatch):
    """The down kernel's rows, NaN planted in every row past the last group,
    gathered back to their tokens: a real token's sum is the scatter-add's,
    to one rounding of each term (a CPU fuses a product into its sum, which
    rounds once where the scatter rounds twice), and a padding token is an
    exact zero."""
    sizes, padding, d, f, tile = LAYOUTS[layout]
    if tile is not None:
        monkeypatch.setattr(moe, "_TN", tile)
    rng = np.random.default_rng(100 + sorted(LAYOUTS).index(layout))
    experts, valid = _assignments(sizes, padding, rng)
    t = experts.shape[0]
    key = jnp.where(valid[:, None], experts, E).reshape(-1)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order).reshape(t, K)
    order = jnp.pad(order, (0, -order.size % moe._TM), constant_values=t * K)
    m, real = order.size, sum(sizes)
    act = jnp.asarray(rng.standard_normal((m, f), np.float32), dtype)
    w2 = jnp.asarray(rng.standard_normal((E, f, d), np.float32) / np.sqrt(f), dtype)
    y = jax.jit(lambda a, w, g: moe._grouped(
        "moe_down", a, w, moe._schedule(g, m), halves=1, epilogue=lambda y: y,
        out_dtype=jnp.float32))(act, w2, jnp.asarray(sizes, jnp.int32))
    y = y.at[real:].set(jnp.nan)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (t, K)), jnp.float32)

    got = np.asarray(jax.jit(moe._combine)(y, inv, weights, valid))
    want = np.asarray(jax.jit(_scatter_combine, static_argnums=4)(y, order, key, weights, E))
    keep = np.asarray(valid)
    assert got.shape == (t, d) and got.dtype == np.float32
    assert np.isfinite(got).all()
    assert (got[~keep] == 0).all()
    terms = np.abs(np.asarray(y)[np.asarray(inv)[keep]] * np.asarray(weights)[keep][..., None])
    assert (np.abs(got[keep] - want[keep]) <= K * 2.0 ** -24 * terms.sum(1)).all()
