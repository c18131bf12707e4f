"""Ring attention (sequence parallelism): exact agreement with full
attention on the 8-device CPU mesh, and the long-context embedder forward:
the encoder's own block with the ring for its attention."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pathway_tpu.models.embedder import EmbedderConfig, embed_tokens, init_params  # noqa: E402
from pathway_tpu.models.ring_attention import (  # noqa: E402
    embed_tokens_long,
    full_attention,
    ring_attention,
)
from pathway_tpu.parallel.mesh import make_mesh  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh (conftest XLA_FLAGS)")
    return make_mesh({"seq": 8})


def test_ring_matches_full_attention(mesh):
    rng = np.random.default_rng(0)
    b, s, h, d = 2, 64, 4, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    mask = jnp.asarray(rng.random((b, s)) > 0.2)
    # at least one valid key per row
    mask = mask.at[:, 0].set(True)
    scale = 1.0 / np.sqrt(d)
    expected = full_attention(q, k, v, mask, scale)
    got = ring_attention(q, k, v, mask, mesh, "seq", scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)
    # masked-out queries still produce finite values (normalizer floor)
    assert np.isfinite(np.asarray(got)).all()


def test_long_context_embedding(mesh):
    cfg = EmbedderConfig(
        vocab_size=512, dim=32, n_layers=2, n_heads=4, max_len=64,
        dtype=jnp.float32,
    )
    params = init_params(cfg, 0)
    rng = np.random.default_rng(1)
    # sequence 4x longer than max_len — impossible for the dense forward
    s = 256
    tokens = rng.integers(1, cfg.vocab_size, (2, s)).astype(np.int32)
    tokens[:, s // 2:] = 0  # long padded tail exercises the mask
    emb = embed_tokens_long(params, jnp.asarray(tokens), cfg, mesh, "seq")
    emb = np.asarray(emb)
    assert emb.shape == (2, cfg.dim)
    np.testing.assert_allclose(np.linalg.norm(emb, axis=-1), 1.0, rtol=1e-5)

    # sequence parallelism must not change the math: compare against the
    # same ring forward on a trivial 1-device mesh
    mesh1 = make_mesh({"seq": 1}) if len(jax.devices()) == 1 else None
    if mesh1 is None:
        from jax.sharding import Mesh

        mesh1 = Mesh(np.array(jax.devices()[:1]), ("seq",))
    emb1 = np.asarray(embed_tokens_long(params, jnp.asarray(tokens), cfg, mesh1, "seq"))
    np.testing.assert_allclose(emb, emb1, rtol=5e-5, atol=5e-5)


def test_the_ring_forward_is_the_encoders_forward_within_its_positions(mesh):
    """Up to the model's positions the ring forward over eight devices gives
    ``embed_tokens``' vectors: the same block, parameters and pool, only the
    attention computed another way."""
    cfg = EmbedderConfig(
        vocab_size=512, dim=32, n_layers=2, n_heads=4, max_len=64,
        dtype=jnp.float32,
    )
    params = init_params(cfg, 5)
    tokens = np.random.default_rng(2).integers(1, cfg.vocab_size, (3, cfg.max_len)).astype(np.int32)
    tokens[1, 40:] = 0
    tokens[2, 9:] = 0
    want = embed_tokens(params, jnp.asarray(tokens), cfg)
    got = embed_tokens_long(params, jnp.asarray(tokens), cfg, mesh, "seq")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5, rtol=0)
