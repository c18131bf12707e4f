"""The LFM2-MoE decoder as the store's embedder (``models/lfm2.py``,
``models/moe.py``), at a tiny size that keeps every mechanism: hidden 64, 4
query and 2 key-value heads, 2 dense layers and one period [attn, conv, conv,
conv], 8 experts with top-4, the expert bias and ``norm_topk_prob``.

Held to the benchmark's plain float32 reference (``benchmark/models/
lfm2_moe.py``, which shares no code with the program): packed, alone, stored
and at the caller's own shape; the expert layer's routing and its grouped
products against their dense forms; and the parameters held once."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from lib import datagen, family, reference  # noqa: E402

from pathway_tpu.internals import tracing  # noqa: E402
from pathway_tpu.models import lfm2, moe  # noqa: E402
from pathway_tpu.serve.stats import SERVE_STATS  # noqa: E402

FAM = family.load({"model_type": "lfm2_moe"})
SEED = 48
MODEL = dict(
    model_type="lfm2_moe", vocab_size=1200, hidden_size=64, num_hidden_layers=6,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    moe_intermediate_size=32, num_dense_layers=2, num_experts=8, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=1.0, use_expert_bias=True, conv_L_cache=3,
    conv_bias=False, norm_eps=1e-5, rope_theta=1e6, max_position_embeddings=128000,
    initializer_range=0.2, served_positions=64)
MAX_LEN = MODEL["served_positions"]
#: how far a bfloat16 program may lie from the float32 reference here:
#: products rounded to 8 bits of mantissa (2^-9 relative) through six layers,
#: and the ties between a token's fourth and fifth expert that such rounding
#: flips (each flip swaps a quarter of that token's expert output), measured
#: at 0.03 at the most over these texts; the fp8 control lies 0.1 and more off
BF16_ATOL = 0.06


def _build(dtype=jnp.float32, model=MODEL, seed=SEED):
    """(embedder, reference parameters, vocabulary index, whole words)."""
    lines, words = datagen.vocabulary(seed, model["vocab_size"], FAM)
    index = {w: i for i, w in enumerate(lines)}
    weights = FAM.make_weights(seed, model)
    emb = FAM.build_embedder(weights, model, index, dtype)
    return emb, FAM.reference_params(weights), index, words


@pytest.fixture(scope="module")
def built():
    return _build()


def _texts(rng, words, lengths):
    """Texts of these token counts, the two framing tokens counted."""
    return [" ".join(words[i] for i in rng.integers(0, len(words), size=n - 2))
            for n in lengths]


def _reference(ref, index, texts, precision="float32"):
    ids = reference.token_ids(texts, index, MAX_LEN, FAM)
    return FAM.encode(ref, ids, MODEL, precision)


@pytest.mark.parametrize("trial", range(3))
def test_packed_texts_equal_the_reference_and_each_text_alone(built, trial):
    """No convolution tap and no attention key crosses into a neighbour's
    segment: a text's vector is the same alone and packed beside others."""
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, trial])
    texts = _texts(rng, words, rng.integers(2, MAX_LEN + 1, size=rng.integers(2, 13)))
    got = np.asarray(emb.embed_texts_device(texts))
    assert got.shape == (len(texts), MODEL["hidden_size"])
    np.testing.assert_allclose(got, _reference(ref, index, texts), atol=1e-5, rtol=0)
    alone = np.concatenate([np.asarray(emb.embed_texts_device([t])) for t in texts])
    np.testing.assert_allclose(got, alone, atol=1e-6, rtol=0)


def test_stored_texts_and_the_callers_rows_pool_the_last_real_token(built):
    """``embed_texts`` (a row a text, padded to a power of two) and
    ``__call__`` (right-padded rows) pool at each row's last real token, not
    at its last position."""
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, 9])
    texts = _texts(rng, words, (3, 17, 40, MAX_LEN))
    want = _reference(ref, index, texts)
    np.testing.assert_allclose(emb.embed_texts(texts), want, atol=1e-5, rtol=0)
    ids = reference.token_ids(texts, index, MAX_LEN + 8, FAM)
    np.testing.assert_allclose(emb(ids), want, atol=1e-5, rtol=0)


def test_bfloat16_stays_within_its_tolerance_and_fp8_does_not(built):
    _, ref, index, words = built
    emb = _build(jnp.bfloat16)[0]
    rng = np.random.default_rng([SEED, 11])
    texts = _texts(rng, words, rng.integers(2, MAX_LEN + 1, size=24))
    want = _reference(ref, index, texts)
    got = np.asarray(emb.embed_texts_device(texts))
    assert np.abs(got - want).max() <= BF16_ATOL
    assert np.abs(_reference(ref, index, texts, "fp8") - want).max() > BF16_ATOL


def test_a_text_over_the_served_limit_is_cut_and_counted(built):
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, 12])
    exact = _texts(rng, words, [MAX_LEN])[0]
    longer = exact + " " + " ".join(words[:5])
    before = SERVE_STATS["embed_truncated_texts_total"]
    whole = np.asarray(emb.embed_texts_device([exact]))
    cut = np.asarray(emb.embed_texts_device([longer]))
    assert SERVE_STATS["embed_truncated_texts_total"] == before + 1
    # the cut keeps the first max_len - 1 tokens and closes with <|im_end|>
    np.testing.assert_allclose(cut, whole, atol=1e-6, rtol=0)


def test_the_served_shapes_stop_at_the_served_limit():
    cfg = lfm2.Lfm2Config.from_hf(dict(MODEL, num_hidden_layers=6))
    assert cfg.max_len == lfm2.SERVED_POSITIONS == 512
    assert lfm2.Lfm2Config.from_hf(MODEL, max_len=64).max_len == 64
    emb = _build()[0]
    assert emb.cfg.max_len == MAX_LEN and emb.shapes[-1][1] == MAX_LEN



def test_a_seeded_decoder_runs_with_the_generated_vocabulary():
    """With no checkpoint the decoder is drawn from a seed (``lfm2.init_params``)
    and reads the vocabulary built in code, as a seeded encoder does."""
    from pathway_tpu.models.embedder import Embedder

    emb = Embedder(lfm2.Lfm2Config.from_hf(MODEL, max_len=32), seed=3)
    got = np.asarray(emb.embed_texts_device(["abc def", "x", ""]))
    assert got.shape == (3, MODEL["hidden_size"])
    np.testing.assert_allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)
    assert [layer["op_norm"].dtype for layer in emb.params["layers"]] == [jnp.float32] * 6

# -- the expert layer ------------------------------------------------------------


def _tokens(t=40, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((t, d), dtype=np.float32))


def _experts(e=8, d=16, f=8, seed=1):
    rng = np.random.default_rng(seed)
    w13 = jnp.asarray(rng.standard_normal((e, d, 2 * f), dtype=np.float32) * 0.3)
    w2 = jnp.asarray(rng.standard_normal((e, f, d), dtype=np.float32) * 0.3)
    return w13, w2


def _dense(h, experts, weights, w13, w2):
    """Every expert over every token, the weight zero off a token's chosen."""
    gate = np.zeros((h.shape[0], w13.shape[0]), np.float32)
    np.put_along_axis(gate, np.asarray(experts), np.asarray(weights), axis=1)
    out = 0.0
    for e in range(w13.shape[0]):
        g, u = np.split(np.asarray(h) @ np.asarray(w13[e]), 2, axis=-1)
        y = (g / (1 + np.exp(-g)) * u) @ np.asarray(w2[e])
        out = out + gate[:, e:e + 1] * y
    return out


def test_the_bias_chooses_the_experts_and_the_unbiased_scores_weigh_them():
    h = _tokens()
    router = jnp.asarray(np.random.default_rng(2).standard_normal((16, 8), dtype=np.float32))
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 0.8], jnp.float32)
    plain, w_plain = moe.route(h, router, None, 4)
    biased, w_biased = moe.route(h, router, bias, 4)
    s = jax.nn.sigmoid(h @ router)
    assert (np.asarray(biased) == 7).any(axis=1).sum() > (np.asarray(plain) == 7).any(axis=1).sum()
    for experts, w in ((plain, w_plain), (biased, w_biased)):
        chosen = np.take_along_axis(np.asarray(s), np.asarray(experts), axis=1)
        np.testing.assert_allclose(w, chosen / (chosen.sum(1, keepdims=True) + 1e-6),
                                   rtol=1e-6, atol=1e-7)


def test_grouped_products_equal_every_expert_gated_off_the_top_k():
    h = _tokens()
    w13, w2 = _experts()
    router = jnp.asarray(np.random.default_rng(3).standard_normal((16, 8), dtype=np.float32))
    experts, weights = moe.route(h, router, None, 4)
    valid = jnp.ones(h.shape[0], bool)
    got = moe.experts_swiglu(h, experts, weights, w13, w2, valid, jnp.float32)
    np.testing.assert_allclose(got, _dense(h, experts, weights, w13, w2), atol=1e-4, rtol=1e-5)


def test_a_router_that_sends_every_token_to_one_expert_loses_no_token():
    h = _tokens()
    w13, w2 = _experts()
    experts = jnp.zeros((h.shape[0], 1), jnp.int32)
    weights = jnp.ones((h.shape[0], 1), jnp.float32)
    valid = jnp.ones(h.shape[0], bool)
    got = moe.experts_swiglu(h, experts, weights, w13, w2, valid, jnp.float32)
    np.testing.assert_allclose(got, _dense(h, experts, weights, w13, w2), atol=1e-4, rtol=1e-5)
    assert (np.abs(np.asarray(got)).sum(1) > 0).all()
    # and a token that is not real is given to no expert
    valid = valid.at[::2].set(False)
    got = np.asarray(moe.experts_swiglu(h, experts, weights, w13, w2, valid, jnp.float32))
    assert (got[::2] == 0).all() and (np.abs(got[1::2]).sum(1) > 0).all()


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, a kernel's
    own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def test_a_served_program_runs_each_expert_layer_as_two_kernels():
    """Each expert layer of a served program is ``moe_gate_up`` then
    ``moe_down``; no ``ragged_dot`` is left, and no float32 [R·L·k, 2f]
    product: the SwiGLU is the gate/up kernel's epilogue. (An expert of 48
    here, so that 2f is not the hidden width, which the layer's float32
    output [R·L·k, d] has.)"""
    emb = _build(model=dict(MODEL, moe_intermediate_size=48))[0]
    cfg = emb.cfg
    assert 2 * cfg.moe_intermediate != cfg.dim
    rows, length = emb.shapes[-1]
    ids = jnp.zeros((3, rows, length), jnp.int32)
    eqns = list(_equations(jax.make_jaxpr(emb._fwd)(emb._handed(looped=False), ids).jaxpr))
    kernels = [e.params["name"] for e in eqns if e.primitive.name == "pallas_call"]
    assert kernels == ["moe_gate_up", "moe_down"] * cfg.moe_layers
    assert not [e for e in eqns if "ragged_dot" in e.primitive.name]
    assignments = rows * length * cfg.experts_per_token
    wide = [v.aval for e in eqns for v in e.outvars
            if v.aval.dtype == jnp.float32 and v.aval.ndim == 2
            and v.aval.shape[0] >= assignments and v.aval.shape[1] == 2 * cfg.moe_intermediate]
    assert not wide


def test_a_served_programs_expert_layer_returns_its_rows_by_a_gather():
    """Under ``moe.experts`` no scatter of a served program writes rows: the
    tokens gather their rows back through the inverse of the sort, one
    [T, d] gather a slot, where a float32 scatter-add into the tokens was
    serialised over a token's k rows. (The scatters left count the rows and
    tiles of each group for the schedule, a scalar an update.)"""
    emb = _build()[0]
    cfg = emb.cfg
    rows, length = emb.shapes[-1]
    ids = jnp.zeros((3, rows, length), jnp.int32)
    eqns = list(_equations(jax.make_jaxpr(emb._fwd)(emb._handed(looped=False), ids).jaxpr))
    experts = [e for e in eqns if "moe.experts" in str(e.source_info.name_stack)]
    back = [e for e in experts if e.primitive.name == "gather"
            and e.outvars[0].aval.shape == (rows * length, cfg.dim)]
    assert len(back) == cfg.experts_per_token * cfg.moe_layers
    assert not [e for e in experts if "scatter" in e.primitive.name
                and e.outvars[0].aval.ndim > 1]


def test_the_references_routed_experts_equal_the_dense_form():
    """The family's reference computes each expert over the tokens routed to
    it (gathered in blocks, added back): the same as every expert over every
    token with the gate zero off each token's top four."""
    import jax.numpy as jnp

    weights = FAM.make_weights(SEED, MODEL)
    _, layers = FAM.reference_params(weights)
    layer = {k: jnp.asarray(v) for k, v in layers[3].items()}  # an MoE layer
    ffn = {k: v for k, v in layer.items()
           if k.startswith(("ffn_norm", "feed_forward")) and ".experts." not in k}
    w1, w3, w2 = (jnp.stack([layer[f"feed_forward.experts.{e}.{n}"] for e in range(8)])
                  for n in ("w1.weight", "w3.weight", "w2.weight"))
    x = _tokens(t=48, d=MODEL["hidden_size"], seed=5)
    real = np.ones(48, bool)
    real[40:] = False
    steps = FAM._steps()
    got = np.asarray(FAM._moe(x, ffn, (w1, w3, w2), MODEL, "float32", real, steps))
    h, experts, w = steps[3](x, ffn, precision="float32", eps=1e-5, k=4, bias=True,
                             norm=True, scale=1.0)
    w13 = jnp.concatenate([jnp.swapaxes(w1, 1, 2), jnp.swapaxes(w3, 1, 2)], -1)
    want = np.asarray(x) + _dense(h, experts, w, w13, jnp.swapaxes(w2, 1, 2))
    np.testing.assert_allclose(got[:40], want[:40], atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(got[40:], np.asarray(x)[40:])


# -- the parameters, held once ---------------------------------------------------


def test_the_decoders_parameter_bytes_are_one_copy():
    before = SERVE_STATS["embed_param_bytes_total"]
    emb = _build(jnp.bfloat16)[0]
    held = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(emb.params))
    assert SERVE_STATS["embed_param_bytes_total"] - before == held
    # every tensor of the checkpoint once: two bytes a parameter, four for
    # those read in float32 (norms, convolution taps, router, expert bias)
    shapes = FAM.tensor_shapes(MODEL)
    wide = sum(int(np.prod(s)) for n, s in shapes.items()
               if n.endswith(("norm.weight", "conv.weight", "gate.weight", "expert_bias")))
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert held == 2 * total + 2 * wide
    # a list of layers, each MoE layer's experts stacked
    layers = emb.params["layers"]
    assert len(layers) == 6 and "w13" in layers[0] and "router" not in layers[0]
    assert layers[2]["w13"].shape == (8, 64, 64) and layers[2]["w2"].shape == (8, 32, 64)


def test_placing_the_parameters_and_a_dispatch_are_traced_and_counted(tmp_path):
    import json

    path = tmp_path / "run.json"
    tracer = tracing.activate(str(path))
    try:
        emb, _, _, words = _build(jnp.bfloat16)
        before = SERVE_STATS["embed_expert_assignments_total"]
        emb.embed_texts_device([" ".join(words[:10]), " ".join(words[:5])])
        tracer.flush()
    finally:
        tracing.deactivate()
    events = [e for e in json.loads(path.read_text())["traceEvents"] if e.get("ph") == "X"]
    place = [e for e in events if e["name"] == "embed.place"]
    assert len(place) == 1
    assert place[0]["args"]["bytes"] == sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(emb.params))
    sent = [e["args"] for e in events if e["name"] == "embed.dispatch"]
    assert sent[-1]["moe_layers"] == 4 and sent[-1]["experts_per_token"] == 4
    assert sent[-1]["tokens"] == 12 + 7 and sent[-1]["texts"] == 2
    assert SERVE_STATS["embed_expert_assignments_total"] - before == (12 + 7) * 4 * 4
