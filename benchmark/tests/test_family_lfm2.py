"""The LFM2-MoE family (``models/lfm2_moe.py``): its weights, framing, work
counts and reference, and a tiny cell of it run end to end on the CPU
rehearsal (``tests/rehearsal_lfm2/``). ``tests/test_benchmark_family_lfm2.py``
takes these cases in, so that tier-1 counts them."""

import hashlib
import json
import os

import harness
import numpy as np
import pytest
from lib import datagen, family, reference

SEED = 2147483659
LFM2 = family.load({"model_type": "lfm2_moe"})
REHEARSAL = os.path.join(harness.TESTS, "rehearsal_lfm2")
#: LiquidAI/LFM2-8B-A1B's config.json (the catalog's row) cut to layers 0-9:
#: 2 dense layers and two [attn, conv, conv, conv] periods, every width as
#: published; the model of ``configs/lfm2-8b-a1b-l10-600k.json``
CUT = {
    "model_type": "lfm2_moe", "vocab_size": 65536, "hidden_size": 2048,
    "num_hidden_layers": 10, "layer_types": ["conv", "conv"] + 2 * [
        "full_attention", "conv", "conv", "conv"],
    "num_attention_heads": 32, "num_key_value_heads": 8, "intermediate_size": 7168,
    "moe_intermediate_size": 1792, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-05,
    "rope_theta": 1000000, "max_position_embeddings": 128000,
    "initializer_range": 0.02, "served_positions": 512,
}
CELL = "tiny-lfm2.read-long-c4"


def _config(path):
    with open(path) as f:
        return json.load(f)


def _tiny():
    return {k: v for k, v in _config(os.path.join(REHEARSAL, "configs", "tiny-lfm2.json")).items()
            if k in LFM2.MODEL_KEYS}


def _digest(weights):
    h = hashlib.sha256()
    for name in sorted(weights):
        h.update(name.encode())
        h.update(weights[name].tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("threads", [1, 3, 8])
def test_the_weights_are_the_same_bytes_whatever_the_threads(threads, monkeypatch):
    model = _tiny()
    want = _digest(LFM2.make_weights(SEED, model))
    monkeypatch.setattr(datagen, "threads", lambda: threads)
    assert _digest(LFM2.make_weights(SEED, model)) == want


def test_the_weights_carry_every_hf_name_of_the_model():
    model = _tiny()
    weights = LFM2.make_weights(SEED, model)
    assert set(weights) == set(LFM2.tensor_shapes(model))
    # 2 + 6 x 2 norms, 3 convolutions x 3 + one attention x 6, 2 dense SwiGLUs
    # x 3, 4 MoE layers x (gate, bias, 8 experts x 3)
    assert len(weights) == 2 + 12 + 5 * 3 + 6 + 2 * 3 + 4 * (2 + 8 * 3)
    norms = [n for n in weights if n.endswith("norm.weight")]
    assert all(abs(weights[n].mean() - 1) < 0.05 for n in norms)
    bias = weights["model.layers.3.feed_forward.expert_bias"]
    assert 0 < np.abs(bias).max() < 0.1  # moves the choice, never decides it


def test_the_weights_are_drawn_as_read_and_one_layer_is_held():
    model = _tiny()
    weights = LFM2.make_weights(SEED, model)
    first = {name: weights[name].copy() for name in weights}  # every layer in turn
    last = f"model.layers.{model['num_hidden_layers'] - 1}."
    assert set(weights._held) == {"", last}
    assert set(weights._held[last]) == {n for n in first if n.startswith(last)}
    again = LFM2.make_weights(SEED, model)
    for name in sorted(first, reverse=True):  # another order: each layer drawn anew
        assert np.array_equal(again[name], first[name])
    assert "model.layers.0.ffn_norm.weight" in again and "nothing" not in again
    with pytest.raises(KeyError):
        again["nothing"]


def test_the_reference_reads_the_weights_layer_by_layer():
    model = _tiny()
    weights = LFM2.make_weights(SEED, model)
    outer, layers = LFM2.reference_params(weights)
    assert outer is weights and len(layers) == model["num_hidden_layers"]
    assert not weights._held  # nothing drawn yet
    name = "feed_forward.expert_bias"
    assert np.array_equal(layers[3][name], weights["model.layers.3." + name])
    assert set(layers[3]) == {n[len("model.layers.3."):] for n in weights
                              if n.startswith("model.layers.3.")}


def test_the_configuration_file_is_the_cut():
    cfg = _config(os.path.join(harness.ROOT, "benchmark", "configs",
                               "lfm2-8b-a1b-l10-600k.json"))
    assert {k: cfg[k] for k in LFM2.MODEL_KEYS} == CUT
    assert cfg["compute_dtype"] == "bfloat16" and cfg["rows"] == 600_000


def test_frame_and_the_token_faults_position():
    assert LFM2.frame([1000, 1001]) == [1, 1000, 1001, 7] and LFM2.FRAMING == 2
    first = LFM2.FIRST_WORD_ID
    assert LFM2.frame([first]).index(first) == 1
    lines, words = datagen.vocabulary(SEED, 3000, LFM2)
    assert lines[0] == "<|pad|>" and lines[1] == "<|startoftext|>" and lines[7] == "<|im_end|>"
    assert lines[first] == words[0]
    ids = reference.token_ids(words[:2], {w: i for i, w in enumerate(lines)}, 6, LFM2)
    assert ids.tolist() == [[1, first, 7, 0, 0, 0], [1, first + 1, 7, 0, 0, 0]]


#: the cut's parameters that one token's products use: 2 dense conv layers
#: (in_proj 2048 x 6144, out_proj 2048², 2048 x 3 taps, SwiGLU 3 x 2048 x
#: 7168), 6 MoE conv layers (the same op, the 2048 x 32 router, 4 experts of
#: 3 x 2048 x 1792) and 2 MoE attention layers (q and out 2048², k and v 2048
#: x 512, router, 4 experts)
CONV, ATTN = 2048 * 6144 + 2048 * 2048 + 2048 * 3, 2 * 2048 * 2048 + 2 * 2048 * 512
DENSE, EXPERT, ROUTER = 3 * 2048 * 7168, 3 * 2048 * 1792, 2048 * 32
PER_TOKEN = 2 * (CONV + DENSE) + 6 * (CONV + ROUTER + 4 * EXPERT) + 2 * (ATTN + ROUTER + 4 * EXPERT)


@pytest.mark.parametrize("seq", [1, 32, 227, 512])
def test_flops_per_token_is_the_hand_count_at_the_cut(seq):
    model = CUT
    assert 2 * PER_TOKEN == 1_192_329_216  # 1.19 GFLOP a token outside attention
    # QK^T and AV over the causal half: 2 x (seq + 1) x 2048 in each of 2 layers
    assert LFM2.flops_per_token(model, seq) == 2 * PER_TOKEN + 8192 * (seq + 1)


@pytest.mark.parametrize("tokens", [1, 2, 8, 1800])
def test_param_bytes_per_call_is_the_hand_count_at_the_cut(tokens):
    model = CUT
    norms = 10 * 2 * 2048 + 2 * 2 * 64 + 2048
    outside = 2 * (CONV + DENSE) + 6 * CONV + 2 * ATTN + 8 * (ROUTER + 32) + norms
    experts = min(32, 4 * tokens)
    want = 2 * (outside + 8 * experts * EXPERT + tokens * 2048)
    assert LFM2.param_bytes_per_call(model, tokens) == want
    if tokens >= 8:  # every expert of every MoE layer: 6.13 GB
        assert abs(want - 6.125e9) < 0.01e9


def test_the_whole_cut_is_held_in_6_39_gb():
    shapes = LFM2.tensor_shapes(CUT)
    assert abs(2 * sum(int(np.prod(s)) for s in shapes.values()) - 6.39e9) < 0.01e9


def test_the_fp8_control_lies_farther_off_than_rounding_at_tiny_size():
    model = _tiny()
    lines, words = datagen.vocabulary(SEED, model["vocab_size"], LFM2)
    texts = datagen.make_texts(SEED, 2, words, 24, 30, 120, 60)
    ids = reference.token_ids(texts, {w: i for i, w in enumerate(lines)}, 128, LFM2)
    params = LFM2.reference_params(LFM2.make_weights(SEED, model))
    f32 = LFM2.encode(params, ids, model)
    fp8 = LFM2.encode(params, ids, model, "fp8")
    assert f32.shape == (24, 64) and np.allclose(np.linalg.norm(f32, axis=1), 1, atol=1e-5)
    # the same texts in another order and batch give the same vectors
    again = LFM2.encode(params, ids[::-1], model, batch=5)[::-1]
    np.testing.assert_allclose(again, f32, atol=1e-6, rtol=0)
    assert np.median(np.abs(fp8 - f32).max(1)) > 1e-3


def test_a_tiny_cell_of_the_family_runs_correct_and_its_control_does_not():
    manifest = os.path.join(REHEARSAL, "BENCHMARK.json")
    code, result, err = harness.run_cell(CELL, seconds=3, trace=1, manifest=manifest,
                                         extra=("--control", "1"))
    assert code == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    limits = _config(os.path.join(REHEARSAL, "limits", f"{CELL}.json"))
    control = result["control"]
    assert control["rank_gap"] > limits["rank_gap"] or control["score_err"] > limits["score_err"]
    assert control["score_err"] > 3 * result["compared"]["score_err"]["value"]
    assert "search_clean_ms" in result["metrics"]
