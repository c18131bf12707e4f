"""Pretrained-embedder path: WordPiece tokenizer parity with
``transformers.BertTokenizer`` and numerical parity of the JAX encoder with
``transformers.BertModel`` over a loaded HF state dict.

Everything runs offline: the HF model is random-initialized from a config
(no download), its state dict loaded through ``load_hf_state_dict``, and
the two forwards compared — proving a real MiniLM checkpoint would load
and reproduce the reference embedder's numbers.
"""

from __future__ import annotations

import numpy as np
import pytest

from pathway_tpu.models.embedder import Embedder, load_hf_state_dict
from pathway_tpu.models.wordpiece import WordPieceTokenizer

VOCAB = (
    "[PAD] [UNK] [CLS] [SEP] [MASK] the quick brown fox jump ##s ##ed over "
    "lazy dog stream process ##ing engine tpu ! , . ' word count hello world"
).split()


def _tokenizer() -> WordPieceTokenizer:
    return WordPieceTokenizer({t: i for i, t in enumerate(VOCAB)})


CASES = [
    "The quick brown fox jumps over the lazy dog!",
    "streaming engines process words",        # ##ing / ##s pieces
    "hello, world.",                           # punctuation splitting
    "HELLO WoRLD",                             # lowercasing
    "unknownword the",                         # [UNK] fallback
    "  spaced\tout\n text ",
    "caf\u00e9 hello",                          # accent stripping
    # the corners of ASCII, where ``encode_batch`` takes its lane of
    # whole-string calls and Python's string methods differ from BERT's rules
    "",
    " \t\r\n ",
    "the\x00dog hel\x00lo",                     # \x00 is deleted: the halves join
    "the\x7fdog wor\x7fld",
    "the\x1cquick\x1dbrown\x1efox\x1f",          # controls to BERT, white space to str.split
    "the \x1c dog",
    "the\x0bdog\x0cfox \x08hello\x1b",
    "the\tquick\nbrown\rfox\r\njumps",
    "!!!",                                     # only punctuation
    "hello,world.'the'!dog",                   # punctuation inside and around words
    "jumps-over [the] {lazy} dog_fox ~ ` ^ $ + = | < > \\ / @ # % & * ( ) ; : ? \"",
    "##s ##ing jump##ed",                      # a literal ## is punctuation
    "JUMPED Jumps jumpING",
    "word" * 25 + " the",                      # 100 characters: the greedy match is tried
    "word" * 25 + "s the",                     # 101: [UNK] whole
    "the " + "x" * 250 + " dog",
    "word" * 20 + "." + "word" * 20,           # split before the length is judged
    "count 10 words 2024",
]


@pytest.fixture(scope="module")
def bert_tokenizers(tmp_path_factory):
    transformers = pytest.importorskip("transformers")
    vocab_file = tmp_path_factory.mktemp("vocab") / "vocab.txt"
    vocab_file.write_text("\n".join(VOCAB) + "\n")
    return (WordPieceTokenizer.from_vocab_file(str(vocab_file)),
            transformers.BertTokenizer(vocab_file=str(vocab_file)))


@pytest.mark.parametrize("text", CASES)
def test_wordpiece_matches_transformers_bert_tokenizer(bert_tokenizers, text):
    ours, theirs = bert_tokenizers
    want = theirs.encode(text)
    assert ours.encode(text) == want
    assert ours.encode_batch([text, "the dog"])[0, :len(want)].tolist() == want


def test_wordpiece_truncation_and_batch():
    tok = _tokenizer()
    ids = tok.encode("the quick brown fox", max_len=4)
    assert len(ids) == 4 and ids[0] == tok.cls_id and ids[-1] == tok.sep_id
    batch = tok.encode_batch(["the dog", "hello world jumps"], max_len=8)
    # as wide as the batch's longest text, not as the limit
    longest = tok.encode("hello world jumps")
    assert batch.shape == (2, len(longest)) and len(longest) < 8
    assert batch[1].tolist() == longest
    assert batch[0, 0] == tok.cls_id
    assert batch[0, -1] == tok.pad_id  # right-padded
    cut = tok.encode_batch(["the dog", "hello world jumps"], max_len=4)
    assert cut.shape == (2, 4) and (cut[:, -1] == tok.sep_id).all()
    assert tok.encode_batch([], max_len=4).shape == (0, 0)


def _tiny_hf_bert():
    transformers = pytest.importorskip("transformers")
    import torch

    cfg = transformers.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=48, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
    )
    torch.manual_seed(7)
    model = transformers.BertModel(cfg).eval()
    # sharpen attention: random-init weights give near-uniform attention,
    # which would mask a wrong head partition (trained checkpoints have
    # sharp attention, where the partition matters)
    with torch.no_grad():
        for layer in model.encoder.layer:
            layer.attention.self.query.weight.mul_(4.0)
            layer.attention.self.key.weight.mul_(4.0)
    return model


def test_bert_arch_matches_transformers_forward():
    import jax.numpy as jnp
    import torch

    model = _tiny_hf_bert()
    emb = Embedder.from_pretrained(
        model.state_dict(), dtype=jnp.float32, n_heads=4
    )
    assert emb.cfg.n_layers == 2
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 64, size=(3, 10)).astype(np.int32)
    ids[0, 7:] = 0  # padding on one row
    ids[2, 4:] = 0

    with torch.no_grad():
        theirs = model(
            input_ids=torch.tensor(ids, dtype=torch.long),
            attention_mask=torch.tensor((ids > 0).astype(np.int64)),
        ).last_hidden_state.numpy()
    mask = (ids > 0)[:, :, None]
    ref_pooled = (theirs * mask).sum(1) / mask.sum(1)
    ref = ref_pooled / np.linalg.norm(ref_pooled, axis=-1, keepdims=True)

    ours = emb(ids)
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)

    # discriminating power: a WRONG head partition must NOT match — this
    # guards the whole parity claim (review r3: a dim-divisibility guess
    # passed only because near-uniform attention masked the partition)
    wrong = Embedder.from_pretrained(
        model.state_dict(), dtype=jnp.float32, n_heads=1
    )
    assert not np.allclose(wrong(ids), ref, atol=2e-4)

    # head count is required for raw state dicts (not derivable from shapes)
    with pytest.raises(ValueError, match="n_heads"):
        Embedder.from_pretrained(model.state_dict())


def test_seeded_parameters_are_laid_out_as_a_loaded_checkpoints():
    """``init_params`` gives ``load_hf_state_dict``'s tree at the same widths:
    the same keys, shapes and float32 host arrays, so a seeded encoder and a
    loaded one run the same programs. The default widths are
    all-MiniLM-L6-v2's published ``config.json``."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.embedder import EmbedderConfig, init_params

    loaded, cfg = load_hf_state_dict(_tiny_hf_bert().state_dict(), n_heads=4)
    seeded = init_params(cfg, seed=3)
    (want, want_tree), (got, got_tree) = (
        jax.tree_util.tree_flatten_with_path(t) for t in (loaded, seeded))
    assert got_tree == want_tree
    for (path, a), (_, b) in zip(want, got):
        assert isinstance(b, np.ndarray), path
        assert (b.shape, b.dtype) == (a.shape, a.dtype), path
    # valued as BERT initialises them: normal at 0.02, biases zero, norms one
    layer = seeded["layers"][1]
    assert abs(float(layer["mlp_in_w"].std()) - 0.02) < 2e-3
    assert not layer["q_b"].any() and (layer["ln2_scale"] == 1).all()
    assert not (seeded["tok_emb"] == init_params(cfg, seed=4)["tok_emb"]).all()
    assert dataclasses.asdict(EmbedderConfig()) == dict(
        vocab_size=30522, dim=384, n_layers=6, n_heads=12, mlp_ratio=4,
        max_len=512, dtype=jnp.bfloat16, ln_eps=1e-12)


def test_a_state_dict_loads_on_the_host_and_serves_resident_in_bfloat16():
    """The float32 masters stay on the host; the embedder places each tensor
    in bfloat16 (layernorm parameters in float32), and its forward gives the
    bits of the forward over the masters, which casts them where it reads
    them."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.embedder import _is_norm, embed_tokens

    model = _tiny_hf_bert()
    masters, cfg = load_hf_state_dict(model.state_dict(), n_heads=4)
    assert all(isinstance(leaf, np.ndarray) and leaf.dtype == np.float32
               for leaf in jax.tree_util.tree_leaves(masters))
    emb = Embedder.from_pretrained(model.state_dict(), dtype=jnp.bfloat16, n_heads=4)
    for path, leaf in jax.tree_util.tree_flatten_with_path(emb.params)[0]:
        assert isinstance(leaf, jax.Array)
        assert leaf.dtype == (jnp.float32 if _is_norm(path[-1].key) else jnp.bfloat16)
    ids = np.random.default_rng(4).integers(1, 64, size=(3, 12)).astype(np.int32)
    ids[1, 5:] = 0
    cfg = emb.cfg
    want = jax.jit(lambda p, t: embed_tokens(p, t, cfg))(masters, jnp.asarray(ids))
    assert (emb(ids) == np.asarray(want)).all()


def test_from_pretrained_directory_with_vocab(tmp_path):
    import json

    import torch

    model = _tiny_hf_bert()
    torch.save(model.state_dict(), tmp_path / "pytorch_model.bin")
    (tmp_path / "config.json").write_text(
        json.dumps({"num_attention_heads": 4, "hidden_size": 32})
    )
    (tmp_path / "vocab.txt").write_text("\n".join(VOCAB) + "\n")
    emb = Embedder.from_pretrained(tmp_path)
    assert emb.cfg.n_heads == 4  # read from config.json
    assert emb.tokenizer is not None
    vecs = emb.embed_texts(["the quick fox", "hello world"], max_len=16)
    assert vecs.shape == (2, 32)
    np.testing.assert_allclose(np.linalg.norm(vecs, axis=1), 1.0, atol=1e-3)
    # deterministic for identical batch shapes (bf16 kernels may differ
    # slightly between batch-size compilations; that is expected)
    again = emb.embed_texts(["the quick fox", "hello world"], max_len=16)
    np.testing.assert_allclose(vecs, again, atol=1e-6)
    # a different batch shape still lands within bf16 tolerance
    solo = emb.embed_texts(["the quick fox"], max_len=16)
    np.testing.assert_allclose(vecs[0], solo[0], atol=5e-3)


def _gelu_reference(name: str):
    if name == "jax":
        import jax

        return lambda x: np.asarray(jax.nn.gelu(x, approximate=False))
    import torch

    return lambda x: torch.nn.functional.gelu(torch.from_numpy(x)).numpy()


@pytest.mark.parametrize("reference", ["jax", "torch"])
def test_the_bert_gelu_is_the_exact_gelu(reference):
    """``_gelu`` (through ``erf``) is ``jax.nn.gelu(approximate=False)``
    (through ``erfc``) and PyTorch's ``gelu``, the function of HF BERT's
    ``"gelu"``, to a few float32 ulps over the range a layer's
    pre-activations take."""
    import jax.numpy as jnp

    from pathway_tpu.models.embedder import _gelu

    grid = np.linspace(-12.0, 12.0, 240_001, dtype=np.float32)
    draws = np.random.default_rng(11).normal(0.0, 3.0, 1_000_000).astype(np.float32)
    for x in (grid, draws):
        ours = np.asarray(_gelu(jnp.asarray(x)))
        assert ours.dtype == np.float32
        np.testing.assert_allclose(ours, _gelu_reference(reference)(x), atol=4e-6, rtol=0)


@pytest.mark.parametrize("program", ["question", "passage", "stored"])
def test_every_encoder_program_computes_the_gelu_through_erf(program):
    """Each kind of program the encoder runs holds ``erf`` and no ``erfc``:
    the GELU of ``jax.nn.gelu(approximate=False)`` is ``erfc``, which the
    TPU compiler fuses into the operand of the ``mlp_out`` product."""
    import re

    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.embedder import _blank_ids

    emb = Embedder.from_pretrained(_tiny_hf_bert().state_dict(), dtype=jnp.bfloat16,
                                   n_heads=4)
    question, passage = emb.shapes[0], emb.shapes[-1]
    looped = program == "passage"
    if program == "stored":
        fn, ids = emb._token_rows, jnp.ones((3, 12), jnp.int32)
    else:
        rows, length = question if program == "question" else passage
        assert (length > question[1]) == looped
        fn, ids = emb._fwd, jnp.asarray(_blank_ids(rows, length))
    text = str(jax.make_jaxpr(fn)(emb._handed(looped=looped), ids))
    # one GELU a layer: laid out, each layer's; looped, the one traced layer's
    assert len(re.findall(r"\berf\b", text)) == (1 if looped else emb.cfg.n_layers)
    assert not re.search(r"\berfc\b", text)
