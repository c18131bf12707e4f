"""The packed forward of ``models/embedder.py``: a call's texts share the rows
of one program of a declared set, each text a segment with its own attention,
positions, pool and norm. Held to the benchmark's plain float32 reference
(the BERT family's, ``benchmark/models/bert.py``, loaded through
``benchmark/lib/family.py`` as the harness loads it) and to the forward of
each text alone: whole queries up to the model's own positions, nothing
compiled after the warm, the stored path's rule, a served 300-token query, a
filtered search that pads vectors and not texts, short questions at sixteen
tokens a row, and the parameters resident in the compute dtype with the
float32 masters' bits."""

import contextlib
import http.client
import json
import os
import socket
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from lib import datagen, family, reference  # noqa: E402

import pathway_tpu as pw  # noqa: E402
from pathway_tpu.models import embedder as embedder_mod  # noqa: E402
from pathway_tpu.models.embedder import (  # noqa: E402
    SHAPES, TEXTS_PER_DISPATCH, Embedder, declared_shapes)
from pathway_tpu.models.wordpiece import WordPieceTokenizer  # noqa: E402
from pathway_tpu.ops.index_engines import BruteForceKnnEngine  # noqa: E402
from pathway_tpu.serve.stats import SERVE_STATS  # noqa: E402

BERT = family.load({"model_type": "bert"})
SEED = 40
MODEL = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
             intermediate_size=64, vocab_size=1200, max_position_embeddings=64,
             type_vocab_size=2, layer_norm_eps=1e-12, initializer_range=0.2)
MAX_LEN = MODEL["max_position_embeddings"]


def _build(model: dict, seed: int = SEED):
    """(embedder, reference parameters, vocabulary index, whole words), all
    from the seed: random weights through ``from_pretrained``, float32."""
    lines, words = datagen.vocabulary(seed, model["vocab_size"], BERT)
    index = {w: i for i, w in enumerate(lines)}
    state = BERT.make_weights(seed, model)
    emb = Embedder.from_pretrained(
        state, n_heads=model["num_attention_heads"],
        tokenizer=WordPieceTokenizer(dict(index)), dtype=jnp.float32)
    return emb, BERT.reference_params(state), index, words


@pytest.fixture(scope="module")
def built():
    return _build(MODEL)


def _text(rng, words, tokens: int) -> str:
    """A text of ``tokens`` tokens, [CLS] and [SEP] counted: a word is one."""
    return " ".join(words[i] for i in rng.integers(0, len(words), size=tokens - 2))


def _reference(ref, index, texts, model=MODEL):
    ids = reference.token_ids(texts, index, model["max_position_embeddings"], BERT)
    return BERT.encode(ref, ids, model, batch=16)


# -- (a) against the plain reference and against each text alone ---------------

@pytest.mark.parametrize("trial", range(6))
def test_packed_texts_equal_the_reference_and_each_text_alone(built, trial):
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, trial])
    texts = [_text(rng, words, int(t))
             for t in rng.integers(2, MAX_LEN + 1, size=rng.integers(1, 17))]
    got = np.asarray(emb.embed_texts_device(texts))
    assert got.shape == (len(texts), MODEL["hidden_size"])
    np.testing.assert_allclose(got, _reference(ref, index, texts), atol=1e-5, rtol=0)
    alone = np.concatenate([np.asarray(emb.embed_texts_device([t])) for t in texts])
    np.testing.assert_allclose(got, alone, atol=1e-5, rtol=0)
    # and the row-a-text forward at the caller's own shape, unpacked
    for t, vec in zip(texts, got):
        ids = np.asarray([emb.tokenizer.encode(t)], np.int32)
        np.testing.assert_allclose(emb(ids)[0], vec, atol=1e-5, rtol=0)


def test_every_length_from_two_tokens_to_the_models_positions(built):
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, 99])
    texts = [_text(rng, words, t) for t in range(2, MAX_LEN + 1)]
    want = _reference(ref, index, texts)
    for s in range(0, len(texts), 9):  # batches that mix neighbouring lengths
        got = np.asarray(emb.embed_texts_device(texts[s:s + 9]))
        np.testing.assert_allclose(got, want[s:s + 9], atol=1e-5, rtol=0)


def test_a_call_larger_than_any_program_is_split_and_still_whole(built):
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, 7])
    texts = [_text(rng, words, int(t)) for t in rng.integers(2, MAX_LEN + 1, size=45)]
    before = SERVE_STATS["embed_dispatches_total"]
    got = np.asarray(emb.embed_texts_device(texts))
    assert SERVE_STATS["embed_dispatches_total"] - before >= 2  # over 32 texts
    np.testing.assert_allclose(got, _reference(ref, index, texts), atol=1e-5, rtol=0)
    assert emb.embed_texts_device([]).shape == (0, MODEL["hidden_size"])


# -- (b) the model's own limit, and what lies beyond it ------------------------

def test_a_text_of_the_models_positions_is_whole_and_a_longer_one_is_cut_and_counted(built):
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, 1])
    exact = _text(rng, words, MAX_LEN)
    longer = exact + " " + _text(rng, words, 9)
    before = SERVE_STATS["embed_truncated_texts_total"]
    whole = np.asarray(emb.embed_texts_device([exact]))
    assert SERVE_STATS["embed_truncated_texts_total"] == before
    np.testing.assert_allclose(whole, _reference(ref, index, [exact]), atol=1e-5, rtol=0)
    # the cut keeps the first max_len - 1 tokens and closes with [SEP], as
    # upstream: the vector of the text's first 62 words
    cut = np.asarray(emb.embed_texts_device([longer]))
    assert SERVE_STATS["embed_truncated_texts_total"] == before + 1
    np.testing.assert_allclose(cut, whole, atol=1e-5, rtol=0)
    # a caller may state less than the model's limit, never more
    short = np.asarray(emb.embed_texts_device([exact], max_len=10))
    first8 = " ".join(exact.split()[:8])
    np.testing.assert_allclose(short, _reference(ref, index, [first8]), atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        np.asarray(emb.embed_texts_device([exact], max_len=10_000)), whole, atol=0, rtol=0)
    np.testing.assert_allclose(emb.embed_texts([longer]), whole, atol=1e-5, rtol=0)


def test_the_tokenizers_lanes_are_counted(built):
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, 7])
    texts = [_text(rng, words, t) for t in (5, 30, 64)]
    keys = ("embed_tokenize_texts_total", "embed_tokenize_fast_texts_total")
    before = [SERVE_STATS[k] for k in keys]
    ascii_only = np.asarray(emb.embed_texts_device(texts))
    # ASCII texts: both counters alike, the share is 1.0
    assert [SERVE_STATS[k] - b for k, b in zip(keys, before)] == [3, 3]
    np.testing.assert_allclose(ascii_only, _reference(ref, index, texts), atol=1e-5, rtol=0)
    # one text that is not ASCII takes the exact path: the counters part,
    # and the accent is stripped there, so the vectors are the same
    accented = [texts[0], texts[1].replace("a", "\u00e1", 1), texts[2]]
    assert not accented[1].isascii()
    before = [SERVE_STATS[k] for k in keys]
    mixed = np.asarray(emb.embed_texts_device(accented))
    assert [SERVE_STATS[k] - b for k, b in zip(keys, before)] == [3, 2]
    np.testing.assert_allclose(mixed, ascii_only, atol=0, rtol=0)


def test_no_served_call_site_cuts_below_the_models_positions():
    import inspect

    from pathway_tpu.xpacks.llm.embedders import TpuEmbedder

    for fn in (Embedder.embed_texts_device, Embedder.embed_texts, TpuEmbedder.__init__,
               WordPieceTokenizer.encode_batch):
        assert inspect.signature(fn).parameters["max_len"].default is None, fn


# -- (c) a closed set of programs, all compiled by the warm --------------------

def test_the_declared_shapes_by_model_length():
    assert declared_shapes(512) == tuple((r, n) for n in (16, 128, 512) for r in SHAPES[n])
    assert len(declared_shapes(512)) == 19
    # a model of fewer positions: its own length takes the row counts of the
    # first declared length that holds it
    assert declared_shapes(64) == (*((r, 16) for r in SHAPES[16]),
                                   *((r, 64) for r in SHAPES[128]))
    assert declared_shapes(16) == tuple((r, 16) for r in SHAPES[16])
    assert [n for _, n in declared_shapes(1024)][-1] == 1024
    for length, rows in SHAPES.items():
        assert list(rows) == sorted(set(rows)) and rows[0] == 1
        assert length >= 16 and length & (length - 1) == 0


def test_after_the_warm_two_hundred_batches_compile_nothing(built, monkeypatch):
    emb, _, _, words = built
    emb.warm()
    assert emb._fwd._cache_size() == len(emb.shapes) == 9
    met = set()
    dispatch = emb._dispatch

    def noting(toks, lengths, texts, plan):
        met.add((plan[0], plan[1]))
        return dispatch(toks, lengths, texts, plan)

    monkeypatch.setattr(emb, "_dispatch", noting)
    rng = np.random.default_rng([SEED, 2])
    takes = emb._take._cache_size()
    for _ in range(200):
        texts = [_text(rng, words, int(t))
                 for t in rng.integers(2, MAX_LEN + 1, size=rng.integers(1, 17))]
        if rng.random() < 0.25:
            emb.embed_texts(texts)
        else:
            emb.embed_texts_device(texts)
    assert emb._fwd._cache_size() == 9 and emb._take._cache_size() == takes
    assert met <= set(emb.shapes) and len(met) >= 5


def test_the_first_served_call_compiles_the_whole_declared_set():
    emb, _, _, words = _build(MODEL, seed=SEED + 1)
    before = SERVE_STATS["embed_shapes_compiled_total"]
    # one short question: every program a later call can be given, and the cuts
    emb.embed_texts_device([words[0]])
    assert emb._fwd._cache_size() == len(emb.shapes) == 9
    assert emb._take._cache_size() == TEXTS_PER_DISPATCH
    assert SERVE_STATS["embed_shapes_compiled_total"] - before == 9
    emb.embed_texts_device([" ".join(words[:20])] * 9)
    assert emb._fwd._cache_size() == 9 and emb._take._cache_size() == TEXTS_PER_DISPATCH
    # an owner may compile them before any call
    emb2 = _build(MODEL, seed=SEED + 1)[0]
    emb2.warm()
    assert emb2._fwd._cache_size() == 9 and emb2._take._cache_size() == TEXTS_PER_DISPATCH
    emb2.embed_texts_device([" ".join(words[:20])])
    assert emb2._fwd._cache_size() == 9
    # and a store fed with texts has them before its first query: ingest has
    # no deadline, a sharded server's first query has one
    emb3 = _build(MODEL, seed=SEED + 1)[0]
    emb3.embed_texts([words[0]])
    assert emb3._fwd._cache_size() == 9 and emb3._take._cache_size() == TEXTS_PER_DISPATCH
    assert SERVE_STATS["embed_shapes_compiled_total"] - before == 27


def test_question_programs_lay_the_layers_out_and_passage_programs_loop(built):
    emb = built[0]
    # kept twice, in the compute dtype: a list for the programs that lay the
    # layers out and the same tensors stacked for those that loop
    layers, stacked = emb.params["layers"], emb.params["stacked"]
    n = MODEL["num_hidden_layers"]
    assert isinstance(layers, list) and len(layers) == n
    assert set(stacked) == set(layers[0])
    for k, v in stacked.items():
        assert v.shape == (n, *layers[0][k].shape) and v.dtype == layers[0][k].dtype

    def loops(length):
        ids = jnp.asarray(embedder_mod._blank_ids(2, length))
        return emb._fwd.lower(emb._handed(looped=length > 16), ids).as_text().count(
            "stablehlo.while")

    # a passage program holds one layer's code, whatever the depth; a question
    # program is laid out layer by layer, as a search's program always was
    assert loops(MAX_LEN) == 1 and loops(16) == 0
    # and the two forms agree
    rows = np.asarray(built[3][:40]).reshape(4, 10)
    ids = np.asarray([emb.tokenizer.encode(" ".join(r)) for r in rows], np.int32)
    a = embedder_mod.embed_tokens(emb._handed(looped=False), jnp.asarray(ids), emb.cfg)
    b = embedder_mod.embed_tokens(emb._handed(looped=True), jnp.asarray(ids), emb.cfg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=0)


# -- (g) the parameters stay on the device in the compute dtype ---------------

def _resident(kind: str, seed: int = SEED + 4):
    """(bfloat16 embedder, its float32 masters, words): the encoder from a
    state dict, or from a seed with the generated vocabulary (whose letters,
    digits and punctuation marks are words of one token each)."""
    if kind == "pretrained":
        lines, words = datagen.vocabulary(seed, MODEL["vocab_size"], BERT)
        state = BERT.make_weights(seed, MODEL)
        emb = Embedder.from_pretrained(
            state, n_heads=MODEL["num_attention_heads"],
            tokenizer=WordPieceTokenizer({w: i for i, w in enumerate(lines)}),
            dtype=jnp.bfloat16)
        masters = embedder_mod.load_hf_state_dict(
            state, n_heads=MODEL["num_attention_heads"])[0]
        return emb, masters, words
    cfg = embedder_mod.EmbedderConfig(
        vocab_size=MODEL["vocab_size"], dim=MODEL["hidden_size"],
        n_layers=MODEL["num_hidden_layers"], n_heads=MODEL["num_attention_heads"],
        mlp_ratio=MODEL["intermediate_size"] // MODEL["hidden_size"], max_len=MAX_LEN)
    emb = Embedder(cfg, seed=seed)
    words = [w for w in emb.tokenizer.vocab if len(w) == 1]
    return emb, embedder_mod.init_params(cfg, seed), words


class _Spy:
    """Stands for a jitted program: notes what each call is handed."""

    def __init__(self, program):
        self.program, self.calls = program, []

    def __call__(self, params, ids):
        self.calls.append((params, ids))
        return self.program(params, ids)

    def _cache_size(self):
        return self.program._cache_size()


def _spied(emb, monkeypatch):
    fwd, rows = _Spy(emb._fwd), _Spy(emb._token_rows)
    monkeypatch.setattr(emb, "_fwd", fwd)
    monkeypatch.setattr(emb, "_token_rows", rows)
    return fwd, rows


@pytest.mark.parametrize("kind", ["pretrained", "seeded"])
def test_resident_parameters_give_the_float32_masters_bits(kind, monkeypatch):
    """A question program, a passage program and a stored batch over the
    resident parameters give the bits of ``embed_tokens`` over the float32
    masters, laid out and looped alike: the cast at load rounds as the cast
    in the program does."""
    import jax

    emb, masters, words = _resident(kind)
    emb.warm()
    fwd, rows = _spied(emb, monkeypatch)
    cfg = emb.cfg
    tables = {k: v for k, v in masters.items() if k != "layers"}
    laid_out = {**tables, "layers": masters["layers"]}
    looped = {**tables, "layers": embedder_mod.stack_layers(masters["layers"])}
    served = jax.jit(lambda p, ids: embedder_mod.embed_tokens(
        p, ids[0], cfg, segments=ids[1], positions=ids[2], texts=TEXTS_PER_DISPATCH))
    stored = jax.jit(lambda p, ids: embedder_mod.embed_tokens(p, ids, cfg))
    rng = np.random.default_rng([SEED, 10])

    def texts(tokens):
        return [" ".join(words[i] for i in rng.integers(0, len(words), size=t - 2))
                for t in tokens]

    question = texts((5, 9, 14))
    got = np.asarray(emb.embed_texts_device(question))
    ids = fwd.calls[-1][1]
    assert ids.shape[2] == 16
    assert (got == np.asarray(served(laid_out, ids))[:3]).all()
    passages = texts((40, 60, 25, 64))
    got = np.asarray(emb.embed_texts_device(passages))
    ids = fwd.calls[-1][1]
    assert ids.shape[2] == MAX_LEN
    assert (got == np.asarray(served(looped, ids))[:4]).all()
    batch = question + passages
    got = emb.embed_texts(batch)
    assert len(rows.calls) == 3  # lengths 16, 32 and 64
    want = np.zeros_like(got)
    for _, ids in rows.calls:
        own = [i for i, t in enumerate(batch) if _stored_row(t) == ids.shape[1]]
        want[own] = np.asarray(stored(laid_out, ids))
    assert (got == want).all()


@pytest.mark.parametrize("kind", ["pretrained", "seeded"])
def test_no_leaf_a_served_program_is_handed_is_float32_but_a_layernorms(kind, monkeypatch):
    import jax

    emb, _, words = _resident(kind)
    emb.warm()
    fwd, rows = _spied(emb, monkeypatch)
    emb.embed_texts_device([" ".join(words[:8])])           # a question program
    emb.embed_texts_device([" ".join(words[:50])] * 2)      # a passage program
    emb.embed_texts([" ".join(words[:8]), " ".join(words[:50])])  # stored
    emb(np.asarray([[2, 7, 9, 3]], np.int32))               # the caller's shape
    assert len(fwd.calls) == 2 and len(rows.calls) == 3
    forms = {}
    for params, ids in fwd.calls + rows.calls:
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        for path, leaf in leaves:
            name = path[-1].key
            want = jnp.float32 if embedder_mod._is_norm(name) else jnp.bfloat16
            assert leaf.dtype == want, (path, leaf.dtype)
        # a program is handed the form it reads, not both
        assert "stacked" not in params
        forms[ids.ndim == 3 and ids.shape[2] > 16] = type(params["layers"])
    assert forms == {True: dict, False: list}


@pytest.mark.parametrize("kind", ["pretrained", "seeded"])
def test_the_parameter_bytes_are_counted_at_load(kind):
    import jax

    before = SERVE_STATS["embed_param_bytes_total"]
    emb, masters, _ = _resident(kind)
    held = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(emb.params))
    assert SERVE_STATS["embed_param_bytes_total"] - before == held

    def resident(name, master):  # half a float32 master's bytes in bfloat16
        return np.asarray(master).nbytes // (1 if embedder_mod._is_norm(name) else 2)

    # the tables once and the layers twice, as a list and as a stack
    assert held == (sum(resident(k, v) for k, v in masters.items() if k != "layers")
                    + 2 * sum(resident(k, v) for layer in masters["layers"]
                              for k, v in layer.items()))


@pytest.mark.parametrize("kind", ["pretrained", "seeded"])
def test_setting_the_parameters_to_none_frees_the_encoder(kind):
    import gc

    import jax

    def live():
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays())

    base = live()
    emb, masters, words = _resident(kind)
    del masters
    emb.embed_texts_device([" ".join(words[:8]), " ".join(words[:50])])
    held = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(emb.params))
    assert live() - base >= held
    emb.params = None
    assert live() == base


# -- (d) a stored vector is a function of its text alone -----------------------

def _stored_row(text: str) -> int:
    """The row a stored text runs in: the power of two that holds its tokens,
    sixteen at the least."""
    return max(16, 1 << (len(text.split()) + 2 - 1).bit_length())


def test_a_stored_texts_vector_does_not_depend_on_its_batch(built):
    """Stored texts keep the path they had before queries were packed: a row
    each, at the text's own length, the texts of one length in one forward.
    The texts it is embedded with, where it stands among them and how many of
    its length there are move a stored text's float32 vector by rounding alone
    (1e-6; a plain batched forward is not the same to the bit at every row),
    and the same batch embedded again gives the same bits."""
    emb, ref, index, words = built
    rng = np.random.default_rng([SEED, 3])
    others = [_text(rng, words, int(t)) for t in rng.integers(2, MAX_LEN + 1, size=60)]
    for tokens in (2, 9, 16, 17, 40, MAX_LEN):
        text = _text(rng, words, tokens)
        same = [t for t in others if _stored_row(t) == _stored_row(text)]
        other = [t for t in others if _stored_row(t) != _stored_row(text)]
        alone = emb.embed_texts([text])[0]
        np.testing.assert_allclose(alone, _reference(ref, index, [text])[0], atol=1e-5, rtol=0)
        for group in (1, 3, 4, 7):  # texts of its length in the batch, itself counted
            for trial in range(4):
                batch = [same[i] for i in rng.permutation(len(same))[:group - 1]]
                batch += [other[i] for i in rng.permutation(len(other))[:rng.integers(0, 12)]]
                batch = [batch[i] for i in rng.permutation(len(batch))]
                at = int(rng.integers(0, len(batch) + 1))
                batch.insert(at, text)
                got = emb.embed_texts(batch)
                np.testing.assert_allclose(got[at], alone, atol=1e-6, rtol=0)
                if group == 1:  # the only text of its length: its own program
                    assert (got[at] == alone).all()
            assert (emb.embed_texts(batch) == got).all()


def test_stored_texts_run_a_row_each_at_their_own_length(built, monkeypatch):
    emb, ref, index, words = built
    shapes = []
    token_rows = emb._token_rows
    monkeypatch.setattr(emb, "_token_rows", lambda params, ids: (
        shapes.append(tuple(ids.shape)), token_rows(params, ids))[1])
    emb.warm()
    served = emb._fwd._cache_size()
    before = dict(SERVE_STATS)
    rng = np.random.default_rng([SEED, 4])
    texts = [_text(rng, words, t) for t in (5, 16, 17, 64, 3)]
    got = emb.embed_texts(texts)
    # three texts of at most sixteen tokens as three rows of sixteen, the one
    # of 17 a row of 32, the one of 64 a row of 64: no row is shared, no
    # program of the served set is run, and a call may state a shorter limit
    assert sorted(shapes) == [(1, 32), (1, 64), (3, 16)]
    assert emb._fwd._cache_size() == served
    np.testing.assert_allclose(got, _reference(ref, index, texts), atol=1e-5, rtol=0)
    assert SERVE_STATS["embed_dispatches_total"] - before["embed_dispatches_total"] == 3
    assert SERVE_STATS["embed_real_tokens_total"] - before["embed_real_tokens_total"] == 105
    assert (SERVE_STATS["embed_padded_tokens_total"]
            - before["embed_padded_tokens_total"]) == 3 * 16 + 32 + 64
    del shapes[:]
    emb.embed_texts(texts[:2], max_len=10)
    assert shapes == [(2, 10)]
    assert emb.embed_texts([]).shape == (0, MODEL["hidden_size"])


# -- (e) served: a 300-token query whole, a filtered search packs 3 texts ------

LONG = dict(MODEL, max_position_embeddings=320)
ROWS, K = 256, 5


@contextlib.contextmanager
def _serving(emb, rows):
    from pathway_tpu.internals.run import request_stop
    from pathway_tpu.io.http._server import terminate_all
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    fed, stop = threading.Event(), threading.Event()

    class Feed(pw.io.python.ConnectorSubject):
        def run(self):
            ids = np.arange(len(rows))
            self.next_batch({"id": ids, "data": [f"row {i}" for i in ids],
                             "_metadata": [{"path": f"d{i}"} for i in ids],
                             "vec": list(rows)})
            self.commit()
            fed.set()
            stop.wait()

    schema = pw.schema_builder({
        "id": pw.column_definition(dtype=int, primary_key=True),
        "data": str, "_metadata": dict, "vec": np.ndarray})
    docs = pw.io.python.read(Feed(), schema=schema, autocommit_duration_ms=None)
    store = DocumentStore(
        docs, BruteForceKnnFactory(dimensions=rows.shape[1], reserved_space=len(rows),
                                   metric="cos", embedder=emb),
        vector_column="vec")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = DocumentStoreServer("127.0.0.1", port, store)
    thread = server.run(threaded=True)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)

    def post(payload, route="/v1/retrieve"):
        conn.request("POST", route, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    try:
        assert fed.wait(60) and server.webserver._started.wait(60)
        for _ in range(100):
            if post({}, "/v1/statistics")[1].get("file_count") == len(rows):
                break
        else:
            raise AssertionError("the index was not built")
        yield post
    finally:
        conn.close()
        stop.set()
        request_stop()
        terminate_all()
        thread.join(60)
        assert not thread.is_alive()


def test_a_served_query_of_300_tokens_answers_the_references_top_k():
    emb, ref, index, words = _build(LONG, seed=SEED + 2)
    rng = np.random.default_rng([SEED, 5])
    queries = [_text(rng, words, t) for t in (300, 320, 200)]
    want = _reference(ref, index, queries, LONG)
    # the same queries cut at 128 tokens, as the served path once did
    cut = _reference(ref, index, [" ".join(q.split()[:126]) for q in queries], LONG)
    # bags of random words pool close to one axis: the rows lie off it, and
    # one row stands where each whole query leaves the axis, so that it leads
    # that query's answer and a cut query scores it elsewhere
    axis = np.concatenate([want, cut]).mean(0)
    axis /= np.linalg.norm(axis)

    def off_axis(v):
        v = v - (v @ axis)[:, None] * axis
        return v / np.linalg.norm(v, axis=1, keepdims=True)

    rows = off_axis(datagen.make_rows(SEED, ROWS, LONG["hidden_size"]))
    rows[:3] = off_axis(want)
    tol = 2e-3  # the scan's bfloat16 operands, on scores under 0.1
    with _serving(emb, rows) as post:
        for i, q in enumerate(queries):
            status, body = post({"query": q, "k": K})
            assert status == 200 and len(body) == K
            got = [(int(hit["text"].split()[1]), -hit["dist"]) for hit in body]
            scores = rows @ want[i]
            assert got[0][0] == i == int(np.argmax(scores))
            for row, score in got:
                assert abs(score - scores[row]) < tol
            assert min(scores[row] for row, _ in got) > np.sort(scores)[-K] - tol
            # and it is not the answer to the query cut at 128 tokens
            assert abs(got[0][1] - (rows @ cut[i])[i]) > 5 * tol
    assert emb._fwd._cache_size() == len(emb.shapes) == 19


def test_a_filtered_search_embeds_its_queries_once_and_pads_vectors():
    emb, ref, index, words = _build(MODEL, seed=SEED + 3)
    rng = np.random.default_rng([SEED, 6])
    rows = datagen.make_rows(SEED, ROWS, MODEL["hidden_size"])
    engine = BruteForceKnnEngine(MODEL["hidden_size"], metric="cos",
                                 reserved_space=ROWS, embedder=emb)
    engine.add_batch(list(range(ROWS)), list(rows),
                     [{"path": f"t{i % 4}/d{i}"} for i in range(ROWS)])
    queries = [_text(rng, words, t) for t in (40, 7, 64)]
    want = _reference(ref, index, queries)
    emb.warm()
    before = dict(SERVE_STATS)
    seen = []
    dispatch = emb._dispatch
    emb._dispatch = lambda toks, lengths, texts, plan: (
        seen.append((len(texts), plan[0], plan[1])), dispatch(toks, lengths, texts, plan))[1]
    out = engine.search(queries, [K] * 3, ["globmatch('t1/*', path)", None,
                                           "globmatch('t3/*', path)"])
    got = {k: SERVE_STATS[k] - before[k] for k in SERVE_STATS}
    # three segments in one program of two rows of 64: the padding to eight
    # queries is zero vectors cut from the program's result, not texts
    assert seen == [(3, 2, 64)]
    assert got["embed_dispatches_total"] == 1
    assert got["embed_real_tokens_total"] == 40 + 7 + 64
    assert got["embed_padded_tokens_total"] == 2 * 64
    assert got["index_filtered_queries_total"] == 2
    for i, (hits, scope) in enumerate(zip(out, (1, None, 3))):
        live = np.asarray([scope is None or r % 4 == scope for r in range(ROWS)])
        scores = np.where(live, rows @ want[i], -np.inf)
        assert len(hits) == K and all(live[key] for key, _ in hits)
        for key, score in hits:
            assert abs(score - scores[key]) < 5e-3
        assert min(scores[key] for key, _ in hits) > np.sort(scores)[-K] - 5e-3


# -- (f) short questions keep their small program -----------------------------

def test_eight_short_questions_run_at_sixteen_tokens_a_row(built, monkeypatch):
    emb, ref, index, words = built
    plans = []
    dispatch = emb._dispatch
    monkeypatch.setattr(emb, "_dispatch", lambda toks, lengths, texts, plan: (
        plans.append(plan), dispatch(toks, lengths, texts, plan))[1])
    rng = np.random.default_rng([SEED, 8])
    for trial in range(20):
        texts = [_text(rng, words, int(t)) for t in rng.integers(5, 17, size=8)]
        got = np.asarray(emb.embed_texts_device(texts))
        np.testing.assert_allclose(got, _reference(ref, index, texts), atol=1e-5, rtol=0)
    assert len(plans) == 20
    assert all(length == 16 and rows <= 8 for rows, length, _ in plans)


def test_more_short_questions_than_sixteen_rows_hold_share_longer_rows(built, monkeypatch):
    emb, ref, index, words = built
    plans = []
    dispatch = emb._dispatch
    monkeypatch.setattr(emb, "_dispatch", lambda toks, lengths, texts, plan: (
        plans.append(plan), dispatch(toks, lengths, texts, plan))[1])
    rng = np.random.default_rng([SEED, 9])
    texts = [_text(rng, words, 16) for _ in range(20)]  # twenty rows of sixteen
    got = np.asarray(emb.embed_texts_device(texts))
    np.testing.assert_allclose(got, _reference(ref, index, texts), atol=1e-5, rtol=0)
    # four to a row of 64: five rows, in the program of eight; still one dispatch
    assert [(rows, length) for rows, length, _ in plans] == [(8, MAX_LEN)]


def test_first_fit_packs_longest_first():
    rows = embedder_mod._pack(np.asarray([3, 16, 8, 5, 8, 9]), 16)
    assert rows == [[1], [5, 3], [2, 4], [0]]
