"""The ASCII lane of ``WordPieceTokenizer.encode_batch`` against the exact path,
``encode``, which asks ``unicodedata`` about every character: the same ids for
every text of a seeded fuzz corpus, whichever way a text went, and the counters
of ``serve/stats.py`` say which way that was.
"""

from __future__ import annotations

import random
import string

import numpy as np
import pytest

from pathway_tpu.models.wordpiece import WordPieceTokenizer
from pathway_tpu.serve.stats import SERVE_STATS

WORDS = ("the quick brown fox jump over lazy dog stream process engine tpu word "
         "count hello world a b c x y z ab abc 1 2 10 2024 3d").split()
PIECES = "##s ##ed ##ing ##a ##b ##c ##x ##1 ##0 ##ab".split()
PUNCTUATION = list("!,.'-#[]_`{}~@\\") + ["##!"]
#: in the vocabulary and over ``max_chars_per_word``: [UNK] all the same
LONG_IN_VOCAB = "q" * 101
VOCAB = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]", *WORDS, *PIECES, *PUNCTUATION,
         LONG_IN_VOCAB, "Upper", "r" * 100]
#: the ASCII controls ``_clean`` deletes; \x1c-\x1f are white space to ``str.split``
DROPPED = ["\x00", "\x01", "\x08", "\x0b", "\x0c", "\x0e", "\x1b", "\x1c", "\x1d", "\x1e",
           "\x1f", "\x7f"]
SPACES = [" ", "  ", "\t", "\n", "\r", "\r\n", " \t "]
NOT_ASCII = {
    "accent": "caf\u00e9", "cjk": "\u65e5\u672c the dog", "ellipsis": "the dog\u2026 jumps",
    "diaeresis": "na\u00efve fox", "zero_width_space": "\u200bthe", "full_width": "\uff21\uff22 c",
    "replacement_character": "the\ufffddog", "combining_mark": "x\u0301y",
    "c1_control": "\x85the dog", "no_break_space": "the\u00a0dog",
}


def _word(rng: random.Random, kind: str) -> str:
    if kind == "lower":
        return rng.choice(WORDS)
    if kind == "upper":
        w = rng.choice(WORDS)
        return rng.choice([w.upper(), w.capitalize(), w.swapcase(), "Upper", "UPPER"])
    if kind == "unknown":  # letters and digits: pieces where they exist, else [UNK]
        return "".join(rng.choices("abcxyzq019", k=rng.randint(1, 9)))
    if kind == "continued":  # a whole word, then ## pieces
        return rng.choice(WORDS) + "".join(p[2:] for p in rng.choices(PIECES, k=rng.randint(1, 3)))
    if kind == "punctuated":
        marks = string.punctuation
        w = rng.choice(WORDS)
        at = rng.randint(0, len(w))
        return rng.choice([
            w + rng.choice(marks), rng.choice(marks) + w, w[:at] + rng.choice(marks) + w[at:],
            rng.choice(marks) * rng.randint(1, 4), "##" + w, w + "##s",
            rng.choice(marks) + w + rng.choice(marks) + rng.choice(WORDS) + rng.choice(marks),
        ])
    if kind == "controls":  # deleted: what is either side of one joins up
        w = rng.choice(WORDS)
        at = rng.randint(0, len(w))
        return rng.choice([w[:at] + rng.choice(DROPPED) + w[at:], rng.choice(DROPPED),
                           rng.choice(DROPPED) + w, w + rng.choice(DROPPED) + rng.choice(WORDS)])
    if kind == "long":
        return rng.choice([LONG_IN_VOCAB, "r" * 100, "r" * 101, "a" * 100, "a" * 101, "a" * 250,
                           "a" * 60 + "." + "a" * 60, "a" * 101 + "!", "Q" * 101,
                           "a" * 50 + "\x00" + "a" * 51])
    if kind == "soup":  # any of the 128 characters, in any order
        return "".join(chr(rng.randrange(128)) for _ in range(rng.randint(1, 12)))
    raise AssertionError(kind)


KINDS = ["lower", "upper", "unknown", "continued", "punctuated", "controls", "long", "soup"]
FIXED = {
    "empty": ["", " ", "\t\n\r", "\x00", "\x1c\x1d\x1e\x1f", "\x7f \x00", "\x0b\x0c"],
    "only_punctuation": ["!", "...", "!?!", "# #", "##", "[CLS]", "[UNK] the", "_", "a_b", "`~`",
                         "'hello'", "hello,world.", "(the)", "{}", "2,024.10", "3-d"],
    "split_corners": ["the\x1cdog", "the \x1c dog", "the\x1fdog\x1e", "the\x00dog", "the\x7fdog",
                      "the\tdog\nfox\rjump\r\nover", "  the   dog  ", "the\x0bdog", "the\x0cdog",
                      "\x1cthe", "the\x1c", "a\x00.\x00b", "The DOG", "UPPER Upper upper"],
}


def _corpus(kind: str) -> list[str]:
    if kind in FIXED:
        return FIXED[kind]
    if kind == "mixed":
        kinds, count = KINDS, 80
    else:
        kinds, count = [kind, "lower"], 40
    rng = random.Random(f"wordpiece-{kind}")
    texts = []
    for _ in range(count):
        n = rng.choice([1, 2, 5, 20, 300])  # 300: longer than any limit below
        parts = [rng.choice(["", *SPACES])]
        for _ in range(n):
            parts += [_word(rng, rng.choice(kinds)), rng.choice(SPACES)]
        texts.append("".join(parts))
    return texts


def _exact(tok: WordPieceTokenizer, texts: list[str], max_len: int | None) -> np.ndarray:
    """``encode_batch`` as it was before the lane: ``encode`` a text."""
    rows = [tok.encode(t, max_len) for t in texts]
    out = np.full((len(rows), max(map(len, rows), default=0)), tok.pad_id, dtype=np.int32)
    for i, ids in enumerate(rows):
        out[i, :len(ids)] = ids
    return out


def _counted(tok: WordPieceTokenizer, texts: list[str], max_len: int | None):
    before = dict(SERVE_STATS)
    got = tok.encode_batch(texts, max_len)
    return (got, SERVE_STATS["embed_tokenize_texts_total"] - before["embed_tokenize_texts_total"],
            SERVE_STATS["embed_tokenize_fast_texts_total"]
            - before["embed_tokenize_fast_texts_total"])


@pytest.mark.parametrize("lowercase", [True, False], ids=["lowercase", "cased"])
@pytest.mark.parametrize("max_len", [None, 1, 4, 16, 513])
@pytest.mark.parametrize("kind", [*KINDS, "mixed", *FIXED])
def test_ascii_lane_gives_the_exact_paths_ids(kind, max_len, lowercase):
    tok = WordPieceTokenizer({t: i for i, t in enumerate(VOCAB)}, lowercase=lowercase)
    texts = _corpus(kind)
    assert all(t.isascii() for t in texts)
    got, n, fast = _counted(tok, texts, max_len)
    assert n == fast == len(texts)  # every one took the lane
    want = _exact(tok, texts, max_len)
    assert got.dtype == np.int32 and got.shape == want.shape
    for text, a, b in zip(texts, got.tolist(), want.tolist()):
        assert a == b, repr(text)
    for text in texts[:8]:  # alone, a text is as wide as itself
        assert tok.encode_batch([text], max_len)[0].tolist() == tok.encode(text, max_len)


def test_the_corpus_meets_its_corners():
    """The fuzz would prove little if it never left the whole-word look-up."""
    tok = WordPieceTokenizer({t: i for i, t in enumerate(VOCAB)})
    ids = [i for t in _corpus("mixed") for i in tok.encode(t)]
    assert tok.unk_id in ids and tok.vocab["##ing"] in ids and tok.vocab["!"] in ids
    assert tok.encode(LONG_IN_VOCAB) == tok.encode_batch([LONG_IN_VOCAB])[0].tolist() == [
        tok.cls_id, tok.unk_id, tok.sep_id]
    assert tok.encode_batch(["r" * 100])[0].tolist() == [tok.cls_id, tok.vocab["r" * 100],
                                                         tok.sep_id]
    assert max(len(tok.encode(t)) for t in _corpus("mixed")) > 513


@pytest.mark.parametrize("max_len", [None, 4, 16])
@pytest.mark.parametrize("other", NOT_ASCII.values(), ids=NOT_ASCII.keys())
def test_a_text_that_is_not_ascii_takes_the_exact_path(other, max_len):
    tok = WordPieceTokenizer({t: i for i, t in enumerate([*VOCAB, "cafe", "\u65e5", "\u2026", "naive"])})
    ascii_texts = _corpus("mixed")[:5]
    texts = [ascii_texts[0], other, *ascii_texts[1:3], other + " the", *ascii_texts[3:]]
    got, n, fast = _counted(tok, texts, max_len)
    assert (n, fast) == (7, 5)  # the share is under 1.0, the ids are equal
    assert got.tolist() == _exact(tok, texts, max_len).tolist()
    got, n, fast = _counted(tok, [other], max_len)
    assert (n, fast) == (1, 0)
    assert got[0].tolist() == tok.encode(other, max_len)


def test_an_empty_batch_counts_nothing():
    tok = WordPieceTokenizer({t: i for i, t in enumerate(VOCAB)})
    got, n, fast = _counted(tok, [], 16)
    assert got.shape == (0, 0) and (n, fast) == (0, 0)


def test_the_generated_vocabulary():
    """The vocabulary of an encoder seeded rather than loaded: [PAD] at 0,
    every id under the encoder's vocabulary, a token a character, no [UNK]
    for printable ASCII, and the lane gives ``encode``'s ids."""
    for size in (64, 109, 128, 30522):
        vocab = WordPieceTokenizer.generated(size).vocab
        assert vocab["[PAD]"] == 0 and sorted(vocab.values()) == list(range(min(size, 109)))
    tok = WordPieceTokenizer.generated(30522)
    v = tok.vocab
    assert tok.pad_id == 0
    assert tok.encode("hello") == [v["[CLS]"], v["h"], v["##e"], v["##l"], v["##l"], v["##o"],
                                   v["[SEP]"]]
    rng = random.Random("generated")
    printable = [chr(c) for c in range(32, 127)] + SPACES
    texts = ["".join(rng.choices(printable, k=rng.randint(0, 400))) for _ in range(200)]
    texts += ["Hello, World! 2024-10-16", string.punctuation, string.ascii_letters]
    got, n, fast = _counted(tok, texts, None)
    assert n == fast == len(texts)
    assert got.tolist() == _exact(tok, texts, None).tolist()
    assert tok.unk_id not in got
