"""WordPiece tokenizer — the real vocab-driven tokenizer for pretrained
MiniLM/BERT-class embedders.

Re-implements BERT's tokenization pipeline (basic tokenization: lowercase /
accent stripping / punctuation splitting / CJK spacing, then greedy
longest-match-first WordPiece with ``##`` continuations) so pretrained
checkpoints see exactly the token ids they were trained with. Verified
against ``transformers.BertTokenizer`` over a shared vocab in
``tests/test_embedder_pretrained.py``. Replaces the hashing stand-in that
``models/embedder.py`` shipped before pretrained weights existed
(reference: ``python/pathway/xpacks/llm/embedders.py:217``
SentenceTransformerEmbedder's underlying tokenizer).

Two routes to the same ids. ``encode`` is the exact path: it asks
``unicodedata`` about every character (accents, CJK, Unicode punctuation and
controls). ``encode_batch`` sends a text for which ``str.isascii()`` holds down
the ASCII lane instead, where those classes are decidable without
``unicodedata``: the controls BERT deletes go, the rest is lower-cased and cut
at white space and punctuation, a word is looked up whole in the vocabulary
first (the first candidate of the greedy longest match, so the answer whenever
it is there), and the greedy match runs only for a word that misses. The lane
is made of calls that run in C over a whole text (``str.lower``,
``str.translate``, ``str.split``, one compiled ``re`` where the text holds
punctuation). Any other text of the batch takes ``encode``, text by text.
Which route a text took is counted (``embed_tokenize_texts_total``,
``embed_tokenize_fast_texts_total`` in ``serve/stats.py``). Nothing is
remembered between calls: no table from a text, or from anything longer than a
word, to ids; the vocabulary is the only table
(``tests/test_wordpiece_fast.py`` holds the lane to ``encode``).
"""

from __future__ import annotations

import re
import unicodedata

import numpy as np

__all__ = ["WordPieceTokenizer"]


def _is_punctuation(ch: str) -> bool:
    cp = ord(ch)
    # ASCII ranges BERT treats as punctuation even when unicodedata does not
    if (33 <= cp <= 47) or (58 <= cp <= 64) or (91 <= cp <= 96) or (123 <= cp <= 126):
        return True
    return unicodedata.category(ch).startswith("P")


def _is_cjk(cp: int) -> bool:
    return (
        0x4E00 <= cp <= 0x9FFF or 0x3400 <= cp <= 0x4DBF
        or 0x20000 <= cp <= 0x2A6DF or 0x2A700 <= cp <= 0x2B73F
        or 0x2B740 <= cp <= 0x2B81F or 0x2B820 <= cp <= 0x2CEAF
        or 0xF900 <= cp <= 0xFAFF or 0x2F800 <= cp <= 0x2FA1F
    )


def _is_control(ch: str) -> bool:
    if ch in ("\t", "\n", "\r"):
        return False
    return unicodedata.category(ch).startswith("C")


#: the ASCII characters ``_clean`` deletes: the controls (category Cc) but
#: \t \n \r. \x1c-\x1f are among them, which ``str.split`` would otherwise
#: take for white space
_ASCII_DROPPED = dict.fromkeys((*range(0, 9), 11, 12, *range(14, 32), 127))
#: what is left of an ASCII text after that: white space, runs of letters and
#: digits, and BERT's ASCII punctuation (33-47, 58-64, 91-96, 123-126: every
#: other printable character), each a token of its own
_ASCII_TOKENS = re.compile(r"[A-Za-z0-9]+|[^A-Za-z0-9\s]")


class WordPieceTokenizer:
    def __init__(
        self,
        vocab: dict[str, int],
        *,
        lowercase: bool = True,
        unk_token: str = "[UNK]",
        cls_token: str = "[CLS]",
        sep_token: str = "[SEP]",
        pad_token: str = "[PAD]",
        max_chars_per_word: int = 100,
    ):
        self.vocab = vocab
        self.lowercase = lowercase
        self.unk_id = vocab[unk_token]
        self.cls_id = vocab[cls_token]
        self.sep_id = vocab[sep_token]
        self.pad_id = vocab.get(pad_token, 0)
        self.max_chars_per_word = max_chars_per_word

    @classmethod
    def from_vocab_file(cls, path: str, **kwargs) -> "WordPieceTokenizer":
        vocab: dict[str, int] = {}
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f):
                tok = line.rstrip("\n")
                if tok:
                    vocab[tok] = i
        return cls(vocab, **kwargs)

    # -- basic tokenization (BERT BasicTokenizer) --------------------------

    def _clean(self, text: str) -> str:
        out = []
        for ch in text:
            cp = ord(ch)
            if cp == 0 or cp == 0xFFFD or _is_control(ch):
                continue
            if ch.isspace():
                out.append(" ")
            elif _is_cjk(cp):
                out.append(f" {ch} ")
            else:
                out.append(ch)
        return "".join(out)

    def _split_word(self, word: str) -> list[str]:
        if self.lowercase:
            word = word.lower()
            word = "".join(
                ch for ch in unicodedata.normalize("NFD", word)
                if unicodedata.category(ch) != "Mn"  # strip accents
            )
        pieces: list[str] = []
        current: list[str] = []
        for ch in word:
            if _is_punctuation(ch):
                if current:
                    pieces.append("".join(current))
                    current = []
                pieces.append(ch)
            else:
                current.append(ch)
        if current:
            pieces.append("".join(current))
        return pieces

    def basic_tokenize(self, text: str) -> list[str]:
        out: list[str] = []
        for word in self._clean(text).split():
            out.extend(self._split_word(word))
        return out

    # -- WordPiece (greedy longest-match-first) ----------------------------

    def wordpiece(self, token: str) -> list[int]:
        if len(token) > self.max_chars_per_word:
            return [self.unk_id]
        ids: list[int] = []
        start = 0
        while start < len(token):
            end = len(token)
            cur: int | None = None
            while start < end:
                piece = token[start:end]
                if start > 0:
                    piece = "##" + piece
                pid = self.vocab.get(piece)
                if pid is not None:
                    cur = pid
                    break
                end -= 1
            if cur is None:
                return [self.unk_id]  # whole word becomes [UNK]
            ids.append(cur)
            start = end
        return ids

    # -- public API --------------------------------------------------------

    def encode(self, text: str, max_len: int | None = None) -> list[int]:
        """[CLS] pieces [SEP], truncated to max_len total."""
        ids = [self.cls_id]
        for token in self.basic_tokenize(text):
            ids.extend(self.wordpiece(token))
        limit = (max_len - 1) if max_len is not None else len(ids) + 1
        ids = ids[:limit]
        ids.append(self.sep_id)
        return ids

    def _encode_ascii(self, text: str, max_len: int | None) -> list[int]:
        """``encode`` of a text for which ``str.isascii()`` holds, by calls
        that run over the whole text: the same ids."""
        if self.lowercase:
            text = text.lower()
        text = text.translate(_ASCII_DROPPED)
        tokens = text.split()
        if not "".join(tokens).isalnum():  # punctuation somewhere: split it off
            tokens = _ASCII_TOKENS.findall(text)
        ids = list(map(self.vocab.get, tokens))
        if None in ids or max(map(len, tokens), default=0) > self.max_chars_per_word:
            hits, ids = ids, []
            for token, hit in zip(tokens, hits):
                if hit is None or len(token) > self.max_chars_per_word:
                    ids.extend(self.wordpiece(token))
                else:
                    ids.append(hit)
        ids.insert(0, self.cls_id)
        limit = (max_len - 1) if max_len is not None else len(ids) + 1
        ids = ids[:limit]
        ids.append(self.sep_id)
        return ids

    def encode_batch(self, texts: list[str], max_len: int | None = None) -> np.ndarray:
        """int32 [batch, longest text of the batch], each text cut at
        ``max_len`` tokens, right-padded with pad_id: no wider than the batch
        needs, so short questions do not carry a model's whole positions.
        An ASCII text takes the lane, any other the exact path (module
        docstring); both are counted."""
        from ..serve.stats import bump

        rows, fast = [], 0
        for text in texts:
            if text.isascii():
                rows.append(self._encode_ascii(text, max_len))
                fast += 1
            else:
                rows.append(self.encode(text, max_len))
        out = np.full((len(rows), max(map(len, rows), default=0)), self.pad_id, dtype=np.int32)
        for i, ids in enumerate(rows):
            out[i, : len(ids)] = ids
        bump("embed_tokenize_texts_total", len(texts))
        bump("embed_tokenize_fast_texts_total", fast)
        return out
