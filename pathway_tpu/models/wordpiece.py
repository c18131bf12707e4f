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
"""

from __future__ import annotations

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

    def encode_batch(self, texts: list[str], max_len: int | None = None) -> np.ndarray:
        """int32 [batch, longest text of the batch], each text cut at
        ``max_len`` tokens, right-padded with pad_id: no wider than the batch
        needs, so short questions do not carry a model's whole positions."""
        rows = [self.encode(t, max_len) for t in texts]
        out = np.full((len(rows), max(map(len, rows), default=0)), self.pad_id,
                      dtype=np.int32)
        for i, ids in enumerate(rows):
            out[i, : len(ids)] = ids
        return out
