"""Everything a run makes from ``--seed`` that is not a model's: vocabulary,
texts, index rows, the documents' folders and the writer's schedule (a model's
weights are its family's, ``models/<model_type>.py``, drawn from ``stream``).
numpy only; imports nothing of the program and nothing of JAX. The same seed
gives the same bytes whatever the number of threads, because every block draws
from a generator of its own.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from lib import family

#: rows made (and fed to the connector) at a time
BLOCK_ROWS = 65_536
_LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))


def threads() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def stream(seed: int, *path: int) -> np.random.Generator:
    """An independent generator for one named part of the run."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *path]))


# -- vocabulary and texts ---------------------------------------------------


def vocabulary(seed: int, size: int, fam) -> tuple[list[str], list[str]]:
    """(vocab.txt lines, the whole words among them). Ids below the family's
    ``FIRST_WORD_ID`` are its ``SPECIALS`` and ``[unusedN]``; then whole words
    of 3 to 9 letters; the last eighth are ``##`` pieces. ``fam`` is the
    model's family (``family.load``)."""
    rng = stream(seed, 1)
    n_pieces = size // 8
    n_words = size - fam.FIRST_WORD_ID - n_pieces
    words: list[str] = []
    seen: set[str] = set()
    while len(words) < n_words:
        lens = rng.integers(3, 10, size=n_words)
        chars = rng.integers(0, 26, size=(n_words, 9))
        for ln, row in zip(lens.tolist(), chars):
            w = "".join(_LETTERS[row[:ln]])
            if w not in seen:
                seen.add(w)
                words.append(w)
                if len(words) == n_words:
                    break
    pieces: list[str] = []
    while len(pieces) < n_pieces:
        ln = int(rng.integers(1, 4))
        p = "##" + "".join(_LETTERS[rng.integers(0, 26, size=ln)])
        if p not in seen:
            seen.add(p)
            pieces.append(p)
    lines = [fam.SPECIALS.get(i, f"[unused{i}]") for i in range(fam.FIRST_WORD_ID)]
    return lines + words + pieces, words


def make_texts(seed: int, part: int, words: list[str], count: int,
               lo: int, hi: int, mean: float) -> list[str]:
    """``count`` distinct texts of ``lo``..``hi`` whole words, the length
    geometric with the given mean (truncated). A word is one token."""
    rng = stream(seed, 2, part)
    p = 1.0 / max(mean - lo + 1.0, 1.0)
    out: list[str] = []
    seen: set[str] = set()
    while len(out) < count:
        ln = min(hi, lo + int(rng.geometric(p)) - 1)
        t = " ".join(words[i] for i in rng.integers(0, len(words), size=ln))
        if t not in seen:
            seen.add(t)
            out.append(t)
    return out


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def fixed_counts(total: int, weights: np.ndarray) -> np.ndarray:
    """``total`` split into parts in proportion to ``weights`` (largest
    remainders take what rounding leaves), each part at least 1: the same
    sizes for every seed."""
    if total < len(weights):
        raise ValueError(f"{total} cannot fill {len(weights)} parts")
    exact = np.asarray(weights, np.float64) * (total - len(weights))
    counts = np.floor(exact).astype(np.int64)
    left = total - len(weights) - int(counts.sum())
    counts[np.argsort(counts - exact, kind="stable")[:left]] += 1
    return counts + 1


def fixed_draws(count: int, weights: np.ndarray) -> np.ndarray:
    """``count`` draws from ``weights`` at the distribution's quantiles: the
    same multiset for every seed, for the caller to put in a seeded order."""
    u = (np.arange(count) + 0.5) / count
    return np.minimum(np.searchsorted(np.cumsum(weights), u), len(weights) - 1)


# -- folders ------------------------------------------------------------------


def doc_folders(seed: int, docs: int, metadata: dict | None) -> np.ndarray:
    """The folder (tenant) of every document, which it keeps through every
    version: folder sizes are the Zipf shares of ``tenant_zipf_s`` (folder 0
    the largest, the same sizes for every seed), and which documents a folder
    holds is a seeded draw. No ``metadata`` key: one folder holds them all."""
    if not metadata:
        return np.zeros(docs, np.int32)
    sizes = fixed_counts(docs, zipf_weights(int(metadata["tenants"]),
                                            float(metadata["tenant_zipf_s"])))
    folders = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    return folders[stream(seed, 6).permutation(docs)]


# -- index rows -------------------------------------------------------------


def unit_rows(seed: int, part: int, block: int, out: np.ndarray) -> None:
    """Fill ``out`` [rows, d] with isotropic unit vectors."""
    stream(seed, 4, part, block).standard_normal(out=out, dtype=np.float32)
    out /= np.sqrt(np.einsum("ij,ij->i", out, out))[:, None]


def make_rows(seed: int, n: int, dim: int) -> np.ndarray:
    """The base index: ``n`` seeded unit vectors, made in blocks in parallel."""
    data = np.empty((n, dim), np.float32)
    starts = range(0, n, BLOCK_ROWS)
    with ThreadPoolExecutor(threads()) as pool:
        list(pool.map(
            lambda s: unit_rows(seed, 0, s // BLOCK_ROWS,
                                data[s:min(s + BLOCK_ROWS, n)]),
            starts,
        ))
    return data


def near(center: np.ndarray, cosine: float, rng: np.random.Generator) -> np.ndarray:
    """Unit vectors at exactly ``cosine`` from the unit rows of ``center``:
    the center plus seeded noise made orthogonal to it."""
    center = np.atleast_2d(center).astype(np.float32)
    noise = rng.standard_normal(center.shape, dtype=np.float32)
    noise -= np.einsum("ij,ij->i", noise, center)[:, None] * center
    noise /= np.linalg.norm(noise, axis=1, keepdims=True)
    c = np.asarray(cosine, np.float32).reshape(-1, 1)
    return (c * center + np.sqrt(1.0 - c * c) * noise).astype(np.float32)


# -- tests/test_embedder_packed.py's entry points --------------------------------
# That test of the program, which a benchmark change may not edit, builds BERT
# model dicts by hand and calls these two and ``reference``'s three by these
# names. They name their family outright; no harness file calls them
# (``benchmark/tests/family_cases.py`` checks).


def make_vocab(seed: int, size: int) -> tuple[list[str], list[str]]:
    return vocabulary(seed, size, family.load({"model_type": "bert"}))


def make_state_dict(seed: int, model: dict) -> dict[str, np.ndarray]:
    return family.load({"model_type": "bert"}).make_weights(seed, model)
