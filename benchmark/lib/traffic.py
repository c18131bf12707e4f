"""The one general traffic generator. A mix is a data file of parameters
(``benchmark/traffic/<mix>.json``); everything a run sends is drawn from it and
the seed here. numpy only — both the load generator (parent) and the child
import it, and both derive the same plan from the same seed.

Rows are addressed (document, chunk, version); the row's primary key is
``doc * chunks + chunk`` and its text ``d<doc>c<chunk>v<ver>``, so a reply says
which version of which row it returned.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

from . import datagen

_TEXT_RE = re.compile(r"^d(\d+)c(\d+)v(\d+)$")


def row_text(doc: int, chunk: int, ver: int) -> str:
    return f"d{doc}c{chunk}v{ver}"


def parse_row_text(text: str) -> tuple[int, int, int] | None:
    m = _TEXT_RE.match(text) if isinstance(text, str) else None
    return tuple(int(g) for g in m.groups()) if m else None


def fixed_lengths(count: int, lo: int, hi: int, mean: float) -> np.ndarray:
    """The same multiset of lengths for every seed: the truncated geometric's
    quantiles, so that seeds differ in order and words, not in work."""
    p = 1.0 / max(mean - lo + 1.0, 1.0)
    u = (np.arange(count) + 0.5) / count
    return np.minimum(hi, lo + np.floor(np.log1p(-u) / np.log1p(-p)).astype(int))


class QueryPool:
    """``pool`` distinct query texts of whole vocabulary words, with a Zipf
    popularity over a seeded order."""

    def __init__(self, seed: int, words: list[str], q: dict):
        rng = datagen.stream(seed, 10)
        lengths = rng.permutation(
            fixed_lengths(q["pool"], q["words_min"], q["words_max"], q["words_mean"])
        )
        self.texts: list[str] = []
        seen: set[str] = set()
        for ln in lengths.tolist():
            while True:
                t = " ".join(words[i] for i in rng.integers(0, len(words), size=ln))
                if t not in seen:
                    break
            seen.add(t)
            self.texts.append(t)
        self.tokens = lengths + 2  # [CLS] and [SEP]
        self.weights = datagen.zipf_weights(q["pool"], q["zipf_s"])
        self.seed = seed

    def client_sequence(self, client: int, count: int = 1 << 16) -> np.ndarray:
        """Query ids one closed-loop client sends, in order."""
        rng = datagen.stream(self.seed, 11, client)
        return rng.choice(len(self.texts), size=count, p=self.weights)


@dataclasses.dataclass(frozen=True)
class Commit:
    index: int
    due: float          # seconds after the window's start
    kind: str           # replace | delete | add
    doc: int
    ver: int | None     # version this commit writes (None: delete)
    old_ver: int | None  # version this commit retracts (None: add)


class WriterPlan:
    """The writer's schedule: ``commits_per_s`` commits a second, each one
    document, kinds in the mix's pattern, the document drawn Zipf over a seeded
    order of documents. The first chunk of every version written is a rung of
    the probe ladder: it lies in the plane of the probe text's own direction at
    a cosine that rises with the commit's index, so one retrieve of the probe
    text shows the newest commits visible, newest first."""

    def __init__(self, seed: int, writer: dict, docs: int, chunks: int,
                 seconds: float):
        self.seed, self.chunks, self.docs = seed, chunks, docs
        self.rate = float(writer["commits_per_s"])
        n = int(self.rate * seconds)
        self.step = min(float(writer["ladder_step"]), 0.5 / max(n + 16, 1))
        rng = datagen.stream(seed, 20)
        order = rng.permutation(docs)
        self.base_ladder = [int(d) for d in order[-int(writer["ladder_base"]):]]
        hot = order[:docs - len(self.base_ladder)]
        weights = datagen.zipf_weights(len(hot), float(writer["doc_zipf_s"]))
        draws = iter(hot[rng.choice(len(hot), size=4 * n + 64, p=weights)].tolist())
        pattern = list(writer["pattern"])
        version: dict[int, int] = {}
        deleted: list[int] = []
        written: list[int] = []
        self.commits: list[Commit] = []
        for i in range(n):
            kind = pattern[i % len(pattern)] if i < n - 1 else "replace"
            if kind == "add" and not deleted:
                kind = "replace"
            if kind == "delete":
                live = [d for d in written[:-1] if d not in deleted]
                if not live:
                    kind = "replace"
            if kind == "replace":
                doc = next(d for d in draws if d not in deleted)
                old = version.get(doc, 0)
                version[doc] = old + 1
                written.append(doc)
                c = Commit(i, i / self.rate, kind, doc, old + 1, old)
            elif kind == "delete":
                doc = live[-1]
                deleted.append(doc)
                c = Commit(i, i / self.rate, kind, doc, None, version.get(doc, 0))
            else:
                doc = deleted.pop(0)
                version[doc] = version.get(doc, 0) + 1
                written.append(doc)
                c = Commit(i, i / self.rate, kind, doc, version[doc], None)
            self.commits.append(c)
        #: (doc, ver) -> index of the commit that wrote it / retired it
        self.written_by = {(c.doc, c.ver): c.index for c in self.commits
                           if c.ver is not None}
        self.retired_by = {(c.doc, c.old_ver): c.index for c in self.commits
                           if c.old_ver is not None}
        self.touched = {c.doc for c in self.commits}
        self.final_rows = docs * chunks - chunks * len(deleted)

    def rung_cosine(self, commit_index: int) -> float:
        """Cosine of a commit's ladder row to the probe direction; base rungs
        take indices -1, -2, ... and lie below every commit's."""
        return 1.0 - (len(self.commits) - 1 - commit_index) * self.step

    def ladder_row(self, commit_index: int, u: np.ndarray, w: np.ndarray) -> np.ndarray:
        c = self.rung_cosine(commit_index)
        return (c * u + np.sqrt(max(0.0, 1.0 - c * c)) * w).astype(np.float32)

    def version_rows(self, commit: Commit, u: np.ndarray, w: np.ndarray) -> np.ndarray:
        """The ``chunks`` vectors of the version a commit writes."""
        rows = np.empty((self.chunks, len(u)), np.float32)
        datagen.unit_rows(self.seed, 5, commit.index, rows)
        rows[0] = self.ladder_row(commit.index, u, w)
        return rows


def probe_direction(e_probe: np.ndarray, axis: np.ndarray, seed: int):
    """(u, w): the probe embedding's part orthogonal to the cone's axis, and a
    seeded unit vector orthogonal to both — the ladder's plane."""
    u = e_probe - (e_probe @ axis) * axis
    u /= np.linalg.norm(u)
    w = datagen.stream(seed, 21).standard_normal(len(u)).astype(np.float32)
    for b in (axis, u):
        w -= (w @ b) * b
    w /= np.linalg.norm(w)
    return u.astype(np.float32), w.astype(np.float32)
