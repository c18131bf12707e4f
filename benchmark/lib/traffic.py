"""The one general traffic generator. A mix is a data file of parameters
(``benchmark/traffic/<mix>.json``); everything a run sends is drawn from it and
the seed here. numpy only — both the load generator (parent) and the child
import it, and both derive the same plan from the same seed.

Rows are addressed (document, chunk, version); the row's primary key is
``doc * chunks + chunk`` and its text ``d<doc>c<chunk>v<ver>``, so a reply says
which version of which row it returned.

Scope is one more dimension of a deployment, in two keys that default to none.
A configuration's ``metadata`` (``tenants``, ``tenant_zipf_s``, ``path``) puts
every document in a folder (``datagen.doc_folders``) and writes the folder
into the row's ``path``. A mix's ``scope`` says how a request is confined to a
folder:

- ``field``: ``filepath_globpattern`` or ``metadata_filter``, the key of the
  request's body that carries it; ``template``: its value, ``{tenant}`` filled
  in (``"t{tenant}/*"``; for ``metadata_filter`` an expression of the grammar
  ``pathway_tpu/utils/filters.py`` parses, ``"globmatch('t{tenant}/*', path)"``);
- ``bind``: ``client`` (a client keeps one folder, the clients' folders being
  the quantiles of Zipf ``client_zipf_s`` over the folders by size, in a seeded
  order) or ``request`` (a new draw from that Zipf every request);
- ``share_unscoped``: the share of requests sent with no filter, evenly spaced
  in a client's sequence from a seeded phase;
- ``passage_in_scope``: the share of pool texts whose planted passage lies in
  the folder the text is asked in; the others' lie in another folder, so a
  scoped answer is filler there and an ignored filter changes the answer.

Every pool text has a home folder and is asked only under it. The harness never
parses a filter: it knows a request's folder because it drew it.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

from . import datagen

_TEXT_RE = re.compile(r"^d(\d+)c(\d+)v(\d+)$")


def row_text(doc: int, chunk: int, ver: int) -> str:
    return f"d{doc}c{chunk}v{ver}"


def row_metadata(doc: int, ver: int, folder: int = 0, path: str = "d{doc}") -> dict:
    """A row's metadata; with no ``metadata`` in the configuration ``path`` is
    ``d<doc>``, as every cell before scope had it."""
    return {"path": path.format(tenant=folder, doc=doc), "ver": ver}


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
    popularity over a seeded order; a text's ``tokens`` are its words and the
    ``framing`` tokens its model's family adds (``FRAMING``)."""

    def __init__(self, seed: int, words: list[str], q: dict, framing: int):
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
        self.tokens = lengths + framing
        self.weights = datagen.zipf_weights(q["pool"], q["zipf_s"])
        self.seed = seed

    def client_sequence(self, client: int, count: int = 1 << 16) -> np.ndarray:
        """Query ids one closed-loop client sends, in order."""
        rng = datagen.stream(self.seed, 11, client)
        return rng.choice(len(self.texts), size=count, p=self.weights)


SCOPE_FIELDS = ("filepath_globpattern", "metadata_filter")


class Scope:
    """A scoped mix's draws: each pool text's home folder, each client's
    folder, each request's folder and text, where the passages go, and the
    rows a folder holds. Parent and child build it from the same seed."""

    def __init__(self, seed: int, scope: dict, folder_of_doc: np.ndarray,
                 pool: QueryPool, clients: int, chunks: int,
                 plan: "WriterPlan | None" = None):
        if scope["field"] not in SCOPE_FIELDS:
            raise ValueError(f"scope.field is one of {SCOPE_FIELDS}, not {scope['field']!r}")
        if scope["bind"] not in ("client", "request"):
            raise ValueError(f"scope.bind is client or request, not {scope['bind']!r}")
        self.seed, self.field, self.template = seed, scope["field"], scope["template"]
        self.per_request = scope["bind"] == "request"
        self.share_unscoped = float(scope["share_unscoped"])
        self.folder_of_doc, self.chunks = folder_of_doc, chunks
        n = self.folders = int(folder_of_doc.max()) + 1
        n_pool = len(pool.texts)
        if not 2 <= n <= n_pool:
            raise ValueError(f"a scoped mix needs 2 or more folders (the "
                             f"configuration's metadata.tenants) and a pool of at "
                             f"least as many texts; have {n} and {n_pool}")
        #: live rows a folder holds before the window, and the fewest it can
        #: hold inside it (a delete takes a document's chunks out for a while)
        self.rows_in = np.bincount(folder_of_doc, minlength=n) * chunks
        deleted = {c.doc for c in plan.commits if c.ver is None} if plan else set()
        self.rows_least = self.rows_in - chunks * np.bincount(
            folder_of_doc[sorted(deleted)], minlength=n)
        self.weights = datagen.zipf_weights(n, float(scope["client_zipf_s"]))
        rng = datagen.stream(seed, 12)
        #: a text's home folder: the folders take turns over a seeded order
        self.home = np.empty(n_pool, np.int64)
        self.home[rng.permutation(n_pool)] = np.arange(n_pool) % n
        #: texts whose passage lies inside their home folder
        self.passage_inside = np.zeros(n_pool, bool)
        inside = int(round(float(scope["passage_in_scope"]) * n_pool))
        self.passage_inside[rng.permutation(n_pool)[:inside]] = True
        self.client_folder = rng.permutation(datagen.fixed_draws(clients, self.weights))
        #: per folder: its texts, most popular first, and their cumulated share
        self._texts = [np.flatnonzero(self.home == f) for f in range(n)]
        self._cum = [np.cumsum(pool.weights[t]) / pool.weights[t].sum() for t in self._texts]

    def client_requests(self, client: int, count: int = 1 << 16):
        """(query ids, folders) one closed-loop client sends, in order; folder
        -1 is a request with no filter (its text is still its folder's)."""
        rng = datagen.stream(self.seed, 13, client)
        if self.per_request:
            folders = rng.choice(self.folders, size=count, p=self.weights)
        else:
            folders = np.full(count, self.client_folder[client], np.int64)
        u = rng.random(count)
        qids = np.empty(count, np.int64)
        for f in np.unique(folders):
            at = np.flatnonzero(folders == f)
            pick = np.minimum(np.searchsorted(self._cum[f], u[at]), len(self._cum[f]) - 1)
            qids[at] = self._texts[f][pick]
        steps = (np.arange(count + 1) + rng.random()) * self.share_unscoped
        folders[np.floor(steps[1:]) > np.floor(steps[:-1])] = -1
        return qids, folders

    def body_fields(self, folder: int) -> dict:
        """What the scope adds to a request's body."""
        return {self.field: self.template.format(tenant=folder)}

    def mask(self, folder: int) -> np.ndarray:
        """The rows of a folder, over the base rows: what a request confined
        to it may answer."""
        return np.repeat(self.folder_of_doc == folder, self.chunks)

    def rows_wanted(self, folder: int, k: int) -> tuple[int, int]:
        """(fewest, most) rows of a whole reply: ``k`` where the folder holds
        ``k`` live rows, else every live row of it, no more."""
        return min(k, int(self.rows_least[folder])), min(k, int(self.rows_in[folder]))

    def place_passages(self, free_docs: list[int]) -> list[int]:
        """The document each pool text's passage goes on, taken in order from
        ``free_docs``: one of the text's home folder for ``passage_inside``,
        else one of another folder."""
        queues: list[list[int]] = [[] for _ in range(self.folders)]
        for d in reversed(free_docs):
            queues[self.folder_of_doc[d]].append(d)
        out = []
        for i, home in enumerate(self.home.tolist()):
            f = home if self.passage_inside[i] else (home + 1 + i % (self.folders - 1)) % self.folders
            if not queues[f]:
                raise ValueError(f"folder {f} has no document left for a passage")
            out.append(queues[f].pop())
        return out


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
