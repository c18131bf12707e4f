"""The child: the one process of a run that touches JAX and holds the chip.

It builds the deployment through the entry points a user calls —
``pw.io.python.read`` -> ``DocumentStore(vector_column=...)`` with
``BruteForceKnnFactory(dimensions, reserved_space, embedder)`` ->
``DocumentStoreServer.run()`` — feeds the index, warms the cell's shapes, says
it is ready, runs the writer and the profiler inside the window, and after the
window frees the program's state and runs the plain reference over the sample
of replies the parent hands back.

Talks to the parent in lines: JSON objects on standard output (one ``event``
each), commands on standard input. Started by ``run.py``; sets no
``PATHWAY_*`` variable. It ends with its parent, wherever it is
(``die_with_parent``), and where it fails it says why (the ``failed`` event)
and leaves within seconds, whatever its threads are waiting for.
"""

from __future__ import annotations

import ctypes
import gc
import glob
import json
import os
import resource
import shutil
import signal
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import check, datagen, files, peaks, spans as spans_mod, traffic  # noqa: E402

NO_CHIP_EXIT = 3
#: a scoped warm-up ends after this many sweeps even if the last still compiled
WARM_SWEEPS = 4
#: and after so many seconds. The contract gives a run whose programs are in
#: the cache 360 s to exit, and the large configuration's takes 165-175 s of
#: them with no filtered search to warm (set-up 80-86 s, ramp and window 54 s,
#: close and reference up to 30 s; ``README.md``, ledger PR 32): 360 - 175 = 185,
#: rounded down. A cell whose filtered searches take longer to warm has no
#: set-up a check can pay. At ``warm_batch_max`` 16 it is 0.66 s a request.
SCOPED_WARM_BUDGET_S = 180.0
PR_SET_PDEATHSIG = 1  # <sys/prctl.h>


def say(event: str, **facts) -> None:
    print(json.dumps({"event": event, **facts}), flush=True)


def log(msg: str) -> None:
    print(f"[child {time.monotonic():.1f}] {msg}", file=sys.stderr, flush=True)


def die_with_parent(parent_pid: int | None) -> None:
    """Ask the kernel for a ``SIGKILL`` when the parent dies, wherever this
    process then is (the index build, the warm-up, the reference): ``run.py``
    gives the child a session of its own, so no signal sent to the parent or to
    its group reaches it, and a ``SIGKILL`` runs no ``finally`` there. The
    parent's main thread is what started us, so it is the process's death that
    counts. Then see that the parent named in the spec is still the parent: if
    it died before the request was in, nothing would tell us."""
    prctl = ctypes.CDLL(None, use_errno=True).prctl
    prctl.argtypes, prctl.restype = [ctypes.c_int] + [ctypes.c_ulong] * 4, ctypes.c_int
    if prctl(PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0):
        log(f"prctl(PR_SET_PDEATHSIG) failed: errno {ctypes.get_errno()}")
    if parent_pid is not None and os.getppid() != parent_pid:
        log(f"the parent (pid {parent_pid}) is gone already; ending")
        os._exit(1)


class Compiles:
    """XLA compilations of this process, from ``jax.monitoring`` (copied from
    ``chip_smoke.py::PhaseLog``): one duration event per program handed to the
    backend, cache hit or not, and one event per cache hit."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.events: list[tuple[float, float]] = []  # (when, seconds)
        self.hits: list[float] = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits.append(time.monotonic())

    def _on_secs(self, event: str, secs: float, **_: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.events.append((time.monotonic(), secs))

    def between(self, t0: float, t1: float) -> dict:
        with self._lock:
            n = sum(t0 <= t < t1 for t, _ in self.events)
            h = sum(t0 <= t < t1 for t in self.hits)
            s = sum(d for t, d in self.events if t0 <= t < t1)
        return {"programs": n, "cache_hits": h, "compiled": n - h, "seconds": s}


class GcPauses:
    """The interpreter's garbage collections in this process, by generation,
    timed from ``gc.callbacks``: a full collection walks every container the
    index build left behind and stops every thread while it does."""

    def __init__(self):
        self.pauses: list[tuple[float, float, int]] = []  # (start, seconds, generation)
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.pauses.append((self._t, time.monotonic() - self._t, info["generation"]))

    def between(self, t0: float, t1: float) -> dict:
        got = [(t, d, g) for t, d, g in list(self.pauses) if t0 <= t < t1]
        full = [(round(t - t0, 3), round(d, 4)) for t, d, g in got if g == 2]
        return {"collections": len(got), "seconds": sum(d for _, d, _ in got),
                "full": full}


class Deployment:
    """The data of one run, all from the seed: vocabulary, pool, weights, rows.
    What is a model's comes from its family (``fam``, the file ``run.py``
    found for the configuration's ``model_type``)."""

    def __init__(self, spec: dict):
        self.spec = spec
        cfg, mix, seed = spec["config"], spec["mix"], spec["seed"]
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.fam = fam = files.load_module(spec["family"])
        self.model = {k: cfg[k] for k in fam.MODEL_KEYS}
        self.dim = cfg["hidden_size"]
        self.chunks = cfg["chunks_per_doc"]
        self.n_rows = cfg["rows"]
        self.docs = self.n_rows // self.chunks
        self.k = cfg["k"]
        #: tokens of the mix's longest query, framed as its family frames it:
        #: the width the reference pads to (padding is masked, so any width
        #: that holds the longest gives the same vectors)
        self.query_width = int(mix["query"]["words_max"]) + fam.FRAMING
        self.vocab_lines, self.words = datagen.vocabulary(seed, cfg["vocab_size"], fam)
        self.vocab_index = {w: i for i, w in enumerate(self.vocab_lines)}
        self.pool = traffic.QueryPool(seed, self.words, mix["query"], fam.FRAMING)
        #: the probe text is the candidate whose embedding stands farthest off
        #: the cone's axis (``plant`` chooses; the parent is told when ready)
        self.probe_candidates = datagen.make_texts(seed, 9, self.words, 16, 6, 6, 6)
        self.probe_text = self.probe_candidates[0]
        self.plan = (
            traffic.WriterPlan(seed, mix["writer"], self.docs, self.chunks,
                               spec["seconds"])
            if mix.get("writer") else None
        )
        #: every document's folder and the template of a row's ``path``; with
        #: no ``metadata`` in the configuration one folder and ``d<doc>``
        meta = cfg.get("metadata") or {}
        self.folder_of_doc = datagen.doc_folders(seed, self.docs, meta)
        #: a document's path, made once: its rows' metadata share it
        self.doc_paths = [
            traffic.row_metadata(d, 0, f, meta.get("path", "d{doc}"))["path"]
            for d, f in enumerate(self.folder_of_doc.tolist())]
        self.scope = (
            traffic.Scope(seed, mix["scope"], self.folder_of_doc, self.pool,
                          int(mix["clients"]), self.chunks, self.plan)
            if mix.get("scope") else None
        )
        self.rows: np.ndarray | None = None
        self.weights: dict | None = None
        self.u = self.w = None
        self._version_cache: dict[int, np.ndarray] = {}

    def make_weights(self) -> None:
        """On the host, in threads (numpy frees the interpreter lock), while
        the main thread imports JAX."""
        self.weights = self.fam.make_weights(self.seed, self.model)

    def make_rows(self) -> None:
        """The base rows, likewise, while the reference embeds the pool."""
        self.rows = datagen.make_rows(self.seed, self.n_rows, self.dim)

    def embed_pool(self, ref_params) -> None:
        """The plain reference's float32 vectors of the pool and the probe
        candidates, which ``plant`` writes passages and ladder rungs near: the
        data is a function of the seed alone and of nothing the program
        computes."""
        from lib import reference

        texts = self.pool.texts + self.probe_candidates
        ids = reference.token_ids(texts, self.vocab_index, self.query_width, self.fam)
        self._emb = self.fam.encode(ref_params, ids, self.model)

    def plant(self) -> None:
        rng = datagen.stream(self.seed, 30)
        n_pool, emb = len(self.pool.texts), self._emb
        axis = emb[:n_pool].mean(0)
        axis /= np.linalg.norm(axis)
        own = emb - (emb @ axis)[:, None] * axis[None, :]
        norms = np.linalg.norm(own, axis=1)
        own /= norms[:, None]
        self.own_share = float(np.median(norms[:n_pool]))
        best = int(np.argmax(norms[n_pool:]))
        self.probe_text = self.probe_candidates[best]
        self.probe_share = float(norms[n_pool + best])
        # one passage row for each pool text, on rows the writer never touches
        banned = set(self.plan.touched) | set(self.plan.base_ladder) if self.plan else set()
        free = [int(d) for d in rng.permutation(self.docs) if int(d) not in banned]
        docs = self.scope.place_passages(free) if self.scope else free[:n_pool]
        chunk = rng.integers(0, self.chunks, size=len(docs))
        self.passage_rows = np.asarray(docs) * self.chunks + chunk
        self.rows[self.passage_rows] = datagen.near(
            own[:n_pool], float(self.mix["passage_cosine"]), rng)
        if self.plan:
            self.u, self.w = traffic.probe_direction(emb[n_pool + best], axis, self.seed)
            for j, doc in enumerate(self.plan.base_ladder):
                self.rows[doc * self.chunks] = self.plan.ladder_row(-1 - j, self.u, self.w)

    def row_vector(self, doc: int, chunk: int, ver: int):
        """The vector that version of that row was written with, or None."""
        if not (0 <= doc < self.docs and 0 <= chunk < self.chunks):
            return None
        if ver == 0:
            return self.rows[doc * self.chunks + chunk]
        ix = self.plan.written_by.get((doc, ver)) if self.plan else None
        if ix is None:
            return None
        if ix not in self._version_cache:
            self._version_cache[ix] = self.plan.version_rows(
                self.plan.commits[ix], self.u, self.w)
        return self._version_cache[ix][chunk]

    def columns(self, doc_chunk_ver: list[tuple[int, int, int]], vecs) -> dict:
        """Connector columns for the given rows."""
        return {
            "id": np.asarray([d * self.chunks + c for d, c, _ in doc_chunk_ver],
                             dtype=np.int64),
            "data": [traffic.row_text(d, c, v) for d, c, v in doc_chunk_ver],
            "_metadata": [{"path": self.doc_paths[d], "ver": v} for d, _, v in doc_chunk_ver],
            "vec": list(vecs),
        }


def build_server(dep: Deployment, port: int, state: dict):
    import jax.numpy as jnp

    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dep.cfg["compute_dtype"]]
    embedder = dep.fam.build_embedder(dep.weights, dep.model, dep.vocab_index, dtype)

    class Feed(pw.io.python.ConnectorSubject):
        """The source: the base rows in blocks, then the writer's commits on
        their schedule once the window has opened."""

        def run(self) -> None:
            n, chunks = dep.n_rows, dep.chunks
            for s in range(0, n, datagen.BLOCK_ROWS):
                e = min(s + datagen.BLOCK_ROWS, n)
                ids = range(s, e)
                self.next_batch(dep.columns(
                    [(i // chunks, i % chunks, 0) for i in ids], dep.rows[s:e]))
                self.commit()
            state["fed"].set()
            state["window"].wait()
            if dep.plan is None or state["stop"].is_set():
                state["stop"].wait()
                return
            t0 = state["t0"]
            for c in dep.plan.commits:
                delay = t0 + c.due - time.monotonic()
                if delay > 0 and state["stop"].wait(delay):
                    return
                rows, vecs, diffs = [], [], []
                if c.old_ver is not None:
                    rows += [(c.doc, ch, c.old_ver) for ch in range(chunks)]
                    vecs += [dep.row_vector(c.doc, ch, c.old_ver) for ch in range(chunks)]
                    diffs += [-1] * chunks
                if c.ver is not None:
                    rows += [(c.doc, ch, c.ver) for ch in range(chunks)]
                    vecs += list(dep.plan.version_rows(c, dep.u, dep.w))
                    diffs += [1] * chunks
                self.next_batch(dep.columns(rows, vecs), np.asarray(diffs, np.int64))
                self.commit()
                state["committed"].append((c.index, time.monotonic() - t0 - c.due))
            state["written"].set()
            state["stop"].wait()

    schema = pw.schema_builder({
        "id": pw.column_definition(dtype=int, primary_key=True),
        "data": str, "_metadata": dict, "vec": np.ndarray,
    })
    docs = pw.io.python.read(Feed(), schema=schema, autocommit_duration_ms=None)
    store = DocumentStore(
        docs,
        BruteForceKnnFactory(
            dimensions=dep.dim, reserved_space=dep.cfg["reserved_space"],
            metric=dep.cfg["metric"], embedder=embedder,
        ),
        vector_column="vec",
    )
    return DocumentStoreServer("127.0.0.1", port, store), embedder


def note_engines() -> list:
    """Every ``BruteForceKnnEngine`` built from now on is appended to the list
    returned (the engine is built inside the lowered graph, out of reach of the
    caller). Not ``gc.get_objects()``: walking the heap from this thread while
    the engine thread builds tuples breaks them
    (``SystemError: Objects/tupleobject.c: bad argument``, seen once in 12 runs)."""
    from pathway_tpu.ops.index_engines import BruteForceKnnEngine

    made: list = []
    init = BruteForceKnnEngine.__init__

    def noting_init(self, *a, **kw):
        init(self, *a, **kw)
        if type(self) is BruteForceKnnEngine:
            made.append(self)

    BruteForceKnnEngine.__init__ = noting_init
    return made


def wrap_layers(engine, embedder, rec: spans_mod.Spans) -> None:
    """Spans around the calls into the layers, from outside the program."""
    search, embed = engine.search, embedder.embed_texts_device

    def spanned_search(queries, limits, filters):
        dirty = bool(engine._dirty or engine._device is None)
        with rec.span("search", q=len(queries), dirty=dirty):
            return search(queries, limits, filters)

    def spanned_embed(texts, *a, **kw):
        with rec.span("embed", q=len(texts)):
            return embed(texts, *a, **kw)

    engine.search = spanned_search
    embedder.embed_texts_device = spanned_embed


def plant_fault(name: str, engine, embedder, fam) -> None:
    """Tests only (``benchmark/tests/test_faults.py``): break the timed path
    underneath, so that a test can see ``correct`` come out false."""
    if name == "answer":  # an answer altered where it is produced
        search = engine.search

        def swapped(queries, limits, filters):
            out = search(queries, limits, filters)
            return [[(hits[-1 - i][0], sc) for i, (_, sc) in enumerate(hits)]
                    for hits in out]

        engine.search = swapped
    elif name == "token":  # a token altered where it is produced
        # each text's first word, where its family frames it, becomes another
        # word of the vocabulary
        tok, first = embedder.tokenizer, fam.FIRST_WORD_ID
        at = fam.frame([first]).index(first)
        encode = tok.encode_batch

        def altered(texts, max_len):
            ids = np.asarray(encode(texts, max_len)).copy()
            ids[:, at] = np.where(ids[:, at] > 0, ids[:, at] % first + first, 0)
            return ids

        tok.encode_batch = altered
    elif name == "half":  # half of the rows left out of the scan
        engine._valid[len(engine._valid) // 2:] = False
        engine._dirty = True
    elif name == "stale":  # a step that returns its state unchanged
        engine.add_batch = lambda *a, **kw: None
        engine.remove = lambda *a, **kw: None
    elif name == "ignore_scope":  # the engine is handed no filter at all
        search = engine.search
        engine.search = lambda queries, limits, filters: search(
            queries, limits, [None] * len(filters))
    elif name == "scope_short":  # a scoped reply loses its last row
        search = engine.search
        engine.search = lambda queries, limits, filters: [
            hits[:-1] if f is not None else hits
            for hits, f in zip(search(queries, limits, filters), filters)]
    elif name == "slow":
        # no planted fault: every search 1.5 s slower, as a dirty search is on
        # the chip, so that commits pile up behind a tick (PERF.md section 7)
        search = engine.search

        def slow(queries, limits, filters):
            time.sleep(1.5)
            return search(queries, limits, filters)

        engine.search = slow
    elif name:
        raise ValueError(f"unknown fault {name!r}")


def post(port: int, route: str, payload: dict, timeout: float = 120.0):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", route, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, (json.loads(body) if resp.status == 200 else body[:200])
    except OSError as e:
        return 0, str(e)
    finally:
        conn.close()


def row_count(port: int):
    status, body = post(port, "/v1/statistics", {})
    return body.get("file_count") if status == 200 and isinstance(body, dict) else None


def warm_up(dep: Deployment, engine, embedder, port: int, compiles: Compiles) -> dict:
    """Every shape the cell's traffic can form: the embed forward and, for
    requests with no filter, ``topk_scores`` at 1..warm_batch_max queries
    (neither buckets the batch axis), called as ``search`` calls them.

    A scoped mix warms its filtered searches through the route: what the
    program compiles for them is its own business. A request of every
    client's, filter and all, is posted 1..warm_batch_max at a time, sweep after
    sweep until one whole sweep hands the backend no program (``WARM_SWEEPS``
    at the most: which requests share a tick is the program's to decide, so
    one sweep may not form every batch).

    That is sum(1..warm_batch_max) posts a sweep and two sweeps at the least,
    so a program too slow at a filtered search cannot be warmed in a time any
    check could pay: the sweeps have ``SCOPED_WARM_BUDGET_S``, and stop once
    they have run past it (the seconds the backend compiled in are left out:
    only a checkout's first run pays them, and it has 1,200 s, not 360). A
    cell that by its first filtered request will not
    keep to that is refused before any other shape is warmed: the first scoped
    body is posted alone twice (the first may compile, or build what the
    program keeps for a folder), the second is timed, and q posts together are
    taken to cost q times that. That is the most a program needs, one that
    serves filtered requests one after another, as today's does; one that scans
    a tick's requests together needs less, and could be refused here though its
    sweeps would fit. That is tolerated: it would have to take over 0.66 s for
    one filtered request alone where 9.4 ms serve a search of eight with no
    filter over the same 1.2M rows (PERF.md section 5), and a refusal names its
    reading, so the reader sees which it was."""
    import jax

    from pathway_tpu.ops.knn import topk_scores

    def retrieve(b: dict) -> list:
        status, body = post(port, "/v1/retrieve", b)
        if status != 200:
            raise RuntimeError(f"warm-up: status {status}: {str(body)[:200]}")
        return body

    first = retrieve({"query": dep.pool.texts[0], "k": dep.k})
    if len(first) != dep.k:
        raise RuntimeError(f"first retrieve: {len(first)} rows: {str(first)[:200]}")
    texts, batch_max, scope = dep.pool.texts, int(dep.mix["warm_batch_max"]), dep.scope
    if scope is not None:
        bodies = []
        for i in range(batch_max):
            qids, folders = scope.client_requests(i % int(dep.mix["clients"]), 1)
            folder = int(folders[0]) if folders[0] >= 0 else int(scope.home[qids[0]])
            bodies.append({"query": texts[qids[0]], "k": dep.k, **scope.body_fields(folder)})
        budget = SCOPED_WARM_BUDGET_S
        for _ in range(2):
            t = time.monotonic()
            retrieve(bodies[0])
            alone = time.monotonic() - t
        need = alone * batch_max * (batch_max + 1)  # two sweeps of sum(1..batch_max)
        log(f"scoped warm-up: a filtered request served alone takes {alone:.3f} s; "
            f"two sweeps would take {need:.1f} s of a budget of {budget:.0f}")
        if need > budget:
            raise RuntimeError(
                f"a filtered request served alone takes {alone:.2f} s; warming this cell "
                f"would take {need:.0f} s of a budget of {budget:.0f} (SCOPED_WARM_BUDGET_S)")
    unfiltered = scope is None or scope.share_unscoped > 0
    for q in range(1, batch_max + 1):
        out = embedder.embed_texts_device(texts[:q])
        if unfiltered:
            out = topk_scores(out, engine._device, dep.k, engine.metric,
                              valid=engine._device_valid)
        jax.block_until_ready(out)
    shapes = {"embed_shapes": embedder._fwd._cache_size(),
              "topk_shapes": topk_scores._cache_size()}
    if scope is None:
        return shapes
    posts, t_sweeps = ThreadPoolExecutor(batch_max), time.monotonic()
    try:
        for sweep in range(1, WARM_SWEEPS + 1):
            t = time.monotonic()
            for q in range(1, batch_max + 1):
                list(posts.map(retrieve, bodies[:q]))
                now = time.monotonic()
                spent = now - t_sweeps - compiles.between(t_sweeps, now)["seconds"]
                if spent > budget:
                    raise RuntimeError(
                        f"scoped warm-up: {spent:.0f} s, compiling left out, into sweep "
                        f"{sweep} at {q} requests together, over the budget of "
                        f"{budget:.0f} (SCOPED_WARM_BUDGET_S)")
            loaded = compiles.between(t, time.monotonic())["programs"]
            log(f"scoped warm-up, sweep {sweep}: {time.monotonic() - t:.1f} s, "
                f"{loaded} programs handed to the backend")
            if not loaded:
                break
    finally:
        # never wait here for a post in flight: where a sweep failed, each may
        # hold on for its whole timeout
        posts.shutdown(wait=False, cancel_futures=True)
    return {**shapes, "warm_sweeps": sweep}


def device_memory(jax) -> dict:
    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit") if k in stats}


def run_reference(dep: Deployment, sample: list[dict], precision: str) -> dict:
    """The reference's query vectors (``vec[text]``) and, for each (text,
    scope) the sample asks, its best-first scores and rows over the stable rows
    of that scope (``top``); with ``precision="fp8"`` these are also its own
    answers, to be judged in the program's place (the control).

    A scope is a mask over the rows made from the seeded folder array, never a
    filter string parsed. The scan goes over the rows the mask keeps, gathered
    and padded with dead rows to whole blocks (one compiled shape, and a tenth
    of the store costs a tenth of the scan)."""
    from lib import reference

    texts = sorted({r["query"] for r in sample})
    ids = reference.token_ids(texts, dep.vocab_index, dep.query_width, dep.fam)
    params = dep.fam.reference_params(dep.weights)
    vec = dict(zip(texts, dep.fam.encode(params, ids, dep.model, precision)))
    del params
    live = np.ones(dep.n_rows, bool)
    unstable = sorted(dep.plan.touched) if dep.plan else []
    for doc in unstable:
        live[doc * dep.chunks:(doc + 1) * dep.chunks] = False
    asked: dict = {}
    for r in sample:
        asked.setdefault(r["scope"], set()).add(r["query"])
    top = {}
    for scope, of_scope in asked.items():
        of_scope = sorted(of_scope)
        q = np.stack([vec[t] for t in of_scope])
        if scope is None:
            scores, rows = reference.scan_topk(q, dep.rows, live, dep.k, precision)
        else:
            at = np.flatnonzero(dep.scope.mask(scope))
            pad = -len(at) % datagen.BLOCK_ROWS
            kept = np.zeros((len(at) + pad, dep.dim), np.float32)
            np.take(dep.rows, at, axis=0, out=kept[:len(at)])
            scores, rows = reference.scan_topk(
                q, kept, np.concatenate([live[at], np.zeros(pad, bool)]), dep.k,
                precision, block=datagen.BLOCK_ROWS)
            del kept
            rows = np.concatenate([at, np.full(pad, -1)])[rows]
        for t, sc, ro in zip(of_scope, scores, rows):
            top[(t, scope)] = (sc, ro)
    return {"vec": vec, "top": top, "unstable": set(unstable)}


def judge(dep: Deployment, sample: list[dict], control: bool) -> dict:
    ref = run_reference(dep, sample, "float32")
    ref_top = {key: scores for key, (scores, _) in ref["top"].items()}
    gap, err, bad = check.compare_sample(
        sample, ref["vec"], ref_top, dep.row_vector, ref["unstable"])
    out = {"rank_gap": gap, "score_err": err, "bad_rows": bad,
           "sampled": len(sample), "queries": len(ref["vec"])}
    if control:
        ctl = run_reference(dep, sample, "fp8")
        answers = []
        for r in sample:
            scores, rows = ctl["top"][(r["query"], r["scope"])]
            answers.append({"query": r["query"], "scope": r["scope"], "rows": [
                (int(row) // dep.chunks, int(row) % dep.chunks, 0, float(s))
                for s, row in zip(scores, rows) if np.isfinite(s)]})
        cgap, cerr, _ = check.compare_sample(
            answers, ref["vec"], ref_top, dep.row_vector, ref["unstable"])
        out["control"] = {"precision": "fp8", "rank_gap": cgap, "score_err": cerr}
    return out


def main() -> int:
    t_child = time.monotonic()
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    die_with_parent(spec.get("parent_pid"))  # before JAX, so before the chip is held
    out_dir = spec["out_dir"]
    dep = Deployment(spec)
    pool = ThreadPoolExecutor(1)
    weights = pool.submit(dep.make_weights)
    rows = pool.submit(dep.make_rows)

    from pathway_tpu.utils import jaxcfg  # noqa: F401  (places the compile cache)

    import jax

    dev = jax.devices()[0]
    rehearsal = dev.platform != "tpu"
    if rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"benchmark: no chip — JAX runs on {dev.platform!r}; only a "
              "caller that sets JAX_PLATFORMS=cpu gets the rehearsal",
              file=sys.stderr)
        return NO_CHIP_EXIT
    if len(jax.devices()) < spec["chips"]:
        print(f"benchmark: the cell asks for {spec['chips']} chips, JAX has "
              f"{len(jax.devices())}", file=sys.stderr)
        return NO_CHIP_EXIT
    chip = None if rehearsal else peaks.peaks_for(dev.device_kind)
    compiles = Compiles(jax)
    pauses = GcPauses()
    rec = spans_mod.Spans(jax.profiler.TraceAnnotation)

    weights.result()
    ref_params = dep.fam.reference_params(dep.weights)
    dep.embed_pool(ref_params)
    del ref_params
    rows.result()
    pool.shutdown()
    log(f"data made: {dep.n_rows} x {dep.dim} rows, "
        f"{sum(a.size for a in dep.weights.values())} parameters; pool embedded")
    dep.plant()
    log(f"planted {len(dep.passage_rows)} passages; a text's own share of its "
        f"embedding is {dep.own_share:.3f} (median norm off the cone's axis), "
        f"the probe text's {dep.probe_share:.3f}")

    state = {"fed": threading.Event(), "window": threading.Event(),
             "stop": threading.Event(), "written": threading.Event(),
             "committed": [], "t0": None}
    from pathway_tpu.internals.run import request_stop
    from pathway_tpu.io.http._server import terminate_all

    engines = note_engines()
    server, embedder = build_server(dep, spec["port"], state)
    thread = server.run(threaded=True)
    try:
        state["fed"].wait()
        t_fed = time.monotonic()
        # the parent's set-up limit is the one that bounds this wait
        while row_count(spec["port"]) != dep.n_rows:
            time.sleep(0.5)
        t_built = time.monotonic()
        log(f"index built: {dep.n_rows} rows in {t_built - t_child:.1f} s "
            "of the child's life")
        if len(engines) != 1:
            raise RuntimeError(f"expected one index engine, found {len(engines)}")
        engine = engines.pop()
        # a test's fault is in the path before the warm-up times it
        plant_fault(spec.get("fault", ""), engine, embedder, dep.fam)
        shapes = warm_up(dep, engine, embedder, spec["port"], compiles)
        wrap_layers(engine, embedder, rec)
        resident = device_memory(jax)
        t_ready = time.monotonic()
        say("ready", probe_text=dep.probe_text,
            setup_compiles=compiles.between(0, t_ready), shapes=shapes, resident=resident,
            seconds={"data_and_import": round(t_fed - t_child, 2),
                     "index_build": round(t_built - t_child, 2),
                     "warm_up": round(t_ready - t_built, 2)})

        # -- the window: the parent names its start on our shared clock
        cmd = json.loads(sys.stdin.readline())
        assert cmd["cmd"] == "window", cmd
        t0, seconds = float(cmd["t0"]), float(cmd["seconds"])
        state["t0"] = t0
        state["window"].set()
        trace = None
        if spec["trace"]:
            trace_s = min(float(dep.mix["trace_seconds"]), seconds)
            trace_dir = os.path.join(out_dir, "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            start = t0 + (seconds - trace_s) / 2
            time.sleep(max(0.0, start - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the spans are TraceAnnotations; no Python frames
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            # the traced span is the time the profiler was on, without its own
            # start and stop: in a process's first trace they took 11 s between
            # them and stalled the server for 3.4 s (my chip run, PR 25)
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            a = time.monotonic()
            time.sleep(trace_s)
            b = time.monotonic()
            jax.profiler.stop_trace()
            log(f"profiler: on for {b - a:.2f} s; start took {a - start:.2f} s, "
                f"stop {time.monotonic() - b:.2f} s")
            trace = {"t0": a, "t1": b, "dir": trace_dir}
        cmd = json.loads(sys.stdin.readline())
        assert cmd["cmd"] == "close", cmd
        t_close = time.monotonic()
        if dep.plan is not None:
            state["written"].wait(timeout=60)
            time.sleep(1.0)  # the quiet second before the count
        rows_after = row_count(spec["port"])
        memory = device_memory(jax)
        in_window = compiles.between(t0, t0 + seconds)
        host_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        searches = rec.within(t0, t0 + seconds)
        facts = {
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "memory": memory, "resident_ready": resident,
            "compiles_in_window": in_window, "rows_after": rows_after,
            "rows_expected": dep.plan.final_rows if dep.plan else dep.n_rows,
            "host_peak_rss_bytes": host_rss,
            "gc_in_window": pauses.between(t0, t0 + seconds),
            "writer_lateness_s": [round(l, 4) for _, l in state["committed"]],
            "spans": searches,
            "trace_window": trace and {"t0": trace["t0"], "t1": trace["t1"]},
            "chip": chip,
        }
    except BaseException:
        state["stop"].set()
        state["window"].set()
        request_stop()
        terminate_all()
        raise
    # -- free the program's state before the reference runs on the chip
    state["stop"].set()
    request_stop()
    terminate_all()
    thread.join(timeout=30)
    log(f"server stopped {time.monotonic() - t_close:.1f} s after the close")
    engine._device = engine._device_valid = None
    engine._host = None
    embedder.params = None
    del engine, embedder, server
    gc.collect()

    if trace is not None:
        from lib import xplane

        files = glob.glob(os.path.join(trace["dir"], "**", "*.xplane.pb"), recursive=True)
        t = time.monotonic()
        facts["trace"] = (xplane.reduce(files[0], ("search", "embed"), trace["t1"] - trace["t0"])
                          if files else None)
        log(f"trace reduced in {time.monotonic() - t:.1f} s")
        if not spec.get("keep_trace"):
            shutil.rmtree(trace["dir"], ignore_errors=True)
    with open(os.path.join(out_dir, "child_facts.json"), "w") as f:
        json.dump(facts, f)
    say("closed", seconds=round(time.monotonic() - t_close, 2))

    cmd = json.loads(sys.stdin.readline())
    assert cmd["cmd"] == "check", cmd
    with open(cmd["sample"]) as f:
        sample = json.load(f)
    t = time.monotonic()
    verdict = judge(dep, sample, bool(spec.get("control")))
    verdict["seconds"] = round(time.monotonic() - t, 2)
    log(f"reference and comparison took {verdict['seconds']} s")
    with open(os.path.join(out_dir, "child_check.json"), "w") as f:
        json.dump(verdict, f)
    say("checked", **verdict)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception as e:  # the boundary: the parent prints the reason last
        traceback.print_exc()
        say("failed", reason=f"{type(e).__name__}: {e}")
        # a failing path may leave threads that hold on (posts in flight, the
        # server's) and the interpreter's exit would join them: all is said
        # and flushed, so go without it
        sys.stderr.flush()
        os._exit(1)
    sys.exit(code)
