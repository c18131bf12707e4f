"""The live index written in place (``ops/index_engines.py``,
``ops/knn.py::index_write``), held to a plain reference
(``reference_live_index.py``): the same interleaving of writes and searches
through three capacity tiers, a flush that moves only the staged slots, a
pickled engine, several commits arriving as one delta, and a document store
that answers whole while a writer commits between and inside query ticks."""

import contextlib
import http.client
import json
import pickle
import socket
import threading
import time

import numpy as np
import pytest
from reference_live_index import ReferenceLiveIndex

import pathway_tpu as pw
from pathway_tpu.engine.delta import Delta
from pathway_tpu.engine.external_index import ExternalIndexNode
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.ops.index_engines import BruteForceKnnEngine, LshKnnEngine
from pathway_tpu.ops.knn import WRITE_BUCKETS
from pathway_tpu.serve.stats import SERVE_STATS

TOL = 4e-3  # bfloat16 operands at width 64-768, float32 accumulation
COUNTERS = ("index_uploads_total", "index_upload_bytes_total", "index_writes_total",
            "index_write_rows_total", "index_write_bytes_total")


def _counters() -> dict:
    return {k: SERVE_STATS[k] for k in COUNTERS}


def _padded(n_slots: int) -> int:
    cap = WRITE_BUCKETS[-1]
    pieces = [min(cap, n_slots - i) for i in range(0, n_slots, cap)]
    return sum(next(b for b in WRITE_BUCKETS if b >= p) for p in pieces)


def _compare(engine, ref: ReferenceLiveIndex, queries, k: int, gone: set) -> None:
    replies = engine.search(list(queries), [k] * len(queries), [None] * len(queries))
    for query, reply in zip(queries, replies):
        want = ref.search(query, k)
        assert len(reply) == min(k, len(ref.rows))
        keys = [key for key, _ in reply]
        assert len(set(keys)) == len(keys) and not set(keys) & gone
        everything = ref.search(query, len(ref.rows))
        for rank, (key, score) in enumerate(reply):
            assert key in ref.rows
            exact = ref.score(query, key)
            assert abs(score - exact) <= TOL, (key, score, exact)
            # no better row was passed over by more than the tolerance
            assert want[rank][1] - exact <= TOL
            # and where the reference's ranking is clear of it, it is the ranking
            near = [s for j, (_, s) in enumerate(everything[:rank + 2]) if j != rank]
            if all(abs(s - want[rank][1]) > 2 * TOL for s in near):
                assert key == want[rank][0], (rank, reply, want)


@pytest.mark.parametrize("seed,dim", [(0, 64), (1, 64), (2, 256), (3, 768)])
def test_engine_against_the_reference_through_three_tiers(seed, dim):
    rng = np.random.default_rng(seed)
    engine = BruteForceKnnEngine(dim, metric="cos", reserved_space=16)
    ref = ReferenceLiveIndex(dim)
    gone: set[int] = set()
    next_key, tiers, placed_at = 0, {engine.capacity}, None

    def vectors(n):
        return rng.standard_normal((n, dim)).astype(np.float32)

    def add_batch(keys, vecs):
        engine.add_batch(list(keys), list(vecs), [None] * len(keys))
        for key, vec in zip(keys, vecs):  # a key twice in one batch: the last wins
            ref.add(key, vec)
            gone.discard(key)

    def remove(key):
        engine.remove(key)
        ref.remove(key)
        gone.add(key)

    for step in range(60):
        live = list(ref.rows)
        kind = rng.choice(["add", "add", "replace", "remove", "dup", "readd"])
        if kind == "add" or len(live) < 4:
            n = int(rng.integers(1, 24))
            add_batch(range(next_key, next_key + n), vectors(n))
            next_key += n
        elif kind == "replace":
            keys = rng.choice(live, size=min(len(live), int(rng.integers(1, 9))), replace=False)
            add_batch([int(key) for key in keys], vectors(len(keys)))
        elif kind == "remove":
            for key in rng.choice(live, size=min(len(live) - 1, int(rng.integers(1, 6))),
                                  replace=False):
                remove(int(key))
        elif kind == "dup":  # one key three times in one batch, a new key beside it
            key = int(rng.choice(live))
            add_batch([key, next_key, key, key], vectors(4))
            next_key += 1
        else:  # removed and added back inside one tick, as an update arrives
            key = int(rng.choice(live))
            remove(key)
            add_batch([key], vectors(1))
        if rng.random() < 0.6:
            continue
        # -- a search: first the mechanism, then the answers
        before = _counters()
        whole = engine._device is None
        staged = (len(np.unique(np.hstack(engine._staged)))
                  if engine._staged else 0)
        assert whole or engine._dirty == bool(staged)
        near = [ref.rows[int(key)] + 0.05 * vectors(1)[0]
                for key in rng.choice(list(ref.rows), size=2)]
        _compare(engine, ref, near + list(vectors(int(rng.integers(1, 3)))),
                 int(rng.choice([1, 5, 10])), gone)
        after = _counters()
        grew = {k: after[k] - before[k] for k in COUNTERS}
        block = engine.capacity * dim * 4
        if whole:  # the first placement, or the first search of a new tier
            assert engine.capacity != placed_at
            assert (grew["index_uploads_total"], grew["index_upload_bytes_total"]) == (1, block)
            assert grew["index_writes_total"] == 0
            placed_at = engine.capacity
        else:
            assert grew["index_uploads_total"] == 0
            assert grew["index_writes_total"] == (1 if staged else 0)
            assert grew["index_write_rows_total"] == staged
            assert grew["index_write_bytes_total"] == _padded(staged) * dim * 4
        assert not engine._dirty and not engine._staged
        tiers.add(engine.capacity)
    assert len(tiers) >= 4, tiers  # 16 and at least three doublings
    assert gone and not set(engine._slots.key_to_slot) & gone


def test_a_large_batch_is_written_in_pieces_of_the_cap():
    dim, cap = 8, WRITE_BUCKETS[-1]
    rng = np.random.default_rng(5)
    engine = BruteForceKnnEngine(dim, reserved_space=4 * cap)
    ref = ReferenceLiveIndex(dim)
    vecs = rng.standard_normal((cap + 70, dim)).astype(np.float32)
    engine.add_batch([0], [vecs[0]], [None])
    ref.add(0, vecs[0])
    engine.search([vecs[0]], [1], [None])  # the block is placed
    before = _counters()
    engine.add_batch(list(range(len(vecs))), list(vecs), [None] * len(vecs))
    for key, vec in enumerate(vecs):
        ref.add(key, vec)
    _compare(engine, ref, [vecs[3], vecs[cap + 69]], 5, set())
    grew = {k: SERVE_STATS[k] - before[k] for k in COUNTERS}
    assert grew["index_uploads_total"] == 0 and grew["index_writes_total"] == 1
    assert grew["index_write_rows_total"] == cap + 70
    assert grew["index_write_bytes_total"] == (cap + 512) * dim * 4 == _padded(cap + 70) * dim * 4


def test_dirty_with_nothing_staged_places_the_whole_block_again():
    rng = np.random.default_rng(6)
    vecs = rng.standard_normal((12, 8)).astype(np.float32)
    engine = BruteForceKnnEngine(8, reserved_space=16)
    engine.add_batch(list(range(12)), list(vecs), [None] * 12)
    assert [key for key, _ in engine.search([vecs[7]], [1], [None])[0]] == [7]
    # the host mask edited behind the engine's back (the benchmark's planted
    # fault does this): nothing is staged, so the whole block goes again
    engine._valid[engine._slots.key_to_slot[7]] = False
    engine._dirty = True
    before = _counters()
    assert 7 not in [key for key, _ in engine.search([vecs[7]], [3], [None])[0]]
    assert SERVE_STATS["index_uploads_total"] == before["index_uploads_total"] + 1
    assert SERVE_STATS["index_writes_total"] == before["index_writes_total"]


def test_a_pickled_engine_answers_as_before_after_in_place_writes():
    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((40, 32)).astype(np.float32)
    engine = BruteForceKnnEngine(32, reserved_space=64)
    engine.add_batch(list(range(30)), list(vecs[:30]), [None] * 30)
    engine.search([vecs[0]], [3], [None])
    engine.remove(4)
    engine.add_batch([5, 30], list(vecs[30:32]), [None, None])  # a replace and an add
    engine.search([vecs[0]], [3], [None])                       # flushed in place
    engine.remove(9)
    engine.add(31, vecs[33], None)                              # staged, not flushed
    state = pickle.dumps(engine)
    restored = pickle.loads(state)
    assert restored._device is None and restored._device_valid is None
    assert restored._staged == [] and restored._dirty
    queries = [vecs[30], vecs[33], vecs[9], vecs[4], vecs[20]]
    before = _counters()
    got = restored.search(queries, [5] * 5, [None] * 5)
    assert SERVE_STATS["index_uploads_total"] == before["index_uploads_total"] + 1
    assert SERVE_STATS["index_writes_total"] == before["index_writes_total"]
    want = engine.search(queries, [5] * 5, [None] * 5)
    assert [[key for key, _ in r] for r in got] == [[key for key, _ in r] for r in want]
    np.testing.assert_allclose([[s for _, s in r] for r in got],
                               [[s for _, s in r] for r in want], atol=1e-6)
    assert got[0][0][0] == 5 and got[1][0][0] == 31
    assert not {4, 9} & {key for r in got for key, _ in r}
    # a state pickled before there were staged slots restores too
    old = dict(engine.__getstate__())
    fresh = BruteForceKnnEngine.__new__(BruteForceKnnEngine)
    fresh.__setstate__(old)
    assert [key for key, _ in fresh.search([vecs[33]], [1], [None])[0]] == [31]


def test_lsh_engine_still_takes_writes_and_removals():
    rng = np.random.default_rng(8)
    vecs = rng.standard_normal((20, 16)).astype(np.float32)
    engine = LshKnnEngine(16, reserved_space=16, n_or=8, n_and=2)
    engine.add_batch(list(range(20)), list(vecs), [None] * 20)  # through a _grow
    assert engine.search([vecs[11]], [1], [None])[0][0][0] == 11
    engine.remove(11)
    assert 11 not in [key for key, _ in engine.search([vecs[11]], [5], [None])[0]]
    assert engine._device is None and not engine._staged


def _data_delta(entries) -> Delta:
    """[(key, vector, diff)] as the indexed-data stream of an index node."""
    data = np.empty(len(entries), dtype=object)
    for i, (_, vec, _) in enumerate(entries):
        data[i] = vec
    return Delta(keys=np.array([key for key, _, _ in entries], dtype=np.uint64),
                 data={"__data__": data},
                 diffs=np.array([diff for _, _, diff in entries], dtype=np.int64))


@pytest.mark.parametrize("order", ["as committed", "retractions last"])
def test_commits_merged_into_one_delta_leave_the_net_state(order):
    """A connector that is behind merges its commit windows into one delta. A
    document replaced and then deleted inside it must be gone, not back with
    the version in between (which the documents' table no longer has, so the
    reply that names it comes out short)."""
    rng = np.random.default_rng(9)
    v = rng.standard_normal((8, 16)).astype(np.float32)
    engine = BruteForceKnnEngine(16, reserved_space=16)
    node = ExternalIndexNode.__new__(ExternalIndexNode)
    node.engine = engine
    node._apply_data(_data_delta([(1, v[0], 1), (2, v[1], 1), (3, v[2], 1)]))
    entries = [
        (1, v[0], -1), (1, v[3], 1),                 # key 1 replaced ...
        (1, v[3], -1),                               # ... and then deleted
        (2, v[1], -1), (2, v[4], 1), (2, v[4], -1), (2, v[5], 1),  # replaced twice
        (3, v[2], -1),                               # deleted ...
        (3, v[6], 1),                                # ... and added back
        (4, v[7], 1), (4, v[7], -1),                 # added and deleted: never there
    ]
    if order == "retractions last":
        entries.sort(key=lambda e: -e[2])
    node._apply_data(_data_delta(entries))
    assert sorted(engine._slots.key_to_slot) == [2, 3]
    reply = engine.search([v[5], v[6], v[3]], [4, 4, 4], [None] * 3)
    assert [key for key, _ in reply[0]][0] == 2 and reply[0][0][1] > 0.99
    assert [key for key, _ in reply[1]][0] == 3 and reply[1][0][1] > 0.99
    assert all(len(r) == 2 and {key for key, _ in r} == {2, 3} for r in reply)


# -- a document store that answers whole under a writer ---------------------

DOCS, CHUNKS, DIM, K = 24, 4, 16, 10


@contextlib.contextmanager
def _live_store(commits: "list[list[tuple]]", burst_at: threading.Event,
                slow_s: float):
    """A pre-embedded document store behind a REST server. ``commits`` is the
    writer's schedule: lists of (doc, chunk, ver, vec, diff), one list a
    commit; they are all committed back to back when ``burst_at`` is set, which
    the first search after the build does — inside that query's tick, which
    then sleeps ``slow_s`` so that the engine is behind by all of them."""
    from pathway_tpu.internals.run import request_stop
    from pathway_tpu.io.http._server import terminate_all
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    fed, stop, committed = threading.Event(), threading.Event(), threading.Event()

    def columns(rows):
        return {"id": np.asarray([d * CHUNKS + c for d, c, _, _ in rows], np.int64),
                "data": [f"d{d}c{c}v{v}" for d, c, v, _ in rows],
                "_metadata": [{"path": f"d{d}", "ver": v} for d, _, v, _ in rows],
                "vec": [vec for _, _, _, vec in rows]}

    class Feed(pw.io.python.ConnectorSubject):
        def run(self):
            self.next_batch(columns([(i // CHUNKS, i % CHUNKS, 0, BASE[i])
                                     for i in range(DOCS * CHUNKS)]))
            self.commit()
            fed.set()
            burst_at.wait()
            for commit in commits:
                self.next_batch(columns([r[:4] for r in commit]),
                                np.asarray([r[4] for r in commit], np.int64))
                self.commit()
            committed.set()
            stop.wait()

    schema = pw.schema_builder({
        "id": pw.column_definition(dtype=int, primary_key=True),
        "data": str, "_metadata": dict, "vec": np.ndarray})
    docs = pw.io.python.read(Feed(), schema=schema, autocommit_duration_ms=None)
    store = DocumentStore(
        docs, BruteForceKnnFactory(dimensions=DIM, reserved_space=16, metric="cos",
                                   embedder=lambda text: PROBE),
        vector_column="vec")
    search = BruteForceKnnEngine.search

    def slow_first(self, queries, limits, filters):
        if fed.is_set() and not burst_at.is_set():
            burst_at.set()
            committed.wait(10)
            time.sleep(slow_s)
        return search(self, queries, limits, filters)

    BruteForceKnnEngine.search = slow_first
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = DocumentStoreServer("127.0.0.1", port, store)
    thread = server.run(threaded=True)

    def post(payload, route="/v1/retrieve"):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            conn.request("POST", route, body=json.dumps(payload),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    try:
        assert fed.wait(60) and server.webserver._started.wait(60)
        for _ in range(200):
            if post({}, "/v1/statistics")[1].get("file_count") == DOCS * CHUNKS:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("the index was not built")
        yield post
    finally:
        BruteForceKnnEngine.search = search
        stop.set()
        burst_at.set()
        request_stop()
        terminate_all()
        thread.join(60)
        G.clear()


PROBE = np.eye(DIM, dtype=np.float32)[0]
_rng = np.random.default_rng(11)
BASE = _rng.standard_normal((DOCS * CHUNKS, DIM)).astype(np.float32)
BASE[:, 0] = 0.0  # the base rows are orthogonal to the probe: every written rung outranks them


def _rung(i: int) -> np.ndarray:
    """A unit vector whose cosine to the probe rises with the commit's index."""
    c = 0.5 + 0.04 * i
    out = np.zeros(DIM, np.float32)
    out[0], out[1] = c, np.sqrt(1.0 - c * c)
    return out


def _schedule():
    """Twelve commits, more than the connector drains as windows of their own:
    replace x3, delete (of the document replaced two commits before), replace
    x3, add (the deleted document back), and again. Returns the commits and,
    per commit, the (doc, ver) it retires."""
    version: dict[int, int] = {}
    current: dict[int, list] = {}
    commits, retires = [], []
    written, deleted = [], []
    pattern = ["replace", "replace", "replace", "delete", "replace", "replace", "replace", "add"]
    fresh = iter(range(DOCS))
    for i in range(12):
        kind = pattern[i % 8]
        if kind == "replace":
            doc = next(fresh)
        elif kind == "delete":
            doc = written[-2]
            deleted.append(doc)
        else:
            doc = deleted.pop(0)
        old = current.get(doc) or [(doc, c, 0, BASE[doc * CHUNKS + c]) for c in range(CHUNKS)]
        rows = [(*r, -1) for r in old] if kind != "add" else []
        retires.append((doc, old[0][2]) if kind != "add" else None)
        if kind != "delete":
            version[doc] = version.get(doc, 0) + 1
            new = [(doc, c, version[doc], _rung(i) if c == 0 else BASE[doc * CHUNKS + c])
                   for c in range(CHUNKS)]
            current[doc] = new
            rows += [(*r, 1) for r in new]
            written.append(doc)
        commits.append(rows)
    return commits, retires


def _parse(text: str) -> tuple[int, int, int]:
    doc, rest = text[1:].split("c")
    chunk, ver = rest.split("v")
    return int(doc), int(chunk), int(ver)


def test_a_store_answers_whole_while_a_writer_commits_between_and_inside_ticks():
    commits, retires = _schedule()
    burst = threading.Event()
    with _live_store(commits, burst, slow_s=0.3) as post:
        replies = []

        def ask():
            status, body = post({"query": "probe", "k": K})
            assert status == 200 and len(body) == K, (status, body)
            rows = [_parse(hit["text"]) for hit in body]
            assert len({r[:2] for r in rows}) == K
            replies.append(rows)

        # the first query's tick is the slow one: all twelve commits land
        # inside it and reach the engine merged; three more callers wait with it
        threads = [threading.Thread(target=ask) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert burst.is_set() and len(replies) == 4
        for _ in range(3):  # and between ticks: the state is the final one
            ask()
    live = {(doc, chunk): 0 for doc in range(DOCS) for chunk in range(CHUNKS)}
    for commit in commits:
        for doc, chunk, ver, _, diff in commit:
            if diff > 0:
                live[(doc, chunk)] = ver
            elif live.get((doc, chunk)) == ver:
                del live[(doc, chunk)]
    # every row of the replies after the burst is the live version of its
    # document, and the rungs come newest first
    for rows in replies[-3:]:
        assert all(live.get((doc, chunk)) == ver for doc, chunk, ver in rows), rows
        assert rows[:7] == replies[-1][:7] and rows[0][2] > 0
    # once a reply has shown a commit applied, nothing that commit (or an
    # earlier one) retired is returned again
    wrote = {}
    for i, commit in enumerate(commits):
        for doc, chunk, ver, _, diff in commit:
            if diff > 0:
                wrote[(doc, ver)] = i
    shown = -1
    for rows in replies:
        retired = {retires[i] for i in range(shown + 1) if retires[i] is not None}
        assert not {(doc, ver) for doc, _, ver in rows} & retired, (rows, shown)
        shown = max([shown] + [wrote[(doc, ver)] for doc, _, ver in rows if (doc, ver) in wrote])
    assert shown == max(wrote.values())


def test_a_commit_that_changes_only_a_vector_reaches_the_next_reply():
    """The documents' side of the reply join holds ``text`` and ``_metadata``
    only, so a commit that replaces a row's vector and nothing else cancels
    there by key. The index's side must still see it: the next reply's
    ``dist`` moves, a delete removes the row, an add brings it back."""
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore

    def row(i: int, cos: float, time: int, diff: int) -> tuple:
        vec = np.zeros(DIM, np.float32)
        vec[0], vec[1] = cos, np.sqrt(1.0 - cos * cos)
        return (i, f"doc {i}", {"path": f"d{i}.txt"}, vec, time, diff)

    schema = pw.schema_builder({
        "id": pw.column_definition(dtype=int, primary_key=True),
        "data": str, "_metadata": dict, "vec": np.ndarray})
    docs = pw.debug.table_from_rows(schema, [
        *(row(i, 0.1 * i, 2, 1) for i in range(6)),
        row(3, 0.3, 6, -1), row(3, 0.9, 6, 1),  # the vector alone changes
        row(3, 0.9, 10, -1),                    # deleted
        row(3, 0.3, 14, 1),                     # and added back
    ], is_stream=True)
    store = DocumentStore(
        docs, BruteForceKnnFactory(dimensions=DIM, reserved_space=16, metric="cos",
                                   embedder=lambda text: PROBE),
        vector_column="vec")
    queries = pw.debug.table_from_rows(
        DocumentStore.RetrieveQuerySchema,
        [(f"at {t:02d}", 6, None, None, t) for t in (4, 8, 12, 16)], is_stream=True)
    asked = pw.debug.table_to_pandas(queries)["query"]
    got = pw.debug.table_to_pandas(store.retrieve_query(queries))["result"]
    G.clear()
    first, moved, gone, back = (
        {hit["text"]: hit for hit in reply}
        for _, reply in sorted((asked[key], got[key]) for key in asked.index))
    assert sorted(first) == sorted(moved) == sorted(back) == [f"doc {i}" for i in range(6)]
    assert abs(first["doc 3"]["dist"] + 0.3) <= TOL
    assert abs(moved["doc 3"]["dist"] + 0.9) <= TOL
    assert moved["doc 3"]["metadata"] == first["doc 3"]["metadata"] == {"path": "d3.txt"}
    assert sorted(gone) == [f"doc {i}" for i in (0, 1, 2, 4, 5)]
    assert back["doc 3"] == first["doc 3"]
    for other in ("doc 0", "doc 1", "doc 2", "doc 4", "doc 5"):
        assert first[other] == moved[other] == gone[other] == back[other]


def test_a_column_is_normalised_while_another_thread_imports_jax(monkeypatch):
    """``sys.modules`` holds a module from the moment its import starts: the
    engine thread must not ask a half-imported jax for ``Array`` (a shard
    responder's first search imports it on the router's thread)."""
    import sys
    import types

    from pathway_tpu.engine.operators import _as_column

    monkeypatch.setitem(sys.modules, "jax", types.ModuleType("jax"))
    out = _as_column([1, 2, 3], 3)
    assert out.tolist() == [1, 2, 3]


def test_the_inputs_route_lists_documents_that_arrived_in_large_batches():
    """The documents' side of the inputs join computes its key over deltas
    that also carry ``_metadata`` dicts: the key's kernel reads its own
    columns, whatever object columns ride beside them."""
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore

    G.clear()
    n, batches = 16, 4
    rng = np.random.default_rng(12)

    class Feed(pw.io.python.ConnectorSubject):
        def run(self):
            for b in range(batches):
                ids = range(b * n, (b + 1) * n)
                self.next_batch({
                    "data": [f"doc {i}" for i in ids],
                    "_metadata": [{"path": f"d{i}.txt"} for i in ids],
                    "vec": list(rng.standard_normal((n, 8)).astype(np.float32)),
                })
                self.commit()

    docs = pw.io.python.read(
        Feed(), schema=pw.schema_from_types(data=str, _metadata=dict, vec=np.ndarray),
        autocommit_duration_ms=None)
    store = DocumentStore(
        docs, BruteForceKnnFactory(dimensions=8, reserved_space=16,
                                   embedder=lambda text: np.ones(8, np.float32)),
        vector_column="vec")
    queries = pw.debug.table_from_rows(DocumentStore.InputsQuerySchema, [(None, None)])
    [files] = pw.debug.table_to_pandas(store.inputs_query(queries))["result"].tolist()
    G.clear()
    assert sorted(f["path"] for f in files) == sorted(f"d{i}.txt" for i in range(n * batches))


def test_a_deferred_bulk_load_is_arranged_when_it_ends_not_under_a_later_write():
    """A join side defers the sort of large batches until something reads it.
    The documents' side of the inputs join is never read, so its backlog must
    be arranged where the bulk load ends — by the first small batch behind
    it, or when the join's input pauses — and not by whichever 16-row write
    later carries the row counter over its bound."""
    from pathway_tpu.engine.operators import Join, _SortedSide

    def batch(lo, hi, diff=1):
        keys = np.arange(lo, hi, dtype=np.uint64)
        col = np.empty(hi - lo, object)
        col[:] = [f"row {i}" for i in range(lo, hi)]
        return keys * 7, keys, [col], np.full(hi - lo, diff, np.int64)

    side = _SortedSide(1)
    side.apply(*batch(0, 300))
    side.apply(*batch(300, 700))
    assert side._pending_rows == 700 and not side._runs
    side.apply(*batch(10, 18, diff=-1))  # a write: eight of the bulk rows retracted
    assert not side._pending and side._pending_rows == 0
    assert sum(len(r[0]) for r in side._runs) in (692, 708)  # cancelled, or still riding
    probe = np.array([12 * 7, 20 * 7, 650 * 7], np.uint64)
    assert side.totals(probe).tolist() == [0, 1, 1]
    side.apply(*batch(700, 716))         # and small batches stay eager after it
    assert not side._pending

    join = Join.__new__(Join)
    join._cleft, join._cright = _SortedSide(1), _SortedSide(1)
    join._cright.apply(*batch(0, 400))
    assert join.advance_to(2) is None and join._cright._pending  # the load's own tick
    join._quiet = False                                          # (process ran in it)
    join.advance_to(4)
    assert join._cright._pending                                 # one quiet tick
    join.advance_to(6)
    assert not join._cright._pending and len(join._cright._runs) == 1
