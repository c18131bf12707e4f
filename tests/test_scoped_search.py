"""A filtered search of ``BruteForceKnnEngine``: a filter is a mask the engine
holds on the device, and a tick's filtered queries are one scan. Held to what
the repository already has as the meaning of a filter,
``compile_metadata_filter``'s predicate over one dict, and to a float32 numpy
scan over exactly the rows it keeps: the column evaluator slot for slot on every
construct of the grammar, replies for ``cos``, ``ip`` and ``l2``, scopes of fewer
than ``k`` rows, under writes, a new tier and a pickle, one ``topk_scores``
call a search, and both of a retrieve's filter fields through the REST route."""

import contextlib
import http.client
import json
import pickle
import socket
import threading
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals import tracing
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.ops import index_engines, knn
from pathway_tpu.ops.index_engines import BruteForceKnnEngine
from pathway_tpu.serve.stats import SERVE_STATS
from pathway_tpu.utils.filters import (
    FilterSyntaxError,
    compile_metadata_filter,
    eval_filter_columns,
    parse_metadata_filter,
)

DIM, K = 64, 10
#: bfloat16 operands, float32 accumulation: 4e-3 on a cosine of unit rows (as
#: ``test_live_index.py``); ``ip`` over rows of norm up to 1.5 and ``l2``'s
#: -(|q|^2 - 2 q.x + |x|^2) over unit rows carry that error two to three times
TOL = {"cos": 4e-3, "ip": 1e-2, "l2": 1.2e-2}


# -- (a) the column evaluator against the per-dict predicate ------------------

def _metadata(rng, n: int) -> list:
    """Seeded metadata with what a store's rows really carry and what they
    should not: missing keys, None, mixed types under one key, nested dicts,
    a path that runs into a string, rows with no metadata, one that is a list."""
    def pick(*options):
        return options[rng.integers(len(options))]

    out = []
    for i in range(n):
        kind = rng.integers(12)
        if kind == 0:
            out.append(None)
            continue
        if kind == 1:
            out.append(["not", "a", "dict"])
            continue
        m = {"path": f"t{rng.integers(4)}/d{rng.integers(30)}"}
        if rng.random() < 0.8:
            m["ver"] = pick(0, 1, 2, 1.0, 1.5, True, False, None, "1", "a")
        if rng.random() < 0.6:
            m["size"] = pick(0, 1, 2, 3, 0.5, 2.0)
        if rng.random() < 0.5:
            m["tags"] = pick([], ["red"], ["red", "blue"], ["blue", 1], "red", None)
        if rng.random() < 0.7:
            m["owner"] = pick("ann", {"name": "ann"}, {"name": "t1/*", "team": {"id": 3}},
                              {"name": "bob", "team": {"id": 1}}, {"team": "ops"}, None)
        if rng.random() < 0.5:
            m["flag"] = pick(True, False, None, 0, "")
        out.append(m)
    return out


FILTERS = [
    "path == 't1/d3'", "path != 't1/d3'", "ver == 1", "ver == 1.0", "ver == true",
    "ver != null", "ver == null", "ver == `1`", "ver == '1'", "ver < 2", "ver <= 1",
    "ver > 0", "ver >= 1.5", "ver > 'a'", "path < 't2'", "path >= 't1/d3'",
    "size < ver", "size == ver", "size != ver", "path == owner.name",
    "globmatch('t1/*', path)", "globmatch('*d1?', path)", "globmatch('t[02]/d*', path)",
    "globmatch(owner.name, path)", "globmatch('t1/*', ver)",
    "contains(path, 'd1')", "contains(tags, 'red')", "contains(tags, 1)",
    "contains(path, ver)", "contains(ver, '1')", "contains(path, missing)",
    "starts_with(path, 't1')", "ends_with(path, '7')", "starts_with(owner.name, 'a')",
    "ends_with(ver, 'a')", "owner.team.id == 3", "owner.team.id >= 2",
    "owner.team.id >= 2 && !contains(tags, 'blue')", "owner.name.first == null",
    "!flag", "flag", "flag && ver", "tags || flag", "(ver == 1) == true",
    "!(path == 't0/d0') && (ver == 0 || ver == 2)",
    "globmatch('t0/*', path) || globmatch('t3/*', path) && size > 1",
    "'a' == 'a'", "'a' == 'b'", "missing.key == null", "null == null", "!null",
    "(globmatch('t2/*', path)) && ver == `0`",
]


def _vectors(rng, n: int, metric: str = "cos") -> np.ndarray:
    """Unit rows (``cos`` is normalised by the engine anyway), for ``ip`` of
    norm 0.5 to 1.5: the sizes ``TOL`` is stated for."""
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    if metric == "ip":
        vecs *= rng.uniform(0.5, 1.5, (n, 1)).astype(np.float32)
    return vecs


def _engine_with(metas: list, rng, metric: str = "cos", reserved: int | None = None):
    n = len(metas)
    vecs = _vectors(rng, n, metric)
    engine = BruteForceKnnEngine(DIM, metric=metric, reserved_space=reserved or n + 40)
    # half through the bulk path, half a row at a time
    engine.add_batch(list(range(0, n, 2)), list(vecs[0::2]), metas[0::2])
    for key in range(1, n, 2):
        engine.add(key, vecs[key], metas[key])
    return engine, vecs


def _meta_of(engine, key: int):
    return engine._slots.meta.get(engine._slots.key_to_slot[key])


@pytest.mark.parametrize("source", FILTERS)
def test_the_column_evaluator_gives_what_the_predicate_gives_slot_for_slot(source):
    rng = np.random.default_rng(FILTERS.index(source))
    metas = _metadata(rng, 400)
    engine, _ = _engine_with(metas, rng)
    for key in rng.choice(400, 25, replace=False).tolist():
        engine.remove(key)  # holes: a dead slot is kept by no filter
    predicate = compile_metadata_filter(source)
    want = np.zeros(engine.capacity, bool)
    for key, slot in engine._slots.key_to_slot.items():
        want[slot] = predicate(engine._slots.meta.get(slot))
    got = engine._keeps(parse_metadata_filter(source))
    assert got.dtype == bool and got.shape == (engine.capacity,)
    assert (got == want).all(), np.flatnonzero(got != want)[:5]
    # and at a handful of slots, as a write evaluates it
    at = rng.integers(0, engine.capacity, 8).astype(np.int32)
    assert (engine._keeps(parse_metadata_filter(source), at) == want[at]).all()
    assert 0 < want.sum() < len(metas) or source in (
        "'a' == 'a'", "'a' == 'b'", "null == null", "!null", "missing.key == null",
        "ver > 'a'", "globmatch('t1/*', ver)", "contains(ver, '1')", "ends_with(ver, 'a')",
        "contains(path, missing)", "owner.name.first == null"), "the case tests nothing"


def test_a_callable_filter_is_a_predicate_over_the_whole_metadata():
    rng = np.random.default_rng(5)
    metas = _metadata(rng, 200)
    engine, _ = _engine_with(metas, rng)

    def wanted(meta):
        return isinstance(meta, dict) and meta.get("ver") == 1

    got = engine._keeps(parse_metadata_filter(wanted))
    for key, slot in engine._slots.key_to_slot.items():
        assert got[slot] == wanted(metas[key])
    assert compile_metadata_filter(wanted) is wanted


def test_values_share_a_code_only_where_the_grammar_reads_them_alike():
    col = index_engines._MetaColumn(8)
    values = [1, 1.0, True, "1", None, 0, False, 0.0, -0.0, [1], [1.0], {"a": 1}, "", []]
    codes = [col.encode(v) for v in values]
    assert len(set(codes)) == len(values) and codes[values.index(None)] == 0
    assert [col.encode(v) for v in values] == codes  # and each again its own
    assert [type(col.values[c]) for c in codes] == [type(v) for v in values]


def test_two_columns_are_compared_pair_by_distinct_pair():
    a = (np.array([0, 1, 2, 0, 1, 2]), [1, 2, None])
    b = (np.array([0, 0, 0, 1, 1, 1]), [2, "x"])
    column = {("a",): a, ("b",): b}.__getitem__
    for source in ("a < b", "a == b", "a != b", "contains(b, a)"):
        predicate = compile_metadata_filter(source)
        want = [predicate({"a": a[1][i], "b": b[1][j]}) for i, j in zip(a[0], b[0])]
        assert eval_filter_columns(parse_metadata_filter(source), column, 6).tolist() == want


@pytest.mark.parametrize("source", ["path ==", "globmatch('a' path)", "(a == 1", "a == 1 )", "a ~ 1"])
def test_a_syntax_error_is_raised_as_before(source):
    engine, vecs = _engine_with([{"path": "a"}] * 20, np.random.default_rng(0))
    with pytest.raises(FilterSyntaxError):
        compile_metadata_filter(source)
    with pytest.raises(FilterSyntaxError):
        engine.search([vecs[0]], [3], [source])
    assert [key for key, _ in engine.search([vecs[0]], [1], ["path == 'a'"])[0]] == [0]


# -- (b) replies against the numpy reference ----------------------------------

def _reference(engine, query, source):
    """((key, score) of every row best first, {key: score}): a float32 scan
    over exactly the live rows the per-dict predicate keeps."""
    predicate = compile_metadata_filter(source)
    keys = [key for key, slot in engine._slots.key_to_slot.items()
            if predicate is None or predicate(engine._slots.meta.get(slot))]
    if not keys:
        return [], {}
    rows = engine._host[[engine._slots.key_to_slot[key] for key in keys]]
    q = engine._vec(query)
    scores = rows @ q if engine.metric != "l2" else -((rows - q) ** 2).sum(axis=1)
    order = np.argsort(-scores, kind="stable")
    return [(keys[i], float(scores[i])) for i in order], dict(zip(keys, scores.tolist()))


def _check(engine, queries, filters, k: int = K) -> None:
    tol = TOL[engine.metric]
    replies = engine.search(list(queries), [k] * len(queries), list(filters))
    assert len(replies) == len(queries)
    for query, source, reply in zip(queries, filters, replies):
        want, exact = _reference(engine, query, source)
        keys = [key for key, _ in reply]
        # every live row of the scope up to k, none twice, none from outside it
        assert len(reply) == min(k, len(exact)), (source, reply)
        assert len(set(keys)) == len(keys) and set(keys) <= set(exact), (source, keys)
        for rank, (key, score) in enumerate(reply):
            assert abs(score - exact[key]) <= tol, (source, key, score, exact[key])
            assert want[rank][1] - exact[key] <= tol  # no better row was passed over
            near = [s for j, (_, s) in enumerate(want[:rank + 2]) if j != rank]
            if all(abs(s - want[rank][1]) > 2 * tol for s in near):
                assert key == want[rank][0], (source, rank, reply, want)


def _folders(n: int, folders: int = 6) -> list:
    return [{"path": f"t{i % folders}/d{i // 4}", "ver": 0} for i in range(n)]


def _queries(rng, vecs, q: int):
    """Near rows that are there, and anywhere; of unit length."""
    near = vecs[rng.integers(0, len(vecs), q // 2)] + 0.05 * _vectors(rng, q // 2)
    near /= np.linalg.norm(near, axis=1, keepdims=True)
    return list(near) + list(_vectors(rng, q - q // 2))


@pytest.mark.parametrize("q", [1, 5, 16])
@pytest.mark.parametrize("metric", list(TOL))
def test_a_batch_of_several_filters_a_repeated_one_and_none(metric, q):
    rng = np.random.default_rng(q)
    engine, vecs = _engine_with(_folders(600), rng, metric)
    mix = ["globmatch('t1/*', path)", None, "globmatch('t3/*', path)",
           "globmatch('t1/*', path)", "starts_with(path, 't5/') && ver == `0`"]
    filters = [mix[i % len(mix)] for i in range(q)]
    _check(engine, _queries(rng, vecs, q), filters)
    # the same searches over again are served from the cache
    built = SERVE_STATS["index_filter_masks_built_total"]
    hits = SERVE_STATS["index_filter_mask_hits_total"]
    _check(engine, _queries(rng, vecs, q), filters)
    assert SERVE_STATS["index_filter_masks_built_total"] == built
    assert SERVE_STATS["index_filter_mask_hits_total"] - hits == sum(f is not None for f in filters)


def test_a_search_with_no_filter_runs_the_program_it_ran_before():
    rng = np.random.default_rng(2)
    engine, vecs = _engine_with(_folders(300), rng)
    queries = _queries(rng, vecs, 3)
    plain = engine.search(queries, [K] * 3, [None] * 3)
    shapes = knn.topk_scores._cache_size()
    engine.search(queries, [K] * 3, ["globmatch('t1/*', path)", None, None])  # [8, n]
    assert knn.topk_scores._cache_size() == shapes + 1
    assert engine.search(queries, [K] * 3, [None] * 3) == plain  # valid [n], as before
    assert knn.topk_scores._cache_size() == shapes + 1
    assert not engine._masks or list(engine._masks) == ["globmatch('t1/*', path)"]
    untouched = BruteForceKnnEngine(DIM, reserved_space=64)
    untouched.add_batch([0, 1], list(vecs[:2]), [{"path": "a"}, None])
    untouched.search([vecs[0]], [1], [None])
    assert not untouched._columns and not untouched._masks  # no filter, nothing built


# -- (c) scopes of fewer than k rows, of none, of all --------------------------

@pytest.mark.parametrize("metric", list(TOL))
def test_a_small_an_empty_and_a_whole_scope(metric):
    rng = np.random.default_rng(8)
    metas = _folders(300)
    for i in range(4):
        metas[i] = {"path": f"few/d{i}", "ver": 0}
    engine, vecs = _engine_with(metas, rng, metric)
    queries = _queries(rng, vecs, 4)
    filters = ["globmatch('few/*', path)", "globmatch('nobody/*', path)",
               "ver == `0`", None]
    _check(engine, queries, filters)
    few, nobody, whole, plain = engine.search(queries, [K] * 4, filters)
    assert sorted(key for key, _ in few) == [0, 1, 2, 3] and nobody == []
    assert len(whole) == K and [key for key, _ in whole] == [
        key for key, _ in engine.search([queries[2]], [K], [None])[0]]
    engine.remove(2)  # and a scope a delete empties further
    assert sorted(key for key, _ in engine.search(queries[:1], [K], filters[:1])[0]) == [0, 1, 3]
    _check(engine, queries, filters)


# -- (d) under writes, a new tier, a pickle -----------------------------------

@pytest.mark.parametrize("metric", list(TOL))
def test_cached_masks_follow_writes_in_place_and_are_dropped_with_the_block(metric):
    rng = np.random.default_rng(4)
    engine, vecs = _engine_with(_folders(500), rng, metric, reserved=1024)
    filters = ["globmatch('t1/*', path)", "globmatch('t2/*', path)", None,
               "globmatch('t1/*', path) && ver == `1`"]
    queries = _queries(rng, vecs, 4)
    _check(engine, queries, filters)

    def counters():
        return {k: SERVE_STATS[k] for k in (
            "index_filter_masks_built_total", "index_filter_masks_dropped_total",
            "index_uploads_total", "index_writes_total")}

    before, masks = counters(), {key: id(entry) for key, entry in engine._masks.items()}
    assert len(masks) == 3
    fresh = _vectors(rng, 80, metric)
    # add into t1, move a row from t2 to t1 (a replace that changes folder, by
    # the bulk path and by the single one), delete, add back elsewhere
    engine.add(9000, fresh[0], {"path": "t1/d9000", "ver": 1})
    moved = [key for key in range(500) if _meta_of(engine, key)["path"].startswith("t2/")][:3]
    engine.add_batch(moved[:2], list(fresh[1:3]),
                     [{"path": "t1/moved", "ver": 1}, {"path": "t1/moved", "ver": 0}])
    engine.add(moved[2], fresh[3], {"path": "t1/moved", "ver": 1})
    gone = [key for key in range(500) if _meta_of(engine, key)["path"].startswith("t1/")][:5]
    for key in gone:
        engine.remove(key)
    _check(engine, queries, filters)
    engine.add(gone[0], fresh[4], {"path": "t2/back", "ver": 1})
    engine.add_batch(list(range(9100, 9170)), list(fresh[5:75]),  # a write in two buckets
                     [{"path": f"t{i % 3}/new", "ver": 1} for i in range(70)])
    _check(engine, queries, filters)
    after = counters()
    # the block and the masks were written in place: nothing placed, nothing
    # rebuilt, and the very entries are still the cache's
    assert after["index_uploads_total"] == before["index_uploads_total"]
    assert after["index_writes_total"] == before["index_writes_total"] + 2
    assert after["index_filter_masks_built_total"] == before["index_filter_masks_built_total"]
    assert after["index_filter_masks_dropped_total"] == before["index_filter_masks_dropped_total"]
    assert {key: id(entry) for key, entry in engine._masks.items()} == masks
    for source, (ast, mask) in engine._masks.items():
        assert (np.asarray(mask) == engine._keeps(ast)).all(), source

    # a new tier drops columns and masks with the block, and the next search
    # builds them from the metadata again
    engine.add_batch(list(range(20000, 20600)), list(_vectors(rng, 600, metric)),
                     _folders(600))
    assert engine.capacity == 2048 and not engine._masks and not engine._columns
    assert SERVE_STATS["index_filter_masks_dropped_total"] == after[
        "index_filter_masks_dropped_total"] + 3
    _check(engine, queries, filters)
    assert SERVE_STATS["index_filter_masks_built_total"] == after[
        "index_filter_masks_built_total"] + 3

    # a pickle carries neither; the restored engine answers as the reference
    state = engine.__getstate__()
    assert "_masks" not in state and "_columns" not in state and "_device" not in state
    restored = pickle.loads(pickle.dumps(engine))
    assert not restored._masks and not restored._columns and restored._device is None
    _check(restored, queries, filters)
    restored.add(moved[0], fresh[75], {"path": "t2/again", "ver": 1})
    _check(restored, queries, filters)


def test_a_whole_placement_drops_the_masks():
    rng = np.random.default_rng(6)
    engine, vecs = _engine_with(_folders(200), rng)
    queries, filters = _queries(rng, vecs, 2), ["globmatch('t1/*', path)", None]
    _check(engine, queries, filters)
    engine._valid[100:] = False  # behind the engine's back: dirty, nothing staged
    engine._dirty = True
    for key in [key for key, slot in engine._slots.key_to_slot.items() if slot >= 100]:
        engine._slots.release(key)
    _check(engine, queries, filters)
    assert all(engine._slots.key_to_slot[key] < 100
               for key, _ in engine.search(queries[:1], [K], filters[:1])[0])


def test_the_cache_is_bounded_by_bytes_and_drops_the_least_recently_used(monkeypatch):
    rng = np.random.default_rng(9)
    engine, vecs = _engine_with(_folders(120), rng, reserved=128)
    monkeypatch.setattr(index_engines, "MASK_CACHE_BYTES", 3 * engine.capacity)
    sources = [f"globmatch('t{i}/*', path)" for i in range(5)]
    dropped = SERVE_STATS["index_filter_masks_dropped_total"]
    for source in sources[:3]:
        _check(engine, [vecs[0]], [source])
    _check(engine, [vecs[0]], [sources[0]])  # used again: now the newest
    _check(engine, [vecs[0]], [sources[3]])
    assert list(engine._masks) == [sources[2], sources[0], sources[3]]
    assert SERVE_STATS["index_filter_masks_dropped_total"] == dropped + 1
    # a search of more filters than the cache holds still answers each
    _check(engine, list(vecs[:5]), sources)
    assert len(engine._masks) == 3


def test_a_column_of_ever_new_values_is_built_afresh_not_grown_without_end():
    rng = np.random.default_rng(10)
    engine, vecs = _engine_with(_folders(16), rng, reserved=16)
    source = "stamp >= 100"
    _check(engine, [vecs[0]], [source])
    for stamp in range(100):
        engine.add(stamp % 16, vecs[stamp % 16], {"path": "t0/d0", "stamp": stamp})
        if ("stamp",) in engine._columns:
            assert len(engine._columns[("stamp",)].values) <= 2 * engine.capacity
    engine.add(3, vecs[3], {"path": "t0/d0", "stamp": 100})
    _check(engine, [vecs[3]], [source])
    assert [key for key, _ in engine.search([vecs[3]], [K], [source])[0]] == [3]


# -- (e) one scan a search -----------------------------------------------------

@pytest.mark.parametrize("filters", [
    [None] * 4,
    ["globmatch('t1/*', path)"] * 4,
    ["globmatch('t1/*', path)", "globmatch('t2/*', path)", "globmatch('t3/*', path)", None],
    [f"globmatch('t{i}/*', path)" for i in range(6)] + [None, "ver == `0`", "ver == `1`"],
])
def test_a_search_makes_one_topk_scores_call_whatever_its_filters(filters, monkeypatch):
    rng = np.random.default_rng(len(filters))
    engine, vecs = _engine_with(_folders(300), rng)
    calls, scan = [], knn.topk_scores

    def counted(queries, index, k, metric="cos", valid=None):
        calls.append((queries.shape, valid.shape))
        return scan(queries, index, k, metric, valid=valid)

    monkeypatch.setattr(knn, "topk_scores", counted)
    queries = _queries(rng, vecs, len(filters))
    for _ in range(2):  # masks built, then masks cached
        engine.search(queries, [K] * len(filters), filters)
    if any(f is not None for f in filters):
        q = {4: 8, 9: 16}[len(filters)]  # padded to a power of two, 8 at the least
        assert calls == [((q, DIM), (q, engine.capacity))] * 2
    else:
        assert calls == [((len(filters), DIM), (engine.capacity,))] * 2


def test_the_spans_of_a_filtered_search(tmp_path):
    rng = np.random.default_rng(12)
    engine, vecs = _engine_with(_folders(200), rng)
    filters = ["globmatch('t1/*', path)", None, "globmatch('t1/*', path)", "ver == `0`"]
    tracer = tracing.activate(str(tmp_path / "trace.json"))
    try:
        engine.search(list(vecs[:4]), [K] * 4, filters)
        engine.add(7, vecs[7], {"path": "t1/d7", "ver": 0})
        engine.search(list(vecs[:4]), [K] * 4, filters)
        events, _ = tracer.events_since(0)
    finally:
        tracing.deactivate()
    spans = [e for e in events if e.get("ph") == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e.get("args") or {})
    first, second = by_name["index.search"]
    assert (first["filtered"], first["filters"], second["filtered"]) == (3, 2, 3)
    cold, warm = by_name["index.mask"]
    assert (cold["filtered"], cold["distinct"], cold["hits"], cold["built"]) == (3, 2, 0, 2)
    assert (warm["filtered"], warm["distinct"], warm["hits"], warm["built"]) == (3, 2, 3, 0)
    assert cold["parent"] == "index.search"
    builds = by_name["index.mask.build"]
    assert [b["columns_built"] for b in builds] == [1, 1] and builds[0]["parent"] == "index.mask"
    assert builds[0]["slots"] == engine.capacity and builds[0]["kept"] == 34
    [update] = by_name["index.mask.update"]
    assert (update["masks"], update["slots"], update["parent"]) == (2, 1, "index.write")


# -- (f) through the REST route -------------------------------------------------

@contextlib.contextmanager
def _store(rows: int, folders: int):
    from pathway_tpu.internals.run import request_stop
    from pathway_tpu.io.http._server import terminate_all
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    rng = np.random.default_rng(13)
    vecs = rng.standard_normal((rows, DIM)).astype(np.float32)
    fed, stop = threading.Event(), threading.Event()

    class Feed(pw.io.python.ConnectorSubject):
        def run(self):
            self.next_batch({
                "id": np.arange(rows, dtype=np.int64),
                "data": [f"row{i}" for i in range(rows)],
                "_metadata": [{"path": f"t{i % folders}/d{i}", "ver": 0} for i in range(rows)],
                "vec": list(vecs)})
            self.commit()
            fed.set()
            stop.wait()

    schema = pw.schema_builder({
        "id": pw.column_definition(dtype=int, primary_key=True),
        "data": str, "_metadata": dict, "vec": np.ndarray})
    docs = pw.io.python.read(Feed(), schema=schema, autocommit_duration_ms=None)
    store = DocumentStore(
        docs, BruteForceKnnFactory(dimensions=DIM, reserved_space=rows, metric="cos",
                                   embedder=lambda text: vecs[int(text)]),
        vector_column="vec")
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
        for _ in range(400):
            if post({}, "/v1/statistics")[1].get("file_count") == rows:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("the index was not built")
        yield post, vecs
    finally:
        stop.set()
        request_stop()
        terminate_all()
        thread.join(60)
        G.clear()


def test_a_retrieve_is_confined_by_either_filter_field_to_one_answer():
    with _store(rows=240, folders=6) as (post, vecs):
        for folder, query in [(1, "7"), (4, "100"), (1, "9")]:
            status, by_glob = post({"query": query, "k": K,
                                    "filepath_globpattern": f"t{folder}/*"})
            assert status == 200 and len(by_glob) == K, by_glob
            status, by_filter = post({"query": query, "k": K, "metadata_filter":
                                      f"globmatch('t{folder}/*', path)"})
            assert status == 200 and by_filter == by_glob
            assert all(hit["metadata"]["path"].startswith(f"t{folder}/") for hit in by_glob)
            # the float32 scan over the folder's rows, best first
            rows = np.arange(folder, 240, 6)
            unit = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
            scores = unit[rows] @ unit[int(query)]
            want = [f"row{rows[i]}" for i in np.argsort(-scores)[:K]]
            got = [hit["text"] for hit in by_glob]
            assert set(got[:5]) == set(want[:5]) or got == want, (got, want)
        status, both = post({"query": "7", "k": K, "filepath_globpattern": "t1/*",
                             "metadata_filter": "ver == `1`"})
        assert status == 200 and both == []
        status, plain = post({"query": "7", "k": K})
        assert status == 200 and plain[0]["text"] == "row7"
        assert len({hit["metadata"]["path"].split("/")[0] for hit in plain}) > 1
