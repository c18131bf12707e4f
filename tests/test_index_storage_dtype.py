"""The device copy of an index is kept in the dtype the scan multiplies in
(``ops/knn.py::storage_dtype``): bfloat16 for ``cos`` and ``ip``, rounded once
when a row is written, float32 for ``l2``, whose norms read float32. Held to
what the scan gave before, ``topk_scores`` over the float32 host block cast
inside the call: the replies are equal to the last bit through adds, in-place
replaces, removes, a new tier and a pickle. A tier's programs all compile at
its placement, and a placement never holds two whole blocks."""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.internals import tracing
from pathway_tpu.ops import knn
from pathway_tpu.ops.index_engines import BruteForceKnnEngine
from pathway_tpu.ops.knn import ShardedKnnIndex, WRITE_BUCKETS, topk_scores
from pathway_tpu.serve.stats import SERVE_STATS

DIM, K = 64, 10
STORED = {"cos": jnp.bfloat16, "ip": jnp.bfloat16, "l2": jnp.float32}
STAGES = ("placed", "replaced", "removed", "grown", "pickled")


def _vectors(rng, n: int, dim: int = DIM) -> np.ndarray:
    return rng.standard_normal((n, dim)).astype(np.float32)


def _search(engine, queries):
    return engine.search(list(queries), [K] * len(queries), [None] * len(queries))


def _as_before(engine, queries):
    """The replies of the scan over the float32 block, cast inside the call."""
    q = np.stack([engine._vec(x) for x in queries])
    s, ids = topk_scores(jnp.asarray(q), jnp.asarray(engine._host), K, engine.metric,
                         valid=jnp.asarray(engine._valid))
    s, ids = np.asarray(s), np.asarray(ids)
    return [engine._pack(s[i], ids[i], K) for i in range(len(queries))]


def _engine_at(stage: str, metric: str, rng) -> BruteForceKnnEngine:
    """An engine taken through the stages up to ``stage``, with a search after
    each so that every later write finds a device copy to write into."""
    engine = BruteForceKnnEngine(DIM, metric=metric, reserved_space=256)
    probe = _vectors(rng, 1)
    engine.add_batch(list(range(200)), list(_vectors(rng, 200)), [None] * 200)
    for step in STAGES[1:STAGES.index(stage) + 1]:
        _search(engine, probe)
        uploads = SERVE_STATS["index_uploads_total"]
        if step == "replaced":  # keys that are there: written in place
            engine.add_batch(list(range(40, 70)), list(_vectors(rng, 30)), [None] * 30)
            engine.add(7, _vectors(rng, 1)[0], None)
        elif step == "removed":  # holes in the tier, one of them filled again
            for key in range(0, 200, 3):
                engine.remove(key)
            engine.add(1000, _vectors(rng, 1)[0], None)
        elif step == "grown":  # over the tier: a new block, placed whole
            engine.add_batch(list(range(2000, 2300)), list(_vectors(rng, 300)), [None] * 300)
            assert engine.capacity > 256 and engine._device is None
        elif step == "pickled":
            engine = pickle.loads(pickle.dumps(engine))
            assert engine._device is None
        if step in ("replaced", "removed"):
            _search(engine, probe)
            assert SERVE_STATS["index_uploads_total"] == uploads  # written in place
    return engine


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("q", [1, 15])
@pytest.mark.parametrize("metric", list(STORED))
def test_replies_equal_the_scan_over_the_float32_block(metric, q, stage):
    rng = np.random.default_rng(STAGES.index(stage) * 31 + q)
    engine = _engine_at(stage, metric, rng)
    live = engine._host[engine._valid]
    # near rows that are there, and anywhere
    queries = list(live[rng.integers(0, len(live), q // 2)] + 0.05 * _vectors(rng, q // 2))
    queries += list(_vectors(rng, q - len(queries)))
    replies = _search(engine, queries)
    assert engine._device.dtype == STORED[metric]
    assert engine._host.dtype == np.float32
    assert engine._device.shape == engine._host.shape
    assert all(len(r) == K for r in replies)
    assert replies == _as_before(engine, queries)  # keys and scores, to the bit


@pytest.mark.parametrize("metric", list(STORED))
def test_every_program_of_a_tier_compiles_at_its_placement(metric):
    rng = np.random.default_rng(3)
    engine = BruteForceKnnEngine(DIM, metric=metric, reserved_space=2048)
    engine.add_batch(list(range(1500)), list(_vectors(rng, 1500)), [None] * 1500)
    queries = list(_vectors(rng, 4))
    assert _search(engine, queries) == _as_before(engine, queries)
    sizes = (topk_scores._cache_size(), knn.index_writer()._cache_size(),
             knn.index_fill._cache_size())
    uploads = SERVE_STATS["index_uploads_total"]
    cap = WRITE_BUCKETS[-1]
    for n in (1, 8, 9, 64, 100, cap, cap + 1, 2 * cap + 70):
        keys = rng.choice(1500, size=n, replace=False)
        engine.add_batch([int(key) for key in keys], list(_vectors(rng, n)), [None] * n)
        assert _search(engine, queries) == _as_before(engine, queries)
    assert (topk_scores._cache_size(), knn.index_writer()._cache_size(),
            knn.index_fill._cache_size()) == sizes
    assert SERVE_STATS["index_uploads_total"] == uploads


@pytest.mark.parametrize("metric", list(STORED))
def test_a_placement_holds_one_block_and_one_chunk(metric, monkeypatch, tmp_path):
    rows, chunk, dim = 1000, 64, 72  # not a multiple: the last chunk reaches back
    monkeypatch.setattr(knn, "PLACE_ROWS", chunk)
    fill, seen = knn.index_fill, []

    def watched(block, piece, start):
        # a width no other test uses, so what is counted is this engine's
        wide = [a for a in jax.live_arrays() if a.ndim == 2 and a.shape[1] == dim]
        seen.append((block.dtype, piece.dtype, piece.shape, int(start),
                     sum(a.nbytes for a in wide)))
        return fill(block, piece, start)

    monkeypatch.setattr(knn, "index_fill", watched)
    rng = np.random.default_rng(11)
    engine = BruteForceKnnEngine(dim, metric=metric, reserved_space=rows)
    engine.add_batch(list(range(rows)), list(_vectors(rng, rows, dim)), [None] * rows)
    queries = list(_vectors(rng, 2, dim))
    tracer = tracing.activate(str(tmp_path / "trace.json"))
    try:
        _search(engine, queries)
        # behind with nothing staged: the whole block once more, the old one gone first
        engine._valid[rows // 2:] = False
        engine._dirty = True
        replies = _search(engine, queries)
        events, _ = tracer.events_since(0)
    finally:
        tracing.deactivate()
    stored = jnp.dtype(STORED[metric])
    block = rows * dim * stored.itemsize
    starts = [min(s, rows - chunk) for s in range(0, rows, chunk)]
    assert [s[3] for s in seen] == starts * 2
    for block_dtype, piece_dtype, shape, _, resident in seen:
        assert (block_dtype, piece_dtype, shape) == (stored, np.float32, (chunk, dim))
        # at a chunk's start the device holds the block being filled and
        # nothing else of its width: no float32 copy, no block of before
        assert resident == block
    assert np.array_equal(np.asarray(engine._device), engine._host.astype(stored))
    assert replies == _as_before(engine, queries)
    uploads = [e["args"] for e in events if e["name"] == "index.upload"]
    assert [(u["whole"], u["bytes"], u["dtype"]) for u in uploads] == \
        [(True, rows * dim * 4, stored.name)] * 2


@pytest.mark.parametrize("metric", list(STORED))
def test_sharded_index_stores_what_its_scan_multiplies(metric):
    rng = np.random.default_rng(5)
    index = ShardedKnnIndex(DIM, capacity=256, metric=metric)
    rows = _vectors(rng, 200)
    index.add(rows)
    assert index._data.dtype == STORED[metric]
    block = np.zeros((256, DIM), np.float32)
    block[:200] = rows
    queries = _vectors(rng, 15)
    s, ids = index.query(queries, K)
    want_s, want_ids = topk_scores(jnp.asarray(queries), jnp.asarray(block), K, metric,
                                   valid=jnp.asarray(np.arange(256) < 200))
    assert np.array_equal(ids, np.asarray(want_ids)) and np.array_equal(s, np.asarray(want_s))
