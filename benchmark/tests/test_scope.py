"""Scope, in pure numpy on the CPU: the folders and the clients' bindings from
the seed, the bytes a request and a row carry with and without it, the
reference's mask against the grammar the program parses (the one place the two
meet), and how a scoped reply is judged."""

import hashlib
import json
import os
import subprocess
import sys

import harness
import numpy as np
import pytest
import run as bench_run
from lib import check, datagen, family, loadgen, traffic

SEED = 2147483659
REHEARSAL = os.path.join(harness.TESTS, "rehearsal")
META = {"tenants": 8, "tenant_zipf_s": 1.0, "path": "t{tenant}/d{doc}"}
SCOPE = {"field": "filepath_globpattern", "template": "t{tenant}/*", "bind": "client",
         "client_zipf_s": 1.0, "share_unscoped": 0.125, "passage_in_scope": 0.75}
DOCS, CHUNKS, K = 512, 4, 10


def _load(kind, name):
    with open(os.path.join(REHEARSAL, kind, name + ".json")) as f:
        return json.load(f)


def _pool(seed=SEED):
    cfg, mix = _load("configs", "tiny-bert"), _load("traffic", "read-c4")
    fam = family.load(cfg)
    _, words = datagen.vocabulary(seed, cfg["vocab_size"], fam)
    return traffic.QueryPool(seed, words, mix["query"], fam.FRAMING)


def _scope(seed=SEED, plan=None, **changed):
    folders = datagen.doc_folders(seed, DOCS, META)
    return traffic.Scope(seed, {**SCOPE, **changed}, folders, _pool(seed), 4, CHUNKS, plan)


def test_without_scope_a_request_and_a_row_are_the_parents_bytes():
    # golden digests from the parent commit's code (PR 31) for this seed: 50
    # requests of each of the four clients of tiny-bert.read-c4, three rows
    pool, bodies = _pool(), hashlib.sha256()
    for c in range(4):
        ask = bench_run.reader_queries(pool, None, c)
        for _ in range(50):
            qid, text, folder = ask()
            assert folder is None
            bodies.update(loadgen.request_body(text, K, {}).encode())
    assert bodies.hexdigest() == \
        "7ed4e4849a258245e88cb8ae03bc3f221023239dd8817aaf21f4600771c03e20"
    first = bench_run.reader_queries(pool, None, 0)()
    assert loadgen.request_body(first[1], K, {}) == '{"query": "yjprz mnw fowry", "k": 10}'
    assert not datagen.doc_folders(SEED, DOCS, None).any()  # one folder holds all
    rows = hashlib.sha256()
    for d, v in [(0, 0), (17, 3), (511, 1)]:
        rows.update(json.dumps(traffic.row_metadata(d, v)).encode())
    assert rows.hexdigest() == \
        "f339589336f805ea62d5e943adb303bb3de6805bfb0011f3631ffe3309526b36"
    assert json.dumps(traffic.row_metadata(17, 3)) == '{"path": "d17", "ver": 3}'


def test_with_scope_a_request_carries_the_filter_and_a_row_its_folder():
    scope = _scope()
    assert loadgen.request_body("a b", K, scope.body_fields(3)) == \
        '{"query": "a b", "k": 10, "filepath_globpattern": "t3/*"}'
    by_filter = _scope(field="metadata_filter", template="globmatch('t{tenant}/*', path)")
    assert by_filter.body_fields(3) == {"metadata_filter": "globmatch('t3/*', path)"}
    assert traffic.row_metadata(17, 3, 5, META["path"]) == {"path": "t5/d17", "ver": 3}
    with pytest.raises(ValueError):
        _scope(field="query")
    with pytest.raises(ValueError):
        _scope(bind="tick")


def test_folders_and_bindings_are_the_same_in_another_process():
    scope = _scope()
    qids, folders = scope.client_requests(2, 256)
    here = {"folders": datagen.doc_folders(SEED, DOCS, META).tolist(),
            "clients": scope.client_folder.tolist(), "home": scope.home.tolist(),
            "qids": qids.tolist(), "asked": folders.tolist()}
    code = (
        "import json, sys; sys.path.insert(0, sys.argv[1])\n"
        "import test_scope as t\n"
        "s = t._scope(); q, f = s.client_requests(2, 256)\n"
        "print(json.dumps({'folders': t.datagen.doc_folders(t.SEED, t.DOCS, t.META).tolist(),"
        " 'clients': s.client_folder.tolist(), 'home': s.home.tolist(),"
        " 'qids': q.tolist(), 'asked': f.tolist()}))\n")
    out = subprocess.run([sys.executable, "-c", code, harness.TESTS], capture_output=True,
                         text=True, timeout=120, check=True, cwd=harness.TESTS,
                         env=dict(os.environ, PYTHONHASHSEED="7"))
    assert json.loads(out.stdout.strip().splitlines()[-1]) == here


def test_every_seed_gets_the_same_sizes_in_another_order():
    a, b = _scope(SEED), _scope(SEED + 1)
    assert sorted(a.rows_in.tolist()) == sorted(b.rows_in.tolist())
    assert a.rows_in.tolist() == [c * CHUNKS for c in (186, 94, 63, 47, 38, 32, 28, 24)]
    assert (a.folder_of_doc != b.folder_of_doc).any()
    # the clients' folders: the quantiles of Zipf 1.0 over 8 folders, shuffled
    assert sorted(a.client_folder.tolist()) == sorted(b.client_folder.tolist()) == [0, 1, 2, 5]
    # a client keeps its folder, asks only texts at home there, and sends one
    # request in eight with no filter
    qids, folders = a.client_requests(1, 800)
    own = int(a.client_folder[1])
    assert set(folders.tolist()) == {own, -1} and (folders == -1).sum() == 100
    assert (a.home[qids] == own).all()
    # bound anew every request: the folders follow the Zipf, the text its folder
    qids, folders = _scope(bind="request").client_requests(1, 4000)
    scoped = folders >= 0
    assert (a.home[qids[scoped]] == folders[scoped]).all()
    share = np.bincount(folders[scoped], minlength=8) / scoped.sum()
    assert np.abs(share - datagen.zipf_weights(8, 1.0)).max() < 0.03


def test_the_references_mask_is_what_the_programs_filter_keeps():
    # the scope made by construction against the grammar the program parses:
    # the only place where the harness's folders meet pathway_tpu
    from pathway_tpu.utils.filters import compile_metadata_filter
    from pathway_tpu.xpacks.llm.document_store import DocumentStore

    scope = _scope()
    by_filter = _scope(field="metadata_filter", template="globmatch('t{tenant}/*', path)")
    metas = [traffic.row_metadata(d, 0, int(scope.folder_of_doc[d]), META["path"])
             for d in range(DOCS) for _ in range(CHUNKS)]
    for folder in range(scope.folders):
        for fields in (scope.body_fields(folder), by_filter.body_fields(folder)):
            keep = compile_metadata_filter(DocumentStore.merge_filters(
                fields.get("metadata_filter"), fields.get("filepath_globpattern")))
            kept = np.array([keep(m) for m in metas])
            assert (kept == scope.mask(folder)).all()
            assert kept.sum() == scope.rows_in[folder]


def test_passages_lie_inside_and_outside_their_texts_folder():
    scope = _scope()
    free = datagen.stream(SEED, 99).permutation(DOCS).tolist()
    docs = scope.place_passages(free)
    assert len(set(docs)) == len(docs) == len(scope.home)
    at_home = scope.folder_of_doc[docs] == scope.home
    assert (at_home == scope.passage_inside).all()
    assert at_home.sum() == 48  # 0.75 of the 64 texts
    with pytest.raises(ValueError):
        scope.place_passages(free[:40])


def _reply(scope, rows, query="q"):
    return {"query": query, "scope": scope, "rows": rows}


def test_out_of_scope_counts_a_stray_row_and_reads_0_on_a_clean_reply():
    folders = np.array([0, 0, 1, 1, 2])
    clean = _reply(1, [(2, 0, 0, 0.5), (3, 1, 0, 0.4)])
    stray = _reply(1, [(2, 0, 0, 0.5), (4, 0, 0, 0.4), (9, 0, 0, 0.3)])  # another's; none
    unfiltered = _reply(None, [(0, 0, 0, 0.5), (4, 0, 0, 0.4)])
    assert check.out_of_scope([clean, unfiltered], folders) == 0
    assert check.out_of_scope([clean, stray, unfiltered], folders) == 2


def test_a_scope_with_fewer_than_k_live_rows_is_judged_on_all_of_them():
    folders = np.repeat(np.arange(3), [4, 2, 250]).astype(np.int32)  # 16, 8, 1000 rows
    pool = _pool()
    scope = traffic.Scope(SEED, SCOPE, folders, pool, 4, CHUNKS)
    assert scope.rows_wanted(0, K) == (10, 10) and scope.rows_wanted(1, K) == (8, 8)
    hits = [{"text": traffic.row_text(4 + i // 4, i % 4, 0), "dist": -0.5 + i / 100}
            for i in range(8)]
    assert len(check.reply_rows(hits, *scope.rows_wanted(1, K))) == 8   # every row of it
    assert check.reply_rows(hits[:7], *scope.rows_wanted(1, K)) is None  # one short
    assert check.reply_rows(hits + hits[:1], 8, 8) is None               # one too many
    assert check.reply_rows(hits, K, K) is None  # and a request with no filter wants k
    # a delete inside the window takes a document's chunks out for a while
    plan = type("Plan", (), {"commits": [traffic.Commit(0, 0.0, "delete", 5, None, 0)]})
    shrunk = traffic.Scope(SEED, SCOPE, folders, pool, 4, CHUNKS, plan)
    assert shrunk.rows_wanted(1, K) == (4, 8) and shrunk.rows_wanted(2, K) == (10, 10)
    # the rank gap is taken against the folder's own best, not the store's
    vec = {"q": np.array([1.0, 0.0])}
    rows = {(4, 0): np.array([0.6, 0.8]), (5, 0): np.array([0.5, 0.5])}
    reply = _reply(1, [(4, 0, 0, 0.6), (5, 0, 0, 0.5)])
    top = {("q", 1): np.array([0.6, 0.5] + [-np.inf] * 8), ("q", None): np.full(10, 0.9)}
    gap, err, bad = check.compare_sample(
        [reply], vec, top, lambda d, c, v: rows.get((d, c)), set())
    assert (gap, bad) == (0.0, 0) and err < 1e-12
    gap, _, _ = check.compare_sample(
        [dict(reply, scope=None)], vec, top, lambda d, c, v: rows.get((d, c)), set())
    assert gap == pytest.approx(0.4)


# The model family's cases (``family_cases.py``: the BERT family's file against
# golden values, a family added as one file) are taken in here, so that the
# tier-1 tests count them through ``tests/test_benchmark_scope.py``.
import family_cases  # noqa: E402

globals().update(
    {name: case for name, case in vars(family_cases).items() if name.startswith("test_")})
