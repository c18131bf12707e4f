"""LLM xpack: splitters, prompts, rerankers, DocumentStore, RAG answerers
(reference test model: python/pathway/xpacks/llm tests — fake chats and
embedders, no network)."""

from __future__ import annotations

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
from pathway_tpu.xpacks.llm import prompts
from pathway_tpu.xpacks.llm.document_store import DocumentStore
from pathway_tpu.xpacks.llm.llms import BaseChat
from pathway_tpu.xpacks.llm.question_answering import (
    AdaptiveRAGQuestionAnswerer,
    BaseRAGQuestionAnswerer,
    answer_with_geometric_rag_strategy,
)
from pathway_tpu.xpacks.llm.rerankers import EncoderReranker, rerank_topk_filter
from pathway_tpu.xpacks.llm.splitters import NullSplitter, TokenCountSplitter


@pytest.fixture(autouse=True)
def _clean_graph():
    G.clear()
    yield
    G.clear()


def fake_embed(text: str) -> np.ndarray:
    v = np.zeros(16)
    for ch in str(text)[:400]:
        v[ord(ch) % 16] += 1.0
    return v / (np.linalg.norm(v) or 1.0)


class EchoDocsChat(BaseChat):
    """Fake chat: answers with the count of 'Sources'/'Articles' docs seen —
    lets tests assert what context reached the model."""

    def _call_model(self, messages, **kwargs):
        return "reply: " + messages[-1]["content"][:40]


DOCS = [
    ("TPUs multiply matrices on a systolic array called the MXU.", {"path": "tpu.txt", "modified_at": 3}),
    ("Kafka is a distributed message broker for event streams.", {"path": "kafka.txt", "modified_at": 7}),
    ("Croissants are made with laminated butter dough.", {"path": "food.txt", "modified_at": 5}),
]


def _store(splitter=None):
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str, _metadata=dict), DOCS
    )
    return DocumentStore(
        docs,
        BruteForceKnnFactory(dimensions=16, embedder=fake_embed),
        splitter=splitter or NullSplitter(),
    )


def _rows(table):
    cap = pw.debug.table_to_dicts(table)
    return cap


def test_token_count_splitter_bounds():
    s = TokenCountSplitter(min_tokens=3, max_tokens=6)
    text = "one two three. four five six. seven eight. nine ten eleven twelve."
    chunks = s.__wrapped__(text)
    assert len(chunks) >= 2
    for chunk, meta in chunks:
        assert len(chunk.split()) <= 6
    # nothing lost
    rejoined = " ".join(c for c, _ in chunks)
    assert rejoined.split() == text.split()


def test_document_store_retrieve_and_filter():
    store = _store()
    queries = pw.debug.table_from_rows(
        DocumentStore.RetrieveQuerySchema,
        [("systolic array MXU matrices", 2, None, None)],
    )
    [row] = pw.debug.table_to_pandas(store.retrieve_query(queries))["result"].tolist()
    assert row[0]["metadata"]["path"] == "tpu.txt"
    assert len(row) == 2

    G.clear()
    store = _store()
    queries = pw.debug.table_from_rows(
        DocumentStore.RetrieveQuerySchema,
        [("systolic array MXU matrices", 2, None, "kafka*")],
    )
    [row] = pw.debug.table_to_pandas(store.retrieve_query(queries))["result"].tolist()
    assert [d["metadata"]["path"] for d in row] == ["kafka.txt"]


def test_document_store_statistics_and_inputs():
    store = _store()
    stats_q = pw.debug.table_from_rows(DocumentStore.StatisticsQuerySchema, [()])
    [stats] = pw.debug.table_to_pandas(store.statistics_query(stats_q))["result"].tolist()
    assert stats == {"file_count": 3, "last_modified": 7}

    G.clear()
    store = _store()
    inputs_q = pw.debug.table_from_rows(
        DocumentStore.InputsQuerySchema, [(None, None)]
    )
    [files] = pw.debug.table_to_pandas(store.inputs_query(inputs_q))["result"].tolist()
    assert {f["path"] for f in files} == {"tpu.txt", "kafka.txt", "food.txt"}


def test_base_rag_answer_query():
    store = _store()
    rag = BaseRAGQuestionAnswerer(EchoDocsChat(), store, search_topk=2)
    queries = pw.debug.table_from_rows(
        rag.AnswerQuerySchema,
        [("what is the MXU?", None, None, False)],
    )
    [ans] = pw.debug.table_to_pandas(rag.answer_query(queries))["result"].tolist()
    assert ans.startswith("reply:")


def test_base_rag_answer_returns_context_docs():
    store = _store()
    rag = BaseRAGQuestionAnswerer(EchoDocsChat(), store, search_topk=2)
    queries = pw.debug.table_from_rows(
        rag.AnswerQuerySchema,
        [("what is the MXU?", None, None, True)],
    )
    [ans] = pw.debug.table_to_pandas(rag.answer_query(queries))["result"].tolist()
    assert set(ans.keys()) == {"response", "context_docs"}
    assert len(ans["context_docs"]) == 2


class CountingChat(BaseChat):
    """Refuses until it sees >= need docs in the prompt (Articles block)."""

    def __init__(self, need: int, **kwargs):
        super().__init__(**kwargs)
        self.need = need
        self.calls: list[int] = []

    def _call_model(self, messages, **kwargs):
        content = messages[-1]["content"]
        articles = content.split("Articles:\n", 1)[1].rsplit("\n\nQ:", 1)[0]
        n_docs = len([p for p in articles.split("\n\n") if p.strip()])
        self.calls.append(n_docs)
        if n_docs >= self.need:
            return f"answered with {n_docs} docs"
        return prompts.NO_INFO_ANSWER


def test_geometric_rag_strategy_expands_until_answer():
    chat = CountingChat(need=4)
    docs = [f"doc {i}" for i in range(8)]
    ans = answer_with_geometric_rag_strategy(
        "q?", docs, chat, n_starting_documents=1, factor=2, max_iterations=4
    )
    assert ans == "answered with 4 docs"
    assert chat.calls == [1, 2, 4]


def test_geometric_rag_strategy_gives_up():
    chat = CountingChat(need=100)
    ans = answer_with_geometric_rag_strategy(
        "q?", ["a", "b"], chat, n_starting_documents=1, factor=2, max_iterations=3
    )
    assert ans == prompts.NO_INFO_ANSWER


def test_adaptive_rag_answer_query():
    store = _store()
    chat = CountingChat(need=1)
    rag = AdaptiveRAGQuestionAnswerer(
        chat, store, n_starting_documents=1, factor=2, max_iterations=3
    )
    queries = pw.debug.table_from_rows(
        rag.AnswerQuerySchema,
        [("what is the MXU?", None, None, False)],
    )
    [ans] = pw.debug.table_to_pandas(rag.answer_query(queries))["result"].tolist()
    assert ans == "answered with 1 docs"


def test_encoder_reranker_and_topk():
    class FakeEmbedderUDF:
        def __wrapped__(self, text):
            return fake_embed(text)

    rr = EncoderReranker(FakeEmbedderUDF())
    same = rr.__wrapped__("hello world", "hello world")
    diff = rr.__wrapped__("hello world", "zzzzzz qqqq")
    assert same > diff

    docs, scores = rerank_topk_filter(
        ["a", "b", "c"], [0.1, 0.9, 0.5], k=2
    )
    assert docs == ("b", "c") and scores == (0.9, 0.5)


def test_summarize_query():
    store = _store()
    rag = BaseRAGQuestionAnswerer(EchoDocsChat(), store)
    q = pw.debug.table_from_rows(
        rag.SummarizeQuerySchema, [((["text one", "text two"],))]
    )
    [ans] = pw.debug.table_to_pandas(rag.summarize_query(q))["result"].tolist()
    assert ans.startswith("reply:")


def test_qa_rest_server_roundtrip():
    """Full serve path over HTTP: answer/retrieve/statistics/list_documents
    (reference integration_tests/webserver + xpack QARestServer)."""
    import time

    from pathway_tpu.internals.run import request_stop
    from pathway_tpu.io.http._server import terminate_all
    from pathway_tpu.xpacks.llm.question_answering import RAGClient
    from pathway_tpu.xpacks.llm.servers import QASummaryRestServer

    class FactChat(BaseChat):
        def _call_model(self, messages, **kw):
            c = messages[-1]["content"]
            if "MXU" in c and "systolic" in c:
                return "The MXU is the systolic array."
            return prompts.NO_INFO_ANSWER

    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str, _metadata=dict), DOCS
    )
    store = DocumentStore(
        docs, BruteForceKnnFactory(dimensions=16, embedder=fake_embed)
    )
    rag = AdaptiveRAGQuestionAnswerer(
        FactChat(), store, n_starting_documents=1, factor=2, max_iterations=2
    )
    server = QASummaryRestServer("127.0.0.1", 18737, rag)
    try:
        server.run(threaded=True)
        time.sleep(1.0)
        client = RAGClient(url="http://127.0.0.1:18737", timeout=20)
        assert client.answer("what is the MXU?") == "The MXU is the systolic array."
        hits = client.retrieve("systolic array MXU matrices", k=1)
        assert [d["metadata"]["path"] for d in hits] == ["tpu.txt"]
        assert client.statistics()["file_count"] == 3
        assert {d["path"] for d in client.list_documents()} == {
            "tpu.txt", "kafka.txt", "food.txt"
        }
    finally:
        request_stop()
        terminate_all()
        if server._thread is not None:
            server._thread.join(timeout=10)


def test_document_store_pre_embedded_mode():
    # vector_column: docs arrive as chunks with precomputed embeddings;
    # the index scores those vectors while queries go through the embedder
    rows = [
        (text, meta, fake_embed(text)) for text, meta in DOCS
    ]
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str, _metadata=dict, vec=np.ndarray), rows
    )
    store = DocumentStore(
        docs,
        BruteForceKnnFactory(dimensions=16, embedder=fake_embed),
        vector_column="vec",
    )
    queries = pw.debug.table_from_rows(
        DocumentStore.RetrieveQuerySchema,
        [("systolic array MXU matrices", 2, None, None)],
    )
    [row] = pw.debug.table_to_pandas(store.retrieve_query(queries))["result"].tolist()
    assert row[0]["metadata"]["path"] == "tpu.txt"
    assert row[0]["text"].startswith("TPUs multiply")
    assert len(row) == 2


def _seeded_store(n: int, dim: int, seed: int):
    """``n`` pre-embedded rows from ``seed`` and a store over them whose
    embedder maps a query ``q<j>`` to the ``j``-th of 8 seeded vectors."""
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, dim)).astype(np.float32)
    probes = rng.standard_normal((8, dim)).astype(np.float32)
    rows = [
        (f"chunk {i}", {"path": f"dir{i % 3}/f{i}.txt", "owner": f"o{i % 5}"}, vecs[i])
        for i in range(n)
    ]
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str, _metadata=dict, vec=np.ndarray), rows
    )
    factory = BruteForceKnnFactory(
        dimensions=dim, reserved_space=64, metric="cos",
        embedder=lambda text: probes[int(text[1:])],
    )
    return DocumentStore(docs, factory, vector_column="vec"), factory


def _asked_and_answered(store, queries) -> list[tuple]:
    """(query text, reply) of every row of ``queries``, in row-id order."""
    asked = pw.debug.table_to_pandas(queries)["query"]
    got = pw.debug.table_to_pandas(store.retrieve_query(queries))["result"]
    return [(asked[key], got[key]) for key in asked.index]


def _holds_array(value) -> bool:
    if isinstance(value, np.ndarray):
        return True
    if isinstance(value, dict):
        return any(_holds_array(v) for v in value.values())
    return isinstance(value, (tuple, list)) and any(_holds_array(v) for v in value)


def test_a_pre_embedded_store_keeps_the_vectors_out_of_the_reply_path():
    from pathway_tpu.internals.graph_runner import GraphRunner

    store, _ = _seeded_store(40, 16, seed=3)
    assert store.index.data_table.column_names() == ["text", "_metadata"]
    assert store.chunked_documents.column_names() == ["text", "_metadata", "_pw_vector"]
    queries = pw.debug.table_from_rows(
        DocumentStore.RetrieveQuerySchema,
        [("q0", 5, None, None), ("q1", 3, "owner == 'o2'", None), ("q2", 4, None, "dir1/*")],
    )
    result = store.retrieve_query(queries)
    # every table from the reply (the index node's output) and the documents'
    # side of the join down to ``result``: the index's own input, above the
    # reply, is the one place the vectors are
    seen, todo = {}, [result]
    while todo:
        table = todo.pop()
        if id(table) in seen:
            continue
        seen[id(table)] = table
        if table._kind != "custom" and table is not store.index.data_table:
            todo.extend(table._inputs)
    between = list(seen.values())
    kinds = {t._kind for t in between}
    assert "custom" in kinds and id(store.index.data_table) in seen
    assert {"flatten", "join_select", "groupby_reduce", "update_rows"} <= kinds, kinds
    rows = 0
    for table, cap in zip(between, GraphRunner().run_tables(*between)):
        for _, row in cap.state.iter_items():
            rows += 1
            assert not _holds_array(row), (table._kind, table.column_names())
    assert rows > 40 + 3 * 4
    r0, r1, r2 = (reply for _, reply in sorted(_asked_and_answered(store, queries)))
    assert len(r0) == 5 and set(r0[0]) == {"text", "metadata", "dist"}
    assert [d["metadata"]["owner"] for d in r1] == ["o2"] * 3
    assert all(d["metadata"]["path"].startswith("dir1/") for d in r2) and len(r2) == 4


def test_replies_equal_those_of_a_store_whose_data_table_carries_the_vector():
    queries_rows = [(f"q{j}", 10, None, None) for j in range(8)] + [
        ("q1", 7, "owner == 'o3'", None), ("q5", 25, None, "dir2/*"),
        ("q6", 1, "owner == 'o0'", "dir0/*"), ("q7", 3, "owner == 'nobody'", None),
    ]

    def replies(carry_vector: bool):
        G.clear()
        store, factory = _seeded_store(300, 24, seed=17)
        if carry_vector:
            # what the store handed ``DataIndex`` before: the chunk table whole
            chunked = store.chunked_documents
            store.index = factory.build_index(
                pw.ColumnReference(chunked, "_pw_vector"), chunked,
                metadata_column=pw.this._metadata,
            )
            assert "_pw_vector" in store.index.data_table.column_names()
        queries = pw.debug.table_from_rows(DocumentStore.RetrieveQuerySchema, queries_rows)
        return _asked_and_answered(store, queries)

    without, carried = replies(False), replies(True)
    assert len(without) == len(carried) == len(queries_rows)
    for (q_a, a), (q_b, b) in zip(without, carried):
        assert q_a == q_b and len(a) == len(b)
        for hit_a, hit_b in zip(a, b):
            assert set(hit_a) == set(hit_b) == {"text", "metadata", "dist"}
            assert hit_a["text"] == hit_b["text"]
            assert hit_a["metadata"] == hit_b["metadata"]
            assert hit_a["dist"] == hit_b["dist"]  # score by score, to the bit
        dists = [hit["dist"] for hit in a]
        assert dists == sorted(dists)
    assert sorted(len(a) for _, a in without) == sorted([10] * 8 + [7, 25, 1, 0])


def test_brute_force_bulk_add_matches_per_row():
    from pathway_tpu.ops.index_engines import BruteForceKnnEngine

    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((300, 16)).astype(np.float32)
    a = BruteForceKnnEngine(16, reserved_space=16)
    b = BruteForceKnnEngine(16, reserved_space=16)
    for i, v in enumerate(vecs):
        a.add(i, v, {"path": f"{i}.txt"} if i % 3 == 0 else None)
    b.add_batch(
        list(range(300)), list(vecs),
        [{"path": f"{i}.txt"} if i % 3 == 0 else None for i in range(300)],
    )
    # updates through the bulk path replace, not duplicate
    b.add_batch([7, 8], [vecs[7], vecs[8]], [None, None])
    a.add(7, vecs[7], None)
    a.add(8, vecs[8], None)
    q = rng.standard_normal((4, 16)).astype(np.float32)
    ra = a.search(list(q), [5] * 4, [None] * 4)
    rb = b.search(list(q), [5] * 4, [None] * 4)
    assert [[k for k, _ in r] for r in ra] == [[k for k, _ in r] for r in rb]
    # metadata filters survive the bulk path
    [fa] = a.search([q[0]], [3], ["globmatch('9.txt', path)"])
    [fb] = b.search([q[0]], [3], ["globmatch('9.txt', path)"])
    assert [k for k, _ in fa] == [k for k, _ in fb] == [9]


def test_vector_store_adapter_constructors_gated():
    # reference vector_store.py:92/:135 — LangChain / LlamaIndex adapter
    # constructors exist and gate on their client libraries
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    t = pw.debug.table_from_markdown("data\nhello")
    with pytest.raises(ImportError, match="langchain_core"):
        VectorStoreServer.from_langchain_components(t, embedder=object())
    with pytest.raises(ImportError, match="llama-index-core"):
        VectorStoreServer.from_llamaindex_components(
            t, transformations=[object()]
        )
