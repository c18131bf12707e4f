"""DocumentStore — live parse→split→index retrieval over any connector.

Re-design of ``python/pathway/xpacks/llm/document_store.py:32``: documents
stream in from connectors (``data`` bytes + optional ``_metadata``), are
parsed and chunked by UDFs, and indexed by an ``InnerIndexFactory``
(TPU brute-force/LSH KNN, BM25, or hybrid — ``pathway_tpu/stdlib/indexing``).
Retrieval/statistics/inputs queries are live tables, so answers update as
documents change.
"""

from __future__ import annotations

import json
from typing import Any, Callable, Iterable

import pathway_tpu as pw
from ...internals import dtype as dt
from ...internals.expression import apply_with_type
from ...internals.table import Table
from ...internals.thisclass import this
from ...stdlib.indexing.data_index import _SCORE
from ._utils import doc_dicts

__all__ = ["DocumentStore", "SlidesDocumentStore"]


class DocumentStore:
    """parse → (post-process) → split → index; query surfaces mirroring the
    reference: ``retrieve_query``, ``statistics_query``, ``inputs_query``."""

    class RetrieveQuerySchema(pw.Schema):
        query: str
        k: int
        metadata_filter: str | None = pw.column_definition(default_value=None)
        filepath_globpattern: str | None = pw.column_definition(default_value=None)

    class StatisticsQuerySchema(pw.Schema):
        pass

    class InputsQuerySchema(pw.Schema):
        metadata_filter: str | None = pw.column_definition(default_value=None)
        filepath_globpattern: str | None = pw.column_definition(default_value=None)

    def __init__(
        self,
        docs: Table | Iterable[Table],
        retriever_factory: Any,
        parser: Callable | None = None,
        splitter: Callable | None = None,
        doc_post_processors: list[Callable] | None = None,
        vector_column: str | None = None,
    ):
        from .splitters import NullSplitter

        if isinstance(docs, Table):
            docs_list = [docs]
        else:
            docs_list = list(docs)
        if not docs_list:
            raise ValueError("DocumentStore needs at least one docs table")
        self.docs = self._ensure_metadata(
            docs_list[0]
            if len(docs_list) == 1
            else docs_list[0].concat_reindex(*docs_list[1:])
        )
        self.parser = parser or self.default_parser()
        self.splitter = splitter or NullSplitter()
        self.doc_post_processors = doc_post_processors or []
        self.retriever_factory = retriever_factory
        #: pre-embedded mode: when set, ``docs`` rows are already chunks and
        #: this column holds their embedding vectors — parse/split are
        #: skipped and the index is built over the vectors directly (the
        #: retriever's embedder then only embeds queries). The common
        #: "embeddings computed offline / by another pipeline" deployment.
        #: The vectors live in the index and are no part of a reply or of
        #: ``DataIndex``'s right side: carried there, every matched row's
        #: 3-4 KB array was joined, grouped and hashed cell by cell in
        #: Python each tick, for a column ``retrieve_query`` never reads.
        self.vector_column = vector_column
        self.build_pipeline()

    @staticmethod
    def default_parser():
        from .parsers import ParseUtf8

        return ParseUtf8()

    # ------------------------------------------------------------------

    def _ensure_metadata(self, table: Table) -> Table:
        if "_metadata" not in table.column_names():
            return table.with_columns(_metadata=apply_with_type(
                lambda d: {}, dt.ANY, this.data
            ))
        mdt = dt.unoptionalize(table.schema.columns()["_metadata"].dtype)
        if mdt == dt.STR:
            # connectors (pw.io.fs/s3 with_metadata=True) deliver the
            # metadata as a JSON string; the pipeline merges dicts
            return table.with_columns(_metadata=apply_with_type(
                lambda m: json.loads(m) if m else {}, dt.ANY, this._metadata
            ))
        return table

    def build_pipeline(self) -> None:
        docs = self.docs

        if self.vector_column is not None:
            # pre-embedded chunks: index straight over the vector column
            chunked = docs.select(
                text=this.data,
                _metadata=this._metadata,
                _pw_vector=this[self.vector_column],
            )
            self.parsed_documents = chunked.select(
                text=this.text, _metadata=this._metadata
            )
            self.chunked_documents = chunked
            # indexed over the vectors, repacked from what a reply shows
            # (same universe and row ids as ``chunked``)
            self.index = self.retriever_factory.build_index(
                pw.ColumnReference(chunked, "_pw_vector"),
                self.parsed_documents,
                metadata_column=this._metadata,
            )
            return

        # parse: data -> [(text, meta)]; one row per parsed part
        parsed = docs.select(
            parts=self.parser(this.data), _metadata=this._metadata
        ).flatten(this.parts)
        parsed = parsed.select(
            text=apply_with_type(lambda p: p[0], dt.STR, this.parts),
            _metadata=apply_with_type(
                lambda p, m: {**(m or {}), **(p[1] or {})},
                dt.ANY, this.parts, this._metadata,
            ),
        )
        for post in self.doc_post_processors:
            parsed = parsed.select(
                text=apply_with_type(post, dt.STR, this.text),
                _metadata=this._metadata,
            )
        self.parsed_documents = parsed

        # split: text -> [(chunk, meta)]; one row per chunk
        chunked = parsed.select(
            chunks=self.splitter(this.text), _metadata=this._metadata
        ).flatten(this.chunks)
        chunked = chunked.select(
            text=apply_with_type(lambda c: c[0], dt.STR, this.chunks),
            _metadata=apply_with_type(
                lambda c, m: {**(m or {}), **(c[1] or {})},
                dt.ANY, this.chunks, this._metadata,
            ),
        )
        self.chunked_documents = chunked

        self.index = self.retriever_factory.build_index(
            pw.ColumnReference(chunked, "text"),
            chunked,
            metadata_column=this._metadata,
        )

    # ------------------------------------------------------------------

    @staticmethod
    def merge_filters(metadata_filter: str | None, globpattern: str | None) -> str | None:
        """Combine a metadata filter and a path glob into one filter string
        (reference document_store.py _get_jmespath_filter)."""
        parts = []
        if metadata_filter:
            parts.append(f"({metadata_filter})")
        if globpattern:
            if "'" in globpattern:
                # the filter grammar's string literals have no escape form
                raise ValueError(
                    "filepath_globpattern must not contain single quotes"
                )
            parts.append(f"globmatch('{globpattern}', path)")
        return " && ".join(parts) if parts else None

    def retrieve_query(self, retrieval_queries: Table) -> Table:
        """One row per query: ``result`` = tuple of doc dicts
        (text/metadata/score as ``dist``), most relevant first."""
        queries = retrieval_queries.with_columns(
            __filter=apply_with_type(
                self.merge_filters, dt.Optional(dt.STR),
                this.metadata_filter, this.filepath_globpattern,
            ),
        )
        res = self.index.query_as_of_now(
            pw.ColumnReference(queries, "query"),
            number_of_matches=this.k,
            collapse_rows=True,
            metadata_filter=this["__filter"],
        ).select(
            qid=pw.left.id,
            result=apply_with_type(
                doc_dicts, dt.ANY,
                pw.right.text, pw.right._metadata, pw.right[_SCORE],
            )
        )
        # key results by the incoming query rows (REST writers complete
        # responses by row key)
        return res.with_id(this.qid).select(result=this.result)

    def statistics_query(self, info_queries: Table) -> Table:
        """Global doc-count/last-modified stats per query row
        (reference document_store.py statistics_query)."""
        counts = self.docs.reduce(
            count=pw.reducers.count(),
            last_modified=pw.reducers.max(apply_with_type(
                lambda m: int((m or {}).get("modified_at", 0)), dt.INT,
                this._metadata,
            )),
        )
        stats = counts.select(
            __one=0,
            result=apply_with_type(
                lambda c, lm: {"file_count": int(c), "last_modified": int(lm)},
                dt.ANY, this.count, this.last_modified,
            )
        )
        tagged = info_queries.with_columns(__one=0)
        joined = tagged.join_left(
            stats, pw.left["__one"] == pw.right["__one"]
        ).select(qid=pw.left.id, result=pw.right.result)
        return joined.with_id(this.qid).select(result=this.result)

    def inputs_query(self, input_queries: Table) -> Table:
        """List indexed input files (path + metadata) per query row.

        A query meets the documents when it arrives: the documents' side of
        the join takes a write as the rows it changes. (One tuple of every
        document's metadata, kept up to date for queries to join, would be
        built anew and hashed whole by every write: seconds a commit at a
        million documents, with no inputs query anywhere.)"""
        from ...utils.filters import compile_metadata_filter

        def list_files(metas, metadata_filter, globpattern):
            flt = DocumentStore.merge_filters(metadata_filter, globpattern)
            pred = compile_metadata_filter(flt) if flt else None
            out = []
            for m in metas or ():
                m = m or {}
                if pred is None or pred(m):
                    out.append({"path": m.get("path"), **m})
            return tuple(out)

        docs = self.docs.select(__one=0, _metadata=this._metadata)
        tagged = input_queries.with_columns(__one=0)
        # left, so that a query over an empty store is answered too; its
        # one padded row carries no metadata and the tuple skips it
        listed = tagged.join_left(
            docs, pw.left["__one"] == pw.right["__one"]
        ).select(
            qid=pw.left.id,
            metadata_filter=pw.left.metadata_filter,
            filepath_globpattern=pw.left.filepath_globpattern,
            _metadata=pw.right._metadata,
        )
        grouped = listed.groupby(this.qid).reduce(
            qid=this.qid,
            result=apply_with_type(
                list_files, dt.ANY,
                pw.reducers.tuple(this._metadata, skip_nones=True),
                pw.reducers.any(this.metadata_filter),
                pw.reducers.any(this.filepath_globpattern),
            ),
        )
        return grouped.with_id(this.qid).select(result=this.result)


class SlidesDocumentStore(DocumentStore):
    """Slide-deck flavor of the store (reference document_store.py:471):
    identical pipeline whose default parser is the slide pipeline
    (``parsers.SlideParser`` — per-slide parts with title/notes metadata,
    vision stage injectable), so decks land one searchable part per slide."""

    @staticmethod
    def default_parser():
        from .parsers import SlideParser

        return SlideParser()

    def parsed_documents_with_metadata(self) -> Table:
        return self.parsed_documents
