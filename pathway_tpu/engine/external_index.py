"""External-index engine operator: live index maintenance + query answering.

Re-design of the reference's ``UseExternalIndexAsOfNow`` timely operator
(``src/engine/dataflow/operators/external_index.rs:38``) and the native index
engines behind it (``src/external_integration/``: USearch HNSW, Tantivy BM25,
brute-force KNN). Two differences, both TPU-first:

- the vector scoring path is an XLA kernel (bf16 matmul on the MXU + top-k)
  instead of a CPU HNSW graph walk — see ``ops/knn.py``;
- besides the reference's as-of-now semantics this node also supports
  *maintained* semantics (``DataIndex.query``): when the indexed data
  changes, every stored query is re-answered and the node emits
  retract/insert diffs for answers that changed, which is what the
  reference achieves with its differential join machinery.

The node's contract: input 0 is the indexed-data stream (columns
``__data__`` and optionally ``__filter_data__``), input 1 the query stream
(``__query__``, ``__limit__``, optionally ``__filter__``). Output is keyed
by query key with one column ``_pw_index_reply`` holding a tuple of
``(matched_key, score)`` pairs, best first.

Scale-out serving (``PATHWAY_SERVE_SHARDED=1``, as-of-now indexes under a
sharded run whose comm supports the serve seam): instead of gathering the
whole index to worker 0, the data stream hash-shards to owner workers —
each worker's engine holds only its ``shard_rows`` slice — while queries
still gather to worker 0, which fans each batch out over
``serve/router.py``'s scatter/gather and merges per-shard top-k
(``serve/merge.py``, generalizing ``ops/knn.py``'s single-host
gather-merge). A dead or slow shard degrades the answer (flagged through
``serve/status.py`` to the REST edge) instead of hanging it.
"""

from __future__ import annotations

import weakref
from typing import Any, Protocol

import numpy as np

from .delta import Delta
from .executor import Node

__all__ = ["IndexEngine", "ExternalIndexNode", "REPLY_COLUMN"]

REPLY_COLUMN = "_pw_index_reply"

#: per-WorkerContext count of ExternalIndexNode.on_shard calls. on_shard
#: runs in node_id order within each worker's own graph build, and every
#: worker lowers the same program, so the ordinal is a construction-order
#: node identity that AGREES across workers and processes (raw node_id
#: does not: each thread worker's build advances the global id counter).
_serve_ordinals: "weakref.WeakKeyDictionary[Any, int]" = (
    weakref.WeakKeyDictionary()
)


def _next_serve_ordinal(ctx: Any) -> int:
    n = _serve_ordinals.get(ctx, -1) + 1
    _serve_ordinals[ctx] = n
    return n


def _serve_sharding_enabled() -> bool:
    import os

    return os.environ.get("PATHWAY_SERVE_SHARDED", "0").strip().lower() in (
        "1", "true", "yes", "on",
    )


class IndexEngine(Protocol):
    """Host-side mutable index; scoring may run on device (TPU)."""

    def add(self, key: int, data: Any, filter_data: Any) -> None: ...

    def remove(self, key: int) -> None: ...

    def search(
        self, queries: list[Any], limits: list[int], filters: list[Any]
    ) -> list[list[tuple[int, float]]]:
        """For each query: [(key, score), ...] best-first, honoring filters."""
        ...


class ExternalIndexNode(Node):
    def __init__(self, data_node: Node, query_node: Node, engine: IndexEngine,
                 *, asof_now: bool, serve_sharded: bool | None = None):
        super().__init__([data_node, query_node], [REPLY_COLUMN])
        self.engine = engine
        self.asof_now = asof_now
        #: None = consult PATHWAY_SERVE_SHARDED at shard time
        self.serve_sharded = serve_sharded
        # query key -> (data, limit, filter, last_reply)
        self._queries: dict[int, list[Any]] = {}
        # asof-now mode still must retract answers when the *query* retracts
        self._answered: dict[int, tuple] = {}
        # set by on_shard when scale-out serving activates
        self._serve_router: Any = None
        self._serve_handle: Any = None
        self._serve_node_key: Any = None
        self._serve_worker: int = 0

    # the engine (host arenas; device caches are dropped by the engines'
    # __getstate__) snapshots alongside the standing queries
    STATE_FIELDS = ("engine", "_queries", "_answered")

    # gather-routed: the whole index lives on worker 0 under any layout.
    # (Sharded-serve mode hash-shards the engine; each worker snapshots
    # and restores its own slice, which supervised recovery at unchanged
    # worker count — the serve smoke's regime — round-trips exactly.
    # Offline RESCALE of a sharded index is out of scope: run it with
    # PATHWAY_SERVE_SHARDED=0.)
    RESHARD = "pinned"

    def restore_state(self, state: dict) -> None:
        fresh = self.engine
        super().restore_state(state)
        # non-picklable config (embedder closures) carries over from the
        # freshly-built engine — see BruteForceKnnEngine.__getstate__
        if getattr(self.engine, "embedder", None) is None:
            self.engine.embedder = getattr(fresh, "embedder", None)

    def on_shard(self, ctx) -> None:
        ordinal = _next_serve_ordinal(ctx)
        want = (
            self.serve_sharded
            if self.serve_sharded is not None
            else _serve_sharding_enabled()
        )
        if not want or not self.asof_now or not ctx.is_sharded:
            # maintained semantics re-answer standing queries on every
            # index change, which a worker can't do over peer shards it
            # never sees — scale-out serving is as-of-now only
            return
        comm = ctx.comm
        if comm is None or not getattr(comm, "supports_serve", lambda: False)():
            return
        from ..serve.registry import registry
        from ..serve.router import get_router

        self._serve_node_key = ("xidx", ordinal)
        self._serve_worker = ctx.worker_id
        self._serve_handle = registry().register(
            self._serve_node_key, ctx.worker_id, self._shard_search
        )
        self._serve_router = get_router(comm, ctx.n_workers)

    def _shard_search(
        self, queries: list[Any], limits: list[int], filters: list[Any]
    ) -> list:
        """Responder entry (router dispatcher thread): search this
        worker's shard. The ShardHandle holds its lock around this call;
        ``process`` takes the same lock while mutating the engine."""
        return self.engine.search(list(queries), list(limits), list(filters))

    def exchange_specs(self):
        if self._serve_router is not None:
            # scale-out serving: data hash-shards to owner workers (each
            # engine holds its shard_rows slice); queries still gather to
            # worker 0, the scatter origin
            return [("key",), ("gather",)]
        # the index lives on worker 0 (sharded index variants live at the
        # ops layer: ops/knn.py sharded_topk with all-gather merge)
        return [("gather",), ("gather",)]

    def _serve_scatter(self, keys: list[int], entries: list[list]) -> list:
        """Answer a query batch by scatter/gather over every shard worker;
        deposits per-key degraded status for the REST edge to pick up."""
        from ..serve import status as serve_status
        from ..serve.merge import deadline_from_ms, default_deadline_ms

        deadlines = [serve_status.take_deadline(k) for k in keys]
        known = [d for d in deadlines if d is not None]
        # one scatter per batch: the widest per-query deadline bounds the
        # batch (each edge still enforces its own, tighter wait)
        deadline_ns = (
            max(known) if known else deadline_from_ms(default_deadline_ms())
        )
        res = self._serve_router.scatter_search(
            self._serve_node_key,
            self._serve_worker,
            [e[0] for e in entries],
            [e[1] for e in entries],
            [e[2] for e in entries],
            deadline_ns=deadline_ns,
        )
        if res["degraded"] or res["deadline_exceeded"]:
            st = {
                "degraded": res["degraded"],
                "missing_shards": res["missing_shards"],
                "deadline_exceeded": res["deadline_exceeded"],
            }
            for k in keys:
                serve_status.note_status(k, st)
        return res["hits"]

    def process(self, time: int, in_deltas: list[Delta | None]) -> Delta | None:
        data_d, query_d = in_deltas
        index_changed = False
        if data_d is not None and len(data_d):
            if self._serve_handle is not None:
                # serve responders search concurrently from the router's
                # dispatcher threads: mutate under the shard lock so no
                # search observes a half-applied tick
                with self._serve_handle.lock:
                    self._apply_data(data_d)
            else:
                self._apply_data(data_d)
            index_changed = True

        out_keys: list[int] = []
        out_replies: list[tuple] = []
        out_diffs: list[int] = []

        new_qkeys: list[int] = []
        if query_d is not None and len(query_d):
            qcols = query_d.data
            qdatas = qcols["__query__"]
            qlimits = qcols.get("__limit__")
            qfilters = qcols.get("__filter__")
            # retractions first: an in-tick update may carry (+new, -old) in
            # either order and must land as the new query
            qorder = np.argsort(query_d.diffs, kind="stable")
            for i in qorder:
                k = int(query_d.keys[i])
                q = qdatas[i]
                lim = int(qlimits[i]) if qlimits is not None else 3
                flt = qfilters[i] if qfilters is not None else None
                if query_d.diffs[i] > 0:
                    self._queries[k] = [q, lim, flt, None]
                    new_qkeys.append(k)
                else:
                    self._queries.pop(k, None)
                    prev = self._answered.pop(k, None)
                    if prev is not None:
                        out_keys.append(k)
                        out_replies.append(prev)
                        out_diffs.append(-1)

        # answer new queries against the current index state
        if new_qkeys:
            entries = [self._queries[k] for k in new_qkeys]
            if self._serve_router is not None:
                replies = self._serve_scatter(new_qkeys, entries)
            else:
                replies = self.engine.search(
                    [e[0] for e in entries], [e[1] for e in entries],
                    [e[2] for e in entries],
                )
            for k, rep in zip(new_qkeys, replies):
                reply = tuple((int(mk), float(s)) for mk, s in rep)
                out_keys.append(k)
                out_replies.append(reply)
                out_diffs.append(1)
                self._answered[k] = reply
                if not self.asof_now:
                    self._queries[k][3] = reply
            if self.asof_now:
                for k in new_qkeys:
                    self._queries.pop(k, None)

        # maintained semantics: index changed → re-answer standing queries
        if index_changed and not self.asof_now and self._queries:
            fresh = set(new_qkeys)
            standing = [k for k in self._queries if k not in fresh]
            if standing:
                entries = [self._queries[k] for k in standing]
                replies = self.engine.search(
                    [e[0] for e in entries], [e[1] for e in entries],
                    [e[2] for e in entries],
                )
                for k, rep in zip(standing, replies):
                    reply = tuple((int(mk), float(s)) for mk, s in rep)
                    prev = self._queries[k][3]
                    if prev == reply:
                        continue
                    if prev is not None:
                        out_keys.append(k)
                        out_replies.append(prev)
                        out_diffs.append(-1)
                    out_keys.append(k)
                    out_replies.append(reply)
                    out_diffs.append(1)
                    self._queries[k][3] = reply
                    self._answered[k] = reply

        if not out_keys:
            return None
        data = np.empty(len(out_replies), dtype=object)
        for i, r in enumerate(out_replies):
            data[i] = r
        return Delta(
            keys=np.array(out_keys, dtype=np.uint64),
            data={REPLY_COLUMN: data},
            diffs=np.array(out_diffs, dtype=np.int64),
        )

    def _apply_data(self, data_d: Delta) -> None:
        from ..internals.tracing import span
        from ..serve.stats import bump

        diffs = data_d.diffs
        added = int(diffs[diffs > 0].sum())
        removed = int(-diffs[diffs < 0].sum())
        bump("index_rows_added_total", added)
        bump("index_rows_removed_total", removed)
        with span("index.apply", added=added, removed=removed):
            # several commits may arrive as one delta (a connector merges
            # the windows it is behind by): a retraction then stands beside
            # its own insert, and only the net change is this tick's. What
            # is left goes removals first, so an update (retract + insert of
            # one key, in either order) lands as the new value.
            data_d = data_d.consolidated()
            cols, keys, diffs = data_d.data, data_d.keys, data_d.diffs
            filt = cols.get("__filter_data__")
            datas = cols["__data__"]
            for k in keys[diffs < 0].tolist():
                self.engine.remove(k)
            pos = np.flatnonzero(diffs > 0)
            if len(pos):
                add_keys = keys[pos].tolist()
                add_datas = [datas[i] for i in pos]
                add_filts = [filt[i] for i in pos] if filt is not None else [None] * len(pos)
                add_batch = getattr(self.engine, "add_batch", None)
                if add_batch is not None:
                    # one batched embed + insert per tick, not per document
                    add_batch(add_keys, add_datas, add_filts)
                else:
                    for k, d, f in zip(add_keys, add_datas, add_filts):
                        self.engine.add(k, d, f)
