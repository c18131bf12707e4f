"""Serve-plane counters — the ``serve.*`` observability surface.

Module-global like ``engine/fusion.py``'s FUSION_STATS and
``io/python.py``'s INGEST_STAGE_STATS: every component of the serve
plane bumps these under a lock, and the observability hub snapshots
them into ``/snapshot`` / ``/query`` documents, the
``pathway_serve_*`` prometheus families, the ``serve.*`` signals
series (which the autoscale decider consumes) and the ``pathway-tpu
top`` serve line.

The snapshot is EMPTY until the serve plane has actually done
something, so expositions of pipelines that never serve stay
byte-identical to the seed's.
"""

from __future__ import annotations

import threading
from typing import Callable

__all__ = [
    "SERVE_STATS",
    "bump",
    "serve_stats_snapshot",
    "register_gauge_provider",
    "reset_serve_stats",
]

#: monotone counters; every key ends ``_total`` (the serve_metrics gate
#: checks this — prometheus renders _total keys as counters)
SERVE_STATS: dict[str, int] = {
    #: queries admitted at the edge (one per accepted REST request)
    "queries_total": 0,
    #: queries refused with 429 (saturated: queue at bound)
    "rejected_total": 0,
    #: queries that waited in the admission queue before a slot freed
    "queued_total": 0,
    #: queries dropped at ANY hop because their deadline had passed
    "deadline_dropped_total": 0,
    #: gathers that completed with at least one shard missing
    "degraded_total": 0,
    #: cross-worker scatter posts (one per remote shard per query batch)
    "scatter_posts_total": 0,
    #: per-shard searches executed (local + remote responders)
    "shard_searches_total": 0,
    #: gathers merged into a final result (degraded or not)
    "results_merged_total": 0,
    #: duplicate shard results discarded by correlation-id dedup
    "duplicate_results_total": 0,
    #: admission slots cancelled by client disconnect
    "cancelled_total": 0,
    #: shard responder errors surfaced as failed shards
    "errors_total": 0,
    # -- the layers under the edge, counted at the boundaries the spans of
    # -- internals/tracing.py time (docs/observability.md has the table)
    #: BruteForceKnnEngine.search calls / the queries they carried
    "index_searches_total": 0,
    "index_search_queries_total": 0,
    #: whole-block placements a search made (no device copy to write into:
    #: the first search, a new capacity tier, a restored engine)
    "index_uploads_total": 0,
    "index_upload_bytes_total": 0,
    #: in-place writes of staged slots into the device copy: flushes, the
    #: distinct slots they carried, bytes of rows sent (padded slots x dim x 4)
    "index_writes_total": 0,
    "index_write_rows_total": 0,
    "index_write_bytes_total": 0,
    #: filters (BruteForceKnnEngine): queries that carried one / of them,
    #: those whose mask was cached on the device when their search began /
    #: masks built (a filter first seen, or seen again after its mask went) /
    #: masks dropped (the cache's byte bound, a new capacity tier, a whole
    #: placement of the block)
    "index_filtered_queries_total": 0,
    "index_filter_mask_hits_total": 0,
    "index_filter_masks_built_total": 0,
    "index_filter_masks_dropped_total": 0,
    #: rows the dataflow added to / removed from an external index
    "index_rows_added_total": 0,
    "index_rows_removed_total": 0,
    #: commit windows a python connector closed into a delta
    "connector_windows_total": 0,
    #: tokens the embed forward was asked for: real ones / the positions
    #: its programs computed, rows x row length (useful over attempted work)
    "embed_real_tokens_total": 0,
    "embed_padded_tokens_total": 0,
    #: forwards dispatched (one a call on the served path) / texts longer
    #: than the length limit, cut there / programs of the declared shape set
    #: compiled or loaded (all of them by ``Embedder.warm``: the first served call)
    "embed_dispatches_total": 0,
    "embed_truncated_texts_total": 0,
    "embed_shapes_compiled_total": 0,
    #: bytes of parameters an ``Embedder`` placed on the device, every form
    #: its programs read (its model's ``resident_params``), at load
    "embed_param_bytes_total": 0,
    #: (token, expert) assignments the embed forward was asked for: real
    #: tokens x experts per token x expert layers, a dispatch at a time (a
    #: model with no expert layer adds none)
    "embed_expert_assignments_total": 0,
    #: texts ``WordPieceTokenizer.encode_batch`` was handed / those of them
    #: that took its ASCII lane (the rest, texts that are not ASCII, were
    #: tokenized a character at a time in Python)
    "embed_tokenize_texts_total": 0,
    "embed_tokenize_fast_texts_total": 0,
}

_lock = threading.Lock()

#: live-gauge providers (admission controllers, routers) — each returns
#: a {name: value} dict merged into the snapshot; names must NOT end
#: ``_total`` (they are gauges: in-flight, queue depth, pending gathers)
_gauge_providers: list[Callable[[], dict[str, float]]] = []


def bump(key: str, n: int = 1) -> None:
    with _lock:
        SERVE_STATS[key] += n


def register_gauge_provider(fn: Callable[[], dict[str, float]]) -> None:
    with _lock:
        if fn not in _gauge_providers:
            _gauge_providers.append(fn)


def serve_stats_snapshot() -> dict[str, float]:
    """Counters + live gauges, or ``{}`` when the serve plane never ran
    (keeps non-serving expositions byte-identical)."""
    with _lock:
        counters = dict(SERVE_STATS)
        providers = list(_gauge_providers)
    if not any(counters.values()) and not providers:
        return {}
    out = {k: float(v) for k, v in counters.items()}
    for fn in providers:
        try:
            for k, v in fn().items():
                out[k] = float(v)
        except Exception:
            # telemetry must not fail the plane it observes
            continue
    return out


def reset_serve_stats() -> None:
    """Test hook: zero the counters and drop gauge providers."""
    with _lock:
        for k in SERVE_STATS:
            SERVE_STATS[k] = 0
        _gauge_providers.clear()
