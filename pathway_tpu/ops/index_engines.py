"""Mutable index engines: TPU brute-force KNN, LSH KNN, BM25, hybrid fusion.

These implement the ``engine.external_index.IndexEngine`` protocol and
replace the reference's native index integrations
(``src/external_integration/{usearch,tantivy,brute_force_knn}_integration.rs``).
The KNN hot path is an XLA kernel: one bf16 matmul on the MXU over the whole
index block + ``lax.top_k`` (``ops/knn.py``); the index lives device-resident
in a capacity-doubling arena so shapes stay static per capacity tier and the
jit cache stays warm. BM25 is host-side (string-heavy, branchy — the wrong
shape for the MXU), mirroring the reference's Tantivy choice of CPU.
"""

from __future__ import annotations

import collections
import math
import re
from typing import Any, Callable

import numpy as np

from ..utils.filters import (
    compile_metadata_filter,
    eval_filter_columns,
    lookup_path,
    parse_metadata_filter,
)

#: bytes of filter masks a ``BruteForceKnnEngine`` keeps on the device, the
#: least recently used dropped first. A mask is one byte a slot, so 128 MiB
#: hold 111 filters' masks at 1,200,000 slots and 64 at 2,048,000: under one
#: per cent of a chip's 16 GiB for more folders than a tick's callers ask.
MASK_CACHE_BYTES = 128 << 20
#: the fewest queries a search that carries a filter is scanned as. Every batch
#: size is a program of its own: the sizes 8, 16, 32, ... are few enough that a
#: server has met them all soon after its first filtered requests, where 1, 2
#: and 4 turn up late and singly. At 1,200,000 x 1024 a scan of eight takes
#: the device no longer than a scan of one, and their masks 0.35 ms more to
#: stack (PERF.md section 6, PR 36)
SCOPED_BATCH_MIN = 8

__all__ = [
    "BruteForceKnnEngine",
    "LshKnnEngine",
    "BM25Engine",
    "HybridEngine",
]


def _as_json(filter_data: Any) -> Any:
    import json as _json

    if filter_data is None:
        return None
    if isinstance(filter_data, str):
        try:
            return _json.loads(filter_data)
        except ValueError:
            return None
    from ..internals.json import Json

    if isinstance(filter_data, Json):
        return filter_data.value
    return filter_data


class _SlotArena:
    """Keyed slot allocator with a free list (host-side directory of the
    device-resident index block)."""

    def __init__(self) -> None:
        self.key_to_slot: dict[int, int] = {}
        self.slot_to_key: dict[int, int] = {}
        self.meta: dict[int, Any] = {}
        self.free: list[int] = []
        self.high = 0

    def alloc(self, key: int) -> int:
        slot = self.free.pop() if self.free else self.high
        if slot == self.high:
            self.high += 1
        self.key_to_slot[key] = slot
        self.slot_to_key[slot] = key
        return slot

    def release(self, key: int) -> int | None:
        slot = self.key_to_slot.pop(key, None)
        if slot is None:
            return None
        self.slot_to_key.pop(slot, None)
        self.meta.pop(slot, None)
        self.free.append(slot)
        return slot


class _MetaColumn:
    """One metadata path over the slots, dictionary-encoded: slot i reads
    ``values[codes[i]]``, and code 0 is None (no metadata, no such key). Two
    values share a code only where every construct of the filter grammar
    reads them alike: equal strings, or one type and one ``repr``."""

    def __init__(self, capacity: int):
        self.codes = np.zeros(capacity, np.int32)
        self.values: list = []
        self._code: dict = {}
        self.encode(None)

    def encode(self, value: Any) -> int:
        key = value if type(value) is str else (type(value), repr(value))
        code = self._code.get(key)
        if code is None:
            code = self._code[key] = len(self.values)
            self.values.append(value)
        return code


class BruteForceKnnEngine:
    """Exact KNN on TPU: the index block is one [capacity, dim] device array.

    ``metric``: "cos" (inputs L2-normalized at insert/query time) or "l2"
    (negative squared distance). Capacity doubles on overflow.

    Writes land in the host block and the host mask and stage the slots they
    touched; the next search brings the device copy up to date by writing
    only those slots in place (``ops/knn.py::index_write``, block and mask
    donated, the slot count padded to ``WRITE_BUCKETS``). The whole block is
    placed only where there is no device copy to write into: the first
    search, after ``_grow`` (a new capacity tier, so the scan and the write
    programs compile once more — all of them at that placement, none later),
    after unpickling, and when ``_dirty`` was set with nothing staged.

    A filtered query is an unfiltered one plus a mask the engine already
    holds. Metadata is kept by column for the paths filters have named
    (``_columns``, built in one pass over the slots when a path is first
    named, kept current by ``add`` and ``_bulk_add``); a filter's mask over
    the slots, its rows among the live ones, is evaluated from them once and
    cached on the device under the filter's source (``_masks``, bounded by
    ``MASK_CACHE_BYTES``); an in-place write evaluates each cached mask at the
    written slots only and writes it in place, and whatever drops the device
    copy drops the masks with it. A search stacks its queries' masks to
    ``valid`` [q, n] and scans once; its query axis is padded to a power of
    two, ``SCOPED_BATCH_MIN`` at the least, so that a deployment's filtered
    searches are two or three programs whatever batches its ticks form. A
    store that is never sent a filter builds none of this.
    """

    def __init__(self, dimensions: int, *, metric: str = "cos",
                 reserved_space: int = 1024,
                 embedder: Callable[[str], np.ndarray] | None = None):
        self.dim = dimensions
        self.metric = metric
        self.embedder = embedder
        self.capacity = max(16, int(reserved_space))
        self._host = np.zeros((self.capacity, self.dim), dtype=np.float32)
        self._valid = np.zeros(self.capacity, dtype=bool)
        self._slots = _SlotArena()
        self._device = self._device_valid = None  # lazily synced jax copy
        #: true when the device copy is behind the host block
        self._dirty = True
        #: slots written on the host since the device copy was last current
        #: (ints and arrays); empty while there is no device copy
        self._staged: list = []
        #: metadata path -> its column, for the paths filters have named
        self._columns: dict[tuple[str, ...], _MetaColumn] = {}
        #: filter source -> (parsed tree, device mask [capacity] of the live
        #: rows it keeps), least recently used first
        self._masks: collections.OrderedDict = collections.OrderedDict()

    # operator snapshots pickle the whole engine; the device mirror and what
    # is derived from the metadata are caches rebuilt after restore
    def __getstate__(self):
        st = dict(self.__dict__)
        for name in ("_device", "_device_valid", "_staged", "_columns", "_masks"):
            st.pop(name, None)
        # the embedder may be an arbitrary closure (not picklable); the
        # restoring node grafts the freshly-constructed engine's embedder back
        st["embedder"] = None
        return st

    def __setstate__(self, st):
        self.__dict__.update(st)
        self._device = self._device_valid = None
        self._dirty = True
        self._staged = []
        self._columns = {}
        self._masks = collections.OrderedDict()

    def _stage(self, slots) -> None:
        """The host block changed at ``slots``: the device copy, if there is
        one, is behind by exactly these."""
        self._dirty = True
        if self._device is not None:
            self._staged.append(slots)

    # -- mutation ----------------------------------------------------------
    def _vec(self, data: Any) -> np.ndarray:
        if isinstance(data, str):
            if self.embedder is None:
                raise TypeError("string data requires an embedder")
            batch = getattr(self.embedder, "embed_texts", None)
            # a models.Embedder works directly as the engine embedder
            data = batch([data])[0] if batch is not None else self.embedder(data)
        v = np.asarray(data, dtype=np.float32).reshape(-1)
        if v.shape[0] != self.dim:
            raise ValueError(f"vector dim {v.shape[0]} != index dim {self.dim}")
        if self.metric == "cos":
            # "ip" deliberately skips this: raw inner product keeps magnitude
            n = float(np.linalg.norm(v))
            if n > 0:
                v = v / n
        return v

    def add(self, key: int, data: Any, filter_data: Any) -> None:
        v = self._vec(data)
        if key in self._slots.key_to_slot:
            self._slots.release(key)
        slot = self._slots.alloc(key)
        if slot >= self.capacity:
            self._grow()
        self._host[slot] = v
        self._valid[slot] = True
        meta = self._slots.meta[slot] = _as_json(filter_data)
        self._note_metadata([slot], [meta])
        self._stage(slot)

    def add_batch(self, keys: list[int], datas: list[Any], filters: list[Any]) -> None:
        """Bulk insertion: all string payloads of one tick are embedded in
        one batched call (``Embedder.embed_texts``: one MXU forward for each
        power-of-two length its texts fall into, all on their way before the
        first is fetched, instead of one per document) — the ingest-path
        analog of the device-resident query fusion. Called by
        ExternalIndexNode when available.

        When every payload is already a vector and this is a plain
        brute-force engine (no subclass bucketing hooks), insertion is one
        vectorized slab write — normalize + slot-assign the whole tick at
        numpy speed instead of a million ``add`` calls (the 1M-doc
        north-star ingest path)."""
        batch = getattr(self.embedder, "embed_texts", None)
        text_ix = [
            i for i, d in enumerate(datas) if isinstance(d, str)
        ] if batch is not None else []
        if text_ix:
            vecs = batch([datas[i] for i in text_ix])
            datas = list(datas)
            for j, i in enumerate(text_ix):
                datas[i] = np.asarray(vecs[j], dtype=np.float32)
        if type(self).add is BruteForceKnnEngine.add and not any(
            isinstance(d, str) for d in datas
        ):
            self._bulk_add(keys, datas, filters)
            return
        for k, d, f in zip(keys, datas, filters):
            self.add(k, d, f)

    def _bulk_add(self, keys: list[int], datas: list[Any], filters: list[Any]) -> None:
        n = len(keys)
        if n == 0:
            return
        try:
            vecs = np.stack([np.asarray(d, dtype=np.float32).reshape(-1)
                             for d in datas])
        except ValueError:  # ragged dims — per-row path raises the right error
            for k, d, f in zip(keys, datas, filters):
                self.add(k, d, f)
            return
        if vecs.shape[1] != self.dim:
            raise ValueError(
                f"vector dim {vecs.shape[1]} != index dim {self.dim}"
            )
        if self.metric == "cos":
            norms = np.linalg.norm(vecs, axis=1, keepdims=True)
            np.divide(vecs, norms, out=vecs, where=norms > 0)
        ikeys = [int(k) for k in keys]
        if len(set(ikeys)) != len(ikeys):
            # duplicate keys in one tick (diff multiplicity, in-tick
            # updates): keep only the last occurrence — matching the
            # per-row path, where each add replaces the previous slot
            last = {k: i for i, k in enumerate(ikeys)}
            keep = sorted(last.values())
            ikeys = [ikeys[i] for i in keep]
            vecs = vecs[keep]
            filters = [filters[i] for i in keep]
            n = len(ikeys)
        for k in ikeys:
            if k in self._slots.key_to_slot:
                self._slots.release(k)
        if self._slots.free:
            slots = np.array([self._slots.alloc(k) for k in ikeys],
                             dtype=np.int64)
        else:  # fresh block: bulk dict updates, no per-key alloc calls
            start = self._slots.high
            slots = np.arange(start, start + n, dtype=np.int64)
            self._slots.high = start + n
            slot_list = slots.tolist()
            self._slots.key_to_slot.update(zip(ikeys, slot_list))
            self._slots.slot_to_key.update(zip(slot_list, ikeys))
        if self._slots.high > self.capacity:
            self._grow(self._slots.high)
        self._host[slots] = vecs
        self._valid[slots] = True
        metas = [_as_json(f) for f in filters]
        self._slots.meta.update(
            (slot, m) for slot, m in zip(slots.tolist(), metas) if m is not None)
        self._note_metadata(slots, metas)
        self._stage(slots)

    def remove(self, key: int) -> None:
        slot = self._slots.release(key)
        if slot is not None:
            self._valid[slot] = False
            self._stage(slot)

    def _grow(self, needed: int | None = None) -> None:
        new_cap = self.capacity * 2
        while new_cap < (needed or 0):
            new_cap *= 2
        host = np.zeros((new_cap, self.dim), dtype=np.float32)
        host[: self.capacity] = self._host
        valid = np.zeros(new_cap, dtype=bool)
        valid[: self.capacity] = self._valid
        self._host, self._valid, self.capacity = host, valid, new_cap
        # a new tier is a new block: the old device copy goes now, so that
        # the two never coexist, and the next search places the new one;
        # columns and masks are of the old capacity, and go with it
        self._device = self._device_valid = None
        self._staged = []
        self._columns = {}
        self._drop_masks()

    def _sync_device(self) -> None:
        """Bring the device copy up to date with the host block (span
        ``index.upload``): the staged slots written in place (``index.write``
        under it), or the whole block placed where there is no copy to
        write into."""
        from ..internals.tracing import span
        from ..serve.stats import bump

        import jax.numpy as jnp

        from .knn import PLACE_ROWS, WRITE_BUCKETS, index_fill, storage_dtype

        if self._device is None or not self._staged:
            dtype = storage_dtype(self.metric)
            self._drop_masks()  # of a copy that goes, or of an unknown change
            with span("index.upload", bytes=self._host.nbytes, whole=True,
                      dtype=dtype.name):
                self._device = self._device_valid = None  # never two blocks
                # the block is filled chunk by chunk, each chunk of float32
                # rows cast on the device and waited for, so that what the
                # device holds beside the block is one chunk at a time
                self._device = jnp.zeros((self.capacity, self.dim), dtype)
                chunk = min(self.capacity, PLACE_ROWS)
                for at in range(0, self.capacity, chunk):
                    # the last chunk reaches back over rows already placed
                    at = min(at, self.capacity - chunk)
                    self._device = index_fill(
                        self._device, self._host[at:at + chunk], np.int32(at)
                    ).block_until_ready()
                self._device_valid = jnp.asarray(self._valid)
            bump("index_uploads_total")
            bump("index_upload_bytes_total", self._host.nbytes)
            # every write program of this tier compiles now, on writes that
            # change nothing (slot 0 with its own row), and none while serving
            self._write([np.zeros(b, np.int32) for b in WRITE_BUCKETS])
        else:
            slots = np.unique(np.hstack(self._staged)).astype(np.int32)
            cap = WRITE_BUCKETS[-1]
            # a piece is padded to its bucket with its own slots over again,
            # each with its own row, so the padding writes nothing new
            pieces = [
                np.resize(p, next(b for b in WRITE_BUCKETS if b >= len(p)))
                for p in (slots[i:i + cap] for i in range(0, len(slots), cap))
            ]
            padded = sum(len(p) for p in pieces)
            nbytes = padded * self.dim * 4
            with span("index.upload", bytes=nbytes, whole=False,
                      dtype=self._device.dtype.name), \
                    span("index.write", rows=len(slots), padded=padded, bytes=nbytes):
                self._write(pieces)
                if self._masks:
                    with span("index.mask.update", masks=len(self._masks),
                              slots=len(slots)):
                        self._write_masks(self._masks.values(), pieces)
            bump("index_writes_total")
            bump("index_write_rows_total", len(slots))
            bump("index_write_bytes_total", nbytes)
        self._dirty = False
        self._staged = []

    def _write(self, pieces: list[np.ndarray]) -> None:
        from .knn import index_writer

        write = index_writer()
        for p in pieces:
            self._device, self._device_valid = write(
                self._device, self._device_valid, p, self._host[p], self._valid[p])

    # -- filters: metadata by column, masks on the device -------------------
    def _note_metadata(self, slots, metas: list) -> None:
        """``metas`` were written at ``slots``: the columns follow. (A freed
        slot keeps its codes: it is not live, so no mask reads them.)"""
        for path, col in list(self._columns.items()):
            col.codes[slots] = [col.encode(lookup_path(m, path)) for m in metas]
            if len(col.values) > 2 * self.capacity:
                # values no slot holds any more pile up under rewrites: the
                # next filter that names the path builds the column afresh
                del self._columns[path]

    def _column(self, path: tuple[str, ...]) -> _MetaColumn:
        col = self._columns.get(path)
        if col is None:
            col = self._columns[path] = _MetaColumn(self.capacity)
            meta = self._slots.meta
            col.codes[np.fromiter(meta, np.int64, len(meta))] = np.fromiter(
                (col.encode(lookup_path(m, path)) for m in meta.values()),
                np.int32, len(meta))
        return col

    def _keeps(self, ast: tuple, slots=slice(None)) -> np.ndarray:
        """The live rows a filter keeps, over every slot or at ``slots``."""
        def column(path):
            col = self._column(path)
            return col.codes[slots], col.values

        live = self._valid[slots]
        return live & eval_filter_columns(ast, column, len(live))

    def _drop_masks(self) -> None:
        from ..serve.stats import bump

        if self._masks:
            bump("index_filter_masks_dropped_total", len(self._masks))
            self._masks.clear()

    def _write_masks(self, entries, pieces: list[np.ndarray]) -> None:
        """Each of the cache's ``entries`` evaluated at the slots of ``pieces``
        (each padded to a write bucket) and written there in place."""
        from .knn import mask_write

        for entry in entries:
            for p in pieces:
                entry[1] = mask_write(entry[1], p, self._keeps(entry[0], p))

    def _valid_of(self, keys: list, pad: int):
        """``valid`` [q + pad, n] of a search whose queries carry the filters
        ``keys`` (None for a query with none): each query's mask from the
        cache, built and cached where it is not there, the store's own for a
        query with no filter, the last repeated for the padding."""
        import jax.numpy as jnp

        from ..internals.tracing import span
        from ..serve.stats import bump
        from .knn import WRITE_BUCKETS, stack_valid

        distinct = dict.fromkeys(k for k in keys if k is not None)
        filtered = len(keys) - keys.count(None)
        hits = sum(k in self._masks for k in keys if k is not None)
        with span("index.mask", filtered=filtered, distinct=len(distinct),
                  hits=hits, built=sum(k not in self._masks for k in distinct)):
            masks = {None: self._device_valid}
            for key in distinct:
                entry = self._masks.get(key)
                if entry is None:
                    columns = len(self._columns)
                    with span("index.mask.build", slots=self.capacity) as sp:
                        ast = parse_metadata_filter(key)
                        keep = self._keeps(ast)
                        entry = self._masks[key] = [ast, jnp.asarray(keep)]
                        # the programs that keep it current compile now, on
                        # writes that change nothing (slot 0 with its own
                        # value), and none while serving
                        self._write_masks(
                            [entry], [np.zeros(b, np.int32) for b in WRITE_BUCKETS])
                        if sp is not None:
                            sp.args.update(kept=int(keep.sum()),
                                           columns_built=len(self._columns) - columns)
                    bump("index_filter_masks_built_total")
                self._masks.move_to_end(key)
                masks[key] = entry[1]
            rows = [masks[k] for k in keys]
            valid = stack_valid(*rows, *rows[-1:] * pad)
            # the bound holds between searches; this one has its rows in hand
            while len(self._masks) > 1 and len(self._masks) * self.capacity > MASK_CACHE_BYTES:
                self._masks.popitem(last=False)
                bump("index_filter_masks_dropped_total")
        bump("index_filtered_queries_total", filtered)
        bump("index_filter_mask_hits_total", hits)
        return valid

    # -- search ------------------------------------------------------------
    def search(self, queries: list[Any], limits: list[int], filters: list[Any]):
        from ..internals.tracing import span
        from ..serve.stats import bump

        bump("index_searches_total")
        bump("index_search_queries_total", len(queries))
        # a filter is known by its source, a callable by itself
        keys = [f if f is None or callable(f) else str(f) for f in filters]
        filtered = len(keys) - keys.count(None)
        with span(
            "index.search", q=len(queries),
            dirty=bool(self._dirty or self._device is None),
            k=max(limits, default=0),
            filtered=filtered, filters=len(set(keys) - {None}),
        ):
            n = self._slots.high
            if n == 0 or not queries:
                return [[] for _ in queries]
            from ..utils import jaxcfg  # noqa: F401

            import jax.numpy as jnp

            from .knn import topk_scores

            # a search that carries a filter is padded along its query axis
            # to a power of two: so few programs serve whatever batches the
            # ticks form, and all are met early. What is padded is the
            # vectors (the embedder's zero rows, the last vector again where
            # the host has them), never the texts: a query is embedded once
            pad = 0
            if filtered:
                pad = max(SCOPED_BATCH_MIN, 1 << (len(queries) - 1).bit_length()) - len(queries)
            dev_embed = getattr(self.embedder, "embed_texts_device", None)
            if dev_embed is not None and all(isinstance(x, str) for x in queries):
                # device-resident query embeddings (already L2-normalized by the
                # model head) flow straight into the scorer: embed -> score ->
                # top_k pipelines as queued device work with a single blocking
                # fetch at _pack time
                with span("index.embed", q=len(queries)):
                    q = dev_embed(list(queries), rows=len(queries) + pad)
            else:
                q = np.stack([self._vec(x) for x in queries])
                q = np.concatenate([q, np.repeat(q[-1:], pad, axis=0)])
            if self._dirty or self._device is None:
                self._sync_device()

            kmax = min(max(limits), int(self._valid.sum()))
            if kmax <= 0:
                return [[] for _ in queries]

            # a query's rows are the live ones its filter keeps: masked to
            # -inf before the device's top-k, so a scope of fewer than k
            # rows answers with every one of them and no more
            valid = self._valid_of(keys, pad) if filtered else self._device_valid
            with span("index.score", q=len(queries), rows=n):
                s, ids = topk_scores(jnp.asarray(q), self._device, kmax,
                                     self.metric, valid=valid)
            with span("index.fetch"):  # where the host waits for the device
                s, ids = np.asarray(s), np.asarray(ids)
            with span("index.pack", replies=len(queries)):
                return [
                    self._pack(s[i], ids[i], limits[i]) for i in range(len(queries))
                ]

    def _pack(self, scores: np.ndarray, slots: np.ndarray, limit: int):
        out = []
        for sc, slot in zip(scores, slots):
            if len(out) >= limit or not np.isfinite(sc):
                break
            key = self._slots.slot_to_key.get(int(slot))
            if key is not None:
                out.append((key, float(sc)))
        return out


class LshKnnEngine(BruteForceKnnEngine):
    """LSH-bucketed approximate KNN (reference ``LshKnn``,
    ``stdlib/ml/index.py`` classic impl): random-hyperplane signatures route
    vectors to buckets; queries score only their buckets' candidates — the
    exact scoring of the candidate set still runs through the TPU kernel
    path when the set is large, numpy below that.
    """

    def __init__(self, dimensions: int, *, metric: str = "cos",
                 reserved_space: int = 1024, n_or: int = 4, n_and: int = 8,
                 bucket_length: float | None = None, seed: int = 0,
                 embedder: Callable[[str], np.ndarray] | None = None):
        super().__init__(dimensions, metric=metric,
                         reserved_space=reserved_space, embedder=embedder)
        rng = np.random.default_rng(seed)
        self.n_or = n_or
        self.n_and = n_and
        self._planes = rng.standard_normal((n_or, n_and, dimensions)).astype(
            np.float32
        )
        self._buckets: list[dict[int, set[int]]] = [dict() for _ in range(n_or)]
        self._slot_sigs: dict[int, list[int]] = {}

    def _signatures(self, v: np.ndarray) -> list[int]:
        bits = (np.einsum("oad,d->oa", self._planes, v) > 0).astype(np.uint64)
        weights = (2 ** np.arange(self.n_and, dtype=np.uint64))
        return [int((bits[o] * weights).sum()) for o in range(self.n_or)]

    def add(self, key: int, data: Any, filter_data: Any) -> None:
        if key in self._slots.key_to_slot:
            # clean old bucket entries before re-slotting (plain super().add
            # would re-allocate the slot and leak the old signatures)
            self.remove(key)
        super().add(key, data, filter_data)
        slot = self._slots.key_to_slot[key]
        sigs = self._signatures(self._host[slot])
        self._slot_sigs[slot] = sigs
        for o, sig in enumerate(sigs):
            self._buckets[o].setdefault(sig, set()).add(slot)

    def remove(self, key: int) -> None:
        slot = self._slots.key_to_slot.get(key)
        super().remove(key)
        if slot is not None:
            for o, sig in enumerate(self._slot_sigs.pop(slot, [])):
                self._buckets[o].get(sig, set()).discard(slot)

    def search(self, queries: list[Any], limits: list[int], filters: list[Any]):
        if self._slots.high == 0 or not queries:
            return [[] for _ in queries]
        filt_fns = [compile_metadata_filter(f) for f in filters]
        out = []
        for qd, lim, fv in zip(queries, limits, filt_fns):
            v = self._vec(qd)
            cand: set[int] = set()
            for o, sig in enumerate(self._signatures(v)):
                cand |= self._buckets[o].get(sig, set())
            cand = {s for s in cand if self._valid[s]}
            if fv is not None:
                cand = {s for s in cand if fv(self._slots.meta.get(s))}
            if not cand:
                out.append([])
                continue
            slots = np.fromiter(cand, dtype=np.int64)
            block = self._host[slots]
            if self.metric in ("cos", "ip"):
                scores = block @ v
            else:
                scores = -((block - v[None, :]) ** 2).sum(axis=1)
            top = np.argsort(-scores)[:lim]
            out.append([
                (self._slots.slot_to_key[int(slots[i])], float(scores[i]))
                for i in top
            ])
        return out


_TOKEN_RE = re.compile(r"[A-Za-z0-9_]+")


def tokenize(text: str) -> list[str]:
    return [t.lower() for t in _TOKEN_RE.findall(text)]


class BM25Engine:
    """In-memory BM25 full-text index (replaces the reference's Tantivy
    integration, ``tantivy_integration.rs``). Host-side inverted index:
    token → {key: tf}; Okapi BM25 scoring with k1/b."""

    def __init__(self, *, ram_budget: int = 0, in_memory_index: bool = True,
                 k1: float = 1.2, b: float = 0.75):
        self.k1 = k1
        self.b = b
        self._postings: dict[str, dict[int, int]] = {}
        self._doc_len: dict[int, int] = {}
        self._doc_tokens: dict[int, list[str]] = {}
        self._meta: dict[int, Any] = {}

    def add(self, key: int, data: Any, filter_data: Any) -> None:
        if key in self._doc_len:
            self.remove(key)
        toks = tokenize(str(data))
        self._doc_tokens[key] = toks
        self._doc_len[key] = len(toks)
        self._meta[key] = _as_json(filter_data)
        for t in toks:
            self._postings.setdefault(t, {})
            self._postings[t][key] = self._postings[t].get(key, 0) + 1

    def remove(self, key: int) -> None:
        toks = self._doc_tokens.pop(key, None)
        if toks is None:
            return
        self._doc_len.pop(key, None)
        self._meta.pop(key, None)
        for t in set(toks):
            plist = self._postings.get(t)
            if plist is not None:
                plist.pop(key, None)
                if not plist:
                    del self._postings[t]

    def search(self, queries: list[Any], limits: list[int], filters: list[Any]):
        n_docs = len(self._doc_len)
        if n_docs == 0 or not queries:
            return [[] for _ in queries]
        avgdl = sum(self._doc_len.values()) / n_docs
        filt_fns = [compile_metadata_filter(f) for f in filters]
        out = []
        for q, lim, fv in zip(queries, limits, filt_fns):
            scores: dict[int, float] = {}
            for t in tokenize(str(q)):
                plist = self._postings.get(t)
                if not plist:
                    continue
                idf = math.log(1.0 + (n_docs - len(plist) + 0.5) / (len(plist) + 0.5))
                for key, tf in plist.items():
                    dl = self._doc_len[key]
                    denom = tf + self.k1 * (1 - self.b + self.b * dl / avgdl)
                    scores[key] = scores.get(key, 0.0) + idf * tf * (self.k1 + 1) / denom
            ranked = sorted(scores.items(), key=lambda kv: (-kv[1], kv[0]))
            if fv is not None:
                ranked = [(k, s) for k, s in ranked if fv(self._meta.get(k))]
            out.append([(k, float(s)) for k, s in ranked[:lim] if s > 0])
        return out


class HybridEngine:
    """Reciprocal-rank fusion over sub-engines (reference ``HybridIndex``,
    ``stdlib/indexing/hybrid_index.py``): score = Σ 1/(rrf_k + rank)."""

    def __init__(self, engines: list[Any], *, rrf_k: int = 60,
                 adapters: list[Callable[[Any], Any]] | None = None):
        self.engines = engines
        self.rrf_k = rrf_k
        self.adapters = adapters or [None] * len(engines)

    def add(self, key: int, data: Any, filter_data: Any) -> None:
        for eng, ad in zip(self.engines, self.adapters):
            eng.add(key, ad(data) if ad else data, filter_data)

    def remove(self, key: int) -> None:
        for eng in self.engines:
            eng.remove(key)

    def search(self, queries: list[Any], limits: list[int], filters: list[Any]):
        # each sub-engine retrieves a deeper pool so fusion has candidates
        deep = [max(l * 2, l + 5) for l in limits]
        per_engine = [
            eng.search(
                [ad(q) if ad else q for q in queries], deep, filters
            )
            for eng, ad in zip(self.engines, self.adapters)
        ]
        out = []
        for qi in range(len(queries)):
            fused: dict[int, float] = {}
            for replies in per_engine:
                for rank, (key, _score) in enumerate(replies[qi]):
                    fused[key] = fused.get(key, 0.0) + 1.0 / (self.rrf_k + rank + 1)
            ranked = sorted(fused.items(), key=lambda kv: (-kv[1], kv[0]))
            out.append([(k, float(s)) for k, s in ranked[: limits[qi]]])
        return out
