"""MeshComm — the ICI communication backend for the sharded dataflow.

Wraps a host :class:`~pathway_tpu.parallel.comm.Comm` (LocalComm threads)
and routes the dense numeric part of every Exchange frame through a
``bucketed_all_to_all`` XLA collective over a 1-D ``jax.sharding.Mesh``
(``engine/mesh_exchange.py`` → ``parallel/exchange.py``), so on TPU the
record bytes move over ICI instead of host memory. Object/string columns
ride the shared deposit and are re-zipped by source order.

Per tick + exchange channel, the fused protocol (r4 — replaces the
three-allgather/one-exchange protocol VERDICT r3 measured at 20× the host
path) is:

1. every worker deposits (signature, per-destination counts, its local
   Delta by reference, destination array) into a shared slot and hits ONE
   barrier;
2. the driver thread (worker 0) agrees dtype kinds + power-of-two caps,
   packs ALL workers' dense rows into one pinned staging buffer, ships it
   with a single sharded ``device_put``, runs the jitted collective, and
   publishes the result; second barrier;
3. every worker reads back only its own device shard and re-zips any
   host-path (object) columns straight from the deposited Deltas.

Total host synchronization: 2 barriers per channel-tick (was 8), one
device upload (was one per worker plus a result allgather).

Enable with ``PATHWAY_MESH_EXCHANGE=1``. Single-process runs use
:class:`MeshComm` (threads over one process's devices); ``spawn -n M``
runs bootstrap ``jax.distributed`` (``parallel/distributed.py``) and use
:class:`MultiHostMeshComm`, whose collective spans every process's
devices — ICI within a pod, DCN across pods.

Host-boundary frames (the object-column swap of
:meth:`MultiHostMeshComm.exchange_deltas` and any control payloads that
ride the inner ClusterComm) reuse the columnar wire codec
(``parallel/frames.py``): the ``(src, {name: column})`` payload shape is
recognized by the encoder and ships through the same
directory-plus-buffers frame layout as Delta exchanges, so no host
boundary ever pays ``pickle.dumps`` on a dense column.

Reference being replaced: timely's ``zero_copy`` allocator
(``external/timely-dataflow/communication/src/allocator/zero_copy/``).
"""

from __future__ import annotations

import threading
from typing import Any, Sequence

import numpy as np

from ..engine.delta import Delta, concat_deltas
from ..engine.mesh_exchange import (
    HOST,
    MeshExchangeRunner,
    local_signature,
)
from .comm import Comm

__all__ = ["MeshComm", "MultiHostMeshComm"]


class MeshComm(Comm):
    def __init__(self, inner: Comm, mesh: Any = None):
        from ..utils import jaxcfg  # noqa: F401

        import jax
        from jax.sharding import Mesh

        self.inner = inner
        self.n_workers = inner.n_workers
        if mesh is None:
            devices = jax.devices()
            if len(devices) < self.n_workers:
                raise RuntimeError(
                    f"mesh exchange needs ≥{self.n_workers} devices, have "
                    f"{len(devices)} — run with "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N on "
                    "CPU, or disable PATHWAY_MESH_EXCHANGE"
                )
            mesh = Mesh(np.array(devices[: self.n_workers]), ("workers",))
        self.mesh = mesh
        self.runner = MeshExchangeRunner(mesh, "workers")
        # (channel, tick) -> {"payloads": [...], "result": ...}; entries for
        # a channel are deleted by the driver at the NEXT tick's compute
        # phase, when the post-deposit barrier proves no reader remains
        self._slots: dict[tuple, dict] = {}
        self._slot_lock = threading.Lock()
        # tracing: link every worker's deposit to the driver's collective
        # and the collective back to each worker's readback (flow events
        # with deterministic ids — one shared tracer, no context to ship)
        from ..internals.tracing import get_tracer, mint_flow_tag

        self._tracer = get_tracer()
        self._flow_tag = mint_flow_tag()

    def _flow_id(self, channel: int, tick: int, worker: int,
                 phase: str) -> str:
        from ..internals.tracing import make_flow_id

        return make_flow_id(
            self._tracer, self._flow_tag,
            f"mx{channel}", f"t{tick}", f"{phase}{worker}",
        )

    # host-comm delegation (control plane + non-delta payloads)

    def exchange(self, channel, tick, worker_id, buckets):
        return self.inner.exchange(channel, tick, worker_id, buckets)

    def allgather(self, tag, worker_id, obj):
        return self.inner.allgather(tag, worker_id, obj)

    def barrier(self, worker_id: int):
        self.inner.barrier(worker_id)

    # async (frontier-driven) plane: host-path delegation — the ICI
    # collective is inherently bulk-synchronous, so PATHWAY_ASYNC_EXEC=1
    # with mesh exchange routes record exchange over the host plane
    def supports_async(self) -> bool:
        return self.inner.supports_async()

    def async_attach(self, worker_id, waker):
        self.inner.async_attach(worker_id, waker)

    def async_post_exchange(self, worker_id, channel, time, buckets,
                            ingest_ns=None, seq=None, enq_ns=None):
        return self.inner.async_post_exchange(
            worker_id, channel, time, buckets, ingest_ns, seq, enq_ns
        )

    def async_broadcast(self, worker_id, payload):
        self.inner.async_broadcast(worker_id, payload)

    def async_drain(self, worker_id):
        return self.inner.async_drain(worker_id)

    def async_congested(self, worker_id):
        return self.inner.async_congested(worker_id)

    def abort(self):
        self.inner.abort()

    def close(self):
        # the final tick's slots have no successor tick to reclaim them
        with self._slot_lock:
            self._slots.clear()
        self.inner.close()

    def comm_stats(self) -> dict[str, float]:
        out = dict(self.inner.comm_stats())
        out["mesh_pending_slots"] = float(len(self._slots))
        out.update(self.runner.stats())
        return out

    # the ICI data plane

    def exchange_deltas(
        self,
        channel: int,
        tick: int,
        worker_id: int,
        buckets: Sequence[Delta | None],
        column_names: list[str],
    ) -> list[Delta]:
        """All-to-all of columnar Delta buckets; dense columns over the
        device mesh, object columns re-zipped from the shared deposit."""
        n = self.n_workers
        parts = [
            (dst, d) for dst, d in enumerate(buckets) if d is not None and len(d)
        ]
        local = concat_deltas([d for _, d in parts], column_names) if parts else None
        dest = (
            np.concatenate(
                [np.full(len(d), dst, dtype=np.int32) for dst, d in parts]
            )
            if parts
            else np.empty(0, dtype=np.int32)
        )
        counts = np.zeros(n, dtype=np.int64)
        for dst, d in parts:
            counts[dst] += len(d)
        sig = local_signature(local, column_names)

        key = (channel, tick)
        tracer = self._tracer
        if tracer is not None:
            tracer.flow_start(
                "mesh.deposit",
                self._flow_id(channel, tick, worker_id, "in"),
                channel=channel,
                tick=tick,
            )
        with self._slot_lock:
            slot = self._slots.setdefault(key, {"payloads": [None] * n})
            slot["payloads"][worker_id] = (sig, counts, local, dest)
        self.inner.barrier(worker_id)  # all deposits visible

        if worker_id == 0:
            with self._slot_lock:
                # all workers deposited (channel, tick) → every worker has
                # finished all earlier ticks on EVERY channel (the sweep is
                # sequential per worker); reclaim all older slots
                stale = [k for k in self._slots if k[1] < tick]
                for k in stale:
                    del self._slots[k]
                slot = self._slots[key]
            try:
                slot["result"] = self.runner.run_tick(
                    slot["payloads"], column_names
                )
                if tracer is not None:
                    # the driver's collective consumed every deposit and
                    # fans the result back out — close/open the flows here,
                    # inside the driver's tick slice
                    for w in range(n):
                        tracer.flow_end(
                            "mesh.deposit",
                            self._flow_id(channel, tick, w, "in"),
                        )
                        tracer.flow_start(
                            "mesh.result",
                            self._flow_id(channel, tick, w, "out"),
                        )
            except BaseException as e:  # noqa: BLE001 — re-raised on peers
                slot["result"] = _DriverError(e)
                self.inner.barrier(worker_id)
                raise
            self.inner.barrier(worker_id)
        else:
            self.inner.barrier(worker_id)
            slot = self._slots[key]

        result = slot["result"]
        if isinstance(result, _DriverError):
            raise RuntimeError(
                "mesh exchange failed on the driver worker"
            ) from result.error
        if tracer is not None:
            tracer.flow_end(
                "mesh.result", self._flow_id(channel, tick, worker_id, "out")
            )
        if result is None:
            return []
        kinds, cap_bucket, gvals, gvalid = result

        per_dev = self.runner.n * cap_bucket
        my_vals = self.runner.my_shard(gvals, worker_id, per_dev)
        my_valid = self.runner.my_shard(gvalid, worker_id, per_dev)

        host_cols: dict[int, dict[str, np.ndarray]] = {}
        host_names = [c for c, k in zip(column_names, kinds) if k == HOST]
        if host_names:
            for src, payload in enumerate(slot["payloads"]):
                _, _, src_local, src_dest = payload
                if src_local is None or not len(src_local):
                    continue
                mine = src_dest == worker_id
                if mine.any():
                    ix = np.flatnonzero(mine)
                    host_cols[src] = {
                        c: src_local.data[c][ix] for c in host_names
                    }

        return self.runner.unpack_arrivals(
            vals=my_vals,
            valid=my_valid.astype(bool),
            kinds=kinds,
            column_names=column_names,
            host_cols=host_cols,
        )


class _DriverError:
    """Marks a failed driver tick so peers re-raise instead of hanging."""

    def __init__(self, error: BaseException):
        self.error = error


class MultiHostMeshComm(Comm):
    """Cross-process mesh exchange: the DCN/ICI data plane over a
    ``jax.distributed`` multi-controller mesh (VERDICT r4 item 6).

    Processes each own ``threads`` workers and (at least) ``threads``
    local devices; the global 1-D mesh orders devices process-major so
    worker ``p*threads + t`` owns device ``t`` of process ``p``. Per
    channel-tick:

    1. every worker allgathers its tiny control tuple (dtype signature,
       per-destination counts) over the host ClusterComm, and deposits its
       local Delta in a PROCESS-local slot;
    2. each process's leader thread packs its workers' dense rows into
       process-local staging, forms its slice of the global array with
       ``jax.make_array_from_process_local_data``, and all leaders execute
       the same jitted ``bucketed_all_to_all`` simultaneously
       (multi-controller SPMD) — the record bytes ride ICI/DCN;
    3. every worker reads back its own addressable shard; object/string
       columns swap over the host ClusterComm and re-zip by source order.

    Reference: timely's cluster allocator
    (``communication/src/allocator/zero_copy/``) + bootstrap
    (``communication/src/initialize.rs``).
    """

    def __init__(self, inner: Comm, process_id: int, n_processes: int,
                 threads: int):
        from ..utils import jaxcfg  # noqa: F401

        import jax
        from jax.sharding import Mesh

        self.inner = inner
        self.n_workers = inner.n_workers
        self.process_id = process_id
        self.n_processes = n_processes
        self.threads = threads
        by_process: dict[int, list] = {}
        for d in jax.devices():
            by_process.setdefault(d.process_index, []).append(d)
        ordered = []
        for p in sorted(by_process):
            local = by_process[p]
            if len(local) < threads:
                raise RuntimeError(
                    f"process {p} exposes {len(local)} devices < "
                    f"{threads} workers — mesh exchange needs one device "
                    "per worker"
                )
            ordered.extend(local[:threads])
        if len(ordered) < self.n_workers:
            raise RuntimeError(
                f"mesh exchange needs ≥{self.n_workers} devices across "
                f"processes, have {len(ordered)}"
            )
        self.mesh = Mesh(np.array(ordered[: self.n_workers]), ("workers",))
        self.runner = MeshExchangeRunner(self.mesh, "workers")
        # process-local coordination among this process's worker threads
        self._local_barrier = threading.Barrier(threads)
        self._slot_lock = threading.Lock()
        self._slots: dict[tuple, dict] = {}
        # tracing: local deposit→leader flows (cross-process linkage rides
        # the inner ClusterComm's frame contexts)
        from ..internals.tracing import get_tracer, mint_flow_tag

        self._tracer = get_tracer()
        self._flow_tag = mint_flow_tag()

    def _flow_id(self, channel: int, tick: int, worker: int,
                 phase: str) -> str:
        from ..internals.tracing import make_flow_id

        return make_flow_id(
            self._tracer, self._flow_tag,
            f"mxh{channel}", f"t{tick}", f"{phase}{worker}",
        )

    # host-comm delegation

    def exchange(self, channel, tick, worker_id, buckets):
        return self.inner.exchange(channel, tick, worker_id, buckets)

    def allgather(self, tag, worker_id, obj):
        return self.inner.allgather(tag, worker_id, obj)

    def barrier(self, worker_id: int):
        self.inner.barrier(worker_id)

    # async (frontier-driven) plane delegation — see MeshComm note
    def supports_async(self) -> bool:
        return self.inner.supports_async()

    def async_attach(self, worker_id, waker):
        self.inner.async_attach(worker_id, waker)

    def async_post_exchange(self, worker_id, channel, time, buckets,
                            ingest_ns=None, seq=None, enq_ns=None):
        return self.inner.async_post_exchange(
            worker_id, channel, time, buckets, ingest_ns, seq, enq_ns
        )

    def async_broadcast(self, worker_id, payload):
        self.inner.async_broadcast(worker_id, payload)

    def async_drain(self, worker_id):
        return self.inner.async_drain(worker_id)

    def async_congested(self, worker_id):
        return self.inner.async_congested(worker_id)

    def abort(self):
        self._local_barrier.abort()
        self.inner.abort()

    def close(self):
        with self._slot_lock:
            self._slots.clear()
        self.inner.close()

    def comm_stats(self) -> dict[str, float]:
        out = dict(self.inner.comm_stats())
        out["mesh_pending_slots"] = float(len(self._slots))
        out.update(self.runner.stats())
        return out

    def _local_index(self, worker_id: int) -> int:
        return worker_id - self.process_id * self.threads

    def exchange_deltas(
        self,
        channel: int,
        tick: int,
        worker_id: int,
        buckets: Sequence[Delta | None],
        column_names: list[str],
    ) -> list[Delta]:
        from ..engine.mesh_exchange import _pow2, agree_kinds

        n = self.n_workers
        parts = [
            (dst, d) for dst, d in enumerate(buckets) if d is not None and len(d)
        ]
        local = concat_deltas([d for _, d in parts], column_names) if parts else None
        dest = (
            np.concatenate(
                [np.full(len(d), dst, dtype=np.int32) for dst, d in parts]
            )
            if parts
            else np.empty(0, dtype=np.int32)
        )
        counts = np.zeros(n, dtype=np.int64)
        for dst, d in parts:
            counts[dst] += len(d)
        sig = local_signature(local, column_names)

        key = (channel, tick)
        tracer = self._tracer
        if tracer is not None:
            tracer.flow_start(
                "mesh.deposit",
                self._flow_id(channel, tick, worker_id, "in"),
                channel=channel,
                tick=tick,
            )
        with self._slot_lock:
            slot = self._slots.setdefault(
                key, {"payloads": [None] * self.threads}
            )
            slot["payloads"][self._local_index(worker_id)] = (local, dest)
        # ONE global control allgather per channel-tick
        metas = self.inner.allgather(
            ("mxh", channel, tick), worker_id, (sig, counts.tolist())
        )
        total = sum(sum(m[1]) for m in metas)
        kinds = agree_kinds([m[0] for m in metas], len(column_names))
        cap_in = _pow2(max(sum(m[1]) for m in metas)) if total else 8
        cap_bucket = _pow2(max(max(m[1]) for m in metas)) if total else 8

        try:
            self._local_barrier.wait()  # all local deposits visible
            leader = self._local_index(worker_id) == 0
            if leader:
                with self._slot_lock:
                    stale = [k for k in self._slots if k[1] < tick]
                    for k in stale:
                        del self._slots[k]
                    slot = self._slots[key]
                try:
                    if total:
                        # count only THIS process's deposited rows — every
                        # leader runs this block, so recording the global
                        # total would inflate the fleet sum n_processes×
                        local_rows = sum(
                            sum(metas[w][1])
                            for w in range(
                                self.process_id * self.threads,
                                (self.process_id + 1) * self.threads,
                            )
                        )
                        self.runner.note_collective(local_rows)
                    slot["result"] = (
                        self._run_collective(
                            slot["payloads"], column_names, kinds,
                            cap_in, cap_bucket,
                        )
                        if total
                        else None
                    )
                    if tracer is not None:
                        base = self.process_id * self.threads
                        for w in range(base, base + self.threads):
                            tracer.flow_end(
                                "mesh.deposit",
                                self._flow_id(channel, tick, w, "in"),
                            )
                            tracer.flow_start(
                                "mesh.result",
                                self._flow_id(channel, tick, w, "out"),
                            )
                except BaseException as e:  # noqa: BLE001
                    slot["result"] = _DriverError(e)
                    self._local_barrier.wait()
                    raise
                self._local_barrier.wait()
            else:
                self._local_barrier.wait()
                slot = self._slots[key]
        except threading.BrokenBarrierError:
            raise RuntimeError(
                "a peer worker failed — aborting mesh exchange"
            ) from None

        result = slot["result"]
        if isinstance(result, _DriverError):
            raise RuntimeError(
                "mesh exchange failed on the process leader"
            ) from result.error
        if tracer is not None:
            tracer.flow_end(
                "mesh.result", self._flow_id(channel, tick, worker_id, "out")
            )

        host_names = [c for c, k in zip(column_names, kinds) if k == HOST]
        host_cols: dict[int, dict[str, np.ndarray]] = {}
        if host_names and total:
            obj_buckets: list[Any] = [None] * n
            if parts:
                per_dst: dict[int, dict[str, list]] = {}
                for dst, d in parts:
                    cols = per_dst.setdefault(dst, {c: [] for c in host_names})
                    for c in host_names:
                        cols[c].append(d.data[c])
                for dst, cols in per_dst.items():
                    obj_buckets[dst] = (
                        worker_id,
                        {c: np.concatenate(v) for c, v in cols.items()},
                    )
            received = self.inner.exchange(
                ("mxh-obj", channel), tick, worker_id, obj_buckets
            )
            for src, cols in received:
                host_cols[src] = cols

        if result is None:
            return []
        gvals, gvalid = result
        per_dev = n * cap_bucket
        my_vals = self.runner.my_shard(gvals, worker_id, per_dev)
        my_valid = self.runner.my_shard(gvalid, worker_id, per_dev)
        return self.runner.unpack_arrivals(
            vals=my_vals,
            valid=my_valid.astype(bool),
            kinds=kinds,
            column_names=column_names,
            host_cols=host_cols,
        )

    def _run_collective(self, payloads, column_names, kinds, cap_in, cap_bucket):
        """Leader thread: pack this PROCESS's workers, form the process-local
        slice of the global array, run the collective with every other
        process's leader."""
        import time as _time

        from ..utils import jaxcfg  # noqa: F401

        import jax

        tracer = self._tracer
        t0 = _time.perf_counter_ns() if tracer is not None else 0
        vals, dst = self.runner.pack_blocks(
            list(payloads), kinds, column_names, cap_in
        )
        sh_v, sh_d = self.runner._mesh_shardings()
        gvals = jax.make_array_from_process_local_data(sh_v, vals)
        gdest = jax.make_array_from_process_local_data(sh_d, dst)
        width = self.runner.width(kinds)
        out = self.runner._kernel(cap_in, cap_bucket, width)(gvals, gdest)
        if tracer is not None:
            tracer.complete(
                "mesh.collective",
                t0,
                {"cap_in": cap_in, "cap_bucket": cap_bucket,
                 "process": self.process_id},
            )
        return out
