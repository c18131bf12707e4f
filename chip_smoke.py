"""Chip smoke: the live RAG path, end to end, on the TPU.

    python3 chip_smoke.py

drives the system's main path once through the entry points a user calls —
``examples/rag_server/serve.py``'s ``DocumentStoreServer`` at the full
MiniLM-L6 widths of the default ``EmbedderConfig`` (6 layers, 384 wide, 12
heads, 1536 feed-forward, vocabulary 30,528, bf16; random weights from a
seed) — and checks that what comes out is right. It is the quickest proof
that the system still starts on the chip. It measures nothing: the wall
times it prints are smoke timings, not metrics.

This process never imports JAX. It runs the phases as sequential child
processes (``--phase NAME``), so exactly one process holds the chip at a
time; the children share the persistent compilation cache that
``pathway_tpu/utils/jaxcfg.py`` places. Each phase prints one JSON line
naming the device it ran on; a failed phase fails the run. The last line of
standard output is ``{"ok": true, "device": {...}}``.

Phases:

- ``rag``    ingest (>= 8,192 chunks embedded on the chip), serve (sequential
             requests and one concurrent burst), live update (add a file,
             delete a file) — three lines, one server, one process;
- ``index``  a second server in pre-embedded mode over 1,000,000 x 384
             vectors with one needle row;
- ``host``   a static 1M-row wordcount on the host plane, native module
             required;
- ``fourchip`` only when JAX reports >= 4 devices: the multi-chip dry run,
             the mesh-sharded index at 1M x 384 and the sharded server's
             block placement.

Without a TPU a child exits non-zero with one line saying so.
``--rehearse-on-cpu`` runs every phase at a tiny size on whatever platform
JAX has (the sandbox's CPU) to debug the script itself; no absence of a chip
selects it.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

SEED = 20260926
#: the contract's limit is 1200 s; leave room to report
DEADLINE_S = 1150.0
NO_TPU_EXIT = 2

SIZES = {
    "chip": {
        "files": 512, "chunks_per_file": 16, "min_chunks": 8192,
        "sequential": 32, "burst": 64, "live_deadline_s": 90.0,
        "index_rows": 1_000_000, "index_sequential": 16,
        "wordcount_rows": 1_000_000, "sharded_files": 64,
    },
    "rehearsal": {
        "files": 6, "chunks_per_file": 2, "min_chunks": 12,
        "sequential": 4, "burst": 8, "live_deadline_s": 90.0,
        "index_rows": 4096, "index_sequential": 3,
        "wordcount_rows": 20_000, "sharded_files": 6,
    },
}
DIM = 384
K = 3
SENTENCE_WORDS = 32
#: TokenCountSplitter(max_tokens=256) packs exactly this many sentences
SENTENCES_PER_CHUNK = 256 // SENTENCE_WORDS


# -- parent: no JAX here ----------------------------------------------------


def run_children(rehearse: bool) -> int:
    t_end = time.monotonic() + DEADLINE_S
    records: list[dict] = []
    for phase in ("rag", "index", "host"):
        records += _run_child(phase, rehearse, t_end)
    device = records[0]["device"]
    if device["count"] >= 4:
        records += _run_child("fourchip", rehearse, t_end)
    # the cache is live when a later process found programs an earlier
    # one compiled (the index server embeds queries with rag's programs)
    later_hits = sum(r["cache_hits"] for r in records if r["phase"] == "index")
    if later_hits < 1:
        print("chip_smoke: the second process found nothing in the "
              "compile cache", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _run_child(phase: str, rehearse: bool, t_end: float) -> list[dict]:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if rehearse:
        cmd.append("--rehearse-on-cpu")
    records: list[dict] = []
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, text=True, start_new_session=True,
    )
    killer = threading.Timer(
        max(1.0, t_end - time.monotonic()), _kill_group, args=(proc,)
    )
    killer.start()
    try:
        assert proc.stdout is not None
        for line in proc.stdout:
            print(line, end="", flush=True)
            if line.startswith("{"):
                records.append(json.loads(line))
        code = proc.wait()
    finally:
        killer.cancel()
        _kill_group(proc)
    if code == NO_TPU_EXIT:
        sys.exit(code)  # the child has said so, in one line
    if code != 0 or not records:
        print(f"chip_smoke: phase {phase} failed (exit {code})",
              file=sys.stderr)
        sys.exit(1)
    return records


def _kill_group(proc: subprocess.Popen) -> None:
    """Stop the child and anything it started."""
    with contextlib.suppress(ProcessLookupError):
        os.killpg(proc.pid, signal.SIGKILL)


# -- child: one phase, one process, one chip --------------------------------


class PhaseLog:
    """Counts this process's XLA compilations and prints the phase lines."""

    def __init__(self, rehearse: bool):
        from pathway_tpu.utils import jaxcfg  # noqa: F401  (places the cache)

        import jax

        self.jax = jax
        dev = jax.devices()[0]
        if dev.platform != "tpu" and not rehearse:
            print(f"chip_smoke: no TPU — JAX runs on {dev.platform!r}",
                  file=sys.stderr)
            sys.exit(NO_TPU_EXIT)
        self.device = {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
        }
        self._lock = threading.Lock()
        self._requests = 0
        self._hits = 0
        self._secs = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        self._mark = (0, 0, 0.0, time.monotonic())

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self._hits += 1

    def _on_secs(self, event: str, secs: float, **_: object) -> None:
        # one per program handed to the backend, cache hit or not
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._requests += 1
                self._secs += secs

    def emit(self, phase: str, **facts: object) -> None:
        """One JSON line for the phase that just ended."""
        with self._lock:
            now = (self._requests, self._hits, self._secs, time.monotonic())
        r0, h0, s0, t0 = self._mark
        self._mark = now
        stats = self.jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "phase": phase,
            "device": self.device,
            "jax": self.jax.__version__,
            "smoke_wall_s": round(now[3] - t0, 2),
            "compiles": (now[0] - r0) - (now[1] - h0),
            "cache_hits": now[1] - h0,
            "compile_s": round(now[2] - s0, 2),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            **facts,
        }), flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port: int, route: str, payload: dict, timeout: float = 150.0):
    """(status, decoded body). An HTTP error status is returned, not raised;
    status 0 means no answer (the server is not listening yet)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")
    except (urllib.error.URLError, OSError) as e:
        return 0, str(e)


def _file_count(port: int) -> int | None:
    """Documents the store holds, by /v1/statistics (None before the first)."""
    status, body = _post(port, "/v1/statistics", {})
    return body["file_count"] if status == 200 and body else None


def _await(what: str, deadline_s: float, probe) -> float:
    """Poll ``probe`` until it is true; seconds it took. Raises past the
    deadline."""
    t0 = time.monotonic()
    while True:
        if probe():
            return time.monotonic() - t0
        if time.monotonic() - t0 > deadline_s:
            raise RuntimeError(f"{what}: not within {deadline_s:.0f} s")
        time.sleep(0.25)


@contextlib.contextmanager
def _serving(server):
    """Run a DocumentStoreServer on its own thread; stop it on the way out."""
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.run import request_stop
    from pathway_tpu.io.http._server import terminate_all

    thread = server.run(threaded=True)
    try:
        yield
    finally:
        request_stop()
        terminate_all()
        thread.join(timeout=30)
        G.clear()


def _engines() -> list:
    """The live index engines of this process (built inside the lowered
    graph, so found by type)."""
    import gc

    from pathway_tpu.ops.index_engines import BruteForceKnnEngine

    return [o for o in gc.get_objects() if isinstance(o, BruteForceKnnEngine)]


def _top(body: list) -> tuple[str, float]:
    """(text, cosine) of the best hit; ``dist`` is the negated similarity."""
    return body[0]["text"], -float(body[0]["dist"])


def _check_retrieve(port: int, query: str, expect_text: str | None) -> None:
    status, body = _post(port, "/v1/retrieve", {"query": query, "k": K})
    if status != 200 or len(body) != K:
        raise RuntimeError(f"retrieve: status {status}, body {str(body)[:200]}")
    if expect_text is not None:
        text, cos = _top(body)
        if text != expect_text or cos < 0.99:
            raise RuntimeError(
                f"planted chunk not first: cosine {cos:.4f}, got {text[:60]!r}"
            )


def _burst(port: int, queries: list[tuple[str, str | None]]) -> None:
    """All queries at once, one thread each; every one must pass."""
    errors: list[Exception] = []

    def fire(q: str, expect: str | None) -> None:
        try:
            _check_retrieve(port, q, expect)
        except Exception as e:  # reported below, on the main thread
            errors.append(e)

    threads = [threading.Thread(target=fire, args=qe) for qe in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    if errors or any(t.is_alive() for t in threads):
        raise RuntimeError(f"burst: {len(errors)} failed; first: {errors[:1]}")


class Corpus:
    """Seeded text files whose chunking is known in advance: sentences of
    exactly SENTENCE_WORDS words, so a bulk file splits into exactly
    ``chunks_per_file`` chunks, and a short file is one chunk whose text is
    the file's text verbatim."""

    def __init__(self, root: str, seed: int):
        self.root = root
        self.rng = random.Random(seed)
        letters = "abcdefghijklmnopqrstuvwxyz"
        self.vocab = [
            "".join(self.rng.choices(letters, k=self.rng.randint(3, 9)))
            for _ in range(4096)
        ]

    def _sentences(self, n: int) -> str:
        return " ".join(
            " ".join(self.rng.choices(self.vocab, k=SENTENCE_WORDS)) + "."
            for _ in range(n)
        )

    def write_bulk(self, name: str, chunks: int) -> None:
        self._write(name, self._sentences(chunks * SENTENCES_PER_CHUNK))

    def write_single_chunk(self, name: str) -> str:
        text = self._sentences(SENTENCES_PER_CHUNK - 2)
        self._write(name, text)
        return text

    def queries(
        self, n: int, every: int, planted: list[tuple[str, str]]
    ) -> list[tuple[str, str | None]]:
        """``n`` (query, expected best text) pairs: every ``every``-th is a
        planted one, the others random words with nothing expected."""
        return [
            planted[(i // every) % len(planted)] if i % every == 0
            else (" ".join(self.rng.choices(self.vocab, k=12)), None)
            for i in range(n)
        ]

    def _write(self, name: str, text: str) -> None:
        os.makedirs(self.root, exist_ok=True)
        # rename into place: the watcher never reads a half-written file
        tmp = os.path.join(self.root, f".{name}.part")
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, os.path.join(self.root, name))


def phase_rag(sz: dict, log: PhaseLog, workdir: str) -> None:
    from examples.rag_server.serve import build_server
    from pathway_tpu.ops.knn import topk_scores

    corpus = Corpus(os.path.join(workdir, "docs"), SEED)
    for i in range(sz["files"]):
        corpus.write_bulk(f"bulk_{i:04d}.txt", sz["chunks_per_file"])
    planted = {
        f"planted_{i}.txt": corpus.write_single_chunk(f"planted_{i}.txt")
        for i in range(4)
    }
    n_files = sz["files"] + len(planted)
    n_chunks = sz["files"] * sz["chunks_per_file"] + len(planted)
    assert n_chunks >= sz["min_chunks"]

    port = _free_port()
    server = build_server(corpus.root, "127.0.0.1", port)
    with _serving(server):
        # -- ingest: every chunk embedded on the chip by add_batch
        _await("ingest", 900.0, lambda: _file_count(port) == n_files)
        (engine,) = _engines()
        indexed = int(engine._valid.sum())
        if indexed != n_chunks:
            raise RuntimeError(f"indexed {indexed} chunks, wrote {n_chunks}")
        status, inputs = _post(port, "/v1/inputs", {})
        if status != 200 or len(inputs) != n_files:
            raise RuntimeError(f"inputs: status {status}, {len(inputs)} files")
        embed_fwd = engine.embedder._fwd
        log.emit(
            "ingest", files=n_files, chunks=indexed,
            index_capacity=engine.capacity,
            embed_forward_shapes=embed_fwd._cache_size(),
        )

        # -- serve: correctness of sequential requests and one burst
        verbatim = [(text, text) for text in planted.values()]
        for q, expect in corpus.queries(sz["sequential"], 2, verbatim):
            _check_retrieve(port, q, expect)
        _burst(port, corpus.queries(sz["burst"], 4, verbatim))
        log.emit(
            "serve", sequential=sz["sequential"], burst=sz["burst"], k=K,
            embed_forward_shapes=embed_fwd._cache_size(),
            topk_shapes=topk_scores._cache_size(),
        )

        # -- live update: the index follows the directory while serving
        fresh = corpus.write_single_chunk("live_added.txt")

        def sees_fresh() -> bool:
            status, body = _post(port, "/v1/retrieve", {"query": fresh, "k": K})
            return status == 200 and bool(body) and _top(body)[0] == fresh

        add_s = _await("live add", sz["live_deadline_s"], sees_fresh)
        _check_retrieve(port, fresh, fresh)
        gone = planted["planted_0.txt"]
        os.unlink(os.path.join(corpus.root, "planted_0.txt"))

        def forgot_gone() -> bool:
            status, body = _post(port, "/v1/retrieve", {"query": gone, "k": K})
            return status == 200 and all(hit["text"] != gone for hit in body)

        delete_s = _await("live delete", sz["live_deadline_s"], forgot_gone)
        if _file_count(port) != n_files:
            raise RuntimeError("after add+delete: the file count is off")
        log.emit(
            "live_update", deadline_s=sz["live_deadline_s"],
            add_visible_smoke_s=round(add_s, 2),
            delete_visible_smoke_s=round(delete_s, 2),
            index_block_devices=sorted(
                str(d) for d in engine._device.devices()
            ),
        )


def phase_index(sz: dict, log: PhaseLog, workdir: str) -> None:
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.ops.knn import topk_scores
    from pathway_tpu.serve.stats import SERVE_STATS
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnFactory,
    )
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import TpuEmbedder
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    n = sz["index_rows"]
    needle_row = n // 3
    needle = "where does the needle row of the seeded index live"
    embedder = TpuEmbedder()
    needle_vec = embedder.embedder.embed_texts([needle])[0]
    rng = np.random.default_rng(SEED)
    feed_rows = 65_536

    written = "which row was written after the first search"
    written_vec = embedder.embedder.embed_texts([written])[0]
    write_now = threading.Event()

    class Feed(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for start in range(0, n, feed_rows):
                stop = min(start + feed_rows, n)
                vecs = rng.standard_normal((stop - start, DIM), dtype=np.float32)
                vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
                if start <= needle_row < stop:
                    vecs[needle_row - start] = needle_vec
                if start == 0:
                    first_vec = vecs[0].copy()
                self.next_batch({
                    "data": [f"doc {i}" for i in range(start, stop)],
                    "_metadata": [
                        {"path": f"d{i}.txt"} for i in range(start, stop)
                    ],
                    "vec": list(vecs),
                })
                self.commit()
            # one write once the index has been searched: row 0 replaced
            # (its slot goes through the free list), the count unchanged
            write_now.wait()
            self.next_batch({
                "data": ["doc 0", "doc written"],
                "_metadata": [{"path": "d0.txt"}, {"path": "written.txt"}],
                "vec": [first_vec, written_vec],
            }, np.array([-1, 1], np.int64))
            self.commit()

    docs = pw.io.python.read(
        Feed(),
        schema=pw.schema_from_types(data=str, _metadata=dict, vec=np.ndarray),
        autocommit_duration_ms=None,
    )
    store = DocumentStore(
        docs,
        BruteForceKnnFactory(
            dimensions=DIM, reserved_space=n, embedder=embedder.embedder,
        ),
        vector_column="vec",
    )
    port = _free_port()
    server = DocumentStoreServer("127.0.0.1", port, store)
    words = Corpus(workdir, SEED + 1)
    planted = [(needle, f"doc {needle_row}")]
    with _serving(server):
        _await("index build", 900.0, lambda: _file_count(port) == n)
        for q, expect in words.queries(sz["index_sequential"], 2, planted):
            _check_retrieve(port, q, expect)
        _burst(port, words.queries(sz["burst"], 8, planted))
        # the write path: the row lands in the device block in place, so
        # the block is not placed again and the written row is found
        before = dict(SERVE_STATS)
        write_now.set()

        def written_found() -> bool:
            try:
                _check_retrieve(port, written, "doc written")
            except RuntimeError:
                return False
            return True

        _await("the written row", 60.0, written_found)
        grew = {k: SERVE_STATS[k] - before[k] for k in (
            "index_uploads_total", "index_writes_total", "index_write_rows_total")}
        if grew["index_uploads_total"] or grew["index_writes_total"] != 1:
            raise RuntimeError(f"a write re-placed the index block: {grew}")
        (engine,) = _engines()
        log.emit(
            "index", rows=int(engine._valid.sum()), dim=DIM,
            index_capacity=engine.capacity,
            sequential=sz["index_sequential"], burst=sz["burst"], k=K,
            topk_shapes=topk_scores._cache_size(), **grew,
        )


def phase_host(sz: dict, log: PhaseLog, workdir: str) -> None:
    import pathway_tpu as pw
    from pathway_tpu.native import native_available, native_unavailable_reason

    if not native_available():
        raise RuntimeError(
            f"native module unavailable: {native_unavailable_reason()}"
        )
    rng = random.Random(SEED)
    words = [f"w{rng.randrange(997)}" for _ in range(sz["wordcount_rows"])]
    path = os.path.join(workdir, "words.csv")
    with open(path, "w") as f:
        f.write("word\n" + "\n".join(words) + "\n")
    table = pw.io.csv.read(
        path, schema=pw.schema_from_types(word=str), mode="static"
    )
    counts = table.groupby(pw.this.word).reduce(
        pw.this.word, count=pw.reducers.count()
    )
    got: dict[str, int] = {}

    def on_change(key, row, time, is_addition) -> None:
        if is_addition:
            got[row["word"]] = int(row["count"])

    pw.io.subscribe(counts, on_change=on_change)
    pw.run()
    if got != dict(collections.Counter(words)):
        raise RuntimeError("wordcount: counts differ from the reference")
    x64 = bool(log.jax.config.jax_enable_x64)
    log.emit("host", rows=len(words), distinct=len(got), native=True, x64=x64)
    if x64:
        # the chip never runs a program under x64, and nothing in the
        # package may switch it on
        raise RuntimeError("host: jax_enable_x64 is on")


def phase_fourchip(sz: dict, log: PhaseLog, workdir: str) -> None:
    import numpy as np

    import __graft_entry__
    from examples.rag_server.serve import build_server
    from pathway_tpu.ops.knn import ShardedKnnIndex, topk_scores

    jax = log.jax
    devices = jax.devices()[:4]

    # (a) the multi-chip dry run: (data, model) step, sharded KNN
    # all-gather, ring attention ppermute, MeshComm all-to-all, recovery
    __graft_entry__.dryrun_multichip(4)
    log.emit("fourchip_dryrun", devices=[str(d) for d in devices])

    # (b) the mesh-sharded index at the north-star size: a quarter a chip,
    # answers equal to one device's
    n = sz["index_rows"]
    rng = np.random.default_rng(SEED)
    docs = rng.standard_normal((n, DIM), dtype=np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    planted = np.linspace(0, n - 1, 8).astype(np.int64)  # rows in every shard
    queries = docs[planted]
    mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    index = ShardedKnnIndex(dim=DIM, capacity=n, mesh=mesh)
    index.add(docs)
    shard_rows = sorted(
        (str(s.device), s.data.shape[0])
        for s in index._data.addressable_shards
    )
    if [r for _, r in shard_rows] != [n // 4] * 4:
        raise RuntimeError(f"index not a quarter a chip: {shard_rows}")
    s_mesh, i_mesh = index.query(queries, k=10)
    s_one, i_one = (np.asarray(a) for a in topk_scores(
        jax.device_put(queries, devices[0]),
        jax.device_put(docs, devices[0]), 10,
    ))
    if not (i_mesh[:, 0] == planted).all() or not (i_one[:, 0] == planted).all():
        raise RuntimeError("sharded index: planted rows not first")
    # scores are bf16 products, so near-ties may order differently: equal
    # score lists, and every id the mesh returned scores what it claims
    if not np.allclose(s_mesh, s_one, atol=4e-3):
        raise RuntimeError("sharded index: scores differ from one device's")
    claimed = np.einsum("qkd,qd->qk", docs[i_mesh], queries)
    if not np.allclose(claimed, s_mesh, atol=8e-3):
        raise RuntimeError("sharded index: ids do not score what they claim")
    log.emit(
        "fourchip_index", rows=n, dim=DIM, shard_rows=shard_rows,
        ids_identical=bool((i_mesh == i_one).all()),
    )
    del index, docs

    # (c) the served pipeline with four worker shards: where each shard's
    # index block lives. The lock-step executor is pinned because under the
    # default asynchronous one a multi-worker DocumentStoreServer answers
    # before the retrieve cascade has crossed the workers, with [] (README
    # "Running"; drop the pin when that is repaired).
    os.environ["PATHWAY_THREADS"] = "4"
    os.environ["PATHWAY_SERVE_SHARDED"] = "1"
    os.environ["PATHWAY_ASYNC_EXEC"] = "0"
    corpus = Corpus(os.path.join(workdir, "docs"), SEED)
    for i in range(sz["sharded_files"]):
        corpus.write_bulk(f"bulk_{i:04d}.txt", sz["chunks_per_file"])
    text = corpus.write_single_chunk("planted.txt")
    n_files = sz["sharded_files"] + 1
    port = _free_port()
    server = build_server(corpus.root, "127.0.0.1", port)
    with _serving(server):
        _await("sharded ingest", 600.0, lambda: _file_count(port) == n_files)
        _check_retrieve(port, text, text)
        blocks = sorted(
            (int(e._valid.sum()), sorted(str(d) for d in e._device.devices()))
            for e in _engines() if e._device is not None
        )
        log.emit(
            "fourchip_serve", threads=os.environ["PATHWAY_THREADS"],
            shard_blocks=[{"rows": r, "devices": d} for r, d in blocks],
        )


PHASES = {
    "rag": phase_rag, "index": phase_index, "host": phase_host,
    "fourchip": phase_fourchip,
}


def run_phase(phase: str, rehearse: bool) -> int:
    log = PhaseLog(rehearse)
    with tempfile.TemporaryDirectory(prefix=f"chip_smoke_{phase}_") as workdir:
        PHASES[phase](SIZES["rehearsal" if rehearse else "chip"], log, workdir)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="run one phase in this process (what the parent "
                    "starts; also how the four-chip leg is run by hand)")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="tiny sizes on whatever platform JAX has; for "
                    "debugging this script where there is no chip")
    args = ap.parse_args()
    if args.phase:
        return run_phase(args.phase, args.rehearse_on_cpu)
    return run_children(args.rehearse_on_cpu)


if __name__ == "__main__":
    sys.exit(main())
