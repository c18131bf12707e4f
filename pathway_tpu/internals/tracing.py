"""In-process span tracing and run profiling.

Re-design of the reference's telemetry pair — Rust OTLP traces/metrics
(``src/engine/telemetry.rs:47-156``) and the Python build/run spans
(``python/pathway/internals/graph_runner/telemetry.py``,
``graph_runner/__init__.py:146-176``) — for an environment with no
network egress: instead of pushing OTLP over gRPC, the tracer records
spans in memory and writes the Chrome Trace Event format (the catapult
JSON array understood by ``chrome://tracing`` and ``ui.perfetto.dev``)
when the run finishes.

Activation is env-first like every other engine knob
(``internals/config.py``): set ``PATHWAY_TRACE_FILE=/path/run.json``.
Spans are also recorded while a ``jax.profiler`` session is live
(``TraceAnnotation.is_enabled()``): start a profile, get the engine's
spans of that stretch, as ``<tempdir>/pathway-tpu/spans/<pid>.json``
written by the end-of-run flush (the newest 8 files are kept). Otherwise
``get_tracer()`` returns ``None`` and every instrumentation site is that
one check — no timestamps are taken, and a run nobody traced writes no
file.

Two clocks, one call: a ``with span(...)`` entered while a profiler
session is live also enters ``jax.profiler.TraceAnnotation(name, **args)``,
so the span lies in the profiler's host plane on the device trace's clock;
the trace file's ``trace.clock_sync`` metadata carries
``origin_monotonic_ns``, so ``origin_monotonic_ns + ts`` is
``time.monotonic_ns()``. (A ``complete(name, t0_ns)`` event — one that
began on another thread or in the past — is in the file only: an annotation
cannot be entered after the fact.) A span entered inside another one, on
the same thread or in the same asyncio task, carries ``parent`` (the
enclosing span's name) and inherits its ``req`` / ``tick`` identifier, so
the spans of one request or one tick share an id and a layer's self time
is its span less its children.

Span taxonomy (mirrors the reference's span names where it has them):

- ``graph.build`` — lowering the parse graph to engine nodes
  (reference span ``graph_runner/__init__.py:146``);
- ``engine.run`` — the whole executor run;
- ``tick`` — one logical-time sweep, with the minted timestamp attached;
- per-node events under each tick, named ``<NodeClass>#<id>``, with the
  emitted row count, the ``tick`` and ``parent: "tick"`` — the analog of
  timely's event logging stream (``DIFFERENTIAL_LOG_ADDR``, reference
  ``dataflow.rs:5540-5548``). While a node's ``process`` runs,
  :meth:`Tracer.scope` names it in the span context, so a span inside an
  operator carries ``parent: "<NodeClass>#<id>"`` and the tick's id: the
  phases ``groupby.update`` / ``groupby.emit``, ``join.probe`` /
  ``join.consolidate`` and ``subscribe.deliver`` (``engine/operators.py``),
  one span a call;
- counter samples of ``EngineStats`` totals per tick (they ride the
  tick's own append: ``complete(..., counter=...)``), rendered by the
  trace viewers as time series, one ``serve_stats`` sample of the
  ``serve/stats.py`` counters at each flush, and a ``fusion_stats`` sample
  of ``engine/fusion.py``'s at a profiler session's first span and at
  each flush;
- the serving path: ``rest.request`` / ``rest.admit`` / ``rest.in_engine``
  / ``rest.wake`` / ``rest.reply`` (``io/http/_server.py``),
  ``connector.window`` (``io/python.py``), ``engine.poll`` and
  ``engine.park`` (the streaming loops: with ``tick`` they cover the engine
  thread from one tick to the next),
  ``index.apply`` (``engine/external_index.py``), ``index.search`` with
  ``index.embed`` / ``index.upload`` / ``index.score`` / ``index.fetch`` /
  ``index.pack`` (``ops/index_engines.py``), ``embed.tokenize`` /
  ``embed.dispatch`` (``models/embedder.py``) — ``docs/observability.md``
  has the table.

Multi-process runs write one file per process (``<path>.p<process_id>``,
like the per-process metrics ports of ``engine/http_server.rs:21``);
worker threads separate naturally by ``tid``. Cross-process linkage is
Dapper-style: every tracer carries a cluster-wide ``run_id``
(``PATHWAY_RUN_ID``, stamped by ``pathway-tpu spawn``), comm frames ship a
``(run_id, flow_id)`` trace context, and both ends emit Chrome flow
events (``ph: s``/``f``) bound by that id — ``pathway-tpu trace merge``
assembles the per-process files into one clock-aligned cluster timeline
(``observability/trace_merge.py``), using the per-peer clock offsets the
cluster handshake estimates (``parallel/cluster.py``) and records here via
:meth:`Tracer.set_clock_offsets`.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import secrets
import sys
import tempfile
import threading
import time
from typing import Any

__all__ = [
    "Tracer",
    "activate",
    "deactivate",
    "get_tracer",
    "init_from_env",
    "mint_flow_tag",
    "run_tracer",
    "span",
    "spans_dir",
]

#: the span this thread (or asyncio task) is inside of, for ``parent`` and
#: the inherited ``req`` / ``tick`` identifier
_current: "contextvars.ContextVar[_Span | _Scope | None]" = contextvars.ContextVar(
    "pathway_span", default=None
)
_ID_KEYS = ("req", "tick")
#: span files of profiler sessions kept under :func:`spans_dir`
_KEEP_SESSION_FILES = 8


def _annotation():
    """``jax.profiler.TraceAnnotation`` if jax's profiler is loaded, else
    None. Never imports jax: a process that has not cannot hold a session
    (and ``import pathway_tpu`` stays off JAX)."""
    mod = sys.modules.get("jax.profiler")
    return getattr(mod, "TraceAnnotation", None)


def mint_flow_tag() -> str:
    """Per-comm-instance disambiguator for deterministic flow ids (ids are
    ``<run_id>/<tag>/...``): several comm backends — or repeated ``pw.run``
    calls under ``activate()`` — share one tracer, and two instances
    minting ids from the same (channel, tick) coordinates must not
    collide. One shared definition so every comm layer's ids stay
    mergeable by the same scheme."""
    return secrets.token_hex(2)


def make_flow_id(tracer: "Tracer", tag: str, *coords: Any) -> str:
    """THE flow-id scheme: ``<run_id>/<tag>/<coord>/...``. Every comm
    backend builds its ids here — the run id scopes them cluster-wide,
    ``tag`` (a :func:`mint_flow_tag`) scopes them per comm instance, and
    the coordinates make them deterministic so sender and receiver can
    mint the same id without shipping context (LocalComm/MeshComm) or
    ship it once per frame (ClusterComm)."""
    return "/".join([tracer.run_id, tag, *map(str, coords)])


def _ids_of(outer: "_Span | _Scope | None") -> dict[str, Any]:
    """The ``req`` / ``tick`` a span hands down to what runs inside it."""
    if outer is None:
        return {}
    return {k: outer.args[k] for k in _ID_KEYS if k in outer.args}


class _Span:
    __slots__ = ("tracer", "name", "args", "counter", "t0", "_ann", "_token")

    def __init__(self, tracer: "Tracer", name: str, args: dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.args = args
        #: ``(name, values)`` set before the span ends: a counter sample
        #: appended with it (see :meth:`Tracer.complete`)
        self.counter = None

    def __enter__(self) -> "_Span":
        args = self.args
        outer = _current.get()
        if outer is not None:
            args.setdefault("parent", outer.name)
            for k, v in _ids_of(outer).items():
                args.setdefault(k, v)
        ann = _annotation()
        if ann is not None and ann.is_enabled():
            self._ann = ann(self.name, **args)
            self._ann.__enter__()
        else:
            self._ann = None
        self._token = _current.set(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.tracer.complete(
            self.name, self.t0, self.args or None, self.counter
        )
        _current.reset(self._token)
        if self._ann is not None:
            self._ann.__exit__(*exc)


class _Scope:
    """What ``_current`` names while code runs on behalf of an event that
    ``complete()`` records when it ends (a node of a tick): a span entered
    meanwhile takes ``name`` as its ``parent`` and inherits the ids. Reads
    no clock, enters no annotation, records nothing itself."""

    __slots__ = ("name", "args", "_token")

    def __init__(self, name: str, args: dict[str, Any]):
        self.name = name
        self.args = args

    def __enter__(self) -> "_Scope":
        self._token = _current.set(self)
        return self

    def __exit__(self, *exc) -> None:
        _current.reset(self._token)


class Tracer:
    """Collects Chrome-trace events; thread-safe, append-only.

    ``path=None`` collects without writing a local trace file — the mode
    used when only an OTLP endpoint (``internals/telemetry.py``) consumes
    the spans."""

    def __init__(self, path: str | None, max_events: int | None = None):
        self.path = path
        #: True for the tracer of profiler sessions (:func:`get_tracer`):
        #: its file goes under :func:`spans_dir`, which flush creates and
        #: prunes
        self.session = False
        self._events: list[dict[str, Any]] = []
        self._lock = threading.Lock()
        self._pid = os.getpid()
        #: cluster-wide run identity: every process of one spawn shares it
        #: (the CLI stamps PATHWAY_RUN_ID), so flow ids minted here are
        #: unique AND recognizable across the whole ensemble's trace files
        self.run_id = os.environ.get("PATHWAY_RUN_ID") or secrets.token_hex(4)
        #: wall-clock anchor of the perf_counter origin — what lets the
        #: merge CLI (and the OTLP exporters) place this process's relative
        #: timestamps on a shared unix timeline
        unix_now = time.time_ns()
        #: perf_counter origin so timestamps start near zero in the viewer
        self._origin = time.perf_counter_ns()
        self.origin_unix_ns = unix_now
        #: the same instant on ``time.monotonic_ns()``, the clock load
        #: generators and harnesses time requests on
        self.origin_monotonic_ns = time.monotonic_ns()
        #: peer process id -> (unix-clock offset ns, rtt ns), estimated by
        #: the cluster handshake ping (ClusterComm); written to the trace
        #: file so `trace merge` can align per-host clocks
        self._clock_offsets: dict[int, tuple[float, float]] = {}
        #: streaming pipelines run forever (run.py) — bound the buffer so
        #: tracing a long-lived run keeps the most recent window instead of
        #: growing without limit; oldest half is dropped on overflow
        if max_events is None:
            max_events = int(
                os.environ.get("PATHWAY_TRACE_MAX_EVENTS", "500000")
            )
        self._max_events = max(max_events, 2)
        self._dropped = 0
        self._appended = 0
        self._flush_mark = -1  # _appended value at the last write
        #: incremental-export cursor shared by the periodic OTLP flusher
        #: and the end-of-run push (internals/telemetry.py)
        self._otlp_mark = 0

    # -- recording ----------------------------------------------------

    def _ts(self, ns: int) -> float:
        return (ns - self._origin) / 1e3  # µs

    def span(self, name: str, **args: Any) -> _Span:
        """``with tracer.span("graph.build", tables=3): ...``"""
        return _Span(self, name, args)

    def scope(self, name: str, **ids: Any) -> _Scope:
        """``with tracer.scope("Join#23", tick=t): ...`` around work whose
        own event is a ``complete()`` at its end: the spans inside name it
        as parent."""
        return _Scope(name, ids)

    def complete(
        self,
        name: str,
        t0_ns: int,
        args: dict[str, Any] | None = None,
        counter: tuple[str, dict[str, float]] | None = None,
    ) -> None:
        """A finished duration event that began at ``t0_ns``. With
        ``counter=(name, values)`` a counter sample is appended in the SAME
        lock acquisition, so the pair is adjacent in the buffer and the
        overflow drop can never orphan the sample from its span (the
        executor's per-tick row counters use this)."""
        ev = {
            "name": name,
            "ph": "X",
            "ts": self._ts(t0_ns),
            "dur": (time.perf_counter_ns() - t0_ns) / 1e3,
            "pid": self._pid,
            "tid": threading.get_ident() % 2**31,
        }
        if args:
            ev["args"] = args
        if counter is None:
            self._append(ev)
            return
        cname, values = counter
        cev = {
            "name": cname,
            "ph": "C",
            "ts": ev["ts"] + ev["dur"],
            "pid": self._pid,
            "args": values,
        }
        self._append(ev, cev)

    def _append(self, *evs: dict[str, Any]) -> None:
        with self._lock:
            self._events.extend(evs)
            self._appended += len(evs)
            if len(self._events) > self._max_events:
                n = len(self._events)
                drop = n // 2
                # span-boundary-consistent chunking: never let the kept
                # window BEGIN with a counter sample whose owning span was
                # just dropped (complete(..., counter=...) appends the pair
                # adjacently, so skipping leading "C" events preserves it)
                while drop < n and self._events[drop].get("ph") == "C":
                    drop += 1
                if drop >= n:  # pathological all-counter buffer
                    drop = n // 2
                self._dropped += drop
                del self._events[:drop]

    def instant(self, name: str, **args: Any) -> None:
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",
            "ts": self._ts(time.perf_counter_ns()),
            "pid": self._pid,
            "tid": threading.get_ident() % 2**31,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def _counter_event(self, name: str, values: dict[str, float]) -> dict:
        return {
            "name": name,
            "ph": "C",
            "ts": self._ts(time.perf_counter_ns()),
            "pid": self._pid,
            "args": values,
        }

    def _fusion_sample(self) -> dict:
        """The engine's fusion and consolidation counters as they stand
        now (``engine/fusion.py`` FUSION_STATS): a session's first span and
        every written file carry one, so their difference is the session's
        own share (rows consolidated, rows whose content was hashed)."""
        from ..engine.fusion import FUSION_STATS

        return self._counter_event("fusion_stats", dict(FUSION_STATS))

    # -- cross-worker flow linkage ------------------------------------

    def flow_start(self, name: str, flow_id: str, **args: Any) -> None:
        """Begin a Chrome flow (``ph: s``) — the sending half of a
        cross-worker arrow. The event must fall inside a duration slice on
        this thread (comm call sites sit inside the tick span); the
        receiving side closes the flow with :meth:`flow_end` using the
        SAME id, which travels in the comm frame's trace context."""
        ev = {
            "name": name,
            "cat": "comm",
            "ph": "s",
            "id": str(flow_id),
            "ts": self._ts(time.perf_counter_ns()),
            "pid": self._pid,
            "tid": threading.get_ident() % 2**31,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def flow_end(self, name: str, flow_id: str, **args: Any) -> None:
        """Close a flow (``ph: f``) at the receiving worker; ``bp: e``
        binds the arrow to the enclosing slice."""
        ev = {
            "name": name,
            "cat": "comm",
            "ph": "f",
            "bp": "e",
            "id": str(flow_id),
            "ts": self._ts(time.perf_counter_ns()),
            "pid": self._pid,
            "tid": threading.get_ident() % 2**31,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    # -- merge/alignment metadata -------------------------------------

    def set_clock_offsets(self, offsets: dict[int, tuple[float, float]]) -> None:
        """Record per-peer unix-clock offset estimates (peer process id ->
        (offset ns, rtt ns), offset = peer clock minus ours) from the
        cluster handshake ping — flushed as ``trace.clock_sync`` metadata
        for ``pathway-tpu trace merge``."""
        with self._lock:
            self._clock_offsets = dict(offsets)

    def events_since(self, mark: int) -> tuple[list[dict[str, Any]], int]:
        """Events appended after the ``mark`` cursor (an ``_appended``
        value), plus the new cursor — the incremental-export protocol used
        by the periodic OTLP flusher (observability/exporter.py) and the
        end-of-run push, which share one cursor so nothing double-exports.
        Events already dropped by the ring buffer are simply gone: when
        more than ``new`` events were appended but the buffer holds fewer,
        the negative slice caps at the buffer — every returned event is
        still strictly after ``mark`` (the buffer always holds the newest
        ``len(_events)`` appends), so a drop can neither skip live events
        nor re-export old ones (tests/test_tracing.py drop-cursor cases)."""
        with self._lock:
            new = self._appended - mark
            if new <= 0:
                return [], self._appended
            return list(self._events[-new:]), self._appended

    # -- output -------------------------------------------------------

    def flush(self) -> str | None:
        """Write the full event buffer to the trace file. Re-flushable: a
        tracer kept alive across several ``pw.run`` calls (``activate()``)
        rewrites the file with the accumulated events each time; a flush
        with nothing new since the last write is a no-op. Never raises —
        tracing is auxiliary and must not fail (or mask the error of) the
        run it observes."""
        if self.path is None:  # OTLP-only mode: no local file
            return None
        with self._lock:
            if self._flush_mark == self._appended:
                return None
            self._flush_mark = self._appended
            events = list(self._events)
        path = self.path
        # raw env read, not PathwayConfig: config validation can refuse the
        # worker layout (e.g. over the worker cap) and flush must not raise
        try:
            n_processes = int(os.environ.get("PATHWAY_PROCESSES", "1"))
            process_id = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
        except ValueError:
            n_processes, process_id = 1, 0
        if n_processes > 1:
            path = f"{path}.p{process_id}"
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": self._pid,
                "args": {"name": "pathway_tpu"},
            },
            # merge/alignment anchor: run identity, this process's place in
            # the ensemble, its unix-clock origin, and the handshake's
            # per-peer clock-offset estimates (trace_merge.py consumes it)
            {
                "name": "trace.clock_sync",
                "ph": "i",
                "s": "g",
                "ts": 0.0,
                "pid": self._pid,
                "tid": 0,
                "args": {
                    "run_id": self.run_id,
                    "process_id": process_id,
                    "origin_unix_ns": self.origin_unix_ns,
                    "origin_monotonic_ns": self.origin_monotonic_ns,
                    # how long a thread may hold the interpreter lock while
                    # another waits for it: what ``rest.wake`` is read against
                    "switch_interval_s": sys.getswitchinterval(),
                    "clock_offsets": {
                        str(p): [off, rtt]
                        for p, (off, rtt) in sorted(
                            self._clock_offsets.items()
                        )
                    },
                },
            },
        ]
        if self._dropped:
            meta.append(
                {
                    "name": "trace.dropped_events",
                    "ph": "i",
                    "s": "g",
                    "ts": 0.0,
                    "pid": self._pid,
                    "tid": 0,
                    "args": {"count": self._dropped},
                }
            )
        # the serve-plane counters as they stand now (index, embedder and
        # connector counts among them): one sample per written file, not
        # kept in the buffer
        from ..serve.stats import SERVE_STATS

        events.append(self._counter_event("serve_stats", dict(SERVE_STATS)))
        events.append(self._fusion_sample())
        try:
            if self.session:
                os.makedirs(os.path.dirname(path), exist_ok=True)
            # atomic rewrite: the periodic flusher rewrites this file every
            # interval, and a SIGKILL mid-write must leave the PREVIOUS
            # complete flush on disk, not a torn JSON — crashed runs are
            # exactly the ones whose trace gets read
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(
                    {"traceEvents": meta + events, "displayTimeUnit": "ms"}, f
                )
            os.replace(tmp, path)
            if self.session:
                _prune_session_files(os.path.dirname(path))
        except (OSError, TypeError, ValueError) as e:
            import warnings

            warnings.warn(
                f"could not write trace file {path!r}: {e}", RuntimeWarning
            )
            return None
        return path


_active: Tracer | None = None
_env_checked = False
_programmatic = False
#: the tracer of this process's profiler sessions, made when the first
#: one is seen live; None in a process nobody profiled
_session: Tracer | None = None
_session_lock = threading.Lock()
_NO_SPAN = contextlib.nullcontext()


def spans_dir() -> str:
    """Where the spans of profiler sessions go, one file per process."""
    return os.path.join(tempfile.gettempdir(), "pathway-tpu", "spans")


def _prune_session_files(directory: str) -> None:
    try:
        files = [
            os.path.join(directory, n)
            for n in os.listdir(directory)
            if n.endswith(".json")
        ]
        files.sort(key=os.path.getmtime, reverse=True)
        for old in files[_KEEP_SESSION_FILES:]:
            os.remove(old)
    except OSError:
        pass  # another process pruning the same directory


def activate(path: str) -> Tracer:
    """Programmatic activation (the env var is the usual route). Survives
    ``pw.run``'s env re-read until ``deactivate()``."""
    global _active, _env_checked, _programmatic
    _active = Tracer(path)
    _env_checked = True
    _programmatic = True
    return _active


def deactivate() -> None:
    global _active, _env_checked, _programmatic
    _active = None
    _env_checked = True
    _programmatic = False


def init_from_env() -> Tracer | None:
    """Install a tracer if ``PATHWAY_TRACE_FILE`` is set (read through
    ``PathwayConfig`` so the config snapshot and the tracer agree). Called
    at the top of every run so each ``pw.run`` re-reads the environment; a
    tracer installed via ``activate()`` is kept as-is."""
    global _active, _env_checked
    if _programmatic:
        return _active
    try:
        from .config import get_pathway_config

        path = get_pathway_config().trace_file
    except (ImportError, RuntimeError):
        # config can refuse bad worker env vars; tracing still works
        path = os.environ.get("PATHWAY_TRACE_FILE")
    if path:
        _active = Tracer(path)
    elif os.environ.get("PATHWAY_TELEMETRY_SERVER") or os.environ.get(
        "PATHWAY_MONITORING_SERVER"
    ):
        # an OTLP endpoint alone still needs a span collector — file-less
        # tracer (the reference enables telemetry without local tracing,
        # telemetry.rs:215-221)
        _active = Tracer(None)
    else:
        _active = None
    _env_checked = True
    return _active


def get_tracer() -> Tracer | None:
    """The tracer spans go to NOW: the one an operator asked for, else the
    session tracer while a ``jax.profiler`` session is live, else None.
    Call it at the site, not once at construction: a profile may start in
    the middle of a run."""
    global _session
    if not _env_checked:
        init_from_env()
    if _active is not None:
        return _active
    ann = _annotation()
    if ann is None or not ann.is_enabled():
        return None
    if _session is None:
        with _session_lock:
            if _session is None:
                tracer = Tracer(
                    os.path.join(spans_dir(), f"{os.getpid()}.json")
                )
                tracer.session = True
                tracer._append(tracer._fusion_sample())
                _session = tracer
    return _session


def run_tracer() -> Tracer | None:
    """The tracer a run flushes when it ends: the operator's, else the
    session tracer if a profiler session was live at any point (its flush
    is a no-op when nothing was recorded since the last one)."""
    if not _env_checked:
        init_from_env()
    return _active if _active is not None else _session


def span(name: str, **args: Any):
    """Span on the tracer of the moment, or a no-op context when nothing
    records — lets instrumentation sites keep a single code path. ``with
    span(...) as sp`` gives the span (``sp.args`` may be added to until it
    ends) or None."""
    tracer = get_tracer()
    if tracer is None:
        return _NO_SPAN
    return tracer.span(name, **args)


def current_ids() -> dict[str, Any]:
    """``req`` / ``tick`` of the span this thread is inside of — for a
    ``complete()`` event that ends here but began elsewhere."""
    return _ids_of(_current.get())
