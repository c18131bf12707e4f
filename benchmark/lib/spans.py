"""Spans the benchmark records from outside the program, kept in memory and
written out when the run ends. A span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so that the profiler's trace
carries it on the device trace's clock."""

from __future__ import annotations

import contextlib
import threading
import time


class Spans:
    def __init__(self, annotate=None):
        self._lock = threading.Lock()
        self.spans: list[dict] = []
        self._annotate = annotate  # jax.profiler.TraceAnnotation, or None

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = self._annotate(name) if self._annotate else contextlib.nullcontext()
        t0 = time.monotonic()
        try:
            with ann:
                yield attrs
        finally:
            rec = {"name": name, "t0": t0, "t1": time.monotonic(), **attrs}
            with self._lock:
                self.spans.append(rec)

    def within(self, t0: float, t1: float) -> list[dict]:
        """Spans that started inside [t0, t1)."""
        with self._lock:
            return [s for s in self.spans if t0 <= s["t0"] < t1]


def median_ms(spans: list[dict], name: str, **where) -> float | None:
    """Median duration in ms of the recorded spans of that name whose
    attributes equal ``where``; None when there is none."""
    d = sorted((s["t1"] - s["t0"]) * 1e3 for s in spans
               if s["name"] == name and all(s.get(k) == v for k, v in where.items()))
    if not d:
        return None
    mid = len(d) // 2
    return d[mid] if len(d) % 2 else (d[mid - 1] + d[mid]) / 2
