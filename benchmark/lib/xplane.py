"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
readers need: the device's busy union, each device program's time per call,
the device operations that took most time, and the idle gaps named by the
benchmark span that covered them. Reads with ``jax.profiler.ProfileData`` and
nothing else.

On a TPU the device is the plane ``/device:TPU:<n>``; its line ``XLA Modules``
holds one event per program execution (``jit_topk_scores(...)``) and ``XLA Ops``
one per operation. A trace with no device plane (the CPU rehearsal) reduces
the XLA CPU client's threads in the device's place, so that the same code runs;
such a run is a rehearsal and reports under no device metric's name.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
CPU_STAND_IN = "tf_XLAPjRtCpuClient"
#: device gaps shorter than this are launch spacing, not idleness worth a name
MIN_GAP_S = 20e-6


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """(covered seconds, merged intervals) of [(start, end)] in seconds."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def _stats(durations: list[float]) -> dict:
    return {"calls": len(durations), "total_s": sum(durations),
            "median_s": sorted(durations)[len(durations) // 2]}


def program_name(event_name: str) -> str:
    """``jit_topk_scores(1234)`` -> ``topk_scores``; others unchanged."""
    m = re.match(r"^(?:jit_|pjit_)?([A-Za-z0-9_\.<>\-]+?)(?:\(\d+\))?$", event_name)
    return m.group(1) if m else event_name


def read_planes(path: str) -> dict:
    """{plane name: {line name: [(event name, start s, duration s)]}}."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                for ev in line.events
            )
    return out


def reduce_planes(planes: dict, span_names: tuple[str, ...] = (),
                  traced_s: float | None = None) -> dict | None:
    """``traced_s``: how long the profiler ran, where the caller knows it; the
    window is never shorter than that (a trace of an idle device ends with its
    last event, not with the profiler)."""
    devices = sorted(n for n in planes if DEVICE_PLANE.match(n))
    stand_in = not devices
    if stand_in:
        host = next((n for n in planes if n.startswith("/host:")), None)
        if host is None:
            return None
        ops_by_dev = {host: [ev for ln, evs in planes[host].items()
                             if ln.startswith(CPU_STAND_IN)
                             for ev in evs if ev[2] > 0 and not ev[0].startswith("end: ")]}
        mods_by_dev = {host: []}
    else:
        ops_by_dev = {d: [ev for ln in OP_LINES for ev in planes[d].get(ln, [])]
                      for d in devices}
        mods_by_dev = {d: [ev for ln in MODULE_LINES for ev in planes[d].get(ln, [])]
                       for d in devices}
        for d in devices:  # a runtime that names no op line: modules bound the busy time
            if not ops_by_dev[d]:
                ops_by_dev[d] = mods_by_dev[d]
    every = [ev for lines in planes.values() for evs in lines.values() for ev in evs
             if ev[2] >= 0]
    if not every or not any(ops_by_dev.values()):
        return None
    t_lo = min(s for _, s, _ in every)
    t_hi = max(s + d for _, s, d in every)
    if traced_s is not None:
        t_hi = max(t_hi, t_lo + traced_s)

    # host spans of the benchmark, for naming the gaps
    spans = sorted(
        (s, s + d, n) for name, lines in planes.items() if name.startswith("/host:")
        for evs in lines.values() for n, s, d in evs if n in span_names
    )

    def covering(t: float) -> str:
        for s, e, n in spans:
            if s <= t < e:
                return n
            if s > t:
                break
        return "outside any span"

    busy, gaps_named, longest = [], {}, []
    op_time: dict[str, float] = {}
    programs: dict[str, list[float]] = {}
    in_span: dict[str, dict[str, list[float]]] = {}
    for d, ops in ops_by_dev.items():
        covered, merged = _union([(s, s + dur) for _, s, dur in ops])
        busy.append(covered)
        edges = [t_lo] + [t for iv in merged for t in iv] + [t_hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= MIN_GAP_S:
                n = covering((a + b) / 2)
                gaps_named[n] = gaps_named.get(n, 0.0) + (b - a)
                longest.append((b - a, n))
        for n, _, dur in ops:
            op_time[n] = op_time.get(n, 0.0) + dur
        for n, start, dur in mods_by_dev[d]:
            programs.setdefault(program_name(n), []).append(dur)
            for s0, s1, span in spans:  # every benchmark span it started under
                if s0 <= start < s1:
                    in_span.setdefault(span, {}).setdefault(program_name(n), []).append(dur)
    longest.sort(reverse=True)
    n_dev = len(ops_by_dev)
    return {
        "stand_in": stand_in,
        "devices": devices,
        "window_s": t_hi - t_lo,
        "busy_s": sum(busy) / n_dev,
        "programs": {n: _stats(v) for n, v in programs.items()},
        "programs_in_span": {span: {n: _stats(v) for n, v in progs.items()}
                             for span, progs in in_span.items()},
        "device_ops": [[n, t / n_dev] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": (
            [[f"all gaps: {n}", t / n_dev] for n, t in
             sorted(gaps_named.items(), key=lambda kv: -kv[1])[:5]]
            + [[f"longest: {n}", t] for t, n in longest[:5]]
        ),
    }


def reduce(path: str, span_names: tuple[str, ...] = (),
           traced_s: float | None = None) -> dict | None:
    return reduce_planes(read_planes(path), span_names, traced_s)


def program_time(trace: dict | None, needle: str, *, span: str | None = None,
                 other_than: str | None = None) -> dict | None:
    """The device programs whose name holds ``needle``, pooled; with ``span``,
    only executions that started under a benchmark span of that name, and with
    ``other_than`` every program there whose name does not hold it."""
    if not trace:
        return None
    table = trace["programs"] if span is None else trace["programs_in_span"].get(span, {})
    hits = [v for n, v in table.items()
            if (needle in n if other_than is None else other_than not in n)]
    if not hits:
        return None
    calls = sum(h["calls"] for h in hits)
    total = sum(h["total_s"] for h in hits)
    return {"calls": calls, "total_s": total, "mean_s": total / calls,
            "median_s": max(hits, key=lambda h: h["calls"])["median_s"]}
