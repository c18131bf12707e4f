#!/usr/bin/env python
"""What each node of the reply path costs a tick, on this machine's CPU.

On the chip's host a traced run gives a node's time and its phases
(``benchmark/tests/dump_nodes.py``, after ``benchmark/run.py --trace 1``);
this is the sizing tool for a sandbox with no chip, and the one that profiles
a node function by function. It runs the same dataflow a ``/v1/retrieve`` tick
runs after the search,
``DataIndex.query_as_of_now(..., number_of_matches=10, collapse_rows=True)``
over a pre-embedded store shaped as ``DocumentStore``'s ``vector_column``
branch shapes it (indexed over the vectors, repacked from ``text`` and
``_metadata``), with every node's ``process`` timed: 20,000 rows, and in
every tick 8 new queries and the retraction of the 8 before, as a tick takes
the answers of the tick before back. It prints each node's median a tick,
and beside it the values a tick that the native hash handed back to Python
(``hash_fallback_calls_total``, ``docs/observability.md``) and, for a
group-by on the general path, the share of its rows that its reducers were
given by column (``groupby_rows_by_column_total`` over ``groupby_rows_total``:
1.0 on the reply path);
``--profile Flatten`` (any node class) also prints cProfile's view of that
class's ``process``.

The proportions are what carries over to the chip's host, not the
milliseconds (PERF.md sections 5 and 7; ``Subscribe`` has no REST response
writer here, so its ``on_time_end`` is missing). JAX runs the search here, on
the CPU:

    JAX_PLATFORMS=cpu python scripts/reply_path_nodes.py [--profile Join]
"""

from __future__ import annotations

import argparse
import collections
import cProfile
import os
import pstats
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pathway_tpu as pw  # noqa: E402
from pathway_tpu import indexing  # noqa: E402
from pathway_tpu.engine import external_index, fusion, operators  # noqa: E402,F401  (node classes)
from pathway_tpu.engine.executor import Node  # noqa: E402
from pathway_tpu.internals.table_io import rows_to_table  # noqa: E402


def _node_classes(cls=Node):
    for sub in cls.__subclasses__():
        yield sub
        yield from _node_classes(sub)


COUNTED = (
    "hash_fallback_calls_total",
    "groupby_rows_total",
    "groupby_rows_by_column_total",
)


def time_nodes(
    spent: dict, counted: dict, profiled: str | None, profile: cProfile.Profile
) -> None:
    """Wrap ``process`` of every node class that defines one: a call's
    seconds go to ``spent[(label, tick time)]``, and what it added to each
    counter of ``COUNTED`` to ``counted[(label, tick time, counter)]``."""
    stats = fusion.FUSION_STATS

    def timed(process, profiled_here):
        def wrapper(self, time_, ins):
            if profiled_here:
                profile.enable()
            before = [stats[c] for c in COUNTED]
            t0 = time.perf_counter()
            try:
                return process(self, time_, ins)
            finally:
                label = f"{type(self).__name__}#{self.node_id}"
                spent[label, time_] += time.perf_counter() - t0
                for c, was in zip(COUNTED, before):
                    counted[label, time_, c] += stats[c] - was
                if profiled_here:
                    profile.disable()

        return wrapper

    for cls in set(_node_classes()):
        if "process" in vars(cls):
            cls.process = timed(cls.process, cls.__name__ == profiled)


def build(rows: int, dim: int, per_tick: int, ticks: int, k: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    vectors = rng.standard_normal((rows, dim)).astype(np.float32)
    chunked = rows_to_table(
        ["text", "_metadata", "_pw_vector"],
        [
            (f"chunk {i} of the store", {"path": f"d{i // 8}", "ver": 0}, vectors[i])
            for i in range(rows)
        ],
    )
    parsed = chunked.select(text=pw.this.text, _metadata=pw.this._metadata)
    index = indexing.DataIndex(
        parsed,
        indexing.BruteForceKnn(
            data_column=chunked._pw_vector, dimensions=dim, reserved_space=rows
        ),
    )
    # tick t brings its own queries and takes back those of tick t - 1
    asked = rng.standard_normal((ticks, per_tick, dim)).astype(np.float32)
    query_rows, times, diffs = [], [], []
    for t in range(ticks):
        for back, diff in ((0, 1), (1, -1)):
            if t - back < 0:
                continue
            for q in range(per_tick):
                query_rows.append((f"q{t - back}.{q}", asked[t - back, q]))
                times.append(2 * (t + 1))
                diffs.append(diff)
    queries = rows_to_table(["qid", "qvec"], query_rows, times=times, diffs=diffs)
    replies = index.query_as_of_now(
        queries.qvec, number_of_matches=k, collapse_rows=True
    ).select(qid=pw.left.qid, texts=pw.right.text, metadata=pw.right._metadata)
    pw.io.subscribe(replies, on_change=lambda **kw: None)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=20_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--per-tick", type=int, default=8)
    ap.add_argument("--ticks", type=int, default=120)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--profile", metavar="NODE_CLASS")
    args = ap.parse_args()

    spent: dict = collections.defaultdict(float)
    counted: dict = collections.defaultdict(int)
    profile = cProfile.Profile()
    time_nodes(spent, counted, args.profile, profile)
    build(args.rows, args.dim, args.per_tick, args.ticks, args.k, args.seed)
    pw.run()

    by_node = collections.defaultdict(list)
    # a node's counters over the steady ticks, in COUNTED's order
    totals = collections.defaultdict(lambda: [0] * len(COUNTED))
    # the first query ticks compile the search and have nothing to take back
    steady = {t for _, t in spent if t > 2 * 4 and t <= 2 * args.ticks}
    for (label, t), seconds in spent.items():
        if t in steady:
            by_node[label].append(seconds * 1e3)
            for i, c in enumerate(COUNTED):
                totals[label][i] += counted[label, t, c]
    print(f"{len(steady)} steady ticks of {args.per_tick} queries in and "
          f"{args.per_tick} out, k = {args.k}, over {args.rows} rows")
    print(f"{'node':28s} {'ticks':>6s} {'median ms':>10s} {'mean ms':>9s} "
          f"{'fallbacks a tick':>17s} {'rows by column':>15s}")
    for label, ms in sorted(by_node.items(), key=lambda kv: -statistics.median(kv[1])):
        if len(ms) * 2 < len(steady):
            continue  # the documents' side: it worked once, at the start
        handed_back, rows, by_column = totals[label]
        share = f"{by_column / rows:15.3f}" if rows else f"{'':15s}"
        print(f"{label:28s} {len(ms):6d} {statistics.median(ms):10.3f} "
              f"{statistics.fmean(ms):9.3f} {handed_back / len(ms):17.1f} {share}")
    handed_back, rows, by_column = map(sum, zip(*totals.values()))
    print(f"values the native hash handed back to Python, all nodes: "
          f"{handed_back / max(len(steady), 1):.1f} a tick")
    print(f"rows x reducers the group-bys' general path was fed: "
          f"{rows / max(len(steady), 1):.1f} a tick, "
          f"{by_column / rows if rows else 0.0:.3f} of them by column")
    if args.profile:
        pstats.Stats(profile).sort_stats("cumulative").print_stats(18)
    return 0


if __name__ == "__main__":
    sys.exit(main())
