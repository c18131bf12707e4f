"""Frontier-driven asynchronous execution (PATHWAY_ASYNC_EXEC).

- mode selection: async is the sharded-streaming default, =0 restores
  the BSP tick loop, mesh exchange keeps BSP unless explicitly asked;
- parity: streaming sharded programs produce identical final multisets
  single-worker vs async vs the =0 escape hatch, fused AND unfused;
- exactly-once under async: the chaos smoke (SIGKILL mid-run + sup-
  ervised restart) and the sink smoke's kill scenario run with
  PATHWAY_ASYNC_EXEC=1 pinned explicitly;
- the TCP cluster transport (spawn -n 2) drains a streaming wordcount
  to exact counts through the async plane.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from collections import Counter

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G

SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)


# -- mode selection ----------------------------------------------------------


def _executor_for(monkeypatch, n_workers=2, mesh=False):
    from pathway_tpu.engine.executor import Executor
    from pathway_tpu.parallel.comm import LocalComm, WorkerContext

    comm = LocalComm(n_workers)
    if mesh:
        comm.exchange_deltas = lambda *a, **k: []  # quacks like MeshComm
    ex = Executor.__new__(Executor)
    ex.ctx = WorkerContext(0, n_workers, comm)
    return ex


def test_async_is_default_for_sharded_streaming(monkeypatch):
    monkeypatch.delenv("PATHWAY_ASYNC_EXEC", raising=False)
    assert _executor_for(monkeypatch)._use_async()


def test_escape_hatch_restores_bsp(monkeypatch):
    monkeypatch.setenv("PATHWAY_ASYNC_EXEC", "0")
    assert not _executor_for(monkeypatch)._use_async()


def test_mesh_exchange_defaults_to_bsp_unless_asked(monkeypatch):
    monkeypatch.delenv("PATHWAY_ASYNC_EXEC", raising=False)
    assert not _executor_for(monkeypatch, mesh=True)._use_async()
    monkeypatch.setenv("PATHWAY_ASYNC_EXEC", "1")
    assert _executor_for(monkeypatch, mesh=True)._use_async()


# -- streaming parity: single vs async vs BSP escape hatch -------------------


def _run_streaming(build, monkeypatch, threads: int, async_exec: str,
                   fusion: str = "1") -> Counter:
    G.clear()
    acc: Counter = Counter()
    lock = threading.Lock()
    table = build()
    cols = table.column_names()

    def on_change(key, row, time, is_addition):
        with lock:
            acc[tuple(row[c] for c in cols)] += 1 if is_addition else -1

    pw.io.subscribe(table, on_change=on_change)
    monkeypatch.setenv("PATHWAY_THREADS", str(threads))
    monkeypatch.setenv("PATHWAY_ASYNC_EXEC", async_exec)
    monkeypatch.setenv("PATHWAY_FUSION", fusion)
    try:
        pw.run()
    finally:
        monkeypatch.setenv("PATHWAY_THREADS", "1")
        monkeypatch.delenv("PATHWAY_ASYNC_EXEC", raising=False)
        monkeypatch.delenv("PATHWAY_FUSION", raising=False)
        G.clear()
    assert all(v >= 0 for v in acc.values()), f"negative multiplicity: {acc}"
    return +acc


def _wordcount_prog():
    n, batch = 4_000, 250
    words = [f"w{i % 53}" for i in range(n)]

    class Feed(pw.io.python.ConnectorSubject):
        def run(self):
            for s in range(0, n, batch):
                self.next_batch({"word": words[s:s + batch]})
                self.commit()

    t = pw.io.python.read(
        Feed(), schema=pw.schema_from_types(word=str),
        autocommit_duration_ms=None,
    )
    return t.groupby(pw.this.word).reduce(
        pw.this.word, c=pw.reducers.count()
    )


def _join_retract_prog():
    # a streaming fact feed WITH retractions joined to a static dimension
    # table, grouped — drives ("column",) and ("mix",) exchange routes plus
    # negative diffs through the async data plane
    import pandas as pd

    right = pw.debug.table_from_pandas(
        pd.DataFrame({"rid": list(range(40)), "grp": [i % 5 for i in range(40)]})
    )

    class Facts(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(600):
                self.next(fid=i % 40, seq=i)
                if i % 7 == 3:
                    self._remove(fid=(i - 3) % 40, seq=i - 3)
                if i % 25 == 24:
                    self.commit()
            self.commit()

    facts = pw.io.python.read(
        Facts(), schema=pw.schema_from_types(fid=int, seq=int),
        autocommit_duration_ms=None,
    )
    joined = facts.join(right, facts.fid == right.rid).select(
        grp=right.grp, seq=facts.seq
    )
    return joined.groupby(pw.this.grp).reduce(
        pw.this.grp, n=pw.reducers.count(), s=pw.reducers.sum(pw.this.seq)
    )


@pytest.mark.parametrize("prog", [_wordcount_prog, _join_retract_prog])
@pytest.mark.parametrize("fusion", ["1", "0"])
def test_parity_async_vs_bsp_vs_single(monkeypatch, prog, fusion):
    single = _run_streaming(prog, monkeypatch, 1, "0", fusion)
    bsp = _run_streaming(prog, monkeypatch, 2, "0", fusion)
    a2 = _run_streaming(prog, monkeypatch, 2, "1", fusion)
    a4 = _run_streaming(prog, monkeypatch, 4, "1", fusion)
    assert bsp == single  # the escape hatch IS the old engine
    assert a2 == single
    assert a4 == single


# -- exactly-once under async (explicit PATHWAY_ASYNC_EXEC=1) ---------------


def test_chaos_smoke_async_pinned(tmp_path, monkeypatch):
    from chaos_smoke import EXPECTED, run_smoke

    monkeypatch.setenv("PATHWAY_ASYNC_EXEC", "1")
    result = run_smoke(workdir=str(tmp_path))
    assert result["final"] == EXPECTED
    assert result["generations"] == [0, 1]


def test_sink_kill_async_pinned(tmp_path, monkeypatch):
    import sink_smoke

    monkeypatch.setenv("PATHWAY_ASYNC_EXEC", "1")
    workdir = str(tmp_path)
    baseline = sink_smoke.scenario_clean(workdir)
    report = sink_smoke.scenario_kill(workdir, baseline)
    assert 0 < report["rows_before_kill"] < report["rows_total"]


# -- TCP cluster transport through the async plane ---------------------------


_CLUSTER_PROG = """
import json, os, sys
sys.path.insert(0, {repo!r})
import pathway_tpu as pw

n_rows, batch = 20_000, 1_000
words = [f"w{{i % 97}}" for i in range(n_rows)]


class Feed(pw.io.python.ConnectorSubject):
    def run(self):
        for s in range(0, n_rows, batch):
            self.next_batch({{"word": words[s:s + batch]}})
            self.commit()


t = pw.io.python.read(
    Feed(), schema=pw.schema_from_types(word=str),
    autocommit_duration_ms=None,
)
counts = t.groupby(pw.this.word).reduce(pw.this.word, c=pw.reducers.count())
from collections import Counter

net = Counter()


def on_change(key, row, time, is_addition):
    # multiset semantics: retract/insert pair order within one update
    # delta is not part of the engine contract — net multiplicities are
    net[(row["word"], int(row["c"]))] += 1 if is_addition else -1


pw.io.subscribe(counts, on_change=on_change)
pw.run()
if int(os.environ.get("PATHWAY_PROCESS_ID", "0")) == 0:
    final = {{w: c for (w, c), v in net.items() if v > 0}}
    with open(sys.argv[1], "w") as f:
        json.dump(final, f)
"""


@pytest.mark.slow
def test_cluster_n2_async(tmp_path):
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = tmp_path / "prog.py"
    out = tmp_path / "out.json"
    prog.write_text(_CLUSTER_PROG.format(repo=repo))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo,
        "PATHWAY_ASYNC_EXEC": "1",
    }
    r = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "-t", "1", "--first-port", str(port),
            sys.executable, str(prog), str(out),
        ],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    acc = json.loads(out.read_text())
    expected = {f"w{i}": 20_000 // 97 + (1 if i < 20_000 % 97 else 0)
                for i in range(97)}
    assert acc == expected
