"""Multi-worker dataflow parity: the core operator matrix rerun sharded.

The reference's Python suite runs multi-worker by just setting
``PATHWAY_THREADS`` (SURVEY §4; ``src/engine/dataflow/config.rs:88-117``) —
same here: every program below runs once single-worker and once at
``-t 2/4/8`` (threads over ``LocalComm``) and ``-n 2 -t 2`` (TCP
``ClusterComm`` mesh between spawned processes), asserting the final row
multisets are identical. Between them the programs drive every Exchange
route spec: ``("mix", …)`` (groupby group-cols, deduplicate instance),
``("column", …)`` (join keys), ``("key",)`` (concat/update_rows),
``("gather",)`` (iterate, global deduplicate, subscribe sinks).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap
import threading
from collections import Counter

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.testing import T, _norm


def _collect(build, monkeypatch, threads: int) -> Counter:
    """Build the program, subscribe to its result, run with
    PATHWAY_THREADS=threads, return the final row multiset."""
    G.clear()
    acc: Counter = Counter()
    lock = threading.Lock()
    table = build()
    cols = table.column_names()

    def on_change(key, row, time, is_addition):
        with lock:
            acc[tuple(_norm(row[c]) for c in cols)] += 1 if is_addition else -1

    pw.io.subscribe(table, on_change=on_change)
    monkeypatch.setenv("PATHWAY_THREADS", str(threads))
    try:
        pw.run()
    finally:
        monkeypatch.setenv("PATHWAY_THREADS", "1")
        G.clear()
    assert all(v >= 0 for v in acc.values()), f"negative final multiplicity: {acc}"
    return +acc


def _rows_table(n: int = 64):
    """A 64-row table whose keys land on every shard at -t 8."""
    lines = ["k | v"]
    for i in range(n):
        lines.append(f"g{i % 7} | {i}")
    return T("\n".join(lines))


def prog_groupby_dense():
    # semigroup reducers -> dense arena path; route spec ("mix", group cols)
    t = _rows_table()
    return t.groupby(pw.this.k).reduce(
        pw.this.k, s=pw.reducers.sum(pw.this.v), c=pw.reducers.count()
    )


def prog_groupby_multiset():
    # min/max/sorted_tuple -> general multiset path (retraction-correct)
    t = _rows_table()
    return t.groupby(pw.this.k).reduce(
        pw.this.k,
        mn=pw.reducers.min(pw.this.v),
        mx=pw.reducers.max(pw.this.v),
        st=pw.reducers.sorted_tuple(pw.this.v),
    )


def _join_sides():
    left_lines = ["name | dept"]
    right_lines = ["did | dname"]
    for i in range(40):
        left_lines.append(f"p{i} | {i % 12}")
    for i in range(10):
        right_lines.append(f"{i} | dep{i}")
    return T("\n".join(left_lines)), T("\n".join(right_lines))


def prog_join_inner():
    left, right = _join_sides()
    return left.join(right, left.dept == right.did).select(
        pw.left.name, dname=pw.right.dname
    )


def prog_join_outer():
    left, right = _join_sides()
    return left.join_outer(right, left.dept == right.did).select(
        name=pw.left.name, dname=pw.right.dname
    )


def prog_concat_update_rows():
    t1 = T("\n".join(["id | a"] + [f"{i} | {i}" for i in range(1, 20)]))
    t2 = T("\n".join(["id | a"] + [f"{i} | {i}" for i in range(20, 40)]))
    t3 = T("\n".join(["id | a"] + [f"{i} | {i * 10}" for i in range(10, 30)]))
    pw.universes.promise_are_pairwise_disjoint(t1, t2)
    return t1.concat(t2).update_rows(t3)


def prog_tumbling_window():
    lines = ["t | v"]
    for i in range(50):
        lines.append(f"{i} | {i}")
    t = T("\n".join(lines))
    return t.windowby(pw.this.t, window=pw.temporal.tumbling(duration=10)).reduce(
        start=pw.this._pw_window_start,
        s=pw.reducers.sum(pw.this.v),
        c=pw.reducers.count(),
    )


def prog_iterate():
    t = T("\n".join(["a"] + [str(i) for i in (1, 3, 7, 50, 61, 97)]))

    def double_small(t):
        return t.select(a=pw.if_else(t.a < 100, t.a * 2, t.a))

    return pw.iterate(double_small, t=t)


def prog_deduplicate_instanced():
    # per-instance dedup -> ("mix", [instance]) route
    lines = ["k | v"]
    for i in range(40):
        lines.append(f"g{i % 5} | {i}")
    t = T("\n".join(lines))
    return t.deduplicate(
        value=pw.this.v, instance=pw.this.k, acceptor=lambda new, old: new > old
    )


def prog_deduplicate_global():
    # single global instance -> ("gather",) route
    t = _rows_table()
    return t.deduplicate(value=pw.this.v, acceptor=lambda new, old: new > old)


def prog_streaming_counts():
    # drives the sharded streaming event loop (_stream_loop_sharded):
    # one owner worker polls the subject; ticks are agreed via allgather
    class S(pw.io.python.ConnectorSubject):
        def run(self):
            words = ["foo", "bar", "baz", "qux"]
            for i in range(24):
                self.next(word=words[i % 4])
                if i % 6 == 5:
                    self.commit()

    t = pw.io.python.read(S(), schema=pw.schema_from_types(word=str))
    return t.groupby(pw.this.word).reduce(pw.this.word, c=pw.reducers.count())


PROGRAMS = {
    "groupby_dense": prog_groupby_dense,
    "groupby_multiset": prog_groupby_multiset,
    "join_inner": prog_join_inner,
    "join_outer": prog_join_outer,
    "concat_update_rows": prog_concat_update_rows,
    "tumbling_window": prog_tumbling_window,
    "iterate": prog_iterate,
    "deduplicate_instanced": prog_deduplicate_instanced,
    "deduplicate_global": prog_deduplicate_global,
    "streaming_counts": prog_streaming_counts,
}

_baselines: dict[str, Counter] = {}


def _baseline(name: str, monkeypatch) -> Counter:
    if name not in _baselines:
        _baselines[name] = _collect(PROGRAMS[name], monkeypatch, threads=1)
    return _baselines[name]


@pytest.mark.parametrize("threads", [2, 4, 8])
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_sharded_thread_parity(name, threads, monkeypatch):
    expected = _baseline(name, monkeypatch)
    got = _collect(PROGRAMS[name], monkeypatch, threads=threads)
    assert got == expected, (
        f"{name} at -t {threads} diverged from single-worker:\n"
        f"  missing={expected - got}\n  extra={got - expected}"
    )


@pytest.mark.parametrize("threads", [2, 8])
@pytest.mark.parametrize(
    "name",
    [
        "groupby_dense",      # all-dense frames: keys/diffs/values over mesh
        "groupby_multiset",   # string group col -> host path re-zip
        "join_inner",         # ("column",) routes, string payloads
        "concat_update_rows", # ("key",) routes, dense int payloads
        "iterate",            # ("gather",) route
        "streaming_counts",   # realtime source auto-exchange under the
                              # allgather-driven streaming loop (bench path)
    ],
)
def test_mesh_exchange_parity(name, threads, monkeypatch):
    """Same programs with the ICI path on: dense columns ride
    bucketed_all_to_all over the 8-virtual-device CPU mesh (conftest),
    object columns re-zip from the host path."""
    expected = _baseline(name, monkeypatch)
    monkeypatch.setenv("PATHWAY_MESH_EXCHANGE", "1")
    try:
        got = _collect(PROGRAMS[name], monkeypatch, threads=threads)
    finally:
        monkeypatch.delenv("PATHWAY_MESH_EXCHANGE", raising=False)
    assert got == expected, (
        f"{name} with mesh exchange at -t {threads} diverged:\n"
        f"  missing={expected - got}\n  extra={got - expected}"
    )


def test_sharded_results_nonempty(monkeypatch):
    # guard against the suite passing vacuously (empty == empty)
    for name in PROGRAMS:
        assert sum(_baseline(name, monkeypatch).values()) > 0, name


# ---------------------------------------------------------------------------
# multi-process: the same program under spawn -n 2 -t 2 over the TCP mesh

_CLUSTER_PROGRAM = """
import json, sys
from collections import Counter

import pathway_tpu as pw
from pathway_tpu.testing import T, _norm

lines = ["k | v"]
for i in range(64):
    lines.append(f"g{i % 7} | {i}")
t = T("\\n".join(lines))
counts = t.groupby(pw.this.k).reduce(
    pw.this.k, s=pw.reducers.sum(pw.this.v), c=pw.reducers.count()
)
names = T("\\n".join(["k | label"] + [f"g{i} | L{i}" for i in range(7)]))
res = counts.join(names, counts.k == names.k).select(
    pw.right.label, s=pw.left.s, c=pw.left.c
)

acc = Counter()
cols = res.column_names()
pw.io.subscribe(
    res,
    on_change=lambda key, row, time, is_addition: acc.update(
        {tuple(_norm(row[c]) for c in cols): 1 if is_addition else -1}
    ),
)
pw.run()
rows = [[list(k), v] for k, v in sorted(acc.items()) if v != 0]
if rows:  # only the worker-0 process observed the gathered output
    with open(sys.argv[1], "w") as f:
        json.dump(rows, f)
"""


def test_cluster_barrier_multithreaded():
    """ClusterComm.barrier with threads_per_process > 1: every worker passes
    its real worker_id and tags come from per-worker sequences, so all four
    workers rendezvous (advisor r2: the old process-local counter + hardcoded
    worker 0 deadlocked this exact shape)."""
    from pathway_tpu.parallel.cluster import ClusterComm

    port = _free_port()
    comms: dict[int, ClusterComm] = {}

    def make(pid):
        comms[pid] = ClusterComm(
            process_id=pid, n_processes=2, threads_per_process=2, first_port=port
        )

    makers = [threading.Thread(target=make, args=(p,)) for p in (0, 1)]
    for m in makers:
        m.start()
    for m in makers:
        m.join(30)
    assert set(comms) == {0, 1}

    errors = []

    def work(pid, local):
        wid = pid * 2 + local
        try:
            for _ in range(3):  # repeated barriers: sequences must stay agreed
                comms[pid].barrier(wid)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    ts = [
        threading.Thread(target=work, args=(p, i), daemon=True)
        for p in (0, 1) for i in (0, 1)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    for c in comms.values():
        c.close()
    assert not errors, errors
    assert not any(t.is_alive() for t in ts), "barrier deadlocked"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_cluster_process_parity(tmp_path, monkeypatch):
    prog = tmp_path / "prog.py"
    prog.write_text(textwrap.dedent(_CLUSTER_PROGRAM))
    out_single = tmp_path / "single.json"
    out_cluster = tmp_path / "cluster.json"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo_root}
    subprocess.run(
        [sys.executable, str(prog), str(out_single)],
        env={**base_env, "PATHWAY_THREADS": "1", "PATHWAY_PROCESSES": "1"},
        check=True, timeout=120,
    )
    subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "-t", "2", "--first-port", str(_free_port()),
            sys.executable, str(prog), str(out_cluster),
        ],
        env=base_env, check=True, timeout=180,
    )
    single = json.loads(out_single.read_text())
    cluster = json.loads(out_cluster.read_text())
    assert single == cluster
    assert len(single) == 7


# multi-host address book (PATHWAY_ADDRESSES — timely hostfile analog)


def test_address_book_resolution():
    from pathway_tpu.parallel.cluster import _address_book

    # default: one machine, contiguous ports
    assert _address_book(None, 3, "127.0.0.1", 9000) == [
        ("127.0.0.1", 9000), ("127.0.0.1", 9001), ("127.0.0.1", 9002)
    ]
    # explicit host:port entries win over first_port
    assert _address_book(["a:1", "b:2"], 2, "x", 9000) == [("a", 1), ("b", 2)]
    # bare hostnames (a plain hostfile) get first_port + pid
    assert _address_book(["hostA", "hostB"], 2, "x", 7000) == [
        ("hostA", 7000), ("hostB", 7001)
    ]
    with pytest.raises(ValueError, match="2 hosts for 3 processes"):
        _address_book(["a", "b"], 3, "x", 9000)


def test_config_addresses_validation(monkeypatch):
    from pathway_tpu.internals.config import get_pathway_config

    monkeypatch.setenv("PATHWAY_PROCESSES", "2")
    monkeypatch.setenv("PATHWAY_ADDRESSES", "hostA:1234, hostB:5678")
    assert get_pathway_config().addresses == ["hostA:1234", "hostB:5678"]
    monkeypatch.setenv("PATHWAY_ADDRESSES", "onlyone:1")
    with pytest.raises(RuntimeError, match="one host\\[:port\\] per process"):
        get_pathway_config()


def test_cluster_parity_with_address_book(tmp_path):
    """The 2-process mesh forms from PATHWAY_ADDRESSES with non-contiguous
    ports and a bogus first_port, proving connections use the book (the
    multi-host path, here with both 'hosts' on loopback)."""
    prog = tmp_path / "prog.py"
    prog.write_text(textwrap.dedent(_CLUSTER_PROGRAM))
    out_single = tmp_path / "single.json"
    out_cluster = tmp_path / "cluster.json"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo_root}
    subprocess.run(
        [sys.executable, str(prog), str(out_single)],
        env={**base_env, "PATHWAY_THREADS": "1", "PATHWAY_PROCESSES": "1"},
        check=True, timeout=120,
    )
    book = f"127.0.0.1:{_free_port()},127.0.0.1:{_free_port()}"
    subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "-t", "2", "--first-port", "1",
            "-a", book, "-p", "0", "-p", "1",
            sys.executable, str(prog), str(out_cluster),
        ],
        env=base_env, check=True, timeout=180,
    )
    assert json.loads(out_single.read_text()) == json.loads(
        out_cluster.read_text()
    )


def test_spawn_rejects_bad_address_book_and_pids(tmp_path):
    from click.testing import CliRunner

    from pathway_tpu.cli import main

    runner = CliRunner()
    r = runner.invoke(main, [
        "spawn", "-n", "2", "-a", "onlyhost:1", "true"
    ])
    assert r.exit_code != 0
    assert "one host[:port] per process" in r.output
    r = runner.invoke(main, ["spawn", "-n", "2", "-p", "5", "true"])
    assert r.exit_code != 0
    assert "out of range" in r.output


def test_address_parsing_edge_cases():
    from pathway_tpu.parallel.cluster import _parse_address

    assert _parse_address("host", 9) == ("host", 9)
    assert _parse_address("host:123", 9) == ("host", 123)
    assert _parse_address("::1", 9) == ("::1", 9)  # bare IPv6 = host only
    assert _parse_address("[::1]:80", 9) == ("::1", 80)
    assert _parse_address("[fe80::2]", 9) == ("fe80::2", 9)
    for bad in (":1", "h:", "h:abc", "h:0", "h:70000", "[::1", "[::1]x"):
        with pytest.raises(ValueError):
            _parse_address(bad, 9)


def test_spawn_rejects_malformed_book_and_duplicate_pids():
    from click.testing import CliRunner

    from pathway_tpu.cli import main

    runner = CliRunner()
    r = runner.invoke(main, [
        "spawn", "-n", "2", "-a", "hostA:abc,hostB:1", "true"
    ])
    assert r.exit_code != 0 and "non-numeric port" in r.output
    r = runner.invoke(main, [
        "spawn", "-n", "2", "-a", "hostA:1,hostB:2",
        "-p", "0", "-p", "0", "true",
    ])
    assert r.exit_code != 0 and "distinct" in r.output


def test_multihost_mesh_exchange_parity(tmp_path):
    """2-process loopback mesh over jax.distributed: dense Exchange columns
    ride the cross-process device collective (MultiHostMeshComm) and the
    output matches the single-worker run (VERDICT r4 item 6 — the engine
    call site + test for parallel/distributed.py)."""
    prog = tmp_path / "prog.py"
    prog.write_text(textwrap.dedent(_CLUSTER_PROGRAM))
    out_single = tmp_path / "single.json"
    out_mesh = tmp_path / "mesh.json"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo_root}
    subprocess.run(
        [sys.executable, str(prog), str(out_single)],
        env={**base_env, "PATHWAY_THREADS": "1", "PATHWAY_PROCESSES": "1"},
        check=True, timeout=120,
    )
    first_port = _free_port()
    coord_port = _free_port()
    while coord_port in (first_port, first_port + 1):
        coord_port = _free_port()  # the -n 2 mesh binds first_port(+1)
    r = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "-t", "2", "--first-port", str(first_port),
            sys.executable, str(prog), str(out_mesh),
        ],
        env={
            **base_env,
            "PATHWAY_MESH_EXCHANGE": "1",
            "PATHWAY_COORDINATOR": f"127.0.0.1:{coord_port}",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
        check=False, timeout=300, capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(out_single.read_text()) == json.loads(
        out_mesh.read_text()
    )
