"""The benchmark's entry:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It starts one child that holds the chip
(``lib/server_proc.py``), is itself the load generator, and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and
last the numbers compared, each beside its limit. Everything else goes to
standard error or into ``benchmark/out/``.

A cell, a configuration, a traffic mix, a per-layer metric and a cell's limits
are files found by name (``lib/files.py``); ``BENCHMARK.json`` names them. A model
family is a file found by the ``model_type`` its configuration names
(``lib/family.py``); one that has none stops the run here, before the child.
A configuration may put its documents in folders (``metadata``) and a mix may
confine its requests to them (``scope``): ``lib/traffic.py`` has the keys.

A run owns the whole life of the child. It ends in one of four ways, and each
leaves no process of the run alive: with a result line (exit 0); by a signal
(``SIGTERM``, ``SIGINT``, ``SIGHUP``: exit 128 + its number); by its own
deadline (``SETUP_LIMIT_S``, ``RUN_LIMIT_S``: exit 5); or because the child
ended first (its code, or 1; its reason is the last line of standard error).
Killed outright, it is followed by the child (``server_proc.die_with_parent``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from lib import check, datagen, family, files, loadgen, traffic  # noqa: E402

REFUSED_EXIT = 4
DEADLINE_EXIT = 5
# The two limits of a run, from this process's start; its waits on the child
# and on the callers have no other. They are one timer (``Life.arm``), so they cut
# a wait wherever the main thread is: on the child, on the callers, on the probes.
# - Set-up, to the child's ``ready``: twice the slowest on record, the large
#   cell's first run in a checkout, which compiles (``first_setup_s`` 326.73,
#   ledger, PR 29): 2 x 326.73 = 653, rounded up. A warm set-up is 64-86 s.
# - The whole run: the contract gives a cell's first run in a checkout 1,200 s
#   (a later one 360 s) and ``README.md``'s command by hand 1,500 s; 50 s under
#   the lesser, for the kill, the last lines and the interpreter's exit. After
#   the slowest set-up allowed that leaves 450 s for ramp, window (51 s), the
#   minute the probes may wait, close and reference (up to 30 s, README).
SETUP_LIMIT_S = 700.0
RUN_LIMIT_S = 1150.0
#: a child that has said ``checked`` ends by itself; so long is it given
CHILD_EXIT_GRACE_S = 20.0
AFTER_CLOSE_S = 60.0
ENDING_SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)


def log(msg: str) -> None:
    print(f"[run {time.monotonic() - T_START:7.1f}] {msg}", file=sys.stderr, flush=True)


class Ending(BaseException):
    """The run ends itself: a signal from outside, or its own deadline."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


class ChildEnded(RuntimeError):
    """The child ended before it said what the run was waiting for."""


class Life:
    """How a run ends itself: the stage it is in, the one timer that is its
    deadline, and the signals that end it. Each raises ``Ending`` into the main
    thread, wherever that is waiting, so ``main`` clears up on every path."""

    def __init__(self):
        self.stage = "set-up"
        self.armed = ""  # the limit the timer stands for, as its line names it

    def enter(self, stage: str) -> None:
        """Name the stage the run is in, for the line an ending prints."""
        self.stage = stage
        log(f"stage: {stage}")

    def arm(self, name: str, limit_s: float) -> None:
        """(Re)set the timer: the run ends ``limit_s`` after it started."""
        self.armed = f"{name} = {limit_s:.0f} s"
        signal.setitimer(signal.ITIMER_REAL,
                         max(T_START + limit_s - time.monotonic(), 0.001))

    def take(self) -> None:
        """From here a signal or the timer ends the run."""
        for s in ENDING_SIGNALS:
            signal.signal(s, self._on_signal)
        signal.signal(signal.SIGALRM, self._on_deadline)

    def release(self) -> None:
        """The run has its result, or an ending is under way: nothing from
        outside interrupts the clearing up, or turns a result into a loss."""
        signal.setitimer(signal.ITIMER_REAL, 0)
        for s in (*ENDING_SIGNALS, signal.SIGALRM):
            signal.signal(s, signal.SIG_IGN)

    def _on_signal(self, signum, frame) -> None:
        raise Ending(128 + signum, f"stopped by {signal.Signals(signum).name}")

    def _on_deadline(self, signum, frame) -> None:
        raise Ending(DEADLINE_EXIT, f"the run met its deadline ({self.armed})")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(manifest_path: str, workload: str) -> dict:
    manifest = load_json(manifest_path)
    mdir = os.path.dirname(os.path.abspath(manifest_path))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg_file = configs[cell["config"]]["file"]
    cfg_path = os.path.join(mdir, cfg_file)
    if not os.path.isfile(cfg_path):
        cfg_path = os.path.join(ROOT, cfg_file)
    config = load_json(cfg_path)
    try:
        fam = family.load(config, mdir)
    except family.NoFamily as e:
        raise SystemExit(str(e)) from None
    return {
        "manifest": manifest, "manifest_dir": mdir, "cell": cell,
        "config": config, "family": fam,
        "mix": load_json(files.find("traffic", cell["traffic"], ".json", mdir)),
        "limits": load_json(files.find("limits", cell["name"], ".json", mdir)),
    }


def metrics_of(manifest: dict, group: str, cell_name: str) -> list[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def layer_reader(name: str, manifest_dir: str):
    return files.load_module(files.find("layers", name, ".py", manifest_dir)).read


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """The server process and the line protocol with it."""

    def __init__(self, spec_path: str):
        # the compile cache lives inside the checkout, at the fixed path the
        # program itself would choose (pathway_tpu/utils/jaxcfg.py), with no
        # size cap: the cell's programs are some hundreds of MB, and a capped
        # cache that they do not fit evicts every entry before its next use.
        # The CPU rehearsal keeps its programs apart, under out/: the repo's own
        # CPU tests read the checkout's cache, and tests/test_signals_smoke.py
        # fails once the rehearsal's programs are in it.
        cache = (os.path.join(HERE, "out", "jax_cache_cpu")
                 if os.environ.get("JAX_PLATFORMS") == "cpu"
                 else os.path.join(ROOT, ".jax_cache"))
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
        env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "lib", "server_proc.py"), spec_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            start_new_session=True, cwd=ROOT, env=env,
        )
        self.events: list[dict] = []
        self._cv = threading.Condition()
        self._eof = False
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                try:
                    ev = json.loads(line)
                except ValueError:
                    ev = None
                if isinstance(ev, dict) and "event" in ev:
                    with self._cv:
                        self.events.append(ev)
                        self._cv.notify_all()
                    continue
            print(f"[child out] {line}", file=sys.stderr, flush=True)
        with self._cv:
            self._eof = True
            self._cv.notify_all()

    def wait_event(self, name: str) -> dict:
        """Until the child says ``name`` or ends; the run's deadline and a
        signal raise into this wait (``Ending``), so it has no limit of its own."""
        with self._cv:
            while True:
                for ev in self.events:
                    if ev["event"] == name:
                        return ev
                if self._eof:
                    why = [ev["reason"] for ev in self.events if ev["event"] == "failed"]
                    raise ChildEnded(
                        f"the child ended (exit {self.proc.wait()}) before {name!r}"
                        + (f": {why[-1]}" if why else ""))
                self._cv.wait()

    def send(self, **cmd) -> None:
        try:
            self.proc.stdin.write(json.dumps(cmd) + "\n")
            self.proc.stdin.flush()
        except BrokenPipeError:
            pass  # the child has ended: the wait that follows says so, and why

    def stop(self, grace_s: float) -> int | None:
        """End the child and anything it started; wait until it has ended. A
        child that is finishing by itself is given ``grace_s``; None where it
        had to be killed."""
        try:
            code = self.proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            code = None
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        return code


def percentile(values: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def reader_queries(pool, scope, client: int):
    """``next_query`` of one closed-loop client: (query id, text, folder) in
    the order it sends them; the folder is None where the request carries no
    filter. All are drawn here, before the window opens."""
    if scope is None:
        ids = pool.client_sequence(client).tolist()
        return ((q, pool.texts[q], None) for q in ids).__next__
    ids, folders = (a.tolist() for a in scope.client_requests(client))
    return ((q, pool.texts[q], f if f >= 0 else None)
            for q, f in zip(ids, folders)).__next__


def draw_sample(records: list[dict], pool_tokens, count: int, seed: int) -> list[dict]:
    """``count`` answered requests drawn from the seed, the longest query in."""
    done = [r for r in records if r["rows"] is not None]
    if not done:
        return []
    rng = datagen.stream(seed, 40)
    pick = set(rng.choice(len(done), size=min(count, len(done)), replace=False).tolist())
    readers = [i for i, r in enumerate(done) if r["qid"] >= 0]
    if readers:
        pick.add(max(readers, key=lambda i: pool_tokens[done[i]["qid"]]))
    return [done[i] for i in sorted(pick)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another BENCHMARK.json (the CPU rehearsal's, under "
                    "benchmark/tests/); its files are found beside it first")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="after the window also judge the reference in fp8 in "
                    "the program's place and print its readings (never part "
                    "of the result)")
    ap.add_argument("--keep-trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="",
                    help="tests only: break the timed path underneath "
                    "(see benchmark/tests/)")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "pathway_tpu")):
        print("benchmark: the system under test (pathway_tpu/) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return REFUSED_EXIT
    loaded = load_cell(args.manifest, args.workload)
    cell, cfg, mix = (loaded[k] for k in ("cell", "config", "mix"))
    name, seed = cell["name"], args.seed
    out_dir = os.path.join(HERE, "out", name)
    os.makedirs(out_dir, exist_ok=True)
    port = free_port()
    spec = {
        "cell": name, "config": cfg, "family": loaded["family"].__file__,
        "mix": mix, "seed": seed,
        "seconds": args.seconds, "trace": args.trace, "control": args.control,
        "chips": cell["chips"], "port": port, "out_dir": out_dir,
        "keep_trace": args.keep_trace, "fault": args.fault,
        # the child ends with this process (``server_proc.die_with_parent``)
        "parent_pid": os.getpid(),
    }
    spec_path = os.path.join(out_dir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    life = Life()
    life.take()
    life.arm("SETUP_LIMIT_S", SETUP_LIMIT_S)
    child, done, code, last = None, None, 1, None
    try:
        child = Child(spec_path)
        with open(os.path.join(out_dir, "child.pid"), "w") as f:
            f.write(f"{child.proc.pid}\n")
        log(f"child started: pid {child.proc.pid} (out/{name}/child.pid), its own session")
        done = drive(args, loaded, spec, child, life)
        life.release()
        log("the run has its result; the child is ending by itself")
        child.stop(CHILD_EXIT_GRACE_S)
        code = 0
    except Ending as e:
        # also one that came between the result and ``release``: a run is a
        # result or a loss, never both
        code, done = e.code, None
        last = f"benchmark: {e} in stage {life.stage!r}; the child is killed, no result"
    except ChildEnded as e:
        code = child.proc.poll() or 1
        last = f"benchmark: {e}"
    finally:
        # on every path but the one above the child's session is killed at once
        life.release()
        if child is not None:
            log(f"child ended (exit {child.stop(0)})")
    # the numbers compared, each beside its limit, end standard error; where
    # there are none, the reason the run gave no result does. The result line
    # comes when nothing of the run is left, and only with exit 0.
    if done is not None:
        result, tail = done
        print("\n".join(tail), file=sys.stderr, flush=True)
        print(json.dumps(result), flush=True)
    if last:
        print(last, file=sys.stderr, flush=True)
    return code


def drive(args, loaded: dict, spec: dict, child: Child, life: Life) -> tuple[dict, list[str]]:
    """The run from the child's start to its verdict: the result line's object,
    and the numbers compared, each beside its limit, as lines."""
    manifest, cell, cfg, fam, mix, limits = (
        loaded[k] for k in ("manifest", "cell", "config", "family", "mix", "limits"))
    name, seed, seconds, port = cell["name"], args.seed, args.seconds, spec["port"]
    out_dir = spec["out_dir"]
    k, chunks = cfg["k"], cfg["chunks_per_doc"]

    # the same plan the child derives, from the same seed
    _, words = datagen.vocabulary(seed, cfg["vocab_size"], fam)
    pool = traffic.QueryPool(seed, words, mix["query"], fam.FRAMING)
    plan = (traffic.WriterPlan(seed, mix["writer"], cfg["rows"] // chunks, chunks, seconds)
            if mix.get("writer") else None)
    folder_of_doc = datagen.doc_folders(seed, cfg["rows"] // chunks, cfg.get("metadata"))
    scope = (traffic.Scope(seed, mix["scope"], folder_of_doc, pool, int(mix["clients"]),
                           chunks, plan)
             if mix.get("scope") else None)

    ready = child.wait_event("ready")
    probe_text = ready["probe_text"]
    log(f"ready after {time.monotonic() - T_START:.1f} s: {json.dumps(ready)}")
    life.enter("window")
    life.arm("RUN_LIMIT_S", RUN_LIMIT_S)

    # -- the window. The callers start ``ramp_s`` before it opens: sixteen first
    # requests sent at one instant make one tick of one query and one of
    # fifteen, a state the closed loop takes a second or three to leave, and the
    # window measures the loop that has settled. The ramp is set-up.
    ramp = float(mix.get("ramp_s", 0.0))
    t0 = time.monotonic() + 0.25 + ramp
    t_end = t0 + seconds
    setup_s = t0 - T_START
    child.send(cmd="window", t0=t0, seconds=seconds)
    readers = []
    for c in range(int(mix["clients"])):
        readers.append(loadgen.Client(
            f"r{c}", port, mix["route"], k, t0 - ramp,
            reader_queries(pool, scope, c),
            lambda: time.monotonic() >= t_end, scope,
        ))
    probes, probe_state = [], {"done": False}
    for p in range(int(mix.get("probes", 0))):
        probes.append(loadgen.Client(
            f"p{p}", port, mix["route"], k, t0 - ramp + p * 0.05,
            lambda: (-1, probe_text, None),
            lambda: probe_state["done"] or time.monotonic() >= t_end + AFTER_CLOSE_S,
        ))
    for c in probes:
        c.start()
    # the generator's own full collections (two a window, 12-18 ms each over the
    # records it keeps) stopped all sixteen callers at once, and the loop fell
    # back into the one-and-fifteen state for up to two seconds each time
    # (PERF.md section 6, PR 32): none while callers run
    gc.collect()
    gc.disable()
    try:
        # every caller ends by its own ``until`` and its request's timeout
        records = loadgen.run_clients(readers)
    finally:
        gc.enable()
    log(f"readers done: {len(records)} requests")
    seen: list[float | None] = []
    if plan is not None:
        # probes go on until every write has been seen, a minute at the most
        while time.monotonic() < t_end + AFTER_CLOSE_S:
            got = [r for c in probes for r in list(c.records) if r["rows"] is not None]
            seen = check.first_seen(got + [r for r in records if r["rows"] is not None], plan)
            if all(s is not None for s in seen):
                break
            time.sleep(0.25)
        probe_state["done"] = True
        for c in probes:
            c.join(timeout=loadgen.REQUEST_TIMEOUT_S)
    probe_records = sorted((r for c in probes for r in c.records), key=lambda r: r["send"])
    life.enter("close")
    child.send(cmd="close")
    child.wait_event("closed")
    facts = load_json(os.path.join(out_dir, "child_facts.json"))

    # -- correctness: the sample goes to the child, which runs the reference
    in_window = [r for r in records if t0 <= r["send"] < t_end]
    answered = [r for r in in_window if r["rows"] is not None]
    everything = records + probe_records
    sample = draw_sample(records, pool.tokens, int(mix["sample_requests"]), seed)
    sample += draw_sample(probe_records, pool.tokens, int(mix["sample_requests"]) // 4, seed + 1)
    sample_path = os.path.join(out_dir, "sample.json")
    with open(sample_path, "w") as f:
        json.dump([{"query": r["query"], "scope": r["scope"], "rows": r["rows"]}
                   for r in sample], f)
    life.enter("check")
    child.send(cmd="check", sample=sample_path)
    verdict = child.wait_event("checked")

    bad = [r for r in everything if r["rows"] is None]
    if bad:
        kinds: dict = {}
        for r in bad:
            kinds[r["status"]] = kinds.get(r["status"], 0) + 1
        log(f"bad replies by status (0 is no answer, 200 is malformed): {kinds}; "
            f"first: {str(bad[0].get('error'))[:1500]}")
    numbers = {
        "rank_gap": verdict["rank_gap"], "score_err": verdict["score_err"],
        "bad_replies": float(len(bad) + verdict["bad_rows"]),
    }
    if not sample:
        numbers["rank_gap"] = numbers["score_err"] = float("inf")
    if scope is not None:
        numbers["out_of_scope"] = float(check.out_of_scope(
            [r for r in everything if r["rows"] is not None], folder_of_doc))
    lost = 0
    if plan is not None:
        lost = sum(s is None for s in seen)
        rows_after = facts["rows_after"]
        numbers["order_violations"] = float(check.order_violations(
            [r for r in everything if r["rows"] is not None], plan))
        numbers["lost_writes"] = float(lost)
        numbers["count_off"] = (float("inf") if rows_after is None
                                else float(abs(rows_after - facts["rows_expected"])))
    correct, table = check.decide(numbers, limits)

    # -- end-to-end metrics, over all requests of the window
    lat = [r["recv"] - r["send"] for r in in_window]
    worst = max(lat, default=0.0)
    lat_all = [(r["recv"] - r["send"]) if r["rows"] is not None else worst for r in in_window]
    completed = [r for r in answered if r["recv"] <= t_end]
    metrics: dict[str, dict] = {}
    e2e = {
        "setup_s": setup_s,
        "retrieve_qps": len(completed) / seconds,
        "retrieve_p50_ms": percentile(lat_all, 50) * 1e3 if lat_all else None,
        "retrieve_p95_ms": percentile(lat_all, 95) * 1e3 if lat_all else None,
    }
    attempted, failed = len(in_window), len(in_window) - len(answered)
    if plan is not None:
        grace = float(mix["grace_s"])
        fresh, late = [], 0
        for c, s in zip(plan.commits, seen):
            due = t0 + c.due
            if s is None or s - due > (t_end - due) + grace:
                late += 1
            fresh.append((s if s is not None else t_end + AFTER_CLOSE_S) - due)
        e2e["freshness_p95_s"] = percentile(fresh, 95)
        attempted += len(plan.commits)
        failed += late
        log(f"freshness: median {percentile(fresh, 50):.3f} s, p95 "
            f"{e2e['freshness_p95_s']:.3f} s over {len(fresh)} writes; {late} "
            f"not seen within the window's end plus {grace:.0f} s; writer "
            f"lateness max {max(facts['writer_lateness_s'], default=0):.4f} s")
    log(f"requests: {len(in_window)} sent in the window, {len(answered)} "
        f"answered, {len(completed)} completed inside it; "
        f"p50 {e2e['retrieve_p50_ms']} ms p95 {e2e['retrieve_p95_ms']} ms")
    if lat_all:
        # where a rate and a median part ways: the tail, and the longest
        # stretches of the window in which no reply came back at all
        recvs = sorted([t0] + [r["recv"] for r in answered if r["recv"] <= t_end] + [t_end])
        quiet = sorted(((b - a, a - t0) for a, b in zip(recvs, recvs[1:])), reverse=True)[:3]
        log(f"latency: mean {sum(lat_all) / len(lat_all) * 1e3:.1f} ms, p99 "
            f"{percentile(lat_all, 99) * 1e3:.1f} ms, max {max(lat_all) * 1e3:.1f} ms; "
            "longest stretches with no reply: "
            + ", ".join(f"{d * 1e3:.0f} ms at {at:.1f} s" for d, at in quiet))
    with open(os.path.join(out_dir, "requests.jsonl"), "w") as f:
        for r in everything:
            f.write(json.dumps({"client": r["client"], "qid": r["qid"], "scope": r["scope"],
                                "send": r["send"] - t0, "recv": r["recv"] - t0,
                                "status": r["status"], "ok": r["rows"] is not None}) + "\n")

    mem = facts["memory"]
    resident = facts["resident_ready"].get("bytes_in_use", 0)
    device = dict(facts["device"])
    device["memory_peak_bytes"] = max(mem.get("peak_bytes_in_use", 0), resident)
    log(f"device memory: resident when ready {resident}, peak_bytes_in_use "
        f"{mem.get('peak_bytes_in_use')} (a program's temporaries are not in it "
        f"on this runtime), limit {mem.get('bytes_limit')}; child peak RSS "
        f"{facts['host_peak_rss_bytes']}")
    log(f"compiles in the window: {facts['compiles_in_window']}")
    log(f"garbage collections in the window (full ones as [at s, took s]): "
        f"{facts['gc_in_window']}")

    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer") for m in manifest[g]}
    result: dict = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if not args.trace:
        for m in metrics_of(manifest, "end_to_end", name):
            value = e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        tw = facts["trace_window"]
        trace = facts.get("trace")
        live_rows = facts["rows_expected"]
        traced = [r for r in answered + [p for p in probe_records if p["rows"] is not None]
                  if tw and tw["t0"] <= r["recv"] < tw["t1"]]
        probe_tokens = len(probe_text.split()) + fam.FRAMING
        tokens = [int(pool.tokens[r["qid"]]) if r["qid"] >= 0 else probe_tokens
                  for r in traced]
        counts = {
            "compiles_in_window": facts["compiles_in_window"]["compiled"],
            "traced_requests": len(traced),
            "traced_tokens": tokens,
            "live_rows": live_rows,
            # send to full reply of every request of the window, as the
            # end-to-end percentiles take them (a failed one the worst)
            "window_latency_ms": [x * 1e3 for x in lat_all],
        }
        if scope is not None:
            # the live rows a traced request was confined to (of the store,
            # where it carried no filter)
            counts["traced_scope_rows"] = [
                live_rows if r["scope"] is None else int(scope.rows_in[r["scope"]])
                for r in traced]
        cell_facts = {"name": name, "config": cfg, "family": fam, "mix": mix,
                      "chip": facts["chip"], "window": [t0, t_end], "trace_window": tw}
        for m in metrics_of(manifest, "per_layer", name):
            read = layer_reader(m["name"], loaded["manifest_dir"])
            value = read(trace, facts["spans"], counts, cell_facts)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                                   "idle_gaps": trace["idle_gaps"][:10]}
    result["metrics"] = metrics
    result["device"] = device
    # JSON has no infinity: a number that could not be read prints as 1e30
    compared = {n: {"value": v if v is not None and np.isfinite(v) else 1e30, "limit": lim}
                for n, v, lim in table}
    if "control" in verdict:
        log(f"control ({verdict['control']['precision']} in the program's place): "
            f"{json.dumps(verdict['control'])}")
        result["control"] = verdict["control"]
    result["compared"] = compared  # last: the numbers compared, beside their limits
    tail = [f"compared {n} = {v} (limit {lim})" for n, v, lim in table]
    return result, [*tail, f"correct = {correct}"]


if __name__ == "__main__":
    sys.exit(main())
