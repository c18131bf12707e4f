"""Shared by the tests: run the benchmark's command on the CPU rehearsal."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
REHEARSAL = os.path.join(TESTS, "rehearsal", "BENCHMARK.json")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def command(workload: str, *, seed: int = 2147483659, seconds: float = 3.0,
            trace: int = 0, manifest: str = REHEARSAL, extra: tuple[str, ...] = (),
            script: str = os.path.join(BENCH, "run.py")) -> list[str]:
    return [sys.executable, script, "--manifest", manifest,
            "--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace), *extra]


def environment(env: dict | None = None) -> dict:
    """The rehearsal's: the CPU, no ``BENCH_RUN``; a None in ``env`` unsets."""
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("BENCH_RUN", None)
    for k, v in (env or {}).items():
        if v is None:
            full_env.pop(k, None)
        else:
            full_env[k] = v
    return full_env


def processes_of(workload: str) -> list[int]:
    """Every process whose command line holds the run's ``spec.json``."""
    spec, found = os.path.join(BENCH, "out", workload, "spec.json").encode(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if spec in f.read():
                    found.append(int(pid))
        except OSError:
            pass
    return found


def child_gone(workload: str, within_s: float = 5.0) -> bool:
    """The pid ``run.py`` wrote to ``child.pid`` and every process of that
    run's spec have ended ``within_s`` seconds from now."""
    with open(os.path.join(BENCH, "out", workload, "child.pid")) as f:
        pid = int(f.read())
    t_end = time.monotonic() + within_s
    while time.monotonic() < t_end:
        if not processes_of(workload) and not os.path.exists(f"/proc/{pid}"):
            return True
        time.sleep(0.05)
    return False


def run_cell(workload: str, *, env: dict | None = None, timeout: float = 300.0, **how):
    """(exit code, the last line's object or None, standard error).

    A run that outlasts ``timeout`` is killed: ``subprocess.run`` kills
    ``run.py`` alone, and the child, which holds the device and its port,
    follows it by itself (``server_proc.die_with_parent``); that is asserted
    here, so a rehearsal that hangs leaves no server behind."""
    try:
        proc = subprocess.run(command(workload, **how), capture_output=True, text=True,
                              timeout=timeout, env=environment(env), cwd=ROOT)
    except subprocess.TimeoutExpired:
        assert child_gone(workload), f"{workload}: the child outlived its killed parent"
        raise
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return proc.returncode, result, proc.stderr
