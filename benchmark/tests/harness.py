"""Shared by the tests: run the benchmark's command on the CPU rehearsal."""

from __future__ import annotations

import json
import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
REHEARSAL = os.path.join(TESTS, "rehearsal", "BENCHMARK.json")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def run_cell(workload: str, *, seed: int = 2147483659, seconds: float = 3.0,
             trace: int = 0, manifest: str = REHEARSAL, extra: tuple[str, ...] = (),
             env: dict | None = None, timeout: float = 300.0):
    """(exit code, the last line's object or None, standard error)."""
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("BENCH_RUN", None)
    if env is not None:
        full_env.update(env)
        for k, v in env.items():
            if v is None:
                full_env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", manifest,
         "--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), *extra],
        capture_output=True, text=True, timeout=timeout, env=full_env, cwd=ROOT,
    )
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return proc.returncode, result, proc.stderr
