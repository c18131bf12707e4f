"""Start-up and device selection (PR 21): where the compile cache lives, who
may import JAX, what runs without a chip, and which native object loads.

Everything that depends on process-wide JAX state runs in a subprocess: the
test process itself has the compile cache off (conftest) and JAX imported.
"""

from __future__ import annotations

import importlib.machinery
import importlib.util
import inspect
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra: str | None) -> dict:
    """The test environment with the compile cache back on, as in product
    runs, plus ``extra`` (None removes a variable)."""
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    for k, v in extra.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


def _run(code: str, env: dict, timeout: float = 120.0):
    return subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=timeout, cwd=REPO,
    )


# one tiny program through the embedder's door; prints the configured
# directory and how many programs were compiled / found in the cache
_COMPILE_ONE = """
import json
from pathway_tpu.utils import jaxcfg
import jax, jax.numpy as jnp
stats = {"compiled": 0, "hits": 0}
def on_event(event, **kw):
    if event == "/jax/compilation_cache/cache_hits":
        stats["hits"] += 1
def on_secs(event, secs, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        stats["compiled"] += 1
jax.monitoring.register_event_listener(on_event)
jax.monitoring.register_event_duration_secs_listener(on_secs)
jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)(jnp.ones((7, 5))).block_until_ready()
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir,
                  "fixed": jaxcfg.COMPILE_CACHE_DIR,
                  "compiled": stats["compiled"] - stats["hits"]}))
"""


def _listing(path: str) -> set:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_compile_cache_follows_the_environment(tmp_path):
    placed = str(tmp_path / "placed_cache")
    checkout_cache = os.path.join(REPO, ".jax_cache")
    before = _listing(checkout_cache)
    env = _env(JAX_COMPILATION_CACHE_DIR=placed)
    first = _run(_COMPILE_ONE, env)
    assert first.returncode == 0, first.stderr[-2000:]
    out = json.loads(first.stdout.strip().splitlines()[-1])
    # the environment's directory, untouched by code
    assert out["dir"] == placed
    assert out["compiled"] >= 1
    assert _listing(placed), "nothing was written to the placed directory"
    assert _listing(checkout_cache) == before, "the checkout cache was written too"
    # a second process finds what the first compiled
    second = _run(_COMPILE_ONE, env)
    assert second.returncode == 0, second.stderr[-2000:]
    again = json.loads(second.stdout.strip().splitlines()[-1])
    assert again["compiled"] < out["compiled"]


def test_compile_cache_defaults_to_one_path_in_the_checkout():
    # off, so that this test writes nothing into the checkout: the
    # directory is configured all the same
    env = _env(JAX_ENABLE_COMPILATION_CACHE="false")
    dirs = set()
    for _ in range(2):
        r = _run(_COMPILE_ONE, env)
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["dir"] == out["fixed"] == os.path.join(REPO, ".jax_cache")
        dirs.add(out["dir"])
    assert len(dirs) == 1


def test_compile_cache_path_is_not_built_from_process_state():
    from pathway_tpu.utils import jaxcfg

    src = inspect.getsource(jaxcfg.place_compile_cache)
    assert not re.search(r"tempfile|getpid|\btime\b", src)
    module_src = inspect.getsource(jaxcfg)
    assert not re.search(r"import tempfile|getpid|import time", module_src)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def _package_sources():
    """(path relative to the checkout, text) of every module of the package."""
    for root, _dirs, files in os.walk(os.path.join(REPO, "pathway_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    yield os.path.relpath(path, REPO), f.read()


def test_every_jax_import_goes_through_jaxcfg():
    """A module that imports jax without jaxcfg could compile before the
    cache is placed."""
    offenders = [
        path for path, src in _package_sources()
        if not path.endswith("utils/jaxcfg.py")
        and re.search(r"^\s*(import jax\b|from jax\b)", src, re.M)
        and "jaxcfg" not in src
    ]
    assert not offenders, offenders


def test_nothing_switches_x64_on():
    """The chip runs every device program without x64, so the package never
    updates ``jax_enable_x64``, and the tests do not run in another mode."""
    with open(os.path.join(REPO, "tests", "conftest.py")) as f:
        sources = [("tests/conftest.py", f.read()), *_package_sources()]
    offenders = [
        path for path, src in sources
        if re.search(r"enable_x64|JAX_ENABLE_X64", src)
    ]
    assert not offenders, offenders


def test_package_cli_and_xpack_imports_stay_off_jax():
    code = """
import importlib, pkgutil, sys
import pathway_tpu, pathway_tpu.cli
import pathway_tpu.xpacks.llm as llm
for m in pkgutil.iter_modules(llm.__path__):
    importlib.import_module(f"pathway_tpu.xpacks.llm.{m.name}")
import chip_smoke
sys.exit(1 if "jax" in sys.modules else 0)
"""
    # a spawn child's environment must not pull jax in either
    env = _env(PATHWAY_PROCESSES="2", PATHWAY_MESH_EXCHANGE="1")
    r = _run(code, env)
    assert r.returncode == 0, "jax was imported\n" + r.stderr[-2000:]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = subprocess.run(
        [sys.executable, SMOKE], env=_env(JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode != 0
    assert r.stdout.strip() == "", "a result was printed without a chip"
    assert r.stderr.strip().splitlines()[-1].startswith("chip_smoke: no TPU")


def test_chip_smoke_rehearsal_runs_every_phase(tmp_path):
    env = _env(
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    r = subprocess.run(
        [sys.executable, SMOKE, "--rehearse-on-cpu"], env=env,
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    phases = [x["phase"] for x in lines[:-1]]
    assert phases == [
        "ingest", "serve", "live_update", "index", "host",
        "fourchip_dryrun", "fourchip_index", "fourchip_serve",
    ]
    for x in lines[:-1]:
        assert x["device"]["platform"] == "cpu" and x["jax"]
        assert {"smoke_wall_s", "compiles", "compile_s",
                "peak_bytes_in_use"} <= set(x)
    by_phase = {x["phase"]: x for x in lines[:-1]}
    assert by_phase["host"]["native"] is True
    assert by_phase["index"]["cache_hits"] >= 1


def test_native_object_is_named_by_its_source(tmp_path):
    """The loader builds and loads only the object whose name carries the
    hash of the native.c beside it; leftovers are never loaded."""
    native_dir = os.path.join(REPO, "pathway_tpu", "native")
    work = tmp_path / "native"
    work.mkdir()
    for name in ("__init__.py", "native.c"):
        shutil.copy(os.path.join(native_dir, name), work / name)
    suffix = importlib.machinery.EXTENSION_SUFFIXES[0]
    # what an older checkout left behind: not an object at all
    (work / f"_pathway_native{suffix}").write_bytes(b"stale")

    def load():
        spec = importlib.util.spec_from_file_location(
            "native_under_test", work / "__init__.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    first = load()
    if not first.native_available():
        pytest.skip(f"no C compiler: {first.native_unavailable_reason()}")
    built = {p.name for p in work.glob("_pathway_native*.so")}
    assert len(built) == 1 and (work / f"_pathway_native{suffix}").name not in built
    # a changed source is a new object; the old one is not loaded and goes
    with open(work / "native.c", "a") as f:
        f.write("\n/* changed */\n")
    second = load()
    assert second.native_available()
    rebuilt = {p.name for p in work.glob("_pathway_native*.so")}
    assert len(rebuilt) == 1 and rebuilt != built
    assert os.path.basename(second.get_native().__file__) in rebuilt
