"""Native runtime loader.

Compiles ``native.c`` (CPython C API — no pybind11 in this environment)
with the system compiler on first use and caches the shared object next to
the source. The object's file name carries a hash of the source it was built
from, so an object left over from another ``native.c`` is never loaded: a
changed source has a new name and is built afresh. When the build fails the
callers fall back to pure Python (the C and Python hash paths are
bit-identical, enforced by tests/test_native.py, so key values never
change) and the failure is logged with the compiler's message;
:func:`native_unavailable_reason` returns it.
"""

from __future__ import annotations

import contextlib
import glob
import hashlib
import importlib.util
import logging
import os
import subprocess
import sysconfig

__all__ = ["get_native", "native_available", "native_unavailable_reason"]

_log = logging.getLogger(__name__)

_cached: object | None = None
_tried = False
_reason: str | None = None


def _build(src: str, out: str) -> str | None:
    """Compile ``src`` into ``out``; returns None on success, else why not."""
    include = sysconfig.get_paths()["include"]
    cc = os.environ.get("CC", "gcc")
    # build under a private name and rename: processes that start together
    # (spawn -n) never load a half-written object
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = [
        cc, "-O3", "-shared", "-fPIC", "-std=c11",
        f"-I{include}", src, "-o", tmp,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{cc}: {e}"
    if proc.returncode != 0 or not os.path.exists(tmp):
        return (
            f"{cc} exited {proc.returncode}: "
            f"{proc.stderr.decode('utf-8', 'replace')[-2000:]}"
        )
    os.replace(tmp, out)
    return None


def _load() -> object:
    here = os.path.dirname(__file__)
    src = os.path.join(here, "native.c")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    out = os.path.join(here, f"_pathway_native_{digest}{suffix}")
    if not os.path.exists(out):
        error = _build(src, out)
        if error is not None:
            raise RuntimeError(error)
        for stale in glob.glob(os.path.join(here, "_pathway_native*.so")):
            if stale != out:
                with contextlib.suppress(OSError):
                    os.unlink(stale)
    spec = importlib.util.spec_from_file_location("_pathway_native", out)
    if spec is None or spec.loader is None:
        raise RuntimeError(f"cannot load {out}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def get_native():
    """The compiled module, or None when unavailable."""
    global _cached, _tried, _reason
    if _tried:
        return _cached
    _tried = True
    try:
        _cached = _load()
    except Exception as e:  # boundary: every caller has a Python path
        _reason = f"{type(e).__name__}: {e}"
        _log.warning(
            "native module unavailable, using the pure-Python paths: %s",
            _reason,
        )
    return _cached


def native_available() -> bool:
    return get_native() is not None


def native_unavailable_reason() -> str | None:
    """Why :func:`get_native` returned None (None when it loaded)."""
    get_native()
    return _reason
