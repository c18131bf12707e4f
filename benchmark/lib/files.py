"""Files that ``BENCHMARK.json`` or a configuration names by a name:
``<kind>/<name><ext>`` beside the manifest, else under ``benchmark/``; and a
Python file among them (a per-layer reader, a model family) loaded as a
module, once in a process."""

from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_modules: dict[str, object] = {}


def find(kind: str, name: str, ext: str, manifest_dir: str | None = None) -> str:
    """``<kind>/<name><ext>`` beside the manifest first, else in ``benchmark/``."""
    looked = []
    for base in (manifest_dir, BENCH):
        if base is None:
            continue
        path = os.path.join(base, kind, name + ext)
        if os.path.isfile(path):
            return path
        looked.append(path)
    raise FileNotFoundError(f"benchmark: no {kind}/{name}{ext}; looked for "
                            + " and ".join(looked))


def load_module(path: str):
    """The module in ``path``, loaded once in a process."""
    path = os.path.abspath(path)
    if path not in _modules:
        kind = os.path.basename(os.path.dirname(path))
        stem = os.path.splitext(os.path.basename(path))[0].replace(".", "_")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]
