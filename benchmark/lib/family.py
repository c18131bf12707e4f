"""A model family is one file: ``models/<model_type>.py`` beside the manifest,
else ``benchmark/models/<model_type>.py`` (``files.find``), found by the
``model_type`` its configuration names, as a per-layer metric is found by its
name. The file gives what the harness needs of a model (``benchmark/README.md``,
"Adding a model family"); ``models/bert.py`` is the example.

A ``model_type`` with no file stops the run in the parent, before any child
starts, with the paths it looked for.
"""

from __future__ import annotations

from lib import files


class NoFamily(LookupError):
    """A configuration's ``model_type`` has no family file."""


def load(cfg: dict, manifest_dir: str | None = None):
    """The family that a configuration names by its ``model_type``."""
    if "model_type" not in cfg:
        raise NoFamily("benchmark: the configuration names no model_type")
    try:
        path = files.find("models", cfg["model_type"], ".py", manifest_dir)
    except FileNotFoundError as e:
        raise NoFamily(f"benchmark: model_type {cfg['model_type']!r} has no family "
                       f"file ({e})") from None
    return files.load_module(path)
