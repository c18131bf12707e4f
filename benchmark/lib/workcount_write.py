"""Bytes that an in-place write of index rows NEEDS, computed from shapes
(kept beside ``workcount.py``, which counts the retrieve step)."""

from __future__ import annotations


def stored_bytes(metric: str) -> float:
    """Bytes a value of the device copy takes, from the configuration's
    ``metric``: the copy is kept in the type the scan multiplies in, bfloat16
    for ``cos`` and ``ip``, float32 for ``l2``, whose row norms read float32
    (``PERF.md`` section 3; stated here, not asked of the program)."""
    return {"cos": 2.0, "ip": 2.0, "l2": 4.0}[metric]


def index_write_bytes(padded_slots: float, dim: int, metric: str) -> float:
    """One call of the write program over ``padded_slots`` slots of a block of
    width ``dim``: each row comes in as float32 (4 bytes a value) and is
    written in the stored type, and its byte of the valid mask comes in and is
    written."""
    return padded_slots * (dim * (4.0 + stored_bytes(metric)) + 2.0)
