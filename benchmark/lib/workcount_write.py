"""Bytes that an in-place write of index rows NEEDS, computed from shapes
(kept beside ``workcount.py``, which counts the retrieve step)."""

from __future__ import annotations


def index_write_bytes(padded_slots: float, dim: int) -> float:
    """One call of the write program over ``padded_slots`` slots of a float32
    block of width ``dim``: each row comes in and is written (4 bytes an
    element, twice), and its byte of the valid mask likewise."""
    return padded_slots * (dim * 4.0 * 2.0 + 2.0)
