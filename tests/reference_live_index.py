"""A plain reference of a live vector index, for the tests that hold
``BruteForceKnnEngine`` to it: a dict from key to float32 vector, add /
remove / replace, exact cosine top-k by numpy at float32. Imports nothing of
``pathway_tpu``."""

from __future__ import annotations

import numpy as np


class ReferenceLiveIndex:
    def __init__(self, dim: int):
        self.dim = dim
        self.rows: dict[int, np.ndarray] = {}

    @staticmethod
    def _unit(vec) -> np.ndarray:
        v = np.asarray(vec, np.float32).reshape(-1)
        n = np.float32(np.linalg.norm(v))
        return v / n if n > 0 else v

    def add(self, key: int, vec) -> None:
        """Insert, or replace the row the key holds."""
        v = self._unit(vec)
        assert v.shape == (self.dim,)
        self.rows[int(key)] = v

    def remove(self, key: int) -> None:
        self.rows.pop(int(key), None)

    def score(self, query, key: int) -> float:
        return float(self._unit(query) @ self.rows[int(key)])

    def search(self, query, k: int) -> list[tuple[int, float]]:
        """The ``min(k, live)`` best (key, cosine), best first."""
        if not self.rows:
            return []
        keys = list(self.rows)
        scores = np.stack([self.rows[key] for key in keys]) @ self._unit(query)
        order = np.argsort(-scores, kind="stable")[:k]
        return [(keys[i], float(scores[i])) for i in order]
