"""The plain reference that decides ``correct``. It imports nothing of the
program and takes nothing the program made: it rebuilds the graph from the
benchmark's own raw pairs with numpy, and judges each answer by the
guarantees the configuration states:

* every vertex is colored (colors > 0);
* no edge is monochromatic (a valid distance-1 coloring);
* no color exceeds max degree + 1 (first fit over a neighbourhood).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


class EdgeSet:
    """A simple undirected graph as sorted unique int64 keys
    ``u * V + v`` with ``u < v``."""

    def __init__(self, num_vertices: int, keys: np.ndarray):
        self.num_vertices = int(num_vertices)
        self.keys = keys

    @classmethod
    def from_pairs(cls, num_vertices: int, pairs: np.ndarray) -> "EdgeSet":
        return cls(num_vertices, canonical_keys(num_vertices, pairs))

    def endpoints(self) -> Tuple[np.ndarray, np.ndarray]:
        return divmod(self.keys, np.int64(self.num_vertices))

    def max_degree(self) -> int:
        u, v = self.endpoints()
        deg = np.bincount(u, minlength=self.num_vertices) \
            + np.bincount(v, minlength=self.num_vertices)
        return int(deg.max()) if deg.size else 0


def canonical_keys(num_vertices: int, pairs: np.ndarray) -> np.ndarray:
    """Sorted unique keys of the undirected edges in ``pairs`` (either
    orientation; self loops and duplicates dropped)."""
    pairs = np.asarray(pairs).reshape(-1, 2).astype(np.int64)
    u = np.minimum(pairs[:, 0], pairs[:, 1])
    v = np.maximum(pairs[:, 0], pairs[:, 1])
    keep = u != v
    return np.unique(u[keep] * np.int64(num_vertices) + v[keep])


def judge_coloring(ref: EdgeSet, colors: np.ndarray) -> Dict[str, int]:
    """The numbers compared for one coloring of ``ref``."""
    colors = np.asarray(colors)
    V = ref.num_vertices
    if colors.shape != (V,):
        return {"conflict_edges": int(ref.keys.size), "uncolored": V,
                "max_color": -1}
    u, v = ref.endpoints()
    return {"conflict_edges": int((colors[u] == colors[v]).sum()),
            "uncolored": int((colors <= 0).sum()),
            "max_color": int(colors.max()) if V else 0}


def palette(colors: np.ndarray) -> int:
    """Distinct positive colors in use."""
    colors = np.asarray(colors)
    return int(np.unique(colors[colors > 0]).size)
