"""The benchmark's own graph: unique undirected edges with one weight
each, made by a generator under ``bench/graphs/`` and handed to the
service as the symmetric directed edge list its ``Graph`` expects.

Both the service and the host reference read the same arrays, so the
edges counted for GTEPS and the edges the reference searches are the
edges that were served.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class BenchGraph:
    """``num_vertices`` vertices and undirected edges ``{lo[i], hi[i]}``
    with ``lo < hi``, no duplicates and weight ``w[i]`` in both
    directions."""

    num_vertices: int
    lo: np.ndarray  # (U,) int32
    hi: np.ndarray  # (U,) int32
    w: np.ndarray   # (U,) float32

    @property
    def num_undirected(self) -> int:
        return int(self.lo.shape[0])

    def degrees(self) -> np.ndarray:
        V = self.num_vertices
        return (np.bincount(self.lo, minlength=V)
                + np.bincount(self.hi, minlength=V))

    def directed(self):
        """(src, dst, w): each undirected edge in both directions."""
        src = np.concatenate([self.lo, self.hi])
        dst = np.concatenate([self.hi, self.lo])
        return src, dst, np.concatenate([self.w, self.w])

    def component_edges(self) -> np.ndarray:
        """(V,) int64: for every vertex, the number of undirected edges
        with both ends in its connected component -- Graph500's edge
        count for a search from that vertex."""
        from scipy.sparse import coo_matrix
        from scipy.sparse.csgraph import connected_components
        V = self.num_vertices
        adj = coo_matrix((np.ones(self.num_undirected, np.int8),
                          (self.lo, self.hi)), shape=(V, V))
        _, label = connected_components(adj, directed=False)
        per_label = np.bincount(label[self.lo], minlength=label.max() + 1)
        return per_label[label].astype(np.int64)

