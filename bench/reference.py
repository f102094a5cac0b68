"""Plain host reference for BFS and SSSP: numpy and scipy, independent
of the code under test (it imports nothing of ``repro``).

BFS parents are the smallest-id neighbour one level closer to the root
(the tie rule of a min-combining push BFS); SSSP distances come from
scipy's float64 Dijkstra. The graph is symmetric, so a vertex's
in-neighbours are the column indices of its CSR row.
"""
from __future__ import annotations

import numpy as np

from bench.graph import BenchGraph

INT32_MAX = np.iinfo(np.int32).max


class HostReference:
    """CSR of the symmetric graph with sorted rows, plus the per-row
    segment starts that the vectorised checks reduce over."""

    def __init__(self, g: BenchGraph):
        import scipy.sparse as sp
        V = g.num_vertices
        src, dst, w = g.directed()
        A = sp.csr_matrix((w.astype(np.float64), (dst, src)), shape=(V, V))
        A.sort_indices()
        self.V = V
        self.A = A
        # edge i runs src[i] -> dst[i]; edges are grouped by dst, and by
        # ascending src within one dst
        counts = np.diff(A.indptr)
        self.dst = np.repeat(np.arange(V, dtype=np.int32), counts)
        self.src = A.indices.astype(np.int32)
        self.w = A.data.astype(np.float32)
        self.key = self.dst.astype(np.int64) * V + self.src
        self.has_in = counts > 0
        self.starts = A.indptr[:-1][self.has_in]

    def segment_min(self, vals: np.ndarray, fill) -> np.ndarray:
        """Per-vertex minimum of ``vals`` over its in-edges."""
        out = np.full(self.V, fill, vals.dtype)
        if self.starts.size:
            out[self.has_in] = np.minimum.reduceat(vals, self.starts)
        return out

    def edge_index(self, src: np.ndarray, dst: np.ndarray):
        """(index, found): where edge ``src -> dst`` sits in the edge
        arrays, and whether it exists."""
        k = dst.astype(np.int64) * self.V + src
        idx = np.minimum(np.searchsorted(self.key, k), self.key.size - 1)
        return idx, self.key[idx] == k

    def bfs_parents(self, root: int) -> np.ndarray:
        """(V,) int32: the root for itself, -1 where unreached, else
        the smallest neighbour one level closer to the root."""
        from scipy.sparse import csgraph
        lvl = csgraph.shortest_path(self.A, method="D", unweighted=True,
                                    indices=[root])[0]
        lv = np.where(np.isfinite(lvl), lvl, -2).astype(np.int32)
        cand = np.where(lv[self.src] + 1 == lv[self.dst], self.src,
                        INT32_MAX)
        par = self.segment_min(cand, INT32_MAX)
        par[par == INT32_MAX] = -1
        par[root] = root
        return par

    def sssp_distances(self, root: int) -> np.ndarray:
        """(V,) float64 shortest-path distances, inf where unreached."""
        from scipy.sparse import csgraph
        return csgraph.dijkstra(self.A, directed=True, indices=[root])[0]
