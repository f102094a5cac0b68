"""The comparison that decides ``correct``: served answers against the
host reference, as numbers each held to a limit.

    missing            queries that raised or never answered      limit 0
    bfs_bad_vertices   vertices whose BFS parent differs from the
                       reference's smallest-id parent             limit 0
    sssp_rel_err       widest relative gap of a served distance
                       from float64 Dijkstra, over the vertices
                       both reach                                 config
    sssp_bad_vertices  vertices that break the float32 SSSP
                       contract: reached set differs from
                       Dijkstra's, root not (0, itself), not a
                       float32 fixed point of the Bellman
                       equations, or a parent that is no
                       neighbour or does not attain the distance  limit 0

Counts are exact comparisons and have the limit 0. The limit of
``sssp_rel_err`` is the configuration's ``limits.sssp_rel_err``, set
between the program's readings and those of the lower-precision
control (``bench/control.py``); PERF.md gives both.
"""
from __future__ import annotations

import numpy as np

from bench.reference import HostReference

EXACT = ("missing", "bfs_bad_vertices", "sssp_bad_vertices")


def bfs_bad_vertices(ref: HostReference, root: int,
                     parent: np.ndarray) -> int:
    return int(np.count_nonzero(parent != ref.bfs_parents(root)))


def sssp_numbers(ref: HostReference, root: int, dist: np.ndarray,
                 parent: np.ndarray):
    """(relative error, bad vertices) of one served SSSP answer."""
    want = ref.sssp_distances(root)
    reached = np.isfinite(dist)
    bad = ~(reached == np.isfinite(want))
    both = reached & np.isfinite(want)
    gap = np.abs(dist[both].astype(np.float64) - want[both])
    rel = gap / np.maximum(want[both], np.finfo(np.float64).tiny)
    rel_err = float(rel.max(initial=0.0))
    # float32 Bellman fixed point: dist[v] = min_u fl(dist[u] + w_uv)
    best = ref.segment_min((dist[ref.src] + ref.w).astype(np.float32),
                           np.float32(np.inf))
    best[root] = 0.0
    bad |= best != dist
    bad[root] |= not (dist[root] == 0.0 and parent[root] == root)
    # every reached vertex but the root has a parent that is a
    # neighbour and attains its distance; an unreached one has none
    v = np.flatnonzero(reached)
    v = v[v != root]
    p = parent[v]
    idx, found = ref.edge_index(np.maximum(p, 0), v)
    attains = (dist[np.maximum(p, 0)] + ref.w[idx]).astype(
        np.float32) == dist[v]
    bad[v] |= ~((p >= 0) & found & attains)
    bad[~reached] |= parent[~reached] != -1
    return rel_err, int(np.count_nonzero(bad))


def compare(ref: HostReference, answers, limits: dict, missing: int):
    """``answers``: (kernel, root, state) triples. Returns
    ``(correct, numbers)`` where ``numbers`` maps each compared name to
    ``{"value": ..., "limit": ...}``."""
    numbers = {"missing": {"value": missing, "limit": 0}}
    kernels = {k for k, _, _ in answers}
    if "bfs" in kernels:
        numbers["bfs_bad_vertices"] = {"value": sum(
            bfs_bad_vertices(ref, r, s["parent"])
            for k, r, s in answers if k == "bfs"), "limit": 0}
    if "sssp" in kernels:
        rel, bad = 0.0, 0
        for k, r, s in answers:
            if k == "sssp":
                e, b = sssp_numbers(ref, r, s["dist"], s["parent"])
                rel, bad = max(rel, e), bad + b
        numbers["sssp_rel_err"] = {"value": rel,
                                   "limit": float(limits["sssp_rel_err"])}
        numbers["sssp_bad_vertices"] = {"value": bad, "limit": 0}
    correct = all(n["value"] <= n["limit"] for n in numbers.values())
    return correct, numbers
