"""The host reference, the comparison that decides ``correct``, and the
lower-precision control that it has to fail."""
import heapq

import numpy as np
import pytest

from bench import control, harness
from bench.check import compare, sssp_numbers
from bench.reference import HostReference
from conftest import REPO, tiny_kronecker

LIMITS = {"sssp_rel_err": 1e-4}


@pytest.fixture(scope="module")
def graph():
    mod = harness.load_module(REPO / "bench/graphs/kronecker.py")
    return mod.generate(tiny_kronecker(scale=8), 21)


@pytest.fixture(scope="module")
def ref(graph):
    return HostReference(graph)


def adjacency(g):
    adj = [[] for _ in range(g.num_vertices)]
    for a, b, w in zip(g.lo.tolist(), g.hi.tolist(), g.w.tolist()):
        adj[a].append((b, w))
        adj[b].append((a, w))
    return adj


def roots_of(g, n=3):
    return [int(r) for r in np.flatnonzero(g.degrees() > 0)[:n]]


def test_bfs_parents_are_min_id_parents_one_level_up(graph, ref):
    adj = adjacency(graph)
    for r in roots_of(graph):
        lvl = {r: 0}
        frontier = [r]
        while frontier:
            nxt = []
            for u in frontier:
                for v, _ in adj[u]:
                    if v not in lvl:
                        lvl[v] = lvl[u] + 1
                        nxt.append(v)
            frontier = nxt
        want = np.full(graph.num_vertices, -1)
        for v, lv in lvl.items():
            if v == r:
                want[v] = r
            else:
                want[v] = min(u for u, _ in adj[v] if lvl.get(u) == lv - 1)
        np.testing.assert_array_equal(ref.bfs_parents(r), want)


def test_sssp_distances_are_dijkstras(graph, ref):
    adj = adjacency(graph)
    for r in roots_of(graph):
        dist = {r: 0.0}
        heap = [(0.0, r)]
        while heap:
            d, u = heapq.heappop(heap)
            if d > dist[u]:
                continue
            for v, w in adj[u]:
                if d + w < dist.get(v, np.inf):
                    dist[v] = d + w
                    heapq.heappush(heap, (d + w, v))
        want = np.full(graph.num_vertices, np.inf)
        for v, d in dist.items():
            want[v] = d
        np.testing.assert_allclose(ref.sssp_distances(r), want, rtol=1e-12)


def sound_answers(graph, ref, roots):
    """BFS from the reference, SSSP from Bellman-Ford at float32."""
    sssp = control.sssp(graph, roots, "float32")
    return ([("bfs", r, {"parent": ref.bfs_parents(r)}) for r in roots]
            + [("sssp", r, a) for r, a in zip(roots, sssp)])


def test_sound_answers_are_correct(graph, ref):
    correct, numbers = compare(ref, sound_answers(graph, ref,
                                                  roots_of(graph)),
                               LIMITS, missing=0)
    assert correct, numbers
    assert numbers["sssp_rel_err"]["value"] < 1e-6


def test_missing_answers_are_not_correct(graph, ref):
    correct, numbers = compare(ref, sound_answers(graph, ref,
                                                  roots_of(graph)),
                               LIMITS, missing=1)
    assert not correct and numbers["missing"]["value"] == 1


def test_lower_precision_control_is_not_correct(graph, ref):
    roots = roots_of(graph)
    answers = [("sssp", r, a)
               for r, a in zip(roots, control.sssp(graph, roots))]
    correct, numbers = compare(ref, answers, LIMITS, missing=0)
    assert not correct
    assert numbers["sssp_rel_err"]["value"] > 10 * LIMITS["sssp_rel_err"]


@pytest.mark.parametrize("fault", ["parent", "distance", "root", "reach"])
def test_one_altered_sssp_vertex_is_caught(graph, ref, fault):
    r = roots_of(graph)[0]
    a = control.sssp(graph, [r], "float32")[0]
    dist, parent = a["dist"].copy(), a["parent"].copy()
    v = int(np.flatnonzero(np.isfinite(dist) & (dist > 0))[-1])
    if fault == "parent":
        parent[v] = v
    elif fault == "distance":
        dist[v] = np.nextafter(dist[v], np.float32(np.inf))
    elif fault == "root":
        parent[r] = -1
    else:
        dist[v], parent[v] = np.inf, -1
    _, bad = sssp_numbers(ref, r, dist, parent)
    assert bad >= 1


def test_one_altered_bfs_parent_is_caught(graph, ref):
    r = roots_of(graph)[0]
    parent = ref.bfs_parents(r)
    v = int(np.flatnonzero(parent >= 0)[-1])
    parent[v] = -1 if parent[v] != r else r + 1
    _, numbers = compare(ref, [("bfs", r, {"parent": parent})], LIMITS, 0)
    assert numbers["bfs_bad_vertices"]["value"] == 1
