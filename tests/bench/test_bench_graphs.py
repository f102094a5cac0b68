"""The benchmark's graph generators and its Graph500 edge count."""
import json

import numpy as np
import pytest

from bench import harness
from bench.graph import BenchGraph
from conftest import REPO, tiny_kronecker

ROAD = dict(json.loads((REPO / "bench/configs/road131k.json").read_text()),
            side=24)
GENERATORS = {"kronecker": tiny_kronecker(scale=8), "road_grid": ROAD}


def generate(name, seed):
    mod = harness.load_module(REPO / "bench" / "graphs" / f"{name}.py")
    return mod.generate(GENERATORS[name], seed)


@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_generator_reproducible_per_seed(name):
    a, b = generate(name, 2 ** 31 + 5), generate(name, 2 ** 31 + 5)
    cfg = dict(GENERATORS[name],
               structure_seed=GENERATORS[name]["structure_seed"] + 1)
    mod = harness.load_module(REPO / "bench" / "graphs" / f"{name}.py")
    c = mod.generate(cfg, 2 ** 31 + 6)
    for x, y in ((a.lo, b.lo), (a.hi, b.hi), (a.w, b.w)):
        np.testing.assert_array_equal(x, y)
    assert a.num_undirected != c.num_undirected or \
        not np.array_equal(a.lo, c.lo)


@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_generator_gives_a_simple_undirected_graph(name):
    g = generate(name, 11)
    assert g.lo.dtype == g.hi.dtype == np.int32 and g.w.dtype == np.float32
    assert (g.lo < g.hi).all() and g.hi.max() < g.num_vertices
    keys = g.lo.astype(np.int64) * g.num_vertices + g.hi
    assert np.unique(keys).size == keys.size
    lo, hi = GENERATORS[name]["weights"]
    assert (g.w >= lo).all() and (g.w < hi).all()


def test_kronecker_size_and_skew():
    g = generate("kronecker", 3)
    n = 1 << 8
    # 16 * 2**8 draws, less self-loops and duplicates
    assert 0.5 * 16 * n < g.num_undirected < 16 * n
    deg = g.degrees()
    assert deg.max() > 10 * np.median(deg[deg > 0])     # power law


def test_road_grid_degree():
    g = generate("road_grid", 3)
    assert 2.5 < 2 * g.num_undirected / g.num_vertices < 3.1


def test_road_grid_is_the_same_network_for_every_seed():
    a, b = generate("road_grid", 2 ** 31 + 1), generate("road_grid", 7)
    for x, y in ((a.lo, b.lo), (a.hi, b.hi), (a.w, b.w)):
        np.testing.assert_array_equal(x, y)


def brute_component_edges(g: BenchGraph):
    V = g.num_vertices
    adj = [[] for _ in range(V)]
    for a, b in zip(g.lo, g.hi):
        adj[a].append(b)
        adj[b].append(a)
    comp = [-1] * V
    for s in range(V):
        if comp[s] < 0:
            comp[s], stack = s, [s]
            while stack:
                u = stack.pop()
                for v in adj[u]:
                    if comp[v] < 0:
                        comp[v] = s
                        stack.append(v)
    edges = {}
    for a in g.lo:
        edges[comp[a]] = edges.get(comp[a], 0) + 1
    return np.array([edges.get(comp[v], 0) for v in range(V)])


@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_component_edges_match_a_plain_search(name):
    g = generate(name, 5)
    np.testing.assert_array_equal(g.component_edges(),
                                  brute_component_edges(g))


def test_component_edges_of_two_components():
    g = BenchGraph(6, np.array([0, 1, 3], np.int32),
                   np.array([1, 2, 4], np.int32), np.ones(3, np.float32))
    np.testing.assert_array_equal(g.component_edges(), [2, 2, 2, 1, 1, 0])


def test_structure_seed_keeps_the_graph_and_moves_its_labels():
    # the run's seed leaves the graph as it is; another structure_seed
    # draws another graph under other labels
    cfg = GENERATORS["kronecker"]
    mod = harness.load_module(REPO / "bench" / "graphs" / "kronecker.py")
    a, b = mod.generate(cfg, 2 ** 31 + 1), mod.generate(cfg, 2 ** 31 + 2)
    for x, y in ((a.lo, b.lo), (a.hi, b.hi), (a.w, b.w)):
        np.testing.assert_array_equal(x, y)
    c = mod.generate(dict(cfg, structure_seed=cfg["structure_seed"] + 1),
                     2 ** 31 + 1)
    assert not np.array_equal(a.degrees(), c.degrees())
