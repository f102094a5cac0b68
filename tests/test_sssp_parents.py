"""SSSP parent pointers, resolved once after the superstep loop by the
kernel's ``finalize`` instead of an argmin carry every superstep.

A host simulation of the loop gives ``dist`` and ``t`` (the superstep in
which each distance last fell); a host oracle of the finalize rule then
gives the parents, which every engine path must reproduce bit for bit
on graphs full of tied shortest paths: integer weights, zero weights,
and weights below half an ulp of the distance they are added to. The
parent graph must be a tree of neighbours that attain each distance,
also for a query stopped by the superstep cap; and the loop itself must
hold one scatter, as BFS's does, with the finalize outside it."""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from repro.core import algorithms as ALG
from repro.core import graph as G
from repro.core import partition as PT
from repro.core.engine import Engine
from repro.core.stepper import LaneMeta, LaneTable

INT_MAX = np.iinfo(np.int32).max
KINDS = ("integer", "zero", "subulp", "real")


def _symmetric(n, lo, hi, w):
    return G.Graph(num_vertices=n,
                   src=np.concatenate([lo, hi]).astype(np.int32),
                   dst=np.concatenate([hi, lo]).astype(np.int32),
                   weights=np.concatenate([w, w]).astype(np.float32))


def tie_graph(kind: str, side: int = 11, seed: int = 0) -> G.Graph:
    """A ``side``² grid (many equally short paths) plus a few random
    chords, weighted by ``kind``: integers 1-3; 0 or 1; 1 or 1e-9 (below
    half an ulp of every distance >= 1); uniform reals."""
    rng = np.random.default_rng(seed)
    n = side * side
    idx = np.arange(n).reshape(side, side)
    lo = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel(),
                         rng.integers(0, n, 12)])
    hi = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel(),
                         rng.integers(0, n, 12)])
    keep = lo != hi
    lo, hi = lo[keep], hi[keep]
    m = lo.size
    w = {"integer": lambda: rng.integers(1, 4, m),
         "zero": lambda: rng.integers(0, 2, m),
         "subulp": lambda: np.where(rng.random(m) < 0.5, 1.0, 1e-9),
         "real": lambda: rng.uniform(0.5, 2.0, m)}[kind]()
    return _symmetric(n, lo, hi, w)


def host_loop(g, root, cap=None):
    """The kernel's superstep loop on the host: (dist, t, supersteps)."""
    V = g.num_vertices
    dist = np.full(V, np.inf, np.float32)
    dist[root] = 0.0
    t = np.full(V, -1, np.int32)
    active = np.zeros(V, bool)
    active[root] = True
    s = 0
    while active.any() and (cap is None or s < cap):
        m = active[g.src]
        cand = np.full(V, np.inf, np.float32)
        np.minimum.at(cand, g.dst[m],
                      (dist[g.src[m]] + g.weights[m]).astype(np.float32))
        better = cand < dist
        dist = np.where(better, cand, dist)
        t = np.where(better, np.int32(s), t)
        active = better
        s += 1
    return dist, t, s


def oracle_parents(g, dist, t):
    """The finalize rule on the host: (parent, vertices that needed the
    tie-break on t)."""
    V = g.num_vertices
    parent = np.full(V, -1, np.int32)
    fallback = 0
    into = [[] for _ in range(V)]
    for u, v, w in zip(g.src, g.dst, g.weights):
        into[v].append((u, w))
    for v in range(V):
        if not np.isfinite(dist[v]):
            continue
        if t[v] < 0:
            parent[v] = v
            continue
        cands = [u for u, w in into[v]
                 if np.float32(dist[u] + w) <= dist[v]]
        strict = [u for u in cands if dist[u] < dist[v]]
        if strict:
            parent[v] = min(strict)
            continue
        fallback += 1
        tied = [u for u in cands if dist[u] == dist[v]]
        first = min(t[u] for u in tied)
        parent[v] = min(u for u in tied if t[u] == first)
    return parent, fallback


def assert_tree(g, root, dist, parent, *, exact=True):
    """Root is its own parent, unreached vertices have none, every other
    reached vertex's parent is an in-neighbour whose distance plus the
    edge attains (``exact``) or bounds its own, and following parents
    from any vertex ends at the root."""
    V = g.num_vertices
    reached = np.isfinite(dist)
    assert parent[root] == root and dist[root] == 0.0
    assert (parent[~reached] == -1).all()
    w_of = {}
    for u, v, w in zip(g.src, g.dst, g.weights):
        w_of.setdefault((int(u), int(v)), []).append(w)
    for v in np.flatnonzero(reached):
        if v == root:
            continue
        p = int(parent[v])
        assert (p, int(v)) in w_of, (v, p)
        sums = [np.float32(dist[p] + w) for w in w_of[(p, int(v))]]
        if exact:
            assert any(s == dist[v] for s in sums), (v, p)
        else:
            assert any(s <= dist[v] for s in sums), (v, p)
    for v in np.flatnonzero(reached):
        x = int(v)
        for _ in range(V):
            if x == root:
                break
            x = int(parent[x])
        assert x == root, f"vertex {v} is on a parent cycle"


@pytest.fixture(scope="module", params=KINDS)
def case(request):
    g = tie_graph(request.param)
    pg = PT.partition_graph(g, 4, method="greedy", pad_multiple=16)
    return request.param, g, pg


ROOTS = (0, 60, 120)


def test_parents_follow_the_rule(case):
    kind, g, pg = case
    eng = Engine(ALG.sssp(), pg, mode="gravfm", backend="ref")
    fell_back = 0
    for root in ROOTS:
        res = eng.run(root=root)
        dist, t, steps = host_loop(g, root)
        assert np.array_equal(res.state["dist"].view(np.int32),
                              dist.view(np.int32))
        assert np.array_equal(res.state["t"], t)
        assert res.supersteps == steps
        want, fallback = oracle_parents(g, dist, t)
        assert np.array_equal(res.state["parent"], want)
        assert res.comm["parent_fallback"] == fallback
        assert_tree(g, root, dist, res.state["parent"])
        fell_back += fallback
    # the tie-break on t engages exactly where no strictly shorter
    # neighbour exists: zero weights and weights lost to rounding
    if kind in ("zero", "subulp"):
        assert fell_back > 0
    else:
        assert fell_back == 0


@pytest.mark.parametrize("cap", [1, 3])
def test_capped_query_keeps_neighbour_parents(case, cap):
    _, g, pg = case
    eng = Engine(ALG.sssp(), pg, mode="gravfm", backend="ref")
    for root in ROOTS:
        res = eng.run(max_supersteps=cap, root=root)
        dist, t, steps = host_loop(g, root, cap)
        assert res.supersteps == steps == cap
        assert np.array_equal(res.state["dist"].view(np.int32),
                              dist.view(np.int32))
        want, _ = oracle_parents(g, dist, t)
        assert np.array_equal(res.state["parent"], want)
        assert_tree(g, root, dist, res.state["parent"], exact=False)


def _same(a, b, where):
    for k in ("dist", "t", "parent"):
        assert np.array_equal(np.asarray(a.state[k]).view(np.int32),
                              np.asarray(b.state[k]).view(np.int32)), (
            where, k)
    assert a.supersteps == b.supersteps, where
    assert a.messages == b.messages, where
    assert a.comm["parent_fallback"] == b.comm["parent_fallback"], where


def test_engine_paths_agree(case):
    """run, run_batch, gravf mode, the Pallas backend, the continuous
    lane stepper (queries joining mid-flight) and a park/restore into
    another slot give the same dist, t, parent and counters."""
    _, g, pg = case
    eng = Engine(ALG.sssp(), pg, mode="gravfm", backend="ref")
    roots = np.array(ROOTS + (ROOTS[0],), np.int32)
    solo = {int(r): eng.run(root=int(r)) for r in roots}
    for r, res in zip(roots, eng.run_batch(root=roots)):
        _same(res, solo[int(r)], "run_batch")
    for mode, backend in (("gravf", "ref"), ("gravfm", "pallas")):
        other = Engine(ALG.sssp(), pg, mode=mode, backend=backend,
                       tile_e=64, tile_r=32)
        for r in ROOTS:
            _same(other.run(root=r), solo[r], (mode, backend))

    # continuous: root 0 alone for two supersteps, then the others join;
    # root 0's lane is parked after three and restored into another slot
    tab = LaneTable(eng.make_stepper(3), 3, ("root",))
    tab.admit({0: LaneMeta(payload=ROOTS[0], qkw={"root": ROOTS[0]})})
    for _ in range(2):
        tab.step(tab.alive_mask(10_000))
    tab.admit({1: LaneMeta(payload=ROOTS[1], qkw={"root": ROOTS[1]}),
               2: LaneMeta(payload=ROOTS[2], qkw={"root": ROOTS[2]})})
    tab.step(tab.alive_mask(10_000))
    parked = tab.checkpoint(0)
    results = {}
    for _ in range(1_000):
        done = [s for s in tab.done_slots(10_000)]
        if done:
            # as the continuous scheduler retires: the done lanes alone
            host = tab.fetch(done)
            for j, s in enumerate(done):
                results[tab.meta[s].payload] = eng.lane_result(host, j)
                tab.release(s)
            if parked is not None and 2 in done:
                tab.restore(2, parked)
                parked = None
        if tab.in_flight() == 0 and parked is None:
            break
        if tab.alive_mask(10_000).any():
            tab.step(tab.alive_mask(10_000))
    assert sorted(results) == sorted(ROOTS)
    for r, res in results.items():
        _same(res, solo[r], ("continuous", r))


def test_fetch_finalizes_the_given_lanes_alone():
    """A lane stepper's fetch gathers the lanes asked for into one lane
    or the whole width and finalizes those alone: any subset, in any
    order, reads as the same lanes of a whole-array fetch, and after one
    fetch per width nothing new is traced."""
    g = tie_graph("zero")
    pg = PT.partition_graph(g, 4, method="greedy", pad_multiple=16)
    eng = Engine(ALG.sssp(), pg, mode="gravfm", backend="ref")
    st = eng.make_stepper(5)
    assert st.fetch_widths() == [1, 5]
    roots = np.array([0, 60, 120, 7, 33], np.int32)
    carry, act, _ = st.init({"root": roots})
    for _ in range(4):    # some lanes still in flight, none quiescent
        carry, act, _ = st.step(carry, act)
    for k in st.fetch_widths():
        st.fetch(carry, range(k))
    traces = eng.traces
    whole = st.fetch(carry)
    for lanes in ([3], [4, 0], [2, 0, 1], [1, 2, 3, 4], [4, 3, 2, 1, 0]):
        part = st.fetch(carry, lanes)
        for key in ("dist", "t", "parent"):
            assert np.array_equal(part.state[key], whole.state[key][lanes])
        assert np.array_equal(part.stats["parent_fallback"],
                              whole.stats["parent_fallback"][lanes])
        assert np.array_equal(part.superstep, whole.superstep[lanes])
    assert st.fetch(carry, []).state["parent"].shape[0] == 0
    assert eng.traces == traces
    # a kernel without a finalize copies the lanes as they are
    bfs = Engine(ALG.bfs(), pg, mode="gravfm", backend="ref").make_stepper(3)
    assert bfs.fetch_widths() == []
    c, _, _ = bfs.init({"root": roots[:3]})
    part = bfs.fetch(c, [2, 0])
    assert np.array_equal(part.state["parent"],
                          np.asarray(c.state["parent"])[[2, 0]])


def test_service_schedulers_agree():
    """Bucketed and continuous serving answer SSSP with the same parents
    as a solo run, on a graph with zero-weight ties."""
    from repro.service import GraphQueryService, QueryRequest
    g = tie_graph("zero", side=9, seed=3)
    pg = PT.partition_graph(g, 2, method="greedy")
    want = {r: Engine(ALG.sssp(), pg, mode="gravfm", backend="ref").run(
        root=r) for r in (0, 7, 40)}
    for sched in ("bucketed", "continuous"):
        svc = GraphQueryService(num_shards=2, scheduling=sched, slots=2,
                                max_batch=2)
        svc.add_graph("g", g)
        svc.warm("g", "sssp")
        traces = svc.stats_snapshot()["plan_traces"]
        futs = {r: svc.submit(QueryRequest("g", "sssp", {"root": r},
                                           deadline_ms=1e9))
                for r in want}
        svc.flush()
        for r, f in futs.items():
            _same(f.result(timeout=120), want[r], sched)
        assert svc.stats_snapshot()["plan_traces"] == traces, sched


# ---------------------------------------------------------------------------
# the shard engine: one exchange of (dist, t), then the local pass
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import numpy as np
from repro.core import algorithms as ALG, partition as PT
from repro.core.engine import Engine
from repro.core.engine_shardmap import ShardEngine
from repro.core.stepper import LaneMeta, LaneTable
from repro.launch.mesh import auto_mesh
from test_sssp_parents import tie_graph

mesh = auto_mesh((4,), ("graph",))
roots = np.array([0, 60, 120], np.int32)
for kind in ("zero", "subulp"):
    g = tie_graph(kind)
    pg = PT.partition_graph(g, 4, method="greedy", pad_multiple=16)
    ref = Engine(ALG.sssp(), pg, mode="gravfm", backend="ref")
    want = {{int(r): ref.run(root=int(r)) for r in roots}}
    assert sum(w.comm["parent_fallback"] for w in want.values()) > 0

    def same(a, b, where):
        for k in ("dist", "t", "parent"):
            x = np.asarray(a.state[k]).view(np.int32)
            y = np.asarray(b.state[k]).view(np.int32)
            assert np.array_equal(x, y), (where, k)
        assert a.comm["parent_fallback"] == b.comm["parent_fallback"], where

    for exch in ("allgather", "ring", "frontier", "unicast", "combined"):
        se = ShardEngine(ALG.sssp(), pg, mesh=mesh, exchange=exch,
                         backend="ref")
        for overlap in (False, True):
            for r in roots:
                same(se.run(overlap=overlap, root=int(r)), want[int(r)],
                     (kind, exch, overlap))
        for r, res in zip(roots, se.run_batch(root=roots)):
            same(res, want[int(r)], (kind, exch, "batch"))
    se = ShardEngine(ALG.sssp(), pg, mesh=mesh, exchange="allgather",
                     backend="ref")
    tab = LaneTable(se.make_stepper(2), 2, ("root",))
    tab.admit({{0: LaneMeta(payload=0, qkw={{"root": 0}}),
               1: LaneMeta(payload=60, qkw={{"root": 60}})}})
    tab.step(tab.alive_mask(10_000))
    parked = tab.checkpoint(0)
    while tab.alive_mask(10_000).any():
        tab.step(tab.alive_mask(10_000))
    same(se.lane_result(tab.fetch(), 1), want[60],
         (kind, "stepper"))
    # the retiring lane alone (a width-1 finalize), as the scheduler asks
    same(se.lane_result(tab.fetch([1]), 0), want[60],
         (kind, "stepper", "one lane"))
    tab.release(1)
    tab.restore(1, parked)
    while tab.alive_mask(10_000).any():
        tab.step(tab.alive_mask(10_000))
    same(se.lane_result(tab.fetch(), 1), want[0], (kind, "restored"))
print("SHARD-PARENTS-OK")
"""


def test_shard_engine_parents_agree():
    """Every exchange, both schedules, the batched program and the shard
    lane stepper (with a park/restore) agree with the global-array
    engine on dist, t, parent and the fallback counter."""
    here = os.path.dirname(os.path.abspath(__file__))
    script = _SHARD_SCRIPT.format(
        src=os.path.join(os.path.dirname(here), "src"), tests=here)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARD-PARENTS-OK" in proc.stdout


# ---------------------------------------------------------------------------
# structure of the compiled programs (CPU lowering)
# ---------------------------------------------------------------------------

def _computations(hlo: str):
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    return comps


def _reachable(comps, roots):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                               line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def _scatters(comps, names):
    return [n for c in names for line in comps[c]
            for n in re.findall(r"%?([\w.\-]+) = \S+ scatter\(", line)]


def test_sssp_loop_holds_one_scatter_and_finalize_sits_outside():
    """The SSSP batch program's while body holds as many scatter ops as
    BFS's (one: the value pass's segment-min); the finalize's
    segment-mins sit outside the loop, under ``gravfm.finalize``, and
    its tie-break is a conditional, not a select over both branches."""
    from repro.service.trace import hlo_op_scopes
    g = tie_graph("integer", side=8)
    pg = PT.partition_graph(g, 2)
    loop_scatters, outside = {}, {}
    for name in ("bfs", "sssp"):
        eng = Engine(ALG.ALGORITHMS[name](), pg, backend="ref")
        text = eng.lower_batch(4).compile().as_text()
        comps = _computations(text)
        # the superstep loop (the finalize maps over queries in a loop
        # of its own, under gravfm.finalize)
        bodies = [b for lines in comps.values() for line in lines
                  if "gravfm.loop" in line
                  for b in re.findall(r"while\(.*body=%?([\w.\-]+)", line)]
        assert len(bodies) == 1, name
        inner = _reachable(comps, bodies)
        loop_scatters[name] = _scatters(comps, inner)
        outside[name] = _scatters(comps, set(comps) - inner)
        _, scopes = hlo_op_scopes(text)
        finalize = {op for op, s in scopes.items() if s == "gravfm.finalize"}
        if name == "bfs":
            assert not finalize and not outside[name]
        else:
            assert finalize
            assert " conditional(" in text
    assert len(loop_scatters["bfs"]) == len(loop_scatters["sssp"]) == 1
    # the strictly-shorter pass, then the fallback's two (t, then id)
    assert len(outside["sssp"]) == 3
