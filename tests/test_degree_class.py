"""The in-degree-class combine (``kernels/degree_class.py``) against the
``segment_combine`` oracle, and the one-chip engine that runs it
(``Engine(backend="ref")``) against the Pallas backend and the shard
engine on the same graphs."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as ALG
from repro.core import graph as G
from repro.core import partition as PT
from repro.core.engine import Engine
from repro.core.stepper import LaneMeta, LaneTable
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.degree_class import (SUBLANES, ClassSegments,
                                        build_classes, class_widths,
                                        edge_major)


def _segments(degrees, n_parts=1, pad=0):
    """Sorted CSC segment ids of a graph whose vertex ``v`` has in-degree
    ``degrees[v]``, split over ``n_parts`` shards of ``Vm`` vertices with
    one discard bin each, and ``pad`` invalid lanes in each shard's bin
    (the partition's padding). Returns (seg, valid, num_segments)."""
    degrees = np.asarray(degrees, np.int64)
    vm = -(-degrees.size // n_parts)
    degrees = np.concatenate([degrees, np.zeros(vm * n_parts - degrees.size,
                                                np.int64)])
    seg, valid = [], []
    for p in range(n_parts):
        local = np.repeat(np.arange(vm), degrees[p * vm:(p + 1) * vm])
        seg += [p * (vm + 1) + local, np.full(pad, p * (vm + 1) + vm)]
        valid += [np.ones(local.size, bool), np.zeros(pad, bool)]
    return (np.concatenate(seg).astype(np.int64), np.concatenate(valid),
            n_parts * (vm + 1))


def _degrees(kind, rng):
    if kind == "random":          # many empty segments
        return rng.integers(0, 4, 300) * (rng.random(300) < 0.5)
    if kind == "heavy":           # one segment of 3,000 in-edges
        d = rng.integers(0, 6, 200)
        d[77] = 3000
        return d
    if kind == "degree1":
        return np.ones(257, np.int64)
    if kind == "road":            # in-degree 1 to 4, as a road grid
        return rng.integers(1, 5, 500)
    if kind == "spread":          # every width up to 300
        return np.arange(301)
    assert kind == "empty"
    return np.zeros(40, np.int64)


def _both(kind, combiner, dtype, batch=None):
    """(class combine, oracle) over the same values, optionally for a
    batch of rows under ``jax.vmap``."""
    rng = np.random.default_rng([KINDS.index(kind), len(combiner)])
    seg, valid, S = _segments(_degrees(kind, rng), n_parts=3, pad=5)
    lo = build_classes(seg, valid, S)
    shape = (seg.size,) if batch is None else (batch, seg.size)
    if np.issubdtype(dtype, np.floating):
        vals = rng.standard_normal(shape).astype(dtype)
    else:
        vals = rng.integers(-1000, 1000, shape).astype(dtype)
    ident = kops.identity_for(combiner, dtype)
    vals = np.where(valid, vals, ident).astype(dtype)

    def placed(v):
        return lo.place(v, ident)

    def ours(v):
        return kref.segment_combine(
            v, ClassSegments(jnp.asarray(lo.inv), lo.classes), S, combiner)

    def oracle(v):
        return kref.segment_combine(v, jnp.asarray(seg.astype(np.int32)),
                                    S, combiner)

    if batch is None:
        got, want = ours(jnp.asarray(placed(vals))), oracle(vals)
    else:
        pv = np.stack([placed(v) for v in vals])
        got = jax.vmap(ours)(jnp.asarray(pv))
        want = jax.vmap(oracle)(jnp.asarray(vals))
    return np.asarray(got), np.asarray(want)


KINDS = ("random", "heavy", "degree1", "road", "spread", "empty")


@pytest.mark.parametrize("batch", [None, 4])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("combiner,dtype", [
    ("min", np.int32), ("min", np.float32),
    ("max", np.int32), ("max", np.float32),
    ("add", np.int32), ("add", np.float32)])
def test_class_combine_matches_oracle(combiner, dtype, kind, batch):
    got, want = _both(kind, combiner, dtype, batch)
    assert got.shape == want.shape
    if combiner == "add" and np.issubdtype(dtype, np.floating):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", KINDS)
def test_place_keeps_every_valid_edge_once(kind):
    rng = np.random.default_rng(3)
    seg, valid, S = _segments(_degrees(kind, rng), n_parts=2, pad=7)
    lo = build_classes(seg, valid, S)
    ids = np.arange(seg.size)
    placed = lo.place(ids, -1)
    kept = placed[placed >= 0]
    assert np.array_equal(np.sort(kept), np.flatnonzero(valid))
    assert np.array_equal(placed >= 0, lo.lane_valid)
    # each lane sits in the block of its segment's class: the placed
    # segment ids read back through the layout give every segment its
    # in-degree
    seg_placed = lo.place(seg, S)
    counts = np.asarray(kref.segment_combine(
        jnp.asarray(np.where(lo.lane_valid, 1, 0).astype(np.int32)),
        ClassSegments(jnp.asarray(lo.inv), lo.classes), S, "add"))
    assert np.array_equal(counts, np.bincount(seg[valid], minlength=S)[:S])
    assert np.all(seg_placed[~lo.lane_valid] == S)


def test_widths_and_pad_share_by_hand():
    assert class_widths(1).tolist() == [1]
    assert class_widths(40).tolist() == (
        list(range(1, 17)) + [18, 20, 22, 24, 26, 28, 30, 32, 36, 40])
    assert [edge_major(w) for w in (1, 7, 8, 12, 16, 18, 24, 36, 64)] == [
        True, True, False, True, False, True, False, True, False]
    # in-degrees 1, 1, 17, 33, 8: classes 1 (two segments, rows padded to
    # SUBLANES), 8 (one), 18 (one, edge-major: rows padded to SUBLANES)
    # and 36 (one, edge-major)
    seg, valid, S = _segments([1, 1, 17, 33, 8], pad=3)
    lo = build_classes(seg, valid, S)
    assert lo.classes == ((1, SUBLANES), (8, 1), (18, SUBLANES),
                          (36, SUBLANES))
    lanes = SUBLANES * 1 + 8 + 18 * SUBLANES + 36 * SUBLANES
    assert lo.num_lanes == lanes
    assert lo.pad_share == pytest.approx((lanes - 60) / 60)
    # a road-like graph of in-degree 1 to 4 pads only its row counts
    seg, valid, S = _segments(np.arange(4 * SUBLANES * 10) % 4 + 1)
    lo = build_classes(seg, valid, S)
    assert [w for w, _ in lo.classes] == [1, 2, 3, 4]
    assert lo.pad_share == 0.0


# ---------------------------------------------------------------------------
# the one-chip engine on the class layout
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def kron():
    """A Kronecker graph (skewed in-degrees, many classes, padding) split
    over 4 shards; SSSP and PageRank read its random weights."""
    g = G.rmat(8, 8, seed=5, weighted=True).symmetrized()
    return PT.partition_graph(g, 4, pad_multiple=16)


def _agree(name, a, b, where):
    for k in a.state:
        x, y = np.asarray(a.state[k]), np.asarray(b.state[k])
        if name == "pagerank":
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6,
                                       err_msg=str((where, k)))
        else:
            assert np.array_equal(x.view(np.int32) if x.dtype == np.float32
                                  else x,
                                  y.view(np.int32) if y.dtype == np.float32
                                  else y), (where, k)
    assert a.supersteps == b.supersteps, where
    assert a.messages == b.messages, where
    for k in ("parent_fallback", "unicast_words", "bcast_filtered_words"):
        assert a.comm.get(k) == b.comm.get(k), (where, k)


@pytest.mark.parametrize("name", ["bfs", "sssp", "wcc", "pagerank"])
def test_engine_ref_agrees_with_pallas(kron, name):
    """run, run_batch and a lane stepper of the class-layout engine give
    what the Pallas backend gives: bit for bit but for PageRank's sums.
    WCC and PageRank take no query, so they run alone."""
    kern = ALG.ALGORITHMS[name]
    ref = Engine(kern(), kron, backend="ref")
    pal = Engine(kern(), kron, backend="pallas", tile_e=64, tile_r=32)
    assert ref.combine_classes > 1 and ref.combine_pad_share > 0
    assert pal.combine_classes is None
    if not kern().query_params:
        _agree(name, ref.run(), pal.run(), "run")
        return
    roots = (0, 17, 101)
    want = {r: pal.run(root=r) for r in roots}
    for r in roots:
        _agree(name, ref.run(root=r), want[r], ("run", r))
    for r, res in zip(roots, ref.run_batch(root=np.array(roots))):
        _agree(name, res, want[r], ("run_batch", r))
    tab = LaneTable(ref.make_stepper(2), 2, ("root",))
    tab.admit({j: LaneMeta(payload=r, qkw={"root": r})
               for j, r in enumerate(roots[:2])})
    while tab.alive_mask(10_000).any():
        tab.step(tab.alive_mask(10_000))
    host = tab.fetch()
    for j, r in enumerate(roots[:2]):
        _agree(name, ref.lane_result(host, j), want[r], ("stepper", r))


_SHARD_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {src!r})
import numpy as np
from repro.core import algorithms as ALG, graph as G, partition as PT
from repro.core.engine import Engine
from repro.core.engine_shardmap import ShardEngine
from repro.launch.mesh import auto_mesh

mesh = auto_mesh((4,), ("graph",))
g = G.rmat(8, 8, seed=5, weighted=True).symmetrized()
pg = PT.partition_graph(g, 4, pad_multiple=16)
roots = np.array([0, 17, 101], np.int32)
for name in ("bfs", "sssp", "wcc", "pagerank"):
    kern = ALG.ALGORITHMS[name]
    eng = Engine(kern(), pg, backend="ref")
    se = ShardEngine(kern(), pg, mesh=mesh, exchange="allgather",
                     backend="ref")
    if kern().query_params:
        pairs = zip(eng.run_batch(root=roots), se.run_batch(root=roots))
    else:
        pairs = [(eng.run(), se.run())]
    for a, b in pairs:
        for k in a.state:
            x, y = np.asarray(a.state[k]), np.asarray(b["state"][k])
            if name == "pagerank":
                assert np.allclose(x, y, rtol=1e-5, atol=1e-6), (name, k)
            else:
                assert np.array_equal(x, y), (name, k)
        assert a.supersteps == b["supersteps"], name
        assert a.messages == b["messages"], name
print("CLASS-SHARD-OK")
"""


def test_engine_ref_agrees_with_shard_engine():
    """The class-layout engine and the shard engine (which keeps the
    scatter) answer alike on four devices: every kernel, batched."""
    here = os.path.dirname(os.path.abspath(__file__))
    script = _SHARD_SCRIPT.format(
        src=os.path.join(os.path.dirname(here), "src"))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CLASS-SHARD-OK" in proc.stdout


def test_service_records_the_combine_layout():
    """Each engine the plan cache builds on the class layout leaves its
    padded-lane share and class count on its ``engine_build`` span and
    in ``stats_snapshot()["combine_layout"]``."""
    from repro.service import GraphQueryService
    g = G.rmat(8, 8, seed=5, weighted=True).symmetrized()
    svc = GraphQueryService(num_shards=4, max_batch=2)
    svc.add_graph("g", g)
    for k in ("bfs", "sssp"):
        svc.warm("g", k, batch_sizes=[2])
    eng = Engine(ALG.bfs(), svc.plans.graph("g", 4), backend="ref")
    want = {"combine_pad_share": eng.combine_pad_share,
            "combine_classes": eng.combine_classes}
    entries = svc.stats_snapshot()["combine_layout"]
    assert sorted(e["kernel"] for e in entries) == ["bfs", "sssp"]
    for e in entries:
        assert e["graph_id"] == "g"
        assert {k: e[k] for k in want} == want
    builds = [e for e in svc.trace_snapshot() if e.kind == "engine_build"]
    assert len(builds) == 2
    for b in builds:
        assert {k: b.attrs[k] for k in want} == want
