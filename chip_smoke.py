#!/usr/bin/env python3
"""Serve BFS and SSSP queries on a TPU at Graph500 RMAT scale 20, and
check every answer against a plain host reference.

    python chip_smoke.py             # one chip: bucketed, continuous and
                                     # Pallas phases of GraphQueryService
    python chip_smoke.py --chips 4   # four chips: the mesh path only, the
                                     # combined and allgather exchanges,
                                     # each synchronous and overlapped

The graph is ``graph.rmat(20, 16, weighted=True).symmetrized()``: the
Graph500 Kronecker parameters, 2**20 vertices, generated from ``--seed``.
BFS parents must equal the min-id parent at the previous level; SSSP
distances must be a float32 fixed point of the Bellman equations and
agree with scipy's float64 Dijkstra.

The script exits non-zero, printing no result, when JAX finds no TPU, and
when any query, check or phase fails. Its last line of standard output
is one JSON object naming the device. JAX's persistent compilation cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says, or else in ``.jax_cache/``
next to this file. It starts no other process.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
GID = "rmat"
INT32_MAX = np.iinfo(np.int32).max
# SSSP distances against scipy's float64 Dijkstra: float32 rounding
# accumulates about one ulp per edge of a shortest path
SSSP_RTOL = 1e-5


class SmokeFailure(AssertionError):
    """A served answer, or the served path itself, is wrong."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def place_compile_cache() -> str:
    """JAX_COMPILATION_CACHE_DIR when it is set (JAX reads it itself),
    else a fixed directory of this checkout: the path is part of the
    cache key, so it must not move between runs."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# host reference (numpy / scipy; independent of the code under test)
# ---------------------------------------------------------------------------

class HostReference:
    """Edge arrays in CSC order plus a scipy CSR matrix of the graph."""

    def __init__(self, g):
        import scipy.sparse as sp
        V = g.num_vertices
        self.V = V
        self.A = sp.csr_matrix((g.weights.astype(np.float64),
                                (g.src, g.dst)), shape=(V, V))
        order = np.lexsort((g.src, g.dst))
        self.src = g.src[order]
        self.dst = g.dst[order]
        self.w = g.weights[order]
        self.key = self.dst.astype(np.int64) * V + self.src
        indeg = np.bincount(g.dst, minlength=V)
        self.has_in = indeg > 0
        self.starts = (np.cumsum(indeg) - indeg)[self.has_in]

    def _segment_min(self, vals, fill):
        out = np.full(self.V, fill, vals.dtype)
        out[self.has_in] = np.minimum.reduceat(vals, self.starts)
        return out

    def bfs_parents(self, roots) -> np.ndarray:
        """(len(roots), V) int32: the root for itself, -1 where
        unreached, else the smallest in-neighbour one level closer."""
        from scipy.sparse import csgraph
        out = np.empty((len(roots), self.V), np.int32)
        for lo in range(0, len(roots), 16):
            chunk = roots[lo:lo + 16]
            lvl = csgraph.shortest_path(self.A, method="D", unweighted=True,
                                        indices=chunk)
            for i, r in enumerate(chunk):
                lv = np.where(np.isfinite(lvl[i]), lvl[i], -2).astype(
                    np.int32)
                cand = np.where(lv[self.src] + 1 == lv[self.dst], self.src,
                                INT32_MAX)
                par = self._segment_min(cand, INT32_MAX)
                par[par == INT32_MAX] = -1
                par[r] = r
                out[lo + i] = par
        return out

    def sssp_distances(self, roots) -> np.ndarray:
        from scipy.sparse import csgraph
        return csgraph.dijkstra(self.A, directed=True, indices=roots)

    def check_sssp(self, root: int, dist: np.ndarray, parent: np.ndarray,
                   want: np.ndarray, what: str) -> None:
        reached = np.isfinite(dist)
        check((reached == np.isfinite(want)).all(),
              f"{what}: reached set differs from Dijkstra")
        check(np.allclose(dist[reached], want[reached], rtol=SSSP_RTOL,
                          atol=0.0),
              f"{what}: distances differ from Dijkstra beyond rtol "
              f"{SSSP_RTOL}")
        check(dist[root] == 0.0 and parent[root] == root,
              f"{what}: root state wrong")
        # float32 Bellman fixed point: dist[v] = min_u fl(dist[u] + w_uv)
        best = self._segment_min((dist[self.src] + self.w).astype(np.float32),
                                 np.float32(np.inf))
        best[root] = 0.0
        check(np.array_equal(best, dist),
              f"{what}: not a float32 fixed point of the Bellman equations")
        # every parent is an in-neighbour that attains the distance
        v = np.flatnonzero(reached)
        v = v[v != root]
        p = parent[v].astype(np.int64)
        check((p >= 0).all(), f"{what}: reached vertex without parent")
        k = v.astype(np.int64) * self.V + p
        idx = np.minimum(np.searchsorted(self.key, k), self.key.size - 1)
        check((self.key[idx] == k).all(), f"{what}: parent is no neighbour")
        check(np.array_equal(dist[p] + self.w[idx], dist[v]),
              f"{what}: parent does not attain the distance")
        check((parent[~reached] == -1).all(),
              f"{what}: unreached vertex has a parent")


# ---------------------------------------------------------------------------
# served phases
# ---------------------------------------------------------------------------

def serve(svc, requests, timeout_s: float):
    """Submit every request to the started service and wait for all of
    them; a future that raises fails the run."""
    futs = [svc.submit(r) for r in requests]
    return [f.result(timeout=timeout_s) for f in futs]


def run_phase(svc, g, kernels, requests, timeout_s: float, what: str):
    """add_graph() and warm() the kernels, answer ``requests[0]`` through
    the synchronous ``query()``, then start the scheduler thread, submit
    the rest and stop. Returns the results in request order; fails if
    ``plan_traces`` moved after warm()."""
    svc.add_graph(GID, g)
    t = time.perf_counter()
    for k in kernels:
        svc.warm(GID, k)
    log(f"[{what}] warm (compile) {time.perf_counter() - t:.3f} s")
    before = svc.stats_snapshot()
    t = time.perf_counter()
    head = requests[0]
    out = [svc.query(GID, head.kernel, **head.query_kwargs)]
    svc.start()
    out += serve(svc, requests[1:], timeout_s)
    svc.stop()
    wall = time.perf_counter() - t
    after = svc.stats_snapshot()
    check(after["plan_traces"] == before["plan_traces"],
          f"[{what}] plan_traces grew after warm(): "
          f"{before['plan_traces']} -> {after['plan_traces']}")
    counters = ", ".join(
        f"{k} {after[k] - before[k]:g}"
        for k in ("batches_dispatched", "supersteps_total", "busy_time_s"))
    log(f"[{what}] {len(out)} queries served in {wall:.3f} s (host clock); "
        f"plan_traces {before['plan_traces']} -> {after['plan_traces']}; "
        f"{counters}; max supersteps {max(r.supersteps for r in out)}")
    return out


def check_bfs(results, roots, want, what: str) -> None:
    for res, r, par in zip(results, roots, want):
        check(np.array_equal(res.state["parent"], par),
              f"{what}: BFS root {r} parents differ from the host reference")


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use']} B"


def fitting_batch(plans, num_shards: int, start: int) -> int:
    """The largest power of two <= ``start`` whose compiled BFS and SSSP
    batch programs fit the device beside what is already resident."""
    import jax
    from repro.service import PlanKey
    dev = jax.devices()[0]
    b = start
    while b >= 1:
        fits, why = True, []
        for kernel in ("bfs", "sssp"):
            eng = plans.get_plan(PlanKey(GID, kernel, "gravfm", num_shards,
                                         b, backend="ref")).engine
            try:
                m = eng.lower_batch(b).compile().memory_analysis()
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                fits = False
                why.append(f"{kernel} refused by the compiler: "
                           f"{str(e).splitlines()[0][:160]}")
                continue
            need = m.temp_size_in_bytes + m.output_size_in_bytes
            stats = dev.memory_stats() or {}
            if "bytes_limit" in stats:
                free = stats["bytes_limit"] - stats["bytes_in_use"]
                ok = need <= free
                fits = fits and ok
                why.append(f"{kernel} temp+out {need} B "
                           f"{'<=' if ok else '>'} free {free} B")
            else:
                why.append(f"{kernel} temp+out {need} B (device reports "
                           "no memory limit)")
        verdict = "fits" if fits else "does not fit"
        log(f"batch {b}: {verdict} ({'; '.join(why)})")
        if fits:
            return b
        b //= 2
    raise SmokeFailure("no batch size fits the device")


def one_chip(g, ref, bfs_roots, sssp_roots, args, platform: str) -> None:
    import jax
    from repro.service import (GraphQueryService, PlanCache, PlanKey,
                               QueryRequest)
    dev = jax.devices()[0]
    S = inspect.signature(GraphQueryService).parameters
    num_shards = S["num_shards"].default
    default_batch = S["max_batch"].default

    t = time.perf_counter()
    plans = PlanCache()
    pg = plans.register_graph(GID, g, num_shards=num_shards)
    log(f"partitioned into {num_shards} shards in "
        f"{time.perf_counter() - t:.3f} s; PartitionedGraph.device_nbytes "
        f"{pg.device_nbytes} B")

    t = time.perf_counter()
    batch = fitting_batch(plans, num_shards, default_batch)
    log(f"max_batch = {batch}: the largest power of two <= the service "
        f"default {default_batch} whose BFS and SSSP programs fit "
        f"(probe {time.perf_counter() - t:.3f} s)")

    bfs_want = ref.bfs_parents(bfs_roots)
    sssp_want = ref.sssp_distances(sssp_roots)
    timeout = args.query_timeout

    def bfs_reqs(roots):
        return [QueryRequest(GID, "bfs", {"root": int(r)}) for r in roots]

    def sssp_reqs(roots):
        return [QueryRequest(GID, "sssp", {"root": int(r)}) for r in roots]

    def check_sssp(results, roots, want, what):
        for res, r, w in zip(results, roots, want):
            ref.check_sssp(int(r), res.state["dist"], res.state["parent"], w,
                           f"{what} SSSP root {r}")

    n_bfs = len(bfs_roots)
    ref_bfs = None
    for phase, kw in (("bucketed", dict(scheduling="bucketed")),
                      ("continuous", dict(scheduling="continuous",
                                          slots=batch))):
        svc = GraphQueryService(max_batch=batch, plan_cache=plans, **kw)
        out = run_phase(svc, g, ("bfs", "sssp"),
                        bfs_reqs(bfs_roots) + sssp_reqs(sssp_roots),
                        args.query_timeout, phase)
        t = time.perf_counter()
        check_bfs(out[:n_bfs], bfs_roots, bfs_want, phase)
        check_sssp(out[n_bfs:], sssp_roots, sssp_want, phase)
        ref_bfs = ref_bfs or out[:n_bfs]
        log(f"[{phase}] {n_bfs} BFS + {len(sssp_roots)} SSSP answered and "
            f"checked against the host reference "
            f"({time.perf_counter() - t:.3f} s to check)")

    svc = GraphQueryService(max_batch=batch, backend="pallas",
                            plan_cache=plans)
    out = run_phase(svc, g, ("bfs",), bfs_reqs(bfs_roots),
                    args.query_timeout, "pallas")
    check_bfs(out, bfs_roots, bfs_want, "pallas")
    for a, b in zip(out, ref_bfs):
        check(np.array_equal(a.state["parent"], b.state["parent"]),
              "[pallas] BFS parents are not bit-identical to backend=ref")
    eng = plans.get_plan(PlanKey(GID, "bfs", "gravfm", num_shards, batch,
                                 backend="pallas")).engine
    hlo = eng.lower_batch(batch).compile().as_text()
    n_calls = hlo.count('custom_call_target="tpu_custom_call"')
    if platform == "tpu":
        check(n_calls > 0, "[pallas] compiled HLO holds no tpu_custom_call")
    log(f"[pallas] {n_bfs} BFS answered, equal to the reference and "
        f"bit-identical to backend=ref; tpu_custom_call in the compiled "
        f"batch-{batch} HLO: {n_calls}")
    log(f"peak_bytes_in_use: {peak_bytes(dev)}")


def mesh(g, ref, bfs_roots, args) -> None:
    import jax
    from repro.service import GraphQueryService, PlanCache, QueryRequest
    n = args.chips
    plans = PlanCache()
    t = time.perf_counter()
    pg = plans.register_graph(GID, g, num_shards=n)
    log(f"partitioned into {n} shards in {time.perf_counter() - t:.3f} s; "
        f"PartitionedGraph.device_nbytes {pg.device_nbytes} B")
    want = ref.bfs_parents(bfs_roots)
    for exchange in ("combined", "allgather"):
        svc = GraphQueryService(num_shards=n, exchange=exchange,
                                scheduling="continuous", slots=args.batch,
                                max_batch=args.batch, plan_cache=plans)
        t = time.perf_counter()
        svc.add_graph(GID, g)
        svc.warm(GID, "bfs", overlap=False)
        svc.warm(GID, "bfs", overlap=True)
        log(f"[{exchange}] engine build + warm (compile) "
            f"{time.perf_counter() - t:.3f} s")
        traces = svc.stats_snapshot()["plan_traces"]
        svc.start()
        # distinct roots per schedule: a repeated root would be answered
        # from the result cache without touching the engine
        half = len(bfs_roots) // 2
        for overlap, sl in ((False, slice(0, half)),
                            (True, slice(half, None))):
            before = svc.stats_snapshot()
            t = time.perf_counter()
            out = serve(svc, [QueryRequest(GID, "bfs", {"root": int(r)},
                                           overlap=overlap)
                              for r in bfs_roots[sl]], args.query_timeout)
            wall = time.perf_counter() - t
            after = svc.stats_snapshot()
            what = f"{exchange} overlap={overlap}"
            check_bfs(out, bfs_roots[sl], want[sl], what)
            log(f"[{what}] {len(out)} BFS answered and checked; served in "
                f"{wall:.3f} s (host clock); supersteps_total "
                f"{after['supersteps_total'] - before['supersteps_total']:g}, "
                f"busy_time_s {after['busy_time_s'] - before['busy_time_s']:g}")
        svc.stop()
        after = svc.stats_snapshot()["plan_traces"]
        check(after == traces,
              f"[{exchange}] plan_traces grew after warm(): "
              f"{traces} -> {after}")
        devs = set()
        for e in svc.trace_snapshot():
            if e.kind == "superstep":
                devs.update(e.attrs.get("devices", ()))
        series = svc.metrics_snapshot().get(
            "gravfm_device_supersteps_total", {}).get("series", [])
        per_dev = {}
        for s in series:    # one series per (class, device)
            dev = s["labels"]["device"]
            per_dev[dev] = per_dev.get(dev, 0) + s["value"]
        check(len(devs) == n and set(per_dev) == devs,
              f"[{exchange}] supersteps attributed to {sorted(devs)} / "
              f"{sorted(per_dev)}, not to {n} distinct devices")
        log(f"[{exchange}] plan_traces {traces} -> {after}; supersteps per "
            f"device {per_dev}")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            check(stats["peak_bytes_in_use"] > 0, f"{d} held no buffers")
        log(f"{d}: peak_bytes_in_use {peak_bytes(d)}")


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--scale", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bfs", type=int, default=64,
                    help="BFS queries per phase")
    ap.add_argument("--sssp", type=int, default=16,
                    help="SSSP queries per phase (one chip)")
    ap.add_argument("--batch", type=int, default=8,
                    help="lanes per exchange on the four-chip mesh")
    ap.add_argument("--query-timeout", type=float, default=600.0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    cache = place_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import graph as G

    log(f"device_kind {devices[0].device_kind!r}, platform {platform}, "
        f"{len(devices)} devices; jax {jax.__version__}; compile cache "
        f"{cache}")
    t0 = time.perf_counter()
    g = G.rmat(args.scale, 16, seed=args.seed, weighted=True).symmetrized()
    rng = np.random.default_rng(args.seed)
    roots = rng.choice(np.flatnonzero(g.out_degrees() > 0), args.bfs,
                       replace=False)
    ref = HostReference(g)
    log(f"graph rmat({args.scale}, 16, seed={args.seed}, weighted=True)"
        f".symmetrized(): {g.num_vertices} vertices, {g.num_edges} edges; "
        f"host build {time.perf_counter() - t0:.3f} s")

    t = time.perf_counter()
    if args.chips == 1:
        one_chip(g, ref, roots, roots[:args.sssp], args, platform)
    else:
        mesh(g, ref, roots, args)
    log(f"phases done in {time.perf_counter() - t:.3f} s; total "
        f"{time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
