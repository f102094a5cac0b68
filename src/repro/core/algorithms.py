"""The paper's benchmark algorithms (BFS, WCC, PageRank) plus SSSP and
degree centrality, written as GraVF-M kernels.

Each is a handful of elementwise-jnp lines — the direct counterpart of the
paper's ~30-line Verilog kernels (§3 WCC listing). State is a dict of
per-vertex arrays; the ``active`` convention mirrors the paper: gather sets
an ``active`` bit in state, apply reads and clears it and issues the update.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .gas import GasKernel

__all__ = ["bfs", "wcc", "pagerank", "sssp", "degree_centrality", "ALGORITHMS"]

INT_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# WCC — the paper's worked example (§3). Propagate the lowest vertex id.
# ---------------------------------------------------------------------------

def wcc() -> GasKernel:
    def init_state(vert_gid, out_deg, valid, **_):
        gid = jnp.where(valid, vert_gid, INT_MAX)
        return {"label": gid.astype(jnp.int32),
                "active": valid}  # every vertex broadcasts its id first

    def apply(state, vert_gid, out_deg, superstep):
        payload = state["label"]
        active = state["active"]
        new_state = {"label": state["label"],
                     "active": jnp.zeros_like(active)}
        return new_state, payload, active

    def scatter(payload, weight, src_gid, src_outdeg):
        return payload  # forward the label as-is (paper Listing 3)

    def gather(state, combined, got, superstep):
        # paper Listing 1: keep the smaller label, mark active on change.
        new_label = got & (combined < state["label"])
        return {
            "label": jnp.where(new_label, combined, state["label"]),
            "active": state["active"] | new_label,
        }

    return GasKernel(
        name="wcc", init_state=init_state, apply=apply, scatter=scatter,
        gather=gather, combiner="min", msg_dtype=jnp.int32,
        update_bits=32, message_bits=32)


# ---------------------------------------------------------------------------
# BFS — parent-pointer spanning tree (graph500 flavour, paper §6.2).
# ---------------------------------------------------------------------------

def bfs(root: int = 0) -> GasKernel:
    # ``root`` is a *query parameter*: init_state accepts it as a traced
    # scalar (overridable per call / per batch lane), with the factory
    # argument as the default — so `bfs(7)` and `bfs().init_state(...,
    # root=7)` agree and the engine can vmap a batch of roots through one
    # superstep loop without re-tracing.
    def init_state(vert_gid, out_deg, valid, *, root=root, **_):
        root = jnp.asarray(root, jnp.int32)
        is_root = vert_gid == root
        return {
            "parent": jnp.where(is_root, root, -1).astype(jnp.int32),
            "active": is_root & valid,
        }

    def apply(state, vert_gid, out_deg, superstep):
        payload = vert_gid.astype(jnp.int32)  # "I am your parent"
        active = state["active"]
        return ({"parent": state["parent"],
                 "active": jnp.zeros_like(active)}, payload, active)

    def scatter(payload, weight, src_gid, src_outdeg):
        return payload

    def gather(state, combined, got, superstep):
        newly = got & (state["parent"] < 0)
        return {
            "parent": jnp.where(newly, combined, state["parent"]),
            "active": state["active"] | newly,
        }

    return GasKernel(
        name="bfs", init_state=init_state, apply=apply, scatter=scatter,
        gather=gather, combiner="min", msg_dtype=jnp.int32,
        update_bits=32, message_bits=32, query_params=("root",))


# ---------------------------------------------------------------------------
# PageRank — Pregel-style fixed 30 supersteps (paper §6.2).
# ---------------------------------------------------------------------------

def pagerank(num_supersteps: int = 30, damping: float = 0.85) -> GasKernel:
    def init_state(vert_gid, out_deg, valid, *, num_vertices, **_):
        base = jnp.where(valid, 1.0 / num_vertices, 0.0).astype(jnp.float32)
        return {"score": base, "num_vertices": jnp.float32(num_vertices)}

    def apply(state, vert_gid, out_deg, superstep):
        # contribution = score / out_degree, divided at the sender (Pregel).
        payload = state["score"] / jnp.maximum(out_deg, 1).astype(jnp.float32)
        active = jnp.full(vert_gid.shape, superstep < num_supersteps)
        return state, payload, active

    def scatter(payload, weight, src_gid, src_outdeg):
        return payload

    def gather(state, combined, got, superstep):
        n = state["num_vertices"]
        acc = jnp.where(got, combined, 0.0)
        score = (1.0 - damping) / n + damping * acc
        return {"score": score.astype(jnp.float32), "num_vertices": n}

    return GasKernel(
        name="pagerank", init_state=init_state, apply=apply, scatter=scatter,
        gather=gather, combiner="add", msg_dtype=jnp.float32,
        max_supersteps=num_supersteps, update_bits=32, message_bits=32)


# ---------------------------------------------------------------------------
# SSSP — beyond-paper. Message key = candidate distance (min-combined).
# The loop carries distances only; the parent pointers are resolved once,
# from the final distances, by ``finalize``.
# ---------------------------------------------------------------------------

def sssp(root: int = 0) -> GasKernel:
    def init_state(vert_gid, out_deg, valid, *, root=root, **_):
        root = jnp.asarray(root, jnp.int32)
        is_root = vert_gid == root
        dist = jnp.where(is_root, 0.0, jnp.inf).astype(jnp.float32)
        return {
            "dist": dist,
            # superstep whose gather last lowered dist; -1: never (the
            # root, and every vertex not reached)
            "t": jnp.full(vert_gid.shape, -1, jnp.int32),
            "active": is_root & valid,
        }

    def apply(state, vert_gid, out_deg, superstep):
        payload = state["dist"]
        active = state["active"]
        st = dict(state)
        st["active"] = jnp.zeros_like(active)
        return st, payload, active

    def scatter(payload, weight, src_gid, src_outdeg):
        return payload + weight

    def gather(state, combined, got, superstep):
        better = got & (combined < state["dist"])
        return {
            "dist": jnp.where(better, combined, state["dist"]),
            "t": jnp.where(better, superstep, state["t"]),
            "active": state["active"] | better,
        }

    def finalize(state, vert_gid, view):
        """Parent pointers from the final ``dist`` and ``t``.

        For a reached vertex v other than the root, its candidates are
        the in-neighbours u with fl(dist[u] + w_uv) <= dist[v] (at a
        fixed point ``<=`` is ``==``; a query stopped by the superstep
        cap still gets a neighbour). parent[v] is the smallest candidate
        id with dist[u] < dist[v]; where there is none (zero weights, or
        weights too small to change a float32 sum), it is, among the
        candidates with dist[u] == dist[v], the one with the smallest
        t[u], then the smallest id. The root is its own parent; an
        unreached vertex has -1.

        A parent always exists, and the parent graph is a tree: (dist,
        t) strictly decreases along every parent edge. Take the sender
        u* whose message last lowered dist[v], in superstep t[v], with
        the distance d it held when it sent (weights are >= 0, so d <=
        dist[v]). If u* improved again, dist[u*] < d <= dist[v] and
        fl(dist[u*] + w) <= fl(d + w) = dist[v]: a strictly shorter
        candidate. Otherwise dist[u*] = d and u* was lowered (or is the
        root, t = -1) before it sent: t[u*] < t[v], so the fallback's
        smallest t is below t[v].

        The fallback's two segment-mins run behind one ``lax.cond`` for
        the whole batch, only when some vertex needs them.
        """
        dist, t = state["dist"], state["t"]

        def candidates():
            # recomputed inside the tie-break rather than held across
            # it: on the chip each (queries, lanes) array is gigabytes
            du, dv = view.src("dist"), view.dst(dist)
            msg = scatter(du, view.w, view.src_gid, view.src_outdeg)
            return du, dv, view.valid & (msg <= dv)

        du, dv, on_path = candidates()
        parent = view.min(jnp.where(on_path & (du < dv), view.src_gid,
                                    INT_MAX))
        reached = dist < jnp.inf
        root = reached & (t < 0)
        need = reached & ~root & (parent == INT_MAX)

        def tie_break(parent):
            du, dv, on_path = candidates()
            tu = view.src("t")
            tied = on_path & (du == dv) & view.dst(need)
            first_t = view.min(jnp.where(tied, tu, INT_MAX))
            first = tied & (tu == view.dst(first_t))
            return jnp.where(need, view.min(
                jnp.where(first, view.src_gid, INT_MAX)), parent)

        parent = jax.lax.cond(view.any(need), tie_break, lambda p: p,
                              parent)
        parent = jnp.where(root, vert_gid, jnp.where(reached, parent, -1))
        return ({**state, "parent": parent.astype(jnp.int32)},
                {"parent_fallback": view.count(need)})

    return GasKernel(
        name="sssp", init_state=init_state, apply=apply, scatter=scatter,
        gather=gather, combiner="min", msg_dtype=jnp.float32,
        finalize=finalize, update_bits=32, message_bits=32,
        query_params=("root",))


# ---------------------------------------------------------------------------
# Degree centrality — single-superstep sanity workload.
# ---------------------------------------------------------------------------

def degree_centrality() -> GasKernel:
    def init_state(vert_gid, out_deg, valid, **_):
        return {"indeg": jnp.zeros(vert_gid.shape, jnp.float32),
                "done": jnp.zeros(vert_gid.shape, bool)}

    def apply(state, vert_gid, out_deg, superstep):
        active = (superstep == 0) & jnp.ones(vert_gid.shape, bool)
        return state, jnp.ones(vert_gid.shape, jnp.float32), active

    def scatter(payload, weight, src_gid, src_outdeg):
        return payload

    def gather(state, combined, got, superstep):
        return {"indeg": jnp.where(got, combined, state["indeg"]),
                "done": state["done"] | got}

    return GasKernel(
        name="degree", init_state=init_state, apply=apply, scatter=scatter,
        gather=gather, combiner="add", msg_dtype=jnp.float32,
        max_supersteps=1, update_bits=32, message_bits=32)



ALGORITHMS = {
    "bfs": bfs,
    "wcc": wcc,
    "pagerank": pagerank,
    "sssp": sssp,
    "degree": degree_centrality,
}
