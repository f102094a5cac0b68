#!/usr/bin/env python3
"""The lower-precision control: the plain reference's SSSP, Bellman-Ford
in bfloat16 (the precision below the float32 that the configurations
state), put in the program's place and judged by the same comparison.
It has to come out not correct; PERF.md gives its readings beside the
program's, and each limit lies between them.

    python3 bench/control.py --workload g500-s18.mix --seeds 11 12 13

For each seed it builds the cell's graph, takes the first ``--roots``
roots the cell's clients would send, answers them with the control on
the default device, and prints the compared numbers as one JSON line.
``--dtype float32`` runs the same Bellman-Ford at the stated precision,
as a witness that the comparison passes a sound answer.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent.parent


@partial(jax.jit, static_argnames=("num_vertices",))
def _bellman_ford(src, dst, w, root, *, num_vertices):
    inf = jnp.array(jnp.inf, w.dtype)
    d0 = jnp.full((num_vertices,), inf).at[root].set(0)

    def relax(c):
        d, _, n = c
        best = jax.ops.segment_min(d[src] + w, dst,
                                   num_segments=num_vertices)
        nd = jnp.minimum(d, best)
        return nd, jnp.any(nd != d), n + 1

    d, _, _ = jax.lax.while_loop(lambda c: c[1] & (c[2] < num_vertices),
                                 relax, (d0, jnp.array(True), 0))
    big = jnp.iinfo(jnp.int32).max
    cand = jnp.where(d[src] + w == d[dst], src, big)
    par = jax.ops.segment_min(cand, dst, num_segments=num_vertices)
    par = jnp.where(jnp.isfinite(d) & (par < big), par, -1).at[root].set(root)
    return d.astype(jnp.float32), par


def sssp(graph, roots, dtype="bfloat16"):
    """Control answers ``{"dist", "parent"}`` for each root, computed in
    ``dtype`` on the default device."""
    src, dst, w = graph.directed()
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    w = jnp.asarray(w).astype(dtype)
    out = []
    for r in roots:
        d, p = _bellman_ford(src, dst, w, jnp.int32(r),
                             num_vertices=graph.num_vertices)
        out.append({"dist": np.asarray(d), "parent": np.asarray(p)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--roots", type=int, default=4)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.check import compare
    from bench.reference import HostReference
    cell = harness.load_cell(ROOT, args.workload)
    harness.open_devices(cell.chips)
    harness.place_compile_cache(ROOT)
    for seed in args.seeds:
        t = time.perf_counter()
        graph = harness.generate_graph(cell, seed)
        roots = [int(r) for r in harness.draw_roots(
            graph, int(cell.config["structure_seed"]), seed,
            harness.batch_size(cell))[:args.roots]]
        answers = sssp(graph, roots, args.dtype)
        t_ctl = time.perf_counter() - t
        correct, numbers = compare(
            HostReference(graph),
            [("sssp", r, a) for r, a in zip(roots, answers)],
            cell.config.get("limits", {}), missing=0)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "dtype": args.dtype, "roots": roots,
                          "correct": correct, "control_s": t_ctl,
                          "checks": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
