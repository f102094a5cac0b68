"""Road-network stand-in: a ``side`` x ``side`` grid of intersections
whose roads (each undirected edge to the right and downward neighbour)
are kept with probability ``keep``, with weights uniform in
``[w_lo, w_hi)``. Labels are row-major. At ``keep`` 0.7 the average
degree is about 2.8, as in the DIMACS USA road graphs, and a shortest
path crosses hundreds of intersections.

The roads and weights come from the configuration's ``structure_seed``,
so every run serves the same network, as a road service does; the
run's seed only orders the roots its clients send.
"""
from __future__ import annotations

import numpy as np

from bench.graph import BenchGraph


def generate(cfg: dict, seed: int) -> BenchGraph:
    del seed    # the network is the configuration's, not the run's
    side = int(cfg["side"])
    rng = np.random.default_rng([int(cfg["structure_seed"]), 0x70AD])
    v = np.arange(side * side, dtype=np.int32).reshape(side, side)
    lo = np.concatenate([v[:, :-1].ravel(), v[:-1, :].ravel()])
    hi = np.concatenate([v[:, 1:].ravel(), v[1:, :].ravel()])
    keep = rng.random(lo.shape[0]) < float(cfg["keep"])
    w_lo, w_hi = (float(x) for x in cfg["weights"])
    w = rng.uniform(w_lo, w_hi, lo.shape[0]).astype(np.float32)
    return BenchGraph(side * side, lo[keep], hi[keep], w[keep])
