"""Graph500 Kronecker graph (spec v3, section 3), made on the device.

Each of ``edgefactor * 2**scale`` edges picks, at each of ``scale``
levels, one quadrant of the adjacency matrix with probabilities A, B,
C and D = 1 - A - B - C, as the spec's reference generator does: the
source bit is 1 with probability C + D, and the destination bit is 1
with probability D / (C + D) after a source 1 and B / (A + B) after a
source 0. Vertex labels are then permuted at random, and each edge gets
a weight uniform in ``[w_lo, w_hi)``.

The edges, the label permutation and the weights all come from the
configuration's ``structure_seed``: every run serves the same graph, as
a deployment does, and the run's seed only orders the roots its clients
send. The service compiles the graph into its programs, so a graph
that moved with the seed would also recompile them on every run.

The service's ``Graph`` is symmetric without self-loops or duplicates,
so edges are made undirected (``lo < hi``), self-loops are dropped and
of duplicates the first keeps its weight. The draw, the relabelling and
the duplicate sort run in one jitted call from the seed; the host only
compacts the result.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.graph import BenchGraph


@partial(jax.jit, static_argnames=("scale", "edgefactor", "abc", "w_range"))
def _draw(key, k_perm, *, scale, edgefactor, abc, w_range):
    a, b, c = abc
    d = 1.0 - a - b - c
    n, m = 1 << scale, edgefactor << scale

    def threshold(p):   # P(bits < t) = p for uniform 32-bit bits
        return jnp.uint32(min(int(p * 2.0 ** 32), 2 ** 32 - 1))

    t_src = threshold(c + d)
    t_dst1, t_dst0 = threshold(d / (c + d)), threshold(b / (a + b))
    k_bits, k_w = jax.random.split(key)

    def level(i, carry):
        src, dst = carry
        bits = jax.random.bits(jax.random.fold_in(k_bits, i), (2, m),
                               jnp.uint32)
        sbit = bits[0] < t_src
        dbit = bits[1] < jnp.where(sbit, t_dst1, t_dst0)
        return (src * 2 + sbit.astype(jnp.int32),
                dst * 2 + dbit.astype(jnp.int32))

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    w = jax.random.uniform(k_w, (m,), jnp.float32, *w_range)
    lo, hi = jnp.minimum(src, dst), jnp.maximum(src, dst)
    lo = jnp.where(lo == hi, n, lo)          # self-loops sort last
    lo, hi, w = jax.lax.sort((lo, hi, w), num_keys=2, is_stable=True)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    return lo, hi, w, first & (lo < n)


def generate(cfg: dict, seed: int) -> BenchGraph:
    def key(s, tag):
        rng = np.random.default_rng([s, tag])
        return jax.random.key(int(rng.integers(0, 2 ** 31)))

    del seed    # the graph is the configuration's, not the run's
    lo, hi, w, keep = jax.device_get(_draw(
        key(int(cfg["structure_seed"]), 0x6500),
        key(int(cfg["structure_seed"]), 0x6501), scale=int(cfg["scale"]),
        edgefactor=int(cfg["edgefactor"]),
        abc=(float(cfg["A"]), float(cfg["B"]), float(cfg["C"])),
        w_range=tuple(float(x) for x in cfg["weights"])))
    return BenchGraph(1 << int(cfg["scale"]), lo[keep], hi[keep], w[keep])
