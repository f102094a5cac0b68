"""The GraVF-M three-stage programming model (paper §3).

A graph algorithm is a :class:`GasKernel` — three small pure functions with
fixed interfaces, the JAX counterpart of the paper's three Verilog modules:

  gather  : called (logically once per message) to fold messages into
            vertex state. As in all high-throughput vertex-centric systems
            the fold must be a commutative monoid, so the engine
            pre-aggregates messages per destination with ``combiner`` and
            calls ``gather`` once per vertex with the combined value.
  apply   : called once per vertex at the end of a superstep; reads the
            final state and may issue ONE update (payload + active flag).
            This ≤1-update-per-vertex bound is what makes the GraVF-M
            broadcast optimization legal (paper §4.1).
  scatter : called once per (update, out-edge) to finalize the message.
            In GraVF-M the engine runs it at the RECEIVER, on demand.

A kernel may add a fourth, ``finalize``: one pass over the in-edges after
the superstep loop has stopped, inside the same compiled program, that
derives what the loop need not carry every superstep (SSSP's parent
pointers). It sees the edges through an :class:`EdgeView`, which each
engine implements once over its own edge layout.

SSSP's finalize picks, for each reached vertex v, among the in-neighbours
u with fl(dist[u] + w_uv) <= dist[v], the smallest id with dist[u] <
dist[v]; where none exists, the one with dist[u] == dist[v] and the
smallest t[u] (the superstep in which dist[u] last fell; -1 at the
root), then the smallest id. The parents form a tree rooted at the root
because (dist, t) strictly falls along every parent edge: the sender
whose message last lowered dist[v] is either strictly shorter by now,
or unchanged since it sent and so lowered in an earlier superstep.

All functions are elementwise jnp code, vectorized by the engine over
vertices/edges — the analogue of the paper's per-cycle hardware pipelines.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Protocol

import jax.numpy as jnp
import numpy as np

__all__ = ["GasKernel", "EdgeView", "COMBINER_IDENTITY",
           "segment_combine_ref"]

State = Any  # pytree of (num_vertices,) arrays


def _id_for(combiner: str, dtype) -> Any:
    dt = jnp.dtype(dtype)
    if combiner == "add":
        return np.zeros((), dt)
    if combiner == "min":
        if jnp.issubdtype(dt, jnp.floating):
            return np.array(np.inf, dt)
        return np.array(jnp.iinfo(dt).max, dt)
    if combiner == "max":
        if jnp.issubdtype(dt, jnp.floating):
            return np.array(-np.inf, dt)
        return np.array(jnp.iinfo(dt).min, dt)
    raise ValueError(f"unknown combiner {combiner}")


COMBINER_IDENTITY = _id_for


class EdgeView(Protocol):
    """The in-edges of the vertices a program owns, one lane per edge,
    for a kernel's ``finalize``. Per-vertex arrays have the shape of the
    state's leaves, which lead with a query axis; per-lane arrays lead
    with the same query axis, except the static edge attributes below.
    Padding lanes are False in ``valid``."""

    w: jnp.ndarray           # per-lane edge weight
    src_gid: jnp.ndarray     # per-lane global id of the source
    src_outdeg: jnp.ndarray  # per-lane out-degree of the source
    valid: jnp.ndarray       # per-lane: a real edge

    def src(self, key: str) -> jnp.ndarray:
        """State leaf ``key`` at each lane's source vertex (across
        shards: the view exchanges that leaf)."""

    def dst(self, x: jnp.ndarray) -> jnp.ndarray:
        """Per-vertex ``x`` at each lane's destination vertex."""

    def min(self, vals: jnp.ndarray) -> jnp.ndarray:
        """Per-vertex minimum of per-lane ``vals`` over its in-edges
        (the dtype's maximum where it has none)."""

    def any(self, pred: jnp.ndarray) -> jnp.ndarray:
        """Scalar: ``pred`` holds for some vertex of some query, on any
        shard. Decides a ``lax.cond`` outside the query vmap, whose
        branches may use every method here but ``any``: every shard
        takes the same branch."""

    def count(self, mask: jnp.ndarray) -> jnp.ndarray:
        """(queries,) int32: True vertices of ``mask`` per query."""


@dataclasses.dataclass(frozen=True)
class GasKernel:
    """A user graph algorithm.

    Shapes (engine-side, per shard):
      init_state(vert_gid, out_deg, valid, **params)      -> state pytree
      apply(state, vert_gid, out_deg, superstep)           -> (state, payload, active)
      scatter(payload, weight, src_gid, src_outdeg)        -> message value
      gather(state, combined_msg, got_msg, superstep)      -> state
      finalize(state, vert_gid, view)                      -> (state, counters)

    ``combiner`` ∈ {"min", "max", "add"} pre-aggregates messages per
    destination vertex; ``msg_dtype`` is the message value dtype;
    ``update_dtype`` the update payload dtype (usually identical — the
    paper's m_update/m_message ratio, which enters the §5 model).
    """

    name: str
    init_state: Callable[..., State]
    apply: Callable[..., Any]
    scatter: Callable[..., jnp.ndarray]
    gather: Callable[..., State]
    combiner: str
    msg_dtype: Any
    update_dtype: Any = None
    max_supersteps: int = 0  # 0 = until quiescence
    # Bit widths for the §5 performance model (paper's m_update/m_message).
    update_bits: int = 32
    message_bits: int = 32
    # got = (combined != identity) is exact for this kernel (saves a
    # reduction pass). All built-ins qualify; see engine._deliver_*.
    got_from_identity: bool = True
    # Optional pass over the in-edges (an ``EdgeView``) once the loop has
    # stopped, for every query of a program at once: the state leaves
    # lead with the query axis. Returns the final state and a dict of
    # per-query int32 counters, which join the program's stats. It runs
    # under the ``gravfm.finalize`` device scope; a kernel without it
    # compiles to programs with no such pass.
    finalize: Optional[Callable[..., Any]] = None
    # Names of ``init_state`` keyword parameters that are *per-query* and
    # traceable (accepted as JAX scalars, e.g. BFS/SSSP ``root``). The
    # engine's ``run_batch`` maps these over a leading query-batch axis and
    # the query service uses them to validate batching compatibility.
    # Kernels with an empty tuple (WCC, PageRank) answer one global
    # question, so batching them only duplicates work.
    query_params: tuple = ()

    @property
    def identity(self):
        return _id_for(self.combiner, self.msg_dtype)

    def describe(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "combiner": self.combiner,
            "msg_dtype": str(jnp.dtype(self.msg_dtype)),
            "max_supersteps": self.max_supersteps,
            "update_bits": self.update_bits,
            "message_bits": self.message_bits,
            "query_params": list(self.query_params),
        }


def segment_combine_ref(vals, seg_ids, num_segments: int, combiner: str):
    """Pure-jnp oracle for per-destination message aggregation (the fused
    receiver-side scatter+gather hot loop). ``seg_ids`` may contain
    ``num_segments`` for padding lanes (routed to a discard bin)."""
    import jax

    n = num_segments + 1  # one discard bin for padding
    if combiner == "add":
        out = jax.ops.segment_sum(vals, seg_ids, num_segments=n)
    elif combiner == "min":
        out = jax.ops.segment_min(vals, seg_ids, num_segments=n)
    elif combiner == "max":
        out = jax.ops.segment_max(vals, seg_ids, num_segments=n)
    else:
        raise ValueError(combiner)
    # segment_min/max produce the dtype identity for empty bins already;
    # slice off the discard bin.
    return out[:num_segments]
