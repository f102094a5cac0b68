"""Explicit-collective (shard_map) variant of the GraVF-M engine.

The global-array engine in ``engine.py`` relies on XLA SPMD to infer the
collectives. This variant drives them explicitly, which is where the
paper's architectural ideas become *schedulable*:

  exchange="allgather"  — paper-faithful GraVF-M: one all_gather of the
      per-shard update arrays per superstep (the broadcast of §4.1), then
      receiver-side scatter+gather over the local dst-partitioned edges.

  exchange="ring"       — the floating-barrier analogue (§4.3): the
      broadcast is decomposed into P-1 ``ppermute`` hops around the mesh
      ring. Each arriving chunk is scattered/gathered IMMEDIATELY while
      the next hop is in flight, so transport overlaps compute and no
      shard waits for a full-system barrier — different shards are
      working on different "parts" of the superstep at any instant,
      exactly the paper's floating barrier invariant (all messages of a
      superstep are still folded before apply runs).

  exchange="frontier"   — beyond-paper: the §4.3 neighbor-filter idea
      taken further. Instead of the dense |V|/P update array, each shard
      compacts its ACTIVE updates into a capacity-bounded (id, payload)
      buffer; a one-scalar psum picks the smallest sufficient capacity
      bucket per superstep (lax.switch over precompiled sizes) and only
      that buffer is broadcast. Traffic tracks the live frontier the way
      BFS/WCC actually behave, not |V|.

  mode="gravf"          — baseline unicast: per-destination-shard message
      blocks exchanged with one ``all_to_all`` per superstep (Fig. 4
      left), gather at the receiver.

  exchange="combined"   — the paper's headline degree-factor trick:
      per-edge messages are segment-reduced AT THE SOURCE by
      (destination shard, destination vertex) — the Pallas windowed
      segment-combine over a dst-sorted per-pair layout — and the
      ``all_to_all`` then ships ONE (id, payload) entry per remote
      destination vertex instead of one per edge. The receiver folds the
      pre-combined partials into its accumulator with the same monoid,
      so wire words drop by roughly the average degree (perfmodel's
      ``words_per_superstep`` predicts the exact padded-layout cost).

All exchanges produce bit-identical states to ``engine.py`` (tested in a
multi-device subprocess; see tests/test_engine_shardmap.py).

Every exchange additionally has an **overlapped** (pipelined) schedule,
selected per stepper/run with ``overlap=True``: the superstep is split
into partition windows and the collective for window ``k+1`` is issued
*before* the scatter/combine of window ``k`` runs, double-buffering the
in-flight receive block inside the shard_map body (the window index is a
``lax.fori_loop`` carry, never a Python int — see analysis rule RTR005).
Concretely:

  allgather/frontier — the one-shot ``all_gather`` is decomposed into P
      ``ppermute`` hops accumulating into the same flat receive array the
      gather would have produced; each arriving chunk is placed while the
      next hop is already in flight, then ONE receiver-side consume runs
      (bit-identical by construction: the flat array equals the gathered
      one, and the reported wire words are unchanged).
  ring — the hop for chunk ``k+1`` is issued before chunk ``k``'s bucket
      consume instead of after it; consume/merge order is unchanged.
  unicast/combined — the ``all_to_all`` payload is chunked into column
      windows folded one behind the collective; per-window partials merge
      with the combiner (exact for min/max combiners, the same
      construction the ring/unicast equality test already proves).
      Kernels with ``got_from_identity`` skip the
      activity (and sync-combined's per-slot got) streams entirely —
      activity is recovered as ``recv != identity`` — so the overlapped
      wire carries fewer collective launches than the synchronous one
      while reporting the same words (the bytes the serial schedule
      would move; stats stay comparable across schedules).

Both schedules are traced once per (width, overlap) at warm; toggling
``overlap`` per request re-traces nothing.

A kernel's ``finalize`` (SSSP's parent pointers) runs once after the
loop, whatever the exchange: an ``all_gather`` of each state leaf it
reads at the sources, when it reads it, and a local pass over each
shard's CSC in-edge lanes (``_edge_view``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, NamedTuple, Optional

import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..kernels import ops as kops
from ..kernels import ref as kref
from .engine import named, span
from .gas import GasKernel
from .partition import PartitionedGraph
from .stepper import (LaneStepperBase, LaneView, StepCarry,
                      SuperstepProgram, select_lanes)

__all__ = ["ShardEngine", "ShardLaneStepper", "build_shard_data",
           "ShardData"]

AXIS = "graph"

def _exchange_op(op):
    """``op``, a ``jax.lax`` collective, under the ``gravfm.exchange``
    device scope: the exchange's device time is then named apart from
    the edge pass (``gravfm.deliver``) it is interleaved with."""
    def f(*args, **kwargs):
        with jax.named_scope("gravfm.exchange"):
            return op(*args, **kwargs)
    return f


# the collectives every exchange runs on, each under gravfm.exchange
xc = types.SimpleNamespace(**{
    name: _exchange_op(getattr(jax.lax, name))
    for name in ("all_gather", "all_to_all", "ppermute", "pmax")})


def _count(mask):
    """The messages a mask carries, for the stats: counter work, under
    the ``gravfm.stats`` scope inside the edge pass."""
    with jax.named_scope("gravfm.stats"):
        return jnp.sum(mask.astype(jnp.int32))


def _counters(stats):
    """The kernel finalize's counters among a program's stats: every
    entry but the loop's own ``messages`` and ``words``."""
    return {k: v for k, v in stats.items() if k not in ("messages", "words")}


def _psum_counters(stats):
    """:func:`_counters` summed over the shards (inside shard_map)."""
    return {k: jax.lax.psum(v, AXIS) for k, v in _counters(stats).items()}


def _shard_map(f, *, mesh, in_specs, out_specs):
    # invoked only from _build-time factories
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,  # analysis: allow(RTR002)
                         out_specs=out_specs, check_vma=False)


class ShardData(NamedTuple):
    """All arrays carry a leading shard axis sharded over mesh axis
    ``graph``; inside shard_map each block is one shard's data."""
    vert_gid: jnp.ndarray       # (P, Vm)
    vert_valid: jnp.ndarray     # (P, Vm)
    out_deg: jnp.ndarray        # (P, Vm)
    flt_cnt: jnp.ndarray        # (P, Vm)
    # CSC lanes in Pallas layout (allgather/frontier paths)
    wid: jnp.ndarray            # (P, n_tiles)
    rel: jnp.ndarray            # (P, L)
    window_written: jnp.ndarray  # (P, n_windows)
    src_slot: jnp.ndarray       # (P, L) global slot = part*Vm + local
    src_gid: jnp.ndarray        # (P, L)
    src_outdeg: jnp.ndarray     # (P, L)
    w: jnp.ndarray              # (P, L)
    lane_valid: jnp.ndarray     # (P, L)
    seg: jnp.ndarray            # (P, L) local segment (dst_local; pad Vm)
    # ring buckets: in-edges grouped by SOURCE shard (transposed pair layout)
    rb_src_local: jnp.ndarray   # (P, P, E2)
    rb_src_gid: jnp.ndarray
    rb_src_outdeg: jnp.ndarray
    rb_w: jnp.ndarray
    rb_dst_local: jnp.ndarray
    rb_valid: jnp.ndarray
    # gravf unicast blocks (source-side layout)
    pair_src_local: jnp.ndarray  # (P, P, E2)
    pair_src_gid: jnp.ndarray
    pair_src_outdeg: jnp.ndarray
    pair_w: jnp.ndarray
    pair_valid: jnp.ndarray
    recv_dst_local: jnp.ndarray  # (P, P, E2)
    # combined exchange: source-side dst-sorted edge lanes (Pallas layout
    # over flat (dest shard, dst rank) segments) + the static per-(peer,
    # rank) receive ids — the wire never carries ids at runtime
    comb_wid: jnp.ndarray = None        # (P, comb_tiles)
    comb_rel: jnp.ndarray = None        # (P, CL)
    comb_written: jnp.ndarray = None    # (P, comb_windows)
    comb_src_local: jnp.ndarray = None  # (P, CL)
    comb_src_gid: jnp.ndarray = None    # (P, CL)
    comb_src_outdeg: jnp.ndarray = None  # (P, CL)
    comb_w: jnp.ndarray = None          # (P, CL)
    comb_valid: jnp.ndarray = None      # (P, CL)
    comb_seg: jnp.ndarray = None        # (P, CL) flat q*(R+1)+rank; pad Sc
    comb_recv_dst_local: jnp.ndarray = None  # (P, P, comb_max)


@dataclasses.dataclass(frozen=True)
class ShardMeta:
    P: int
    v_max: int
    e_pair_max: int
    n_tiles: int
    n_windows: int
    tile_e: int
    tile_r: int
    num_vertices: int
    frontier_capacities: tuple = ()
    comb_max: int = 0        # padded distinct remote dsts per shard pair
    comb_tiles: int = 0
    comb_windows: int = 0


def _build_shard_layouts(pg: PartitionedGraph, tile_e: int, tile_r: int):
    """Per-shard Pallas layouts padded to a common tile count (SPMD)."""
    P, Vm = pg.num_parts, pg.v_max
    S = Vm + 1
    layouts = []
    for p in range(P):
        seg = pg.in_dst_local[p].astype(np.int64)
        # sorted within shard by construction
        layouts.append(kops.build_layout(seg, S, tile_e=tile_e,
                                         tile_r=tile_r))
    n_tiles = max(l.n_tiles for l in layouts)
    n_windows = layouts[0].n_windows
    L = n_tiles * tile_e

    wid = np.zeros((P, n_tiles), np.int32)
    rel = np.full((P, L), tile_r, np.int32)
    written = np.zeros((P, n_windows), bool)
    src_slot = np.zeros((P, L), np.int32)
    src_gid = np.zeros((P, L), np.int32)
    src_outdeg = np.ones((P, L), np.int32)
    w = np.zeros((P, L), np.float32)
    lane_valid = np.zeros((P, L), bool)
    seg_l = np.full((P, L), Vm, np.int32)

    for p, lo in enumerate(layouts):
        nt, ll = lo.n_tiles, lo.num_lanes
        wid[p, :nt] = lo.window_id
        # pad tiles continue accumulating (identity) into the last window
        wid[p, nt:] = lo.window_id[-1] if nt else 0
        rel[p, :ll] = lo.rel
        written[p] = lo.window_written
        ev = pg.in_valid[p]
        src_slot[p, :ll] = lo.place(pg.in_src_slot[p], 0)
        src_gid[p, :ll] = lo.place(pg.in_src_gid[p], 0)
        src_outdeg[p, :ll] = lo.place(pg.in_src_outdeg[p], 1)
        w[p, :ll] = lo.place(pg.in_w[p], 0.0)
        lane_valid[p, :ll] = lo.place(ev, False) & lo.lane_valid
        seg_l[p, :ll] = lo.place(pg.in_dst_local[p], Vm)

    return (dict(wid=wid, rel=rel, window_written=written,
                 src_slot=src_slot, src_gid=src_gid, src_outdeg=src_outdeg,
                 w=w, lane_valid=lane_valid, seg=seg_l),
            n_tiles, n_windows)


def _build_combined_layouts(pg: PartitionedGraph, tile_e: int, tile_r: int):
    """Source-side layout for the combined exchange: each shard's edges,
    dst-sorted within each destination-shard bucket, as a Pallas windowed
    layout over the flat segment id ``q*(R+1) + dst_rank`` (the bucket's
    discard bin is rank R, so the flat ids stay globally sorted). The
    segment-combine over this layout yields the per-(peer, rank) partials
    that go on the wire — one slot per distinct remote destination."""
    cb = pg.combined_buckets()
    P, Vm = pg.num_parts, pg.v_max
    R = cb["comb_max"]
    Sc = P * (R + 1)
    seg_all = (np.arange(P, dtype=np.int64)[None, :, None] * (R + 1)
               + cb["dst_rank"].astype(np.int64))      # (P, P, E2)
    layouts = [kops.build_layout(seg_all[p].reshape(-1), Sc,
                                 tile_e=tile_e, tile_r=tile_r)
               for p in range(P)]
    n_tiles = max(l.n_tiles for l in layouts)
    n_windows = layouts[0].n_windows
    L = n_tiles * tile_e

    wid = np.zeros((P, n_tiles), np.int32)
    rel = np.full((P, L), tile_r, np.int32)
    written = np.zeros((P, n_windows), bool)
    src_local = np.zeros((P, L), np.int32)
    src_gid = np.zeros((P, L), np.int32)
    src_outdeg = np.ones((P, L), np.int32)
    w = np.zeros((P, L), np.float32)
    valid = np.zeros((P, L), bool)
    seg_l = np.full((P, L), Sc, np.int32)

    for p, lo in enumerate(layouts):
        nt, ll = lo.n_tiles, lo.num_lanes
        wid[p, :nt] = lo.window_id
        wid[p, nt:] = lo.window_id[-1] if nt else 0
        rel[p, :ll] = lo.rel
        written[p] = lo.window_written
        src_local[p, :ll] = lo.place(cb["src_local"][p].reshape(-1), 0)
        src_gid[p, :ll] = lo.place(cb["src_gid"][p].reshape(-1), 0)
        src_outdeg[p, :ll] = lo.place(cb["src_outdeg"][p].reshape(-1), 1)
        w[p, :ll] = lo.place(cb["w"][p].reshape(-1), 0.0)
        valid[p, :ll] = (lo.place(cb["valid"][p].reshape(-1), False)
                         & lo.lane_valid)
        seg_l[p, :ll] = lo.place(
            seg_all[p].reshape(-1).astype(np.int32), Sc)

    return (dict(comb_wid=wid, comb_rel=rel, comb_written=written,
                 comb_src_local=src_local, comb_src_gid=src_gid,
                 comb_src_outdeg=src_outdeg, comb_w=w, comb_valid=valid,
                 comb_seg=seg_l,
                 comb_recv_dst_local=np.ascontiguousarray(
                     cb["comb_dst"].swapaxes(0, 1))),
            R, n_tiles, n_windows)


def build_shard_data(pg: PartitionedGraph, *, tile_e: int = 512,
                     tile_r: int = 256) -> tuple:
    """(ShardData of numpy arrays, ShardMeta)."""
    P, Vm = pg.num_parts, pg.v_max
    lanes, n_tiles, n_windows = _build_shard_layouts(pg, tile_e, tile_r)
    comb, comb_max, comb_tiles, comb_windows = _build_combined_layouts(
        pg, tile_e, tile_r)

    flt = pg.nbr_filter.copy()
    flt[np.arange(pg.num_vertices), pg.part_of] = False
    flt_cnt = np.zeros((P, Vm), np.int32)
    flt_cnt[pg.part_of, pg.local_of] = flt.sum(axis=1).astype(np.int32)

    # ring buckets: shard p's in-edges grouped by source shard q =
    # transpose of the pair (source-side) layout. src_local is local to q.
    rb = dict(
        rb_src_local=pg.pair_src_local.swapaxes(0, 1),
        rb_src_gid=pg.pair_src_gid.swapaxes(0, 1),
        rb_src_outdeg=pg.pair_src_outdeg.swapaxes(0, 1),
        rb_w=pg.pair_w.swapaxes(0, 1),
        rb_dst_local=pg.pair_dst_local.swapaxes(0, 1),
        rb_valid=pg.pair_valid.swapaxes(0, 1),
    )

    data = ShardData(
        vert_gid=pg.vert_gid, vert_valid=pg.vert_valid, out_deg=pg.out_deg,
        flt_cnt=flt_cnt,
        **{k: np.ascontiguousarray(v) for k, v in lanes.items()},
        **{k: np.ascontiguousarray(v) for k, v in rb.items()},
        pair_src_local=pg.pair_src_local, pair_src_gid=pg.pair_src_gid,
        pair_src_outdeg=pg.pair_src_outdeg, pair_w=pg.pair_w,
        pair_valid=pg.pair_valid,
        recv_dst_local=pg.pair_dst_local.swapaxes(0, 1),
        **{k: np.ascontiguousarray(v) for k, v in comb.items()},
    )
    # frontier capacity buckets: powers of two up to Vm
    caps = []
    c = max(64, Vm // 16)
    while c < Vm:
        caps.append(c)
        c *= 4
    caps.append(Vm)
    meta = ShardMeta(P=P, v_max=Vm, e_pair_max=pg.e_pair_max,
                     n_tiles=n_tiles, n_windows=n_windows,
                     tile_e=tile_e, tile_r=tile_r,
                     num_vertices=pg.num_vertices,
                     frontier_capacities=tuple(caps),
                     comb_max=comb_max, comb_tiles=comb_tiles,
                     comb_windows=comb_windows)
    return data, meta


def abstract_shard_data(meta: ShardMeta, mesh=None,
                        exchange: str = "allgather") -> ShardData:
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation). Fields
    unused by the chosen exchange are None (pruned from the input
    signature, so argument bytes reflect what that architecture loads)."""
    P, Vm, E2 = meta.P, meta.v_max, meta.e_pair_max
    Lf = meta.n_tiles * meta.tile_e
    CL = meta.comb_tiles * meta.tile_e
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    none6 = (None,) * 6
    csc = exchange in ("allgather", "frontier")
    ring = exchange == "ring"
    uni = exchange == "unicast"
    comb = exchange == "combined"
    return ShardData(
        vert_gid=sds((P, Vm), i32), vert_valid=sds((P, Vm), b),
        out_deg=sds((P, Vm), i32), flt_cnt=sds((P, Vm), i32),
        wid=sds((P, meta.n_tiles), i32) if csc else None,
        rel=sds((P, Lf), i32) if csc else None,
        window_written=sds((P, meta.n_windows), b) if csc else None,
        src_slot=sds((P, Lf), i32) if csc else None,
        src_gid=sds((P, Lf), i32) if csc else None,
        src_outdeg=sds((P, Lf), i32) if csc else None,
        w=sds((P, Lf), f32) if csc else None,
        lane_valid=sds((P, Lf), b) if csc else None,
        seg=sds((P, Lf), i32) if csc else None,
        rb_src_local=sds((P, P, E2), i32) if ring else None,
        rb_src_gid=sds((P, P, E2), i32) if ring else None,
        rb_src_outdeg=sds((P, P, E2), i32) if ring else None,
        rb_w=sds((P, P, E2), f32) if ring else None,
        rb_dst_local=sds((P, P, E2), i32) if ring else None,
        rb_valid=sds((P, P, E2), b) if ring else None,
        pair_src_local=sds((P, P, E2), i32) if uni else None,
        pair_src_gid=sds((P, P, E2), i32) if uni else None,
        pair_src_outdeg=sds((P, P, E2), i32) if uni else None,
        pair_w=sds((P, P, E2), f32) if uni else None,
        pair_valid=sds((P, P, E2), b) if uni else None,
        recv_dst_local=sds((P, P, E2), i32) if uni else None,
        comb_wid=sds((P, meta.comb_tiles), i32) if comb else None,
        comb_rel=sds((P, CL), i32) if comb else None,
        comb_written=sds((P, meta.comb_windows), b) if comb else None,
        comb_src_local=sds((P, CL), i32) if comb else None,
        comb_src_gid=sds((P, CL), i32) if comb else None,
        comb_src_outdeg=sds((P, CL), i32) if comb else None,
        comb_w=sds((P, CL), f32) if comb else None,
        comb_valid=sds((P, CL), b) if comb else None,
        comb_seg=sds((P, CL), i32) if comb else None,
        comb_recv_dst_local=sds((P, P, meta.comb_max), i32)
        if comb else None,
    )


class ShardEngine:
    """shard_map execution of a GasKernel over a device mesh axis."""

    def __init__(self, kernel: GasKernel, pg_or_meta, *,
                 mesh: Mesh, exchange: str = "allgather",
                 backend: str = "pallas",
                 tile_e: int = 512, tile_r: int = 256,
                 params: Optional[Dict[str, Any]] = None):
        assert exchange in ("allgather", "ring", "frontier", "unicast",
                            "combined")
        self.kernel = kernel
        self.mesh = mesh
        self.exchange = exchange
        self.backend = backend
        self.params = dict(params or {})
        if isinstance(pg_or_meta, PartitionedGraph):
            self.pg = pg_or_meta
            np_data, self.meta = build_shard_data(
                pg_or_meta, tile_e=tile_e, tile_r=tile_r)
            self.params.setdefault("num_vertices", pg_or_meta.num_vertices)
            sharding = NamedSharding(mesh, P(AXIS))
            self._data = jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), sharding), np_data)
        else:
            self.pg = None
            self.meta = pg_or_meta
            self._data = None
        self._device_resident = self._data is not None
        self.params.setdefault("num_vertices", self.meta.num_vertices)
        # jitted program cache (per superstep cap) + trace counter; see
        # Engine.traces for the counting trick.
        self.traces = 0
        # event bus for run/run_batch's execute/fetch/collect spans (see
        # Engine.trace)
        self.trace = None
        self._run_cache: Dict[Any, Any] = {}
        # one program per schedule; the overlapped variant is built
        # lazily (its windowed folds require a min/max combiner) and
        # both share this engine's device data and jit caches.
        self._progs: Dict[bool, SuperstepProgram] = {
            False: self._make_program(False)}
        self._prog = self._progs[False]
        self._steppers: Dict[Any, "ShardLaneStepper"] = {}

    def _prog_for(self, overlap: bool) -> SuperstepProgram:
        overlap = bool(overlap)
        prog = self._progs.get(overlap)
        if prog is None:
            prog = self._progs[overlap] = self._make_program(overlap)
        return prog

    def _make_program(self, overlap: bool = False) -> SuperstepProgram:
        """Per-shard step-granular program (runs inside shard_map blocks;
        termination uses the §4.3 distributed activity bit)."""
        if overlap and self.exchange in ("unicast", "combined") \
                and self.kernel.combiner not in ("min", "max"):
            raise ValueError(
                "overlap=True windows the all_to_all receiver fold, which "
                "is only exact for min/max combiners; kernel "
                f"{self.kernel.name!r} combines with "
                f"{self.kernel.combiner!r}")
        deliver = {
            ("allgather", False): self._deliver_allgather,
            ("ring", False): self._deliver_ring,
            ("frontier", False): self._deliver_frontier,
            ("unicast", False): self._deliver_unicast,
            ("combined", False): self._deliver_combined,
            ("allgather", True): self._deliver_allgather_ov,
            ("ring", True): self._deliver_ring_ov,
            ("frontier", True): self._deliver_frontier_ov,
            ("unicast", True): self._deliver_unicast_ov,
            ("combined", True): self._deliver_combined_ov,
        }[(self.exchange, bool(overlap))]

        def init_stats():
            return {"messages": jnp.int32(0), "words": jnp.float32(0.0)}

        def update_stats(stats, d, active, aux):
            return {"messages": stats["messages"] + aux["n_msgs"],
                    "words": stats["words"] + aux["words"]}

        def global_any(b):
            return jax.lax.pmax(b.astype(jnp.int32), AXIS) > 0

        return SuperstepProgram(self.kernel, deliver,
                                init_stats=init_stats,
                                update_stats=update_stats,
                                edge_view=self._edge_view,
                                global_any=global_any)

    # ---------------- per-shard delivery kernels ----------------------
    def _local_combine(self, masked, d, combiner):  # analysis: traced
        """Per-shard segmented combine (Pallas kernel or jnp oracle)."""
        m = self.meta
        if self.backend == "pallas":
            from ..kernels.edge_gather import segment_combine_windows
            return segment_combine_windows(
                d.wid, d.rel, masked, combiner=combiner,
                tile_e=m.tile_e, tile_r=m.tile_r, n_windows=m.n_windows,
                window_written=d.window_written,
                num_segments=m.v_max + 1)
        return kref.segment_combine(masked, d.seg, m.v_max + 1, combiner)

    def _comb_combine(self, masked, d, combiner):  # analysis: traced
        """Source-side segmented combine over the dst-sorted combined
        layout: one output slot per (destination shard, dst rank)."""
        m = self.meta
        n_seg = m.P * (m.comb_max + 1)
        if self.backend == "pallas":
            from ..kernels.edge_gather import segment_combine_windows
            return segment_combine_windows(
                d.comb_wid, d.comb_rel, masked, combiner=combiner,
                tile_e=m.tile_e, tile_r=m.tile_r,
                n_windows=m.comb_windows, window_written=d.comb_written,
                num_segments=n_seg)
        return kref.segment_combine(masked, d.comb_seg, n_seg, combiner)

    def _consume(self, d, payload_flat, active_flat):  # analysis: traced
        """Receiver-side scatter+gather against the local CSC lanes given
        the (already transported) flat update array."""
        k, m = self.kernel, self.meta
        vals = jnp.take(payload_flat, d.src_slot)
        act = jnp.take(active_flat, d.src_slot) & d.lane_valid
        msg = k.scatter(vals, d.w, d.src_gid, d.src_outdeg)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        masked = jnp.where(act, msg, ident)
        acc = self._local_combine(masked, d, k.combiner)[: m.v_max]
        if k.got_from_identity:
            got = acc != ident
        else:
            gv = jnp.where(act, 1, 0).astype(jnp.int32)
            got = self._local_combine(gv, d, "max")[: m.v_max] > 0
        n_msgs = _count(act)
        return acc, got, n_msgs

    def _edge_view(self, d, state) -> LaneView:  # analysis: traced
        """The EdgeView of a shard's CSC in-edge lanes. ``src(key)``
        exchanges state leaf ``key`` alone, (Q, Vm) per shard, with one
        ``all_gather``; the pass moves nothing else between shards.
        ``any`` is taken over every shard, so a ``lax.cond`` on it takes
        the same branch on each, and a branch may exchange a leaf too."""
        m = self.meta

        def src_of(key):
            with jax.named_scope("gravfm.exchange"):
                g = xc.all_gather(state[key], AXIS)          # (P, Q, Vm)
            full = jnp.swapaxes(g, 0, 1).reshape(g.shape[1], -1)
            return jax.vmap(lambda x: jnp.take(x, d.src_slot))(full)

        def combine(vals, combiner):
            return self._local_combine(vals, d, combiner)

        def any_of(pred):
            return jax.lax.pmax(jnp.any(pred).astype(jnp.int32), AXIS) > 0

        return LaneView(src_of=src_of, seg=d.seg, combine=combine,
                        any_of=any_of, vshape=(m.v_max,), w=d.w,
                        src_gid=d.src_gid, src_outdeg=d.src_outdeg,
                        valid=d.lane_valid)

    # ---------------- exchanges ---------------------------------------
    def _deliver_allgather(self, d, payload, active):  # analysis: traced
        m = self.meta
        upd = xc.all_gather(payload, AXIS)          # (P, Vm)
        act = xc.all_gather(active, AXIS)
        # actual wire: the DENSE padded update array goes to every peer
        words = jnp.float32(m.v_max * (m.P - 1))
        acc, got, n_msgs = self._consume(
            d, upd.reshape(-1), act.reshape(-1))
        return acc, got, {"n_msgs": n_msgs, "words": words}

    def _deliver_frontier(self, d, payload, active):  # analysis: traced
        """Compact ACTIVE updates to (id, payload) pairs; broadcast the
        smallest sufficient capacity bucket."""
        k, m = self.kernel, self.meta
        me = jax.lax.axis_index(AXIS)
        n_act = jnp.sum(active.astype(jnp.int32))
        n_max = xc.pmax(n_act, AXIS)
        caps = m.frontier_capacities
        ident = kops.identity_for(k.combiner, k.msg_dtype)

        (idx,) = jnp.nonzero(active, size=m.v_max, fill_value=m.v_max)
        drop = m.P * m.v_max  # out-of-bounds target -> dropped by scatter

        def branch(cap):
            def f(_):
                ids = idx[:cap]                    # local active vertex ids
                valid = ids < m.v_max
                safe = jnp.minimum(ids, m.v_max - 1)
                pay = jnp.take(payload, safe)
                slots = me * m.v_max + safe
                # broadcast the COMPACT (id, payload) buffer only
                slots_all = xc.all_gather(slots, AXIS).reshape(-1)
                pay_all = xc.all_gather(pay, AXIS).reshape(-1)
                val_all = xc.all_gather(valid, AXIS).reshape(-1)
                tgt = jnp.where(val_all, slots_all, drop)
                # each slot has a unique owner => plain scatter-set is exact
                pf = jnp.full((m.P * m.v_max,), ident, pay_all.dtype)
                pf = pf.at[tgt].set(pay_all, mode="drop")
                af = jnp.zeros((m.P * m.v_max,), bool)
                af = af.at[tgt].set(True, mode="drop")
                # wire words actually moved: the padded buffer, id+payload
                words = jnp.float32(cap * 2 * (m.P - 1))
                return pf, af, words
            return f

        # smallest capacity bucket that fits the global max frontier
        sel = jnp.searchsorted(jnp.asarray(caps), n_max)
        sel = jnp.minimum(sel, len(caps) - 1)
        pf, af, words = jax.lax.switch(sel, [branch(c) for c in caps],
                                       operand=None)
        acc, got, n_msgs = self._consume(d, pf, af)
        return acc, got, {"n_msgs": n_msgs, "words": words}

    def _combine2(self, a, b):  # analysis: traced
        """Two-operand fold of the kernel's combiner monoid."""
        k = self.kernel
        if k.combiner == "add":
            return a + b
        return jnp.minimum(a, b) if k.combiner == "min" else jnp.maximum(a, b)

    def _ring_bucket_consume(self, d, q, chunk_payload,  # analysis: traced
                             chunk_active):
        """Scatter+gather the edges whose SOURCE shard is q against the
        chunk of q's updates currently held."""
        k, m = self.kernel, self.meta
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        b_src = d.rb_src_local[q]
        vals = jnp.take(chunk_payload, b_src)
        act = jnp.take(chunk_active, b_src) & d.rb_valid[q]
        msg = k.scatter(vals, d.rb_w[q], d.rb_src_gid[q],
                        d.rb_src_outdeg[q])
        masked = jnp.where(act, msg, ident)
        seg = d.rb_dst_local[q]
        acc_q = kref.segment_combine(masked, seg, m.v_max, k.combiner)
        gv = kref.segment_combine(
            jnp.where(act, 1, 0).astype(jnp.int32), seg, m.v_max, "max")
        return acc_q, gv > 0, _count(act)

    def _deliver_ring(self, d, payload, active):  # analysis: traced
        """P-hop ppermute ring; each arriving chunk is consumed against the
        matching source-shard edge bucket while the next hop is in flight
        (floating-barrier analogue)."""
        k, m = self.kernel, self.meta
        me = jax.lax.axis_index(AXIS)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        perm = [(i, (i + 1) % m.P) for i in range(m.P)]
        bucket_consume = lambda q, p, a: self._ring_bucket_consume(d, q, p, a)  # noqa: E731
        combine = self._combine2

        def body(i, st):
            acc, got, n_msgs, chunk_p, chunk_a = st
            q = (me - i) % m.P
            acc_q, got_q, nm = bucket_consume(q, chunk_p, chunk_a)
            acc = combine(acc, acc_q)
            got = got | got_q
            n_msgs = n_msgs + nm
            # next hop in flight while (in the compiled TPU schedule) the
            # next bucket's compute proceeds
            chunk_p = xc.ppermute(chunk_p, AXIS, perm)
            chunk_a = xc.ppermute(chunk_a, AXIS, perm)
            return acc, got, n_msgs, chunk_p, chunk_a

        acc0 = jnp.full((m.v_max,), ident, k.msg_dtype)
        got0 = jnp.zeros((m.v_max,), bool)
        st = (acc0, got0, jnp.int32(0), payload, active)
        acc, got, n_msgs, _, _ = jax.lax.fori_loop(0, m.P, body, st)
        # ring moves the same dense bytes as allgather, in P-1 hops
        words = jnp.float32(m.v_max * (m.P - 1))
        return acc, got, {"n_msgs": n_msgs, "words": words}

    def _deliver_unicast(self, d, payload, active):  # analysis: traced
        """GraVF baseline: source-side scatter + all_to_all blocks."""
        k, m = self.kernel, self.meta
        vals = jnp.take(payload, d.pair_src_local.reshape(-1)).reshape(
            d.pair_src_local.shape)
        act = jnp.take(active, d.pair_src_local.reshape(-1)).reshape(
            d.pair_src_local.shape) & d.pair_valid
        msg = k.scatter(vals, d.pair_w, d.pair_src_gid, d.pair_src_outdeg)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        masked = jnp.where(act, msg, ident)
        recv = xc.all_to_all(masked, AXIS, split_axis=0,
                                  concat_axis=0, tiled=False)
        recv_act = xc.all_to_all(act, AXIS, split_axis=0,
                                      concat_axis=0, tiled=False)
        seg = d.recv_dst_local
        acc = kref.segment_combine(recv.reshape(-1), seg.reshape(-1),
                                   m.v_max, k.combiner)
        gv = kref.segment_combine(
            jnp.where(recv_act, 1, 0).astype(jnp.int32).reshape(-1),
            seg.reshape(-1), m.v_max, "max")
        got = gv > 0
        n_msgs = _count(act)
        # actual wire: all_to_all ships the PADDED per-pair blocks
        words = jnp.float32(m.e_pair_max * (m.P - 1))
        return acc, got, {"n_msgs": n_msgs, "words": words}

    def _deliver_combined(self, d, payload, active):  # analysis: traced
        """Combine-at-source (the paper's degree-factor headline): fold
        the per-edge messages down to one partial per (destination shard,
        destination vertex) BEFORE the wire, then all_to_all blocks of
        ``comb_max`` slots — the receiver merges pre-combined partials
        with the same monoid, so the two-level fold is exact for min/max
        and reorder-tolerant for add."""
        k, m = self.kernel, self.meta
        R = m.comb_max
        vals = jnp.take(payload, d.comb_src_local)
        act = jnp.take(active, d.comb_src_local) & d.comb_valid
        msg = k.scatter(vals, d.comb_w, d.comb_src_gid, d.comb_src_outdeg)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        masked = jnp.where(act, msg, ident)
        accs = self._comb_combine(masked, d, k.combiner)       # (n_seg,)
        send = accs.reshape(m.P, R + 1)[:, :R]                 # (P, R)
        send_act = self._comb_combine(
            jnp.where(act, 1, 0).astype(jnp.int32), d, "max"
        ).reshape(m.P, R + 1)[:, :R] > 0
        recv = xc.all_to_all(send, AXIS, split_axis=0,
                                  concat_axis=0, tiled=False)
        recv_act = xc.all_to_all(send_act, AXIS, split_axis=0,
                                      concat_axis=0, tiled=False)
        seg = d.comb_recv_dst_local                            # (P, R)
        acc = kref.segment_combine(recv.reshape(-1), seg.reshape(-1),
                                   m.v_max, k.combiner)
        gv = kref.segment_combine(
            jnp.where(recv_act, 1, 0).astype(jnp.int32).reshape(-1),
            seg.reshape(-1), m.v_max, "max")
        got = gv > 0
        n_msgs = _count(act)
        # actual wire: one (id, payload) slot per padded remote dst —
        # the degree-factor win over unicast's e_pair_max per-edge blocks
        words = jnp.float32(2 * R * (m.P - 1))
        return acc, got, {"n_msgs": n_msgs, "words": words}

    # ---------------- overlapped (pipelined) exchanges ------------------
    # Window count for the chunked all_to_all pipelines. Static (it fixes
    # the traced loop bounds); the *index* of the in-flight window is a
    # fori_loop carry — see RTR005.
    OVERLAP_WINDOWS = 4

    def _n_windows(self, extent: int) -> int:
        return max(1, min(self.OVERLAP_WINDOWS, int(extent)))

    def _deliver_allgather_ov(self, d, payload, active):  # analysis: traced
        """Pipelined allgather: the broadcast decomposed into P ppermute
        hops that accumulate into the SAME flat receive array all_gather
        would produce, each chunk placed while the next hop is already in
        flight; one receiver-side consume then runs, so states, message
        counts and wire words are bit-identical to the one-shot gather."""
        m = self.meta
        me = jax.lax.axis_index(AXIS)
        perm = [(i, (i + 1) % m.P) for i in range(m.P)]

        def body(i, st):
            upd, actf, cur_p, cur_a, nxt_p, nxt_a = st
            # hop i+2's transport first: the in-flight buffer moves on
            # while chunk i is being placed (double buffer)
            new_p = xc.ppermute(nxt_p, AXIS, perm)
            new_a = xc.ppermute(nxt_a, AXIS, perm)
            q = (me - i) % m.P
            upd = jax.lax.dynamic_update_slice(upd, cur_p, (q * m.v_max,))
            actf = jax.lax.dynamic_update_slice(actf, cur_a, (q * m.v_max,))
            return upd, actf, nxt_p, nxt_a, new_p, new_a

        st = (jnp.zeros((m.P * m.v_max,), payload.dtype),
              jnp.zeros((m.P * m.v_max,), jnp.bool_),
              payload, active,
              xc.ppermute(payload, AXIS, perm),
              xc.ppermute(active, AXIS, perm))
        upd, actf = jax.lax.fori_loop(0, m.P, body, st)[:2]
        words = jnp.float32(m.v_max * (m.P - 1))
        acc, got, n_msgs = self._consume(d, upd, actf)
        return acc, got, {"n_msgs": n_msgs, "words": words}

    def _deliver_frontier_ov(self, d, payload, active):  # analysis: traced
        """Pipelined frontier: same capacity-bucket compaction as the
        synchronous schedule, but the compact (id, payload, valid) buffer
        rings around in P ppermute hops, each arriving chunk scatter-set
        into the flat receive arrays while the next hop is in flight.
        Slot owners are unique, so the set order cannot change a bit."""
        k, m = self.kernel, self.meta
        me = jax.lax.axis_index(AXIS)
        n_act = jnp.sum(active.astype(jnp.int32))
        n_max = xc.pmax(n_act, AXIS)
        caps = m.frontier_capacities
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        perm = [(i, (i + 1) % m.P) for i in range(m.P)]

        (idx,) = jnp.nonzero(active, size=m.v_max, fill_value=m.v_max)
        drop = m.P * m.v_max  # out-of-bounds target -> dropped by scatter

        def branch(cap):
            def f(_):
                ids = idx[:cap]                    # local active vertex ids
                valid = ids < m.v_max
                safe = jnp.minimum(ids, m.v_max - 1)
                pay = jnp.take(payload, safe)
                slots = me * m.v_max + safe

                def body(i, st):
                    pf, af, cs, cp, cv, ns, np_, nv = st
                    ms = xc.ppermute(ns, AXIS, perm)
                    mp = xc.ppermute(np_, AXIS, perm)
                    mv = xc.ppermute(nv, AXIS, perm)
                    tgt = jnp.where(cv, cs, drop)
                    pf = pf.at[tgt].set(cp, mode="drop")
                    af = af.at[tgt].set(True, mode="drop")
                    return pf, af, ns, np_, nv, ms, mp, mv

                st = (jnp.full((m.P * m.v_max,), ident, pay.dtype),
                      jnp.zeros((m.P * m.v_max,), jnp.bool_),
                      slots, pay, valid,
                      xc.ppermute(slots, AXIS, perm),
                      xc.ppermute(pay, AXIS, perm),
                      xc.ppermute(valid, AXIS, perm))
                pf, af = jax.lax.fori_loop(0, m.P, body, st)[:2]
                # wire words actually moved: identical to the sync path
                words = jnp.float32(cap * 2 * (m.P - 1))
                return pf, af, words
            return f

        sel = jnp.searchsorted(jnp.asarray(caps), n_max)
        sel = jnp.minimum(sel, len(caps) - 1)
        pf, af, words = jax.lax.switch(sel, [branch(c) for c in caps],
                                       operand=None)
        acc, got, n_msgs = self._consume(d, pf, af)
        return acc, got, {"n_msgs": n_msgs, "words": words}

    def _deliver_ring_ov(self, d, payload, active):  # analysis: traced
        """Double-buffered ring: hop k+1's ppermute is issued BEFORE chunk
        k's bucket consume (the sync ring permutes after). Consume and
        merge order are unchanged, so the fold is bit-identical."""
        k, m = self.kernel, self.meta
        me = jax.lax.axis_index(AXIS)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        perm = [(i, (i + 1) % m.P) for i in range(m.P)]

        def body(i, st):
            acc, got, n_msgs, cur_p, cur_a, nxt_p, nxt_a = st
            # issue hop i+2's transport before touching chunk i
            new_p = xc.ppermute(nxt_p, AXIS, perm)
            new_a = xc.ppermute(nxt_a, AXIS, perm)
            q = (me - i) % m.P
            acc_q, got_q, nm = self._ring_bucket_consume(
                d, q, cur_p, cur_a)
            acc = self._combine2(acc, acc_q)
            got = got | got_q
            n_msgs = n_msgs + nm
            return acc, got, n_msgs, nxt_p, nxt_a, new_p, new_a

        acc0 = jnp.full((m.v_max,), ident, k.msg_dtype)
        got0 = jnp.zeros((m.v_max,), bool)
        st = (acc0, got0, jnp.int32(0), payload, active,
              xc.ppermute(payload, AXIS, perm),
              xc.ppermute(active, AXIS, perm))
        acc, got, n_msgs = jax.lax.fori_loop(0, m.P, body, st)[:3]
        words = jnp.float32(m.v_max * (m.P - 1))
        return acc, got, {"n_msgs": n_msgs, "words": words}

    def _window_pipeline(self, seg3, masked3, act3,  # analysis: traced
                         n_win, ident):
        """Chunked all_to_all pipeline shared by the overlapped unicast
        and combined exchanges: the collective for column window k+1 is
        issued while window k's receive block (the double buffer riding
        the fori_loop carry) is folded into the accumulator. Per-window
        partials merge with the combiner, which is exact for min/max
        combiners. ``act3 is None`` elides the activity stream for
        got_from_identity kernels (activity is recovered as ``recv !=
        identity``)."""
        k, m = self.kernel, self.meta
        dummy = jnp.int32(0)

        def a2a(x):
            return xc.all_to_all(x, AXIS, split_axis=0,
                                      concat_axis=0, tiled=False)

        def issue(wi):
            wi = jnp.minimum(wi, n_win - 1)
            bp = a2a(jax.lax.dynamic_index_in_dim(
                masked3, wi, 1, keepdims=False))
            ba = (a2a(jax.lax.dynamic_index_in_dim(
                act3, wi, 1, keepdims=False))
                if act3 is not None else dummy)
            return bp, ba

        def fold(wi, acc, got, bp, ba):
            seg_w = jax.lax.dynamic_index_in_dim(
                seg3, wi, 1, keepdims=False).reshape(-1)
            recv = bp.reshape(-1)
            acc_w = kref.segment_combine(recv, seg_w, m.v_max, k.combiner)
            if act3 is not None:
                gv = kref.segment_combine(
                    jnp.where(ba.reshape(-1), 1, 0).astype(jnp.int32),
                    seg_w, m.v_max, "max")
                got = got | (gv > 0)
            return self._combine2(acc, acc_w), got

        def body(w, st):
            acc, got, bp, ba = st
            nb = issue(w + 1)     # window w+1's collective in flight...
            acc, got = fold(w, acc, got, bp, ba)  # ...while w folds
            return (acc, got) + nb

        acc0 = jnp.full((m.v_max,), ident, k.msg_dtype)
        got0 = jnp.zeros((m.v_max,), bool)
        st = jax.lax.fori_loop(
            0, n_win - 1, body, (acc0, got0) + issue(jnp.int32(0)))
        acc, got = fold(jnp.int32(n_win - 1), *st)
        if act3 is None:
            got = acc != ident
        return acc, got

    def _window3(self, a, n_win, cw, fill):  # analysis: traced
        """(P, E) -> (P, n_win, cw) column windows, identity-padded."""
        m = self.meta
        pad = n_win * cw - a.shape[-1]
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)), constant_values=fill)
        return a.reshape(m.P, n_win, cw)

    def _deliver_unicast_ov(self, d, payload, active):  # analysis: traced
        """Overlapped GraVF baseline: the per-pair message blocks cross
        the wire in column windows, the collective for window k+1 in
        flight while window k folds at the receiver."""
        k, m = self.kernel, self.meta
        vals = jnp.take(payload, d.pair_src_local.reshape(-1)).reshape(
            d.pair_src_local.shape)
        act = jnp.take(active, d.pair_src_local.reshape(-1)).reshape(
            d.pair_src_local.shape) & d.pair_valid
        msg = k.scatter(vals, d.pair_w, d.pair_src_gid, d.pair_src_outdeg)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        masked = jnp.where(act, msg, ident)
        n_win = self._n_windows(m.e_pair_max)
        cw = -(-m.e_pair_max // n_win)
        masked3 = self._window3(masked, n_win, cw, ident)
        seg3 = self._window3(d.recv_dst_local, n_win, cw, m.v_max)
        act3 = (None if k.got_from_identity
                else self._window3(act, n_win, cw, False))
        acc, got = self._window_pipeline(seg3, masked3, act3, n_win, ident)
        n_msgs = _count(act)
        # reported wire: the bytes the serial schedule moves (see module
        # docstring) — keeps stats comparable across schedules
        words = jnp.float32(m.e_pair_max * (m.P - 1))
        return acc, got, {"n_msgs": n_msgs, "words": words}

    def _deliver_combined_ov(self, d, payload, active):  # analysis: traced
        """Overlapped combine-at-source: the per-(peer, rank) partial
        blocks cross the wire in column windows behind the receiver fold;
        the source-side segment-combine is the synchronous one."""
        k, m = self.kernel, self.meta
        R = m.comb_max
        vals = jnp.take(payload, d.comb_src_local)
        act = jnp.take(active, d.comb_src_local) & d.comb_valid
        msg = k.scatter(vals, d.comb_w, d.comb_src_gid, d.comb_src_outdeg)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        masked = jnp.where(act, msg, ident)
        accs = self._comb_combine(masked, d, k.combiner)       # (n_seg,)
        send = accs.reshape(m.P, R + 1)[:, :R]                 # (P, R)
        n_win = self._n_windows(R)
        cw = -(-R // n_win) if R else 0
        masked3 = self._window3(send, n_win, cw, ident)
        seg3 = self._window3(d.comb_recv_dst_local, n_win, cw, m.v_max)
        act3 = None
        if not k.got_from_identity:
            send_act = self._comb_combine(
                jnp.where(act, 1, 0).astype(jnp.int32), d, "max"
            ).reshape(m.P, R + 1)[:, :R] > 0
            act3 = self._window3(send_act, n_win, cw, False)
        acc, got = self._window_pipeline(seg3, masked3, act3, n_win, ident)
        n_msgs = _count(act)
        words = jnp.float32(2 * R * (m.P - 1))
        return acc, got, {"n_msgs": n_msgs, "words": words}

    # ---------------- superstep + loop ---------------------------------
    def _shard_step(self, d: ShardData, payload, active, state, superstep):
        """One superstep as a plain function (kept for the dry-run /
        roofline hooks); thin shim over the SuperstepProgram step."""
        c = self._prog.step(d, StepCarry(state, payload, active, superstep,
                                         self._prog.init_stats()))
        return (c.state, c.payload, c.active, c.stats["messages"],
                c.stats["words"])

    def _make_run(self, cap: int, qkeys: tuple = (),
                  overlap: bool = False):
        ck = ("single", cap, qkeys, bool(overlap))
        if ck in self._run_cache:
            return self._run_cache[ck]
        prog = self._prog_for(overlap)

        def shard_fn(d: ShardData, qkw):
            self.traces += 1  # trace-time side effect (see Engine.traces)
            # shard_map blocks keep a size-1 leading (sharded) axis
            d = jax.tree.map(lambda a: a[0], d)
            c = prog.finalize_one(d, prog.while_run(d, cap, self.params, qkw))
            total_msgs = jax.lax.psum(c.stats["messages"], AXIS)
            total_words = jax.lax.psum(c.stats["words"], AXIS)
            # re-add shard axis
            state = jax.tree.map(lambda a: a[None], c.state)
            return (state, c.superstep, total_msgs, total_words,
                    _psum_counters(c.stats))

        in_specs = jax.tree.map(lambda _: P(AXIS), self._data,
                                is_leaf=lambda x: x is None)
        qspec = {kk: P() for kk in qkeys}
        state_spec = P(AXIS)
        fn = _shard_map(
            named(shard_fn, self._program_name("run", overlap)),
            mesh=self.mesh,
            in_specs=(in_specs, qspec),
            out_specs=(state_spec, P(), P(), P(), P()))
        fn = jax.jit(fn)
        self._run_cache[ck] = fn
        return fn

    def _make_run_batch(self, cap: int, qkeys: tuple, batch: int,
                        overlap: bool = False):
        """Query-batched shard_map program: the per-superstep exchange is
        shared by all B queries (one collective moves the (B, ·) payload);
        finished queries are frozen lane-wise so state/stats stay
        bit-identical to B sequential runs."""
        ck = ("batch", cap, qkeys, batch, bool(overlap))
        if ck in self._run_cache:
            return self._run_cache[ck]
        prog = self._prog_for(overlap)

        def shard_fn(d: ShardData, qkw):
            self.traces += 1  # trace-time side effect
            d = jax.tree.map(lambda a: a[0], d)

            carry = jax.vmap(
                lambda kw: prog.init_carry(d, self.params, kw))(qkw)
            step_v = jax.vmap(lambda c: prog.step(d, c))

            def alive_of(c):
                # per-query distributed termination bit (§4.3, per lane)
                with jax.named_scope("gravfm.cond"):
                    loc = jnp.any(c.active, axis=-1).astype(jnp.int32)
                    return jax.lax.pmax(loc, AXIS) > 0       # (B,)

            def cond(st):
                s, c = st
                with jax.named_scope("gravfm.cond"):
                    any_local = jnp.any(c.active).astype(jnp.int32)
                    return (jax.lax.pmax(any_local, AXIS) > 0) & (s < cap)

            def body(st):
                s, c = st
                # finished lanes are frozen (select), so their state,
                # superstep count and stats stay bit-identical to a solo
                # run while the batch keeps stepping
                c = select_lanes(alive_of(c), step_v(c), c)
                return s + 1, c

            with jax.named_scope("gravfm.loop"):
                _, carry = jax.lax.while_loop(
                    cond, body, (jnp.int32(0), carry))
            carry = prog.finalize(d, carry)
            total_msgs = jax.lax.psum(carry.stats["messages"], AXIS)  # (B,)
            total_words = jax.lax.psum(
                jnp.sum(carry.stats["words"]), AXIS)
            # re-add shard axis leading so out spec P(AXIS) shards it
            state = jax.tree.map(lambda a: a[None], carry.state)  # (1, B, ·)
            return (state, carry.superstep, total_msgs, total_words,
                    _psum_counters(carry.stats))

        in_specs = jax.tree.map(lambda _: P(AXIS), self._data,
                                is_leaf=lambda x: x is None)
        qspec = {kk: P() for kk in qkeys}
        fn = _shard_map(
            named(shard_fn, self._program_name(f"batch{batch}", overlap)),
            mesh=self.mesh,
            in_specs=(in_specs, qspec),
            out_specs=(P(AXIS), P(), P(), P(), P()))
        fn = jax.jit(fn)
        self._run_cache[ck] = fn
        return fn

    def _program_name(self, kind: str, overlap: bool) -> str:
        return (f"{self.kernel.name}_{self.exchange}"
                f"{'_overlap' if overlap else ''}_{kind}")

    def lower(self, batch: int, overlap: bool = False
              ) -> jax.stages.Lowered:
        """Lower the program a plan of ``batch`` queries dispatches with
        int32 query parameters (see ``Engine.lower``): :meth:`run`'s for
        one query, :meth:`run_batch`'s otherwise."""
        cap = self.kernel.max_supersteps or 100_000
        qkeys = tuple(sorted(self.kernel.query_params))
        shape = () if batch == 1 else (batch,)
        qkw = {k: jax.ShapeDtypeStruct(shape, jnp.int32) for k in qkeys}
        fn = (self._make_run(cap, qkeys, overlap) if batch == 1 else
              self._make_run_batch(cap, qkeys, batch, overlap))
        return fn.lower(self._data, qkw)

    def _result_comm(self, words: float,
                     counters: Dict[str, float]) -> Dict[str, Any]:
        return {"exchange_words": words, "wire_words": words,
                "exchange": self.exchange,
                "scheme": f"shard_{self.exchange}", **counters}

    def run(self, max_supersteps: Optional[int] = None,
            overlap: bool = False, **query_kwargs):
        """Single query (an :class:`~.engine.EngineResult`; also indexable
        like the historical result dict). ``query_kwargs`` (e.g.
        ``root=7``) are traced scalars, matching ``Engine.run``.
        ``overlap=True`` runs the pipelined exchange schedule
        (bit-identical results; see the module docstring)."""
        unknown = set(query_kwargs) - set(self.kernel.query_params)
        if unknown:
            raise ValueError(
                f"kernel {self.kernel.name!r} takes query params "
                f"{tuple(self.kernel.query_params)}, got unexpected "
                f"{sorted(unknown)}")
        cap = (max_supersteps or self.kernel.max_supersteps or 100_000)
        qkw = {kk: jnp.asarray(v) for kk, v in query_kwargs.items()}
        fn = self._make_run(cap, tuple(sorted(qkw)), overlap)
        with span(self.trace, "execute"):
            out = fn(self._data, qkw)
            jax.block_until_ready(out)
        with span(self.trace, "fetch"):
            state_np, s, msgs, words, ctr = jax.tree.map(np.asarray, out)
        from .engine import EngineResult, collect
        with span(self.trace, "collect"):
            return EngineResult(
                state=collect(self.pg, state_np) if self.pg else state_np,
                supersteps=int(s[0] if np.ndim(s) else s),
                messages=int(msgs.reshape(-1)[0]),
                comm=self._result_comm(
                    float(words.reshape(-1)[0]),
                    {k: float(v.reshape(-1)[0]) for k, v in ctr.items()}),
                raw_state=state_np,
            )

    def run_batch(self, max_supersteps: Optional[int] = None,
                  overlap: bool = False, **query_arrays):
        """Batched multi-query run (see ``Engine.run_batch``). Returns a
        list of per-query result dicts; ``exchange_words`` is reported for
        the whole batch on each entry (the queries share the wire)."""
        if not query_arrays:
            raise ValueError("run_batch needs at least one per-query array")
        unknown = set(query_arrays) - set(self.kernel.query_params)
        if unknown:
            raise ValueError(
                f"kernel {self.kernel.name!r} takes query params "
                f"{tuple(self.kernel.query_params)}, got unexpected "
                f"{sorted(unknown)}")
        cap = (max_supersteps or self.kernel.max_supersteps or 100_000)
        qkw = {kk: jnp.atleast_1d(jnp.asarray(v))
               for kk, v in query_arrays.items()}
        batch = next(iter(qkw.values())).shape[0]
        fn = self._make_run_batch(cap, tuple(sorted(qkw)), batch, overlap)
        with span(self.trace, "execute"):
            res = fn(self._data, qkw)
            jax.block_until_ready(res)
        with span(self.trace, "fetch"):
            # state leaves (P, B, ...)
            state_np, sq, msgs, words, ctr = jax.tree.map(np.asarray, res)
        from .engine import EngineResult, collect
        with span(self.trace, "collect"):
            sq = sq.reshape(-1, sq.shape[-1])[0]
            msgs = msgs.reshape(-1, msgs.shape[-1])[0]
            words = float(words.reshape(-1)[0])
            ctr = {k: v.reshape(-1, v.shape[-1])[0] for k, v in ctr.items()}
            out = []
            for q in range(sq.shape[0]):
                state_q = jax.tree.map(lambda a: a[:, q], state_np)
                out.append(EngineResult(
                    state=collect(self.pg, state_q) if self.pg else state_q,
                    supersteps=int(sq[q]),
                    messages=int(msgs[q]),
                    comm=self._result_comm(
                        words, {k: float(v[q]) for k, v in ctr.items()}),
                    raw_state=state_q,
                ))
            return out

    @property
    def device_nbytes(self) -> int:
        """Engine-tier graph bytes (0 when built meta-only)."""
        if self._data is None:
            return 0
        return int(sum(a.nbytes for a in jax.tree.leaves(self._data)))

    # ---------------- residency tier (see Engine.offload/upload) -------
    @property
    def device_resident(self) -> bool:
        return self._device_resident

    def offload(self) -> int:
        """Demote the sharded layout to host numpy copies (the engine
        tier of the store's host-spill residency); jitted programs and
        their caches survive untouched. Returns the bytes demoted."""
        if self._data is None or not self._device_resident:
            return 0
        host = jax.tree.map(np.asarray, self._data)
        self._data = host
        self._device_resident = False
        return int(sum(a.nbytes for a in jax.tree.leaves(host)))

    def upload(self) -> float:
        """Promote offloaded arrays back into mesh-sharded device
        buffers. Avals are unchanged, so the next dispatch hits the
        existing jit caches (zero re-traces). Returns wall seconds."""
        if self._data is None or self._device_resident:
            return 0.0
        t0 = time.perf_counter()
        sharding = NamedSharding(self.mesh, P(AXIS))
        data = jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), sharding), self._data)
        jax.block_until_ready(data)
        self._data = data
        self._device_resident = True
        return time.perf_counter() - t0

    # ---------------- step-granular entry point ------------------------
    def make_stepper(self, width: int,
                     overlap: bool = False) -> "ShardLaneStepper":
        """Host-drivable ``width``-lane slot array over the explicit
        collectives (see ``Engine.make_stepper``): one jitted shard_map
        call per superstep, with admit/retire between supersteps.
        Steppers are cached per (width, overlap) — both schedules share
        this engine's device data, so toggling ``overlap`` per request
        hits an already-traced plan (zero steady-state re-traces)."""
        if self._data is None:
            raise ValueError("make_stepper needs device data; this engine "
                             "was built meta-only (dry-run)")
        key = (width, bool(overlap))
        st = self._steppers.get(key)
        if st is None:
            st = ShardLaneStepper(self, width, overlap=bool(overlap))
            self._steppers[key] = st
        return st

    def lane_result(self, carry_host, lane: int):
        """Package one retired stepper lane as an
        :class:`~.engine.EngineResult` (same fields as :meth:`run`);
        per-shard stats are folded across the shard axis (the host-side
        psum)."""
        from .engine import EngineResult, collect
        state_q = jax.tree.map(lambda a: np.asarray(a[:, lane]),
                               carry_host.state)
        stats = {k: v[:, lane].sum() for k, v in carry_host.stats.items()}
        return EngineResult(
            state=collect(self.pg, state_q) if self.pg else state_q,
            supersteps=int(carry_host.superstep[0, lane]),
            messages=int(stats["messages"]),
            comm=self._result_comm(float(stats["words"]), {
                k: float(v) for k, v in _counters(stats).items()}),
            raw_state=state_q,
        )

    # ---------------- dry-run hooks ------------------------------------
    def superstep_fn(self):
        """One full superstep (deliver + gather + apply) as a jittable fn
        over (data, payload, active, state, superstep) — the unit that the
        multi-pod dry-run lowers and the roofline analyses."""
        def shard_fn(d, payload, active, state, superstep):
            return self._shard_step(d, payload, active, state, superstep)

        return shard_fn


class ShardLaneStepper(LaneStepperBase):
    """W-lane continuous-stepping handle over a :class:`ShardEngine`.

    Mirrors ``core.stepper.LaneStepper`` but every carry leaf keeps a
    leading shard axis (global shape ``(P, W, ...)`` sharded over the
    mesh ``graph`` axis), and admit/step are shard_map programs so each
    superstep runs the engine's explicit collective exactly once for all
    W lanes. The shard_map wrappers are built lazily on the first
    ``init`` (the carry pytree structure — hence the in/out spec trees —
    depends on the kernel's state dict and the query kwarg dtypes), then
    reused forever: steady-state admit/step/retire re-traces nothing.
    """

    _lane_axis = 1  # carry leaves are (P, W, ...)

    def __init__(self, eng: ShardEngine, width: int,
                 overlap: bool = False):
        self.eng = eng
        self.width = width
        self.overlap = bool(overlap)
        self._prog = eng._prog_for(self.overlap)
        self._fns = None  # (init, admit, step) jitted shard_map programs
        self._restore = None   # built with the other programs
        self._probe = jax.jit(self._probe_of)

        def fetch_lane_fn(carry, lane):
            eng.traces += 1  # trace-time side effect (see Engine.traces)
            # checkpoint gathers ONLY the lane's per-shard slices
            # (leaves (P, ...)), never the whole (P, W, ...) slot array
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, lane, 1, keepdims=False), carry)

        self._fetch_lane = jax.jit(fetch_lane_fn)

    def _probe_of(self, carry):
        # on the GLOBAL carry (outside shard_map): lane-alive is the
        # host-side form of the §4.3 pmax'd activity bit; the third
        # element is the cumulative wire words over all shards+lanes
        # (LaneStepperBase peels it off into ``last_wire_words`` so the
        # public (carry, act, steps) contract is unchanged)
        return (jnp.any(carry.active, axis=(0, 2)), carry.superstep[0],
                jnp.sum(carry.stats["words"]))

    def _build(self, qkw):
        eng, prog = self.eng, self._prog
        data_spec = jax.tree.map(lambda _: P(AXIS), eng._data,
                                 is_leaf=lambda x: x is None)
        qspec = {k: P() for k in qkw}
        lane_spec = P()

        def strip(t):
            return jax.tree.map(lambda a: a[0], t)

        def readd(t):
            return jax.tree.map(lambda a: a[None], t)

        def init_local(d, kw_arrays):
            return jax.vmap(
                lambda kw: prog.init_carry(d, eng.params, kw))(kw_arrays)

        # Carry structure (and so the spec trees) via eval_shape of the
        # collective-free local init.
        d_local = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), eng._data)
        qkw_struct = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for k, v in qkw.items()}
        carry_struct = jax.eval_shape(init_local, d_local, qkw_struct)
        carry_spec = jax.tree.map(lambda _: P(AXIS), carry_struct)

        def init_fn(d, kw):
            eng.traces += 1  # trace-time side effect (see Engine.traces)
            return readd(init_local(strip(d), kw))

        def admit_fn(d, carry, kw, fresh):
            eng.traces += 1
            d = strip(d)
            return readd(select_lanes(fresh, init_local(d, kw),
                                      strip(carry)))

        def step_fn(d, carry, alive):
            eng.traces += 1
            d, c = strip(d), strip(carry)
            return readd(select_lanes(
                alive, jax.vmap(lambda cc: prog.step(d, cc))(c), c))

        def restore_fn(carry, lane_c, fresh):
            eng.traces += 1
            c, lc = strip(carry), strip(lane_c)
            # splice the parked lane's per-shard carry slices back via
            # the admit-path select: bit-identical resume
            new = jax.tree.map(
                lambda leaf: jnp.broadcast_to(
                    leaf[None], (self.width,) + leaf.shape), lc)
            return readd(select_lanes(fresh, new, c))

        # a checkpoint slice drops the lane axis: leaves (P, ...)
        ckpt_spec = jax.tree.map(lambda _: P(AXIS), carry_struct)

        init_sm = _shard_map(init_fn, mesh=eng.mesh,
                             in_specs=(data_spec, qspec),
                             out_specs=carry_spec)
        admit_sm = _shard_map(admit_fn, mesh=eng.mesh,
                              in_specs=(data_spec, carry_spec, qspec,
                                        lane_spec),
                              out_specs=carry_spec)
        step_sm = _shard_map(step_fn, mesh=eng.mesh,
                             in_specs=(data_spec, carry_spec, lane_spec),
                             out_specs=carry_spec)
        restore_sm = _shard_map(restore_fn, mesh=eng.mesh,
                                in_specs=(carry_spec, ckpt_spec,
                                          lane_spec),
                                out_specs=carry_spec)

        # fuse the lane probe into the same dispatch (see LaneStepper)
        def with_probe(sm):
            def f(*args):
                c = sm(*args)
                return (c, *self._probe_of(c))
            return jax.jit(f)

        self._fns = (with_probe(init_sm), with_probe(admit_sm),
                     with_probe(step_sm))
        self._restore = with_probe(restore_sm)

        if prog.kernel.finalize is not None:
            def finalize_fn(d, carry, lanes):
                eng.traces += 1
                return readd(prog.finalize(strip(d), jax.tree.map(
                    lambda a: jnp.take(a, lanes, axis=0), strip(carry))))

            # the finalized carry adds leaves (the kernel's outputs and
            # counters): every leaf keeps the leading shard axis
            fin = jax.jit(_shard_map(
                finalize_fn, mesh=eng.mesh,
                in_specs=(data_spec, carry_spec, lane_spec),
                out_specs=P(AXIS)))
            self._finalize = lambda c, lanes: fin(eng._data, c, lanes)

    def init(self, qkw):
        q = self._qdev(qkw)
        if self._fns is None:
            self._build(q)
        return self._unpack(self._fns[0](self.eng._data, q))

    def admit(self, carry, qkw, fresh):
        return self._unpack(self._fns[1](self.eng._data, carry,
                                         self._qdev(qkw),
                                         jnp.asarray(fresh)))

    def step(self, carry, alive):
        return self._unpack(self._fns[2](self.eng._data, carry,
                                         jnp.asarray(alive)))
