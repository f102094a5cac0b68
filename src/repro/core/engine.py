"""The GraVF-M superstep engine.

Executes a :class:`GasKernel` over a :class:`PartitionedGraph` in either of
the paper's two architectures (§4.1, Fig. 4):

  mode="gravf"   — baseline: scatter runs at the SOURCE shard, per-edge
                   messages are exchanged shard-to-shard (unicast; the
                   axis-transpose below lowers to all_to_all when the shard
                   axis is device-sharded).
  mode="gravfm"  — the paper's contribution: apply emits ≤1 update per
                   vertex; the per-shard update arrays are broadcast (the
                   flat take below lowers to all_gather); scatter runs at
                   the RECEIVER against its destination-partitioned edge
                   list, and messages are generated on demand and consumed
                   immediately (in VMEM, inside the Pallas kernel).

The engine is written as a *global-array* program with an explicit leading
shard axis: it runs unchanged on one CPU device (this container) and on a
TPU mesh by sharding the leading axis (`launch/mesh.py` + jit shardings) —
XLA SPMD then emits the all_gather / all_to_all named above. An explicit
shard_map variant with a compute/communication-overlapped ring broadcast
(the floating-barrier analogue) lives in `engine_shardmap.py`.

Superstep loop semantics follow §4.3: apply runs on the initial state first
("the barrier is injected into the apply modules to begin execution"), and
distributed termination is the all-reduced "no shard sent updates" bit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..kernels import ref as kref
from ..kernels.degree_class import ClassSegments, build_classes
from ..kernels.edge_gather import segment_combine_windows
from .gas import GasKernel
from .partition import PartitionedGraph
from .stepper import LaneStepper, LaneView, SuperstepProgram

__all__ = ["Engine", "EngineResult", "collect"]

HARD_SUPERSTEP_CAP = 100_000


def span(bus, kind: str):
    """``bus.span(kind)`` on an engine's duck-typed event bus (the
    service's ``TraceBus``, which the plan cache attaches), or a no-op
    for an engine that has none."""
    return contextlib.nullcontext() if bus is None else bus.span(kind)


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under the ``__name__`` ``name`` (its signature kept):
    ``jax.jit`` names the XLA module after it, and the profiler's "XLA
    Modules" line shows that name for every execution of the program."""
    @functools.wraps(fn)
    def f(*args):
        return fn(*args)
    f.__name__ = f.__qualname__ = name
    return f


class _GravfmData(NamedTuple):
    vert_gid: jnp.ndarray       # (P, Vm) int32
    vert_valid: jnp.ndarray     # (P, Vm) bool
    out_deg: jnp.ndarray        # (P, Vm) int32
    flt_cnt: jnp.ndarray        # (P, Vm) int32 remote shards w/ neighbors
    src_slot: jnp.ndarray       # (L,) int32 lanes
    src_gid: jnp.ndarray        # (L,) int32
    src_outdeg: jnp.ndarray     # (L,) int32
    w: jnp.ndarray              # (L,) f32
    lane_valid: jnp.ndarray     # (L,) bool
    lane_remote: jnp.ndarray    # (L,) bool: src shard != dst shard
    seg: jnp.ndarray            # (L,) int32 clipped segment ids (finalize)
    # Pallas tile layout (backend="pallas"; None for "ref")
    wid: Optional[jnp.ndarray] = None      # (n_tiles,) int32
    rel: Optional[jnp.ndarray] = None      # (L,) int32
    written: Optional[jnp.ndarray] = None  # (n_windows,) bool
    # in-degree-class layout (backend="ref"; None for "pallas")
    inv: Optional[jnp.ndarray] = None      # (S,) int32 segment -> class row


class _GravfData(NamedTuple):
    vert_gid: jnp.ndarray
    vert_valid: jnp.ndarray
    out_deg: jnp.ndarray
    flt_cnt: jnp.ndarray
    pair_src_local: jnp.ndarray    # (P, P, E2)
    pair_src_gid: jnp.ndarray
    pair_src_outdeg: jnp.ndarray
    pair_w: jnp.ndarray
    pair_valid: jnp.ndarray
    recv_dst_local: jnp.ndarray    # (P, P, E2) static swapped dst locals


@dataclasses.dataclass
class EngineResult:
    state: Dict[str, np.ndarray]   # per-vertex global arrays (V,)
    supersteps: int
    messages: int                  # traversed edges (paper's TEPS numerator)
    comm: Dict[str, float]         # measured network words by scheme
    raw_state: Any = None          # sharded (P, Vm) state pytree
    # messages delivered to each shard (P,), summing to ``messages``;
    # None where the engine does not count them apart (``Engine``)
    shard_messages: Optional[Tuple[int, ...]] = None

    _FIELDS = ("state", "supersteps", "messages", "comm", "raw_state",
               "shard_messages")

    def __getitem__(self, key):
        """Dict-style access (``res["state"]``, ``res["exchange_words"]``)
        for callers written against the shard engine's historical result
        dicts; unknown keys fall through to ``comm``."""
        if key in self._FIELDS:
            return getattr(self, key)
        return self.comm[key]


def collect(pg: PartitionedGraph, state) -> Dict[str, np.ndarray]:
    """(P, Vm) shard layout -> (V,) global arrays."""
    out = {}
    for k, v in state.items():
        v = np.asarray(v)
        if v.ndim >= 2 and v.shape[:2] == (pg.num_parts, pg.v_max):
            out[k] = v[pg.part_of, pg.local_of]
        else:
            out[k] = v
    return out


class Engine:
    """Builds and runs the jitted superstep program for one (kernel, graph,
    mode) triple — the analogue of the paper's RTL elaboration."""

    def __init__(self, kernel: GasKernel, pg: PartitionedGraph, *,
                 mode: str = "gravfm", backend: str = "pallas",
                 tile_e: int = 512, tile_r: int = 256,
                 params: Optional[Dict[str, Any]] = None):
        assert mode in ("gravf", "gravfm")
        assert backend in ("pallas", "ref")
        self._name = f"{kernel.name}_{mode}"
        self.kernel = kernel
        self.pg = pg
        self.mode = mode
        self.backend = backend
        self.params = dict(params or {})
        self.params.setdefault("num_vertices", pg.num_vertices)

        P, Vm = pg.num_parts, pg.v_max
        self._P, self._Vm = P, Vm
        # remote-shard neighbor count per vertex (paper's filter bitmap)
        flt = pg.nbr_filter.copy()
        flt[np.arange(pg.num_vertices), pg.part_of] = False
        flt_cnt_g = flt.sum(axis=1).astype(np.int32)
        flt_cnt = np.zeros((P, Vm), np.int32)
        flt_cnt[pg.part_of, pg.local_of] = flt_cnt_g

        # the in-degree-class layout's padded lanes over its edges, and
        # its class count (mode "gravfm" on backend "ref"; else None)
        self.combine_pad_share: Optional[float] = None
        self.combine_classes: Optional[int] = None
        if mode == "gravfm":
            self._data = self._build_gravfm(flt_cnt, tile_e, tile_r)
        else:
            self._data = self._build_gravf(flt_cnt)

        # Trace accounting: the loop body bumps this Python counter, which
        # only executes while JAX is *tracing* — so it counts compilations,
        # not calls. The service plan cache asserts steady-state serving
        # performs zero re-traces against this.
        self.traces = 0
        self._device_resident = True
        # event bus for the execute/fetch/collect spans of run/run_batch
        # (duck-typed; the plan cache attaches the service's TraceBus)
        self.trace = None
        self._prog = self._make_program()
        self._steppers: Dict[int, LaneStepper] = {}
        self._loop = self._make_loop()
        self._step = jax.jit(named(self._loop, f"{self._name}_run"))
        self._batch_steps: Dict[int, Any] = {}

    # ------------------------------------------------------------------
    def _build_gravfm(self, flt_cnt, tile_e, tile_r) -> _GravfmData:
        pg, P, Vm = self.pg, self._P, self._Vm
        S = P * (Vm + 1)
        seg_flat = (np.arange(P, dtype=np.int64)[:, None] * (Vm + 1)
                    + pg.in_dst_local).reshape(-1)
        valid_flat = pg.in_valid.reshape(-1)
        # Padding edges already carry dst_local == Vm -> their segment is the
        # shard's discard bin; the array stays sorted.
        if self.backend == "pallas":
            layout = kops.build_layout(seg_flat, S, tile_e=tile_e,
                                       tile_r=tile_r)
        else:
            layout = build_classes(seg_flat, valid_flat, S)
            self.combine_pad_share = layout.pad_share
            self.combine_classes = len(layout.classes)
        self._layout = layout
        place = layout.place
        src_slot = place(pg.in_src_slot.reshape(-1), 0)
        src_gid = place(pg.in_src_gid.reshape(-1), 0)
        src_outdeg = place(pg.in_src_outdeg.reshape(-1), 1)
        w = place(pg.in_w.reshape(-1), 0.0)
        lane_valid = place(valid_flat, False) & layout.lane_valid
        seg = place(seg_flat.astype(np.int32), S)
        # src shard of each lane vs owning shard of its segment
        src_part = src_slot // Vm
        dst_part = seg // (Vm + 1)
        lane_remote = (src_part != dst_part) & lane_valid
        return _GravfmData(
            vert_gid=jnp.asarray(pg.vert_gid),
            vert_valid=jnp.asarray(pg.vert_valid),
            out_deg=jnp.asarray(pg.out_deg),
            flt_cnt=jnp.asarray(flt_cnt),
            src_slot=jnp.asarray(src_slot),
            src_gid=jnp.asarray(src_gid),
            src_outdeg=jnp.asarray(src_outdeg),
            w=jnp.asarray(w),
            lane_valid=jnp.asarray(lane_valid),
            lane_remote=jnp.asarray(lane_remote),
            seg=jnp.asarray(seg),
            **(dict(wid=jnp.asarray(layout.window_id),
                    rel=jnp.asarray(layout.rel),
                    written=jnp.asarray(layout.window_written))
               if self.backend == "pallas" else
               dict(inv=jnp.asarray(layout.inv))),
        )

    def _build_gravf(self, flt_cnt) -> _GravfData:
        pg = self.pg
        return _GravfData(
            vert_gid=jnp.asarray(pg.vert_gid),
            vert_valid=jnp.asarray(pg.vert_valid),
            out_deg=jnp.asarray(pg.out_deg),
            flt_cnt=jnp.asarray(flt_cnt),
            pair_src_local=jnp.asarray(pg.pair_src_local),
            pair_src_gid=jnp.asarray(pg.pair_src_gid),
            pair_src_outdeg=jnp.asarray(pg.pair_src_outdeg),
            pair_w=jnp.asarray(pg.pair_w),
            pair_valid=jnp.asarray(pg.pair_valid),
            recv_dst_local=jnp.asarray(pg.pair_dst_local.swapaxes(0, 1)),
        )

    # ------------------------------------------------------------------
    def _combine(self, data: _GravfmData, vals, combiner: str):  # analysis: traced
        """Segmented combine over the CSC lanes: the Pallas kernel over
        the tile layout carried in ``data``, or one dense reduce per
        in-degree class (its padding lanes set to the identity)."""
        lo = self._layout
        if self.backend == "pallas":
            return segment_combine_windows(
                data.wid, data.rel, vals, combiner=combiner,
                tile_e=lo.tile_e, tile_r=lo.tile_r, n_windows=lo.n_windows,
                window_written=data.written, num_segments=lo.num_segments)
        ident = kops.identity_for(combiner, vals.dtype)
        return kref.segment_combine(jnp.where(data.lane_valid, vals, ident),
                                    ClassSegments(data.inv, lo.classes),
                                    lo.num_segments, combiner)

    def _deliver_gravfm(self, data: _GravfmData, payload, active):  # analysis: traced
        """Broadcast updates; receiver-side scatter + gather-combine."""
        k, P, Vm = self.kernel, self._P, self._Vm
        payload_flat = payload.reshape(P * Vm)
        active_flat = active.reshape(P * Vm)
        # THE broadcast: every shard reads every shard's updates (lowers to
        # all_gather of the |V|-bounded update array under SPMD sharding).
        vals = jnp.take(payload_flat, data.src_slot)
        act = jnp.take(active_flat, data.src_slot) & data.lane_valid
        msg = k.scatter(vals, data.w, data.src_gid, data.src_outdeg)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        masked = jnp.where(act, msg, ident)

        acc_full = self._combine(data, masked, k.combiner)
        acc = acc_full.reshape(P, Vm + 1)[:, :Vm]

        if k.got_from_identity:
            got = acc != ident
        else:
            gv = jnp.where(act, 1, 0).astype(jnp.int32)
            got_full = self._combine(data, gv, "max")
            got = got_full.reshape(P, Vm + 1)[:, :Vm] > 0

        with jax.named_scope("gravfm.stats"):
            n_msgs = jnp.sum(act.astype(jnp.int32))
            n_remote_msgs = jnp.sum(
                (act & data.lane_remote).astype(jnp.int32))
        return acc, got, {"n_msgs": n_msgs, "n_remote": n_remote_msgs}

    def _deliver_gravf(self, data: _GravfData, payload, active):  # analysis: traced
        """Source-side scatter, unicast exchange (paper Fig. 4 left)."""
        k, P, Vm = self.kernel, self._P, self._Vm
        pe = jnp.broadcast_to(payload[:, None, :], (P, P, Vm))
        ae = jnp.broadcast_to(active[:, None, :], (P, P, Vm))
        vals = jnp.take_along_axis(pe, data.pair_src_local, axis=2)
        act = jnp.take_along_axis(ae, data.pair_src_local, axis=2)
        act = act & data.pair_valid
        msg = k.scatter(vals, data.pair_w, data.pair_src_gid,
                        data.pair_src_outdeg)
        ident = kops.identity_for(k.combiner, k.msg_dtype)
        masked = jnp.where(act, msg, ident)

        # THE unicast exchange: shard-axis transpose (lowers to all_to_all).
        recv = jnp.swapaxes(masked, 0, 1)
        recv_act = jnp.swapaxes(act, 0, 1)
        seg = (jnp.arange(P, dtype=jnp.int32)[:, None, None] * (Vm + 1)
               + data.recv_dst_local)
        S = P * (Vm + 1)
        acc_full = kref.segment_combine(
            recv.reshape(-1), seg.reshape(-1), S, k.combiner)
        acc = acc_full.reshape(P, Vm + 1)[:, :Vm]

        if k.got_from_identity:
            got = acc != ident
        else:
            got_full = kref.segment_combine(
                jnp.where(recv_act, 1, 0).astype(jnp.int32).reshape(-1),
                seg.reshape(-1), S, "max")
            got = got_full.reshape(P, Vm + 1)[:, :Vm] > 0

        with jax.named_scope("gravfm.stats"):
            n_msgs = jnp.sum(act.astype(jnp.int32))
            cross = ~jnp.eye(P, dtype=bool)[:, :, None]
            n_remote = jnp.sum((act & cross).astype(jnp.int32))
        return acc, got, {"n_msgs": n_msgs, "n_remote": n_remote}

    def _edge_view(self, data, state) -> LaneView:  # analysis: traced
        """The EdgeView of this engine's receiver-side lanes: the CSC
        lanes (gravfm), or the pair blocks as the receiver folds them
        after the exchange (gravf)."""
        P, Vm = self._P, self._Vm
        if self.mode == "gravfm":
            src_slot, seg = data.src_slot, data.seg
            attrs = (data.w, data.src_gid, data.src_outdeg, data.lane_valid)

            def combine(vals, combiner):
                return self._combine(data, vals, combiner)
        else:
            shard = jnp.arange(P, dtype=jnp.int32)[:, None, None]

            def recv(a):  # source-side (P, P, E2) -> receiver lanes
                return jnp.swapaxes(a, 0, 1).reshape(-1)

            src_slot = recv(shard * Vm + data.pair_src_local)
            seg = (shard * (Vm + 1) + data.recv_dst_local).reshape(-1)
            attrs = tuple(map(recv, (data.pair_w, data.pair_src_gid,
                                     data.pair_src_outdeg,
                                     data.pair_valid)))

            def combine(vals, combiner):
                return kref.segment_combine(vals, seg, P * (Vm + 1),
                                            combiner)

        def src_of(key):
            return jax.vmap(lambda x: jnp.take(x.reshape(-1), src_slot))(
                state[key])

        w, src_gid, src_outdeg, valid = attrs
        return LaneView(src_of=src_of, seg=seg, combine=combine,
                        vshape=(P, Vm), w=w, src_gid=src_gid,
                        src_outdeg=src_outdeg, valid=valid)

    # ------------------------------------------------------------------
    def _make_program(self) -> SuperstepProgram:
        """The step-granular core: one superstep = deliver -> gather ->
        stats -> apply, factored so run/run_batch (while_loop over it)
        and the service's continuous scheduler (host-driven, one step at
        a time) execute the exact same traced computation."""
        deliver = (self._deliver_gravfm if self.mode == "gravfm"
                   else self._deliver_gravf)
        P = self._P

        def init_stats():
            return {
                "messages": jnp.int32(0),
                "unicast_words": jnp.float32(0.0),
                "bcast_naive_words": jnp.float32(0.0),
                "bcast_filtered_words": jnp.float32(0.0),
            }

        def update_stats(stats, data, active, aux):
            n_act = jnp.sum(active.astype(jnp.int32))
            n_flt = jnp.sum(jnp.where(active, data.flt_cnt, 0))
            return {
                "messages": stats["messages"] + aux["n_msgs"],
                "unicast_words":
                    stats["unicast_words"]
                    + aux["n_remote"].astype(jnp.float32),
                "bcast_naive_words":
                    stats["bcast_naive_words"]
                    + (n_act * (P - 1)).astype(jnp.float32),
                "bcast_filtered_words":
                    stats["bcast_filtered_words"]
                    + n_flt.astype(jnp.float32),
            }

        return SuperstepProgram(self.kernel, deliver,
                                init_stats=init_stats,
                                update_stats=update_stats,
                                edge_view=self._edge_view)

    def _make_batch_program(self, batch: int):
        """The jitted program run_batch dispatches for ``batch`` queries:
        a leading query axis on the per-query kwargs. vmap of the
        while_loop freezes finished queries' carries (their cond is
        False), so quiescent queries ride along at zero semantic cost
        until the whole batch terminates; the kernel's finalize then
        runs after the loop, outside the vmap. One jit per batch
        size, so that each compiled program has its own module name."""
        fn = self._batch_steps.get(batch)
        if fn is None:
            prog = self._prog

            def run_batch(data, cap, query_kwargs):
                self.traces += 1  # trace-time side effect (see run)
                c = jax.vmap(lambda kw: prog.while_run(
                    data, cap, self.params, kw))(query_kwargs)
                c = prog.finalize(data, c)
                return c.state, c.superstep, c.stats

            fn = self._batch_steps[batch] = jax.jit(
                named(run_batch, f"{self._name}_batch{batch}"))
        return fn

    def _make_loop(self):
        prog = self._prog

        def loop(data, cap, query_kwargs):
            self.traces += 1  # Python side effect: runs at trace time only
            c = prog.while_run(data, cap, self.params, query_kwargs)
            c = prog.finalize_one(data, c)
            return c.state, c.superstep, c.stats

        return loop

    # ------------------------------------------------------------------
    @property
    def device_resident(self) -> bool:
        """Whether the graph-layout pytree currently lives in device
        buffers (vs host-spill numpy copies)."""
        return self._device_resident

    @property
    def device_nbytes(self) -> int:
        """Bytes of the engine-tier graph layout (the pytree the jitted
        programs are driven with — exactly what :meth:`offload` demotes).
        The GraphStore charges these true engine-tier bytes against its
        budget instead of the partition-layout proxy."""
        return int(sum(a.nbytes for a in jax.tree.leaves(self._data)))

    def offload(self) -> int:
        """Demote the graph's device arrays to host (numpy) copies — the
        engine tier of the GraphStore's host-spill residency. The traced
        programs (and their jit caches) survive untouched; dispatching
        while offloaded still works (the runtime re-uploads per call),
        it is just slower until :meth:`upload` promotes the arrays back.
        Returns the bytes demoted."""
        if not self._device_resident:
            return 0
        host = jax.tree.map(np.asarray, self._data)
        self._rebind_data(host, resident=False)
        return int(sum(a.nbytes for a in jax.tree.leaves(host)))

    def upload(self) -> float:
        """Promote offloaded graph arrays back into device buffers.
        Shapes/dtypes are unchanged, so the next dispatch hits the
        existing jit cache — the spill/refault contract is zero
        re-traces. Returns the wall seconds the upload took."""
        if self._device_resident:
            return 0.0
        t0 = time.perf_counter()
        data = jax.tree.map(jnp.asarray, self._data)
        jax.block_until_ready(data)
        self._rebind_data(data, resident=True)
        return time.perf_counter() - t0

    def _rebind_data(self, data, *, resident: bool) -> None:
        self._data = data
        self._device_resident = resident
        for st in self._steppers.values():
            st.bind_data(data)

    # ------------------------------------------------------------------
    def _check_query_kwargs(self, kwargs: Dict[str, Any]) -> None:
        # A misspelled name would be swallowed by init_state's **_ and the
        # kernel would silently run with its defaults — reject instead.
        unknown = set(kwargs) - set(self.kernel.query_params)
        if unknown:
            raise ValueError(
                f"kernel {self.kernel.name!r} takes query params "
                f"{tuple(self.kernel.query_params)}, got unexpected "
                f"{sorted(unknown)}")

    def run(self, max_supersteps: Optional[int] = None,
            **query_kwargs) -> EngineResult:
        """Single query. ``query_kwargs`` (e.g. ``root=7``) are traced
        scalars forwarded to the kernel's ``init_state`` — they override
        the constructor ``params`` without re-tracing."""
        cap = max_supersteps or self.kernel.max_supersteps or HARD_SUPERSTEP_CAP
        self._check_query_kwargs(query_kwargs)
        qkw = {kk: jnp.asarray(v) for kk, v in query_kwargs.items()}
        with span(self.trace, "execute"):
            out = self._step(self._data, jnp.int32(cap), qkw)
            jax.block_until_ready(out)
        with span(self.trace, "fetch"):
            state, s, stats = jax.tree.map(np.asarray, out)
        with span(self.trace, "collect"):
            comm = {kk: float(v) for kk, v in stats.items()}
            comm["scheme"] = ("gravfm_broadcast" if self.mode == "gravfm"
                              else "gravf_unicast")
            comm["wire_words"] = comm[self.wire_stat]
            return EngineResult(
                state=collect(self.pg, state),
                supersteps=int(s),
                messages=int(stats["messages"]),
                comm=comm,
                raw_state=state,
            )

    def run_batch(self, max_supersteps: Optional[int] = None,
                  **query_arrays) -> "list[EngineResult]":
        """One superstep loop over a leading query-batch axis.

        ``query_arrays`` maps per-query kernel parameters (the kernel's
        ``query_params``, e.g. BFS/SSSP ``root``) to (B,) arrays. All B
        queries share every per-superstep broadcast/exchange; per-query
        termination masks (the vmapped while_loop carry select) let
        finished queries go quiescent without stalling the batch.
        Returns one :class:`EngineResult` per query, bit-identical to B
        sequential :meth:`run` calls.
        """
        if not query_arrays:
            raise ValueError(
                "run_batch needs at least one per-query array, e.g. "
                "root=np.array([...]); see GasKernel.query_params")
        self._check_query_kwargs(query_arrays)
        cap = max_supersteps or self.kernel.max_supersteps or HARD_SUPERSTEP_CAP
        qkw = {kk: jnp.atleast_1d(jnp.asarray(v))
               for kk, v in query_arrays.items()}
        sizes = {kk: v.shape[0] for kk, v in qkw.items()}
        batch = next(iter(sizes.values()))
        if any(b != batch for b in sizes.values()):
            raise ValueError(f"inconsistent query batch sizes: {sizes}")
        with span(self.trace, "execute"):
            out = self._make_batch_program(batch)(self._data, jnp.int32(cap),
                                             qkw)
            jax.block_until_ready(out)
        with span(self.trace, "fetch"):
            state, s, stats = jax.tree.map(np.asarray, out)
        with span(self.trace, "collect"):
            comm_scheme = ("gravfm_broadcast" if self.mode == "gravfm"
                           else "gravf_unicast")
            results = []
            for q in range(batch):
                state_q = jax.tree.map(lambda a: a[q], state)
                comm = {kk: float(v[q]) for kk, v in stats.items()}
                comm["scheme"] = comm_scheme
                comm["wire_words"] = comm[self.wire_stat]
                results.append(EngineResult(
                    state=collect(self.pg, state_q),
                    supersteps=int(s[q]),
                    messages=int(stats["messages"][q]),
                    comm=comm,
                    raw_state=state_q,
                ))
            return results

    def lower_batch(self, batch: int) -> jax.stages.Lowered:
        """Lower the program :meth:`run_batch` dispatches for ``batch``
        queries against this engine's graph arrays, without running it.
        ``.compile()`` on the result gives the compiler's verdict for the
        device: ``memory_analysis()`` says whether the batch fits, and
        ``as_text()`` shows which kernels it holds."""
        qkw = {p: jax.ShapeDtypeStruct((batch,), jnp.int32)
               for p in self.kernel.query_params}
        cap = self.kernel.max_supersteps or HARD_SUPERSTEP_CAP
        return self._make_batch_program(batch).lower(
            self._data, jnp.int32(cap), qkw)

    def lower(self, batch: int) -> jax.stages.Lowered:
        """Lower the program a plan of ``batch`` queries dispatches with
        int32 query parameters: :meth:`run`'s for one query,
        :meth:`run_batch`'s otherwise. It shares JAX's trace and compile
        caches with that dispatch, so compiling it first costs the
        dispatch nothing more."""
        if batch > 1:
            return self.lower_batch(batch)
        qkw = {p: jax.ShapeDtypeStruct((), jnp.int32)
               for p in self.kernel.query_params}
        cap = self.kernel.max_supersteps or HARD_SUPERSTEP_CAP
        return self._step.lower(self._data, jnp.int32(cap), qkw)

    # ------------------------------------------------------------------
    def make_stepper(self, width: int) -> LaneStepper:
        """A host-drivable ``width``-lane slot array over this engine's
        superstep program — the step-granular entry point the continuous
        scheduler drives (admit / one-superstep / probe / retire). Lanes
        run the same vmapped computation as :meth:`run_batch`, so a lane
        is bit-identical to a solo :meth:`run` of its query regardless
        of which superstep it was spliced in at. Cached per width: the
        jitted admit/step programs trace once, then recycle slots
        forever with zero re-traces."""
        assert width >= 1
        st = self._steppers.get(width)
        if st is None:
            st = LaneStepper(self._prog, self._data, self.params, width,
                             trace_hook=self._bump_traces,
                             wire_stat=self.wire_stat)
            self._steppers[width] = st
        return st

    @property
    def wire_stat(self) -> str:
        """Which stats entry counts the words this mode's scheme actually
        puts on the wire (filtered broadcast for GraVF-M, per-edge unicast
        for GraVF) — surfaced uniformly as ``comm["wire_words"]``."""
        return ("bcast_filtered_words" if self.mode == "gravfm"
                else "unicast_words")

    def _bump_traces(self) -> None:
        self.traces += 1

    def lane_result(self, carry_host, lane: int) -> EngineResult:
        """Package one retired lane of a host-fetched stepper carry as an
        :class:`EngineResult` (same fields as :meth:`run`)."""
        state_q = jax.tree.map(lambda a: np.asarray(a[lane]),
                               carry_host.state)
        comm = {kk: float(v[lane]) for kk, v in carry_host.stats.items()}
        comm["scheme"] = ("gravfm_broadcast" if self.mode == "gravfm"
                          else "gravf_unicast")
        comm["wire_words"] = comm[self.wire_stat]
        return EngineResult(
            state=collect(self.pg, state_q),
            supersteps=int(carry_host.superstep[lane]),
            messages=int(carry_host.stats["messages"][lane]),
            comm=comm,
            raw_state=state_q,
        )
