"""Step-granular superstep core.

The engines used to bake the whole superstep iteration into one opaque
``jax.lax.while_loop``: you could run a query to completion, but nothing
could observe or intervene *between* supersteps. :class:`SuperstepProgram`
factors that loop into three small pure functions over an explicit
:class:`StepCarry`:

  init_carry(data, params, query_kwargs) -> carry
      kernel ``init_state`` + the superstep-0 ``apply`` (paper §4.3: "the
      barrier is injected into the apply modules to begin execution").
  step(data, carry) -> carry
      exactly ONE superstep: deliver (broadcast/exchange + receiver-side
      scatter + gather-combine) -> gather -> stats -> next apply.
  alive(carry)
      the per-program termination bit (any vertex still active).

The same traced ``step`` is then driven three ways:

  * ``while_run`` — a ``lax.while_loop`` over ``step``: the engines'
    fast path, bit-identical to the pre-refactor monolithic loop (same
    ops in the same order, same trace counts).
  * ``jax.vmap`` of ``while_run`` / of ``step`` — the query-batched
    paths (``run_batch`` and the shard_map batched program).
  * :class:`LaneStepper` — a host-drivable W-lane handle (jitted
    admit/step/probe) that the service's continuous scheduler uses to
    retire finished queries mid-flight and splice newly arrived roots
    into freed lanes between supersteps.

Both engines parameterize the program with their own ``deliver`` (which
collective moves the updates), stats fold and ``edge_view`` (the
:class:`~.gas.EdgeView` a kernel's ``finalize`` reads); the loop
structure lives here once.

  finalize(data, carry) -> carry
      the kernel's pass over the in-edges once its queries have stopped
      (SSSP's parent pointers), over a carry that leads with the query
      axis; the identity for a kernel without one. ``while_run``'s
      callers apply it inside the same compiled program, and a lane
      stepper's ``fetch`` applies it to the retiring lanes alone before
      the host copy.

Because the carry is explicit, a lane is *preemptible*: between
supersteps its carry slice is host-fetchable (``fetch_lane``) and can be
spliced back later (``restore``) to resume bit-identically — something a
whole-run ``lax.while_loop`` can never offer. :class:`LaneTable` packages
that lifecycle (slot occupancy, per-lane scheduling metadata, the
checkpoint/restore verbs) for the service's continuous scheduler.
"""
from __future__ import annotations

import dataclasses
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["StepCarry", "SuperstepProgram", "LaneStepper",
           "LaneStepperBase", "LaneView", "select_lanes",
           "LaneMeta", "LaneCheckpoint", "LaneTable", "lane_dtype",
           "PRIORITY_BOOST_S"]

# One request-priority level is worth this many seconds of deadline
# urgency. Kept finite (rather than a lexicographic priority dimension)
# so a parked lane's deadline-aging credit can eventually exceed ANY
# priority boost — the starvation-freedom guarantee.
PRIORITY_BOOST_S = 60.0


class StepCarry(NamedTuple):
    """Everything one in-flight query owns between supersteps."""
    state: Any              # kernel state pytree of per-vertex arrays
    payload: jnp.ndarray    # pending update values (apply output)
    active: jnp.ndarray     # pending update mask
    superstep: jnp.ndarray  # int32 supersteps completed
    stats: Dict[str, jnp.ndarray]


def select_lanes(mask, new, old):
    """Per-lane carry select: lanes where ``mask`` is True take ``new``,
    the rest keep ``old`` (the explicit form of the freeze that vmap of
    while_loop performs on finished lanes, under the same device scope,
    ``gravfm.loop``)."""
    def sel(n, o):
        b = mask.reshape((mask.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(b, n, o)
    with jax.named_scope("gravfm.loop"):
        return jax.tree.map(sel, new, old)


class LaneView:
    """The :class:`~.gas.EdgeView` both engines build: edge lanes that
    index flat per-vertex arrays, for every query of a state whose
    leaves lead with the query axis.

    ``src_of(key)`` gives state leaf ``key`` at each lane's source,
    (Q, lanes); ``any_of(pred)`` reduces a predicate to one scalar over
    every shard. ``seg`` is each lane's destination in a query's
    per-vertex array padded by one discard slot per shard (``vshape``
    with its last axis one longer, flattened), as the engine's
    segmented ``combine(vals, combiner)`` numbers its segments.
    """

    def __init__(self, *, src_of, seg, combine, vshape,
                 w, src_gid, src_outdeg, valid, any_of=jnp.any):
        self.src = src_of
        self.any = any_of
        self.w, self.src_gid, self.src_outdeg = w, src_gid, src_outdeg
        self.valid = valid
        self._seg, self._combine = seg, combine
        self._padded = tuple(vshape[:-1]) + (vshape[-1] + 1,)

    def dst(self, x):
        def one(x):
            pad = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 1)]).reshape(-1)
            return jnp.take(pad, jnp.minimum(self._seg, pad.size - 1))
        return jax.vmap(one)(x)

    def min(self, vals):
        return jax.vmap(lambda v: self._combine(v, "min").reshape(
            self._padded)[..., :-1])(vals)

    def count(self, mask):
        return jnp.sum(mask.reshape(mask.shape[0], -1), axis=1,
                       dtype=jnp.int32)


class SuperstepProgram:
    """init/step/alive/finalize for one (kernel, graph layout, deliver)
    triple.

    ``deliver(data, payload, active)`` returns ``(acc, got, aux)`` where
    ``aux`` is a dict of per-superstep scalars folded into the running
    stats by ``update_stats(stats, data, active, aux)`` (``active`` is
    the pre-apply mask of the superstep being folded). ``global_any``
    reduces the local activity bit across shards (identity for the
    global-array engine, ``pmax`` inside shard_map). ``edge_view(data,
    state)`` builds the engine's EdgeView over a state that leads with
    the query axis.
    """

    def __init__(self, kernel, deliver: Callable[..., Any], *,
                 init_stats: Callable[[], Dict[str, jnp.ndarray]],
                 update_stats: Callable[..., Dict[str, jnp.ndarray]],
                 edge_view: Callable[..., Any],
                 global_any: Optional[Callable[[jnp.ndarray],
                                               jnp.ndarray]] = None):
        self.kernel = kernel
        self.deliver = deliver
        self.init_stats = init_stats
        self.update_stats = update_stats
        self.edge_view = edge_view
        self.global_any = global_any or (lambda b: b)

    # ------------------------------------------------------------------
    # Every phase runs under a ``jax.named_scope`` (``gravfm.<phase>``:
    # init, deliver, gather, stats, apply, cond, loop for the loop's own
    # lane selects, and finalize; the shard engine adds exchange):
    # metadata only, so the compiled instructions are unchanged, but each
    # fusion's ``op_name`` then names the phase it came from, and the
    # service maps device ops in a profiler trace back to the phase.

    def init_carry(self, data, params: Dict[str, Any],
                   query_kwargs: Dict[str, Any]) -> StepCarry:
        k = self.kernel
        with jax.named_scope("gravfm.init"):
            state = k.init_state(data.vert_gid, data.out_deg,
                                 data.vert_valid,
                                 **{**params, **query_kwargs})
            state, payload, active = k.apply(state, data.vert_gid,
                                             data.out_deg, 0)
            active = active & data.vert_valid
            return StepCarry(state, payload, active, jnp.int32(0),
                             self.init_stats())

    def step_deliver(self, data, carry: StepCarry):
        """Scatter/exchange, the edge pass: move this superstep's pending
        updates to their receivers. Returns the opaque delivered tuple
        ``(acc, got, aux)`` that ``step_combine`` folds."""
        with jax.named_scope("gravfm.deliver"):
            return self.deliver(data, carry.payload, carry.active)

    def step_combine(self, data, carry: StepCarry, delivered) -> StepCarry:
        """Gather-combine the delivered updates into vertex state and
        fold the superstep's stats. Same superstep index as ``carry``
        (the counter advances in ``step_apply``)."""
        k = self.kernel
        state, payload, active, s, stats = carry
        acc, got, aux = delivered
        with jax.named_scope("gravfm.gather"):
            state = k.gather(state, acc, got, s)
        with jax.named_scope("gravfm.stats"):
            stats = self.update_stats(stats, data, active, aux)
        return StepCarry(state, payload, active, s, stats)

    def step_apply(self, data, mid: StepCarry) -> StepCarry:
        """The vertex apply of the *next* superstep's updates: advances
        the superstep counter and re-masks activity."""
        k = self.kernel
        state, _, active, s, stats = mid
        with jax.named_scope("gravfm.apply"):
            state, payload, active = k.apply(state, data.vert_gid,
                                             data.out_deg, s + 1)
            active = active & data.vert_valid
            return StepCarry(state, payload, active, s + 1, stats)

    def step(self, data, carry: StepCarry) -> StepCarry:
        return self.step_apply(data, self.step_combine(
            data, carry, self.step_deliver(data, carry)))

    def alive(self, carry: StepCarry) -> jnp.ndarray:
        with jax.named_scope("gravfm.cond"):
            return self.global_any(jnp.any(carry.active))

    def is_done(self, carry: StepCarry) -> jnp.ndarray:
        return ~self.alive(carry)

    # ------------------------------------------------------------------
    def while_run(self, data, cap, params: Dict[str, Any],
                  query_kwargs: Dict[str, Any]) -> StepCarry:
        """The fast path: run to quiescence (or ``cap``) in one
        ``lax.while_loop`` over ``step``. The loop itself runs under
        ``gravfm.loop``, which names what vmap adds to a batched loop
        (the per-lane freeze of finished lanes' carries)."""
        carry = self.init_carry(data, params, query_kwargs)

        def cond(c):
            with jax.named_scope("gravfm.cond"):
                return self.alive(c) & (c.superstep < cap)

        def body(c):
            return self.step(data, c)

        with jax.named_scope("gravfm.loop"):
            return jax.lax.while_loop(cond, body, carry)

    def finalize(self, data, carry: StepCarry) -> StepCarry:
        """The kernel's ``finalize`` over ``carry``, whose leaves lead
        with the query axis; its counters join the stats."""
        if self.kernel.finalize is None:
            return carry
        with jax.named_scope("gravfm.finalize"):
            view = self.edge_view(data, carry.state)
            state, counters = self.kernel.finalize(
                carry.state, data.vert_gid, view)
        return carry._replace(state=state,
                              stats={**carry.stats, **counters})

    def finalize_one(self, data, carry: StepCarry) -> StepCarry:
        """:meth:`finalize` of one query's carry (no query axis)."""
        if self.kernel.finalize is None:
            return carry
        one = jax.tree.map(lambda a: a[None], carry)
        return jax.tree.map(lambda a: a[0], self.finalize(data, one))


class LaneStepperBase:
    """Host-side plumbing shared by every lane stepper (the global-array
    LaneStepper below and engine_shardmap's ShardLaneStepper): the
    (carry, lane_active, supersteps) return contract, kwarg upload, and
    host fetch. Subclasses provide the jitted ``_init``/``_admit``/
    ``_step``/``_probe``/``_fetch_lane``/``_restore`` programs (the
    lane-indexing axis differs: the global-array stepper's carry leads
    with the lane axis, the shard stepper's with the shard axis)."""

    # cumulative wire words (across all lanes) as of the last dispatch —
    # updated by ``_unpack`` when the fused probe carries a words element;
    # LaneTable.step turns consecutive values into per-superstep deltas
    # for the trace bus.
    last_wire_words: float = 0.0

    def _unpack(self, out):
        carry = out[0]
        if len(out) > 3:
            self.last_wire_words = float(np.asarray(out[3]))
        return carry, np.asarray(out[1]), np.asarray(out[2])

    @staticmethod
    def _qdev(qkw: Dict[str, np.ndarray]):
        return {k: jnp.asarray(v) for k, v in qkw.items()}

    def probe(self, carry: StepCarry):
        out = self._probe(carry)
        return np.asarray(out[0]), np.asarray(out[1])

    # jitted ``(carry, lanes) -> carry``: the carry of the ``lanes``
    # slots, gathered into a narrower slot array and passed through the
    # kernel's finalize; None for a kernel without one. Set by subclasses,
    # which also say which axis of a carry leaf indexes the lanes.
    _finalize = None
    _lane_axis = 0

    def fetch_widths(self) -> List[int]:
        """Slot-array widths ``fetch`` runs a compiled finalize at: one
        lane, and the stepper's width; none for a kernel without a
        finalize. Nothing between: the chip works a batch of queries in
        rows 128 wide, so on a v5e at Graph500 scale 18 two lanes took
        0.40 s, all 32 0.37 s and one 0.21 s."""
        if self._finalize is None:
            return []
        return sorted({1, self.width})

    def fetch(self, carry: StepCarry,
              lanes: Optional[Sequence[int]] = None) -> StepCarry:
        """Host copy of the carry of ``lanes`` (every lane when None), in
        that order along the lane axis. A kernel's ``finalize`` runs on
        the device first, over those lanes only: they are gathered into a
        slot array of the narrowest ``fetch_widths`` width that holds
        them (padded with copies of the last), so lanes still in flight
        take no part in the pass or its tie-break."""
        if self._finalize is None:
            host = jax.tree.map(np.asarray, carry)
            if lanes is None:
                return host
            return jax.tree.map(
                lambda a: np.take(a, list(lanes), axis=self._lane_axis),
                host)
        lanes = (list(range(self.width)) if lanes is None
                 else [int(i) for i in lanes])
        n = len(lanes)
        k = next(w for w in self.fetch_widths() if w >= max(n, 1))
        idx = np.asarray(lanes + (lanes or [0])[-1:] * (k - n), np.int32)
        out = self._finalize(carry, jnp.asarray(idx))
        head = (slice(None),) * self._lane_axis + (slice(0, n),)
        return jax.tree.map(lambda a: np.asarray(a)[head], out)

    def fetch_lane(self, carry: StepCarry, lane: int) -> StepCarry:
        """Host copy of exactly ONE lane's carry slice (the checkpoint
        payload): only that lane's bytes cross the device->host boundary,
        not the whole slot array. The lane index is a traced scalar, so
        parking different lanes re-traces nothing."""
        return jax.tree.map(np.asarray,
                            self._fetch_lane(carry, jnp.int32(lane)))

    def restore(self, carry: StepCarry, lane_carry: StepCarry,
                fresh: np.ndarray):
        """Splice a checkpointed lane's carry back into ``fresh`` slots
        of the in-flight slot array — the admit-path select with the
        parked carry instead of a fresh ``init_carry``, so the lane
        resumes bit-identically from its parked superstep (state,
        superstep counter and running stats all survive verbatim)."""
        if getattr(self, "_restore", None) is None:
            raise RuntimeError(
                "stepper has no compiled programs yet; init() a slot "
                "array before restoring a checkpoint into it")
        lane_dev = jax.tree.map(jnp.asarray, lane_carry)
        return self._unpack(self._restore(carry, lane_dev,
                                          jnp.asarray(fresh)))

    def bind_data(self, data) -> None:
        """Swap the graph-layout pytree the jitted programs are driven
        with — the engine's offload/upload across the store's host-spill
        tier. Shapes/dtypes must match the original (the jit caches key
        on avals, so a rebind re-traces nothing)."""
        self._data = data


class LaneStepper(LaneStepperBase):
    """Host-drivable fixed-width slot array over a SuperstepProgram.

    All functions are jitted once per (width, dtypes) signature; the
    fresh/alive masks are traced values, so steady-state slot recycling
    re-traces nothing (``trace_hook`` — usually the owning engine's
    trace counter bump — fires at trace time only, which the service's
    plan cache asserts against).

    ``init``/``admit``/``step`` return ``(carry, lane_active (W,),
    supersteps (W,))`` — the probe is fused into the same device call,
    so the continuous scheduler's steady state costs exactly ONE
    dispatch per superstep (and blocks on only 2·W scalars, not the
    vertex state).

      init(qkw)                -> all W lanes initialized
      admit(carry, qkw, fresh) -> ``fresh`` lanes re-initialized
      step(carry, alive)       -> one superstep for ``alive`` lanes,
                                  everything else frozen
      probe(carry)             -> host (lane_active (W,), supersteps (W,))
      fetch(carry, lanes)      -> host copy of those lanes' carry, finalized
    """

    def __init__(self, prog: SuperstepProgram, data, params: Dict[str, Any],
                 width: int, *, trace_hook: Callable[[], None] = None,
                 wire_stat: Optional[str] = None):
        self.width = width
        hook = trace_hook or (lambda: None)

        def probe_of(carry):
            # ``wire_stat`` names the stats entry that counts words this
            # engine's scheme actually puts on the wire; its lane sum
            # rides the fused probe so per-superstep traffic telemetry
            # costs no extra dispatch (see LaneStepperBase._unpack)
            out = (jax.vmap(lambda c: jnp.any(c.active))(carry),
                   carry.superstep)
            if wire_stat is not None:
                out = out + (jnp.sum(carry.stats[wire_stat]),)
            return out

        def init_fn(d, qkw):
            hook()
            c = jax.vmap(lambda kw: prog.init_carry(d, params, kw))(qkw)
            return (c, *probe_of(c))

        def admit_fn(d, carry, qkw, fresh):
            hook()
            new = jax.vmap(
                lambda kw: prog.init_carry(d, params, kw))(qkw)
            c = select_lanes(fresh, new, carry)
            return (c, *probe_of(c))

        def step_fn(d, carry, alive):
            hook()
            new = jax.vmap(lambda c: prog.step(d, c))(carry)
            c = select_lanes(alive, new, carry)
            return (c, *probe_of(c))

        def fetch_lane_fn(carry, lane):
            hook()
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, lane, 0, keepdims=False), carry)

        def restore_fn(carry, lane_carry, fresh):
            hook()
            new = jax.tree.map(
                lambda leaf: jnp.broadcast_to(leaf[None],
                                              (width,) + leaf.shape),
                lane_carry)
            c = select_lanes(fresh, new, carry)
            return (c, *probe_of(c))

        def finalize_fn(d, carry, lanes):
            hook()
            return prog.finalize(d, jax.tree.map(
                lambda a: jnp.take(a, lanes, axis=0), carry))

        self._data = data
        if prog.kernel.finalize is not None:
            fin = jax.jit(finalize_fn)
            self._finalize = lambda c, lanes: fin(self._data, c, lanes)
        self._init = jax.jit(init_fn)
        self._admit = jax.jit(admit_fn)
        self._step = jax.jit(step_fn)
        self._probe = jax.jit(probe_of)
        self._fetch_lane = jax.jit(fetch_lane_fn)
        self._restore = jax.jit(restore_fn)

    def init(self, qkw: Dict[str, np.ndarray]):
        return self._unpack(self._init(self._data, self._qdev(qkw)))

    def admit(self, carry: StepCarry, qkw: Dict[str, np.ndarray],
              fresh: np.ndarray):
        return self._unpack(self._admit(self._data, carry,
                                        self._qdev(qkw),
                                        jnp.asarray(fresh)))

    def step(self, carry: StepCarry, alive: np.ndarray):
        return self._unpack(self._step(self._data, carry,
                                       jnp.asarray(alive)))


# ---------------------------------------------------------------------------
# lane lifecycle: LaneTable + checkpoint/restore
# ---------------------------------------------------------------------------

def lane_dtype(value) -> np.dtype:
    """Canonical lane-array dtype for a query kwarg (matches the int32 /
    float32 the kernels trace with, so admits never change signature)."""
    a = np.asarray(value)
    if a.dtype.kind in "iub":
        return np.dtype(np.int32)
    if a.dtype.kind == "f":
        return np.dtype(np.float32)
    return a.dtype


@dataclasses.dataclass
class LaneMeta:
    """Per-lane scheduling metadata. ``payload`` is opaque to the core
    (the service stores its (request, future) pair there); everything
    else is what admission, preemption and depth packing decide on.

    ``credit_s`` is the deadline-aging credit a lane accrues while
    parked: the scheduler subtracts it from ``deadline_s`` when ranking,
    so a repeatedly preempted query becomes monotonically more urgent
    and cannot starve (and, once restored, is not the first victim of
    the next preemption)."""

    payload: Any
    qkw: Dict[str, Any]
    tenant: str = "default"
    priority: int = 0
    deadline_s: float = float("inf")
    predicted_depth: float = 0.0
    credit_s: float = 0.0
    parks: int = 0
    seq: int = 0
    # depth-prediction bucket label ("d<decile>" of the root's degree,
    # or None): which per-bucket depth EWMA predicted_depth came from —
    # retirement scores the observation back into the same bucket
    depth_bucket: Optional[str] = None

    def effective_deadline(self) -> float:
        """Scalar urgency (smaller = more urgent): the deadline minus
        the aging credit, with each priority level worth
        :data:`PRIORITY_BOOST_S` seconds. Priority therefore dominates
        ordinary deadline spreads, while a long-parked lane's credit
        grows without bound and eventually outranks any priority."""
        return (self.deadline_s - self.credit_s
                - PRIORITY_BOOST_S * float(self.priority))


@dataclasses.dataclass
class LaneCheckpoint:
    """One parked lane: the host copy of its carry slice plus its
    metadata. ``restore`` splices the carry back into a free slot and
    the query resumes bit-identically from ``superstep`` — state,
    superstep counter and running stats are all part of the carry."""

    carry: StepCarry
    meta: LaneMeta
    superstep: int
    nbytes: int


class LaneTable:
    """First-class lane lifecycle for one stepper's W-wide slot array.

    Owns slot occupancy, the device carry + host probe mirrors
    (``act``/``steps``), the per-lane kwarg arrays, and the per-lane
    :class:`LaneMeta`. The scheduler's policy (who gets a slot, who is
    preempted) stays outside; the mechanics of the four lifecycle verbs
    live here:

      admit(assignments)    — splice fresh queries into free slots (one
                              lane-masked device call for all of them)
      step(alive)           — one superstep for the alive lanes
      checkpoint(slot)      — fetch ONLY that lane's carry slice to host
                              and free the slot (zero re-traces; the
                              preemption "park" half)
      restore(slot, ckpt)   — splice a parked carry back into a free
                              slot via the admit-path select; the lane
                              resumes bit-identically from its parked
                              superstep

    Freed/parked lanes' stale device carry stays in place until a later
    admit/restore overwrites it — the lane-masked select never steps an
    unoccupied lane, so it is inert.

    ``trace`` is an optional duck-typed event bus (anything with an
    ``emit(kind, **fields)`` method — in practice the service layer's
    ``TraceBus``; the core stays import-free of the service package).
    When set, ``step`` emits one ``superstep`` event per dispatch with
    the lane→query attribution (slot -> meta.seq) of the lanes that
    actually stepped, so a query span can be reconstructed into its
    active vs parked intervals.
    """

    def __init__(self, stepper, width: int, query_params, *,
                 trace=None, label: Optional[str] = None,
                 devices: Tuple[str, ...] = ()):
        self.stepper = stepper
        self.width = width
        self.query_params = tuple(query_params)
        self.trace = trace
        self.label = label
        # mesh device attribution for superstep events (shard steppers
        # dispatch to every device of their 1-D graph mesh; () for
        # single-device tables keeps those events unchanged)
        self.devices = tuple(devices)
        self.meta: List[Optional[LaneMeta]] = [None] * width
        self.carry = None
        self.act: Optional[np.ndarray] = None    # (W,) lane-alive probe
        self.steps: Optional[np.ndarray] = None  # (W,) lane supersteps
        self._qkw: Optional[Dict[str, np.ndarray]] = None

    # ---------------- occupancy ---------------------------------------
    @property
    def occupied(self) -> np.ndarray:
        return np.array([m is not None for m in self.meta], bool)

    def in_flight(self) -> int:
        return sum(m is not None for m in self.meta)

    def free_slots(self) -> List[int]:
        return [i for i, m in enumerate(self.meta) if m is None]

    def lanes_of(self, tenant: str) -> int:
        return sum(1 for m in self.meta
                   if m is not None and m.tenant == tenant)

    def active_slots(self) -> List[int]:
        return [i for i, m in enumerate(self.meta) if m is not None]

    def alive_mask(self, cap: int) -> np.ndarray:
        return self.occupied & self.act & (self.steps < cap)

    def done_slots(self, cap: int) -> List[int]:
        """Occupied lanes whose termination mask flipped or that hit the
        superstep cap — ready to retire."""
        return [i for i in range(self.width)
                if self.meta[i] is not None
                and (not self.act[i] or self.steps[i] >= cap)]

    def lane_nbytes(self) -> int:
        """Host bytes one lane's checkpoint occupies (every carry leaf's
        lane axis divides its bytes evenly across the W lanes)."""
        if self.carry is None:
            return 0
        return int(sum(a.nbytes for a in jax.tree.leaves(self.carry))
                   // self.width)

    def predicted_remaining(self, slot: int, residual: float = 1.0
                            ) -> float:
        """Predicted supersteps this lane still needs: its admission-time
        depth prediction minus observed progress; a lane that outlived
        its prediction falls back to the class's observed-depth residual
        (the expected overshoot), floored at one superstep."""
        m = self.meta[slot]
        rem = m.predicted_depth - float(self.steps[slot])
        return rem if rem > 0 else max(float(residual), 1.0)

    # ---------------- lifecycle verbs ---------------------------------
    def _ensure_qkw(self, meta: LaneMeta) -> None:
        if self._qkw is None:
            # lane arrays keyed by the kernel's DECLARED params (not one
            # request's keys), seeded with this request's values — idle
            # lanes then hold a valid query, like the bucketed batcher's
            # padding lanes
            self._qkw = {p: np.full((self.width,), meta.qkw[p],
                                    dtype=lane_dtype(meta.qkw[p]))
                         for p in self.query_params}

    def admit(self, assignments: Dict[int, LaneMeta]) -> None:
        """Splice fresh queries into the given free slots — one
        lane-masked ``init_carry`` select for all of them."""
        if not assignments:
            return
        fresh = np.zeros(self.width, bool)
        # install EVERY meta before anything that can raise: a failure
        # below (missing declared param, device error) then finds all
        # affected lanes in the table, so the class-failure path can
        # resolve their futures instead of stranding them
        for slot, meta in assignments.items():
            assert self.meta[slot] is None, f"slot {slot} occupied"
            self.meta[slot] = meta
            fresh[slot] = True
        for slot, meta in assignments.items():
            self._ensure_qkw(meta)
            for p in self._qkw:
                # a missing declared param raises here and fails the
                # class loudly instead of silently reusing the slot's
                # previous occupant's value
                self._qkw[p][slot] = meta.qkw[p]
        if self.carry is None:
            self.carry, self.act, self.steps = self.stepper.init(self._qkw)
        else:
            self.carry, self.act, self.steps = self.stepper.admit(
                self.carry, self._qkw, fresh)

    def step(self, alive: np.ndarray) -> None:  # analysis: host
        if self.trace is None:
            self.carry, self.act, self.steps = self.stepper.step(
                self.carry, alive)
            return
        # lane->query attribution captured BEFORE the dispatch (a lane
        # that retires this superstep must still be attributed to it)
        lanes = {int(i): self.meta[i].seq
                 for i in np.flatnonzero(alive) if self.meta[i] is not None}
        w0 = getattr(self.stepper, "last_wire_words", 0.0)
        t0 = time.perf_counter()
        self.carry, self.act, self.steps = self.stepper.step(
            self.carry, alive)
        # the probe arrays in the return are host numpy, so perf_counter
        # here bounds the full dispatch+sync, not just the enqueue
        w1 = getattr(self.stepper, "last_wire_words", 0.0)
        extra = {}
        if self.devices:
            # per-device attribution: the mesh devices this dispatch
            # fanned out to (single-device tables omit the column)
            extra["devices"] = list(self.devices)
        self.trace.emit("superstep", klass=self.label,
                        ts=t0, dur_s=time.perf_counter() - t0,
                        lanes=lanes, n_alive=len(lanes),
                        words=max(0.0, w1 - w0), **extra)

    def fetch(self, slots: Optional[Sequence[int]] = None) -> StepCarry:
        """Host copy of the carry of ``slots`` (every slot when None), in
        that order, finalized (see ``LaneStepperBase.fetch``)."""
        return self.stepper.fetch(self.carry, slots)

    def release(self, slot: int) -> LaneMeta:
        """Free one retired lane's slot; returns its metadata."""
        meta = self.meta[slot]
        self.meta[slot] = None
        return meta

    def checkpoint(self, slot: int) -> LaneCheckpoint:
        """Park one lane: fetch its carry slice to host and free the
        slot. The device never sees a shape change and the fetch is
        jitted once, so parking re-traces nothing."""
        meta = self.meta[slot]
        assert meta is not None, f"slot {slot} is empty"
        nbytes = self.lane_nbytes()
        lane = self.stepper.fetch_lane(self.carry, slot)
        self.meta[slot] = None
        meta.parks += 1
        return LaneCheckpoint(carry=lane, meta=meta,
                              superstep=int(self.steps[slot]),
                              nbytes=nbytes)

    def restore(self, slot: int, ckpt: LaneCheckpoint) -> None:
        """Un-park a checkpointed lane into a free slot. The splice goes
        through the same lane-masked select as ``admit``, so the resumed
        computation is bit-identical to never having been parked."""
        assert self.meta[slot] is None, f"slot {slot} occupied"
        meta = ckpt.meta
        # meta first (see admit): a failure in the splice below must
        # leave the lane visible to the class-failure path
        self.meta[slot] = meta
        self._ensure_qkw(meta)
        for p in self._qkw:
            self._qkw[p][slot] = meta.qkw[p]
        if self.carry is None:
            # empty table: materialize a carry first (idle lanes hold a
            # valid dummy query), then overwrite the restored slot
            self.carry, self.act, self.steps = self.stepper.init(self._qkw)
        fresh = np.zeros(self.width, bool)
        fresh[slot] = True
        self.carry, self.act, self.steps = self.stepper.restore(
            self.carry, ckpt.carry, fresh)

    def clear(self) -> List[LaneMeta]:
        """Drop every lane (class failure path); returns the metadata of
        the lanes that were occupied."""
        out = [m for m in self.meta if m is not None]
        self.meta = [None] * self.width
        self.carry = self.act = self.steps = None
        return out
