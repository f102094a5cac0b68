"""Reduce a JAX profiler trace to the device numbers of one window.

A traced run records the whole closed loop with ``jax.profiler`` (the
Python tracer off), marks the host clock once with a ``bench.clock``
annotation so that ``time.perf_counter()`` seconds map onto trace
nanoseconds, and cuts the window ``[t_open, t_close]`` out of the
device planes:

    busy_s      union of the op intervals of each device plane used,
                averaged over those planes
    device_ops  op names by self time inside the window (a ``while``
                op less the body ops nested in it)
    idle_gaps   device idle time inside the window by what the host
                was doing: each part of a gap under the innermost host
                span that covers it

Host spans come from ``TraceAnnotation``s that :func:`host_spans` wraps
around calls into the service's layers for the traced run only; a gap
that no span covers is ``"no host span"``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
from pathlib import Path
from typing import Dict, List, Optional, Tuple

CLOCK_MARK = "bench.clock"
OPS_LINE = "XLA Ops"
NO_SPAN = "no host span"

# (module, class or None, attribute, span name): the calls into the
# service's layers that a traced run wraps in host spans. A missing
# attribute is skipped, so a refactor of the program costs a name in
# the breakdown and never a run.
SPANS = (
    ("repro.service.server", "GraphQueryService", "_dispatch_locked",
     "service.dispatch"),
    ("repro.service.server", "GraphQueryService", "_store_result",
     "service.result_cache_store"),
    ("repro.core.engine", "Engine", "run_batch", "engine.run_batch"),
    ("repro.core.engine", None, "collect", "engine.collect_results"),
)
SPAN_NAMES = frozenset(s[3] for s in SPANS) | {"bench.submit"}


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@contextlib.contextmanager
def host_spans():
    """Wrap the calls named in :data:`SPANS` in ``TraceAnnotation``s for
    the duration of the block, and restore them after."""
    import jax
    undo = []
    for mod_name, cls_name, attr, span in SPANS:
        try:
            owner = importlib.import_module(mod_name)
            if cls_name is not None:
                owner = getattr(owner, cls_name)
            fn = getattr(owner, attr)
        except (ImportError, AttributeError):
            continue

        def wrapped(*a, __fn=fn, __span=span, **kw):
            with jax.profiler.TraceAnnotation(__span):
                return __fn(*a, **kw)

        setattr(owner, attr, functools.wraps(fn)(wrapped))
        undo.append((owner, attr, fn))
    try:
        yield
    finally:
        for owner, attr, fn in reversed(undo):
            setattr(owner, attr, fn)


def op_name(hlo: str) -> str:
    """``fusion.12`` from an op event's HLO text ``%fusion.12 = ...``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: Path) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device op events by device plane, host events) of a trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        Event(op_name(e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                            for e in line.events)
    return device, host


def clock_offset_ns(host: List[Event], t_mark_s: float) -> float:
    """Trace nanoseconds minus ``perf_counter`` nanoseconds, from the
    ``bench.clock`` annotation opened at ``t_mark_s``."""
    marks = [e.start_ns for e in host if e.name == CLOCK_MARK]
    if not marks:
        raise ValueError(f"trace holds no {CLOCK_MARK!r} annotation")
    return marks[0] - t_mark_s * 1e9


def merged(events: List[Event], lo: float, hi: float):
    """Union of the event intervals, clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def self_times(events: List[Event], lo: float, hi: float,
               into: Dict[str, float]) -> None:
    """Add each op's time inside ``[lo, hi]`` less that of the ops
    nested in it (a ``while`` holds its body's ops) to ``into``."""
    stack: List[List] = []      # [event, clipped time of its children]

    def close(item):
        e, inner = item
        d = min(e.end_ns, hi) - max(e.start_ns, lo)
        if d > 0:
            into[e.name] = into.get(e.name, 0.0) + d - inner
            if stack:
                stack[-1][1] += d

    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            close(stack.pop())
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())


def host_timeline(spans: List[Event], lo: float, hi: float):
    """``[lo, hi]`` cut into ``(start, end, name)`` pieces, each named by
    the innermost (shortest) host span that covers it."""
    spans = [e for e in spans if e.start_ns < hi and e.end_ns > lo]
    cuts = sorted({lo, hi} | {x for e in spans for x in (e.start_ns, e.end_ns)
                              if lo < x < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [e for e in spans if e.start_ns <= a and e.end_ns >= b]
        name = (min(cover, key=lambda e: e.end_ns - e.start_ns).name
                if cover else NO_SPAN)
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _attribute(gaps, timeline, into: Dict[str, float]) -> None:
    """Add each idle interval of the sorted ``gaps`` to ``into`` under
    the names of the ``timeline`` pieces it overlaps."""
    i = 0
    for s, t in gaps:
        while i < len(timeline) and timeline[i][1] <= s:
            i += 1
        j = i
        while j < len(timeline) and timeline[j][0] < t:
            a, b, name = timeline[j]
            d = min(b, t) - max(a, s)
            if d > 0:
                into[name] = into.get(name, 0.0) + d
            j += 1


@dataclasses.dataclass
class WindowReduction:
    busy_s: float
    window_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def reduce_window(device: Dict[str, List[Event]], host: List[Event],
                  lo_ns: float, hi_ns: float, planes: List[str],
                  top: int = 10) -> Optional[WindowReduction]:
    """Device numbers of ``[lo_ns, hi_ns]`` over the device ``planes``
    (the chips the cell uses); None when none of them holds an op."""
    used = [p for p in planes if device.get(p)]
    if not used:
        return None
    timeline = host_timeline([e for e in host if e.name in SPAN_NAMES],
                             lo_ns, hi_ns)
    busy, ops, gaps = 0.0, {}, {}
    for plane in used:
        events = device[plane]
        runs = merged(events, lo_ns, hi_ns)
        busy += sum(t - s for s, t in runs)
        self_times(events, lo_ns, hi_ns, ops)
        edges = [lo_ns] + [x for r in runs for x in r] + [hi_ns]
        _attribute([(s, t) for s, t in zip(edges[::2], edges[1::2])
                    if t > s], timeline, gaps)
    n = len(used)

    def ranked(d):
        return [[k, v / n * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return WindowReduction(busy_s=busy / n * 1e-9,
                           window_s=(hi_ns - lo_ns) * 1e-9,
                           device_ops=ranked(ops), idle_gaps=ranked(gaps))
