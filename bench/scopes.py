"""Device time of a traced run by the program's own device scopes, and
the program's own spans by batch.

The service names each phase of its superstep program with a
``jax.named_scope`` (``gravfm.deliver``, ``gravfm.apply``, ...) and
exposes, for every compiled plan, which scope each op of the compiled
program belongs to: ``svc.op_scopes()``, keyed by (XLA module, op
name). A device plane of the trace names each op on its "XLA Ops" line
and each program execution on its "XLA Modules" line; an op belongs to
the module execution that holds its start. Ops of a program the map
does not know read ``UNMAPPED``, ops under no scope ``NO_SCOPE``.

A program without these (an older commit) has no ``op_scopes`` and
records no spans: every function here then returns None or nothing,
and the metrics that read them are left out of the result line.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from bench import trace as tr

MODULES_LINE = "XLA Modules"
NO_SCOPE = "no scope"
UNMAPPED = "unmapped program"


def module_name(event_name: str) -> str:
    """``jit_bfs_gravfm_batch32`` from ``jit_bfs_gravfm_batch32(123)``."""
    return event_name.split("(", 1)[0]


def load_scoped_ops(path, scopes: Dict[tuple, str]):
    """``({device plane: op events, each renamed to its device scope},
    host events)`` of a trace."""
    from jax.profiler import ProfileData
    device: Dict[str, List[tr.Event]] = {}
    host: List[tr.Event] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            # host threads may share a line name: keep every line
            host.extend(tr.Event(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events)
            continue
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines
                 if line.name in (MODULES_LINE, tr.OPS_LINE)}
        if not lines.get(tr.OPS_LINE):
            continue
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       module_name(e.name))
                      for e in lines.get(MODULES_LINE, ()))
        starts = [m[0] for m in mods]
        ops = device[plane.name] = []
        for e in lines[tr.OPS_LINE]:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            module = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else ""
            scope = scopes.get((module, tr.op_name(e.name)))
            ops.append(tr.Event(
                UNMAPPED if scope is None else (scope or NO_SCOPE),
                e.start_ns, e.start_ns + e.duration_ns))
    return device, host


def scope_seconds(run, lo_s: float,
                  hi_s: float) -> Optional[Dict[str, float]]:
    """Device self time (seconds, averaged over the cell's chips) by
    scope within ``[lo_s, hi_s]`` of the host clock; None without a
    trace, a device plane or the program's op-to-scope map."""
    op_scopes = getattr(run.svc, "op_scopes", None)
    if run.xplane is None or op_scopes is None:
        return None
    path, t_mark = run.xplane
    device, host = load_scoped_ops(path, op_scopes())
    planes = sorted(device)[:run.cell.chips]
    if not planes:
        return None
    off = tr.clock_offset_ns(host, t_mark)
    into: Dict[str, float] = {}
    for p in planes:
        tr.self_times(device[p], lo_s * 1e9 + off, hi_s * 1e9 + off, into)
    return {k: v / len(planes) * 1e-9 for k, v in into.items()}


def spans(run, kind: str) -> list:
    """The run's TraceBus span events of ``kind``."""
    return [e for e in run.events if e.kind == kind]


def batches_launched_in_window(run) -> Dict[int, float]:
    """``{batch number: launch time}`` of the batches that took the
    device inside the window (the end of their ``device_wait``)."""
    lo, hi = run.window.t_open, run.window.t_close
    return {e.attrs["batch"]: e.ts + e.dur_s
            for e in spans(run, "device_wait")
            if "batch" in e.attrs and lo <= e.ts + e.dur_s <= hi}
