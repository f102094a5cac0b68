"""Engine: device self time of the ops under the program's
``gravfm.deliver`` scope (the edge pass: payload gather over the
in-edges, message, segment-combine of value, got and parent; the
in-program counters are under ``gravfm.stats``), over the whole traced
loop, per superstep counted by the service's ``supersteps_total`` (the
denominator of ``superstep_device_ms``). Ops are tied to their scope by
the service's ``op_scopes()`` map (bench/scopes.py)."""
from bench.scopes import scope_seconds


def read(run):
    steps = run.counter("supersteps_total", "start", "end")
    by_scope = scope_seconds(run, *run.t_loop)
    if not steps or by_scope is None or "gravfm.deliver" not in by_scope:
        return None
    return by_scope["gravfm.deliver"] / steps * 1e3
