"""Exchange: device self time of the ops under the program's
``gravfm.exchange`` scope, mean over the cell's chips, per superstep
counted by the service's ``supersteps_total``, over the whole traced
loop. Every collective of the shard engine's programs is under that
scope: the all-gather of the vertex updates, the termination votes,
the totals and the parent pass's all-gathers. An op takes the innermost
``gravfm.*`` scope of its ``op_name`` (``svc.op_scopes()``), so an
all-gather inside ``gravfm.finalize`` counts here. A program without
the scope (one chip, or an older commit) reads nothing."""
from bench.scopes import scope_seconds


def read(run):
    steps = run.counter("supersteps_total", "start", "end")
    by_scope = scope_seconds(run, *run.t_loop)
    if not steps or by_scope is None or "gravfm.exchange" not in by_scope:
        return None
    return by_scope["gravfm.exchange"] / steps * 1e3
