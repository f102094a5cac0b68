"""Store: seconds of set-up spent building the cell's graph for
serving: the ``partition`` span (store registry, ``partition_graph``)
plus every ``engine_build`` span (plan cache, one engine per kernel:
host layout arrays and their upload) of the benchmark's graph."""
from bench.harness import GID
from bench.scopes import spans


def read(run):
    parts = [e.dur_s for kind in ("partition", "engine_build")
             for e in spans(run, kind) if e.attrs.get("graph_id") == GID]
    return sum(parts) if parts else None
