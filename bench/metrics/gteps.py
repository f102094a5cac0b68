"""GTEPS over the window: for every query answered in it, the Graph500
edge count of its root's component (undirected edges with both ends in
the component, counted on the benchmark's own graph), summed and
divided by the window's host-clock seconds."""


def read(run):
    ce = run.component_edges
    edges = sum(int(ce[q.root]) for q in run.window.queries
                if q.answer is not None)
    return edges / run.window.seconds / 1e9
