"""Seconds from process start to the window's opening: JAX start-up,
the graph, add_graph, warm() and the wait for the first answer (host
clock)."""


def read(run):
    return run.setup_s
