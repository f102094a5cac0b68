"""Median client-side latency, submit to answer in hand, of the queries
answered in the window (host clock)."""
import numpy as np


def read(run):
    lat = [q.t_done - q.t_submit for q in run.window.queries]
    return float(np.percentile(lat, 50)) * 1e3
