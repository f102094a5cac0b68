"""Scheduler: median wait from the service's ``submit`` event to its
``admit`` event (TraceBus spans), over queries admitted in the window."""
import numpy as np


def read(run):
    submit = {e.qid: e.ts for e in run.events if e.kind == "submit"}
    lo, hi = run.window.t_open, run.window.t_close
    waits = [e.ts - submit[e.qid] for e in run.events
             if e.kind == "admit" and e.qid in submit and lo <= e.ts <= hi]
    if not waits:
        return None
    return float(np.percentile(waits, 50)) * 1e3
