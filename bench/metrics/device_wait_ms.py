"""Scheduler: mean time a formed batch waited for the device, the
service's ``device_wait`` span (``admit`` -> the dispatch lock taken,
server.py ``_dispatch``), over the batches launched in the window. It
is what ``queue_wait_p50_ms`` (batch formation only) leaves out."""
from bench.scopes import spans


def read(run):
    lo, hi = run.window.t_open, run.window.t_close
    waits = [e.dur_s for e in spans(run, "device_wait")
             if lo <= e.ts + e.dur_s <= hi]
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e3
