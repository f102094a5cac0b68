"""Service: mean host time for which a finished batch keeps the next
one off the device, over the batches launched in the window: the sum
of its ``fetch`` and ``collect`` spans (engine.py ``run_batch``,
outputs to host and to per-query results) and its ``resolve`` span
(server.py ``_dispatch_locked``: futures, stats, retire events, result
cache), all taken while it holds the dispatch lock."""
from bench.scopes import batches_launched_in_window, spans


def read(run):
    launched = batches_launched_in_window(run)
    if not launched:
        return None
    host = dict.fromkeys(launched, 0.0)
    for kind in ("fetch", "collect", "resolve"):
        for e in spans(run, kind):
            b = e.attrs.get("batch")
            if b in host:
                host[b] += e.dur_s
    return sum(host.values()) / len(host) * 1e3
