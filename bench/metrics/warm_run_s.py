"""Plan cache: seconds of set-up spent in warm-up executions, the
``warm_run`` spans (plan cache, ``CompiledPlan.warmup``: one full
traversal from vertex 0 per warmed plan) before the window opens."""
from bench.scopes import spans


def read(run):
    warm = [e.dur_s for e in spans(run, "warm_run")
            if e.ts < run.window.t_open]
    return sum(warm) if warm else None
